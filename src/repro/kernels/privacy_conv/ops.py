"""Jit'd public wrapper for the fused privacy layer kernel.

The kernel carries a ``jax.custom_vjp`` so ``e2e`` split learning can
differentiate through it: the forward pass runs the fused Pallas kernel
(pre-pool activation stays in VMEM — the privacy boundary), while the
backward pass rematerializes through the pure-XLA reference
(``privacy_conv_ref``), whose gradients are the ground truth the parity
tests check against.

Switches (also surfaced on ``CNNConfig``):
  * ``use_kernel`` — False falls back to the pure-jnp reference (XLA path).
  * ``interpret`` — None auto-selects Mosaic lowering on a TPU and the
    Pallas interpreter anywhere else. Interpret mode is a Python emulation:
    numerically faithful but slow, so CPU throughput runs should prefer
    ``use_kernel=False`` and keep the kernel path for parity checks.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.privacy_conv.kernel import privacy_conv_pallas
from repro.kernels.privacy_conv.ref import privacy_conv_ref


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _privacy_conv_fused(x, w, b, noise, noise_scale, interpret):
    return privacy_conv_pallas(
        x, w, b, noise, noise_scale=noise_scale, interpret=interpret
    )


def _privacy_conv_fwd(x, w, b, noise, noise_scale, interpret):
    out = _privacy_conv_fused(x, w, b, noise, noise_scale, interpret)
    return out, (x, w, b, noise)


def _privacy_conv_bwd(noise_scale, interpret, residuals, g):
    x, w, b, noise = residuals
    _, vjp = jax.vjp(
        lambda xx, ww, bb: privacy_conv_ref(xx, ww, bb, noise, noise_scale=noise_scale),
        x, w, b,
    )
    dx, dw, db = vjp(g)
    return dx, dw, db, jnp.zeros_like(noise)


_privacy_conv_fused.defvjp(_privacy_conv_fwd, _privacy_conv_bwd)


@partial(jax.jit, static_argnames=("noise_scale", "use_kernel", "interpret"))
def privacy_conv(x, w, b, key=None, *, noise_scale: float = 0.0,
                 use_kernel: bool = True, interpret: bool | None = None):
    """Fused Conv3x3+ReLU+MaxPool2x2+noise (the paper's privacy layer).

    x: [B, H, W, Cin]; w: [3, 3, Cin, Cout]; b: [Cout].
    ``use_kernel=False`` falls back to the pure-jnp reference (XLA path).
    """
    interpret = resolve_interpret(interpret)
    B, H, W, _ = x.shape
    Cout = w.shape[-1]
    if noise_scale > 0.0:
        assert key is not None
        noise = jax.random.normal(key, (B, H // 2, W // 2, Cout), jnp.float32)
    else:
        noise = jnp.zeros((B, H // 2, W // 2, Cout), jnp.float32)
    if use_kernel:
        return _privacy_conv_fused(x, w, b, noise, noise_scale, interpret)
    return privacy_conv_ref(x, w, b, noise, noise_scale=noise_scale)
