"""Jit'd public wrapper for the fused DP release kernel.

The kernel carries a ``jax.custom_vjp`` so ``e2e`` split learning can
differentiate through the release: the forward pass runs the fused Pallas
kernel (unclipped features stay in VMEM — the privacy boundary), while the
backward pass rematerializes through the pure-XLA reference
(``dp_release_ref``), whose gradients are the ground truth the parity tests
check against. Noise is a constant of the release: its cotangent is zero.

Switches (surfaced on ``repro.privacy.DPConfig``):
  * ``use_kernel`` — False falls back to the pure-jnp reference (XLA path;
    the default, and the fastest choice on CPU).
  * ``interpret`` — None auto-selects Mosaic lowering on a TPU and the
    Pallas interpreter anywhere else.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.dp_release.kernel import dp_release_pallas
from repro.kernels.dp_release.ref import dp_release_ref


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _dp_release_fused(x, noise, clip_norm, sigma, interpret):
    return dp_release_pallas(
        x, noise, clip_norm=clip_norm, sigma=sigma, interpret=interpret
    )


def _dp_release_fwd(x, noise, clip_norm, sigma, interpret):
    out = _dp_release_fused(x, noise, clip_norm, sigma, interpret)
    return out, (x, noise)


def _dp_release_bwd(clip_norm, sigma, interpret, residuals, g):
    x, noise = residuals
    _, vjp = jax.vjp(
        lambda xx: dp_release_ref(xx, noise, clip_norm=clip_norm, sigma=sigma), x
    )
    (dx,) = vjp(g)
    return dx, jnp.zeros_like(noise)


_dp_release_fused.defvjp(_dp_release_fwd, _dp_release_bwd)


def dp_release_with_noise(x, noise=None, *, clip_norm: float = 1.0,
                          sigma: float = 0.0, use_kernel: bool = False,
                          interpret: bool | None = None):
    """The release with PRE-DRAWN standard-normal ``noise``.

    Threefry inside a serial ``lax.scan`` body is the guard's dominant cost
    on XLA:CPU, so the fused scan runner hoists the whole epoch's draws out
    of the loop (same keys → bit-identical releases) and calls this with the
    step's noise slice. Meant for use inside an outer jit — not jitted here.
    """
    if use_kernel:
        interpret = resolve_interpret(interpret)
        noise_arr = (noise if noise is not None
                     else jnp.zeros(x.shape, jnp.float32))
        return _dp_release_fused(x, noise_arr, clip_norm, sigma, interpret)
    return dp_release_ref(x, noise, clip_norm=clip_norm,
                          sigma=sigma if noise is not None else 0.0)


@partial(jax.jit, static_argnames=("clip_norm", "sigma", "use_kernel", "interpret"))
def dp_release(x, key=None, *, clip_norm: float = 1.0, sigma: float = 0.0,
               use_kernel: bool = False, interpret: bool | None = None):
    """Fused per-sample L2 clip + Gaussian noise (the guard's release).

    x: [B, ...]; with ``sigma > 0`` a PRNG ``key`` is required — the draw is
    the same shape/dtype either path takes, so kernel and XLA releases match
    in distribution bit-for-bit given the same key.
    """
    if sigma > 0.0:
        assert key is not None, "sigma > 0 requires a PRNG key"
        noise = jax.random.normal(key, x.shape, jnp.float32)
    else:
        noise = None
    return dp_release_with_noise(
        x, noise, clip_norm=clip_norm, sigma=sigma,
        use_kernel=use_kernel, interpret=interpret,
    )
