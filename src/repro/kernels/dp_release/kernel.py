"""Fused DP release kernel: per-sample L2 clip + Gaussian noise in one pass.

The guard's release at the split cut is norm-bound-then-perturb — two
elementwise passes plus a reduction in XLA. Here one grid step processes one
sample: the feature row is read into VMEM once, the L2 norm, the clip scale,
the scale-multiply and the noise add all happen on-chip, and only the
(ε, δ)-DP release is written back to HBM. The UNCLIPPED feature map is never
observable off-chip — the same privacy-boundary argument as the
``privacy_conv`` kernel, applied to the release itself.

Layout: every row is viewed lane-dense as ``[R, 128]`` (zero-padded when the
feature count is not a multiple of 128 — zeros change neither the norm nor
the kept slice). Grid: ``(B,)``; each block is a whole ``[1, R, 128]`` row,
which Mosaic accepts for any batch because its last two dimensions are the
array's own. The VMEM limit is set from the row size: ``x``, ``noise`` and
the release double-buffered, plus the kernel's fp32 temporaries. At the MURA
cut (802,816 features per sample, a 3.1 MiB row) that is about 25 MiB, over
the 16 MiB scoped default and well inside a v5e core's 128 MiB. There is no
MXU work, so the kernel is bandwidth-bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

_LANES = 128
_VMEM_CAP = 100 << 20  # of a v5e core's 128 MiB; the rest is Mosaic's


def _vmem_bytes(rows: int, n_io: int) -> int:
    """VMEM for one grid step: ``n_io`` double-buffered fp32 row blocks, two
    fp32 temporaries (``x * x`` and the release), and 1 MiB of headroom."""
    return (2 * n_io + 2) * rows * _LANES * 4 + (1 << 20)


def _kernel(x_ref, *refs, clip_norm: float, sigma: float):
    noise_ref = refs[0] if sigma > 0.0 else None
    o_ref = refs[-1]
    x = x_ref[0].astype(jnp.float32)  # [R, 128] — one sample's features
    norm = jnp.sqrt(jnp.sum(jnp.sum(x * x, axis=0, keepdims=True)))
    out = x * jnp.minimum(1.0, clip_norm / jnp.maximum(norm, 1e-12))
    if noise_ref is not None:
        out = out + sigma * noise_ref[0].astype(jnp.float32)
    o_ref[0] = out.astype(o_ref.dtype)


def dp_release_pallas(x, noise, *, clip_norm: float, sigma: float = 0.0,
                      interpret: bool | None = None):
    """x: [B, ...] -> same shape; noise: standard-normal draws, same shape
    (ignored, and may be ``None``, when sigma == 0)."""
    interpret = resolve_interpret(interpret)
    b = x.shape[0]
    f = int(np.prod(x.shape[1:]))
    rows = -(-f // _LANES)

    def lane_dense(a):
        a = a.reshape(b, f)
        if rows * _LANES != f:
            a = jnp.pad(a, ((0, 0), (0, rows * _LANES - f)))
        return a.reshape(b, rows, _LANES)

    spec = pl.BlockSpec((1, rows, _LANES), lambda i: (i, 0, 0))
    args = [lane_dense(x)]
    if sigma > 0.0:
        args.append(lane_dense(noise))
    vmem = _vmem_bytes(rows, len(args) + 1)
    if vmem > _VMEM_CAP:
        raise ValueError(f"dp_release: a {f}-feature row needs {vmem >> 20} "
                         f"MiB of VMEM, over the {_VMEM_CAP >> 20} MiB cap")
    out = pl.pallas_call(
        functools.partial(_kernel, clip_norm=clip_norm, sigma=sigma),
        grid=(b,),
        in_specs=[spec] * len(args),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, _LANES), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=vmem),
        interpret=interpret,
    )(*args)
    return out.reshape(b, rows * _LANES)[:, :f].reshape(x.shape)
