"""Fused privacy-preserving layer kernel: Conv3x3 + bias + ReLU + MaxPool2x2
(+ Gaussian noise) — the client-side hot spot of the paper (§III-A).

TPU adaptation: instead of a CUDA im2col pass + separate pooling kernel, one
grid step computes a whole (sample, H-tile) in VMEM. The 3x3 conv is computed
as 9 MXU matmuls [tile_h*W, Cin] @ [Cin, Cout] (tap decomposition); ReLU +
2x2 max-pool + noise-add fuse into the same kernel so the pre-pool activation
NEVER round-trips to HBM — it is also never observable off-chip, which is the
privacy boundary the paper wants.

Grid: (B, H/tile_h). The padded input stays a full-image block (halo tiles
overlap, so the H-tile is cut inside the kernel with pl.dslice); weights/bias
are replicated per step; output/noise are true per-tile blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _kernel(x_ref, w_ref, b_ref, noise_ref, o_ref, *, tile_h: int, W: int,
            noise_scale: float):
    Cin = x_ref.shape[-1]
    Cout = o_ref.shape[-1]
    hi = pl.program_id(1)
    # halo tile [tile_h+2, W+2, Cin] out of the padded full-image block
    x = x_ref[0, pl.dslice(hi * tile_h, tile_h + 2), :, :]
    acc = jnp.zeros((tile_h * W, Cout), jnp.float32)
    for di in range(3):
        for dj in range(3):
            tap = x[di : di + tile_h, dj : dj + W, :].reshape(tile_h * W, Cin)
            acc += jnp.dot(
                tap.astype(jnp.float32),
                w_ref[di, dj].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
    acc += b_ref[:].astype(jnp.float32)[None, :]
    acc = jax.nn.relu(acc).reshape(tile_h, W, Cout)
    pooled = jnp.max(acc.reshape(tile_h // 2, 2, W // 2, 2, Cout), axis=(1, 3))
    if noise_scale > 0.0:
        pooled = pooled + noise_scale * noise_ref[0].astype(jnp.float32)
    o_ref[0] = pooled.astype(o_ref.dtype)


def privacy_conv_pallas(x, w, b, noise, *, noise_scale: float = 0.0,
                        tile_h: int = 0, interpret: bool | None = None):
    """x: [B, H, W, Cin] -> [B, H/2, W/2, Cout]. H, W must be even."""
    interpret = resolve_interpret(interpret)
    B, H, W, Cin = x.shape
    Cout = w.shape[-1]
    assert H % 2 == 0 and W % 2 == 0, (H, W)
    if tile_h <= 0:
        # largest even tile keeping the fp32 conv working set under ~8MB VMEM
        budget = 8 * 1024 * 1024 // 4
        tile_h = H
        while tile_h > 2 and tile_h * W * (Cin + 2 * Cout) > budget:
            tile_h //= 2
        tile_h = max(2, tile_h - (tile_h % 2))
    assert H % tile_h == 0, (H, tile_h)
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))

    grid = (B, H // tile_h)
    return pl.pallas_call(
        functools.partial(_kernel, tile_h=tile_h, W=W, noise_scale=noise_scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, H + 2, W + 2, Cin), lambda bi, hi: (bi, 0, 0, 0)),
            pl.BlockSpec((3, 3, Cin, Cout), lambda bi, hi: (0, 0, 0, 0)),
            pl.BlockSpec((Cout,), lambda bi, hi: (0,)),
            pl.BlockSpec((1, tile_h // 2, W // 2, Cout), lambda bi, hi: (bi, hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, tile_h // 2, W // 2, Cout), lambda bi, hi: (bi, hi, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, H // 2, W // 2, Cout), x.dtype),
        interpret=interpret,
    )(xp, w, b, noise)
