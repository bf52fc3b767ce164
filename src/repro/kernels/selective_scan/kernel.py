"""Mamba-1 selective scan Pallas kernel.

TPU adaptation: the CUDA implementation parallelizes the scan across warps
with shared-memory chunk prefix-sums. On TPU we instead keep the
(d_tile x d_state) recurrent state RESIDENT IN VMEM scratch across the whole
time loop: grid = (B, n_d_tiles, n_t_chunks) with the time dim innermost and
sequential, each step streaming one (t_chunk x d_tile) slab of u/dt and a
(t_chunk x d_state) slab of B/C through VMEM while h never touches HBM.
Discretization (exp(dt*A), dt*B*u) is fused into the scan — dA/dBu are never
materialized in HBM at all (the XLA path materializes both).

  h_t = exp(dt_t * A) * h_{t-1} + (dt_t * u_t) * B_t ;  y_t = h_t @ C_t + D*u_t
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _kernel(u_ref, dt_ref, B_ref, C_ref, A_ref, D_ref, y_ref, h_scr, *,
            t_chunk: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    A = A_ref[...].astype(jnp.float32)      # [d_tile, st]
    D = D_ref[...].astype(jnp.float32)      # [d_tile]
    u = u_ref[0].astype(jnp.float32)        # [t_chunk, d_tile]
    dt = dt_ref[0].astype(jnp.float32)      # [t_chunk, d_tile]
    Bt = B_ref[0].astype(jnp.float32)       # [t_chunk, st]
    Ct = C_ref[0].astype(jnp.float32)       # [t_chunk, st]

    def step(t, carry):
        h, ys = carry
        dA = jnp.exp(dt[t][:, None] * A)                    # [d_tile, st]
        h = dA * h + (dt[t] * u[t])[:, None] * Bt[t][None]  # [d_tile, st]
        y = jnp.dot(h, Ct[t], preferred_element_type=jnp.float32) + D * u[t]
        return h, ys.at[t].set(y)

    h0 = h_scr[...]
    ys0 = jnp.zeros((t_chunk, u.shape[1]), jnp.float32)
    h, ys = jax.lax.fori_loop(0, t_chunk, step, (h0, ys0))
    h_scr[...] = h
    y_ref[0] = ys.astype(y_ref.dtype)


def selective_scan_pallas(u, dt, B, C, A, D, *, d_tile: int = 128,
                          t_chunk: int = 64, interpret: bool | None = None):
    """u, dt: [Bsz, S, di]; B, C: [Bsz, S, st]; A: [di, st]; D: [di].

    Returns y [Bsz, S, di] = selective_scan(u) + D*u.
    """
    interpret = resolve_interpret(interpret)
    Bsz, S, di = u.shape
    st = A.shape[1]
    d_tile = min(d_tile, di)
    t_chunk = min(t_chunk, S)
    assert di % d_tile == 0, (di, d_tile)
    nt = -(-S // t_chunk)
    Sp = nt * t_chunk
    pad = ((0, 0), (0, Sp - S), (0, 0))
    up, dtp, Bp, Cp = (jnp.pad(a, pad) for a in (u, dt, B, C))

    grid = (Bsz, di // d_tile, nt)
    out = pl.pallas_call(
        functools.partial(_kernel, t_chunk=t_chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, t_chunk, d_tile), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, t_chunk, d_tile), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, t_chunk, st), lambda b, d, t: (b, t, 0)),
            pl.BlockSpec((1, t_chunk, st), lambda b, d, t: (b, t, 0)),
            pl.BlockSpec((d_tile, st), lambda b, d, t: (d, 0)),
            pl.BlockSpec((d_tile,), lambda b, d, t: (d,)),
        ],
        out_specs=pl.BlockSpec((1, t_chunk, d_tile), lambda b, d, t: (b, t, d)),
        out_shape=jax.ShapeDtypeStruct((Bsz, Sp, di), u.dtype),
        scratch_shapes=[pltpu.VMEM((d_tile, st), jnp.float32)],
        interpret=interpret,
    )(up, dtp, Bp, Cp, A, D)
    return out[:, :S]
