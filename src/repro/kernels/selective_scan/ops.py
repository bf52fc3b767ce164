"""Jit'd public wrapper for the selective scan kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.selective_scan.kernel import selective_scan_pallas
from repro.kernels.selective_scan.ref import selective_scan_ref


@partial(jax.jit, static_argnames=("d_tile", "t_chunk", "use_kernel", "interpret"))
def selective_scan(u, dt, B, C, A, D, *, d_tile: int = 128, t_chunk: int = 64,
                   use_kernel: bool = True, interpret: bool | None = None):
    """Mamba-1 selective state-space scan (see kernel.py for semantics)."""
    if use_kernel:
        return selective_scan_pallas(
            u, dt, B, C, A, D, d_tile=d_tile, t_chunk=t_chunk, interpret=interpret
        )
    return selective_scan_ref(u, dt, B, C, A, D)
