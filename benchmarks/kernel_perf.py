"""Kernel micro-benchmarks: XLA-path wall time + kernel-vs-oracle error.

``us_per_call`` times the jitted XLA reference path; ``derived`` is the
Pallas kernel's max|err| against that oracle. The kernels lower through
Mosaic on a TPU and run the Pallas interpreter anywhere else
(``repro.kernels.resolve_interpret``), so off the chip only the error
column means anything.
"""
from __future__ import annotations

import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Row = Tuple[str, float, str]


def _time(fn, *args, n=10):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def bench_privacy_conv() -> List[Row]:
    from repro.kernels.privacy_conv.kernel import privacy_conv_pallas
    from repro.kernels.privacy_conv.ref import privacy_conv_ref

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    B, H, W, Cin, Cout = 8, 64, 64, 1, 16  # the paper's COVID CT client layer
    x = jax.random.normal(ks[0], (B, H, W, Cin))
    w = jax.random.normal(ks[1], (3, 3, Cin, Cout)) * 0.1
    b = jnp.zeros((Cout,))
    nz = jax.random.normal(ks[3], (B, H // 2, W // 2, Cout))
    ref = jax.jit(lambda *a: privacy_conv_ref(*a, noise_scale=0.05))
    us = _time(ref, x, w, b, nz)
    err = float(jnp.max(jnp.abs(
        privacy_conv_pallas(x, w, b, nz, noise_scale=0.05)
        - privacy_conv_ref(x, w, b, nz, noise_scale=0.05))))
    return [("kernel/privacy_conv_64x64", us, f"pallas_vs_ref_maxerr={err:.2e}")]


def bench_dp_release() -> List[Row]:
    from repro.kernels.dp_release.kernel import dp_release_pallas
    from repro.kernels.dp_release.ref import dp_release_ref

    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    # the COVID CT cut feature map: [B, 32, 32, 16] post conv+pool
    B, H, W, C = 8, 32, 32, 16
    x = jax.random.normal(ks[0], (B, H, W, C)) * 2
    nz = jax.random.normal(ks[1], (B, H, W, C))
    ref = jax.jit(lambda *a: dp_release_ref(*a, clip_norm=1.0, sigma=0.05))
    us = _time(ref, x, nz)
    err = float(jnp.max(jnp.abs(
        dp_release_pallas(x, nz, clip_norm=1.0, sigma=0.05)
        - dp_release_ref(x, nz, clip_norm=1.0, sigma=0.05))))
    return [("kernel/dp_release_32x32x16", us, f"pallas_vs_ref_maxerr={err:.2e}")]


def bench_flash_attention() -> List[Row]:
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    from repro.kernels.flash_attention.ref import flash_attention_ref

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    BH, S, hd = 4, 512, 64
    q = jax.random.normal(ks[0], (BH, S, hd))
    k = jax.random.normal(ks[1], (BH, S, hd))
    v = jax.random.normal(ks[2], (BH, S, hd))
    ref = jax.jit(lambda *a: flash_attention_ref(*a, causal=True))
    us = _time(ref, q, k, v)
    got = flash_attention_pallas(q[:1, :128], k[:1, :128], v[:1, :128], q_block=64, kv_block=64)
    want = flash_attention_ref(q[:1, :128], k[:1, :128], v[:1, :128])
    err = float(jnp.max(jnp.abs(got - want)))
    return [("kernel/flash_attention_512", us, f"pallas_vs_ref_maxerr={err:.2e}")]


def bench_selective_scan() -> List[Row]:
    from repro.kernels.selective_scan.kernel import selective_scan_pallas
    from repro.kernels.selective_scan.ref import selective_scan_ref

    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    Bsz, S, di, st = 2, 256, 256, 16
    u = jax.random.normal(ks[0], (Bsz, S, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bsz, S, di)) * 0.5 - 1)
    B = jax.random.normal(ks[2], (Bsz, S, st))
    C = jax.random.normal(ks[3], (Bsz, S, st))
    A = -jnp.exp(jax.random.normal(ks[4], (di, st)) * 0.3)
    D = jax.random.normal(ks[5], (di,))
    ref = jax.jit(selective_scan_ref)
    us = _time(ref, u, dt, B, C, A, D, n=5)
    got = selective_scan_pallas(u[:1, :64], dt[:1, :64], B[:1, :64], C[:1, :64], A, D,
                                d_tile=128, t_chunk=32)
    want = selective_scan_ref(u[:1, :64], dt[:1, :64], B[:1, :64], C[:1, :64], A, D)
    err = float(jnp.max(jnp.abs(got - want)))
    return [("kernel/selective_scan_256", us, f"pallas_vs_ref_maxerr={err:.2e}")]
