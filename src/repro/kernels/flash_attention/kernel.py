"""Flash attention (causal / sliding-window / bidirectional) Pallas kernel.

TPU adaptation of the GPU flash algorithm: instead of warp-level softmax
reductions in shared memory, blocks are sized to VMEM (q_block x kv_block
score tiles, multiples of 128 for the MXU) and the online-softmax state
(m, l, acc) lives in VMEM scratch that persists across the innermost
(sequential) kv grid dimension.

Grid: (B*H, n_q_blocks, n_kv_blocks) — kv innermost so each (bh, qi) output
block is revisited; scratch carries m/l/acc between visits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            q_block: int, kv_block: int, seq_len: int, causal: bool,
            window: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale          # [qb, hd]
    k = k_ref[0].astype(jnp.float32)                  # [kb, hd]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [qb, kb]

    q_pos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 0)
    k_pos = ki * kv_block + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 1)
    mask = k_pos < seq_len  # tail padding
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= jnp.abs(q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           q_block: int = 128, kv_block: int = 128,
                           interpret: bool | None = None):
    """q, k, v: [BH, S, hd] (GQA folded by ops.py). Returns [BH, S, hd]."""
    interpret = resolve_interpret(interpret)
    BH, S, hd = q.shape
    scale = 1.0 / (hd ** 0.5)
    q_block = min(q_block, S)
    kv_block = min(kv_block, S)
    nq = -(-S // q_block)
    nk = -(-S // kv_block)
    Sq, Sk = nq * q_block, nk * kv_block
    qp = jnp.pad(q, ((0, 0), (0, Sq - S), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Sk - S), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sk - S), (0, 0)))

    out = pl.pallas_call(
        functools.partial(
            _kernel, q_block=q_block, kv_block=kv_block, seq_len=S,
            causal=causal, window=window, scale=scale,
        ),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, q_block, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, kv_block, hd), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, kv_block, hd), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, 1), jnp.float32),   # m: running max
            pltpu.VMEM((q_block, 1), jnp.float32),   # l: running denom
            pltpu.VMEM((q_block, hd), jnp.float32),  # acc
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :S]
