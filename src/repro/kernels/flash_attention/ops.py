"""Jit'd public wrapper: GQA-aware flash attention."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref


@partial(jax.jit, static_argnames=("causal", "window", "q_block", "kv_block",
                                   "use_kernel", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_block: int = 128, kv_block: int = 128,
                    use_kernel: bool = True, interpret: bool | None = None):
    """GQA flash attention. q: [B, S, H, hd]; k, v: [B, S, KV, hd].

    Folds (B, H) into the kernel's leading grid dim; GQA groups share k/v by
    repetition at the wrapper level (the kernel sees one head per program).
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    fn = flash_attention_pallas if use_kernel else flash_attention_ref
    kw = dict(causal=causal, window=window)
    if use_kernel:
        kw.update(q_block=q_block, kv_block=kv_block, interpret=interpret)
    out = fn(qf, kf, vf, **kw)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
