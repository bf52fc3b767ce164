"""Serving driver: batched prefill + autoregressive decode with KV cache.

  PYTHONPATH=src python -m repro.launch.serve --arch demo-11m --batch 4 \
      --prompt-len 64 --gen 32

This is the LM GENERATION driver: one full model (client embedding + trunk)
decoding autoregressively against a preallocated KV cache, prefilling by
replaying the prompt through ``serve_step`` so prefill and decode share one
cache layout. The split-inference batcher (``repro.serving``, docs/serving.md)
serves guarded single-forward scoring requests through the queue; generation
beyond one forward runs through THIS driver.

``--smoke`` is the CI path: a tiny config asserting decode-step shape/dtype
stability across every step and greedy-decode determinism at temperature 0
(two identical runs, bit-equal token streams), exiting non-zero on violation.

The pieces are importable for tests and for the serving bench:
``build_parser()`` (argparse round-trips), ``prefill_and_decode()`` (the
driver loop), ``sample_logits()`` (temperature 0 ⇒ argmax).
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.lm import token_stream
from repro.launch.compile_cache import configure_compile_cache
from repro.models import model as model_lib
from repro.models.transformer import ModelOptions


def sample_logits(key, logits, temperature: float = 0.8):
    if temperature <= 0:
        return jnp.argmax(logits, -1)
    return jax.random.categorical(key, logits / temperature, axis=-1)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="Batched prefill + KV-cache decode for the LM configs")
    ap.add_argument("--arch", default="demo-11m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI run: assert decode shape/dtype stability "
                         "and greedy determinism at temperature 0")
    return ap


def make_prompts(cfg, batch: int, prompt_len: int, seed: int):
    """The driver's synthetic prompt batch — deterministic given the seed."""
    stream = token_stream(cfg.vocab_size, batch * prompt_len + 1, seed=seed)
    return jnp.asarray(stream[: batch * prompt_len].reshape(batch, prompt_len))


def prefill_and_decode(cfg, params, prompts, *, gen: int,
                       temperature: float = 0.8, seed: int = 0,
                       opts: Optional[ModelOptions] = None,
                       check_steps: bool = False) -> Dict[str, object]:
    """Prefill the KV cache by replaying the prompt through ``serve_step``,
    then decode ``gen`` tokens autoregressively. Returns the generated
    ``tokens [batch, gen]``, the timings, and (``check_steps=True``) asserts
    every decode step returns logits of the SAME shape and dtype — the
    cache layout never drifts mid-stream."""
    batch, prompt_len = prompts.shape
    max_seq = prompt_len + gen
    if opts is None:
        opts = ModelOptions(q_block=min(512, prompt_len),
                            kv_block=min(512, prompt_len))
    decode = jax.jit(
        lambda p, st, tok, pos: model_lib.serve_step(p, cfg, st, tok, pos, opts)
    )
    state = model_lib.init_decode_state(cfg, batch, max_seq, jnp.float32)
    key = jax.random.PRNGKey(seed)

    expect = None

    def checked(logits):
        nonlocal expect
        if not check_steps:
            return logits
        sig = (logits.shape, logits.dtype)
        if expect is None:
            expect = sig
            assert sig[0] == (batch, 1, cfg.vocab_size), sig
        assert sig == expect, f"decode step drifted: {sig} != {expect}"
        return logits

    t0 = time.time()
    logits = None
    for t in range(prompt_len):
        logits, state = decode(params, state, prompts[:, t: t + 1],
                               jnp.int32(t))
        checked(logits)
    t_prefill = time.time() - t0

    out_tokens = []
    tok = sample_logits(key, logits[:, 0], temperature)[:, None]
    t0 = time.time()
    for t in range(prompt_len, max_seq):
        out_tokens.append(np.asarray(tok))
        logits, state = decode(params, state, tok, jnp.int32(t))
        checked(logits)
        key = jax.random.fold_in(key, t)
        tok = sample_logits(key, logits[:, 0], temperature)[:, None]
    t_decode = time.time() - t0

    tokens = np.concatenate(out_tokens, axis=1)
    return {
        "tokens": tokens,
        "tokens_per_s": batch * gen / t_decode if t_decode > 0 else 0.0,
        "prefill_s": t_prefill,
        "decode_s": t_decode,
    }


def run_smoke(args) -> Dict[str, object]:
    """The CI smoke: a tiny greedy double-run. Asserts per-step shape/dtype
    stability (``check_steps``) and that temperature 0 is DETERMINISTIC —
    two identical decodes produce bit-equal token streams."""
    cfg = get_config(args.arch)
    assert not cfg.is_encoder_only, "encoder-only archs have no decode step"
    params = model_lib.init_model(jax.random.PRNGKey(args.seed), cfg,
                                  jnp.float32)
    prompts = make_prompts(cfg, args.batch, args.prompt_len, args.seed)
    runs = [
        prefill_and_decode(cfg, params, prompts, gen=args.gen,
                           temperature=0.0, seed=args.seed,
                           check_steps=True)
        for _ in range(2)
    ]
    a, b = runs[0]["tokens"], runs[1]["tokens"]
    assert a.shape == (args.batch, args.gen), a.shape
    np.testing.assert_array_equal(a, b,
                                  err_msg="greedy decode is not deterministic")
    print(f"SMOKE OK arch={cfg.name} batch={args.batch} "
          f"prompt={args.prompt_len} gen={args.gen} greedy-deterministic")
    return runs[0]


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.smoke:
        result = run_smoke(args)
        return {k: result[k] for k in ("tokens_per_s", "prefill_s", "decode_s")}

    cfg = get_config(args.arch)
    assert not cfg.is_encoder_only, "encoder-only archs have no decode step"
    params = model_lib.init_model(jax.random.PRNGKey(args.seed), cfg,
                                  jnp.float32)
    prompts = make_prompts(cfg, args.batch, args.prompt_len, args.seed)
    result = prefill_and_decode(cfg, params, prompts, gen=args.gen,
                                temperature=args.temperature, seed=args.seed)
    gen, tps = result["tokens"], result["tokens_per_s"]
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen}")
    print(f"prefill {result['prefill_s']:.2f}s, decode {result['decode_s']:.2f}s "
          f"-> {tps:.1f} tok/s")
    print("sample generations (token ids):")
    for b in range(min(2, args.batch)):
        print(f"  req{b}: {gen[b][:16].tolist()}...")
    return {k: result[k] for k in ("tokens_per_s", "prefill_s", "decode_s")}


if __name__ == "__main__":
    configure_compile_cache()
    main()
