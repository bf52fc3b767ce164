"""Model-stack behaviour: decode≡prefill, chunked attention vs naive,
MoE semantics, SSM scan equivalences, hybrid layer pattern, the CNN
client's two convolution forms."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import cnn
from repro.models import model as M
from repro.models import ssm as ssm_mod
from repro.models.attention import chunked_attention
from repro.models.moe import init_moe, moe_forward
from repro.models.transformer import ModelOptions, period_of, stack_split

KEY = jax.random.PRNGKey(0)
OPTS = ModelOptions(q_block=8, kv_block=8, detach_cut=False)


@pytest.mark.parametrize("arch", ["llama3.2-1b", "falcon-mamba-7b", "qwen2-7b"])
def test_decode_matches_prefill(arch):
    cfg = get_config(arch).reduced()
    params = M.init_model(KEY, cfg, jnp.float32)
    B, S = 2, 16
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    full = M.prefill(params, cfg, {"tokens": toks}, OPTS)
    state = M.init_decode_state(cfg, B, S, jnp.float32)
    outs = []
    for t in range(S):
        lg, state = M.serve_step(params, cfg, state, toks[:, t : t + 1], jnp.int32(t), OPTS)
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full), atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "jamba-1.5-large-398b"])
def test_decode_matches_prefill_moe_dropless(arch):
    # high capacity factor => dropless => decode must equal prefill
    cfg = dataclasses.replace(get_config(arch).reduced(), capacity_factor=8.0)
    params = M.init_model(KEY, cfg, jnp.float32)
    B, S = 2, 16
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    full = M.prefill(params, cfg, {"tokens": toks}, OPTS)
    state = M.init_decode_state(cfg, B, S, jnp.float32)
    outs = []
    for t in range(S):
        lg, state = M.serve_step(params, cfg, state, toks[:, t : t + 1], jnp.int32(t), OPTS)
        outs.append(lg)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate(outs, 1)), np.asarray(full), atol=2e-4, rtol=1e-3
    )


def test_chunked_attention_equals_naive_softmax():
    B, S, H, KV, hd = 2, 40, 4, 2, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    got = chunked_attention(q, k, v, causal=True, q_block=8, kv_block=8)
    # naive oracle
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    s = jnp.einsum("bqkgh,bckh->bkgqc", qg / jnp.sqrt(hd), k)
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    want = jnp.einsum("bkgqc,bckh->bqkgh", p, v).reshape(B, S, H, hd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)


def test_moe_capacity_drops_are_bounded_and_combine_weights_sum():
    cfg = dataclasses.replace(
        get_config("mixtral-8x7b").reduced(), capacity_factor=1.0
    )
    p = init_moe(KEY, cfg, jnp.float32)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model))
    y, aux = moe_forward(p, cfg, x)
    assert y.shape == x.shape
    assert jnp.isfinite(aux)
    # aux loss of a uniform router ~ 1.0 (E * sum(1/E * 1/E) * E = 1)
    assert 0.5 < float(aux) < 4.0


def test_moe_chunked_dispatch_matches_global_when_dropless():
    cfg = dataclasses.replace(
        get_config("granite-moe-1b-a400m").reduced(), capacity_factor=16.0
    )
    p = init_moe(KEY, cfg, jnp.float32)
    x = jax.random.normal(KEY, (4, 8, cfg.d_model))
    y1, _ = moe_forward(p, cfg, x, chunks=1)
    y2, _ = moe_forward(p, cfg, x, chunks=4)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5, rtol=1e-4)


def test_ssm_associative_scan_matches_sequential():
    cfg = get_config("falcon-mamba-7b").reduced()
    p = ssm_mod.init_ssm(KEY, cfg, jnp.float32)
    x = jax.random.normal(KEY, (2, 24, cfg.d_model)) * 0.1
    y_seq = ssm_mod.ssm_forward(p, cfg, x, associative=False)
    y_par = ssm_mod.ssm_forward(p, cfg, x, associative=True)
    np.testing.assert_allclose(np.asarray(y_seq), np.asarray(y_par), atol=1e-4, rtol=1e-3)


def test_hybrid_layer_pattern():
    cfg = get_config("jamba-1.5-large-398b")
    kinds = [cfg.layer_kind(i) for i in range(cfg.n_layers)]
    assert kinds.count("attn") == cfg.n_layers // cfg.attn_period  # 1:7 ratio
    assert all(k == "attn" for i, k in enumerate(kinds) if i % 8 == 4)
    moes = [cfg.layer_is_moe(i) for i in range(cfg.n_layers)]
    assert sum(moes) == cfg.n_layers // 2  # MoE every other layer


def test_stack_split_group_alignment():
    for arch in ["llama3.2-1b", "jamba-1.5-large-398b", "falcon-mamba-7b"]:
        cfg = get_config(arch)
        n_client, n_prefix, n_groups = stack_split(cfg)
        period = period_of(cfg)
        assert n_client + n_prefix + n_groups * period == cfg.n_layers


def test_param_count_matches_initialized():
    for arch in ["llama3.2-1b", "mixtral-8x7b", "falcon-mamba-7b", "jamba-1.5-large-398b"]:
        cfg = get_config(arch).reduced()
        params = M.init_model(KEY, cfg, jnp.float32)
        actual = sum(x.size for x in jax.tree.leaves(params))
        assert actual == cfg.param_count(), (arch, actual, cfg.param_count())


def test_privacy_noise_applied_only_with_key():
    cfg = dataclasses.replace(get_config("llama3.2-1b").reduced(), privacy_noise=0.5)
    params = M.init_model(KEY, cfg, jnp.float32)
    toks = jnp.zeros((1, 8), jnp.int32)
    from repro.models.transformer import forward

    a, _ = forward(params, cfg, {"tokens": toks}, OPTS, noise_key=None)
    b, _ = forward(params, cfg, {"tokens": toks}, OPTS, noise_key=jax.random.PRNGKey(7))
    assert float(jnp.max(jnp.abs(a - b))) > 0.0


@pytest.mark.parametrize("cin,hw", [(1, 9), (1, 12), (3, 9), (3, 12), (64, 11), (64, 20)])
def test_client_conv_matches_taps_over_a_bank(cin, hw):
    """Vmapped over a bank of distinct weights, as the fused trainer runs it,
    the client convolution is the tap sum: bit for bit with one input
    channel, within float32 rounding as a native convolution."""
    n_bank, cout = 4, 16
    kx, kbias, *kb = jax.random.split(jax.random.PRNGKey(cin * 100 + hw), n_bank + 2)
    layers = [cnn._init_conv(k, cin, cout) for k in kb]
    bank = jax.tree.map(lambda *a: jnp.stack(a), *layers)
    bank["b"] = jax.random.normal(kbias, bank["b"].shape)  # check the bias too
    x = jax.random.normal(kx, (n_bank, 2, hw, hw, cin))
    got = np.asarray(jax.jit(jax.vmap(cnn.client_conv))(bank, x))
    want = np.asarray(jax.jit(jax.vmap(cnn.conv2d_taps))(bank, x))
    assert got.shape == (n_bank, 2, hw, hw, cout)
    if cin == 1:
        np.testing.assert_array_equal(got, want)
    else:
        scale = float(np.max(np.abs(want)))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * scale)
