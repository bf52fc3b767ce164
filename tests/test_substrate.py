"""Substrate: data generators, checkpointing, schedules, sharding specs."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.checkpoint import latest_checkpoint, load_checkpoint, save_checkpoint
from repro.data import make_cholesterol, make_covid_ct, make_mura, train_val_test_split
from repro.data.lm import lm_batches, token_stream
from repro.optim import cosine_schedule, linear_warmup_cosine
from repro.sharding.specs import tree_specs


def test_covid_ct_generator_learnable_signal():
    x, y = make_covid_ct(100, hw=32, seed=0)
    assert x.shape == (100, 32, 32, 1) and x.min() >= 0 and x.max() <= 1
    # positives are brighter inside the lung (ground-glass)
    pos_mean = x[y > 0.5].mean()
    neg_mean = x[y < 0.5].mean()
    assert pos_mean > neg_mean


def test_mura_class_balance_matches_table2():
    x, y = make_mura(600, hw=32, seed=0, part="shoulder")
    # shoulder: 4168/8379 ≈ 49.7% positive (paper Table 2)
    assert 0.40 < y.mean() < 0.60
    x, y = make_mura(600, hw=32, seed=0, part="hand")
    # hand: 1484/5543 ≈ 26.8%
    assert 0.15 < y.mean() < 0.40


def test_mura_same_seed_same_images_in_every_process():
    import subprocess
    import sys
    code = ("from repro.data.synthetic import make_mura; "
            "x, y = make_mura(4, hw=16, seed=0); print(float(x.sum()), y.tolist())")
    outs = [subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, check=True,
                           env={**os.environ, "PYTHONHASHSEED": s}).stdout
            for s in ("1", "2")]
    assert outs[0] == outs[1] and outs[0]


def test_cholesterol_follows_friedewald():
    x, y = make_cholesterol(500, seed=0, normalize=False)
    tc, hdl, tg = x[:, 4], x[:, 5], x[:, 6]
    pred = np.clip(tc - hdl - tg / 5.0, 10, 250)
    resid = np.abs(pred - y)
    assert np.median(resid) < 15.0  # mostly the Friedewald relation


def test_train_val_test_split_disjoint():
    x = np.arange(100)[:, None]
    y = np.arange(100)
    (tr, _), (va, _), (te, _) = train_val_test_split(x, y)
    all_idx = np.concatenate([tr[:, 0], va[:, 0], te[:, 0]])
    assert len(all_idx) == 100 and len(set(all_idx.tolist())) == 100


def test_token_stream_and_batches():
    s = token_stream(128, 10_000, seed=0)
    assert s.min() >= 0 and s.max() < 128
    it = lm_batches(s, batch=4, seq_len=32)
    b = next(it)
    assert b["tokens"].shape == (4, 32)


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": [jnp.ones((4,)), {"c": jnp.zeros((2, 2))}]}
    path = save_checkpoint(str(tmp_path), 42, tree, {"note": "test"})
    assert latest_checkpoint(str(tmp_path)) == path
    restored, manifest = load_checkpoint(path, tree)
    assert manifest["step"] == 42
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_shape_mismatch_raises(tmp_path):
    tree = {"a": jnp.ones((2, 3))}
    path = save_checkpoint(str(tmp_path), 1, tree)
    with pytest.raises(ValueError):
        load_checkpoint(path, {"a": jnp.ones((3, 2))})


def test_schedules():
    s = cosine_schedule(1.0, 100)
    assert float(s(jnp.asarray(0))) == pytest.approx(1.0)
    assert float(s(jnp.asarray(100))) == pytest.approx(0.1, abs=1e-3)
    w = linear_warmup_cosine(1.0, 10, 100)
    assert float(w(jnp.asarray(5))) == pytest.approx(0.5)


def test_tree_specs_rules_and_divisibility():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    params = {
        "prefix": [{"attn": {"wq": jnp.zeros((64, 128)), "wo": jnp.zeros((128, 64))}}],
        "lm_head": jnp.zeros((64, 256)),
        "final_norm": jnp.zeros((64,)),
    }
    specs = tree_specs(params, mesh)
    assert specs["prefix"][0]["attn"]["wq"] == P(None, "model")
    assert specs["prefix"][0]["attn"]["wo"] == P("model", None)
    assert specs["lm_head"] == P(None, "model")
    assert specs["final_norm"] == P(None)


def test_tree_specs_drops_nondivisible():
    mesh = jax.make_mesh((1, 1), ("data", "model"))

    # simulate 16-way mesh check via a fake leaf whose dim isn't divisible
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 16}

    from repro.sharding.specs import _leaf_spec
    import jax.tree_util as jtu

    path = (jtu.DictKey("wq"),)
    spec = _leaf_spec(FakeMesh(), path, jnp.zeros((64, 24)), data_axes="data",
                      banked_client=False)
    assert spec == P(None, None)  # 24 % 16 != 0 -> replicated


def test_banked_client_leading_dim():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    tree = {"client_banks": {"embed": jnp.zeros((4, 128, 64))}}
    specs = tree_specs(tree, mesh, banked_client=True)
    assert specs["client_banks"]["embed"][0] == "data"
