"""The first-class privacy subsystem: PrivacyGuard at the cut for every
engine, (ε, δ) budget carried in the canonical state, the fused dp_release
kernel, the deprecation shims, and the inversion audit."""
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import CHOLESTEROL_MLP, COVID_CNN
from repro.core import DPConfig, PrivacyGuard, SplitSession, SplitTrainConfig
from repro.core.adapters import cnn_adapter, mlp_adapter
from repro.data import make_cholesterol, make_covid_ct, split_clients
from repro.optim import adamw
from repro.privacy import (
    budget_advance,
    budget_init,
    budget_report,
    composed_epsilon,
    gaussian_release,
)

DP = DPConfig(epsilon=1.0, delta=1e-5, clip_norm=2.0)
UNIFORM_DP = SplitTrainConfig(
    server_batch=48, data_shares=(1.0, 1.0, 1.0), privacy=DP
)


@pytest.fixture(scope="module")
def chol_shards():
    x, y = make_cholesterol(600, seed=0)
    return split_clients(x, y), (x[:100], y[:100])


# ---------------------------------------------------------------- the guard
def test_guard_disabled_is_identity():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 8, 2))
    assert PrivacyGuard()(jax.random.PRNGKey(1), x) is x
    assert not PrivacyGuard.from_config(None).enabled


def test_guard_unclipped_reproduces_legacy_noise_bit_exactly():
    """DPConfig(clip_norm=None, noise_scale=s) — the privacy_noise shim's
    target — must equal the historical Gaussian perturbation bit-for-bit."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 8, 2))
    key = jax.random.PRNGKey(7)
    guard = PrivacyGuard.from_config(DPConfig(clip_norm=None, noise_scale=0.05))
    np.testing.assert_array_equal(
        np.asarray(guard(key, x)), np.asarray(gaussian_release(x, 0.05, key))
    )


def test_guard_clip_bounds_norm_and_noise_scale():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16, 16, 2)) * 10
    clip_only = PrivacyGuard.from_config(
        DPConfig(clip_norm=1.0, noise_scale=0.0)
    )(jax.random.PRNGKey(1), x)
    norms = jnp.linalg.norm(clip_only.reshape(8, -1), axis=-1)
    assert float(norms.max()) <= 1.0 + 1e-5
    noisy = PrivacyGuard.from_config(
        dataclasses.replace(DP, clip_norm=1.0)
    )(jax.random.PRNGKey(1), x)
    clipped = PrivacyGuard.from_config(
        DPConfig(clip_norm=1.0, noise_scale=0.0)
    )(jax.random.PRNGKey(1), x)
    emp = float(jnp.std(noisy - clipped))  # isolates the σ-scaled draw
    sigma = dataclasses.replace(DP, clip_norm=1.0).sigma
    assert 0.8 * sigma < emp < 1.2 * sigma


def test_config_shims_warn_and_map():
    with pytest.deprecated_call():
        tc = SplitTrainConfig(privacy_noise=0.05)
    assert tc.privacy is not None
    assert tc.privacy.clip_norm is None and tc.privacy.noise_scale == 0.05
    with pytest.deprecated_call():
        tc2 = SplitTrainConfig(clip_norm=0.5)
    assert tc2.grad_clip == 0.5


def test_config_shims_consumed_so_replace_cannot_reapply():
    """The deprecated fields are cleared after mapping: a later
    dataclasses.replace() must honor explicit new-field values instead of
    silently re-applying the legacy ones (and must not re-warn)."""
    import warnings as _w

    with pytest.deprecated_call():
        tc = SplitTrainConfig(clip_norm=0.5)
    with _w.catch_warnings():
        _w.simplefilter("error")  # any DeprecationWarning here fails
        tc2 = dataclasses.replace(tc, grad_clip=2.0)
    assert tc2.grad_clip == 2.0 and tc2.clip_norm is None
    with pytest.deprecated_call():
        tcp = SplitTrainConfig(privacy_noise=0.05)
    with _w.catch_warnings():
        _w.simplefilter("error")
        tcp2 = dataclasses.replace(tcp, privacy=None)
    assert tcp2.privacy is None and tcp2.privacy_noise == 0.0


def test_guard_refuses_keyless_noise_release():
    guard = PrivacyGuard.from_config(DP)  # sigma > 0
    x = jnp.ones((2, 4))
    with pytest.raises(AssertionError, match="PRNG key"):
        guard(None, x)
    with pytest.raises(AssertionError, match="noise"):
        guard.release_with_noise(x, None)


def test_deprecated_shim_modules_reexport_privacy():
    import repro.core.dp as core_dp
    import repro.core.inversion as core_inv

    with pytest.warns(DeprecationWarning):
        importlib.reload(core_dp)
    with pytest.warns(DeprecationWarning):
        importlib.reload(core_inv)
    from repro.privacy import dp_release, inversion_attack_report

    assert core_dp.dp_release is dp_release
    assert core_dp.DPConfig is DPConfig
    assert core_inv.inversion_attack_report is inversion_attack_report


# ------------------------------------------------------------- accountant
def test_advanced_composition_beats_basic_and_is_monotone():
    dp = DPConfig(epsilon=0.1, delta=1e-6)
    advs = [composed_epsilon(dp, t)["advanced_epsilon"] for t in (1, 10, 100, 500)]
    assert advs == sorted(advs)  # monotone in releases
    for t in (100, 500):
        rep = composed_epsilon(dp, t)
        assert rep["advanced_epsilon"] < rep["basic_epsilon"]
    assert composed_epsilon(dp, 0)["advanced_epsilon"] == 0.0


def test_unclipped_release_spends_infinite_epsilon():
    dp = DPConfig(clip_norm=None, noise_scale=0.05)
    rep = composed_epsilon(dp, 3)
    assert rep["basic_epsilon"] == float("inf")


def test_budget_leaves_accumulate_on_device():
    b = budget_init()
    assert b["releases"].dtype == jnp.int32
    b = budget_advance(b, DP, 5)
    b = budget_advance(b, DP)
    assert int(b["releases"]) == 6
    assert float(b["epsilon_basic"]) == pytest.approx(6.0)
    rep = budget_report(DP, b)
    assert rep["basic_epsilon"] == pytest.approx(6.0)
    assert rep == budget_report(DP, jax.device_get(b))
    # disabled guard: advance is the identity
    assert budget_advance(b, None, 100) is b


# ----------------------------------------------------- guard across engines
def test_guard_parity_across_engines_sigma0_and_sigma_pos(chol_shards):
    """All six engines run with the guard at the cut. The three SPMD
    engines share one key schedule, so their losses agree (scan/stepwise to
    the last bit at σ=0; to fp32 reassociation once the clip reduction is
    in play); protocol/fused-queue/fedavg train finitely and account their
    releases (the two queue engines bit-match each other — pinned harder in
    tests/test_fused_queue.py)."""
    shards, _ = chol_shards
    ad = mlp_adapter(CHOLESTEROL_MLP)
    for dp in (DPConfig(epsilon=1e6, delta=1e-5, clip_norm=1e9),  # σ≈0 regime
               DP):
        tc = dataclasses.replace(UNIFORM_DP, privacy=dp)
        losses = {}
        for engine, kw in [("fused-scan", {}), ("fused-stepwise", {}),
                           ("looped-ref", {}),
                           ("protocol-async", {"threaded": False}),
                           ("fused-queue", {"threaded": False}),
                           ("fedavg", {})]:
            s = SplitSession(ad, tc, adamw(1e-2), engine=engine, **kw)
            h = s.fit(shards, epochs=2, steps_per_epoch=4)
            losses[engine] = [r["loss"] for r in h]
            assert all(np.isfinite(losses[engine])), engine
            rep = s.privacy_report()
            assert rep["enabled"] and rep["releases"] > 0, engine
            assert rep["basic_epsilon"] == pytest.approx(
                composed_epsilon(dp, rep["releases"])["basic_epsilon"]
            ), engine
        np.testing.assert_allclose(losses["fused-scan"], losses["fused-stepwise"],
                                   rtol=1e-5)
        np.testing.assert_allclose(losses["fused-scan"], losses["looped-ref"],
                                   rtol=1e-4)
        # the queue engines share clients AND keys: exact equality
        assert losses["protocol-async"] == losses["fused-queue"]
        # fused/looped: one release per optimizer step
        assert losses["fused-scan"] is not None


def test_guard_off_release_count_stays_zero(chol_shards):
    shards, _ = chol_shards
    ad = mlp_adapter(CHOLESTEROL_MLP)
    s = SplitSession(ad, SplitTrainConfig(server_batch=48), adamw(1e-2))
    s.fit(shards, epochs=1, steps_per_epoch=3)
    rep = s.privacy_report()
    assert not rep["enabled"] and rep["releases"] == 0
    assert "basic_epsilon" not in rep


def test_protocol_queue_stats_report_budget(chol_shards):
    shards, _ = chol_shards
    ad = mlp_adapter(CHOLESTEROL_MLP)
    s = SplitSession(ad, dataclasses.replace(UNIFORM_DP, data_shares=(0.7, 0.2, 0.1)),
                     adamw(1e-2), engine="protocol-async", threaded=False)
    s.fit(shards, epochs=1, steps_per_epoch=6)
    stats = s.engine.stats
    assert stats["privacy"]["enabled"]
    assert stats["privacy"]["releases"] == s.privacy_report()["releases"] > 0


# ------------------------------------------------------- dp_release kernel
@pytest.mark.parametrize("shape,clip,sigma", [
    ((4, 8, 8, 2), 1.0, 0.0), ((2, 16, 16, 4), 0.5, 0.1),
    ((8, 7), 2.0, 0.05),
    # rows longer than one VMEM tile: divisible (2304 rows -> 2 tiles of
    # 1152) and padded (2305 rows -> 2 tiles of 2048)
    ((2, 64, 64, 72), 1.0, 0.1), ((2, 2304 * 128 + 5), 1.0, 0.1),
])
def test_dp_release_kernel_matches_ref(shape, clip, sigma):
    from repro.kernels.dp_release.kernel import dp_release_pallas
    from repro.kernels.dp_release.ref import dp_release_ref

    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(ks[0], shape) * 3
    nz = jax.random.normal(ks[1], shape)
    got = dp_release_pallas(x, nz, clip_norm=clip, sigma=sigma, interpret=True)
    want = dp_release_ref(x, nz, clip_norm=clip, sigma=sigma)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_dp_release_custom_vjp_matches_xla_reference():
    from repro.kernels.dp_release.ops import dp_release

    key = jax.random.PRNGKey(3)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 6, 6, 2)) * 2

    def make_loss(use_kernel):
        def loss(x):
            out = dp_release(x, key, clip_norm=1.0, sigma=0.1,
                             use_kernel=use_kernel, interpret=True)
            return jnp.sum(out ** 2)
        return loss

    val_k, grad_k = jax.value_and_grad(make_loss(True))(x)
    val_r, grad_r = jax.value_and_grad(make_loss(False))(x)
    np.testing.assert_allclose(float(val_k), float(val_r), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(grad_k), np.asarray(grad_r),
                               rtol=1e-4, atol=1e-5)


# ------------------------------------------------- budget x save/restore
def test_budget_survives_save_restore_and_resume(tmp_path, chol_shards):
    shards, _ = chol_shards
    ad = mlp_adapter(CHOLESTEROL_MLP)
    session = SplitSession(ad, UNIFORM_DP, adamw(1e-2), engine="fused-scan")
    session.fit(shards, epochs=2, steps_per_epoch=3)
    rep = session.privacy_report()
    assert rep["releases"] == 6 == int(session.state["step"])
    assert rep["basic_epsilon"] == pytest.approx(
        composed_epsilon(DP, 6)["basic_epsilon"]
    )
    path = session.save(str(tmp_path))

    fresh = SplitSession(ad, UNIFORM_DP, adamw(1e-2), engine="fused-scan")
    manifest = fresh.restore(path)
    assert manifest["metadata"]["privacy_releases"] == 6
    assert fresh.privacy_report() == rep
    fresh.fit(shards, epochs=1, steps_per_epoch=3)
    rep2 = fresh.privacy_report()
    assert rep2["releases"] == 9
    assert rep2["basic_epsilon"] == pytest.approx(
        composed_epsilon(DP, 9)["basic_epsilon"]
    )
    # evaluate() surfaces the same budget
    ev = fresh.evaluate(*chol_shards[1])
    assert ev["privacy"] == rep2


# ------------------------------------------------------------------ audit
def test_audit_privacy_mse_monotone_in_sigma():
    """The acceptance check: reconstruction MSE rises with guard σ on the
    demo CNN config (and the sweep works on the cholesterol MLP too)."""
    cfg = dataclasses.replace(
        COVID_CNN, input_hw=(16, 16), stages=((8, 1),), dense_units=(16,),
        privacy_noise=0.0,
    )
    ad = cnn_adapter(cfg)
    x, y = make_covid_ct(120, hw=16, seed=0)
    shards = split_clients(x, y)
    session = SplitSession(ad, dataclasses.replace(UNIFORM_DP, server_batch=24),
                           adamw(1e-3))
    session.fit(shards, epochs=1, steps_per_epoch=3)
    rows = session.audit_privacy(jnp.asarray(x[:1]), sigmas=(0.0, 1.0, 8.0),
                                 steps=50)
    mses = [r["mse"] for r in rows]
    assert mses[0] < mses[1] < mses[2], mses
    assert all(np.isfinite(r["psnr_db"]) and -1 <= r["ncc"] <= 1 for r in rows)

    mlp_sess = SplitSession(mlp_adapter(CHOLESTEROL_MLP), UNIFORM_DP, adamw(1e-2))
    xc, yc = make_cholesterol(60, seed=1)
    mlp_sess.fit(split_clients(xc, yc), epochs=1, steps_per_epoch=2)
    mlp_rows = mlp_sess.audit_privacy(jnp.asarray(xc[:1]), sigmas=(0.0, 5.0),
                                      steps=40)
    assert mlp_rows[0]["mse"] < mlp_rows[1]["mse"]
