"""Bring-up check: the split-learning main path on a TPU chip.

  python3 chip_smoke.py                # one chip: every phase below
  python3 chip_smoke.py --four-chips   # four chips: the sharded MURA trunk only

One process, no subprocesses. The phases run in order, and any failure
exits non-zero; no phase catches a failure and carries on.

  1. device   JAX must find a TPU. There is no CPU fallback.
  2. kernels  privacy_conv and dp_release compiled (``interpret=False``) and
              compared with their ``ref.py`` oracles at the paper's widths.
  3. covid    COVID_CNN, three hospitals at 7:2:1 with the PrivacyGuard on,
              through ``SplitSession(engine="auto")``, which must resolve to
              the scanned epoch; then one epoch through ``engine="fused-queue"``.
  4. mura     MURA_VGG19 at 224x224x1 through ``SplitSession``.
  5. serve    ``session.serve(poisson_trace(...))`` on the trained COVID session.

``--four-chips`` runs only MURA_VGG19 with four hospitals on
``make_split_mesh(1, 4)`` and ``make_split_mesh(2, 2)`` against the same seed
and batch on one chip.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
The phase functions take their sizes as arguments so that
``tests/test_chip_smoke.py`` can rehearse them on the CPU at tiny sizes.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.flatten_util import ravel_pytree  # noqa: E402

from repro.configs.paper_models import COVID_CNN, MURA_VGG19, TABLE1_CNN  # noqa: E402
from repro.core import SplitSession, SplitTrainConfig  # noqa: E402
from repro.core.adapters import cnn_adapter  # noqa: E402
from repro.data import make_covid_ct, make_mura, split_clients  # noqa: E402
from repro.kernels import resolve_interpret  # noqa: E402
from repro.kernels.dp_release.kernel import dp_release_pallas  # noqa: E402
from repro.kernels.dp_release.ref import dp_release_ref  # noqa: E402
from repro.kernels.privacy_conv.kernel import privacy_conv_pallas  # noqa: E402
from repro.kernels.privacy_conv.ref import privacy_conv_ref  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402
from repro.launch.mesh import make_split_mesh  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.privacy import DPConfig  # noqa: E402
from repro.serving import poisson_trace  # noqa: E402

# privacy_conv's nine tap matmuls run on the MXU, which may round fp32
# operands to bf16 (8-bit mantissa); outputs are O(1), so 1e-2 bounds it.
PRIVACY_CONV_TOL = dict(rtol=1e-2, atol=1e-2)
# dp_release is fp32 elementwise work plus one norm; only the summation
# order of the norm differs from the oracle.
DP_RELEASE_TOL = dict(rtol=1e-5, atol=1e-6)
# Four-chip MURA against one chip. The model-axis split reorders the trunk's
# matmul reductions; the largest per-step loss difference in three runs on
# the chip was 1.05e-3 (2x2, step 3; the runs repeat bit for bit), and the
# three steps move the loss by 2.5%.
SHARDED_LOSS_RTOL = 5e-3

# The guard at the cut: per-sample L2 clip, then Gaussian noise at the
# paper's privacy-layer scale (CNNConfig.privacy_noise).
GUARD = DPConfig(clip_norm=32.0, noise_scale=0.05)
COVID_SHARES = (0.7, 0.2, 0.1)
COVID_LR = 1e-3  # the paper's, as in examples/covid_ct_split.py
# At 1e-3 a single seed's loss may spike for a few steps in any epoch, on
# the scanned and the stepwise path alike and at any matmul precision
# (PERF.md), so convergence is judged on the mean over several seeds.
COVID_SEEDS = (0, 1, 2)
MURA_SHARES = (0.4, 0.3, 0.2, 0.1)
# The largest power-of-two MURA server batch whose train step fits one
# v5e chip's 16 GB, from compiling the step for a described v5e chip.
MURA_BATCH = 128


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def check_device(count: int = 1) -> dict:
    """Phase 1: the device JAX found; exits non-zero unless it is a TPU
    with at least ``count`` chips."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform "
                         f"{dev.platform!r}); there is no CPU fallback")
    if len(devices) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, JAX found "
                         f"{len(devices)}")
    log("device", kind=repr(dev.device_kind), count=len(devices))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def _check_close(name: str, got, want, tol: dict) -> None:
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, (name, got.shape, want.shape)
    assert np.all(np.isfinite(got)), f"{name}: non-finite kernel output"
    err = float(np.max(np.abs(got - want)))
    np.testing.assert_allclose(got, want, err_msg=name, **tol)
    log("kernels", case=name, max_abs_err=err)


def phase_kernels(*, interpret: bool = False, batch: int = 64,
                  mura_batch: int = MURA_BATCH) -> None:
    """Phase 2: each Pallas kernel of the main path against its oracle at
    the client widths (privacy_conv) and cut widths (dp_release)."""
    key = jax.random.PRNGKey(0)
    for cfg in (COVID_CNN, TABLE1_CNN):
        h, w = cfg.input_hw
        cin, cout = cfg.in_channels, cfg.stages[0][0]
        kx, kw, kn = jax.random.split(jax.random.fold_in(key, h * cin), 3)
        x = jax.random.uniform(kx, (batch, h, w, cin))
        wt = jax.random.normal(kw, (3, 3, cin, cout)) * 0.3
        b = jnp.full((cout,), 0.01)
        noise = jax.random.normal(kn, (batch, h // 2, w // 2, cout))
        got = privacy_conv_pallas(x, wt, b, noise, noise_scale=0.05,
                                  interpret=interpret)
        with jax.default_matmul_precision("highest"):
            want = privacy_conv_ref(x, wt, b, noise, noise_scale=0.05)
        _check_close(f"privacy_conv:{cfg.name}:{batch}x{h}x{w}x{cin}->{cout}",
                     got, want, PRIVACY_CONV_TOL)

    for cfg, b in ((COVID_CNN, batch), (MURA_VGG19, mura_batch)):
        h, w = cfg.input_hw
        shape = (b, h // 2, w // 2, cfg.stages[0][0])  # the cut feature map
        kx, kn = jax.random.split(jax.random.fold_in(key, h), 2)
        x = jax.random.normal(kx, shape) * 2.0
        noise = jax.random.normal(kn, shape)
        got = dp_release_pallas(x, noise, clip_norm=1.0, sigma=0.5,
                                interpret=interpret)
        with jax.default_matmul_precision("highest"):
            want = dp_release_ref(x, noise, clip_norm=1.0, sigma=0.5)
        _check_close(f"dp_release:{cfg.name}:{'x'.join(map(str, shape))}",
                     got, want, DP_RELEASE_TOL)


def _assert_on(tree, platform: str) -> None:
    for leaf in jax.tree.leaves(tree):
        where = {d.platform for d in leaf.devices()}
        assert where == {platform}, f"state leaf on {where}, not {platform}"


def phase_covid(*, cfg=COVID_CNN, n: int = 600, server_batch: int = 64,
                epochs: int = 3, steps: int = 16, seeds=COVID_SEEDS):
    """Phase 3: three hospitals at 7:2:1 with the guard on, through
    ``engine="auto"`` once per seed, then one epoch of the async regime
    (``engine="fused-queue"``). The mean epoch loss over the seeds must
    fall. Returns ``(session, shards, epoch_modes)`` with the first seed's
    session and shards."""
    tc = SplitTrainConfig(n_clients=3, data_shares=COVID_SHARES,
                          server_batch=server_batch, privacy=GUARD)
    adapter = cnn_adapter(cfg)
    runs, modes = [], []
    for seed in seeds:
        x, y = make_covid_ct(n, hw=cfg.input_hw[0], seed=seed)
        shards = split_clients(x, y, shares=COVID_SHARES, seed=seed)
        session = SplitSession(adapter, tc, adamw(COVID_LR), engine="auto",
                               seed=seed)
        losses = [h["loss"] for h in session.fit(shards, epochs=epochs,
                                                 steps_per_epoch=steps)]
        modes.append(session.engine.epoch_mode)
        log("covid", engine="auto", seed=seed, epoch_mode=modes[-1],
            epoch_losses=losses, releases=session.privacy_report()["releases"])
        _assert_on(session.state, jax.devices()[0].platform)
        assert np.all(np.isfinite(losses)), losses
        runs.append((session, shards, losses))
    mean = np.mean([losses for _, _, losses in runs], axis=0).tolist()
    log("covid", seeds=list(seeds), mean_epoch_losses=mean)
    assert mean[-1] < mean[0], f"COVID mean loss did not fall: {mean}"

    session, shards, _ = runs[0]
    queued = SplitSession(adapter, tc, adamw(COVID_LR), engine="fused-queue",
                          seed=seeds[0])
    q_hist = queued.fit(shards, epochs=1, steps_per_epoch=steps)
    q_losses = queued.engine.losses
    log("covid", engine="fused-queue", step_losses=q_losses,
        dropped=queued.engine.stats["dropped"])
    assert len(q_losses) == steps and np.all(np.isfinite(q_losses)), q_losses
    assert np.isfinite(q_hist[0]["loss"])
    return session, shards, modes


def train_mura(*, cfg=MURA_VGG19, server_batch: int = MURA_BATCH,
               epochs: int = 3, mesh=None, n: int = 256, seed: int = 0):
    """MURA through ``SplitSession`` with four hospitals, one step per
    epoch; returns ``(session, per-step losses)``."""
    x, y = make_mura(n, hw=cfg.input_hw[0], seed=seed)
    shards = split_clients(x, y, shares=MURA_SHARES, seed=seed)
    tc = SplitTrainConfig(n_clients=len(MURA_SHARES), data_shares=MURA_SHARES,
                          server_batch=server_batch, privacy=GUARD)
    session = SplitSession(cnn_adapter(cfg), tc, adamw(1e-4), engine="auto",
                           mesh=mesh, seed=seed)
    hist = session.fit(shards, epochs=epochs, steps_per_epoch=1)
    return session, [h["loss"] for h in hist]


def dense_grad_sums(session) -> list:
    """Each trunk dense kernel's AdamW first moment, in float64 on the host.
    The split is detached (the paper's), so the optimizer state covers the
    raveled trunk alone."""
    flat, unravel = ravel_pytree(session.state["server"])
    mu = session.state["opt"]["mu"]
    assert mu.shape == flat.shape, (mu.shape, flat.shape)
    return [np.asarray(d["w"], np.float64) for d in unravel(mu)["dense"]]


def phase_mura(*, cfg=MURA_VGG19, server_batch: int = MURA_BATCH,
               epochs: int = 3, n: int = 256) -> list:
    """Phase 4: MURA_VGG19 at its published widths on one chip."""
    log("mura", input=f"{cfg.input_hw[0]}x{cfg.input_hw[1]}x{cfg.in_channels}",
        server_batch=server_batch, hospitals=len(MURA_SHARES))
    session, losses = train_mura(cfg=cfg, server_batch=server_batch,
                                 epochs=epochs, n=n)
    norms = [float(np.linalg.norm(m)) for m in dense_grad_sums(session)]
    log("mura", epoch_mode=session.engine.epoch_mode, step_losses=losses,
        dense_grad_sum_norms=norms)
    _assert_on(session.state, jax.devices()[0].platform)
    assert np.all(np.isfinite(losses)), losses
    assert all(np.isfinite(v) and v > 0 for v in norms), norms
    return losses


def phase_serve(session, shards, *, rate: float = 2.0, horizon: int = 24,
                min_answered: int = 24) -> None:
    """Phase 5: an open Poisson trace through the trained session's guarded
    split-inference path."""
    trace = poisson_trace(len(shards), rate=rate, horizon=horizon, seed=0,
                          shares=COVID_SHARES)
    report = session.serve(trace, shards)
    log("serve", offered=report.offered, answered=report.answered,
        dropped=report.dropped, shed=report.shed, batches=report.batches,
        mean_batch_fill=report.mean_batch_fill)
    assert report.offered == report.answered + report.dropped + report.shed
    assert report.answered >= min_answered, report.answered
    assert len(report.responses) == report.answered
    assert all(np.all(np.isfinite(r)) for r in report.responses.values())


def phase_mura_sharded(*, cfg=MURA_VGG19, server_batch: int = MURA_BATCH,
                       epochs: int = 3, n: int = 256,
                       grids=((1, 4), (2, 2))) -> None:
    """``--four-chips``: MURA with four hospitals on each ``(clients,
    model)`` grid against the same seed and batch on one chip. Per-step
    losses must agree and the trunk's dense kernels must span the grid.

    Each dense kernel's gradient sum is logged against one chip's but not
    bounded: on the chip it differed by 0.56-0.62 at default precision and
    by 0.075-0.095 at "highest" between runs whose losses agreed to 4e-5,
    and whether that is amplified rounding is open (PERF.md)."""
    ref_session, ref = train_mura(cfg=cfg, server_batch=server_batch,
                                  epochs=epochs, n=n)
    ref_grads = dense_grad_sums(ref_session)
    log("mura-4chip", grid="1 chip", step_losses=ref,
        dense_grad_sum_norms=[float(np.linalg.norm(m)) for m in ref_grads])
    assert np.all(np.isfinite(ref)), ref
    results = []  # every grid is measured and logged before any assert
    for grid in grids:
        mesh = make_split_mesh(*grid, n_clients=len(MURA_SHARES))
        session, losses = train_mura(cfg=cfg, server_batch=server_batch,
                                     epochs=epochs, mesh=mesh, n=n)
        rel = float(np.max(np.abs(np.subtract(losses, ref)) / np.abs(ref)))
        grad_rel = [float(np.linalg.norm(m - r) / np.linalg.norm(r))
                    for m, r in zip(dense_grad_sums(session), ref_grads)]
        dense = session.state["server"]["dense"]
        spans = [len(d["w"].sharding.device_set) for d in dense]
        log("mura-4chip", grid=f"{grid[0]}x{grid[1]}", step_losses=losses,
            max_rel_diff=rel, dense_grad_sum_rel_diff=grad_rel,
            dense_kernel_devices=spans,
            dense_kernel_sharded=[not d["w"].sharding.is_fully_replicated
                                  for d in dense])
        results.append((grid, mesh.size, losses, spans))
    for grid, size, losses, spans in results:
        np.testing.assert_allclose(losses, ref, rtol=SHARDED_LOSS_RTOL,
                                   err_msg=f"grid {grid}")
        assert all(s == size for s in spans), (grid, spans)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded MURA phase")
    args = ap.parse_args(argv)
    device = check_device(4 if args.four_chips else 1)
    log("device", compile_cache=configure_compile_cache())
    if args.four_chips:
        phase_mura_sharded()
    else:
        if resolve_interpret(None):
            raise SystemExit("chip_smoke: kernels would run interpreted on a TPU")
        phase_kernels(interpret=False)
        session, shards, modes = phase_covid()
        assert set(modes) == {"scan"}, f"engine='auto' resolved to {modes} on a TPU"
        phase_mura()
        phase_serve(session, shards)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
