"""The program's own tracing: named scopes in the compiled programs, host
spans in ``fit`` and ``serve``, and the serving queue-wait counter.

Scopes are op_name metadata, read here from the compiled HLO text; spans
are ``jax.profiler`` host events, read from a capture on the CPU."""
import dataclasses
import glob
import os
import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import COVID_CNN
from repro.core import SplitSession, SplitTrainConfig
from repro.core.adapters import cnn_adapter
from repro.core.protocol import make_client_release_fwd, make_fleet_release_fwd
from repro.core.trainer import device_put_shards, make_epoch_runner
from repro.data import make_covid_ct, split_clients
from repro.optim import adamw
from repro.privacy import DPConfig
from repro.privacy.guard import PrivacyGuard
from repro.serving import poisson_trace
from repro.serving.server import make_server_batch_forward

SMALL_CNN = dataclasses.replace(
    COVID_CNN, input_hw=(16, 16), stages=((8, 1), (16, 1)), dense_units=(16,)
)
GUARDED = SplitTrainConfig(server_batch=24,
                           privacy=DPConfig(clip_norm=1.0, noise_scale=0.1))


@pytest.fixture(scope="module")
def shards():
    x, y = make_covid_ct(240, hw=16, seed=0)
    return split_clients(x, y)


def op_names(compiled) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def under(names: set, pattern: str) -> set:
    return {n for n in names if re.search(pattern, n)}


def assert_guard_scopes(names: set) -> None:
    """The guard's noise draw and its release (clip, noise) both sit under
    the ``guard`` scope."""
    guard = under(names, r"\bguard\b")
    assert under(guard, r"_normal")
    assert guard - under(guard, r"_normal")


# ------------------------------------------------------------------ scopes
def test_epoch_program_carries_layer_scopes(shards):
    adapter = cnn_adapter(SMALL_CNN)
    init_state, run_epoch = make_epoch_runner(adapter, GUARDED, adamw(1e-3),
                                              steps_per_epoch=2, mode="scan")
    state = init_state(jax.random.PRNGKey(0))
    data_x, data_y, lens = device_put_shards(shards)
    names = op_names(jax.jit(run_epoch).lower(
        state, data_x, data_y, lens, jax.random.PRNGKey(1)).compile())
    assert under(names, r"\bprivacy_layer\b")
    assert_guard_scopes(names)
    assert under(names, r"\boptimizer\b")
    assert under(names, r"transpose\(jvp\(trunk")
    # the trunk's forward: under trunk and not under its transpose
    assert under(names, r"\btrunk\b") - under(names, r"transpose\(")


def test_serving_programs_carry_layer_scopes(shards):
    adapter = cnn_adapter(SMALL_CNN)
    params = adapter.init(jax.random.PRNGKey(0))
    guard = PrivacyGuard.from_config(GUARDED.privacy)
    x = jnp.asarray(shards[0][0][:1])
    release = make_client_release_fwd(adapter, guard)
    names = op_names(release.lower(params["client"], x,
                                   jax.random.PRNGKey(2)).compile())
    assert under(names, r"\bprivacy_layer\b")
    assert_guard_scopes(names)
    fleet = make_fleet_release_fwd(adapter, guard)
    banks = jax.tree.map(lambda a: jnp.stack([a, a]), params["client"])
    keys = jnp.stack([jax.random.PRNGKey(3), jax.random.PRNGKey(4)])
    ids = jnp.asarray([1, 0])
    names = op_names(fleet.lower(banks, keys, ids, ids,
                                 jnp.stack([x, x])).compile())
    assert under(names, r"\bprivacy_layer\b")
    assert_guard_scopes(names)
    feats = release(params["client"], x, jax.random.PRNGKey(2))
    forward = make_server_batch_forward(adapter)
    names = op_names(forward.lower(params["server"],
                                   jnp.stack([feats, feats])).compile())
    assert under(names, r"\btrunk\b")


# ------------------------------------------------------------------- spans
def host_spans(log_dir: str) -> Counter:
    """Counts of the ``repro.*`` host spans in the profiler's capture."""
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    counts: Counter = Counter()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                counts.update(e.name for e in line.events
                              if e.name.startswith("repro."))
    return counts


def test_fit_spans_upload_per_call_readout_per_epoch(tmp_path, shards):
    s = SplitSession(cnn_adapter(SMALL_CNN), GUARDED, adamw(1e-3),
                     engine="fused-scan", seed=0)
    s.fit(shards, epochs=1, steps_per_epoch=2)  # compile outside the capture
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            s.fit(shards, epochs=3, steps_per_epoch=2)
    spans = host_spans(str(tmp_path))
    assert spans["repro.fit.upload"] == 2
    assert spans["repro.fit.dispatch"] == 6
    assert spans["repro.fit.readout"] == 6


def test_serve_spans_release_per_request_batch_per_batch(tmp_path, shards):
    s = SplitSession(cnn_adapter(SMALL_CNN), GUARDED, adamw(1e-3), seed=0)
    trace = poisson_trace(3, rate=2.5, horizon=6, seed=3)
    s.serve(trace, shards, max_batch=4)  # compile outside the capture
    with jax.profiler.trace(str(tmp_path)):
        rep = s.serve(trace, shards, max_batch=4)
    spans = host_spans(str(tmp_path))
    assert rep.offered > 0 and rep.batches > 0
    assert spans["repro.serve.release"] == rep.offered
    assert spans["repro.serve.batch"] == rep.batches


# --------------------------------------------------------------- counters
def test_queue_wait_per_answered_request_within_latency(shards):
    s = SplitSession(cnn_adapter(SMALL_CNN), GUARDED, adamw(1e-3), seed=0)
    trace = poisson_trace(3, rate=3.0, horizon=8, seed=4)
    a = s.serve(trace, shards, max_batch=2, queue_size=8)
    b = s.serve(trace, shards, max_batch=2, queue_size=8)
    assert a.answered > 0 and a.dropped > 0  # a tight queue drops some
    assert sorted(a.queue_wait_ms) == sorted(a.latency_ms)
    assert len(a.queue_wait_ms) == a.answered
    for rid, wait in a.queue_wait_ms.items():
        assert 0.0 <= wait <= a.latency_ms[rid]
    # a backlog waits across cycles: the later pops wait longer
    assert max(a.queue_wait_ms.values()) > min(a.queue_wait_ms.values())
    # wall-clock stamps stay out of the replay evidence
    assert a.queue_wait_ms != b.queue_wait_ms
    assert a.fingerprint() == b.fingerprint()
    assert "queue_wait_ms" not in a.deterministic_stats()
    assert np.isfinite(list(a.queue_wait_ms.values())).all()
