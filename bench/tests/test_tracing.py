"""The reduction from a trace to the per-layer metrics' inputs.

``testdata/covid_train_epoch.json`` is an excerpt of a trace recorded on a
TPU v5 lite (its ``note`` says how it was cut); the expected numbers were
worked out apart from the reduction, with a per-nanosecond mask of the
device operations and the module events read one by one."""
import pytest

import harness
import tracing

WINDOW = 22_000_000  # ns


@pytest.fixture(scope="module")
def recorded():
    return tracing.load_json(str(harness.BENCH / "testdata" / "covid_train_epoch.json"))


def test_idle_share_of_the_recorded_trace(recorded):
    r = tracing.reduce(recorded)
    assert r.window_s == pytest.approx(WINDOW * 1e-9, abs=1e-15)
    assert r.busy_s == pytest.approx(14_941_258e-9, abs=1e-15)
    assert r.idle_share == pytest.approx(0.3208519090909091, abs=1e-12)


def test_programs_of_the_recorded_trace(recorded):
    r = tracing.reduce(recorded)
    assert {k: v["count"] for k, v in r.programs.items()} == {
        "convert_element_type": 2, "_threefry_fold_in": 2, "sample_plan": 2,
        "step_noise": 2, "_run_epoch_scan": 1}
    assert r.executions() == 9
    device = {k: round(v["device_s"] * 1e9) for k, v in r.programs.items()}
    # the second noise draw runs past the window's end and is clipped
    assert device == {"convert_element_type": 1186, "_threefry_fold_in": 9958,
                      "sample_plan": 30555, "step_noise": 3_955_777,
                      "_run_epoch_scan": 10_964_605}
    gaps = {k: [round(g * 1e9) for g in v] for k, v in r.program_gaps_s.items()}
    assert gaps == {"convert_element_type": [17_315_097],
                    "_threefry_fold_in": [17_314_058],
                    "sample_plan": [17_469_207], "step_noise": [15_302_452]}


def test_breakdown_of_the_recorded_trace(recorded):
    r = tracing.reduce(recorded)
    assert r.device_ops[0][0].startswith("%while")
    assert len(r.device_ops) <= 10 and len(r.idle_gaps) <= 10
    assert all(s > 0 for _, s in r.idle_gaps)
    # the gaps and the busy time cover the window
    assert sum(s for _, s in r.idle_gaps) <= r.window_s - r.busy_s + 1e-12


def test_hand_made_events():
    ev = tracing.Events(
        ops=[("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 45, 60)],
        modules=[("jit_f(1)", 0, 20), ("jit_f(1)", 30, 40), ("jit_g(2)", 45, 60)],
        host=[("bench.window", 0, 50), ("drive", 20, 30)])
    r = tracing.reduce(ev)
    assert r.window_s == pytest.approx(50e-9)
    assert r.busy_s == pytest.approx((20 + 10 + 5) * 1e-9)
    assert r.programs["f"] == {"count": 2, "device_s": pytest.approx(30e-9)}
    assert r.programs["g"]["device_s"] == pytest.approx(5e-9)
    assert r.program_gaps_s == {"f": [pytest.approx(10e-9)]}
    assert r.idle_gaps[0] == ("drive", pytest.approx(10e-9))


def test_capture_writes_a_trace_with_the_window_span(tmp_path):
    import jax.numpy as jnp

    out, path = tracing.capture(lambda: float(jnp.arange(4.0).sum()), str(tmp_path))
    assert out == 6.0
    ev = tracing.load(path)
    lo, hi = tracing.window_of(ev)
    assert hi > lo
