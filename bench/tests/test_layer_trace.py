"""The reduction of the program's own tracing: device self time per named
scope, and the ``repro.*`` host spans, on hand-made events; the span part
also on a capture of a tiny ``fit`` on the CPU."""
import dataclasses

import pytest

import layer_trace as lt_mod
import tracing


def events(ops, scopes, host, modules=()):
    return lt_mod.ScopedEvents(ops=list(ops), modules=list(modules), host=list(host),
                               op_scopes=list(scopes))


def test_self_time_of_a_wrapper_around_nested_ops():
    # a %while of 100 ns holds two body ops (20 + 30 ns), one of which holds
    # a third (5 ns); an op after the loop stands alone
    ops = [("%while", 0, 100), ("a", 10, 30), ("b", 40, 70), ("c", 50, 55),
           ("d", 120, 130)]
    assert lt_mod.self_times(ops, 0, 200) == [50, 20, 25, 5, 10]
    # clipped to the window first: the wrapper keeps only its part inside
    assert lt_mod.self_times(ops, 45, 125) == [30, 0, 20, 5, 5]


def test_self_times_cover_the_busy_time_once():
    ops = [("%while", 0, 100), ("a", 10, 30), ("b", 40, 70), ("c", 50, 55)]
    assert sum(lt_mod.self_times(ops, 0, 100)) == 100


def test_scopes_and_their_backward():
    ops = [("%while", 0, 1000), ("f1", 0, 100), ("f2", 100, 150), ("b1", 150, 400),
           ("g", 400, 450), ("p", 450, 500), ("o", 500, 560), ("x", 560, 600)]
    scopes = [
        "jit(_run_epoch_scan)/while",
        "jit(_run_epoch_scan)/while/body/closed_call/jvp(trunk)/conv_general_dilated",
        "jit(_run_epoch_scan)/while/body/closed_call/jvp(trunk)/reduce_sum",
        "jit(_run_epoch_scan)/while/body/closed_call/transpose(jvp(trunk))/conv",
        "jit(_run_epoch_scan)/while/body/closed_call/vmap(guard)/mul",
        "jit(_run_epoch_scan)/while/body/closed_call/vmap(privacy_layer)/dot_general",
        "jit(_run_epoch_scan)/while/body/closed_call/optimizer/sqrt",
        "jit(_run_epoch_scan)/while/body/dynamic_slice",
    ]
    host = [("bench.window", 0, 1000), ("bench.fit", 0, 1000)]
    lt = lt_mod.layer_times(events(ops, scopes, host))
    r = lt_mod.readings(lt, {"steps": 2}, {})
    ns = 1e-6  # ns in ms
    assert r["train.trunk_fwd_device_ms_per_step"] == pytest.approx(150 * ns / 2)
    assert r["train.trunk_bwd_device_ms_per_step"] == pytest.approx(250 * ns / 2)
    assert r["train.guard_device_ms_per_step"] == pytest.approx(50 * ns / 2)
    assert r["train.privacy_layer_device_ms_per_step"] == pytest.approx(50 * ns / 2)
    assert r["train.optimizer_device_ms_per_step"] == pytest.approx(60 * ns / 2)
    # the loop's own time (1000 less its body's 600 ns) and the slice are
    # under no scope
    assert lt.scope_s["jit(_run_epoch_scan)/while"] == pytest.approx(400e-9)
    # a serving-only metric reads nothing here
    assert r["serve.release_host_ms_per_request"] is None
    assert r["serve.queue_wait_p95_ms"] is None


def test_the_privacy_layers_native_convolution():
    # the privacy layer's first convolution runs as taps (20 ns), its second
    # natively under scope ``conv`` (60 ns), then relu and pool (20 ns); the
    # trunk's convolution primitive is named ``conv_general_dilated`` and is
    # under no ``conv`` scope
    body = "jit(_run_epoch_scan)/while/body/closed_call"
    ops = [("t", 0, 20), ("c", 20, 80), ("r", 80, 100), ("k", 100, 300)]
    scopes = [f"{body}/vmap(privacy_layer)/conv_taps/mul",
              f"{body}/vmap(privacy_layer)/conv/conv_general_dilated",
              f"{body}/vmap(privacy_layer)/max",
              f"{body}/jvp(trunk)/conv_general_dilated"]
    lt = lt_mod.layer_times(events(ops, scopes, [("bench.window", 0, 300)]))
    r = lt_mod.readings(lt, {"steps": 2}, {})
    ns = 1e-6  # ns in ms
    assert r["train.conv_device_ms_per_step"] == pytest.approx(60 * ns / 2)
    assert r["train.privacy_layer_device_ms_per_step"] == pytest.approx(100 * ns / 2)
    assert r["train.trunk_fwd_device_ms_per_step"] == pytest.approx(200 * ns / 2)
    # no steps counted (a serving cell): no per-step reading
    assert lt_mod.readings(lt, {}, {})["train.conv_device_ms_per_step"] is None


def test_scope_matching():
    assert lt_mod.under("jit(f)/while/body/vmap(privacy_layer)/mul", "privacy_layer")
    assert lt_mod.under("jit(f)/transpose(jvp(trunk))/conv", "trunk")
    assert not lt_mod.under("jit(f)/trunk_extra/conv", "trunk")
    assert not lt_mod.under("jit(f)/guarded/mul", "guard")
    assert lt_mod.backward_of("jit(f)/transpose(jvp(trunk))/conv", "trunk")
    assert not lt_mod.backward_of("jit(f)/jvp(trunk)/conv", "trunk")
    assert not lt_mod.backward_of("jit(f)/transpose(jvp(optimizer))/x", "trunk")


def test_idle_time_inside_a_span_that_crosses_the_window_edge():
    # window 100..300; device busy 150-200 and 250-320
    ops = [("a", 150, 200), ("b", 250, 320)]
    host = [("bench.window", 100, 300),
            ("repro.fit.upload", 50, 180),    # crosses the start: 100-180 counts
            ("repro.fit.readout", 240, 400),  # crosses the end: 240-300 counts
            ("repro.fit.readout", 400, 450),  # outside: not counted
            ("other", 100, 300)]
    lt = lt_mod.layer_times(events(ops, ["", ""], host))
    up, ro = lt.spans["repro.fit.upload"], lt.spans["repro.fit.readout"]
    assert (up["count"], ro["count"]) == (1, 1)
    assert up["seconds"] == pytest.approx(80e-9)
    assert up["idle_s"] == pytest.approx(50e-9)   # 100-150
    assert ro["seconds"] == pytest.approx(60e-9)
    assert ro["idle_s"] == pytest.approx(10e-9)   # 240-250
    assert "other" not in lt.spans
    assert lt.idle_s == pytest.approx(200e-9 - 100e-9)
    r = lt_mod.readings(lt, {}, {})
    assert r["train.upload_idle_ms_per_call"] == pytest.approx(50e-6)
    # no scope paths in the trace: the scope metrics read nothing
    assert r["train.trunk_fwd_device_ms_per_step"] is None


def test_span_idle_time_over_many_ops():
    # overlapping device ops and spans, some across the window's edges: each
    # span's idle time is its length in the window less the busy time in it,
    # summed op by op
    import random

    rnd = random.Random(7)
    ops = [("op", s, s + rnd.randrange(1, 900))
           for s in sorted(rnd.randrange(-2000, 102_000) for _ in range(500))]
    spans = [("repro.x", s, s + rnd.randrange(1, 4000))
             for s in (rnd.randrange(-3000, 101_000) for _ in range(300))]
    lt = lt_mod.layer_times(events(ops, [""] * len(ops), [("bench.window", 0, 100_000)] + spans))
    busy = tracing._union([c for _, s, e in ops if (c := tracing._clip(s, e, 0, 100_000))])
    idle, count = 0.0, 0
    for _, s, e in spans:
        if c := tracing._clip(s, e, 0, 100_000):
            covered = sum(max(0, min(e2, c[1]) - max(s2, c[0])) for s2, e2 in busy)
            idle += (c[1] - c[0] - covered) * 1e-9
            count += 1
    assert lt.spans["repro.x"]["count"] == count > 250
    assert lt.spans["repro.x"]["idle_s"] == idle


def test_serving_readings():
    ops = [("f", 40, 50), ("f", 140, 150)]
    host = [("bench.window", 0, 200),
            ("repro.serve.release", 0, 20), ("repro.serve.release", 20, 40),
            ("repro.serve.batch", 40, 60),
            ("repro.serve.release", 100, 140), ("repro.serve.batch", 140, 160)]
    lt = lt_mod.layer_times(events(ops, ["", ""], host))
    r = lt_mod.readings(lt, {}, {"queue_wait_ms": [float(i) for i in range(1, 101)]})
    assert r["serve.release_host_ms_per_request"] == pytest.approx(80e-6 / 3)
    assert r["serve.batch_host_ms_per_batch"] == pytest.approx(20e-6)
    # idle inside the releases 80 ns of the window's 180 ns idle
    assert r["serve.release_idle_share"] == pytest.approx(100 * 80 / 180)
    assert r["serve.queue_wait_p95_ms"] == pytest.approx(95.05)


def test_ops_take_the_scope_of_their_instruction_in_the_running_program():
    ev = tracing.Events(
        ops=[("%fusion.4 = f32[8]{0} fusion(...)", 10, 20), ("%fusion.4", 110, 120),
             ("%copy.1", 130, 140), ("%fusion.4", 300, 310)],
        modules=[("jit__run_epoch_scan(11)", 0, 100), ("jit_forward(12)", 100, 200)],
        host=[("bench.window", 0, 400)])
    programs = {"jit__run_epoch_scan(11)": {"fusion.4": "jit(_run_epoch_scan)/trunk/mul"},
                "jit_forward(12)": {"fusion.4": "jit(forward)/trunk/add"}}
    got = lt_mod.with_scopes(ev, programs)
    # by program and instruction; an instruction the program does not name,
    # and an op outside every program, have no path
    assert got.op_scopes == ["jit(_run_epoch_scan)/trunk/mul", "jit(forward)/trunk/add", "", ""]
    assert got.ops == ev.ops and got.host == ev.host


def test_the_trace_holds_each_programs_op_names(tmp_path):
    """A CPU capture: the metadata plane's optimized HLO gives each op the
    scope it was traced under, keyed by the program's name in the trace."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("trunk"):
            return jnp.tanh(x @ x)

    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    _, path = tracing.capture(lambda: f(x).block_until_ready(), str(tmp_path))
    with open(path, "rb") as fh:
        programs = lt_mod.hlo_op_names(fh.read())
    ops = next(v for k, v in programs.items() if k.startswith("jit_f("))
    # the ops that ran on the CPU, as its host line names them
    ran = {dict(e.stats).get("hlo_op") for pl in jax.profiler.ProfileData.from_file(path).planes
           for line in pl.lines for e in line.events
           if dict(e.stats).get("hlo_module") == "jit_f"}
    ran.discard(None)
    assert ran and all(lt_mod.under(ops[op], "trunk") for op in ran)


def test_fit_spans_in_a_cpu_capture(tmp_path):
    """Host spans of a tiny ``fit``, captured on the CPU and read back."""
    import jax

    from repro.configs.paper_models import COVID_CNN
    from repro.core import SplitSession, SplitTrainConfig
    from repro.core.adapters import cnn_adapter
    from repro.data import make_covid_ct, split_clients
    from repro.optim import adamw

    cfg = dataclasses.replace(COVID_CNN, input_hw=(16, 16), stages=((4, 1), (8, 1)),
                              dense_units=(8,))
    x, y = make_covid_ct(90, hw=16, seed=0)
    shards = split_clients(x, y)
    s = SplitSession(cnn_adapter(cfg), SplitTrainConfig(server_batch=12), adamw(1e-3),
                     engine="fused-scan", seed=0)
    s.fit(shards, epochs=1, steps_per_epoch=2)
    _, path = tracing.capture(lambda: s.fit(shards, epochs=2, steps_per_epoch=2),
                              str(tmp_path))
    lt = lt_mod.layer_times(lt_mod.load(path))
    assert {k: v["count"] for k, v in lt.spans.items()} == {
        "repro.fit.upload": 1, "repro.fit.dispatch": 2, "repro.fit.readout": 2}
    assert all(v["seconds"] > 0 for v in lt.spans.values())
    assert jax.devices()[0].platform == "cpu"


@pytest.mark.parametrize("workload,runner", [("tiny-covid-train", "train"),
                                             ("tiny-covid-serve", "serve")])
def test_probe_runs_a_tiny_cell(checkout, capsys, workload, runner):
    """The script end to end on the CPU: the spans it counts match the
    traced calls' own counts (no device plane here, so no scope reads)."""
    import json

    assert lt_mod.main(["--workload", workload, "--seed", str(2**31 + 5),
                        "--seconds", "0.2"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    spans = {k: v["count"] for k, v in out["spans"].items()}
    r = out["readings"]
    if runner == "train":
        assert spans["repro.fit.upload"] == 1  # the traffic's one traced call
        assert spans["repro.fit.readout"] == 2  # of two epochs
        assert r["train.upload_idle_ms_per_call"] is not None
    else:
        assert spans["repro.serve.release"] == out["totals"]["offered"]
        assert spans["repro.serve.batch"] == out["totals"]["batches"]
        assert r["serve.queue_wait_p95_ms"] is not None
        assert r["serve.release_host_ms_per_request"] > 0
    assert r["train.trunk_fwd_device_ms_per_step"] is None
    # each reading of the cell's runner is its per-layer metric, read alike
    own = {k: v for k, v in r.items() if k.startswith(runner + ".")}
    assert own and {k: out["metrics"][k] for k in own} == own
