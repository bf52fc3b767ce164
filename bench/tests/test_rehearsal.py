"""Each runner end to end on the CPU at tiny sizes, with the look for a
chip skipped: files found by name, the window, the trace reduction, the
comparison with the reference, and the shape of the last line."""
import pytest

from conftest import run_cell

CELLS = ["tiny-covid-train", "tiny-covid-serve", "tiny-vgg-train", "tiny-vgg-serve"]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_is_correct_and_reports_its_metrics(checkout, capsys, workload):
    res = run_cell(capsys, workload)
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device",
                         "call_seconds", "checks"]
    assert res["call_seconds"] and all(s > 0 for s in res["call_seconds"])
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    kind = "train_samples_per_s" if "train" in workload else "serve_requests_per_s"
    assert set(res["metrics"]) == {kind, "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1


@pytest.mark.parametrize("workload", ["tiny-covid-train", "tiny-covid-serve"])
def test_traced_run_reports_the_per_layer_metrics(checkout, capsys, workload):
    res = run_cell(capsys, workload, trace=1)
    assert res["correct"] is True, res["checks"]
    assert list(res)[-3:] == ["breakdown", "call_seconds", "checks"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    # on the CPU no TPU plane exists: only the readers that need none answer
    # (the program's host spans and counters are there; its scopes are not)
    names = set(res["metrics"])
    if "train" in workload:
        assert names == {"device_idle_share.train", "train_mfu",
                         "train.upload_idle_ms_per_call"}
    else:
        assert names == {"device_idle_share.serve", "serve.dispatches_per_request",
                         "serve.latency_p95_ms", "serve.release_host_ms_per_request",
                         "serve.batch_host_ms_per_batch", "serve.release_idle_share",
                         "serve.queue_wait_p95_ms"}


def test_a_new_cell_and_metric_are_found_by_their_files(checkout, capsys):
    """A cell and a per-layer metric added as files only, with no edit to
    an existing file of the benchmark, are run and read."""
    checkout.write("bench/metrics/dummy.calls.py",
                   "def read(ctx):\n    return float(ctx.totals['steps'])\n")
    checkout.write("bench/traffic/tiny-train-s2.json",
                   {"runner": "train", "steps_per_epoch": 2, "epochs_per_call": 1,
                    "traced_calls": 2})
    checkout.bench["per_layer"].append(
        {"name": "dummy.calls", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "host drive",
         "moves": "train_samples_per_s", "workloads": ["tiny-new"]})
    from conftest import TRAIN_LIMITS, tiny_config
    checkout.add_cell("tiny-new",
                      tiny_config("tiny-covid", "tiny-covid", "covid_ct", 3,
                                  [0.7, 0.2, 0.1], 6),
                      "tiny-train-s2", TRAIN_LIMITS)
    res = run_cell(capsys, "tiny-new", trace=1)
    assert res["metrics"]["dummy.calls"] == {"value": 4.0, "unit": "count"}
    res = run_cell(capsys, "tiny-new", trace=0)
    assert res["correct"] is True and "train_samples_per_s" in res["metrics"]


# A model family the benchmark has never run, brought as files alone: a
# runner, a FLOP counter, a layer map, a configuration whose model has none
# of the CNN's keys, and metrics that read the program's spans, the
# configuration's FLOPs and the layer map.
STUB_RUNNER = '''"""A stub family: a jitted square matrix's tanh(x @ x), one call a
unit, inside the program span ``repro.stub.step``."""
import jax
import jax.numpy as jnp
import numpy as np


class Run:
    def __init__(self, cfg, traffic, seed):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed

    def setup(self):
        w = self.cfg["model"]["width"]
        self.x = np.random.default_rng(self.seed).standard_normal((w, w), np.float32)
        self.f = jax.jit(lambda x: jnp.tanh(x @ x))  # a new program every run
        self.y = np.asarray(self.f(self.x))

    def unit(self):
        with jax.profiler.TraceAnnotation("repro.stub.step"):
            self.y = np.asarray(self.f(self.x))
        return {"steps": 1}

    def traced_units(self):
        return self.traffic["traced_calls"]

    def counters(self):
        return {}

    def end_to_end(self, totals):
        return {"stub_steps_per_s": totals["steps"] / totals["seconds"]}

    def outcome(self, totals):
        return int(totals["steps"]), 0

    def release(self):
        self.f = None

    def check(self):
        x = self.x.astype(np.float64)
        return {"gap": float(np.max(np.abs(self.y - np.tanh(x @ x))))}
'''
STUB_FILES = {
    "bench/runners/stub.py": STUB_RUNNER,
    "bench/flops/stub.py": "def model_flops(model):\n"
                           "    return {'train': 2 * model['width'] ** 3}\n",
    "bench/layers/stub.json": {"stub_program": "stub layer"},
    "bench/traffic/tiny-stub.json": {"runner": "stub", "traced_calls": 3},
    "bench/metrics/stub.step_host_ms.py": (
        "def read(ctx):\n"
        "    sp = ctx.layer_times.spans.get('repro.stub.step')\n"
        "    return 1e3 * sp['seconds'] / sp['count'] if sp else None\n"),
    "bench/metrics/stub.step_spans.py": (
        "def read(ctx):\n"
        "    return float(ctx.layer_times.spans['repro.stub.step']['count'])\n"),
    "bench/metrics/stub.flops_traced.py": (
        "def read(ctx):\n"
        "    return float(ctx.flops['train'] * ctx.totals['steps'])\n"),
    "bench/metrics/stub.layer_mapped.py": (
        "def read(ctx):\n"
        "    return float(ctx.layers.get('stub_program') == 'stub layer')\n"),
}
STUB_CONFIG = {"name": "tiny-stub", "source": "test", "flops": "stub",
               "model": {"layers": 2, "width": 8}}


def add_stub_family(checkout, flops="stub"):
    for rel, body in STUB_FILES.items():
        checkout.write(rel, body)
    checkout.bench["end_to_end"].append(
        {"name": "stub_steps_per_s", "unit": "steps/s", "better": "higher",
         "bound": 0.05, "source": "host_clock", "workloads": ["tiny-stub"]})
    for name in ("stub.step_host_ms", "stub.step_spans", "stub.flops_traced",
                 "stub.layer_mapped"):
        checkout.bench["per_layer"].append(
            {"name": name, "unit": "count", "better": "lower",
             "source": "program_span", "layer": "stub layer",
             "moves": "stub_steps_per_s", "workloads": ["tiny-stub"]})
    checkout.add_cell("tiny-stub", {**STUB_CONFIG, "flops": flops}, "tiny-stub",
                      {"gap": 1e-4})


def test_a_new_model_family_is_found_by_its_files(checkout, capsys):
    """A configuration of another family, with its runner, FLOP counter,
    layer map and metrics, runs traced and untraced with no edit to an
    existing file of the benchmark."""
    add_stub_family(checkout)
    res = run_cell(capsys, "tiny-stub", trace=1)
    assert res["correct"] is True, res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # no device plane on the CPU: of the shared metrics none answers here
    assert set(m) == {"stub.step_host_ms", "stub.step_spans", "stub.flops_traced",
                      "stub.layer_mapped"}
    assert m["stub.step_spans"] == 3.0  # the traffic's three traced calls
    assert m["stub.step_host_ms"] > 0
    assert m["stub.flops_traced"] == 3.0 * 2 * 8 ** 3
    assert m["stub.layer_mapped"] == 1.0
    res = run_cell(capsys, "tiny-stub", trace=0)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"stub_steps_per_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("flops", ["no_such_counter", None])
def test_a_configuration_without_its_counter_exits_before_set_up(checkout, flops):
    """An unknown counter, or none named: the run exits non-zero before
    set-up (before even its look for a chip), with no result line, naming
    the file it looked for."""
    add_stub_family(checkout, flops=flops)
    p = _subprocess(checkout.root, "--workload", "tiny-stub", "--seed", "3",
                    "--seconds", "1", "--trace", "1")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert str(checkout.bench_dir.resolve() / "flops") in p.stderr
    assert "no TPU" not in p.stderr


def test_the_traced_run_keeps_a_cache_of_its_own_build(checkout, capsys, tmp_path):
    """With a shared cache directory set, as ``JAX_COMPILATION_CACHE_DIR``
    sets it, the traced run neither writes nor reads an entry there: it
    keeps the persistent cache on, in a directory of the checkout named by
    the code, and a build that differs in a named scope alone gets another
    directory, so its scope metrics read the programs of its own build. The
    untraced run writes its programs to the shared directory."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    import harness

    add_stub_family(checkout)
    shared = tmp_path / "jax-cache"
    shared.mkdir()
    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache")
    before = {k: getattr(jax.config, k) for k in keys}
    try:
        for k, v in zip(keys, (str(shared), 0, 0, True)):
            jax.config.update(k, v)
        own = harness.traced_cache_dir()
        assert own.parent == checkout.root / harness.TRACED_CACHE
        cc.reset_cache()
        run_cell(capsys, "tiny-stub", trace=1)
        assert jax.config.jax_enable_compilation_cache is True
        assert list(shared.iterdir()) == []
        assert jax.config.jax_compilation_cache_dir == str(own)
        assert list(own.iterdir())
        assert harness.traced_cache_dir() == own  # the same build, the same directory
        runner = checkout.bench_dir / "runners" / "stub.py"
        runner.write_text(runner.read_text().replace(
            "self.f = jax.jit(lambda x: jnp.tanh(x @ x))",
            "self.f = jax.jit(jax.named_scope('stub_scope')(lambda x: jnp.tanh(x @ x)))"))
        assert harness.traced_cache_dir() != own
        cc.reset_cache()
        jax.config.update("jax_compilation_cache_dir", str(shared))
        run_cell(capsys, "tiny-stub", trace=0)
        assert list(shared.iterdir())
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_a_runner_layer_map_adds_names_and_redefines_none(checkout):
    import harness

    base = harness.read_json(checkout.bench_dir / "layers.json")
    assert harness.layer_map("stub") == base  # no file for the runner
    checkout.write("bench/layers/stub.json", {"stub_program": "stub layer"})
    assert harness.layer_map("stub") == {**base, "stub_program": "stub layer"}
    checkout.write("bench/layers/stub.json", {"forward": base["forward"]})
    assert harness.layer_map("stub") == base  # the same name, the same layer
    checkout.write("bench/layers/stub.json", {"forward": "stub layer"})
    with pytest.raises(SystemExit, match="forward"):
        harness.layer_map("stub")


def _subprocess(cwd, *args):
    import os
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_non_zero_with_no_result():
    from conftest import ROOT

    p = _subprocess(ROOT, "--workload", "covid-serve", "--seed", "3",
                    "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_non_zero(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files:
    the program is missing, so the run fails before any result."""
    import shutil

    from conftest import BENCH, ROOT

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _subprocess(tmp_path, "--workload", "covid-serve", "--seed", "3",
                    "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
