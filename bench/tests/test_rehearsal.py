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
    names = set(res["metrics"])
    if "train" in workload:
        assert names == {"device_idle_share.train", "train_mfu"}
    else:
        assert names == {"device_idle_share.serve", "serve.dispatches_per_request",
                         "serve.latency_p95_ms"}


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
