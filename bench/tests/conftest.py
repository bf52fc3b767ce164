"""Fixtures for the benchmark's own tests: a copy of the benchmark under a
temporary checkout, with tiny configurations and cells added as files, run
on the CPU with the look for a chip skipped."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODELS = {
    # one conv in the client stage (COVID's shape)
    "tiny-covid": {"input_hw": [16, 16], "in_channels": 1,
                   "stages": [[4, 1], [8, 1]], "dense_units": [8],
                   "n_classes": 1, "cut_layers": 1, "privacy_noise": 0.05,
                   "loss": "bce"},
    # two convs in the client stage (VGG's shape)
    "tiny-vgg": {"input_hw": [16, 16], "in_channels": 1,
                 "stages": [[4, 2], [8, 2], [8, 1]], "dense_units": [16, 16],
                 "n_classes": 1, "cut_layers": 1, "privacy_noise": 0.05,
                 "loss": "bce"},
}
# tiny serving cells compare every answered request, so that a fault in a
# single answer is met however many calls the short window makes
TINY_TRAFFIC = {
    "tiny-train": {"runner": "train", "steps_per_epoch": 3, "epochs_per_call": 2,
                   "traced_calls": 1},
    "tiny-serve": {"runner": "serve", "rate": 3.0, "counts_seed": 1,
                   "cycles_per_call": 6, "request_batch": 1, "max_batch": 4,
                   "queue_size": 16, "per_client_cap": None, "max_wait": None,
                   "traced_calls": 1, "checked_per_call": 1000,
                   "checked_requests": 100000},
}
TRAIN_LIMITS = {"change_median_vs_bf16": 0.05}
SERVE_LIMITS = {"answer_vs_bf16": 0.05, "answer_rms_vs_bf16": 0.05, "ledger": 0}


def tiny_config(name: str, model: str, dataset: str, hospitals: int, shares,
                batch: int) -> dict:
    return {"name": name, "source": "test", "flops": "cnn",
            "dataset": dataset, "model": TINY_MODELS[model],
            "hospitals": hospitals, "shares": shares, "server_batch": batch,
            "mode": "detached", "precision": "float32",
            "guard": {"clip_norm": 32.0, "noise_scale": 0.05},
            "optimizer": {"name": "adamw", "lr": 1e-3, "b1": 0.9, "b2": 0.95,
                          "eps": 1e-8, "weight_decay": 0.0, "grad_clip": 1.0},
            "dataset_size": 30, "reduced": [], "assumed": {}}


class Checkout:
    """A temporary checkout holding a copy of ``bench/``, a BENCHMARK.json
    of tiny cells and the harness pointed at it."""

    def __init__(self, root: Path):
        self.root = root
        self.bench_dir = root / "bench"
        shutil.copytree(BENCH, self.bench_dir,
                        ignore=shutil.ignore_patterns("__pycache__", "tests", "testdata"))
        peaks = json.loads((self.bench_dir / "peaks.json").read_text())
        peaks["cpu"] = {**peaks["TPU v5 lite"], "source": "test"}
        (self.bench_dir / "peaks.json").write_text(json.dumps(peaks))
        real = json.loads((ROOT / "BENCHMARK.json").read_text())
        self.bench = {"command": real["command"], "paths": real["paths"],
                      "run_seconds": 1, "configs": [], "workloads": [],
                      "end_to_end": real["end_to_end"], "per_layer": real["per_layer"]}
        # each metric of a training or serving cell goes to the tiny cells
        # of the same runner
        runners = {w["name"]: self._runner(real, w) for w in real["workloads"]}
        for m in self.bench["end_to_end"] + self.bench["per_layer"]:
            if "workloads" in m:
                m["runner"] = {runners[w] for w in m.pop("workloads")}.pop()
                m["workloads"] = []
        for name, traffic in TINY_TRAFFIC.items():
            self.write(f"bench/traffic/{name}.json", traffic)

    @staticmethod
    def _runner(bench: dict, cell: dict) -> str:
        return json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())["runner"]

    def write(self, rel: str, obj) -> Path:
        path = self.root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(obj if isinstance(obj, str) else json.dumps(obj))
        return path

    def add_cell(self, name: str, config: dict, traffic: str, limits: dict) -> None:
        if not any(c["name"] == config["name"] for c in self.bench["configs"]):
            self.write(f"bench/configs/{config['name']}.json", config)
            self.bench["configs"].append(
                {"name": config["name"], "source": "test",
                 "file": f"bench/configs/{config['name']}.json", "reduced": [],
                 "why": "test"})
        self.bench["workloads"].append({"name": name, "config": config["name"],
                                        "traffic": traffic, "chips": 1, "why": "test"})
        runner = json.loads((self.bench_dir / "traffic" / f"{traffic}.json")
                            .read_text())["runner"]
        for m in self.bench["end_to_end"] + self.bench["per_layer"]:
            if "runner" in m and m["runner"] == runner:
                m["workloads"].append(name)
        self.write(f"bench/limits/{name}.json", limits)
        out = {**self.bench,
               "end_to_end": [{k: v for k, v in m.items() if k != "runner"}
                              for m in self.bench["end_to_end"]],
               "per_layer": [{k: v for k, v in m.items() if k != "runner"}
                             for m in self.bench["per_layer"]]}
        self.write("BENCHMARK.json", out)


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """The tiny cells' checkout. A traced run points JAX's persistent
    compilation cache at the checkout; it is pointed back after."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    import harness

    cache_dir = jax.config.jax_compilation_cache_dir

    co = Checkout(tmp_path)
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "BENCH", co.bench_dir)
    import repro.launch.compile_cache as cc

    monkeypatch.setattr(cc, "configure_compile_cache", lambda: None)
    monkeypatch.setattr(harness, "require_chips",
                        lambda chips: {"platform": "cpu", "kind": "cpu", "count": 1})
    co.add_cell("tiny-covid-train",
                tiny_config("tiny-covid", "tiny-covid", "covid_ct", 3, [0.7, 0.2, 0.1], 6),
                "tiny-train", TRAIN_LIMITS)
    co.add_cell("tiny-covid-serve",
                tiny_config("tiny-covid", "tiny-covid", "covid_ct", 3, [0.7, 0.2, 0.1], 6),
                "tiny-serve", SERVE_LIMITS)
    co.add_cell("tiny-vgg-train",
                tiny_config("tiny-vgg", "tiny-vgg", "mura_xray", 4, [0.4, 0.3, 0.2, 0.1], 8),
                "tiny-train", TRAIN_LIMITS)
    co.add_cell("tiny-vgg-serve",
                tiny_config("tiny-vgg", "tiny-vgg", "mura_xray", 4, [0.4, 0.3, 0.2, 0.1], 8),
                "tiny-serve", SERVE_LIMITS)
    yield co
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    compilation_cache.reset_cache()


def run_cell(capsys, workload: str, seed: int = 2**31 + 17, trace: int = 0,
             seconds: float = 0.2) -> dict:
    """Drive ``run.main`` for a cell; its last standard-output line."""
    import run

    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])
