"""Operation and byte counts computed from shapes, kept with the benchmark.

``<name>.py`` counts one model family's model FLOPs per sample in its
``model_flops(model)``, found by the name that a configuration file gives
under ``"flops"`` (``harness.model_flops``; ``cnn.py``: the paper's split
CNNs); ``kernels/<name>.py`` holds one kernel's operations and bytes per
call, found by the kernel's name."""
from __future__ import annotations

import importlib.util
from pathlib import Path

KERNELS = Path(__file__).resolve().parent / "kernels"


def kernel_cost(name: str, **shape) -> dict:
    """``{"flops": ..., "bytes": ...}`` of one call of kernel ``name``, from
    ``kernels/<name>.py``'s ``cost(**shape)``."""
    path = KERNELS / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no operation count for kernel {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_kernel_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.cost(**shape)
