"""``calibrate.py`` on the CPU with the TPU's default matmul precision
imitated; a witness for limit readings, not part of a benchmark run.

  JAX_PLATFORMS=cpu python3 bench/emulate.py --workload <cell> --seeds 1 2 ...

XLA:CPU computes float32 convolutions and matmuls exactly at any precision
setting. The TPU, at default precision, rounds their operands to bfloat16
and accumulates in float32, except for a matmul whose contraction has one
element, which XLA compiles to an exact float32 multiply. This script
rounds the same way in every convolution and matmul that JAX traces, the
program's and the reference's alike, skips the look for a chip, and runs
``calibrate.main`` with the given arguments. Only cells small enough for the
CPU can be read so.
"""
from __future__ import annotations

import math
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax._src.lax import convolution as _convolution  # noqa: E402
from jax._src.lax import lax as _lax  # noqa: E402

_dot_general = _lax.dot_general
_conv_general_dilated = _convolution.conv_general_dilated
_einsum = jnp.einsum


def _bf16(x):
    if getattr(x, "dtype", None) == jnp.float32:
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def dot_general(lhs, rhs, dimension_numbers, *args, **kwargs):
    (contract, _), _ = dimension_numbers
    if math.prod(lhs.shape[i] for i in contract) > 1:
        lhs, rhs = _bf16(lhs), _bf16(rhs)
    return _dot_general(lhs, rhs, dimension_numbers, *args, **kwargs)


def conv_general_dilated(lhs, rhs, *args, **kwargs):
    return _conv_general_dilated(_bf16(lhs), _bf16(rhs), *args, **kwargs)


def einsum(*args, **kwargs):
    kwargs.setdefault("_dot_general", dot_general)
    return _einsum(*args, **kwargs)


def install() -> None:
    """Route every later trace through the rounding versions."""
    _lax.dot_general = jax.lax.dot_general = dot_general
    _convolution.conv_general_dilated = jax.lax.conv_general_dilated = conv_general_dilated
    jnp.einsum = einsum


def main(argv=None) -> int:
    install()
    import harness
    import repro.launch.compile_cache as compile_cache

    harness.require_chips = lambda chips: {"platform": "cpu", "kind": "cpu (TPU precision imitated)",
                                           "count": 1}
    compile_cache.configure_compile_cache = lambda: None
    import calibrate

    return calibrate.main(argv)


if __name__ == "__main__":
    sys.exit(main())
