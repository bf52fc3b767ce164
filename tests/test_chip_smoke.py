"""CPU rehearsal of ``chip_smoke.py``: every phase at tiny sizes with the
kernels interpreted, the no-TPU exit, and the compile-cache helper."""
import dataclasses

import jax
import pytest

import chip_smoke
from repro.configs.paper_models import COVID_CNN, MURA_VGG19
from repro.launch import compile_cache

TINY_COVID = dataclasses.replace(
    COVID_CNN, input_hw=(16, 16), stages=((4, 1), (8, 1)), dense_units=(16,))
TINY_MURA = dataclasses.replace(
    MURA_VGG19, input_hw=(16, 16), stages=((4, 2), (8, 2)), dense_units=(16, 16))


def test_main_exits_nonzero_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_kernel_phase_interpreted():
    chip_smoke.phase_kernels(interpret=True, batch=2, mura_batch=2)


def test_covid_phase_then_serve():
    session, shards, modes = chip_smoke.phase_covid(
        cfg=TINY_COVID, n=120, server_batch=6, epochs=3, steps=6)
    assert len(modes) == len(chip_smoke.COVID_SEEDS)
    assert set(modes) <= {"scan", "stepwise"}
    chip_smoke.phase_serve(session, shards, rate=2.0, horizon=12,
                           min_answered=12)


def test_mura_phase():
    losses = chip_smoke.phase_mura(cfg=TINY_MURA, server_batch=8, epochs=2, n=40)
    assert len(losses) == 2


def test_sharded_phase_on_a_one_device_grid():
    chip_smoke.phase_mura_sharded(cfg=TINY_MURA, server_batch=8, epochs=2,
                                  n=40, grids=((1, 1),))


def test_compile_cache_keeps_the_env_dir(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set in code


def test_compile_cache_defaults_to_a_fixed_dir_in_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.configure_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = compile_cache.DEFAULT_DIR.parent
    assert path == str(root / ".jax_cache")
    assert (root / "chip_smoke.py").is_file() and (root / "src").is_dir()
