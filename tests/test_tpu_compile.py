"""The main path's Pallas kernels compiled for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed without a chip, refuses
what Mosaic cannot lower (block shapes off the (8, 128) tiling, too much
VMEM) at the real widths, which interpret mode never checks. The topology is
described inside a fixture, never at import, and every compile happens in
this test's own process.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_models import COVID_CNN, MURA_VGG19, TABLE1_CNN
from repro.kernels.dp_release.kernel import dp_release_pallas
from repro.kernels.privacy_conv.kernel import privacy_conv_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("cfg", [COVID_CNN, TABLE1_CNN], ids=lambda c: c.name)
def test_privacy_conv_compiles_at_client_width(one_chip, cfg):
    b = cfg.batch_size
    h, w = cfg.input_hw
    cin, cout = cfg.in_channels, cfg.stages[0][0]
    _compile(partial(privacy_conv_pallas, noise_scale=0.05, interpret=False),
             one_chip, (b, h, w, cin), (3, 3, cin, cout), (cout,),
             (b, h // 2, w // 2, cout))


@pytest.mark.parametrize("cfg", [COVID_CNN, MURA_VGG19], ids=lambda c: c.name)
@pytest.mark.parametrize("sigma", [0.0, 0.5])
def test_dp_release_compiles_at_cut_width(one_chip, cfg, sigma):
    h, w = cfg.input_hw
    cut = (cfg.batch_size, h // 2, w // 2, cfg.stages[0][0])
    compiled = _compile(
        partial(dp_release_pallas, clip_norm=1.0, sigma=sigma, interpret=False),
        one_chip, cut, cut)
    # x, noise and the release are the only buffers of any size: the
    # lane-dense view may copy x and noise once, and nothing more
    mem = compiled.memory_analysis()
    row_bytes = 4 * cut[0] * cut[1] * cut[2] * cut[3]
    assert mem.temp_size_in_bytes <= 2 * row_bytes


def test_mura_train_step_fits_one_chip(one_chip):
    """The fused MURA_VGG19 train step at ``chip_smoke.MURA_BATCH`` fits one
    v5e chip's 16 GiB of HBM with room to spare. The printed memory
    analysis is what the batch was chosen from (``pytest -s`` shows it)."""
    import chip_smoke
    from repro.core import SplitTrainConfig
    from repro.core.adapters import cnn_adapter
    from repro.core.trainer import fused_client_batch, make_spatio_temporal_step
    from repro.optim import adamw

    shares = chip_smoke.MURA_SHARES
    tc = SplitTrainConfig(n_clients=len(shares), data_shares=shares,
                          server_batch=chip_smoke.MURA_BATCH,
                          privacy=chip_smoke.GUARD)
    init, step = make_spatio_temporal_step(cnn_adapter(MURA_VGG19), tc,
                                           adamw(1e-4))

    def on_chip(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    state = jax.tree.map(on_chip, jax.eval_shape(init, jax.random.PRNGKey(0)))
    b = fused_client_batch(tc)
    xs = on_chip(jax.ShapeDtypeStruct((len(shares), b, 224, 224, 1), jnp.float32))
    ys = on_chip(jax.ShapeDtypeStruct((len(shares), b), jnp.float32))
    key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    mem = jax.jit(step, donate_argnums=(0,)).lower(
        state, xs, ys, key).compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"MURA server_batch={tc.server_batch}: "
          f"args {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
          f"temp {mem.temp_size_in_bytes / 2**30:.2f} GiB, "
          f"total {total / 2**30:.2f} GiB")
    assert total <= 12 * 2**30
