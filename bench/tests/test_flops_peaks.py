"""The model-FLOP count and the table of peaks."""
import pytest

import harness
from flops import kernel_cost
from flops.cnn import model_flops


def _cfg(name):
    return harness.read_json(harness.BENCH / "configs" / f"{name}.json")["model"]


def conv(hw, cin, cout):
    return 2 * hw * hw * 9 * cin * cout


def test_mura_vgg19_flops_match_a_hand_count():
    client = conv(224, 1, 64) + conv(224, 64, 64)
    trunk_convs = [conv(112, 64, 128), conv(112, 128, 128),
                   conv(56, 128, 256)] + [conv(56, 256, 256)] * 3 + \
                  [conv(28, 256, 512)] + [conv(28, 512, 512)] * 3 + \
                  [conv(14, 512, 512)] * 4
    dense = [2 * 7 * 7 * 512 * 4096, 2 * 4096 * 4096, 2 * 4096 * 1]
    trunk = sum(trunk_convs) + sum(dense)
    # backward: every weight gradient, every input gradient but the first
    # trunk layer's (its input is the released, detached feature map)
    backward = 2 * trunk - trunk_convs[0]
    f = model_flops(_cfg("mura-vgg19"))
    assert f["client_fwd"] == client == 3_757_178_880
    assert f["trunk_fwd"] == trunk == 35_383_156_736
    assert f["train"] == client + trunk + backward
    assert f["train"] == pytest.approx(108.06e9, rel=1e-3)  # about 110 GFLOP
    assert f["serve"] == client + trunk


def test_covid_cnn_flops_match_a_hand_count():
    client = conv(64, 1, 16)
    trunk_convs = [conv(32, 16, 32), conv(16, 32, 64), conv(8, 64, 128),
                   conv(4, 128, 256)]
    dense = [2 * 2 * 2 * 256 * 64, 2 * 64 * 1]
    trunk = sum(trunk_convs) + sum(dense)
    f = model_flops(_cfg("covid-cnn"))
    assert f["client_fwd"] == client == 1_179_648
    assert f["trunk_fwd"] == trunk == 37_879_936
    assert f["train"] == client + 3 * trunk - trunk_convs[0]
    assert f["train"] == pytest.approx(105.4e6, rel=1e-3)  # about 115 MFLOP
    # with the first trunk layer's input gradient counted too


@pytest.mark.parametrize("name", ["mura-vgg19", "covid-cnn"])
def test_a_configuration_names_its_flop_counter(name):
    cfg = harness.read_json(harness.BENCH / "configs" / f"{name}.json")
    assert cfg["flops"] == "cnn"
    assert harness.model_flops(cfg) == model_flops(cfg["model"])


def test_peak_lookup_refuses_an_unknown_device():
    assert harness.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peak("TPU v9 imaginary")


def test_kernel_costs_are_found_by_name():
    assert kernel_cost("dp_release", rows=2, features=128) == {
        "flops": 6 * 256, "bytes": 12 * 256}
    c = kernel_cost("privacy_conv", batch=1, h=4, w=4, cin=1, cout=2)
    assert c["flops"] == 2 * 16 * 9 * 2
    with pytest.raises(KeyError):
        kernel_cost("no_such_kernel")
