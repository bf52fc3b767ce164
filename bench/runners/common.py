"""Building the system under test from a configuration file."""
from __future__ import annotations

import numpy as np

from gen.images import DATASETS, split_hospitals


def hospital_shards(cfg: dict, seed: int):
    """The configuration's dataset drawn from the seed and split into its
    hospitals' shards."""
    hw = cfg["model"]["input_hw"][0]
    x, y = DATASETS[cfg["dataset"]](cfg["dataset_size"], hw, seed)
    return split_hospitals(x, y, cfg["shares"], seed)


def session(cfg: dict, seed: int):
    """A ``SplitSession`` on the configuration, initialised from the seed,
    with the engine the program picks itself (``engine="auto"``)."""
    from repro.configs.paper_models import CNNConfig
    from repro.core import SplitSession, SplitTrainConfig
    from repro.core.adapters import cnn_adapter
    from repro.optim import adamw
    from repro.privacy import DPConfig

    m = cfg["model"]
    model = CNNConfig(
        name=cfg["name"], input_hw=tuple(m["input_hw"]),
        in_channels=m["in_channels"],
        stages=tuple(tuple(s) for s in m["stages"]),
        n_classes=m["n_classes"], dense_units=tuple(m["dense_units"]),
        cut_layers=m["cut_layers"], privacy_noise=m["privacy_noise"],
        batch_size=cfg["server_batch"], loss=m["loss"])
    o = cfg["optimizer"]
    tc = SplitTrainConfig(
        n_clients=cfg["hospitals"], data_shares=tuple(cfg["shares"]),
        server_batch=cfg["server_batch"], mode=cfg["mode"],
        privacy=DPConfig(**cfg["guard"]), grad_clip=o["grad_clip"])
    opt = adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                weight_decay=o["weight_decay"])
    return SplitSession(cnn_adapter(model), tc, opt, engine="auto", seed=seed)


def leaf_gaps(prog: dict, ref: dict, keep=None) -> list:
    """Per leaf ``|norm_prog - norm_ref|``, each measured against the larger
    of the reference leaf's norm and the median leaf's."""
    if prog.keys() != ref.keys():
        raise ValueError(f"leaves differ: {sorted(prog)} vs {sorted(ref)}")
    median = float(np.median(list(ref.values())))
    return [abs(prog[k] - ref[k]) / max(ref[k], median, 1e-30)
            for k in ref if keep is None or k in keep]


def against_control(gaps: dict, low: dict, keys) -> dict:
    """Each gap in ``keys`` as a share of the same gap of the reference
    computed in bfloat16: how close to the stated precision an answer lies,
    on this seed's own scale. The bfloat16 reference itself reads 1."""
    return {f"{k}_vs_bf16": gaps[k] / max(low[k], 1e-30) for k in keys}


def rel_gap(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)
