"""Model FLOPs of the paper's split CNNs, from the configuration's shapes.

Counted: the multiply-adds of every 3x3 SAME convolution and dense layer,
two operations each. Not counted: bias adds, ReLU, pooling, the loss, the
guard's clip and noise, and the optimizer, which are elementwise.

Training is detached (the paper's regime): the client forward runs once and
is not differentiated; the trunk runs forward and backward, and its backward
needs the weight gradient of every layer and the input gradient of every
layer but the first, whose input is the released feature map."""
from __future__ import annotations


def _layers(cfg: dict):
    """``(side, flops, input_grad_needed)`` of each layer in order, where
    side is "client" or "trunk"."""
    h, w = cfg["input_hw"]
    cin = cfg["in_channels"]
    cut = cfg["cut_layers"]
    first_trunk = True
    for si, (filters, repeats) in enumerate(cfg["stages"]):
        side = "client" if si < cut else "trunk"
        for _ in range(repeats):
            flops = 2 * h * w * 9 * cin * filters
            yield side, flops, side == "trunk" and not first_trunk
            if side == "trunk":
                first_trunk = False
            cin = filters
        h, w = h // 2, w // 2
    d_in = h * w * cin
    for units in list(cfg["dense_units"]) + [cfg["n_classes"]]:
        yield "trunk", 2 * d_in * units, not first_trunk
        first_trunk = False
        d_in = units


def model_flops(cfg: dict) -> dict:
    """Per sample: ``client_fwd``, ``trunk_fwd``, ``train`` (client forward,
    trunk forward and the backward it needs) and ``serve`` (one forward)."""
    client = trunk = backward = 0
    for side, flops, input_grad in _layers(cfg):
        if side == "client":
            client += flops
        else:
            trunk += flops
            backward += flops * (2 if input_grad else 1)
    return {"client_fwd": client, "trunk_fwd": trunk,
            "train": client + trunk + backward, "serve": client + trunk}
