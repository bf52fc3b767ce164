"""The paper's CNN classifiers (custom COVID-19 model, VGG19 for MURA) in JAX.

Structured for split learning: ``params["client"]`` holds the input conv
stage(s) — the privacy-preserving layer (Conv2D + MaxPool2D, paper §III-A) —
and ``params["server"]`` holds the remaining stages + dense head.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.paper_models import CNNConfig
from repro.kernels.privacy_conv.ops import privacy_conv
from repro.models.layers import add_privacy_noise, dense_init


def _init_conv(key, in_ch, out_ch, ksize=3, dtype=jnp.float32):
    kw, kb = jax.random.split(key)
    fan_in = in_ch * ksize * ksize
    return {
        "w": dense_init(kw, fan_in, (ksize, ksize, in_ch, out_ch), dtype),
        "b": jnp.zeros((out_ch,), dtype),
    }


def conv2d(p, x, stride=1):
    """x: [B, H, W, C] NHWC."""
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return y + p["b"]


def conv2d_taps(p, x):
    """Stride-1 SAME conv as one matmul per kernel tap (the Pallas privacy
    kernel's decomposition). For one input channel only: each tap's matmul
    contracts over a single channel, which the TPU compiles to an exact
    float32 product, where a convolution at default precision would round
    the raw image to bfloat16. The benchmark's reference writes the privacy
    layer's first convolution the same way."""
    kh, kw = p["w"].shape[:2]
    ph, pw = kh // 2, kw // 2
    h, w = x.shape[-3], x.shape[-2]
    xp = jnp.pad(x, ((0, 0),) * (x.ndim - 3) + ((ph, ph), (pw, pw), (0, 0)))
    y = None
    for di in range(kh):
        for dj in range(kw):
            tap = jax.lax.slice_in_dim(
                jax.lax.slice_in_dim(xp, di, di + h, axis=-3), dj, dj + w, axis=-2
            )
            t = jnp.einsum("...hwi,io->...hwo", tap, p["w"][di, dj])
            y = t if y is None else y + t
    return y + p["b"]


def max_pool(x, size=2):
    """Non-overlapping max-pool. When the spatial dims divide the window the
    pool is a reshape+max (the Pallas kernel's scheme) — its VJP is a cheap
    equality mask, where `reduce_window`'s SelectAndScatter backward is a
    serial scatter on XLA:CPU that dominates small-model training steps."""
    h, w = x.shape[-3], x.shape[-2]
    if h % size == 0 and w % size == 0:
        shape = x.shape[:-3] + (h // size, size, w // size, size, x.shape[-1])
        return jnp.max(x.reshape(shape), axis=(-4, -2))
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, size, size, 1), (1, size, size, 1), "VALID"
    )


def init_cnn(key, cfg: CNNConfig, dtype=jnp.float32) -> Dict[str, Any]:
    keys = iter(jax.random.split(key, 64))
    in_ch = cfg.in_channels
    stages = []
    for filters, repeats in cfg.stages:
        convs = []
        for _ in range(repeats):
            convs.append(_init_conv(next(keys), in_ch, filters, dtype=dtype))
            in_ch = filters
        stages.append(convs)

    h, w = cfg.input_hw
    h, w = h // (2 ** len(cfg.stages)), w // (2 ** len(cfg.stages))
    flat = h * w * in_ch
    dense = []
    d_in = flat
    for units in cfg.dense_units:
        kw = next(keys)
        dense.append({"w": dense_init(kw, d_in, (d_in, units), dtype), "b": jnp.zeros((units,), dtype)})
        d_in = units
    out = {"w": dense_init(next(keys), d_in, (d_in, cfg.n_classes), dtype), "b": jnp.zeros((cfg.n_classes,), dtype)}

    cut = cfg.cut_layers
    return {
        "client": {"stages": stages[:cut]},
        "server": {"stages": stages[cut:], "dense": dense, "out": out},
    }


def client_conv(p, x):
    """A privacy-layer convolution, in the form its input's channel count
    picks: one input channel keeps :func:`conv2d_taps` (exact float32), more
    run as the native :func:`conv2d`, whose operands default precision
    rounds to bfloat16 in either form. Under the fused trainer's ``vmap``
    over the hospital bank the native form becomes one grouped convolution.
    The scopes ``conv_taps`` and ``conv`` name the form in a trace.

    The native form's input passes an optimization barrier so that it is
    computed once: fused into the convolution, the previous layer's taps
    are recomputed per window (MURA's banked first block on a TPU v5e:
    35 ms fused, 15 + 8 ms apart)."""
    if x.shape[-1] == 1:
        with jax.named_scope("conv_taps"):
            return conv2d_taps(p, x)
    with jax.named_scope("conv"):
        return conv2d(p, jax.lax.optimization_barrier(x))


def _run_stage(convs, x, conv=conv2d):
    """Conv + ReLU per layer, then the pool. ``conv`` is the form of each
    convolution: the trunk's native :func:`conv2d`, or the client's
    :func:`client_conv`, which picks it per layer from the input channels."""
    for c in convs:
        x = jax.nn.relu(conv(c, x))
    return max_pool(x)


def client_forward(params, cfg: CNNConfig, x, noise_key=None):
    """The privacy-preserving layer: conv stage(s) + max-pool (+ noise).

    x: [B, H, W, C]. Returns the feature map shipped to the server — the only
    thing that ever leaves a hospital.

    With ``cfg.use_kernel`` every single-conv stage runs through the fused
    Pallas kernel (conv+ReLU+pool+noise in one VMEM pass); the final stage
    fuses the Gaussian draw on-chip so the kernel and XLA paths see the
    exact same noise (same key, same post-pool shape). Other stages run
    :func:`client_conv`: a convolution over one input channel as tap
    matmuls, one over more channels as a native convolution.
    """
    stages = params["client"]["stages"]
    scale = cfg.privacy_noise if noise_key is not None else 0.0
    for si, convs in enumerate(stages):
        last = si == len(stages) - 1
        if cfg.use_kernel and len(convs) == 1:
            x = privacy_conv(
                x, convs[0]["w"], convs[0]["b"],
                noise_key if (last and scale > 0.0) else None,
                noise_scale=scale if last else 0.0,
                interpret=cfg.interpret,
            )
        else:
            x = _run_stage(convs, x, conv=client_conv)
            if last:
                x = add_privacy_noise(x, scale, noise_key)
    if not stages:
        x = add_privacy_noise(x, scale, noise_key)
    return x


def server_forward(params, cfg: CNNConfig, fmap):
    """Server trunk: remaining conv stages + dense head. fmap -> logits [B, n_classes]."""
    x = fmap
    for convs in params["server"]["stages"]:
        x = _run_stage(convs, x)
    x = x.reshape(x.shape[0], -1)
    for dlay in params["server"]["dense"]:
        x = jax.nn.relu(x @ dlay["w"] + dlay["b"])
    o = params["server"]["out"]
    return x @ o["w"] + o["b"]


def forward(params, cfg: CNNConfig, x, noise_key=None, detach_cut=True):
    fmap = client_forward(params, cfg, x, noise_key)
    if detach_cut:
        fmap = jax.lax.stop_gradient(fmap)
    # whole-model convenience for single-trust-domain use; split
    # deployments go through SplitSession, which guards the cut
    return server_forward(params, cfg, fmap)  # splitlint: ignore[SPL101]
