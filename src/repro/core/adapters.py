"""Split-model adapters: a uniform (init / client_forward / server_forward /
loss / metrics) interface over the paper's CNN, VGG19 and MLP models so the
trainers, the protocol simulation and the benchmarks are model-agnostic."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.configs.paper_models import CNNConfig, MLPConfig
from repro.metrics.losses import (
    bce_with_logits,
    binary_accuracy,
    ce_with_logits,
    mse,
    msle,
    multiclass_accuracy,
    rmsle,
    smape,
)
from repro.models import cnn as cnn_mod
from repro.models import mlp as mlp_mod


@dataclasses.dataclass(frozen=True)
class SplitAdapter:
    name: str
    init: Callable[[Any], Any]  # key -> params {"client","server"}
    client_forward: Callable[..., Any]  # (client_params, x, noise_key) -> features
    server_forward: Callable[..., Any]  # (server_params, features) -> outputs
    loss: Callable[[Any, Any], jnp.ndarray]
    metrics: Callable[[Any, Any], Dict[str, jnp.ndarray]]


# Banked (vmapped-over-clients) views used by the fused trainer: every
# argument gains a leading client axis C — stacked parameter banks
# [C, ...pytree], batches [C, b, ...], PRNG keys [C, 2].
def banked_client_forward(adapter: SplitAdapter, guard=None) -> Callable[..., Any]:
    """(stacked_banks, xs, noise_keys) -> features [C, b, ...].

    With an enabled ``repro.privacy.PrivacyGuard`` the release (clip →
    Gaussian mechanism → quantize) runs INSIDE the vmapped client forward,
    on a fold-in of each client's per-step key — so the guard vectorizes
    over the client axis and shard_maps with it under a device mesh.

    The privacy layer's ops carry the ``privacy_layer`` named scope, the
    guard's its own ``guard`` scope (op_name metadata only)."""
    def layer(bank, x, key):
        with jax.named_scope("privacy_layer"):
            return adapter.client_forward(bank, x, key)

    if guard is None or not guard.enabled:
        return jax.vmap(layer)

    def fwd(bank, x, key):
        return guard(guard.key_for(key), layer(bank, x, key))

    return jax.vmap(fwd)


def per_client_loss(adapter: SplitAdapter) -> Callable[..., jnp.ndarray]:
    """(outputs [C, b, ...], labels [C, b, ...]) -> per-client losses [C]."""
    return jax.vmap(adapter.loss)


def per_client_metrics(adapter: SplitAdapter) -> Callable[..., Dict[str, jnp.ndarray]]:
    """(outputs [C, b, ...], labels [C, b, ...]) -> {metric: [C]}."""
    return jax.vmap(adapter.metrics)


def cnn_adapter(cfg: CNNConfig) -> SplitAdapter:
    if cfg.loss == "bce":
        loss = lambda out, y: bce_with_logits(out, y)
        metrics = lambda out, y: {
            "loss": bce_with_logits(out, y),
            "accuracy": binary_accuracy(out, y),
        }
    else:  # multiclass
        loss = lambda out, y: ce_with_logits(out, y)
        metrics = lambda out, y: {
            "loss": ce_with_logits(out, y),
            "accuracy": multiclass_accuracy(out, y),
        }
    return SplitAdapter(
        name=cfg.name,
        init=lambda key: cnn_mod.init_cnn(key, cfg),
        client_forward=lambda cp, x, nk=None: cnn_mod.client_forward(
            {"client": cp}, cfg, x, nk
        ),
        server_forward=lambda sp, f: cnn_mod.server_forward({"server": sp}, cfg, f),
        loss=loss,
        metrics=metrics,
    )


def mlp_adapter(cfg: MLPConfig) -> SplitAdapter:
    def metrics(out, y):
        return {
            "loss": mse(out, y),
            "msle": msle(out, y),
            "rmsle": rmsle(out, y),
            "smape": smape(out, y),
        }

    return SplitAdapter(
        name=cfg.name,
        init=lambda key: mlp_mod.init_mlp(key, cfg),
        client_forward=lambda cp, x, nk=None: mlp_mod.client_forward(
            {"client": cp}, cfg, x, nk
        ),
        server_forward=lambda sp, f: mlp_mod.server_forward({"server": sp}, cfg, f),
        loss=lambda out, y: mse(out, y),
        metrics=metrics,
    )
