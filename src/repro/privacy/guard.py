"""The PrivacyGuard: ONE release mechanism at the split cut for every engine.

The paper's entire contribution is the privacy-preserving layer at the cut
(§III, §IV-D2). This module makes that layer a first-class, composable
subsystem instead of ad-hoc per-engine noise:

  features --> per-sample L2 clip --> Gaussian mechanism --> optional
  quantize --> the ONLY thing that crosses the trust boundary

A ``PrivacyGuard`` is built from a :class:`DPConfig` and applied by every
execution regime (fused scan/stepwise, looped reference, protocol-async,
FedAvg) at the same place — the feature map leaving ``client_forward`` —
with per-step fold-in JAX keys, so all engines share one noise schedule.
When the config is ``None`` the guard is the identity and compiles to
nothing (the guard-off hot path is bit-exact with the unguarded engines).

Calibration (Dwork & Roth, Thm 3.22): one clipped release is (ε, δ)-DP with

  sigma = sensitivity * sqrt(2 ln(1.25/δ)) / ε,   sensitivity = 2 * clip_norm

Composition over releases is tracked by ``repro.privacy.accountant`` as
int32/float32 leaves inside the canonical ``SplitSession`` state, so the
budget survives ``save``/``restore``.

The clip+noise release runs either as pure XLA (default — fastest on CPU)
or through the fused Pallas kernel ``repro.kernels.dp_release``
(``DPConfig.use_kernel``), which keeps the UNCLIPPED feature map in VMEM.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.dp_release.ops import dp_release_with_noise as _dp_release_op

# Constant folded into the client's per-step noise key to derive the guard's
# own key: the guard never reuses the model-level noise draw, and every
# engine derives the same schedule from the same step keys.
GUARD_KEY_FOLD = 7919


def batched_release_keys(base_keys, releases):
    """Per-item release keys from stacked per-client base keys, on device.

    ``base_keys`` is ``[N]`` stacked PRNG keys (one per item, typically a
    gather of the fleet's per-client base keys by item client id) and
    ``releases`` the ``[N]`` int release counters; returns the ``[N]`` keys
    ``fold_in(base_keys[i], releases[i])``. ``fold_in`` is counter-based
    threefry, so the vmapped batch is BIT-IDENTICAL to folding each key on
    the host one at a time — this is the key-schedule half of the fleet
    production equivalence argument (``protocol.FleetProducer``): batching
    the whole queue cycle's key derivations into the one jitted fleet
    dispatch removes N tiny host dispatches without perturbing a single
    noise draw.
    """
    return jax.vmap(jax.random.fold_in)(base_keys, releases)


@dataclasses.dataclass(frozen=True)
class DPConfig:
    """The privacy knob shared by every engine.

    Two ways to set the noise level:
      * mechanism-calibrated (the default): ``epsilon``/``delta`` +
        ``clip_norm`` give ``sigma`` via the Gaussian mechanism — one
        release is (ε, δ)-DP.
      * explicit: ``noise_scale`` pins σ directly (the legacy
        ``privacy_noise`` semantics); with ``clip_norm=None`` the release
        is the raw legacy perturbation (unclipped ⇒ ε is unbounded, and
        the accountant reports ``inf``).
    """

    epsilon: float = 1.0
    delta: float = 1e-5
    clip_norm: Optional[float] = 1.0  # None disables per-sample clipping
    noise_scale: Optional[float] = None  # explicit σ override (legacy knob)
    quantize_bits: Optional[int] = None  # optional uniform quantization
    # use_kernel routes the clip+noise release through the fused Pallas
    # kernel (repro.kernels.dp_release); interpret=None auto-selects Mosaic
    # lowering on a TPU, the Pallas interpreter anywhere else (slow — CPU
    # throughput runs should keep the default XLA path).
    use_kernel: bool = False
    interpret: Optional[bool] = None

    @property
    def sigma(self) -> float:
        """Noise stddev of one release."""
        if self.noise_scale is not None:
            return float(self.noise_scale)
        if self.clip_norm is None:
            return 0.0
        if self.epsilon <= 0:
            raise ValueError("epsilon must be positive")
        sens = 2.0 * self.clip_norm
        return sens * math.sqrt(2.0 * math.log(1.25 / self.delta)) / self.epsilon

    @property
    def release_epsilon(self) -> float:
        """ε spent by ONE release (the accountant's composition unit).

        Mechanism-calibrated configs spend exactly ``epsilon``. An explicit
        ``noise_scale`` inverts the Gaussian mechanism; without clipping the
        sensitivity is unbounded and the release spends ``inf``.
        """
        if self.noise_scale is None:
            return float(self.epsilon)
        if self.clip_norm is None or self.noise_scale <= 0:
            return math.inf
        sens = 2.0 * self.clip_norm
        return sens * math.sqrt(2.0 * math.log(1.25 / self.delta)) / self.noise_scale


def clip_per_sample(features: jnp.ndarray, clip_norm: float) -> jnp.ndarray:
    """L2-clip each sample's feature map (leading dim = batch)."""
    flat = features.reshape(features.shape[0], -1)
    norms = jnp.linalg.norm(flat.astype(jnp.float32), axis=-1, keepdims=True)
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(norms, 1e-12))
    return (flat * scale).reshape(features.shape).astype(features.dtype)


def gaussian_release(x: jnp.ndarray, scale: float, key) -> jnp.ndarray:
    """The paper's §III-A Gaussian feature perturbation — the guard's no-clip
    path and the building block ``models.layers.add_privacy_noise`` wraps.
    Bit-exact with the historical formula: noise drawn in ``x.dtype``."""
    if scale <= 0.0 or key is None:
        return x
    return x + scale * jax.random.normal(key, x.shape, x.dtype)


def quantize_ste(x: jnp.ndarray, max_abs: float, bits: int) -> jnp.ndarray:
    """Uniform symmetric quantization with a straight-through gradient
    (bandwidth knob for the released feature map; NOT a DP mechanism)."""
    levels = float((1 << (bits - 1)) - 1)
    step = max_abs / levels
    q = jnp.clip(jnp.round(x / step), -levels, levels) * step
    return x + jax.lax.stop_gradient(q - x)


def dp_release(key, features: jnp.ndarray, dp: DPConfig) -> jnp.ndarray:
    """Clip + Gaussian-mechanism noise: the (ε, δ)-DP feature map the client
    is allowed to push into the server queue. (Legacy signature, kept for
    the ``repro.core.dp`` shim; new code should apply a ``PrivacyGuard``.)"""
    clipped = clip_per_sample(features, dp.clip_norm)
    noise = dp.sigma * jax.random.normal(key, features.shape, jnp.float32)
    return (clipped.astype(jnp.float32) + noise).astype(features.dtype)


@dataclasses.dataclass(frozen=True)
class PrivacyGuard:
    """Composable release policy at the cut: clip → noise → quantize.

    ``guard(key, features)`` is pure, jittable and vmappable — the engines
    vmap it over the stacked client axis. ``dp=None`` is the identity.
    """

    dp: Optional[DPConfig] = None

    @classmethod
    def from_config(cls, dp: Optional[DPConfig]) -> "PrivacyGuard":
        return cls(dp=dp)

    @property
    def enabled(self) -> bool:
        return self.dp is not None

    @property
    def sigma(self) -> float:
        return self.dp.sigma if self.dp is not None else 0.0

    def key_for(self, key):
        """Derive the guard's noise key from the client's per-step key, so
        the release draw never aliases the model-level noise draw."""
        return jax.random.fold_in(key, GUARD_KEY_FOLD)

    def keys_for(self, keys):
        """``key_for`` vmapped over stacked keys ``[N]`` — bit-identical to
        deriving each key alone (fold_in is counter-based). Used by the
        fused scan runner's epoch noise pre-draw and the fleet production
        dispatch, where per-item host fold-ins would cost a dispatch each."""
        return jax.vmap(self.key_for)(keys)

    def __call__(self, key, features: jnp.ndarray) -> jnp.ndarray:
        if self.dp is None:
            return features
        noise = None
        if self.dp.sigma > 0.0:
            # a silent no-noise release would still be CHARGED by the
            # accountant — refuse rather than report a guarantee that
            # does not hold
            assert key is not None, "guard sigma > 0 requires a PRNG key"
            noise = jax.random.normal(key, features.shape, jnp.float32)
        return self.release_with_noise(features, noise)

    def release_with_noise(self, features: jnp.ndarray,
                           noise: Optional[jnp.ndarray]) -> jnp.ndarray:
        """The release with PRE-DRAWN standard-normal ``noise`` (``None`` ⇒
        no perturbation). Bit-identical to ``__call__`` when ``noise`` is the
        draw ``__call__`` would make from its key — the fused scan runner
        uses this to hoist the epoch's threefry out of the serial loop body,
        where it dominates the guard's cost on XLA:CPU."""
        if self.dp is None:
            return features
        dp = self.dp
        sigma = dp.sigma
        if sigma > 0.0:
            assert noise is not None, "guard sigma > 0 requires pre-drawn noise"
        if dp.clip_norm is None:
            # unclipped ⇒ exactly the legacy perturbation (bit-exact shim path)
            out = features
            if sigma > 0.0 and noise is not None:
                out = features + sigma * noise.astype(features.dtype)
        else:
            out = _dp_release_op(
                features, noise,
                clip_norm=float(dp.clip_norm), sigma=float(sigma),
                use_kernel=dp.use_kernel, interpret=dp.interpret,
            )
        if dp.quantize_bits is not None:
            out = quantize_ste(out, dp.clip_norm or 1.0, dp.quantize_bits)
        return out
