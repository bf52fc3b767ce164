"""Fused SPMD trainers for spatio-temporal split learning (paper Alg. 1).

The hot path compiles the whole protocol into ONE dispatch per epoch:

  * per-client parameter banks are stacked into a single leading-axis
    pytree, and the privacy-preserving layer is ``jax.vmap``-ed over that
    client axis (the *spatial* split becomes a device axis, not a Python
    loop),
  * every client contributes a homogeneous per-step batch; the paper's
    share-weighted (7:2:1) queue mix is applied as per-client loss
    weights, which equals the seed's ragged concat mix in expectation —
    and exactly when shares are uniform,
  * batch sampling happens on device: epoch data lives in padded device
    arrays and per-step indices come from ``jax.random`` fold-ins, so no
    per-step host RNG draws or host->device copies remain,
  * the epoch is a ``jax.lax.scan`` with a donated carry — metrics come
    back as stacked arrays and are read once per epoch,
  * ``detached`` mode (the *temporal* split) updates ONLY the server
    (stop_gradient at the cut); ``e2e`` is classic split learning and
    differentiates through the client banks — including through the
    Pallas privacy kernel when ``CNNConfig.use_kernel`` is set (its
    ``jax.custom_vjp`` backs onto the XLA reference),
  * ``SplitTrainConfig.privacy`` builds ONE ``repro.privacy.PrivacyGuard``
    that releases (clip → Gaussian mechanism → quantize) at the cut inside
    the vmapped client forward, on fold-in per-step keys shared with the
    looped reference — and the (ε, δ) budget leaves advance on device
    inside the canonical state (``repro.privacy.accountant``).

``make_looped_step`` preserves the seed per-client Python-loop
implementation as the numerical reference; the parity tests and
``benchmarks/trainer_perf.py`` compare the fused engine against it.

A wall-clock-faithful asynchronous queue simulation lives in
``repro.core.protocol``; this module is the throughput-oriented equivalent —
and ``make_server_bank_runner`` is the bridge between the two: it replays a
``FeatureBank`` of queue arrivals (padded slots + validity mask) as ONE
scanned sequence of server trunk updates, bit-identical to
``protocol.SplitServer`` stepping once per pop. The production-side
counterpart is ``protocol.FleetProducer``, which vmaps the fleet's client
forwards + guard releases over the SAME stacked-bank layout this module
owns — between them the queue engines' hot path is one client dispatch per
queue cycle and one server dispatch per epoch.

Role in the engine registry (``repro.core.session``): this module BUILDS the
compiled programs behind ``auto`` / ``fused-scan`` / ``fused-stepwise``
(``make_epoch_runner``), the ``looped-ref`` reference (``make_looped_step``)
and the server half of ``fused-queue`` (``make_server_bank_runner``). It
also defines the canonical state's layout authority: the fused init owns ALL
five leaves — stacked ``client_banks``, ``server``, flat-buffer ``opt``,
int32 ``step``, and the ``privacy`` budget (advanced here on device via
``repro.privacy.accountant``).
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.adapters import (
    SplitAdapter,
    banked_client_forward,
    per_client_loss,
    per_client_metrics,
)
from repro.optim.optimizers import Optimizer, apply_updates, clip_by_global_norm
from repro.privacy.accountant import budget_advance, budget_init
from repro.privacy.guard import DPConfig, PrivacyGuard


# Mesh axis name the canonical state's leading client dimension shards over
# (see ``repro.core.session.SplitSession(mesh=...)`` / ``launch.mesh.make_client_mesh``).
CLIENT_AXIS = "clients"
# Mesh axis name the server TRUNK's parameters shard over, tensor-parallel
# (the second axis of ``launch.mesh.make_split_mesh`` grids; see
# ``repro.sharding.specs.trunk_specs`` for which leaf shards which dim).
MODEL_AXIS = "model"


def _trunk_sharder(mesh: Optional[Mesh], axis: str = MODEL_AXIS):
    """Constraint function for the server trunk (params OR a moment tree
    mirroring it): ``with_sharding_constraint`` every leaf to its
    ``trunk_specs`` layout so GSPMD partitions the trunk matmuls over the
    mesh's model axis. Identity when there is no mesh, no model axis, or the
    axis has size 1 — which is exactly what keeps the 1x1 / Nx1 meshes
    bit-exact with the unsharded engines (no constraint, no reassociation).

    Deliberately GSPMD constraints rather than a manual ``shard_map`` psum:
    the partitioner keeps the op sequence (and therefore the fp32 rounding)
    of each partitioned matmul identical to the unsharded program wherever
    the layout is replicated, and inserts the all-gathers only where the
    specs force one — at the CUT (every model shard consumes the full
    released features) and at the LOGITS (the head falls back to replicated
    when n_classes doesn't divide the axis)."""
    if mesh is None or axis not in mesh.axis_names or mesh.shape[axis] == 1:
        return lambda tree: tree
    from repro.sharding.specs import trunk_specs

    def constrain(tree):
        specs = trunk_specs(tree, mesh, axis=axis)
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, s)
            ),
            tree, specs,
        )

    return constrain


@dataclasses.dataclass(frozen=True)
class SplitTrainConfig:
    n_clients: int = 3
    data_shares: Tuple[float, ...] = (0.7, 0.2, 0.1)
    server_batch: int = 64
    mode: str = "detached"  # detached (paper) | e2e (classic split learning)
    # The privacy knob: a repro.privacy.DPConfig builds the PrivacyGuard
    # every engine applies at the cut (None = guard off, bit-exact with the
    # unguarded engines).
    privacy: Optional[DPConfig] = None
    # Gradient global-norm clip for the server/trainable update (this was
    # historically named ``clip_norm``, which collided with the DP feature
    # clip — see the deprecated fields below).
    grad_clip: float = 1.0
    # DEPRECATED: both map onto the new fields in __post_init__ with a
    # DeprecationWarning. ``privacy_noise`` becomes an unclipped guard
    # (DPConfig(clip_norm=None, noise_scale=...)) reproducing the legacy
    # Gaussian perturbation bit-exactly; ``clip_norm`` was ALWAYS the
    # gradient clip and becomes ``grad_clip``.
    privacy_noise: float = 0.0
    clip_norm: Optional[float] = None

    def __post_init__(self):
        # the deprecated fields are consumed (mapped onto the new fields,
        # then cleared) so a later dataclasses.replace() cannot silently
        # re-apply them over explicitly-set new-field values
        if self.clip_norm is not None:
            warnings.warn(
                "SplitTrainConfig.clip_norm is deprecated (it is the GRADIENT "
                "clip); use grad_clip=",
                DeprecationWarning, stacklevel=3,
            )
            object.__setattr__(self, "grad_clip", float(self.clip_norm))
            object.__setattr__(self, "clip_norm", None)
        if self.privacy_noise != 0.0:
            warnings.warn(
                "SplitTrainConfig.privacy_noise is deprecated; use "
                "privacy=DPConfig(clip_norm=None, noise_scale=...) — the "
                "guard reproduces the legacy perturbation bit-exactly when "
                "clipping is disabled",
                DeprecationWarning, stacklevel=3,
            )
            if self.privacy is None:
                object.__setattr__(
                    self, "privacy",
                    DPConfig(clip_norm=None, noise_scale=float(self.privacy_noise)),
                )
            object.__setattr__(self, "privacy_noise", 0.0)


def client_batch_sizes(tc: SplitTrainConfig) -> List[int]:
    """Per-step client contributions ∝ data shares, summing to server_batch.

    Largest-remainder apportionment. Every client gets ≥ 1 sample whenever
    ``server_batch >= n_clients`` (the seed's drift correction could push
    the LARGEST client to a 0-size batch for tiny server batches, e.g.
    server_batch=2 with shares (0.7, 0.2, 0.1)).
    """
    shares = tc.data_shares
    n = len(shares)
    total = float(sum(shares))
    raw = [s / total * tc.server_batch for s in shares]
    sizes = [int(r) for r in raw]
    by_remainder = sorted(
        range(n), key=lambda j: (raw[j] - sizes[j], shares[j]), reverse=True
    )
    for j in by_remainder[: tc.server_batch - sum(sizes)]:
        sizes[j] += 1
    if tc.server_batch >= n:
        while any(s == 0 for s in sizes):
            sizes[max(range(n), key=lambda j: sizes[j])] -= 1
            sizes[sizes.index(0)] += 1
    return sizes


def fused_client_batch(tc: SplitTrainConfig) -> int:
    """Homogeneous per-client batch for the fused engine (the vmapped client
    axis needs one shape); the share mix becomes loss weights instead of
    ragged batch sizes — see ``client_weights``."""
    return max(1, tc.server_batch // tc.n_clients)


def client_weights(tc: SplitTrainConfig) -> jnp.ndarray:
    """Normalized per-client loss weights reproducing the queue's
    share-proportional steady-state batch mix."""
    w = jnp.asarray(tc.data_shares, jnp.float32)
    return w / jnp.sum(w)


def stack_batches(
    batches: Sequence[Tuple[Any, Any]]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """List of equal-size per-client (x, y) -> stacked ([C, b, ...], [C, b])."""
    xs = jnp.stack([jnp.asarray(x) for x, _ in batches])
    ys = jnp.stack([jnp.asarray(y) for _, y in batches])
    return xs, ys


def finite_mean(values) -> float:
    """Mean over the FINITE entries of ``values``; NaN when there are none.
    Identical to a plain mean on all-finite input (the values pass through
    untouched), but degraded-mode epochs — fault drills with quorum halts or
    all-down windows (``core.faults``) — can report empty or NaN-masked loss
    lists, and a plain mean would propagate the padding into the history."""
    arr = np.asarray(values, np.float64)
    arr = arr[np.isfinite(arr)]
    return float(arr.mean()) if arr.size else float("nan")


# --------------------------------------------------------------------- steps
def _shard_banked_forward(fwd_banked, mesh: Mesh, client_axis: str):
    """shard_map the vmapped privacy layer over the mesh's client axis: each
    hospital's bank + batch + noise key live (and differentiate) on their own
    device. On a 1-device mesh this is a bit-exact no-op — the per-shard body
    is the same vmapped jaxpr over the full client axis."""
    spec = P(client_axis)
    sharded = jax.shard_map(
        fwd_banked, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    if len(mesh.axis_names) == 1:
        return sharded

    # 2-D ("clients", "model") grids: ``check_vma=False`` skips verifying
    # that operands are REPLICATED over the unmentioned model axis, and the
    # unchecked full-to-shard conversion reads whatever is locally resident
    # — if GSPMD laid an operand out sharded over "model" (its right under
    # plain jit), each shard-body would silently misread a model-shard as
    # the full per-client slice. Pin every operand to exactly the layout
    # the manual body assumes: sharded over the client axis, replicated
    # elsewhere. Pure layout, so Nx1 grids stay bit-exact with the 1-D mesh.
    def constrained(banks, xs, keys):
        pin = lambda t: jax.tree.map(
            lambda a: jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, spec)
            ),
            t,
        )
        return sharded(pin(banks), pin(xs), pin(keys))

    return constrained


def _make_fused(
    adapter: SplitAdapter, tc: SplitTrainConfig, opt: Optimizer,
    mesh: Optional[Mesh] = None, client_axis: str = CLIENT_AXIS,
):
    """Shared core of the fused engine: (init_state, unjitted step_core)."""
    detached = tc.mode == "detached"
    weights = client_weights(tc)
    # the PrivacyGuard releases at the cut INSIDE the vmapped client forward
    # (identity when tc.privacy is None — no trace-time overhead). Two
    # equivalent release paths: keyed (draws noise in-step — the stepwise
    # engines) and pre-drawn (the scan runner hoists the epoch's threefry
    # out of the serial loop body and feeds per-step noise slices).
    guard = PrivacyGuard.from_config(tc.privacy)
    fwd_guarded = banked_client_forward(adapter, guard=guard)
    fwd_plain = banked_client_forward(adapter) if guard.enabled else None
    shard_trunk = _trunk_sharder(mesh)
    if mesh is not None:
        if client_axis not in mesh.axis_names:
            raise ValueError(
                f"mesh axes {mesh.axis_names} have no {client_axis!r} axis; "
                f"build the mesh with launch.mesh.make_client_mesh or "
                f"make_split_mesh"
            )
        if tc.n_clients % mesh.shape[client_axis] != 0:
            raise ValueError(
                f"n_clients={tc.n_clients} does not divide over mesh axis "
                f"{client_axis!r} of size {mesh.shape[client_axis]}; the "
                f"stacked client banks shard their leading axis evenly"
            )
        fwd_guarded = _shard_banked_forward(fwd_guarded, mesh, client_axis)
        if fwd_plain is not None:
            fwd_plain = _shard_banked_forward(fwd_plain, mesh, client_axis)
    release_noise = jax.vmap(guard.release_with_noise) if guard.enabled else None
    loss_banked = per_client_loss(adapter)
    metrics_banked = per_client_metrics(adapter)

    def init_state(key):
        k0, *cks = jax.random.split(key, tc.n_clients + 1)
        ref = adapter.init(k0)
        server_params = ref["server"]
        # same per-client keys as the looped path, stacked leaf-wise
        banks = [adapter.init(k)["client"] for k in cks]
        client_banks = jax.tree.map(lambda *xs: jnp.stack(xs), *banks)
        trainable = server_params if detached else (client_banks, server_params)
        # optimizer state lives in the FLAT domain: one fused buffer per
        # moment instead of a tree of tiny per-leaf ops (the leaf-wise
        # clip+update chain dominates small-model steps on CPU)
        return {
            "client_banks": client_banks,
            "server": server_params,
            "opt": opt.init(ravel_pytree(trainable)[0]),
            "step": jnp.zeros((), jnp.int32),
            "privacy": budget_init(),
        }

    def loss_from(client_banks, server_params, xs, ys, noise_keys,
                  guard_noise=None):
        # tensor-parallel trunk: constrain the unraveled server leaves to
        # their trunk_specs layout so the matmuls (and their grads) partition
        # over the model axis; identity off-mesh / on a size-1 model axis
        server_params = shard_trunk(server_params)
        if guard_noise is not None:  # scan path: pre-drawn release noise
            feats = fwd_plain(client_banks, xs, noise_keys)
            feats = release_noise(feats, guard_noise)
        else:  # keyed path (stepwise / guard-off; the draw happens in-step)
            feats = fwd_guarded(client_banks, xs, noise_keys)  # [C, b, ...]
        if detached:
            feats = jax.lax.stop_gradient(feats)
        # the trunk and the loss; their backward is named
        # ``transpose(jvp(trunk))`` by JAX itself
        with jax.named_scope("trunk"):
            c, b = feats.shape[0], feats.shape[1]
            fcat = feats.reshape((c * b,) + feats.shape[2:])
            out = adapter.server_forward(server_params, fcat)
            out_cb = out.reshape((c, b) + out.shape[1:])
            loss = jnp.sum(weights * loss_banked(out_cb, ys))
        return loss, (out_cb, ys)

    def trainable_of(state):
        return state["server"] if detached else (state["client_banks"], state["server"])

    def with_trainable(state, trainable, new_opt):
        # one optimizer step = one guarded release per client: the (ε, δ)
        # budget leaves advance on device, in the same donated state pytree
        priv = budget_advance(state["privacy"], tc.privacy)
        if detached:
            return {**state, "server": trainable, "opt": new_opt,
                    "step": state["step"] + 1, "privacy": priv}
        cb, sp = trainable
        return {**state, "client_banks": cb, "server": sp, "opt": new_opt,
                "step": state["step"] + 1, "privacy": priv}

    def step_flat(flat, opt_state, step, banks, unravel, xs, ys, rng,
                  guard_noise=None):
        """One fused step entirely in the FLAT parameter domain: the model
        unravels the single trainable buffer (slices fuse into the forward),
        the gradient comes back flat, and clip+update are a handful of
        whole-buffer ops instead of a tree of tiny per-leaf ops."""
        noise_keys = jax.random.split(rng, tc.n_clients)

        def lf(fl):
            if detached:
                return loss_from(banks, unravel(fl), xs, ys, noise_keys,
                                 guard_noise)
            cb, sp = unravel(fl)
            return loss_from(cb, sp, xs, ys, noise_keys, guard_noise)

        (loss, (out, ycb)), flat_grads = jax.value_and_grad(lf, has_aux=True)(flat)
        # same math as the seed's leaf-wise clip_by_global_norm + update,
        # fp32-reassociated
        with jax.named_scope("optimizer"):
            gnorm = jnp.sqrt(jnp.sum(jnp.square(flat_grads)))
            scale = jnp.minimum(1.0, tc.grad_clip / jnp.maximum(gnorm, 1e-9))
            updates, new_opt = opt.update(flat_grads * scale, opt_state, flat, step)
            new_flat = flat + updates
        # share-weighted per-client means: equals the seed's concat-mix for
        # linear metrics; nonlinear aggregates (rmsle, smape) become
        # weighted per-client means.
        per = metrics_banked(out, ycb)
        metrics = {k: jnp.sum(weights * v) for k, v in per.items()}
        metrics["grad_norm"] = gnorm
        return new_flat, new_opt, metrics

    def step_core(state, xs, ys, rng):
        flat, unravel = ravel_pytree(trainable_of(state))
        new_flat, new_opt, metrics = step_flat(
            flat, state["opt"], state["step"], state["client_banks"], unravel,
            xs, ys, rng,
        )
        return with_trainable(state, unravel(new_flat), new_opt), metrics

    return init_state, step_core, trainable_of, with_trainable, step_flat


def make_spatio_temporal_step(
    adapter: SplitAdapter, tc: SplitTrainConfig, opt: Optimizer,
    mesh: Optional[Mesh] = None,
):
    """The fused engine step. Returns (init_state, step) with
    ``step(state, xs, ys, rng)`` where ``xs: [C, b, ...]``, ``ys: [C, b, ...]``
    are stacked per-client batches of homogeneous size
    ``fused_client_batch(tc)`` (see ``stack_batches``)."""
    init_state, step_core, *_ = _make_fused(adapter, tc, opt, mesh=mesh)
    # parity tests re-apply one state to several engines, so donating
    # its buffers would invalidate their inputs
    return init_state, jax.jit(step_core)  # splitlint: ignore[JAX205]


def make_looped_step(adapter: SplitAdapter, tc: SplitTrainConfig, opt: Optimizer):
    """The seed per-client Python-loop step (reference implementation).

    ``step(state, batches, rng)`` with ``batches`` a list of (x_c, y_c),
    sizes per ``client_batch_sizes``. Kept for parity tests and as the
    baseline in ``benchmarks/trainer_perf.py``.
    """
    detached = tc.mode == "detached"
    guard = PrivacyGuard.from_config(tc.privacy)

    def init_state(key):
        k0, *cks = jax.random.split(key, tc.n_clients + 1)
        ref = adapter.init(k0)
        server_params = ref["server"]
        client_banks = [adapter.init(k)["client"] for k in cks]
        trainable = server_params if detached else (client_banks, server_params)
        return {
            "client_banks": client_banks,
            "server": server_params,
            "opt": opt.init(trainable),
            "step": jnp.zeros((), jnp.int32),
            "privacy": budget_init(),
        }

    def loss_from(client_banks, server_params, batches, noise_keys):
        feats, labels = [], []
        for c, (x_c, y_c) in enumerate(batches):
            f = adapter.client_forward(client_banks[c], x_c, noise_keys[c])
            if guard.enabled:
                # same fold-in schedule as the fused engines' vmapped guard,
                # so looped and fused releases draw identical noise
                f = guard(guard.key_for(noise_keys[c]), f)
            if detached:
                f = jax.lax.stop_gradient(f)
            feats.append(f)
            labels.append(y_c)
        fcat = jnp.concatenate(feats, axis=0)  # paper Alg.1 l.11: concat features
        ycat = jnp.concatenate(labels, axis=0)
        out = adapter.server_forward(server_params, fcat)
        return adapter.loss(out, ycat), (out, ycat)

    # looped reference step: cross-checks the fused engines on one
    # shared state; donation would free buffers the harness still reads
    @jax.jit  # splitlint: ignore[JAX205]
    def step(state, batches, rng):
        noise_keys = list(jax.random.split(rng, tc.n_clients))
        if detached:

            def lf(server_params):
                return loss_from(state["client_banks"], server_params, batches, noise_keys)

            (loss, (out, ycat)), grads = jax.value_and_grad(lf, has_aux=True)(state["server"])
            grads, gnorm = clip_by_global_norm(grads, tc.grad_clip)
            updates, new_opt = opt.update(grads, state["opt"], state["server"], state["step"])
            new_server = apply_updates(state["server"], updates)
            new_state = {**state, "server": new_server, "opt": new_opt, "step": state["step"] + 1}
        else:

            def lf(trainable):
                cb, sp = trainable
                return loss_from(cb, sp, batches, noise_keys)

            trainable = (state["client_banks"], state["server"])
            (loss, (out, ycat)), grads = jax.value_and_grad(lf, has_aux=True)(trainable)
            grads, gnorm = clip_by_global_norm(grads, tc.grad_clip)
            updates, new_opt = opt.update(grads, state["opt"], trainable, state["step"])
            new_cb, new_server = apply_updates(trainable, updates)
            new_state = {
                **state,
                "client_banks": new_cb,
                "server": new_server,
                "opt": new_opt,
                "step": state["step"] + 1,
            }
        new_state["privacy"] = budget_advance(state["privacy"], tc.privacy)
        metrics = adapter.metrics(out, ycat)
        metrics["grad_norm"] = gnorm
        return new_state, metrics

    return init_state, step


def make_server_bank_runner(adapter: SplitAdapter, opt: Optimizer,
                            grad_clip: float = 1.0, *, unroll: int = 1,
                            mesh: Optional[Mesh] = None):
    """The fused-queue engine's server half: replay a stacked bank of queue
    arrivals as ONE ``lax.scan`` of trunk updates.

    Returns ``run_bank(server_params, opt_state, step0, features, labels,
    valid) -> (server_params, opt_state, step, losses)`` where ``features``
    is ``[K, b, ...]`` released feature slots in queue order, ``labels`` is
    ``[K, b, ...]`` and ``valid`` is a ``[K]`` bool mask (zero-padded slots
    of a partially filled ``core.queue.FeatureBank`` are masked out and
    become identity updates — params, moments and the step counter all hold
    still, and the slot's loss is reported as NaN so it can't silently leak
    into an epoch mean).

    The per-slot math is deliberately the SAME op sequence as
    ``protocol.SplitServer._step`` — ``value_and_grad`` of the adapter loss,
    leaf-wise ``clip_by_global_norm``, ``opt.update``, ``apply_updates`` —
    so a σ=0 fused-queue epoch is bit-identical to protocol-async stepping
    the same items one pop at a time; the scan only removes the per-item
    dispatch (one compiled program per epoch instead of K). ``unroll``
    DEFAULTS TO 1 because that bit-exactness is part of the engine's
    contract: unrolling lets XLA fuse across iterations, which reassociates
    the backward/clip reductions (measured: unroll=2 already diverges in the
    last fp32 bit while every per-slot loss still matches).

    Deliberately NOT donating the params/opt arguments: the fused-queue
    engine interchanges checkpoints and recovery semantics with
    protocol-async, which never invalidates the session's stored state — a
    fit that raises mid-run must leave ``session.state`` readable. The cost
    is one trunk-sized copy per EPOCH (not per step), noise on this path.

    ``mesh=`` (a ``make_split_mesh`` grid) makes the replay tensor-parallel:
    the trunk params AND the optimizer moment trees are constrained to their
    ``trunk_specs`` layouts on entry, the scan carry keeps those layouts, so
    every slot's forward/backward matmuls partition over the model axis with
    an all-gather only at the cut (the banked features stay replicated) and
    at the logits. The per-slot op sequence is unchanged — a mesh whose
    model axis has size 1 is the same program, preserving the σ=0 parity
    contract with ``protocol.SplitServer``."""
    shard_trunk = _trunk_sharder(mesh)

    @jax.jit
    def run_bank(server_params, opt_state, step0, features, labels, valid):
        server_params = shard_trunk(server_params)
        opt_state = shard_trunk(opt_state)
        def body(carry, slot):
            params, opt_state, step = carry
            feats, labs, ok = slot

            def lf(p):
                out = adapter.server_forward(p, feats)
                return adapter.loss(out, labs)

            loss, grads = jax.value_and_grad(lf)(params)
            grads, _ = clip_by_global_norm(grads, grad_clip)
            updates, new_opt = opt.update(grads, opt_state, params, step)
            new_params = apply_updates(params, updates)
            params = jax.tree.map(lambda old, new: jnp.where(ok, new, old),
                                  params, new_params)
            opt_state = jax.tree.map(lambda old, new: jnp.where(ok, new, old),
                                     opt_state, new_opt)
            step = jnp.where(ok, step + 1, step)
            return (params, opt_state, step), jnp.where(ok, loss, jnp.nan)

        (server_params, opt_state, step), losses = jax.lax.scan(
            body, (server_params, opt_state, jnp.asarray(step0, jnp.int32)),
            (features, labels, valid),
            unroll=min(unroll, features.shape[0]),
        )
        return server_params, opt_state, step, losses

    return run_bank


def make_single_client_step(adapter: SplitAdapter, tc: SplitTrainConfig, opt: Optimizer):
    """The baseline: ONE client + server (conventional split learning)."""
    single = dataclasses.replace(tc, n_clients=1, data_shares=(1.0,))
    return make_spatio_temporal_step(adapter, single, opt)


# ------------------------------------------------------------------- loops
def device_put_shards(
    shards: Sequence[Tuple[np.ndarray, np.ndarray]]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Stack ragged per-client shards into padded device arrays.

    Returns (data_x [C, N_max, ...], data_y [C, N_max, ...], lens [C]).
    Float padding is NaN on purpose: the on-device sampler draws indices in
    [0, lens[c]), so any bug that reads padding poisons the loss visibly.
    """
    assert all(len(x) > 0 for x, _ in shards), "empty client shard"
    n_max = max(len(x) for x, _ in shards)

    def pad(a):
        a = np.asarray(a)
        if len(a) == n_max:
            return a
        fill = np.nan if np.issubdtype(a.dtype, np.floating) else 0
        p = np.full((n_max - len(a),) + a.shape[1:], fill, a.dtype)
        return np.concatenate([a, p], axis=0)

    data_x = jnp.asarray(np.stack([pad(x) for x, _ in shards]))
    data_y = jnp.asarray(np.stack([pad(y) for _, y in shards]))
    lens = jnp.asarray([len(x) for x, _ in shards], jnp.int32)
    return data_x, data_y, lens


def make_sample_plan(tc: SplitTrainConfig, steps_per_epoch: int):
    """Jitted (lens [C], epoch_key) -> (idx [T, C, b], step_keys [T, 2]): the
    whole epoch's on-device batch plan from one key. Shared by the fused
    runners and the looped reference engine so that, at equal per-client
    batch sizes, every engine consumes byte-identical batches."""
    c, b = tc.n_clients, fused_client_batch(tc)

    @jax.jit
    def sample_plan(lens, epoch_key):
        k_idx, k_noise = jax.random.split(epoch_key)
        idx = jax.random.randint(
            k_idx, (steps_per_epoch, c, b), 0, lens[None, :, None]
        )
        return idx, jax.random.split(k_noise, steps_per_epoch)

    return sample_plan


def make_epoch_runner(
    adapter: SplitAdapter,
    tc: SplitTrainConfig,
    opt: Optimizer,
    steps_per_epoch: int,
    *,
    unroll: int = 8,
    mode: str = "scan",
    mesh: Optional[Mesh] = None,
):
    """Returns (init_state, run_epoch). ``run_epoch(state, data_x, data_y,
    lens, epoch_key)`` runs ``steps_per_epoch`` fused steps with all batch
    sampling on device (one randint for every step's indices, one split for
    every step's noise key — no per-step host RNG or host->device copies)
    and returns (new_state, metrics) with each metric stacked over steps.

    ``mode="scan"`` (default): the whole epoch is ONE jitted ``lax.scan``
    dispatch with the carry donated and the trainable pytree flattened into
    a single scan-carried buffer; ``unroll`` amortizes XLA's per-iteration
    while-loop overhead. CAVEAT: XLA:CPU compiles loop bodies without the
    parallel task scheduler, so on CPU the scan only pays off for small
    per-step compute — use ``mode="stepwise"`` (one donated-state dispatch
    per step, sampling still on device) for heavy models on CPU.
    ``train_spatio_temporal`` picks automatically."""
    assert mode in ("scan", "stepwise"), mode
    init_state, step_core, trainable_of, with_trainable, step_flat = _make_fused(
        adapter, tc, opt, mesh=mesh
    )
    guard = PrivacyGuard.from_config(tc.privacy)
    take = jax.vmap(lambda d, ix: jnp.take(d, ix, axis=0))
    sample_plan = make_sample_plan(tc, steps_per_epoch)

    # The epoch's RNG — the batch-index plan and the hoisted guard-noise
    # buffer — runs as its OWN jit dispatches, never inlined into the
    # mesh-partitioned epoch program. Under a multi-axis mesh with
    # committed-sharded inputs, GSPMD may spatially partition an inlined
    # threefry in value-changing ways (the legacy non-partitionable
    # implementation gives no sharding-invariance guarantee), so the scan
    # runner mirrors the structure that makes the stepwise runner immune:
    # draw on replicated inputs first, feed the arrays in as operands.
    _noise_draw_cache = {}  # feat shape -> jitted epoch-noise draw

    def _epoch_noise(state, data_x, step_keys):
        """Pre-draw the epoch's release noise [T, C, b, ...] — the same
        per-(step, client) keys the in-body release would fold, so scan and
        stepwise releases stay bit-identical. Returns None when the buffer
        would exceed the 64MB fp32 cap (mirrors the _auto_epoch_mode size
        guard); the keyed in-body path is bit-identical, just slower."""
        bank0 = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
            state["client_banks"],
        )
        x0 = jax.ShapeDtypeStruct(
            (fused_client_batch(tc),) + tuple(data_x.shape[2:]), data_x.dtype
        )
        k0 = jax.ShapeDtypeStruct(step_keys.shape[1:], step_keys.dtype)
        feat = jax.eval_shape(adapter.client_forward, bank0, x0, k0)
        epoch_elems = steps_per_epoch * tc.n_clients * int(np.prod(feat.shape))
        if epoch_elems > (1 << 24):
            return None
        draw = _noise_draw_cache.get(feat.shape)
        if draw is None:

            def step_noise(key):
                cks = jax.random.split(key, tc.n_clients)
                with jax.named_scope("guard"):
                    gks = guard.keys_for(cks)
                    return jax.vmap(
                        lambda k: jax.random.normal(k, feat.shape, jnp.float32)
                    )(gks)

            draw = jax.jit(jax.vmap(step_noise))
            _noise_draw_cache[feat.shape] = draw
        return draw(step_keys)

    @partial(jax.jit, donate_argnums=(0,))
    def _run_epoch_scan(state, data_x, data_y, idx, step_keys, guard_noise):
        flat, unravel = ravel_pytree(trainable_of(state))
        banks = state["client_banks"]  # scan-invariant in detached mode
        xs_extra = () if guard_noise is None else (guard_noise,)
        opt0 = state["opt"]
        if mesh is not None:
            # The scan carry must NOT inherit the committed trunk-sharded
            # layout: raveling sharded server leaves into one flat buffer
            # hands the carry a concatenation-of-shards layout that the SPMD
            # partitioner miscompiles on multi-axis grids (wrong loss from
            # step 0, NaN within a few steps on a 4x2 mesh, XLA:CPU). Pin
            # the carried buffers replicated — bit-exact vs the unsharded
            # scan — and let loss_from's shard_trunk re-shard the unraveled
            # leaves inside each step for the tensor-parallel matmuls.
            rep = NamedSharding(mesh, P())
            flat = jax.lax.with_sharding_constraint(flat, rep)
            opt0 = jax.tree.map(
                lambda a: jax.lax.with_sharding_constraint(a, rep), opt0
            )

        def body(carry, inp):
            fl, opt_state, step = carry
            idx_t, key_t, *noise_t = inp
            fl, opt_state, metrics = step_flat(
                fl, opt_state, step, banks, unravel,
                take(data_x, idx_t), take(data_y, idx_t), key_t, *noise_t,
            )
            return (fl, opt_state, step + 1), metrics

        (flat, opt_state, step), ms = jax.lax.scan(
            body, (flat, opt0, state["step"]), (idx, step_keys) + xs_extra,
            unroll=min(unroll, steps_per_epoch),
        )
        new_state = with_trainable(state, unravel(flat), opt_state)
        new_state["step"] = step
        # the budget leaves stay OUT of the scan carry (they are a pure
        # function of the step count); advance once for the whole epoch
        new_state["privacy"] = budget_advance(
            state["privacy"], tc.privacy, steps_per_epoch
        )
        return new_state, ms

    def run_epoch_scan(state, data_x, data_y, lens, epoch_key):
        idx, step_keys = sample_plan(lens, epoch_key)
        guard_noise = None
        if guard.enabled and guard.sigma > 0.0:
            guard_noise = _epoch_noise(state, data_x, step_keys)
        return _run_epoch_scan(state, data_x, data_y, idx, step_keys,
                               guard_noise)

    @partial(jax.jit, donate_argnums=(0,))
    def step_once(state, data_x, data_y, idx_t, key_t):
        return step_core(state, take(data_x, idx_t), take(data_y, idx_t), key_t)

    def run_epoch_stepwise(state, data_x, data_y, lens, epoch_key):
        idx, step_keys = sample_plan(lens, epoch_key)
        ms = []
        for t in range(steps_per_epoch):
            state, m = step_once(state, data_x, data_y, idx[t], step_keys[t])
            ms.append(m)
        return state, {k: jnp.stack([m[k] for m in ms]) for k in ms[0]}

    return init_state, (run_epoch_scan if mode == "scan" else run_epoch_stepwise)


def _epoch_batches(
    rng: np.random.Generator,
    shards: Sequence[Tuple[np.ndarray, np.ndarray]],
    sizes: Sequence[int],
    steps: int,
):
    """Seed host-side sampler (kept for the looped reference path): one
    np.random draw + host->device copy per client per step."""
    for _ in range(steps):
        batch = []
        for (x, y), b in zip(shards, sizes):
            idx = rng.integers(0, len(x), size=b)
            batch.append((jnp.asarray(x[idx]), jnp.asarray(y[idx])))
        yield batch


def _auto_epoch_mode(shards, tc: SplitTrainConfig) -> str:
    """scan on accelerators; on CPU only while the per-step input volume is
    small enough that XLA:CPU's serial while-loop codegen still wins over
    per-step dispatch (heavy bodies lose their intra-op parallelism there).

    The threshold depends on the host TOPOLOGY, not just the backend: on
    the default 1-device CPU the crossover sits at 32768 elements, but a
    forced multi-device topology (the CI mesh job's
    ``--xla_force_host_platform_device_count=8``) carves the intra-op
    thread pool per device, shrinking exactly the parallelism stepwise
    trades on — re-measured there the crossover doubles to 65536 (scan
    +15% at 65536, parity-within-noise above 131072; methodology in
    docs/engines.md)."""
    if jax.default_backend() in ("tpu", "gpu"):
        return "scan"
    elems = tc.n_clients * fused_client_batch(tc) * int(
        np.prod(np.asarray(shards[0][0]).shape[1:])
    )
    threshold = 32768 if len(jax.devices()) == 1 else 65536
    return "scan" if elems <= threshold else "stepwise"


def train_spatio_temporal(
    adapter: SplitAdapter,
    tc: SplitTrainConfig,
    opt: Optimizer,
    shards: Sequence[Tuple[np.ndarray, np.ndarray]],
    *,
    epochs: int,
    steps_per_epoch: int,
    seed: int = 0,
    eval_fn: Optional[Callable[[Any], Dict[str, float]]] = None,
    epoch_mode: Optional[str] = None,
) -> Tuple[Any, List[Dict[str, float]]]:
    """Deprecated shim: use ``repro.core.session.SplitSession`` (engine
    ``auto`` / ``fused-scan`` / ``fused-stepwise``). Same key schedule, so the
    numbers are unchanged."""
    warnings.warn(
        "train_spatio_temporal is deprecated; use repro.core.session.SplitSession",
        DeprecationWarning, stacklevel=2,
    )
    from repro.core.session import SplitSession

    engine = {None: "auto", "scan": "fused-scan", "stepwise": "fused-stepwise"}[epoch_mode]
    session = SplitSession(adapter, tc, opt, engine=engine, seed=seed)
    history = session.fit(
        shards, epochs=epochs, steps_per_epoch=steps_per_epoch, eval_fn=eval_fn
    )
    return session.state, history


def train_single_client(
    adapter: SplitAdapter,
    tc: SplitTrainConfig,
    opt: Optimizer,
    shard: Tuple[np.ndarray, np.ndarray],
    *,
    epochs: int,
    steps_per_epoch: int,
    seed: int = 0,
    eval_fn: Optional[Callable[[Any], Dict[str, float]]] = None,
):
    """Deprecated shim: use ``SplitSession`` with ``single_client_config``."""
    warnings.warn(
        "train_single_client is deprecated; use "
        "SplitSession(adapter, single_client_config(tc), opt)",
        DeprecationWarning, stacklevel=2,
    )
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return train_spatio_temporal(
            adapter, single_client_config(tc), opt, [shard],
            epochs=epochs, steps_per_epoch=steps_per_epoch, seed=seed, eval_fn=eval_fn,
        )


def single_client_config(tc: SplitTrainConfig) -> SplitTrainConfig:
    """The conventional-split-learning baseline config: ONE client, all data."""
    return dataclasses.replace(tc, n_clients=1, data_shares=(1.0,))


# --------------------------------------------------------------------- eval
@partial(jax.jit, static_argnums=(0,))
def _eval_fwd(adapter: SplitAdapter, client, server, xb):
    # adapter is static (frozen dataclass, hashed by identity), so the
    # compiled forward is shared across client banks and evaluate() calls
    # eval-only forward (noise_key=None disables the stochastic path);
    # metrics are computed on data the evaluator already holds
    return adapter.server_forward(server, adapter.client_forward(client, xb, None))  # splitlint: ignore[SPL101]


def _eval_forward(adapter: SplitAdapter, client, server, x, batch: int):
    outs = []
    for i in range(0, len(x), batch):
        outs.append(np.asarray(_eval_fwd(adapter, client, server, jnp.asarray(x[i : i + batch]))))
    return jnp.asarray(np.concatenate(outs, axis=0))


def stack_pytrees(trees: Sequence[Any]) -> Any:
    """[tree, tree, ...] -> one tree whose leaves gain a leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def unstack_pytree(tree: Any, n: int) -> List[Any]:
    """Inverse of ``stack_pytrees`` for a known leading-axis length."""
    return [jax.tree.map(lambda a, c=c: a[c], tree) for c in range(n)]


def _client_banks_list(banks) -> List[Any]:
    """Canonical stacked banks (or the looped path's list) -> list of banks."""
    if isinstance(banks, (list, tuple)):
        return list(banks)
    return unstack_pytree(banks, jax.tree.leaves(banks)[0].shape[0])


def evaluate(adapter: SplitAdapter, state, x, y, batch: int = 512) -> Dict[str, float]:
    """Full-model eval using client bank 0 (server-side metric suite)."""
    client0 = _client_banks_list(state["client_banks"])[0]
    out = _eval_forward(adapter, client0, state["server"], x, batch)
    return {k: float(v) for k, v in adapter.metrics(out, jnp.asarray(y)).items()}


def evaluate_per_client(
    adapter: SplitAdapter, state, x, y, *,
    batch: int = 512, weights: Optional[Sequence[float]] = None,
    identical_banks: bool = False,
) -> Dict[str, Any]:
    """One eval pass PER client bank over the canonical state.

    Returns the share-weighted mean of every metric at the top level plus
    ``"per_client"``: a list of each hospital's own metric dict (its privacy
    layer + the shared trunk). ``weights`` defaults to uniform.
    ``identical_banks=True`` (e.g. FedAvg's tiled global client block) scores
    one bank and replicates the row instead of running n equal passes."""
    banks = _client_banks_list(state["client_banks"])
    y = jnp.asarray(y)
    per = []
    for client in banks[:1] if identical_banks else banks:
        out = _eval_forward(adapter, client, state["server"], x, batch)
        per.append({k: float(v) for k, v in adapter.metrics(out, y).items()})
    if identical_banks:
        per = per * len(banks)
    if weights is None:
        weights = [1.0 / len(banks)] * len(banks)
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    result: Dict[str, Any] = {
        k: float(sum(wc * p[k] for wc, p in zip(w, per))) for k in per[0]
    }
    result["per_client"] = per
    return result
