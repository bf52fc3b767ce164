"""One ``SplitSession`` over every execution regime of the paper's platform.

The paper's protocol — a privacy-preserving layer at each hospital, the trunk
at the central server — runs in this repo under several regimes: the fused
SPMD engine (scan or stepwise epochs), the seed per-client reference loop,
the wall-clock asynchronous queue protocol, the fused-queue bridge (queue
arrivals replayed through the scanned server path), and the FedAvg baseline.
Each
used to be its own entry point with its own state shape; ``SplitSession``
drives all of them through ONE signature and ONE canonical state pytree, so
checkpointing, evaluation, DP release and the inversion privacy metric apply
uniformly to any regime.

Canonical state::

    {
      "client_banks": pytree, every leaf with a leading [n_clients] axis,
      "server":       server trunk params,
      "opt":          engine-native optimizer state (fused: one flat buffer;
                      looped/protocol: moment trees; fedavg: {}),
      "step":         int32 progress counter in the engine's native unit
                      (fused/looped: optimizer steps; protocol: server steps;
                      fedavg: rounds),
      "privacy":      the (ε, δ) accountant's budget leaves (int32 release
                      count + float32 basic-composition spend) — advanced by
                      every engine's guard applications and checkpointed
                      with the rest of the state,
    }

Engines register by name (see ``available_engines()``); ``engine="auto"``
picks the fused engine and folds in the scan-vs-stepwise backend heuristic
(``_auto_epoch_mode``). ``mesh=`` accepts a 1-D client mesh
(``launch.mesh.make_client_mesh``) or the 2-D ``("clients", "model")`` grid
(``launch.mesh.make_split_mesh``): the canonical leading client axis shards
over ``"clients"`` with ``jax.shard_map`` so each hospital's privacy layer
runs on its own device, and the server trunk (plus its moment trees) shards
tensor-parallel over ``"model"`` via ``repro.sharding.specs.trunk_specs`` —
for the fused engines AND the queue engines (``SplitServer`` steps and the
banked replay both constrain the trunk; ``FleetProducer`` keeps production
on the client axis). On a 1x1 (or single-device) mesh every path is a
bit-exact no-op, asserted by the CPU parity tests and the
``tests/test_mesh_2d.py`` sweep.

Role in the engine registry: this module IS the registry (the
``register_engine`` decorator and every built-in engine class — fused
scan/stepwise/auto, looped-ref, protocol-async, fused-queue, fedavg), plus
the ``SplitSession`` facade over it. It owns no state leaves itself — each
engine's ``to_canonical``/``from_canonical`` pair is the lossless contract
between its native layout and the five canonical leaves above, and the
session only ever stores the native form, converting on demand. See
docs/engines.md for the regimes end-to-end.

    session = SplitSession(adapter, SplitTrainConfig(...), adamw(1e-3))
    session.fit(shards, epochs=30, steps_per_epoch=10)
    session.evaluate(x_test, y_test)   # per-client + share-weighted mean
    session.save("ckpts/")             # canonical state -> npz + manifest
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Protocol, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint.io import load_checkpoint, save_checkpoint
from repro.core import fedavg as fedavg_mod
from repro.core import protocol as protocol_mod
from repro.core.adapters import SplitAdapter
from repro.core.distributed import LLMSplitAdapter, init_llm_state, make_guarded_llm_step
from repro.core.faults import ClientLoopError, FaultPlan
from repro.core.queue import FeatureBank, FeatureQueue
from repro.core.trainer import (
    CLIENT_AXIS,
    SplitTrainConfig,
    _auto_epoch_mode,
    _client_banks_list,
    client_weights,
    device_put_shards,
    evaluate_per_client,
    finite_mean,
    fused_client_batch,
    make_epoch_runner,
    make_looped_step,
    make_sample_plan,
    make_server_bank_runner,
    make_spatio_temporal_step,
    stack_pytrees,
    unstack_pytree,
)
from repro.optim.optimizers import Optimizer
from repro.privacy.accountant import (
    budget_advance,
    budget_init,
    budget_report,
    per_client_report,
)
from repro.privacy.audit import guard_noise_sweep
from repro.privacy.guard import PrivacyGuard

Shards = Sequence[Tuple[np.ndarray, np.ndarray]]
EvalFn = Optional[Callable[[Any], Dict[str, float]]]


class Engine(Protocol):
    """What an execution regime must provide to ride behind ``SplitSession``.

    ``run`` consumes and returns ENGINE-NATIVE state; ``to_canonical`` /
    ``from_canonical`` convert losslessly to/from the canonical pytree (the
    fused engines' native state IS canonical). ``eval_fn`` passed to ``run``
    always receives the canonical state.
    """

    name: str

    def init(self, key) -> Any: ...

    def run(self, state, shards: Shards, *, epochs: int, steps_per_epoch: int,
            eval_fn: EvalFn = None) -> Tuple[Any, List[Dict[str, float]]]: ...

    def to_canonical(self, state) -> Any: ...

    def from_canonical(self, canonical) -> Any: ...


_ENGINES: Dict[str, Callable[..., Engine]] = {}


def register_engine(name: str):
    def deco(factory):
        _ENGINES[name] = factory
        return factory
    return deco


def available_engines() -> List[str]:
    return sorted(_ENGINES)


def _seed_from_key(key) -> int:
    """Low word of an old-style PRNGKey == the int seed it was built from
    (gives the host-side RNG engines the same seed the caller passed)."""
    if not jnp.issubdtype(key.dtype, jnp.integer):  # new-style typed key
        key = jax.random.key_data(key)
    return int(np.asarray(key).ravel()[-1])


# ------------------------------------------------------------ fused engines
class FusedEngine:
    """The throughput path (PR 1): stacked banks + vmapped privacy layer,
    on-device sampling, scanned or stepwise epochs. Native state IS the
    canonical state. ``mode=None`` ("auto") folds in ``_auto_epoch_mode``
    per fit call. Honors both mesh axes: client banks + epoch data shard
    over ``"clients"``, the trunk tensor-parallel over ``"model"``."""

    def __init__(self, adapter: SplitAdapter, tc: SplitTrainConfig,
                 opt: Optimizer, *, mesh: Optional[Mesh] = None,
                 mode: Optional[str] = None, unroll: int = 8):
        assert mode in (None, "scan", "stepwise"), mode
        self.name = "auto" if mode is None else f"fused-{mode}"
        self.adapter, self.tc, self.opt = adapter, tc, opt
        self.mesh, self.mode, self.unroll = mesh, mode, unroll
        self._init_state, _ = make_spatio_temporal_step(adapter, tc, opt, mesh=mesh)
        self._runners: Dict[Tuple[int, str], Callable] = {}
        self._epochs_done = 0
        # the epoch mode the last fit ran ("scan" | "stepwise")
        self.epoch_mode: Optional[str] = None

    def init(self, key):
        self._root = key
        self._epochs_done = 0
        return self._init_state(key)

    def _runner(self, steps_per_epoch: int, mode: str):
        runner = self._runners.get((steps_per_epoch, mode))
        if runner is None:
            _, runner = make_epoch_runner(
                self.adapter, self.tc, self.opt, steps_per_epoch,
                unroll=self.unroll, mode=mode, mesh=self.mesh,
            )
            self._runners[(steps_per_epoch, mode)] = runner
        return runner

    def _place(self, state, data_x, data_y):
        """Shard the client axis of the banks + epoch data over the mesh so
        the shard_mapped privacy layer reads device-local operands; on a 2-D
        grid also pre-place the server trunk in its ``trunk_specs`` layout
        (the in-step constraint would reshard it anyway — placing it here
        once, including right after a cross-shape ``restore()``, avoids a
        per-epoch host-layout transfer)."""
        if self.mesh is None:
            return state, data_x, data_y
        from repro.core.trainer import MODEL_AXIS
        from repro.sharding.specs import client_bank_specs, trunk_shardings

        specs = client_bank_specs(state["client_banks"], self.mesh, CLIENT_AXIS)
        banks = jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(self.mesh, s)),
            state["client_banks"], specs,
        )
        state = {**state, "client_banks": banks}
        if (MODEL_AXIS in self.mesh.axis_names
                and self.mesh.shape[MODEL_AXIS] > 1):
            state["server"] = jax.device_put(
                state["server"], trunk_shardings(state["server"], self.mesh)
            )
        data_sh = NamedSharding(self.mesh, P(CLIENT_AXIS))
        return (
            state,
            jax.device_put(data_x, data_sh),
            jax.device_put(data_y, data_sh),
        )

    def run(self, state, shards, *, epochs, steps_per_epoch, eval_fn=None):
        assert len(shards) == self.tc.n_clients
        mode = self.epoch_mode = self.mode or _auto_epoch_mode(shards, self.tc)
        run_epoch = self._runner(steps_per_epoch, mode)
        # host spans (``jax.profiler``): the upload once per call, the
        # epoch's enqueue and its readout once per epoch
        with jax.profiler.TraceAnnotation("repro.fit.upload"):
            data_x, data_y, lens = device_put_shards(shards)
            state, data_x, data_y = self._place(state, data_x, data_y)
        history = []
        for ep in range(epochs):
            self._epochs_done += 1
            with jax.profiler.TraceAnnotation("repro.fit.dispatch"):
                state, ms = run_epoch(
                    state, data_x, data_y, lens,
                    jax.random.fold_in(self._root, self._epochs_done),
                )
            with jax.profiler.TraceAnnotation("repro.fit.readout"):
                ms = jax.device_get(ms)  # single readout per epoch
                rec = {k: float(np.mean(v)) for k, v in ms.items()}
                rec["epoch"] = ep
            if eval_fn is not None:
                rec.update({f"val_{k}": v for k, v in eval_fn(state).items()})
            history.append(rec)
        return state, history

    def to_canonical(self, state):
        return state

    def from_canonical(self, canonical):
        return canonical


def _fused_factory(mode):
    def factory(adapter, tc, opt, *, mesh=None, **kw):
        return FusedEngine(adapter, tc, opt, mesh=mesh, mode=mode, **kw)
    return factory


register_engine("auto")(_fused_factory(None))
register_engine("fused-scan")(_fused_factory("scan"))
register_engine("fused-stepwise")(_fused_factory("stepwise"))


# ---------------------------------------------------------- looped reference
@register_engine("looped-ref")
class LoopedEngine:
    """The seed per-client Python-loop step behind the session surface.

    Batches come from the SAME on-device sample plan as the fused engines
    (homogeneous per-client size ``fused_client_batch``), so with uniform
    shares the looped and fused engines consume byte-identical batches and
    their losses agree to fp32 reassociation."""

    name = "looped-ref"

    def __init__(self, adapter: SplitAdapter, tc: SplitTrainConfig,
                 opt: Optimizer, *, mesh: Optional[Mesh] = None):
        if mesh is not None:
            raise ValueError("looped-ref does not support mesh=; use a fused engine")
        self.adapter, self.tc, self.opt = adapter, tc, opt
        self.detached = tc.mode == "detached"
        self._init_state, self._step = make_looped_step(adapter, tc, opt)
        self._plans: Dict[int, Callable] = {}
        self._epochs_done = 0

    def init(self, key):
        self._root = key
        self._epochs_done = 0
        return self._init_state(key)

    def run(self, state, shards, *, epochs, steps_per_epoch, eval_fn=None):
        assert len(shards) == self.tc.n_clients
        plan = self._plans.setdefault(
            steps_per_epoch, make_sample_plan(self.tc, steps_per_epoch)
        )
        xs = [np.asarray(x) for x, _ in shards]
        ys = [np.asarray(y) for _, y in shards]
        lens = jnp.asarray([len(x) for x in xs], jnp.int32)
        history = []
        for ep in range(epochs):
            self._epochs_done += 1
            idx, step_keys = plan(lens, jax.random.fold_in(self._root, self._epochs_done))
            idx = np.asarray(idx)
            ms = []
            for t in range(steps_per_epoch):
                batches = [
                    (jnp.asarray(xs[c][idx[t, c]]), jnp.asarray(ys[c][idx[t, c]]))
                    for c in range(self.tc.n_clients)
                ]
                state, m = self._step(state, batches, step_keys[t])
                ms.append(m)
            rec = {k: float(np.mean([float(m[k]) for m in ms])) for k in ms[0]}
            rec["epoch"] = ep
            if eval_fn is not None:
                rec.update({f"val_{k}": v for k, v in eval_fn(self.to_canonical(state)).items()})
            history.append(rec)
        return state, history

    def _map_trainable_banks(self, opt_state, fn):
        """Apply ``fn`` to the banks half of every trainable-shaped moment in
        the optimizer state (e2e trainable = (banks, server))."""
        if self.detached:
            return opt_state  # moments are server-shaped: nothing banked
        return {k: (fn(v[0]), v[1]) for k, v in opt_state.items()}

    def to_canonical(self, state):
        return {
            "client_banks": stack_pytrees(state["client_banks"]),
            "server": state["server"],
            "opt": self._map_trainable_banks(state["opt"], stack_pytrees),
            "step": jnp.asarray(state["step"], jnp.int32),
            "privacy": state["privacy"],
        }

    def from_canonical(self, canonical):
        n = self.tc.n_clients
        return {
            "client_banks": unstack_pytree(canonical["client_banks"], n),
            "server": canonical["server"],
            "opt": self._map_trainable_banks(
                canonical["opt"], lambda t: unstack_pytree(t, n)
            ),
            "step": canonical["step"],
            "privacy": canonical["privacy"],
        }


# ------------------------------------------------------------ async protocol
@register_engine("protocol-async")
class ProtocolEngine:
    """The wall-clock-faithful two-program protocol (``core.protocol``)
    behind the session surface: real client/server objects communicating
    only through a ``FeatureQueue``. One ``steps_per_epoch`` = one server
    queue pop + trunk update. ``threaded=False`` is the deterministic
    round-robin mode (used by the parity tests). ``production="fleet"``
    (default) batches the fleet's releases — one vmapped dispatch per queue
    cycle over the stacked client banks, bit-identical per item to
    ``production="per-item"`` (see ``protocol.FleetProducer``).

    ``mesh=`` (a ``make_split_mesh`` grid) splits the protocol across both
    axes of the cut: fleet production places the stacked banks over
    ``"clients"``, and every ``SplitServer`` trunk update runs
    tensor-parallel over ``"model"`` (``trunk_specs`` constraints inside
    the jitted step). The queue itself — the trust boundary — stays a host
    object; only what was already crossing it is placed."""

    name = "protocol-async"

    def __init__(self, adapter: SplitAdapter, tc: SplitTrainConfig,
                 opt: Optimizer, *, mesh: Optional[Mesh] = None,
                 threaded: bool = False, client_batch: Optional[int] = None,
                 queue_size: int = 64, per_client_cap: Optional[int] = None,
                 production: str = "fleet", fleet_chunk: int = 8,
                 pop_timeout: float = 1.0, pop_retries: int = 0,
                 pop_backoff: float = 2.0):
        if (mesh is not None and CLIENT_AXIS in mesh.axis_names
                and tc.n_clients % mesh.shape[CLIENT_AXIS] != 0):
            raise ValueError(
                f"n_clients={tc.n_clients} does not divide over mesh axis "
                f"{CLIENT_AXIS!r} of size {mesh.shape[CLIENT_AXIS]}; the "
                f"stacked client banks shard their leading axis evenly"
            )
        if tc.mode != "detached":
            raise ValueError(
                f"{self.name} trains the server trunk only (the paper's "
                "detached regime); mode='e2e' needs a fused or looped engine"
            )
        if production not in ("fleet", "per-item"):
            raise ValueError(
                f"production must be 'fleet' or 'per-item', got {production!r}"
            )
        if fleet_chunk < 1:
            # a 0-item chunk would starve the threaded client loops forever
            # (empty production deque -> dead producer threads -> the drive
            # spins on an empty queue); fail loud at construction instead
            raise ValueError(f"fleet_chunk must be >= 1, got {fleet_chunk}")
        if pop_timeout < 0:
            raise ValueError(f"pop_timeout must be >= 0, got {pop_timeout}")
        if pop_retries < 0:
            raise ValueError(f"pop_retries must be >= 0, got {pop_retries}")
        if pop_backoff < 1.0:
            # a shrinking backoff would busy-wait the starved consumer
            raise ValueError(f"pop_backoff must be >= 1.0, got {pop_backoff}")
        self.adapter, self.tc, self.opt = adapter, tc, opt
        self.mesh = mesh
        self.threaded = threaded
        self.client_batch = client_batch or fused_client_batch(tc)
        self.queue_size, self.per_client_cap = queue_size, per_client_cap
        # the threaded consumer's pop wait + exponential-backoff retries
        # (server-side graceful degradation under stragglers/dropout)
        self.pop_timeout, self.pop_retries = pop_timeout, pop_retries
        self.pop_backoff = pop_backoff
        # production="fleet" (default): one vmapped release dispatch per
        # queue cycle over the stacked client banks, bit-identical per item
        # to "per-item" (one jitted dispatch per push — the PR 4 path, kept
        # as the parity reference). fleet_chunk is the threaded drive's
        # per-client lookahead (items per dispatch).
        self.production, self.fleet_chunk = production, fleet_chunk
        self.guard = PrivacyGuard.from_config(tc.privacy)
        # ONE jitted client release shared by the whole fleet across fits
        # (params are arguments, so per-client/per-fit retraces would only
        # re-derive the same program); ditto the fleet-batched release
        self._client_fwd = protocol_mod.make_client_release_fwd(adapter, self.guard)
        self._fleet_fwd = protocol_mod.make_fleet_release_fwd(adapter, self.guard)
        self.losses: List[float] = []
        self.stats: Dict[str, Any] = {}
        self.fault_stats: Dict[str, Any] = {}

    def init(self, key):
        self._noise_seed = _seed_from_key(key)
        self._root_key = key
        ref = self.adapter.init(key)
        banks = [
            self.adapter.init(jax.random.fold_in(key, c + 1))["client"]
            for c in range(self.tc.n_clients)
        ]
        return {
            "client_banks": banks,
            "server": ref["server"],
            "opt": self.opt.init(ref["server"]),
            "step": 0,
            "privacy": budget_init(),
        }

    def _noise_seed_for(self, step: int) -> int:
        """Per-run client RNG base (batch SAMPLING), advanced by consumed
        server steps so a second fit (or a restore-then-fit) draws FRESH
        batches instead of replaying the first fit's sequence. step=0 keeps
        the legacy ``run_protocol`` seed derivation — note the sampled
        index STREAM still differs from PR 2 (clients no longer interleave
        noise-seed draws into the sampling Generator; see ``SplitClient``)."""
        return self._noise_seed + 100003 * int(step)

    def _noise_key_for(self, step: int, client_id: int):
        """Per-client JAX noise base key, advanced by consumed server steps
        — the fold-in discipline all engines share (the clients fold their
        own per-push counter on top of this base)."""
        return jax.random.fold_in(
            jax.random.fold_in(self._root_key, int(step)), client_id
        )

    # clients keep host-NumPy releases here (the per-pop server step consumes
    # them from the host anyway); the fused-queue subclass flips this off
    _client_as_numpy = True
    # the queue engines accept fit(..., faults=FaultPlan): failures are a
    # property of the multi-site transport, which only these engines model
    supports_faults = True

    def _make_clients(self, state, shards):
        """The fleet, seeded from the consumed server step so a second fit
        (or a restore-then-fit) draws fresh batches — shared verbatim by
        protocol-async and fused-queue, which is half of their σ=0 parity."""
        return [
            protocol_mod.SplitClient(
                c, self.adapter, state["client_banks"][c], shards[c],
                batch=self.client_batch,
                noise_seed=self._noise_seed_for(state["step"]),
                noise_key=self._noise_key_for(state["step"], c),
                fwd=self._client_fwd, as_numpy=self._client_as_numpy,
            )
            for c in range(self.tc.n_clients)
        ]

    # ---- the two hooks that differ between the per-pop and banked servers
    def _make_consumer(self, state, queue):
        """The ``drive_protocol`` consumer for this engine."""
        return protocol_mod.SplitServer(
            self.adapter, state["server"], self.opt, queue,
            clip_norm=self.tc.grad_clip,
            opt_state=state["opt"], step_count=int(state["step"]),
            mesh=self.mesh,
        )

    def _make_fleet(self, clients):
        """The fleet-batched producer over this run's clients (banks are
        frozen for the whole run — these engines are structurally detached),
        or ``None`` in per-item mode."""
        if self.production != "fleet":
            return None
        return protocol_mod.FleetProducer(
            clients, self._fleet_fwd, chunk=self.fleet_chunk, mesh=self.mesh,
        )

    def _consume_epoch(self, consumer, clients, queue, shares, steps_per_epoch,
                       fleet=None, faults=None):
        """Drive one epoch through ``drive_protocol`` and return
        ``(losses, server_params, opt_state, step, drive_stats)``. Every
        line of bookkeeping AROUND this hook is shared with the fused-queue
        subclass — keeping the two engines' accounting in lockstep is what
        the σ=0 bit-parity contract rests on."""
        n_before = len(consumer.losses)
        d = protocol_mod.drive_protocol(
            clients, consumer, queue, shares,
            consumer.step_count + steps_per_epoch, threaded=self.threaded,
            fleet=fleet, faults=faults, pop_timeout=self.pop_timeout,
            pop_retries=self.pop_retries, pop_backoff=self.pop_backoff,
        )
        # slice by the count BEFORE the drive, not -steps_per_epoch: a
        # quorum halt can end an epoch short, and a fixed tail slice would
        # then reach back into the previous epoch's losses
        return (consumer.losses[n_before:], consumer.params,
                consumer.opt_state, consumer.step_count, d)

    def _assemble_fault_stats(self, frun, clients, error=None):
        """The ``fault_stats`` report beside ``queue_stats``: the plan, the
        halt state, per-client fault counters, per-client releases actually
        produced (a down hospital's counter holds still), and — when the
        guard is on — each hospital's own (ε, δ) spend this run."""
        fs: Dict[str, Any] = {
            "plan": None, "halted": False, "halt_reason": None,
            "client_error": None,
        }
        if frun is not None:
            fs.update(frun.stats())
        if clients is not None:
            produced = [int(c.releases) for c in clients]
            fs["releases_per_client"] = produced
            if self.guard.enabled:
                fs["per_client_privacy"] = per_client_report(
                    self.tc.privacy, produced
                )
        if error is not None:
            fs["client_error"] = repr(error.cause)
            fs["client_error_id"] = error.client_id
        return fs

    def run(self, state, shards, *, epochs, steps_per_epoch, eval_fn=None,
            faults: Optional[FaultPlan] = None):
        assert len(shards) == self.tc.n_clients
        if faults is not None and faults.n_clients != self.tc.n_clients:
            raise ValueError(
                f"FaultPlan covers {faults.n_clients} clients but the config "
                f"has n_clients={self.tc.n_clients}"
            )
        shares = np.asarray(self.tc.data_shares, np.float64)
        shares = (shares / shares.sum()).tolist()
        queue = FeatureQueue(max_size=self.queue_size,
                             per_client_cap=self.per_client_cap)
        clients = self._make_clients(state, shards)
        fleet = self._make_fleet(clients)
        consumer = self._make_consumer(state, queue)
        # one FaultRun spans the whole run: its transport streams are keyed
        # on (plan seed, the canonical step at fit time, client), so a
        # restored-mid-fault session draws the same stream a continued one
        # does — and the schedule itself is keyed on the server step, which
        # rides in the canonical state
        frun = faults.start_run(int(state["step"])) if faults is not None else None
        dropped = drained = 0
        history = []
        new_state = state
        try:
            for ep in range(epochs):
                losses, server_params, opt_state, step, d = self._consume_epoch(
                    consumer, clients, queue, shares, steps_per_epoch, fleet,
                    frun,
                )
                dropped += d["dropped"]
                drained += d["drained"]
                self.losses.extend(losses)
                rec = {"epoch": ep, "loss": finite_mean(losses),
                       "server_steps": step}
                # per-client budget: the WORST-CASE client's release count
                # this run (every produced batch left the privacy layer,
                # whether or not the queue accepted or transported it; a
                # DOWN client's counter holds still, so a crashed hospital
                # spends no budget while out)
                released = max(c.releases for c in clients)
                new_state = {
                    "client_banks": [c.params for c in clients],
                    "server": server_params,
                    "opt": opt_state,
                    "step": step,
                    "privacy": budget_advance(state["privacy"], self.tc.privacy, released)
                    if self.guard.enabled else state["privacy"],
                }
                if eval_fn is not None:
                    rec.update({f"val_{k}": v
                                for k, v in eval_fn(self.to_canonical(new_state)).items()})
                if d.get("halted"):
                    rec["halted"] = True
                    history.append(rec)
                    break  # the quorum policy ended the run cleanly
                history.append(rec)
        except ClientLoopError as e:
            # a client thread died: surface the exception but leave the
            # audit trail (stats + fault_stats) in place for the caller
            self.fault_stats = self._assemble_fault_stats(frun, clients, e)
            self.stats = {**queue.stats(), "dropped": dropped,
                          "drained": drained,
                          "privacy": budget_report(self.tc.privacy,
                                                   new_state["privacy"])}
            raise
        self.stats = {**queue.stats(), "dropped": dropped, "drained": drained,
                      "privacy": budget_report(self.tc.privacy, new_state["privacy"])}
        self.fault_stats = self._assemble_fault_stats(frun, clients)
        return new_state, history

    def to_canonical(self, state):
        return {
            "client_banks": stack_pytrees(state["client_banks"]),
            "server": state["server"],
            "opt": state["opt"],
            "step": jnp.asarray(state["step"], jnp.int32),
            "privacy": state["privacy"],
        }

    def from_canonical(self, canonical):
        return {
            "client_banks": unstack_pytree(canonical["client_banks"], self.tc.n_clients),
            "server": canonical["server"],
            "opt": canonical["opt"],
            "step": int(canonical["step"]),
            "privacy": canonical["privacy"],
        }


# ------------------------------------------------------------- fused-queue
@register_engine("fused-queue")
class FusedQueueEngine(ProtocolEngine):
    """The async-queue arrival semantics on the fused throughput path.

    Same client fleet, same ``FeatureQueue``, same ``drive_protocol``
    arrival order and drop/drain accounting as ``protocol-async`` — but the
    consumer is a ``BankedConsumer`` that accumulates arriving feature
    batches into the scanned epoch's stacked device buffers (a
    ``FeatureBank``: padded ``[K, b, ...]`` slots + validity mask) instead
    of stepping the trunk once per queue pop. The epoch's trunk updates
    then run as ONE ``lax.scan`` dispatch (``make_server_bank_runner``)
    whose per-slot math is op-identical to ``SplitServer._step``, so a σ=0
    run is bit-exact with ``protocol-async`` while the per-item dispatch
    and per-push host round-trips disappear. Canonical state, save/restore,
    ``evaluate()["privacy"]`` and the accountant behave exactly as for the
    protocol engine (the two engines' checkpoints are interchangeable).
    ``unroll`` defaults to 1 — unrolling the scan would trade the parity
    guarantee away (see ``make_server_bank_runner``).

    Memory: one epoch's releases live on device at once —
    O(steps_per_epoch × client_batch × feature_size), vs protocol-async's
    O(queue_size) items. Because the step counter (and the clients' RNG
    base) is absolute, ``steps_per_epoch`` is purely the BANK CHUNK SIZE
    for this engine: halving it and doubling ``epochs`` replays the exact
    same item sequence bit-for-bit, so bound memory that way."""

    name = "fused-queue"
    # device-resident releases: the bank stack is the ONE host<->device
    # boundary per epoch (protocol-async round-trips every push)
    _client_as_numpy = False

    def __init__(self, adapter: SplitAdapter, tc: SplitTrainConfig,
                 opt: Optimizer, *, mesh: Optional[Mesh] = None,
                 threaded: bool = False, client_batch: Optional[int] = None,
                 queue_size: int = 64, per_client_cap: Optional[int] = None,
                 production: str = "fleet", fleet_chunk: int = 8,
                 pop_timeout: float = 1.0, pop_retries: int = 0,
                 pop_backoff: float = 2.0, unroll: int = 1):
        super().__init__(adapter, tc, opt, mesh=mesh, threaded=threaded,
                         client_batch=client_batch, queue_size=queue_size,
                         per_client_cap=per_client_cap,
                         production=production, fleet_chunk=fleet_chunk,
                         pop_timeout=pop_timeout, pop_retries=pop_retries,
                         pop_backoff=pop_backoff)
        self._run_bank = make_server_bank_runner(
            adapter, opt, tc.grad_clip, unroll=unroll, mesh=mesh
        )

    def _make_consumer(self, state, queue):
        self._server_params, self._opt_state = state["server"], state["opt"]
        return protocol_mod.BankedConsumer(queue, step_count=int(state["step"]))

    def _consume_epoch(self, consumer, clients, queue, shares, steps_per_epoch,
                       fleet=None, faults=None):
        """Bank one epoch of arrivals, then replay the bank as one scanned
        trunk dispatch — everything else (drive order, accounting, state
        assembly) is inherited from ProtocolEngine, line for line. Fleet
        production composes: arrivals enter the bank as ``FeatureSlice``
        refs and ``FeatureBank.stacked`` gathers each production cycle's
        run with one ``jnp.take``, so the whole epoch is a handful of
        device ops end to end."""
        step_before = consumer.step_count
        consumer.bank = bank = FeatureBank(steps_per_epoch)
        d = protocol_mod.drive_protocol(
            clients, consumer, queue, shares,
            step_before + steps_per_epoch, threaded=self.threaded,
            fleet=fleet, faults=faults, pop_timeout=self.pop_timeout,
            pop_retries=self.pop_retries, pop_backoff=self.pop_backoff,
        )
        if len(bank) == 0:
            # a quorum halt (or an all-down window) can end an epoch before
            # a single item arrived; an empty bank has nothing to replay
            return [], self._server_params, self._opt_state, consumer.step_count, d
        self._server_params, self._opt_state, _, losses = self._run_bank(
            self._server_params, self._opt_state, step_before, *bank.stacked()
        )
        losses = np.asarray(jax.device_get(losses))
        epoch_losses = [float(l) for l in losses[: len(bank)]]  # valid slots
        return (epoch_losses, self._server_params, self._opt_state,
                consumer.step_count, d)


# ------------------------------------------------------------------- fedavg
@register_engine("fedavg")
class FedAvgEngine:
    """The paper's FL comparison behind the session surface. ``epochs`` maps
    to FedAvg rounds, ``steps_per_epoch`` to local steps per round. The
    canonical client_banks are n identical copies of the one global client
    block (FedAvg shares everything), so per-client evaluation and the
    privacy metrics still apply."""

    name = "fedavg"
    identical_banks = True  # evaluate scores one bank, replicates the row

    def __init__(self, adapter: SplitAdapter, tc: SplitTrainConfig,
                 opt: Optimizer, *, mesh: Optional[Mesh] = None,
                 local_batch: int = 32):
        if mesh is not None:
            raise ValueError("fedavg does not support mesh=; use a fused engine")
        if tc.mode != "detached":
            raise ValueError(
                "fedavg trains full local models; SplitTrainConfig.mode does "
                "not apply — leave it at the default"
            )
        self.adapter, self.tc, self.opt = adapter, tc, opt
        self.local_batch = local_batch
        self.guard = PrivacyGuard.from_config(tc.privacy)
        self._local_sgd = fedavg_mod.make_local_sgd(adapter, tc, opt)

    def init(self, key):
        self._seed = _seed_from_key(key)
        self._rng = np.random.default_rng(self._seed)
        self._root_key = key
        return {"params": self.adapter.init(key), "round": 0,
                "privacy": budget_init()}

    def run(self, state, shards, *, epochs, steps_per_epoch, eval_fn=None):
        assert len(shards) == self.tc.n_clients
        wrapped = None
        if eval_fn is not None:
            def wrapped(gp):
                return eval_fn(self.to_canonical(
                    {"params": gp, "round": 0, "privacy": state["privacy"]}
                ))
        round_offset = int(state["round"])
        # round 0 keeps exact legacy train_fedavg sampling; later offsets
        # (second fit, or restore-then-fit) reseed from (seed, round) so a
        # resumed session draws the SAME fresh stream a continued one would
        rng = (self._rng if round_offset == 0
               else np.random.default_rng((self._seed, round_offset)))
        params, history = fedavg_mod.fedavg_rounds(
            self.adapter, self.tc, self.opt, shards, state["params"],
            rounds=epochs, local_steps=steps_per_epoch,
            local_batch=self.local_batch, rng=rng,
            round_offset=round_offset, local_sgd=self._local_sgd,
            eval_fn=wrapped, noise_key=self._root_key,
        )
        for i, rec in enumerate(history):
            rec.setdefault("epoch", i)
            rec.setdefault("loss", rec["mean_local_loss"])
        # one guard application per local step per client
        privacy = (budget_advance(state["privacy"], self.tc.privacy,
                                  epochs * steps_per_epoch)
                   if self.guard.enabled else state["privacy"])
        return {"params": params, "round": int(state["round"]) + epochs,
                "privacy": privacy}, history

    def to_canonical(self, state):
        client = state["params"]["client"]
        banks = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (self.tc.n_clients,) + a.shape),
            client,
        )
        return {
            "client_banks": banks,
            "server": state["params"]["server"],
            "opt": {},  # FedAvg re-inits client optimizers every round
            "step": jnp.asarray(state["round"], jnp.int32),
            "privacy": state["privacy"],
        }

    def from_canonical(self, canonical):
        client = jax.tree.map(lambda a: a[0], canonical["client_banks"])
        return {
            "params": {"client": client, "server": canonical["server"]},
            "round": int(canonical["step"]),
            "privacy": canonical["privacy"],
        }


# ---------------------------------------------------------------- llm-split
_take_client_batch = jax.jit(jax.vmap(lambda d, ix: jnp.take(d, ix, axis=0)))


@register_engine("llm-split")
class LLMSplitEngine:
    """The LM split workload (``core.distributed``) behind the session
    surface: per-client banks = embedding + privacy block(s), the server
    trunk = the remaining transformer stack with an UNTIED head. Shards are
    per-client ``(windows, windows)`` pairs of ``[N, S]`` int32 token
    windows (labels == tokens; the shift happens in the loss), sampled by
    the same on-device plan as the fused engines, so its key schedule is
    the standard one: ``fold_in(root, epochs_done)`` per epoch, per-step
    keys from the plan, per-client noise keys split inside the step, and
    the guard's release on ``fold_in(noise_key, GUARD_KEY_FOLD)``.

    ``shared_bank=True`` keeps ONE bank (no leading client dim) in the
    native state — in detached mode identically-initialized frozen banks
    are mathematically one bank; canonical conversion broadcasts to the
    stacked ``[n_clients, ...]`` layout losslessly (and back via ``[0]``).
    ``mode="e2e"`` (classic split learning — grads return to the clients)
    trains per-client banks and therefore rejects ``shared_bank``.

    ``mesh=`` places the 2-D ``("clients", "model")`` grid: banks + epoch
    data shard over ``"clients"``, the trunk tensor-parallel over
    ``"model"`` via ``trunk_specs`` (the transformer rules: QKV/FFN-up
    column-parallel, O/FFN-down row-parallel, the untied head
    vocab-sharded, scanned groups keep their leading group dim). A 1x1
    grid is a bit-exact no-op like every other engine."""

    name = "llm-split"

    def __init__(self, adapter: SplitAdapter, tc: SplitTrainConfig,
                 opt: Optimizer, *, mesh: Optional[Mesh] = None,
                 shared_bank: bool = False):
        if not isinstance(adapter, LLMSplitAdapter) or adapter.cfg is None:
            raise ValueError(
                "llm-split needs an adapter built by "
                "repro.core.distributed.llm_adapter(cfg, opts) — it carries "
                "the transformer config the engine's step factory reads"
            )
        if tc.mode not in ("detached", "e2e"):
            raise ValueError(f"unknown mode {tc.mode!r}")
        if (mesh is not None and CLIENT_AXIS in mesh.axis_names
                and tc.n_clients % mesh.shape[CLIENT_AXIS] != 0):
            raise ValueError(
                f"n_clients={tc.n_clients} does not divide over mesh axis "
                f"{CLIENT_AXIS!r} of size {mesh.shape[CLIENT_AXIS]}; the "
                f"stacked client banks shard their leading axis evenly"
            )
        self.adapter, self.tc, self.opt = adapter, tc, opt
        self.mesh, self.shared_bank = mesh, shared_bank
        # evaluate() scores one bank and replicates the row when shared
        self.identical_banks = shared_bank
        self.guard = PrivacyGuard.from_config(tc.privacy)
        # raises at construction for e2e + shared_bank
        step = make_guarded_llm_step(
            adapter.cfg, adapter.opts, opt, tc.n_clients,
            grad_clip=tc.grad_clip, privacy=tc.privacy,
            shared_bank=shared_bank, mode=tc.mode, mesh=mesh,
        )
        self._step = jax.jit(step, donate_argnums=(0,))
        self._plans: Dict[int, Callable] = {}
        self._epochs_done = 0

    def init(self, key):
        self._root = key
        self._epochs_done = 0
        return init_llm_state(
            key, self.adapter.cfg, self.tc.n_clients, self.opt,
            dtype=self.adapter.dtype, shared_bank=self.shared_bank,
            mode=self.tc.mode,
        )

    def _place(self, state, data_x, data_y):
        """Same placement discipline as the fused engines: bank + data
        leading axes over ``"clients"``, the trunk pre-placed in its
        ``trunk_specs`` layout when the model axis is real (the in-step
        constraint would reshard it anyway; placing once avoids a per-epoch
        host-layout transfer). A shared bank has no client axis — it stays
        replicated, which is its correct layout."""
        if self.mesh is None:
            return state, data_x, data_y
        from repro.core.trainer import MODEL_AXIS
        from repro.sharding.specs import client_bank_specs, trunk_shardings

        if not self.shared_bank and CLIENT_AXIS in self.mesh.axis_names:
            specs = client_bank_specs(state["client_banks"], self.mesh, CLIENT_AXIS)
            banks = jax.tree.map(
                lambda a, s: jax.device_put(a, NamedSharding(self.mesh, s)),
                state["client_banks"], specs,
            )
            state = {**state, "client_banks": banks}
        if (MODEL_AXIS in self.mesh.axis_names
                and self.mesh.shape[MODEL_AXIS] > 1):
            state = {**state, "server": jax.device_put(
                state["server"], trunk_shardings(state["server"], self.mesh)
            )}
        if CLIENT_AXIS in self.mesh.axis_names:
            data_sh = NamedSharding(self.mesh, P(CLIENT_AXIS))
            data_x = jax.device_put(data_x, data_sh)
            data_y = jax.device_put(data_y, data_sh)
        return state, data_x, data_y

    def run(self, state, shards, *, epochs, steps_per_epoch, eval_fn=None):
        assert len(shards) == self.tc.n_clients
        plan = self._plans.setdefault(
            steps_per_epoch, make_sample_plan(self.tc, steps_per_epoch)
        )
        data_x, data_y, lens = device_put_shards(shards)
        state, data_x, data_y = self._place(state, data_x, data_y)
        history = []
        for ep in range(epochs):
            self._epochs_done += 1
            idx, step_keys = plan(
                lens, jax.random.fold_in(self._root, self._epochs_done)
            )
            ms = []
            for t in range(steps_per_epoch):
                batch = {
                    "tokens": _take_client_batch(data_x, idx[t]),
                    "labels": _take_client_batch(data_y, idx[t]),
                }
                state, m = self._step(state, batch, step_keys[t])
                ms.append(m)
            ms = jax.device_get(ms)  # single readout per epoch
            rec = {k: float(np.mean([m[k] for m in ms])) for k in ms[0]}
            rec["epoch"] = ep
            if eval_fn is not None:
                rec.update({f"val_{k}": v
                            for k, v in eval_fn(self.to_canonical(state)).items()})
            history.append(rec)
        return state, history

    def to_canonical(self, state):
        if not self.shared_bank:
            return state
        n = self.tc.n_clients
        banks = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n,) + a.shape),
            state["client_banks"],
        )
        return {**state, "client_banks": banks}

    def from_canonical(self, canonical):
        if not self.shared_bank:
            return canonical
        return {**canonical,
                "client_banks": jax.tree.map(lambda a: a[0], canonical["client_banks"])}


# ------------------------------------------------------------------ session
class SplitSession:
    """The unified engine surface.

    ``SplitSession(adapter, config, opt, engine="auto", mesh=None, seed=0,
    **engine_options)`` — ``engine`` is a registry name (see
    ``available_engines()``) or a prebuilt ``Engine`` instance;
    ``engine_options`` go to the engine factory (e.g. ``threaded=``,
    ``client_batch=``, ``production=`` for the queue engines;
    ``local_batch=`` for fedavg; ``unroll=`` for the fused engines).
    """

    def __init__(self, adapter: SplitAdapter, config: SplitTrainConfig,
                 opt: Optimizer, engine: Any = "auto", *,
                 mesh: Optional[Mesh] = None, seed: int = 0, **engine_options):
        self.adapter, self.config, self.opt = adapter, config, opt
        if isinstance(engine, str):
            try:
                factory = _ENGINES[engine]
            except KeyError:
                raise ValueError(
                    f"unknown engine {engine!r}; available: {available_engines()}"
                ) from None
            engine = factory(adapter, config, opt, mesh=mesh, **engine_options)
        elif mesh is not None or engine_options:
            raise ValueError(
                "mesh= and engine options apply only when engine is a registry "
                "name; configure the prebuilt engine instance directly"
            )
        self.engine: Engine = engine
        self.seed = seed
        self.guard = PrivacyGuard.from_config(config.privacy)
        self._native = self.engine.init(jax.random.PRNGKey(seed))
        self.history: List[Dict[str, float]] = []

    def fit(self, shards: Shards, *, epochs: int, steps_per_epoch: int,
            eval_fn: EvalFn = None,
            faults: Optional[FaultPlan] = None) -> List[Dict[str, float]]:
        """Train for ``epochs x steps_per_epoch`` engine-native units and
        return this call's history (also appended to ``self.history``).
        ``eval_fn``, if given, receives the CANONICAL state after each epoch
        and its dict is merged into the record under ``val_`` keys.
        ``faults``, if given, injects a deterministic :class:`FaultPlan`
        (crash windows, stragglers, transport faults, share skew) into the
        drive — queue engines only — and fills ``self.fault_stats``."""
        assert len(shards) == self.config.n_clients, (
            f"{len(shards)} shards for n_clients={self.config.n_clients}"
        )
        if steps_per_epoch < 1:
            # uniform across engines: a zero-step epoch would diverge per
            # regime (empty bank vs empty loss slice) instead of failing loud
            raise ValueError(f"steps_per_epoch must be >= 1, got {steps_per_epoch}")
        kwargs: Dict[str, Any] = {}
        if faults is not None:
            if not getattr(self.engine, "supports_faults", False):
                raise ValueError(
                    f"engine {self.engine.name!r} does not support faults=; "
                    "fault injection models the multi-site transport, which "
                    "only the queue engines (protocol-async, fused-queue) have"
                )
            kwargs["faults"] = faults
        self._native, history = self.engine.run(
            self._native, shards, epochs=epochs, steps_per_epoch=steps_per_epoch,
            eval_fn=eval_fn, **kwargs,
        )
        self.history.extend(history)
        return history

    @property
    def fault_stats(self) -> Dict[str, Any]:
        """The last fit's fault report (plan, halt state, per-client
        releases/budget, transport counters) — ``{}`` for engines that never
        saw a ``faults=`` plan."""
        return getattr(self.engine, "fault_stats", {})

    @property
    def state(self):
        """The canonical state pytree (see module docstring)."""
        return self.engine.to_canonical(self._native)

    @property
    def native_state(self):
        """The engine's own state representation (escape hatch for shims)."""
        return self._native

    def evaluate(self, x, y, *, batch: int = 512) -> Dict[str, Any]:
        """Per-client evaluation: one full pass per client bank plus the
        share-weighted mean of every metric (top-level keys) and the
        accountant's budget under ``"privacy"``. See
        ``trainer.evaluate_per_client``. (Eval forwards run noise-free —
        the guard protects RELEASES during training, not local scoring.)"""
        result = evaluate_per_client(
            self.adapter, self.state, x, y, batch=batch,
            weights=np.asarray(client_weights(self.config)),
            identical_banks=getattr(self.engine, "identical_banks", False),
        )
        result["privacy"] = self.privacy_report()
        return result

    def serve(self, trace, shards: Shards, *, max_batch: int = 8,
              queue_size: int = 64, per_client_cap: Optional[int] = None,
              max_wait: Optional[int] = None, request_batch: int = 1,
              pop_retries: int = 0, pop_backoff: float = 2.0,
              record_features: bool = False, keep_responses: bool = True):
        """Serve an arrival trace through the split-inference path
        (docs/serving.md): each request runs its hospital's privacy layer,
        releases through THIS session's guard at the cut (the training
        fold-in key schedule, based at the canonical ``step``), queues the
        guarded features, and a continuously-batching consumer answers up
        to ``max_batch`` requests per cycle with one jitted trunk forward.

        Works on any engine's checkpoint — the server is built from the
        CANONICAL state, so a ``restore()``d session serves unchanged.
        Every release spends (ε, δ) budget exactly like a training release:
        the accountant leaf in the canonical state advances by the
        worst-case client's request count (drops and sheds included — the
        features already left the privacy layer).

        ``trace`` comes from ``repro.serving.traces`` (``poisson_trace`` /
        ``bursty_trace`` / ``make_trace``); ``shards`` are the per-hospital
        datasets in the training layout. Returns a
        ``repro.serving.ServeReport``.
        """
        from repro.serving.server import SplitInferenceServer

        server = SplitInferenceServer(
            self.adapter, self.state, guard=self.guard, max_batch=max_batch,
            queue_size=queue_size, per_client_cap=per_client_cap,
            max_wait=max_wait, request_batch=request_batch,
            pop_retries=pop_retries, pop_backoff=pop_backoff,
            record_features=record_features, keep_responses=keep_responses,
            root_key=jax.random.PRNGKey(self.seed),
            mesh=getattr(self.engine, "mesh", None),
        )
        report = server.serve(trace, shards)
        released = max(report.releases_per_client, default=0)
        if self.guard.enabled and released:
            canonical = self.state
            self._native = self.engine.from_canonical({
                **canonical,
                "privacy": budget_advance(
                    canonical["privacy"], self.config.privacy, released
                ),
            })
        return report

    def privacy_report(self, delta_prime: float = 1e-6) -> Dict[str, Any]:
        """The (ε, δ) budget spent so far: the carried release count plus
        basic and advanced composition bounds (``repro.privacy.accountant``).
        Matches ``composed_epsilon(config.privacy, releases)`` exactly —
        including after a ``save``/``restore`` round-trip, because the
        counters live inside the canonical state."""
        return budget_report(
            self.config.privacy, jax.device_get(self.state["privacy"]),
            delta_prime,
        )

    def audit_privacy(self, x_sample, *, sigmas: Sequence[float] = (0.0, 0.1, 1.0),
                      steps: int = 120, seed: int = 0, client: int = 0,
                      ) -> List[Dict[str, float]]:
        """Inversion-attack audit of client ``client``'s trained privacy
        layer (works for the CNN case studies and the cholesterol MLP alike):
        for each guard σ the attack reconstructs ``x_sample`` from the
        released features and reports MSE/PSNR/NCC — reconstruction MSE
        should RISE with σ. Uses the session's configured feature clip
        (``config.privacy.clip_norm``) when one is set."""
        bank = _client_banks_list(self.state["client_banks"])[client]

        def fwd(z):
            return self.adapter.client_forward(bank, z, None)

        clip = self.config.privacy.clip_norm if self.config.privacy else None
        return guard_noise_sweep(
            fwd, jnp.asarray(x_sample), sigmas=sigmas, clip_norm=clip,
            steps=steps, seed=seed,
        )

    def save(self, directory: str, metadata: Optional[dict] = None) -> str:
        """Checkpoint the canonical state via ``checkpoint/io``."""
        state = self.state
        meta = {"engine": self.engine.name, "adapter": self.adapter.name,
                "n_clients": self.config.n_clients,
                "privacy_releases": int(state["privacy"]["releases"]),
                **(metadata or {})}
        epochs_done = getattr(self.engine, "_epochs_done", None)
        if epochs_done is not None:
            meta["epochs_done"] = epochs_done
        return save_checkpoint(directory, int(state["step"]), state, meta)

    def restore(self, path: str) -> dict:
        """Load a canonical checkpoint (template = this session's state
        structure) and adopt it; returns the manifest. The engine's epoch-key
        progress is restored too, so resuming with the ORIGINAL seed
        continues the key schedule instead of replaying consumed epochs
        (batch order + privacy-noise draws)."""
        state, manifest = load_checkpoint(path, self.state)
        self._native = self.engine.from_canonical(state)
        epochs_done = manifest.get("metadata", {}).get("epochs_done")
        if epochs_done is not None and hasattr(self.engine, "_epochs_done"):
            self.engine._epochs_done = int(epochs_done)
        return manifest
