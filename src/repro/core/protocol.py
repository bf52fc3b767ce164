"""Explicit two-program client/server protocol simulation (paper Fig. 1).

Unlike ``core.trainer`` (which fuses the protocol into one SPMD program for
throughput), this module runs REAL separate client objects and a server object
communicating only through a :class:`FeatureQueue` — nothing else crosses the
trust boundary. Used by protocol-fidelity tests and the privacy benchmarks:

  * clients never expose raw data — the test asserts only post-cut feature
    maps enter the queue;
  * the server never touches client parameters;
  * clients run asynchronously (threaded) with rates ∝ their data volume.

Role in the engine registry (``repro.core.session``): this module is the
client/arrival half of BOTH queue-fed engines. ``protocol-async`` pairs the
:class:`SplitClient` fleet with :class:`SplitServer` (one trunk update per
queue pop); ``fused-queue`` pairs the SAME clients and the SAME
:func:`drive_protocol` arrival order with a :class:`BankedConsumer`, which
accumulates pops into a ``core.queue.FeatureBank`` for one scanned server
dispatch per epoch (``core.trainer.make_server_bank_runner``). Production
side, :class:`FleetProducer` batches the fleet: instead of one jitted
client forward per push, every queue cycle's releases run as ONE vmapped
dispatch over the stacked client banks (the canonical stacked-bank layout),
bit-identical per item to ``SplitClient.produce`` — see
:func:`make_fleet_release_fwd`. Canonical state leaves owned here:
``client_banks`` live inside the ``SplitClient`` objects (one bank per
hospital, never crossing the trust boundary; the fleet's stacked view is a
device-side restatement of the same banks on the CLIENT side of the cut)
and ``server``/``opt``/``step`` inside ``SplitServer`` — the engines
assemble the canonical pytree from those after each epoch; the ``privacy``
budget leaf is advanced by the engines from ``SplitClient.releases``.
"""
from __future__ import annotations

import collections
import threading
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.adapters import SplitAdapter
from repro.core.faults import ClientLoopError, FaultRun
from repro.core.queue import FeatureQueue, FeatureSlice
from repro.optim.optimizers import Optimizer, apply_updates, clip_by_global_norm
from repro.privacy.guard import PrivacyGuard, batched_release_keys


def make_client_release_fwd(adapter: SplitAdapter,
                            guard: Optional[PrivacyGuard] = None):
    """The jitted client-side release: ``(params, x, key) -> features``,
    guarded at the cut when the ``PrivacyGuard`` is enabled. Parameters are
    arguments, so ONE compiled function serves every client of a fleet —
    the engines prebuild it and hand it to each ``SplitClient`` instead of
    paying a fresh trace per client per ``fit``. The privacy layer's ops
    carry the ``privacy_layer`` named scope, the guard's ``guard``."""
    guard = guard if guard is not None else PrivacyGuard()

    def release(p, x, k):
        with jax.named_scope("privacy_layer"):
            f = adapter.client_forward(p, x, k)
        return guard(guard.key_for(k), f) if guard.enabled else f

    # a lambda, so that the compiled program keeps its name (``jit__lambda``),
    # by which the benchmark's layer map finds it
    return jax.jit(lambda p, x, k: release(p, x, k))


def make_fleet_release_fwd(adapter: SplitAdapter,
                           guard: Optional[PrivacyGuard] = None):
    """The fleet-batched client release: ``(stacked_banks, base_keys, cids,
    releases, xs) -> features [N, b, ...]`` — one jitted dispatch for a whole
    queue cycle of releases, in place of N ``make_client_release_fwd`` calls.

    ``stacked_banks`` is the canonical stacked-bank layout (every leaf with
    a leading ``[n_clients]`` axis, same as ``session.py``'s canonical
    state); ``base_keys`` the stacked per-client noise base keys; ``cids``
    ``[N]`` int item client ids and ``releases`` ``[N]`` int per-item
    release counters. Per item, this computes EXACTLY what
    ``SplitClient.produce`` computes — ``fwd(banks[cid], x,
    fold_in(base_keys[cid], release))`` with the guard at the cut — but the
    bank gather, the fold-in key schedule
    (``privacy.guard.batched_release_keys``) and the vmapped forward+release
    all live inside ONE compiled program. Every stage is bit-preserving:
    the gather moves data, fold_in is counter-based threefry (batching
    doesn't change the math), and vmapping the forward/guard over the item
    axis yields the same per-item lanes XLA would compute alone — pinned by
    ``tests/test_fleet_production.py``.
    """
    guard = guard if guard is not None else PrivacyGuard()

    def one(bank, x, key):
        with jax.named_scope("privacy_layer"):
            f = adapter.client_forward(bank, x, key)
        return guard(guard.key_for(key), f) if guard.enabled else f

    vfwd = jax.vmap(one)

    @jax.jit
    def fleet(stacked_banks, base_keys, cids, releases, xs):
        banks = jax.tree.map(lambda a: jnp.take(a, cids, axis=0), stacked_banks)
        keys = batched_release_keys(jnp.take(base_keys, cids, axis=0), releases)
        return vfwd(banks, xs, keys)

    return fleet


class SplitClient:
    """A hospital: private data + the privacy-preserving layer ONLY.

    Noise keys are fold-ins of a JAX base key (``noise_key``, default
    derived from ``noise_seed + client_id``) advanced per produced batch —
    NOT host NumPy draws — so protocol releases follow the same
    reproducible key discipline as the fused engines and an enabled
    ``PrivacyGuard`` releases through the exact same mechanism. This
    deliberately changes the legacy stream: the old per-push
    ``rng.integers(1 << 31)`` noise-seed draw is gone, so both the noise
    keys AND the batch-index sequence differ from the pre-guard protocol.
    ``releases`` counts every batch that left the privacy layer (whether or
    not the queue accepted it) for the (ε, δ) accountant.

    ``as_numpy=False`` keeps the released features on device (values
    identical — ``np.asarray`` is a copy, not a rounding): the fused-queue
    engine banks device arrays and pays ONE host<->device boundary per
    epoch instead of one round-trip per push.
    """

    def __init__(self, client_id: int, adapter: SplitAdapter, client_params,
                 data: Tuple[np.ndarray, np.ndarray], batch: int,
                 noise_seed: int = 0, *, noise_key=None,
                 guard: Optional[PrivacyGuard] = None, fwd=None,
                 as_numpy: bool = True):
        self.client_id = client_id
        self.adapter = adapter
        self.params = client_params  # never leaves this object
        self.x, self.y = data
        self.batch = batch
        self.releases = 0
        self._as_numpy = as_numpy
        self._rng = np.random.default_rng(noise_seed + client_id)  # batch sampling
        self._key = (noise_key if noise_key is not None
                     else jax.random.PRNGKey(noise_seed + client_id))
        self._fwd = fwd if fwd is not None else make_client_release_fwd(adapter, guard)

    def sample_batch(self):
        """One host-side batch draw ``(x[idx], y[idx])`` from this client's
        private sampling RNG. Shared by :meth:`produce` and
        :class:`FleetProducer` so per-item and fleet production consume the
        SAME per-client index stream in the same order — half of the fleet
        path's bit-parity contract."""
        idx = self._rng.integers(0, len(self.x), size=self.batch)
        return self.x[idx], self.y[idx]

    def produce(self):
        """One queue item: (released feature map, labels). Raw x never returned."""
        xb_host, yb = self.sample_batch()
        xb = jnp.asarray(xb_host)
        self.releases += 1
        key = jax.random.fold_in(self._key, self.releases)
        features = self._fwd(self.params, xb, key)
        return (np.asarray(features) if self._as_numpy else features), yb


class FleetProducer:
    """Vmapped production across the client fleet: one jitted dispatch per
    queue cycle instead of one per push.

    Wraps a prebuilt ``SplitClient`` fleet. The clients' banks are stacked
    ONCE into the canonical stacked-bank layout (leading ``[n_clients]``
    axis — the same device view ``session.py`` uses for every fused engine;
    the stack lives on the CLIENT side of the cut, so still only released
    features reach the queue), their noise base keys likewise. A production
    request for ``counts[c]`` items per client then:

      1. draws every item's batch indices from each client's OWN sampling
         RNG via ``SplitClient.sample_batch`` — identical host draws, in
         identical per-client order, to the per-item path;
      2. advances each client's ``releases`` by exactly ``counts[c]`` (the
         accountant sees the same worst-case count — the drive loop's cycle
         planner guarantees the per-item path would have produced exactly
         these items);
      3. runs ONE :func:`make_fleet_release_fwd` dispatch — bank gather,
         ``fold_in`` key schedule and vmapped forward+guard all fused;
      4. returns the items IN PER-ITEM PRODUCTION ORDER as
         ``(client_id, FeatureSlice, labels)`` — zero-copy references into
         the batched release array, materialized only where a consumer
         needs a single row.

    Distinct total item counts compile separate fleet programs (the item
    axis is a static shape); a run settles on one steady-state cycle shape
    plus at most a couple of tail shapes.

    ``mesh=`` (a ``launch.mesh`` client or split mesh) shards the stacked
    banks' leading client axis (and the stacked base keys) over the mesh's
    ``"clients"`` axis — production reads device-local banks while the
    consumer side of the cut shards the TRUNK over the ``"model"`` axis.
    Pure placement: ``device_put`` moves bytes, so every release is
    bit-identical to the unplaced fleet's.
    """

    def __init__(self, clients: Sequence[SplitClient], fleet_fwd, *,
                 chunk: int = 8, mesh=None):
        self.clients = list(clients)
        self.chunk = int(chunk)  # threaded mode's per-client dispatch width
        self._fwd = fleet_fwd
        self._banks = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[c.params for c in self.clients]
        )
        self._keys = jnp.stack([c._key for c in self.clients])
        if (mesh is not None and "clients" in mesh.axis_names
                and mesh.shape["clients"] > 1):
            from jax.sharding import NamedSharding

            from repro.sharding.specs import client_bank_specs

            def place(tree):
                specs = client_bank_specs(tree, mesh, "clients")
                return jax.tree.map(
                    lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                    tree, specs,
                )

            self._banks = place(self._banks)
            self._keys = place(self._keys)

    def produce(self, counts: Sequence[int]) -> collections.deque:
        """Produce ``counts[c]`` items for client ``c`` (cycle order: all of
        client 0's items, then client 1's, ...) in one dispatch; returns a
        deque of ``(client_id, features, labels)`` queue items."""
        cids, rels, xs, labels = [], [], [], []
        for client, cnt in zip(self.clients, counts):
            for j in range(int(cnt)):
                xb, yb = client.sample_batch()
                xs.append(xb)
                labels.append(yb)
                cids.append(client.client_id)
                rels.append(client.releases + 1 + j)
            client.releases += int(cnt)
        if not cids:
            return collections.deque()
        feats = self._fwd(
            self._banks, self._keys,
            jnp.asarray(cids, jnp.int32), jnp.asarray(rels, jnp.int32),
            jnp.asarray(np.stack(xs)),
        )
        return collections.deque(
            (cid, FeatureSlice(feats, i), labels[i])
            for i, cid in enumerate(cids)
        )

    def produce_for(self, client: SplitClient, n: int) -> collections.deque:
        """Threaded mode: ``n`` upcoming items for ONE client in one
        dispatch (each client thread batches its own lookahead; releases
        advance at production, like the per-item path — every batch in the
        chunk leaves the privacy layer)."""
        counts = [n if c is client else 0 for c in self.clients]
        return self.produce(counts)


class SplitServer:
    """The centralized server: trunk params + optimizer + the feature queue.

    ``mesh=`` (a ``launch.mesh.make_split_mesh`` grid) makes each pop's
    trunk update tensor-parallel: params and moments are constrained to
    their ``repro.sharding.specs.trunk_specs`` layouts inside the jitted
    step, so the matmuls partition over the ``"model"`` axis with an
    all-gather only at the cut and the logits. A mesh whose model axis has
    size 1 compiles the identical unsharded program (the constraint helper
    is identity there) — the σ=0 bit-parity contract with the fused-queue
    replay is untouched."""

    def __init__(self, adapter: SplitAdapter, server_params, opt: Optimizer,
                 queue: FeatureQueue, clip_norm: float = 1.0,
                 opt_state=None, step_count: int = 0, mesh=None):
        from repro.core.trainer import _trunk_sharder

        self.adapter = adapter
        self.params = server_params
        self.opt = opt
        self.opt_state = opt.init(server_params) if opt_state is None else opt_state
        self.queue = queue
        self.step_count = step_count
        self.losses: List[float] = []
        clip = clip_norm
        shard_trunk = _trunk_sharder(mesh)

        @jax.jit
        def _step(params, opt_state, step, features, labels):
            params = shard_trunk(params)
            opt_state = shard_trunk(opt_state)

            def lf(p):
                out = adapter.server_forward(p, features)
                return adapter.loss(out, labels)

            loss, grads = jax.value_and_grad(lf)(params)
            grads, _ = clip_by_global_norm(grads, clip)
            updates, opt_state = opt.update(grads, opt_state, params, step)
            return apply_updates(params, updates), opt_state, loss

        self._step = _step

    def train_one(self, timeout: float = 1.0, retries: int = 0,
                  backoff: float = 2.0) -> Optional[float]:
        """One queue pop -> one trunk update. ``timeout`` is the pop wait;
        on an empty-handed pop the consumer retries up to ``retries`` times
        with exponentially backed-off waits (``timeout * backoff**k``, each
        counted in ``FeatureQueue.stats()['retries']``) — the server-side
        graceful degradation under stragglers/dropout. All three are engine
        options (``pop_timeout`` / ``pop_retries`` / ``pop_backoff``)."""
        item = _pop_with_backoff(self.queue, timeout, retries, backoff)
        if item is None:
            return None
        _cid, features, labels = item
        self.params, self.opt_state, loss = self._step(
            self.params, self.opt_state,
            jnp.asarray(self.step_count, jnp.int32),
            jnp.asarray(features), jnp.asarray(labels),
        )
        self.step_count += 1
        loss = float(loss)
        self.losses.append(loss)
        return loss


class BankedConsumer:
    """The fused-queue engine's stand-in for ``SplitServer`` inside
    :func:`drive_protocol`: same ``step_count`` / ``train_one`` surface, but
    each pop is ACCEPTED into a ``core.queue.FeatureBank`` (preserving the
    queue's release order) instead of stepping the trunk. The actual trunk
    updates happen afterwards as one ``lax.scan`` over the stacked bank
    (``core.trainer.make_server_bank_runner``) — so with the same clients,
    shares and drive mode, the items consumed (and therefore the σ=0 math)
    are identical to ``protocol-async``'s, just batched into one dispatch."""

    def __init__(self, queue: FeatureQueue, step_count: int = 0):
        self.queue = queue
        self.step_count = step_count
        self.bank = None  # the engine installs a fresh FeatureBank per epoch

    def train_one(self, timeout: float = 1.0, retries: int = 0,
                  backoff: float = 2.0) -> Optional[float]:
        if self.bank is None or self.bank.full:
            return None  # nowhere to put an item: leave it queued
        item = _pop_with_backoff(self.queue, timeout, retries, backoff)
        if item is None:
            return None
        self.bank.accept(*item)
        self.step_count += 1
        return None  # no loss yet — it materializes in the scanned epoch


def _pop_with_backoff(queue: FeatureQueue, timeout: float, retries: int,
                      backoff: float):
    """Pop with exponential backoff: wait ``timeout``, then ``timeout *
    backoff``, ``timeout * backoff**2``, … for up to ``retries`` re-pops.
    Shared by both queue consumers so protocol-async and fused-queue count
    identical ``timeouts``/``retries`` on identical drives."""
    item = queue.pop(timeout=timeout)
    wait = timeout
    for _ in range(int(retries)):
        if item is not None:
            return item
        wait *= backoff
        queue.note_retry()
        item = queue.pop(timeout=wait)
    return item


def _plan_round_robin_cycle(
    queue_len: int, queue_size: int, step: int, total: int,
    quanta: Sequence[int], available: Optional[Sequence[bool]] = None,
) -> List[int]:
    """How many items each client PRODUCES in one round-robin cycle — the
    per-item drive's lazy production contract, restated as pure counting so
    fleet production can batch a cycle without over-producing.

    The per-item loop produces an item only immediately before its push
    attempt, so in the drive's final cycle production stops early: at a
    client boundary once the step target is reached, or one item after the
    queue jams (that item is the ``dropped`` one). Both conditions are a
    deterministic function of (queue occupancy, consumed steps) because in
    round-robin mode the consumer advances ONLY through drains: a client
    with quantum ``q`` gets ``free_slots + (total - step)`` successful
    pushes before the queue jams — each push either takes a free slot or
    forces exactly one drain. Producing more than the per-item path would
    have produced is not a harmless overshoot: it would advance the
    clients' sampling RNGs and ``releases`` counters past the per-item
    stream, breaking resume parity and the (ε, δ) accounting — pinned by
    ``tests/test_fleet_production.py``.

    ``available`` (the fault subsystem's per-client up mask, ``None`` means
    all up) removes DOWN clients from the cycle entirely: they produce
    nothing, advance no RNG streams, and spend no budget — the cycle's
    push/drain arithmetic simply skips them, exactly like the per-item
    drive does. Never over-producing under arbitrary masks is pinned by the
    Hypothesis property test in ``tests/test_faults.py``.
    """
    counts = [0] * len(quanta)
    for i, q in enumerate(int(x) for x in quanta):
        if step >= total:
            break
        if available is not None and not available[i]:
            continue
        if q <= 0:
            continue
        free = queue_size - queue_len
        capacity = free + (total - step)
        if q <= capacity:
            counts[i] = q
            step += max(0, q - free)           # drains this quantum forces
            queue_len = min(queue_size, queue_len + q)
        else:  # jams: `capacity` pushes land, the (capacity+1)-th drops
            counts[i] = capacity + 1
            break
    return counts


def _fault_halt_check(faults: FaultRun, queue: FeatureQueue, step: int) -> bool:
    """The drive's quorum policy: halt cleanly (never spin) when too few
    clients are up, or when the whole fleet is down over an empty queue —
    crash windows are keyed on the server step, which cannot advance
    without arrivals, so THAT stall is provably permanent."""
    plan = faults.plan
    up = sum(plan.up_mask(step))
    if up < plan.halt_below:
        faults.halt(f"quorum lost at step {step}: {up} up < "
                    f"halt_below={plan.halt_below}")
        return True
    if up == 0 and len(queue) == 0:
        faults.halt(f"all clients down at step {step} with an empty queue")
        return True
    return False


def drive_protocol(
    clients: Sequence[SplitClient],
    server,
    queue: FeatureQueue,
    shares: Sequence[float],
    total_server_steps: int,
    *,
    threaded: bool = True,
    fleet: Optional[FleetProducer] = None,
    faults: Optional[FaultRun] = None,
    pop_timeout: float = 1.0,
    pop_retries: int = 0,
    pop_backoff: float = 2.0,
) -> Dict[str, int]:
    """Drive prebuilt clients + a consumer until ``server.step_count``
    reaches ``total_server_steps`` (an ABSOLUTE target, so repeated calls
    resume). ``server`` is anything with the ``step_count`` /
    ``train_one(timeout)`` surface: a ``SplitServer`` (protocol-async) or a
    :class:`BankedConsumer` (fused-queue) — both engines share this exact
    arrival order, which is what makes their σ=0 runs bit-identical.

    With a :class:`FleetProducer` (``fleet=``), production is batched: the
    round-robin drive plans each cycle (:func:`_plan_round_robin_cycle`)
    and produces all of its items in ONE vmapped dispatch, then replays the
    per-item push/drain/drop state machine over the prefetched items — the
    queue sees identical arrivals, the accounting identical events, and the
    items themselves are bit-identical. The threaded drive has each client
    thread produce ``fleet.chunk`` items per dispatch instead of one.
    Fleet planning assumes drains always make room, so a queue with a
    ``per_client_cap`` falls back to per-item production (the cap rejects
    pushes the planner cannot see).

    With a :class:`~repro.core.faults.FaultRun` (``faults=``), the drive
    injects the plan's failures deterministically: down clients are skipped
    (no production, no RNG advance, no budget), surviving clients' quanta
    are live-reweighted from their renormalized shares, stragglers produce
    at reduced quanta (round-robin) or arrive late (threaded), and the
    transport may drop or duplicate a release AFTER it left the privacy
    layer. Transport faults make arrivals invisible to the cycle planner,
    so they force per-item production, like ``per_client_cap``. The quorum
    policy (:func:`_fault_halt_check`) halts the drive cleanly — reported
    in the returned ``halted`` flag and the run's ``fault_stats`` — instead
    of spinning on a queue nobody will ever fill. ``FaultPlan.none()``
    takes these same branches and stays bit-exact with ``faults=None``.

    ``pop_timeout``/``pop_retries``/``pop_backoff`` parameterize the
    threaded consumer's ``train_one`` waits (exponential backoff between
    re-pops; the deterministic drive pops with timeout 0 — queue state is
    synchronous there, so waiting cannot help).

    A threaded client loop that raises no longer dies silently (the drive
    used to hang on join with a dead producer): the first exception stops
    the drive and re-raises as :class:`~repro.core.faults.ClientLoopError`
    with the original as ``__cause__``; the engines record it in
    ``fault_stats["client_error"]``.

    Returns accounting for the engines' ``queue_stats``:
      * ``dropped`` — produced batches never enqueued (0 unless the run
        stops while the queue is full);
      * ``drained`` — consumptions forced by a FULL queue between pushes
        (the PR 2 round-robin fix: a full queue drains the consumer instead
        of silently dropping the batch; always 0 in threaded mode, where
        the consumer pops continuously). A drain is counted only when the
        consumer actually advanced — a ``train_one`` that consumes nothing
        (e.g. a cap-rejected push with nothing poppable) breaks out to the
        drop accounting instead of spinning and inflating the count;
      * ``halted`` — True when the quorum policy stopped the drive short of
        the step target.
    """
    dropped = drained = 0
    if threaded:
        stop = threading.Event()
        errors: List[Tuple[int, BaseException]] = []

        def client_loop(client: SplitClient, share: float):
            pending: collections.deque = collections.deque()
            try:
                while not stop.is_set():
                    if faults is not None and not faults.plan.available(
                        client.client_id, server.step_count
                    ):
                        pending.clear()  # a crash loses its in-flight items
                        time.sleep(0.002)  # (their budget is already spent)
                        continue
                    if not pending:
                        # one dispatch per chunk of releases (or per item
                        # when driving without a fleet)
                        if fleet is not None:
                            pending = fleet.produce_for(client, fleet.chunk)
                        else:
                            f, l = client.produce()
                            pending.append((client.client_id, f, l))
                    cid, f, l = pending.popleft()
                    copies = 1
                    if faults is not None:
                        fate = faults.transit(cid)
                        copies = {"ok": 1, "dup": 2, "drop": 0}[fate]
                    for _ in range(copies):
                        while not queue.push(cid, f, l) and not stop.is_set():
                            time.sleep(0.001)  # backpressure
                    # arrival rate ∝ data share (bigger hospitals push more
                    # often); stragglers arrive late, not never
                    sleep = max(0.0005, 0.002 * (1 - share))
                    if faults is not None:
                        sleep = faults.plan.straggler_sleep(client.client_id, sleep)
                    time.sleep(sleep)
            except Exception as e:
                errors.append((client.client_id, e))
                stop.set()  # a dead producer must stop the drive, not hang it

        threads = [
            threading.Thread(target=client_loop, args=(c, s), daemon=True)
            for c, s in zip(clients, shares)
        ]
        for t in threads:
            t.start()
        while server.step_count < total_server_steps:
            if errors:
                break
            if faults is not None and _fault_halt_check(
                faults, queue, server.step_count
            ):
                break
            server.train_one(timeout=pop_timeout, retries=pop_retries,
                             backoff=pop_backoff)
        stop.set()
        for t in threads:
            t.join(timeout=2.0)
        if errors:
            cid, exc = errors[0]
            raise ClientLoopError(cid, exc) from exc
    else:  # deterministic round-robin (rate ∝ share)
        base_quanta = np.maximum(1, np.round(np.asarray(shares) * 10).astype(int))
        plan_cycles = (fleet is not None and queue.per_client_cap is None
                       and (faults is None or not faults.plan.has_transport_faults))
        stalled_cycles = 0
        while server.step_count < total_server_steps:
            if faults is not None:
                if _fault_halt_check(faults, queue, server.step_count):
                    break
                if stalled_cycles >= 1000:
                    # e.g. drop_prob ~ 1.0: production spins, nothing ever
                    # arrives, the step target is unreachable — stop
                    # spending budget on a queue that will never fill
                    faults.halt(f"no progress for {stalled_cycles} cycles "
                                f"at step {server.step_count}")
                    break
                step_before, pushed_before = server.step_count, queue.pushed
                quanta, up = faults.plan.cycle_quanta(server.step_count, shares)
                faults.note_cycle(up)
            else:
                quanta, up = base_quanta, None
            pending = None
            if plan_cycles:
                pending = fleet.produce(_plan_round_robin_cycle(
                    len(queue), queue.max_size, server.step_count,
                    total_server_steps, quanta, available=up,
                ))
            for i, (c, q) in enumerate(zip(clients, quanta)):
                if server.step_count >= total_server_steps:
                    break
                if up is not None and not up[i]:
                    continue  # down: no production, no RNG advance, no budget
                for _ in range(int(q)):
                    if pending is not None:
                        if not pending:  # planner: never produced per-item
                            break
                        cid, f, l = pending.popleft()
                    else:
                        f, l = c.produce()
                        cid = c.client_id
                    copies = 1
                    if faults is not None and faults.plan.has_transport_faults:
                        fate = faults.transit(cid)
                        if fate == "drop":
                            continue  # lost in transit; budget already spent
                        copies = 2 if fate == "dup" else 1
                    jammed = False
                    for _ in range(copies):
                        # a full queue DRAINS the consumer instead of
                        # dropping the batch (the seed ignored push()'s
                        # return value here, so rejected items silently
                        # vanished)
                        pushed = queue.push(cid, f, l)
                        while not pushed and server.step_count < total_server_steps:
                            before = server.step_count
                            server.train_one(timeout=0.0)
                            if server.step_count == before:
                                break  # consumer can't make room: fall through
                            drained += 1
                            pushed = queue.push(cid, f, l)
                        if not pushed:  # target reached, queue still full
                            dropped += 1
                            jammed = True
                            break
                    if jammed:
                        break
            while len(queue) and server.step_count < total_server_steps:
                server.train_one(timeout=0.0)
            if faults is not None:
                made_progress = (server.step_count != step_before
                                 or queue.pushed != pushed_before)
                stalled_cycles = 0 if made_progress else stalled_cycles + 1
    return {"dropped": dropped, "drained": drained,
            "halted": faults.halted if faults is not None else False}


def run_protocol(
    adapter: SplitAdapter,
    shards: Sequence[Tuple[np.ndarray, np.ndarray]],
    opt: Optimizer,
    *,
    total_server_steps: int,
    client_batch: int = 32,
    data_shares: Optional[Sequence[float]] = None,
    queue_size: int = 64,
    seed: int = 0,
    threaded: bool = True,
) -> Dict[str, Any]:
    """Deprecated shim: use ``repro.core.session.SplitSession`` with
    ``engine="protocol-async"``. Returns the legacy result dict."""
    warnings.warn(
        "run_protocol is deprecated; use SplitSession(engine='protocol-async')",
        DeprecationWarning, stacklevel=2,
    )
    from repro.core.session import SplitSession
    from repro.core.trainer import SplitTrainConfig

    n = len(shards)
    shares = tuple(data_shares or [1.0 / n] * n)
    session = SplitSession(
        adapter, SplitTrainConfig(n_clients=n, data_shares=shares), opt,
        engine="protocol-async", seed=seed, threaded=threaded,
        client_batch=client_batch, queue_size=queue_size,
    )
    session.fit(shards, epochs=1, steps_per_epoch=total_server_steps)
    native = session.native_state
    return {
        "server_params": native["server"],
        "client_params": list(native["client_banks"]),
        "losses": session.engine.losses,
        "queue_stats": session.engine.stats,
        "server_steps": int(native["step"]),
    }
