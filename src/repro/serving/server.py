"""Private split-inference serving: guarded releases -> queue -> batched trunk.

Training (every engine) moves per-hospital activations through ONE
``PrivacyGuard`` release at the cut into the ``FeatureQueue``; this module
reuses that exact machinery to SERVE: each request runs the hospital's
privacy layer, releases through the guard (``make_client_release_fwd`` — the
same jitted release the queue engines train with, same fold-in key
schedule), and pushes the guarded features into a ``FeatureQueue``. A
continuously-batching consumer pops up to ``max_batch`` ready requests per
cycle, pads them into ONE jitted trunk forward (vmapped over the padded
request slots — per-slot lanes bit-exact with the training-path
``adapter.server_forward``, the same argument ``make_server_bank_runner``
rests on), and routes each slot's output back by request id.

The drive is a LOGICAL-CLOCK simulation: one cycle admits the trace's
arrivals for that tick, sheds queue items older than ``max_wait`` cycles,
dispatches one batch, then advances. No wall-clock, no threads — so the
whole request lifecycle (admissions, queue-full drops, per-client-cap
rejections, sheds, batch compositions, cycle latencies, responses) is a
pure function of ``(canonical state, trace, knobs)`` and replays
bit-for-bit from the same seed. Wall-clock times are stamped alongside but
carry no semantics: ``ServeReport.latency_ms`` (admission to answer) and
``ServeReport.queue_wait_ms`` (push to pop), and, under ``jax.profiler``,
the host spans ``repro.serve.release`` (one offered request, from the key
fold-in to the push) and ``repro.serve.batch`` (one cycle's pops through
routing its answers).

Admission control reuses the training queue's accounting verbatim:
``queue_size`` overflow and ``per_client_cap`` rejections are the PR 2/PR 5
drop paths, empty-handed pops count ``timeouts``/``retries`` through the
PR 6 ``_pop_with_backoff`` machinery, and every release — answered, dropped
OR shed — spends (ε, δ) budget exactly like a training release that the
queue rejected (the batch already left the privacy layer).

Trust argument at the cut, inference edition: the server consumes only
guard-released feature maps plus an opaque request id; raw inputs, client
banks and the per-hospital sampling RNGs never cross. See docs/serving.md.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.adapters import SplitAdapter
from repro.core.protocol import _pop_with_backoff, make_client_release_fwd
from repro.core.queue import FeatureQueue
from repro.core.trainer import _client_banks_list, _trunk_sharder
from repro.privacy.guard import PrivacyGuard
from repro.serving.traces import Trace

# fold separating the serving fleet's sampling streams from training's
_SAMPLE_RNG_TAG = 977


def make_server_batch_forward(adapter: SplitAdapter, mesh=None):
    """The serving consumer's ONE jitted dispatch per cycle:
    ``forward(server_params, feats [K, b, ...]) -> outputs [K, b, ...]``.

    ``server_forward`` is vmapped over the ``K`` padded request slots, so
    each slot's lanes are bit-identical to calling the training-path
    ``adapter.server_forward(server_params, feats[i])`` alone — vmapping a
    function over a leading axis computes the same per-lane math XLA would
    compute per call (the ``make_server_bank_runner`` argument, minus the
    update half: serving never touches the trunk). Padded slots run on
    zeros and their outputs are simply never routed. ``mesh=`` constrains
    the trunk tensor-parallel over its ``"model"`` axis exactly like every
    training step (identity on 1-sized/absent axes — bit-exact there). The
    trunk's ops carry the ``trunk`` named scope.
    """
    shard_trunk = _trunk_sharder(mesh)

    @jax.jit
    def forward(server_params, feats):
        server_params = shard_trunk(server_params)
        with jax.named_scope("trunk"):
            return jax.vmap(lambda f: adapter.server_forward(server_params, f))(feats)

    return forward


@dataclasses.dataclass
class ServeReport:
    """One trace's serving outcome. Everything except the ``*_ms`` /
    ``wall_s`` fields is deterministic given (state, trace, knobs) — the
    :meth:`fingerprint` digest is what the replay property test pins."""

    trace_kind: str
    trace_seed: int
    offered: int = 0
    accepted: int = 0          # admitted into the queue
    answered: int = 0
    dropped: int = 0           # rejected at admission (full + cap)
    dropped_full: int = 0
    dropped_cap: int = 0
    shed: int = 0              # admitted, then aged past max_wait
    cycles: int = 0
    batches: int = 0
    batched_items: int = 0
    max_inflight_per_client: List[int] = dataclasses.field(default_factory=list)
    releases_per_client: List[int] = dataclasses.field(default_factory=list)
    per_client: List[Dict[str, int]] = dataclasses.field(default_factory=list)
    latency_cycles: Dict[int, int] = dataclasses.field(default_factory=dict)
    latency_ms: Dict[int, float] = dataclasses.field(default_factory=dict)
    # per answered request: wall time from its push to the pop that took it
    queue_wait_ms: Dict[int, float] = dataclasses.field(default_factory=dict)
    responses: Optional[Dict[int, np.ndarray]] = None
    features: Optional[Dict[int, np.ndarray]] = None
    queue_stats: Dict[str, int] = dataclasses.field(default_factory=dict)
    wall_s: float = 0.0

    @property
    def mean_batch_fill(self) -> float:
        """Mean items per dispatched batch (batching efficiency)."""
        return self.batched_items / self.batches if self.batches else 0.0

    @property
    def throughput_rps(self) -> float:
        return self.answered / self.wall_s if self.wall_s > 0 else 0.0

    def latency_percentiles(self, qs: Sequence[int] = (50, 99)) -> Dict[str, float]:
        """``{"p50_cycles", "p99_cycles", "p50_ms", "p99_ms", ...}`` over
        the ANSWERED requests (drops/sheds have no latency — they are
        counted, not averaged away)."""
        out: Dict[str, float] = {}
        cyc = np.asarray(sorted(self.latency_cycles.values()), np.float64)
        ms = np.asarray(sorted(self.latency_ms.values()), np.float64)
        for q in qs:
            out[f"p{q}_cycles"] = float(np.percentile(cyc, q)) if cyc.size else float("nan")
            out[f"p{q}_ms"] = float(np.percentile(ms, q)) if ms.size else float("nan")
        return out

    def deterministic_stats(self) -> Dict[str, Any]:
        """The replayable summary: every count plus the per-request cycle
        latencies in request-id order. Two serves of the same trace on the
        same state must return EQUAL dicts."""
        return {
            "trace": (self.trace_kind, self.trace_seed),
            "offered": self.offered, "accepted": self.accepted,
            "answered": self.answered, "dropped": self.dropped,
            "dropped_full": self.dropped_full, "dropped_cap": self.dropped_cap,
            "shed": self.shed, "cycles": self.cycles,
            "batches": self.batches, "batched_items": self.batched_items,
            "max_inflight_per_client": list(self.max_inflight_per_client),
            "releases_per_client": list(self.releases_per_client),
            "per_client": [dict(d) for d in self.per_client],
            "latency_cycles": sorted(self.latency_cycles.items()),
            "queue_stats": dict(self.queue_stats),
        }

    def fingerprint(self) -> str:
        """SHA-256 over the deterministic stats AND the response bytes in
        request-id order — bit-for-bit replay evidence."""
        h = hashlib.sha256(repr(self.deterministic_stats()).encode())
        if self.responses is not None:
            for rid in sorted(self.responses):
                h.update(np.ascontiguousarray(self.responses[rid]).tobytes())
        return h.hexdigest()


class SplitInferenceServer:
    """The serving counterpart of the queue engines: a frozen canonical
    state (any engine's checkpoint) serving inference traffic.

    ``state`` is the canonical ``SplitSession`` pytree — ``client_banks``
    (stacked or listed), the ``server`` trunk, and the consumed ``step``
    (which keys the per-client noise bases exactly like a training fit
    started from this state would: ``fold_in(fold_in(root_key, step),
    client_id)``, the ``ProtocolEngine._noise_key_for`` derivation). Per
    request the owning client folds its release counter on top and the
    guard releases on ``guard.key_for`` — the standard schedule, so a
    serving release is bit-identical to ``SplitClient.produce`` on the same
    batch.

    Knobs (all admission control / batching):
      * ``max_batch`` — requests per consumer cycle, padded into one
        jitted trunk dispatch;
      * ``queue_size`` / ``per_client_cap`` — the ``FeatureQueue``'s own
        overflow and fairness rejections (drops);
      * ``max_wait`` — cycles a request may queue before it is shed
        instead of served (``None`` disables shedding);
      * ``request_batch`` — input rows per request (one compiled program
        per value — keep it constant per server);
      * ``pop_retries`` / ``pop_backoff`` — the PR 6 consumer backoff
        surface, counted in ``queue_stats`` like the training drives.
    """

    def __init__(self, adapter: SplitAdapter, state, *,
                 guard: Optional[PrivacyGuard] = None, max_batch: int = 8,
                 queue_size: int = 64, per_client_cap: Optional[int] = None,
                 max_wait: Optional[int] = None, request_batch: int = 1,
                 pop_retries: int = 0, pop_backoff: float = 2.0,
                 record_features: bool = False, keep_responses: bool = True,
                 root_key=None, mesh=None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if request_batch < 1:
            raise ValueError(f"request_batch must be >= 1, got {request_batch}")
        if max_wait is not None and max_wait < 0:
            raise ValueError(f"max_wait must be >= 0, got {max_wait}")
        if pop_backoff < 1.0:
            raise ValueError(f"pop_backoff must be >= 1.0, got {pop_backoff}")
        self.adapter = adapter
        self.guard = guard if guard is not None else PrivacyGuard()
        self.banks = _client_banks_list(state["client_banks"])
        self.server_params = state["server"]
        self.step = int(state["step"])
        self.n_clients = len(self.banks)
        self.max_batch, self.queue_size = int(max_batch), int(queue_size)
        self.per_client_cap = per_client_cap
        self.max_wait, self.request_batch = max_wait, int(request_batch)
        self.pop_retries, self.pop_backoff = int(pop_retries), float(pop_backoff)
        self.record_features = record_features
        self.keep_responses = keep_responses
        root = root_key if root_key is not None else jax.random.PRNGKey(0)
        # the training engines' noise-base derivation, verbatim
        self._noise_keys = [
            jax.random.fold_in(jax.random.fold_in(root, self.step), c)
            for c in range(self.n_clients)
        ]
        # ONE jitted guarded release for the whole fleet (params are
        # arguments), ONE jitted padded trunk forward for every cycle
        self._client_fwd = make_client_release_fwd(adapter, self.guard)
        self._batch_fwd = make_server_batch_forward(adapter, mesh)

    # ------------------------------------------------------------ admission
    def _release(self, client_id: int, x, releases: int):
        """One guarded release: the client's privacy layer + the guard at
        the cut on ``fold_in(noise_base, release_counter)`` — the
        ``SplitClient.produce`` schedule, so serving and training releases
        from the same state are bit-identical."""
        key = jax.random.fold_in(self._noise_keys[client_id], releases)
        return self._client_fwd(self.banks[client_id], jnp.asarray(x), key)

    # ---------------------------------------------------------------- drive
    def serve(self, trace: Trace, shards) -> ServeReport:
        """Run the trace to completion (every admitted request answered or
        shed) and return the :class:`ServeReport`.

        ``shards`` are the per-hospital private datasets in the training
        layout (``[(x, y), ...]``); each request samples ``request_batch``
        rows from ITS OWN client's shard with an RNG keyed on
        ``(trace.seed, client)`` — raw rows stay on the client side of the
        cut, only the guarded release enters the queue.
        """
        if trace.n_clients != self.n_clients:
            raise ValueError(
                f"trace covers {trace.n_clients} clients but the state has "
                f"{self.n_clients} banks")
        if len(shards) != self.n_clients:
            raise ValueError(
                f"{len(shards)} shards for {self.n_clients} clients")
        xs = [np.asarray(x) for x, _ in shards]
        rngs = [np.random.default_rng((trace.seed, _SAMPLE_RNG_TAG, c))
                for c in range(self.n_clients)]
        queue = FeatureQueue(max_size=self.queue_size,
                             per_client_cap=self.per_client_cap)
        report = ServeReport(trace_kind=trace.kind, trace_seed=trace.seed)
        report.per_client = [
            {"offered": 0, "accepted": 0, "answered": 0, "dropped": 0,
             "shed": 0} for _ in range(self.n_clients)
        ]
        releases = [0] * self.n_clients
        inflight = [0] * self.n_clients
        max_inflight = [0] * self.n_clients
        admitted_cycle: Dict[int, int] = {}
        admitted_wall: Dict[int, float] = {}
        owner: Dict[int, int] = {}
        responses: Dict[int, np.ndarray] = {}
        if self.record_features:
            report.features = {}
        arrivals = trace.by_cycle()

        def consume(t: int) -> int:
            """Batch up to max_batch ready requests, shedding anything that
            aged past the deadline on the way, run the trunk on them and
            route the answers; returns the batch's size."""
            batch: List[Tuple[int, Any, int, float]] = []
            while len(batch) < self.max_batch:
                item = _pop_with_backoff(queue, 0.0, self.pop_retries,
                                         self.pop_backoff)
                if item is None:
                    break
                popped = time.perf_counter()
                cid, feats, rid = item
                inflight[cid] -= 1
                if (self.max_wait is not None
                        and t - admitted_cycle[rid] > self.max_wait):
                    report.shed += 1
                    report.per_client[cid]["shed"] += 1
                    admitted_cycle.pop(rid), admitted_wall.pop(rid)
                    continue
                batch.append((cid, feats, rid, popped))
            if not batch:
                return 0
            k = len(batch)
            feats = jnp.stack([jnp.asarray(f) for _, f, _, _ in batch])
            if k < self.max_batch:  # pad to the one compiled shape
                feats = jnp.concatenate([
                    feats,
                    jnp.zeros((self.max_batch - k,) + feats.shape[1:],
                              feats.dtype),
                ])
            outs = jax.device_get(self._batch_fwd(self.server_params, feats))
            now = time.perf_counter()
            for i, (cid, _, rid, popped) in enumerate(batch):
                if rid in responses:
                    raise RuntimeError(f"request {rid} answered twice")
                responses[rid] = np.asarray(outs[i])
                report.answered += 1
                report.per_client[cid]["answered"] += 1
                report.latency_cycles[rid] = t - admitted_cycle.pop(rid)
                pushed = admitted_wall.pop(rid)
                report.latency_ms[rid] = (now - pushed) * 1e3
                report.queue_wait_ms[rid] = (popped - pushed) * 1e3
            report.batches += 1
            report.batched_items += k
            return k

        t = 0
        t0 = time.perf_counter()
        while t < trace.horizon or len(queue) > 0:
            # ---- admissions: this cycle's arrivals release + push
            for req in arrivals.get(t, ()):
                c = req.client_id
                with jax.profiler.TraceAnnotation(
                        "repro.serve.release", req_id=req.req_id, client=c):
                    report.offered += 1
                    report.per_client[c]["offered"] += 1
                    idx = rngs[c].integers(0, len(xs[c]), size=self.request_batch)
                    releases[c] += 1  # budget spent whether or not the push lands
                    feats = self._release(c, xs[c][idx], releases[c])
                    if self.record_features:
                        report.features[req.req_id] = np.asarray(feats)
                    if queue.push(c, feats, req.req_id):
                        report.accepted += 1
                        report.per_client[c]["accepted"] += 1
                        admitted_cycle[req.req_id] = t
                        admitted_wall[req.req_id] = time.perf_counter()
                        owner[req.req_id] = c
                        inflight[c] += 1
                        max_inflight[c] = max(max_inflight[c], inflight[c])
                    else:
                        report.dropped += 1
                        report.per_client[c]["dropped"] += 1
                        if len(queue) >= self.queue_size:
                            report.dropped_full += 1
                        else:  # room in the queue ⇒ the per-client cap rejected
                            report.dropped_cap += 1
            # ---- one consumer cycle; a cycle that finds requests queued
            # is one ``repro.serve.batch`` span
            if len(queue) > 0:
                with jax.profiler.TraceAnnotation("repro.serve.batch") as span:
                    span.set_metadata(size=consume(t))
            else:
                consume(t)
            t += 1
        report.wall_s = time.perf_counter() - t0
        report.cycles = t
        report.max_inflight_per_client = max_inflight
        report.releases_per_client = releases
        report.queue_stats = queue.stats()
        if self.keep_responses:
            report.responses = responses
        # conservation: every offered request is answered, dropped or shed
        assert report.offered == report.answered + report.dropped + report.shed
        assert report.accepted == report.answered + report.shed
        assert not admitted_cycle, "admitted requests left unaccounted"
        return report
