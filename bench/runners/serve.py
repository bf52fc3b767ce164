"""Serving cells: ``SplitInferenceServer.serve`` on the configuration's
seeded initial state, timed over Poisson traces: the same cycles of
arrivals in every call and every run (drawn once from the traffic's
``counts_seed``), in an order drawn from the seed and the call's number.

The server is built once per run as ``SplitSession.serve`` builds it (the
session's guard, its root key and canonical state). Set-up serves one
trace whose cycles bring 1 to ``max_batch + 1`` arrivals, so that every
batch fill the window can meet is compiled before it. Each call of the
window serves those cycles in a new order.

Checked after the window: the ledger of every call (offered = answered +
dropped + shed) and, for a sample of answered requests drawn from the
seed, the answers against the plain reference's, also as a share of how
far the reference computed in bfloat16 lies from it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from runners.common import against_control, hospital_shards, session
from gen.arrivals import requests, shuffled_counts
from reference.split_cnn import serve_answers, serve_rows


def _trace(kind: str, seed: int, counts: np.ndarray):
    from repro.serving.traces import ServeRequest, Trace

    reqs = requests(counts)
    return reqs, Trace(kind=kind, seed=int(seed), n_clients=counts.shape[1],
                       horizon=counts.shape[0],
                       requests=tuple(ServeRequest(r, c, t) for r, c, t in reqs))


def warm_counts(hospitals: int, max_batch: int) -> np.ndarray:
    """Cycle t brings t + 1 arrivals, dealt round-robin over the hospitals,
    for t up to ``max_batch``: every batch fill from 1 to ``max_batch``, and
    a carried-over request."""
    counts = np.zeros((max_batch + 1, hospitals), np.int64)
    for t in range(max_batch + 1):
        for i in range(t + 1):
            counts[t, i % hospitals] += 1
    return counts


class Run:
    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.calls = 0
        self.kept = []  # (trace seed, arrivals, {req_id: answer})
        self.ledger_gap = 0
        self.latency_ms = []
        self.queue_wait_ms = []

    def setup(self) -> None:
        from repro.serving.server import SplitInferenceServer

        t = self.traffic
        self.shards = hospital_shards(self.cfg, self.seed)
        s = session(self.cfg, self.seed)
        self.step = int(s.state["step"])
        self.server = SplitInferenceServer(
            s.adapter, s.state, guard=s.guard, max_batch=t["max_batch"],
            queue_size=t["queue_size"], per_client_cap=t["per_client_cap"],
            max_wait=t["max_wait"], request_batch=t["request_batch"],
            keep_responses=True, root_key=jax.random.PRNGKey(self.seed))
        _, warm = _trace("warm", self.seed, warm_counts(self.cfg["hospitals"],
                                                        t["max_batch"]))
        self.server.serve(warm, self.shards)

    def trace_seed(self, call: int) -> int:
        return self.seed * 4096 + call

    def unit(self) -> dict:
        t = self.traffic
        self.calls += 1
        tseed = self.trace_seed(self.calls)
        counts = shuffled_counts(self.cfg["shares"], rate=t["rate"],
                                 horizon=t["cycles_per_call"],
                                 counts_seed=t["counts_seed"], seed=tseed)
        arrivals, trace = _trace("poisson", tseed, counts)
        with jax.profiler.TraceAnnotation("bench.serve"):
            rep = self.server.serve(trace, self.shards)
        self.ledger_gap += abs(rep.offered - rep.answered - rep.dropped - rep.shed)
        self.latency_ms.extend(rep.latency_ms.values())
        self.queue_wait_ms.extend(rep.queue_wait_ms.values())
        pick = np.random.default_rng((self.seed, self.calls, 5))
        ids = sorted(rep.responses)
        take = pick.choice(len(ids), size=min(t["checked_per_call"], len(ids)),
                           replace=False) if ids else []
        self.kept.append((tseed, arrivals,
                          {ids[i]: np.asarray(rep.responses[ids[i]]) for i in take}))
        return {"offered": rep.offered, "answered": rep.answered,
                "batches": rep.batches}

    def traced_units(self) -> int:
        return self.traffic["traced_calls"]

    def counters(self) -> dict:
        return {"latency_ms": list(self.latency_ms),
                "queue_wait_ms": list(self.queue_wait_ms)}

    def end_to_end(self, totals: dict) -> dict:
        return {"serve_requests_per_s": totals["answered"] / totals["seconds"]}

    def outcome(self, totals: dict):
        return int(totals["offered"]), int(totals["offered"] - totals["answered"])

    def release(self) -> None:
        self.server = None

    def sample(self):
        """The checked requests, drawn from the seed: ``[(client, release,
        row)]`` and the program's answers ``[n, k]``."""
        pool = [(ci, rid) for ci, (_, _, ans) in enumerate(self.kept) for rid in ans]
        rng = np.random.default_rng((self.seed, 9))
        n = min(self.traffic["checked_requests"], len(pool))
        chosen = sorted(pool[i] for i in rng.choice(len(pool), size=n, replace=False))
        reqs, got = [], []
        rows = {}
        for ci, rid in chosen:
            tseed, arrivals, ans = self.kept[ci]
            if ci not in rows:
                rows[ci] = serve_rows(tseed, arrivals, self.shards)
            reqs.append(rows[ci][rid])
            got.append(ans[rid].reshape(-1))
        return reqs, np.stack(got) if got else np.zeros((0, 1), np.float32)

    def readings(self, reqs, dtype=jnp.float32) -> np.ndarray:
        return serve_answers(self.cfg, self.shards, self.seed, self.step, reqs, dtype)

    def compare(self, got: np.ndarray, ref: np.ndarray) -> dict:
        """``answer``: the widest gap between the program's and the
        reference's answers; ``answer_rms``: the root mean square of the
        gaps; both over the RMS of the reference's answers."""
        if not ref.size:
            return {"answer": float("inf"), "answer_rms": float("inf"),
                    "ledger": float(self.ledger_gap)}
        scale = max(float(np.sqrt(np.mean(np.square(ref)))), 1e-30)
        gap = np.abs(got - ref)
        return {"answer": float(np.max(gap)) / scale,
                "answer_rms": float(np.sqrt(np.mean(np.square(gap)))) / scale,
                "ledger": float(self.ledger_gap)}

    def yardstick(self):
        """The checked requests, the float32 reference's answers to them,
        and the bfloat16 reference's gaps from those, computed once."""
        if not hasattr(self, "_yardstick"):
            reqs, got = self.sample()
            ref = self.readings(reqs)
            low = self.compare(self.readings(reqs, jnp.bfloat16), ref)
            self._yardstick = reqs, got, ref, low
        return self._yardstick

    def judged(self, got: np.ndarray) -> dict:
        """``got``'s gaps from the float32 reference's answers, and each as a
        share of the bfloat16 reference's gap."""
        _, _, ref, low = self.yardstick()
        gaps = self.compare(got, ref)
        return {**gaps, **against_control(gaps, low, ("answer", "answer_rms"))}

    def check(self) -> dict:
        return self.judged(self.yardstick()[1])
