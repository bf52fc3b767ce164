"""Training cells: ``SplitSession.fit`` on the configuration, timed.

Set-up builds the session from the seed and makes its first ``fit`` call,
the window's own call (``epochs_per_call`` epochs of ``steps_per_epoch``
steps), which compiles the epoch program and is the call that the reference
follows. The window then makes the same call on the same session until its
time is up.

Read after the window, with the program's state freed, against the plain
reference over the same steps: the worst epoch's gap between mean losses
and between mean pre-clip gradient norms as ``fit`` reports them; the
median over trunk leaves of the gap between the norms of AdamW's first
moment; the worst and the median leaf's gap between the norms of the
parameters' change; and the same gaps as shares of the gaps that the
reference computed in bfloat16 reads (``<gap>_vs_bf16``). The cell's limits
file names the numbers that are compared.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from runners.common import against_control, hospital_shards, leaf_gaps, rel_gap, session
from reference.split_cnn import leaf_norms, train_epochs

# leaves whose gradient is nought to rounding in the reference move under
# AdamW by round-off alone: their change is left out of the comparison
STILL_LEAF = 1e-3
# the gaps that are also read as a share of the bfloat16 reference's
RELATIVE = ("loss", "grad_norm", "change", "change_median")


class Run:
    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.per_call = (traffic["epochs_per_call"] * traffic["steps_per_epoch"]
                         * cfg["hospitals"] * (cfg["server_batch"] // cfg["hospitals"]))

    def setup(self) -> None:
        from jax.flatten_util import ravel_pytree

        self.shards = hospital_shards(self.cfg, self.seed)
        self.session = session(self.cfg, self.seed)
        server0 = jax.tree.map(jnp.copy, self.session.state["server"])
        hist = self.session.fit(self.shards, epochs=self.traffic["epochs_per_call"],
                                steps_per_epoch=self.traffic["steps_per_epoch"])
        state = self.session.state
        _, unravel = ravel_pytree(state["server"])
        self.program = {
            "loss": [h["loss"] for h in hist],
            "grad_norm": [h["grad_norm"] for h in hist],
            "mu": leaf_norms(unravel(state["opt"]["mu"])),
            "change": leaf_norms(jax.tree.map(jnp.subtract, state["server"], server0)),
        }
        del server0, state

    def unit(self) -> dict:
        steps = self.traffic["epochs_per_call"] * self.traffic["steps_per_epoch"]
        with jax.profiler.TraceAnnotation("bench.fit"):
            hist = self.session.fit(self.shards, epochs=self.traffic["epochs_per_call"],
                                    steps_per_epoch=self.traffic["steps_per_epoch"])
        bad = sum(not math.isfinite(h["loss"]) for h in hist)
        return {"samples": self.per_call, "steps": steps,
                "failed_steps": bad * self.traffic["steps_per_epoch"]}

    def traced_units(self) -> int:
        return self.traffic["traced_calls"]

    def counters(self) -> dict:
        return {}

    def end_to_end(self, totals: dict) -> dict:
        return {"train_samples_per_s": totals["samples"] / totals["seconds"]}

    def outcome(self, totals: dict):
        return int(totals["steps"]), int(totals["failed_steps"])

    def release(self) -> None:
        self.session = None

    def readings(self, dtype=jnp.float32, keep_rows=None, reverse_rows=False) -> dict:
        """The reference over the checked call, computed in ``dtype``, as
        the readings the program's are compared with."""
        return train_epochs(self.cfg, self.shards, self.seed,
                            self.traffic["epochs_per_call"],
                            self.traffic["steps_per_epoch"], dtype, keep_rows,
                            reverse_rows)

    def compare(self, got: dict, ref: dict) -> dict:
        """The numbers compared: each a gap that is 0 when ``got`` repeats
        the reference. ``got`` holds per-epoch means (as ``fit`` reports
        them) or per-epoch lists of per-step values (the reference's)."""
        mean = lambda v: float(np.mean(v)) if isinstance(v, list) else float(v)
        worst = lambda a, b: max(rel_gap(mean(x), mean(y)) for x, y in zip(a, b, strict=True))
        med = float(np.median(list(ref["mu"].values())))
        moving = {k for k, v in ref["mu"].items() if v >= STILL_LEAF * med}
        by_epoch = lambda a, b: [rel_gap(mean(x), mean(y)) for x, y in zip(a, b, strict=True)]
        gaps = dict(zip(ref["change"], leaf_gaps(got["change"], ref["change"])))
        return {
            "loss": worst(got["loss"], ref["loss"]),
            "grad_norm": worst(got["grad_norm"], ref["grad_norm"]),
            "first_moment": float(np.median(leaf_gaps(got["mu"], ref["mu"]))),
            "change": max(gaps[k] for k in moving),
            "change_median": float(np.median([gaps[k] for k in moving])),
            # read, not compared: where the gaps lie
            "loss_by_epoch": by_epoch(got["loss"], ref["loss"]),
            "grad_norm_by_epoch": by_epoch(got["grad_norm"], ref["grad_norm"]),
            "change_by_leaf": {k: gaps[k] for k in moving},
        }

    def yardstick(self):
        """The float32 reference's readings and the bfloat16 reference's
        gaps from them, computed once."""
        if not hasattr(self, "_yardstick"):
            ref = self.readings()
            self._yardstick = ref, self.compare(self.readings(jnp.bfloat16), ref)
        return self._yardstick

    def judged(self, got: dict) -> dict:
        """``got``'s gaps from the float32 reference, and each as a share of
        the bfloat16 reference's gap."""
        ref, low = self.yardstick()
        gaps = self.compare(got, ref)
        return {**gaps, **against_control(gaps, low, RELATIVE)}

    def check(self) -> dict:
        return self.judged(self.program)
