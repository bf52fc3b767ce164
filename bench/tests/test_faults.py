"""The comparison that decides ``correct`` fails a broken timed path.

Each test drives a whole run of a tiny cell on the CPU, with the look for a
chip skipped and one fault planted underneath the timed path, and sees
``correct`` come out false. The control, the reference computed in
bfloat16 and put in the program's place, fails too."""
import jax.numpy as jnp
import pytest

from conftest import run_cell


def test_a_step_that_returns_its_state_unchanged(checkout, capsys, monkeypatch):
    from repro.core.session import FusedEngine

    real = FusedEngine.run

    def stale(self, state, shards, **kw):
        _, history = real(self, jax_copy(state), shards, **kw)
        return state, history

    monkeypatch.setattr(FusedEngine, "run", stale)
    res = run_cell(capsys, "tiny-covid-train")
    assert res["correct"] is False
    c = res["checks"]["change_median_vs_bf16"]
    assert c["value"] > c["limit"]


def jax_copy(tree):
    import jax

    return jax.tree.map(jnp.copy, tree)


@pytest.mark.parametrize("workload", ["tiny-covid-train", "tiny-vgg-train"])
def test_half_of_the_batch_left_out(checkout, capsys, monkeypatch, workload):
    import repro.core.adapters as adapters

    real = adapters.bce_with_logits

    def half(out, y):
        n = y.shape[0] // 2
        return real(out[:n], y[:n])

    monkeypatch.setattr(adapters, "bce_with_logits", half)
    res = run_cell(capsys, workload)
    assert res["correct"] is False


@pytest.mark.parametrize("workload", ["tiny-covid-serve", "tiny-vgg-serve"])
def test_an_answer_altered_where_it_is_produced(checkout, capsys, monkeypatch, workload):
    import repro.serving.server as server

    real = server.make_server_batch_forward

    def altered(adapter, mesh=None):
        fwd = real(adapter, mesh)
        return lambda params, feats: fwd(params, feats).at[0].add(0.05)

    monkeypatch.setattr(server, "make_server_batch_forward", altered)
    res = run_cell(capsys, workload)
    assert res["correct"] is False
    c = res["checks"]["answer_vs_bf16"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("workload", ["tiny-covid-serve", "tiny-vgg-serve"])
def test_half_of_a_serving_batch_left_out(checkout, capsys, monkeypatch, workload):
    import repro.serving.server as server

    real = server.make_server_batch_forward

    def half(adapter, mesh=None):
        fwd = real(adapter, mesh)

        def run(params, feats):
            n = feats.shape[0] // 2
            return fwd(params, feats.at[n:].set(0.0))
        return run

    monkeypatch.setattr(server, "make_server_batch_forward", half)
    res = run_cell(capsys, workload)
    assert res["correct"] is False


@pytest.mark.parametrize("workload", ["tiny-covid-train", "tiny-vgg-train",
                                      "tiny-covid-serve", "tiny-vgg-serve"])
def test_the_control_is_not_correct(checkout, capsys, monkeypatch, workload):
    """The reference computed in bfloat16, put in the program's place,
    fails the cell's comparison: it reads 1 on every ``_vs_bf16`` share."""
    import harness

    real = harness.load_module

    def control_check(self):
        if hasattr(self, "sample"):
            return self.judged(self.readings(self.yardstick()[0], jnp.bfloat16))
        return self.judged(self.readings(jnp.bfloat16))

    def load_module(kind, name):
        mod = real(kind, name)
        if kind == "runners":
            monkeypatch.setattr(mod.Run, "check", control_check)
        return mod

    monkeypatch.setattr(harness, "load_module", load_module)
    res = run_cell(capsys, workload)
    assert res["correct"] is False
