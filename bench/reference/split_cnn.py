"""Plain reference of the paper's split CNNs: detached split training and
guarded split inference, in straightforward ``jax.numpy``.

It follows the configuration file and the published algorithm, and imports
nothing of the program. What it must share with the program to compare
step by step it derives from the run's seed by the documented schedule:

* weights: per stage ``split(key, 64)``; a convolution takes the next key,
  splits it and draws its kernel from the first half, LeCun-normal, with a
  zero bias; a dense layer draws from the next key. The trunk comes from the
  first of ``split(PRNGKey(seed), hospitals + 1)``, hospital c's privacy
  layer from key c + 1.
* training batches: epoch e (from 1) uses ``fold_in(PRNGKey(seed), e)``,
  split into an index key (``randint`` over every step, hospital and row)
  and a noise key split once per step; each step's key is split once per
  hospital. AdamW's step count runs on across epochs.
* releases: the privacy layer (each convolution written as a sum over
  its taps, see ``_conv_taps``) adds ``privacy_noise * normal(key)`` after its
  pool; the guard clips each sample's features to ``clip_norm`` and adds
  ``noise_scale * normal(fold_in(key, 7919))``.
* serving: hospital c's request number r (from 1) in a call releases on
  ``fold_in(fold_in(fold_in(PRNGKey(seed), step), c), r)``, on a row drawn
  by ``default_rng((trace_seed, 977, c))``.

``dtype`` is the precision values are kept and computed in. ``float32``
at JAX's default matmul precision, as the configurations state it, is the
reference (on the TPU that rounds every convolution and matmul operand to
bfloat16 and accumulates in float32); ``bfloat16`` is the control.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

GUARD_KEY_FOLD = 7919
SERVE_SAMPLE_TAG = 977


# ---------------------------------------------------------------- weights
def _lecun(key, fan_in, shape):
    scale = 1.0 / jnp.sqrt(jnp.asarray(fan_in, jnp.float32))
    return jax.random.normal(key, shape, jnp.float32) * scale


def init_model(key, model: dict) -> dict:
    """The whole model ``{"client": ..., "server": ...}`` from one key."""
    keys = iter(jax.random.split(key, 64))
    cin = model["in_channels"]
    stages = []
    for filters, repeats in model["stages"]:
        convs = []
        for _ in range(repeats):
            kw, _ = jax.random.split(next(keys))
            convs.append({"w": _lecun(kw, cin * 9, (3, 3, cin, filters)),
                          "b": jnp.zeros((filters,), jnp.float32)})
            cin = filters
        stages.append(convs)
    h, w = (s // 2 ** len(model["stages"]) for s in model["input_hw"])
    d_in = h * w * cin
    dense = []
    for units in model["dense_units"]:
        dense.append({"w": _lecun(next(keys), d_in, (d_in, units)),
                      "b": jnp.zeros((units,), jnp.float32)})
        d_in = units
    out = {"w": _lecun(next(keys), d_in, (d_in, model["n_classes"])),
           "b": jnp.zeros((model["n_classes"],), jnp.float32)}
    cut = model["cut_layers"]
    return {"client": {"stages": stages[:cut]},
            "server": {"stages": stages[cut:], "dense": dense, "out": out}}


@functools.partial(jax.jit, static_argnums=(1,))
def _init_all(key, cfg_json):
    cfg = json.loads(cfg_json)
    k0, *cks = jax.random.split(key, cfg["hospitals"] + 1)
    server = init_model(k0, cfg["model"])["server"]
    banks = [init_model(k, cfg["model"])["client"] for k in cks]
    return server, banks


def _key(cfg: dict) -> str:
    """The configuration as a hashable cache key for compiled functions."""
    return json.dumps(cfg, sort_keys=True)


def init_state(seed: int, cfg: dict):
    """``(trunk, [bank per hospital])`` for the run's seed, float32."""
    return _init_all(jax.random.PRNGKey(seed), _key(cfg))


def cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


# ---------------------------------------------------------------- forward
def _conv(x, p):
    y = jax.lax.conv_general_dilated(x, p["w"], (1, 1), "SAME",
                                     dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return jax.nn.relu(y + p["b"])


def _conv_taps(x, p):
    """The same convolution as the sum over its taps of a matmul of the
    shifted input with that tap's weights. On the TPU a matmul over a single
    input channel compiles to an exact float32 product while a convolution
    rounds its operands to bfloat16, so at default precision this form keeps
    a one-channel image exact in the privacy layer's first convolution."""
    kh, kw = p["w"].shape[:2]
    h, w = x.shape[1:3]
    xp = jnp.pad(x, ((0, 0), (kh // 2, kh // 2), (kw // 2, kw // 2), (0, 0)))
    y = sum(xp[:, i:i + h, j:j + w] @ p["w"][i, j] for i in range(kh) for j in range(kw))
    return jax.nn.relu(y + p["b"])


def _dense(x, p):
    return x @ p["w"] + p["b"]


def _pool(x):
    b, h, w, c = x.shape
    return jnp.max(x.reshape(b, h // 2, 2, w // 2, 2, c), axis=(2, 4))


def client_release(bank, x, key, cfg, dtype):
    """One hospital's privacy layer and the guard at the cut:
    ``x [b, H, W, C] -> released features [b, ...]``."""
    x = x.astype(dtype)
    for convs in bank["stages"]:
        for p in convs:
            x = _conv_taps(x, p)
        x = _pool(x)
    x = x + jnp.asarray(cfg["model"]["privacy_noise"], x.dtype) * \
        jax.random.normal(key, x.shape, jnp.float32).astype(x.dtype)
    g = cfg["guard"]
    n2 = jnp.sum(jnp.square(x), axis=(1, 2, 3), keepdims=True)
    scale = jnp.minimum(1.0, g["clip_norm"] / jnp.sqrt(jnp.maximum(n2, 1e-24)))
    noise = jax.random.normal(jax.random.fold_in(key, GUARD_KEY_FOLD), x.shape,
                              jnp.float32)
    return x * scale.astype(x.dtype) + \
        jnp.asarray(g["noise_scale"], x.dtype) * noise.astype(x.dtype)


def trunk_forward(server, feats):
    """Remaining conv stages and the dense head: ``[n, ...] -> logits [n, k]``."""
    x = feats
    for convs in server["stages"]:
        for p in convs:
            x = _conv(x, p)
        x = _pool(x)
    x = x.reshape(x.shape[0], -1)
    for d in server["dense"]:
        x = jax.nn.relu(_dense(x, d))
    return _dense(x, server["out"])


def _bce(logits, y):
    logits = logits.reshape(y.shape).astype(jnp.float32)
    return jnp.mean(jnp.maximum(logits, 0) - logits * y
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


# ---------------------------------------------------------------- training
def batch_plan(seed: int, epoch: int, lens, steps: int, per_hospital: int):
    """``(idx [steps, hospitals, b], step_keys [steps])`` of one epoch."""
    ekey = jax.random.fold_in(jax.random.PRNGKey(seed), epoch)
    k_idx, k_noise = jax.random.split(ekey)
    lens = jnp.asarray(lens, jnp.int32)
    idx = jax.random.randint(k_idx, (steps, len(lens), per_hospital), 0,
                             lens[None, :, None])
    return np.asarray(idx), jax.random.split(k_noise, steps)


@functools.lru_cache(maxsize=None)
def _train_step_fn(cfg_json, dtype_name, keep_rows, reverse_rows):
    cfg = json.loads(cfg_json)
    dtype = jnp.dtype(dtype_name)
    opt = cfg["optimizer"]
    w = np.asarray(cfg["shares"], np.float64)
    weights = jnp.asarray(w / w.sum(), jnp.float32)

    def loss_fn(server, feats, ys):
        c, b = feats.shape[0], feats.shape[1]
        out = trunk_forward(server, feats.reshape((c * b,) + feats.shape[2:]))
        out = out.reshape((c, b) + out.shape[1:])
        per = jnp.stack([_bce(out[i], ys[i]) for i in range(c)])
        return jnp.sum(weights * per)

    def step(server, mu, nu, t, banks, xs, ys, key):
        keys = jax.random.split(key, len(banks))
        feats = jnp.stack([client_release(bank, xs[i], keys[i], cfg, dtype)
                           for i, bank in enumerate(banks)])
        if keep_rows is not None:  # a fault: the rest of each batch left out
            feats, ys = feats[:, :keep_rows], ys[:, :keep_rows]
        if reverse_rows:  # the same sums in another order: rounding alone
            feats, ys = feats[:, ::-1], ys[:, ::-1]
        loss, g = jax.value_and_grad(loss_fn)(server, jax.lax.stop_gradient(feats), ys)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(a.astype(jnp.float32)))
                             for a in jax.tree.leaves(g)))
        clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
        g = jax.tree.map(lambda a: (a * clip.astype(a.dtype)), g)
        b1, b2 = opt["b1"], opt["b2"]
        mu = jax.tree.map(lambda m, a: (b1 * m + (1 - b1) * a).astype(m.dtype), mu, g)
        nu = jax.tree.map(lambda v, a: (b2 * v + (1 - b2) * a * a).astype(v.dtype), nu, g)
        tf = (t + 1).astype(jnp.float32)
        bc1, bc2 = 1 - b1 ** tf, 1 - b2 ** tf

        def upd(p, m, v):
            u = (m / bc1.astype(m.dtype)) / (jnp.sqrt(v / bc2.astype(v.dtype)) + opt["eps"])
            return (p - (opt["lr"] * u).astype(p.dtype)).astype(p.dtype)

        server = jax.tree.map(upd, server, mu, nu)
        return server, mu, nu, loss, gnorm

    return jax.jit(step)


def train_epochs(cfg: dict, shards, seed: int, epochs: int, steps: int,
                 dtype=jnp.float32, keep_rows=None, reverse_rows=False):
    """The first ``epochs`` epochs of ``steps`` steps of detached training
    from the seed. Returns the readings the benchmark compares: each
    epoch's per-step losses and pre-clip gradient norms, and per-leaf norms
    of the first moment and of the trunk's change after the last step,
    keyed by leaf path. ``keep_rows`` plants a fault: only the first rows of
    each hospital's batch reach the loss. ``reverse_rows`` feeds each
    hospital's rows to the trunk in reverse order: the same arithmetic, with
    the batch's sums taken in another order."""
    dtype = jnp.dtype(dtype)
    server0, banks = init_state(seed, cfg)
    server, banks = cast(server0, dtype), cast(banks, dtype)
    mu = jax.tree.map(jnp.zeros_like, server)
    nu = jax.tree.map(jnp.zeros_like, server)
    per = cfg["server_batch"] // cfg["hospitals"]
    lens = [len(x) for x, _ in shards]
    step = _train_step_fn(_key(cfg), dtype.name, keep_rows, reverse_rows)
    losses, gnorms = [], []
    for epoch in range(1, epochs + 1):
        idx, step_keys = batch_plan(seed, epoch, lens, steps, per)
        losses.append([])
        gnorms.append([])
        for t in range(steps):
            xs = jnp.asarray(np.stack([shards[c][0][idx[t, c]]
                                       for c in range(len(shards))]))
            ys = jnp.asarray(np.stack([shards[c][1][idx[t, c]]
                                       for c in range(len(shards))]))
            server, mu, nu, loss, gn = step(server, mu, nu,
                                            jnp.int32((epoch - 1) * steps + t),
                                            banks, xs, ys, step_keys[t])
            losses[-1].append(float(loss))
            gnorms[-1].append(float(gn))
    delta = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b, server, server0)
    return {"loss": losses, "grad_norm": gnorms,
            "mu": leaf_norms(mu), "change": leaf_norms(delta)}


def leaf_norms(tree) -> dict:
    """``{leaf path: float32 L2 norm}`` of a parameter-shaped tree."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.device_get([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                            for _, a in flat])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in zip(flat, norms)}


# ---------------------------------------------------------------- serving
def serve_rows(trace_seed: int, arrivals, shards) -> dict:
    """``{req_id: (client, release, row)}``: the row each request reads and
    its release number, replayed from the trace in admission order."""
    rngs = [np.random.default_rng((int(trace_seed), SERVE_SAMPLE_TAG, c))
            for c in range(len(shards))]
    count = [0] * len(shards)
    out = {}
    for rid, c, _ in arrivals:
        row = int(rngs[c].integers(0, len(shards[c][0]), size=1)[0])
        count[c] += 1
        out[rid] = (c, count[c], row)
    return out


@functools.lru_cache(maxsize=None)
def _serve_fn(cfg_json, dtype_name):
    cfg = json.loads(cfg_json)
    dtype = jnp.dtype(dtype_name)

    def answer(server, bank, xs, keys):
        feats = jax.vmap(lambda x, k: client_release(bank, x[None], k, cfg, dtype)[0])(
            xs, keys)
        return trunk_forward(server, feats).astype(jnp.float32)

    return jax.jit(answer)


def serve_answers(cfg: dict, shards, seed: int, step: int, requests,
                  dtype=jnp.float32) -> np.ndarray:
    """Logits ``[n, k]`` for ``requests = [(client, release, row), ...]``."""
    dtype = jnp.dtype(dtype)
    server, banks = init_state(seed, cfg)
    server, banks = cast(server, dtype), cast(banks, dtype)
    base = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    fn = _serve_fn(_key(cfg), dtype.name)
    out = np.zeros((len(requests), cfg["model"]["n_classes"]), np.float32)
    for c in range(len(banks)):
        sel = [i for i, r in enumerate(requests) if r[0] == c]
        if not sel:
            continue
        ck = jax.random.fold_in(base, c)
        keys = jnp.stack([jax.random.fold_in(ck, requests[i][1]) for i in sel])
        xs = jnp.asarray(np.stack([shards[c][0][requests[i][2]] for i in sel]))
        out[sel] = np.asarray(fn(server, banks[c], xs, keys))
    return out
