"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device state
(the dry-run must set XLA_FLAGS before the first device query).

Production target: TPU v5e, 256 chips/pod. Single pod = (data=16, model=16);
multi-pod = (pod=2, data=16, model=16) = 512 chips.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules
    (``repro.sharding.logical``) constrain with ``P.UNCONSTRAINED`` and leave
    layout to GSPMD, which an ``Explicit`` axis (the default) refuses."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False, shape=None):
    """Default (16,16) / (2,16,16); ``shape`` overrides the (data, model)
    split (same chip count) — prefill/decode workloads often want a wider
    data axis than training."""
    if shape is None:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if len(shape) == 3 else ("data", "model")
    return _auto_mesh(shape, axes)


def _check_divides(n_clients, axis_size: int, axis: str) -> None:
    """``n_clients`` must divide over the client axis: ``shard_map`` would
    otherwise fail deep inside the traced step (or GSPMD would silently pad
    the bank layout) — fail loud at mesh construction instead."""
    if n_clients is not None and int(n_clients) % int(axis_size) != 0:
        raise ValueError(
            f"n_clients={int(n_clients)} does not divide over the "
            f"{axis!r} mesh axis of size {int(axis_size)}; pick a device "
            f"count that divides n_clients (the stacked client banks shard "
            f"their leading axis evenly, one hospital group per device)"
        )


def make_client_mesh(n_devices=None, axis: str = "clients", *, n_clients=None):
    """1-D mesh over the split-learning client axis: each hospital's privacy
    bank (and its slice of the epoch data) lives on its own device. Used by
    ``SplitSession(mesh=...)``; on a 1-device host this is the bit-exact
    no-op mesh the CPU parity test drives.

    ``n_clients``, when given, is validated against the device count up
    front (the count must divide ``n_clients``) — the alternative is a
    shape error from inside ``shard_map`` long after the mesh was built."""
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if not 1 <= n <= len(devs):
        raise ValueError(
            f"make_client_mesh: n_devices={n} outside [1, {len(devs)}] "
            f"available devices"
        )
    _check_divides(n_clients, n, axis)
    return Mesh(np.asarray(devs[:n]), (axis,))


def make_split_mesh(n_clients_axis: int = 1, n_model_axis: int = 1, *,
                    n_clients=None,
                    client_axis: str = "clients", model_axis: str = "model"):
    """2-D ``("clients", "model")`` mesh for the split-learning platform.

    The ``"clients"`` axis shards the canonical stacked client banks (and
    fleet production) one hospital group per device row; the ``"model"``
    axis shards the server TRUNK tensor-parallel (Megatron-style column/row
    alternation — see ``repro.sharding.specs.trunk_specs``). A ``(1, 1)``
    mesh is the bit-exact no-op every engine is pinned against; ``(N, 1)``
    is the PR 2 client-axis layout; ``(1, N)`` puts every device on the
    trunk — the right shape for trunk-heavy workloads (see
    docs/benchmarks.md, the ``sharded`` block).

    Validates up front: the grid must fit the host's devices, and
    ``n_clients`` (when given) must divide over the client axis."""
    import numpy as np
    from jax.sharding import Mesh

    c, m = int(n_clients_axis), int(n_model_axis)
    if c < 1 or m < 1:
        raise ValueError(
            f"make_split_mesh: axis sizes must be >= 1, got ({c}, {m})"
        )
    devs = jax.devices()
    if c * m > len(devs):
        raise ValueError(
            f"make_split_mesh: a ({c}, {m}) grid needs {c * m} devices but "
            f"only {len(devs)} are available (CI simulates 8 with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8)"
        )
    _check_divides(n_clients, c, client_axis)
    return Mesh(
        np.asarray(devs[: c * m]).reshape(c, m), (client_axis, model_axis)
    )


def make_host_mesh(model: int = 1):
    """Tiny mesh over whatever devices exist (CPU tests)."""
    n = len(jax.devices())
    assert n % model == 0
    return _auto_mesh((n // model, model), ("data", "model"))


def data_axis_size(mesh) -> int:
    return int(
        __import__("numpy").prod(
            [mesh.shape[a] for a in ("pod", "data") if a in mesh.axis_names]
        )
    )
