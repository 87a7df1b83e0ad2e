"""Mesh construction helpers (the production mesh itself lives in
repro.launch.mesh per the assignment; these are the generic utilities)."""

from __future__ import annotations

import jax
from jax.sharding import Mesh


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def tp_mesh(num_shards: int) -> Mesh:
    """Flat ``("tp",)`` mesh over the first ``num_shards`` visible devices —
    the serving engine's tensor-parallel mesh (DESIGN.md §13). Unlike
    ``make_mesh`` the shard count need not equal the device count: a tp=2
    engine on an 8-device host uses devices [0, 1]."""
    import numpy as np
    devs = jax.devices()
    if num_shards < 1:
        raise ValueError(f"tp mesh needs >= 1 shard, got {num_shards}")
    if num_shards > len(devs):
        raise ValueError(
            f"tp={num_shards} exceeds the {len(devs)} visible device(s); "
            f"on CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{num_shards} BEFORE jax initializes")
    return Mesh(np.asarray(devs[:num_shards]), ("tp",))


def sp_tp_mesh(sp: int, tp: int) -> Mesh:
    """2-D ``("sp", "tp")`` mesh over the first ``sp * tp`` visible devices
    — the serving engine's sequence-parallel x tensor-parallel mesh
    (DESIGN.md §14). Row-major: shards that differ only in the tp
    coordinate are adjacent, so the per-layer tp psums stay within a row
    while the sp KV gather/ring crosses rows."""
    import numpy as np
    devs = jax.devices()
    if sp < 1 or tp < 1:
        raise ValueError(f"sp/tp mesh needs >= 1 shard per axis, got "
                         f"sp={sp}, tp={tp}")
    need = sp * tp
    if need > len(devs):
        raise ValueError(
            f"sp={sp} x tp={tp} needs {need} devices but only "
            f"{len(devs)} visible; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} BEFORE jax "
            f"initializes")
    return Mesh(np.asarray(devs[:need]).reshape(sp, tp), ("sp", "tp"))


def data_axis_names(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def num_data_shards(mesh: Mesh) -> int:
    n = 1
    for a in data_axis_names(mesh):
        n *= mesh.shape[a]
    return n
