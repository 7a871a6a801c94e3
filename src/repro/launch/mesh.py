"""Production mesh builders + fleet topology.

A function, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before any jax import).

Besides the single-host device meshes, this module describes the
*fleet*: an N-replica serving topology (one serving engine + host/disk
tier pair per replica, linked by a priced NIC) that
:class:`~repro.serve.router.Router` consumes — the mesh layer's answer to
ROADMAP items 1–2 (the network as another engine class, fleet-scale
serving)."""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import AxisType


@dataclasses.dataclass(frozen=True)
class FleetTopology:
    """N serving replicas behind one router.

    Each replica is an independent :class:`~repro.serve.Engine` with its
    own host/disk tier population (``host_bytes_per_replica`` sizes a
    per-replica :class:`~repro.core.pool.HostPool`; ``None`` = unpooled).
    The inter-replica link is priced with the same constants the
    simulator's sixth channel uses (``HardwareModel.nic_bw`` /
    ``nic_latency``), so the router's migrate-vs-re-prefill choice and the
    simulator's crossover prediction talk about the same wire."""

    n_replicas: int = 3
    host_bytes_per_replica: int | None = None
    nic_bw: float = 3.1e9            # 25 GbE-class
    nic_latency: float = 50e-6
    heartbeat_timeout_s: float = 2.0
    name_prefix: str = "replica"

    def __post_init__(self) -> None:
        if self.n_replicas < 1:
            raise ValueError("a fleet needs at least one replica")

    @property
    def replica_names(self) -> tuple[str, ...]:
        return tuple(f"{self.name_prefix}-{i}"
                     for i in range(self.n_replicas))


def make_fleet_topology(n_replicas: int = 3, **kw) -> FleetTopology:
    """Convenience builder mirroring the mesh makers' shape."""
    return FleetTopology(n_replicas=n_replicas, **kw)


def make_production_mesh(*, multi_pod: bool = False,
                         shape: tuple[int, ...] | None = None):
    """16×16 = 256 chips per pod; 2 pods = 512 chips for the multi-pod
    dry-run. Axes: ('pod',) 'data', 'model'. ``shape`` overrides the
    per-pod (data, model) factorization — e.g. (32, 8) suits archs whose
    head counts divide 8 but not 16 (§Perf iteration A4)."""
    if shape is None:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    elif multi_pod and len(shape) == 2:
        shape = (2, *shape)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    assert len(shape) == len(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_smoke_mesh():
    """Single-device mesh with the production axis names (CPU tests)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))
