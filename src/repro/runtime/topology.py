"""Topology: the device/mesh layer behind the Session facade.

Everything above this module reasons about three logical axes — "pod"
(hybrid-sharded DP: params replicated, grads all-reduced once per step),
"data" (the FSDP + EP + vocab axis) and "model" (pipeline groups ×
stages; TP-free per the paper). This module owns how those axes land on
physical devices:

* a :class:`Topology` describes the hardware — hosts × devices-per-host,
  an interconnect class, and ``kind`` ("fake_cpu" single-process CPU
  demos, "tpu_host" the chips of one attached TPU host, "gpu_cluster"
  NVLink-island clusters, "tpu_pod" ICI pods); :func:`attached_topology`
  reads the live host's kind, chip count and ``device_kind`` from JAX;
* :meth:`Topology.axis_layout` derives the pods×data×model widths from
  the hardware under a ``cost_preset``: the a800 preset confines the
  FSDP axis to the NVLink island (intra-host gathers) and folds the
  remaining nodes into hybrid-sharded DP pods, the tpu_v5e preset keeps
  FSDP across a full 16×16 pod (uniform ICI makes the wide gather
  cheap) and maps pods to physical pods;
* :meth:`Topology.ensure_devices` performs the per-kind device
  bootstrap — fake host devices for "fake_cpu", a guarded
  ``jax.distributed.initialize`` for real multi-host kinds;
* :meth:`Topology.build_mesh` turns the derived layout into the
  ``jax.Mesh`` every Session runs on.

The old ``launch/mesh.py`` hard-coded 16×16 pod lives on as the
``tpu_pod`` / ``tpu_pod_x2`` presets; elastic restarts shrink a
topology's data axis (:meth:`Topology.shrink`) and rebuild the Session
on the survivor subset.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any

KINDS = ("fake_cpu", "tpu_host", "gpu_cluster", "tpu_pod")

# default interconnect class per kind (informational + used by the
# layout derivation notes; the α–β constants live in core/plan.py)
_INTERCONNECT = {
    "fake_cpu": "host",
    "tpu_host": "ici",
    "gpu_cluster": "nvlink+ib",
    "tpu_pod": "ici",
}

# TPU v5e hardware constants (per chip) used by the roofline analysis;
# re-exported by launch/mesh.py for compatibility.
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link (~4 links usable)


class TopologyError(ValueError):
    """Invalid topology (message says how to fix it)."""


@dataclasses.dataclass(frozen=True)
class Topology:
    """Hosts × devices-per-host (× pods) plus the interconnect class.

    ``devices_per_host=None`` on the "fake_cpu" kind resolves from
    ``$SPMD_DEVICES`` (default 8) at :meth:`ensure_devices` /
    :meth:`total_devices` time — the same env contract every entry
    point already uses. ``data=`` pins the FSDP axis explicitly (elastic
    shrink sets it); None derives it from the hardware.
    """

    kind: str = "fake_cpu"
    hosts: int = 1
    devices_per_host: int | None = None
    pods: int = 1
    interconnect: str | None = None
    data: int | None = None         # explicit FSDP-axis width
    name: str | None = None         # preset provenance (None = ad hoc)
    device_kind: str | None = None  # jax device_kind of a live host

    def __post_init__(self):
        if self.interconnect is None:
            object.__setattr__(self, "interconnect",
                               _INTERCONNECT.get(self.kind))

    # ------------------------------------------------------------------ #
    def validate(self) -> "Topology":
        if self.kind not in KINDS:
            raise TopologyError(
                f"unknown topology kind {self.kind!r}; pick one of "
                f"{KINDS} (or a preset name from "
                f"{sorted(TOPOLOGY_PRESETS)})")
        if self.hosts < 1:
            raise TopologyError(f"hosts must be >= 1, got {self.hosts}")
        if self.devices_per_host is not None and self.devices_per_host < 1:
            raise TopologyError(
                f"devices_per_host must be >= 1, got "
                f"{self.devices_per_host}")
        if self.devices_per_host is None and self.kind != "fake_cpu":
            raise TopologyError(
                f"kind={self.kind!r} needs an explicit devices_per_host "
                "(only fake_cpu resolves it from $SPMD_DEVICES)")
        if self.pods < 1:
            raise TopologyError(f"pods must be >= 1, got {self.pods}")
        if self.hosts % self.pods != 0:
            raise TopologyError(
                f"pods ({self.pods}) must partition the hosts "
                f"({self.hosts}) evenly — a pod is a host group")
        if self.data is not None and self.data < 1:
            raise TopologyError(f"data must be >= 1, got {self.data}")
        if self.kind == "fake_cpu" and self.hosts != 1:
            raise TopologyError(
                "fake_cpu topologies are single-process (hosts=1); model "
                "a multi-host run with kind='gpu_cluster' or 'tpu_pod'")
        return self

    # ------------------------------------------------------------------ #
    @property
    def total_devices(self) -> int:
        return self.hosts * self._dph()

    def _dph(self) -> int:
        if self.devices_per_host is not None:
            return self.devices_per_host
        env = os.environ.get("SPMD_DEVICES")
        return int(env) if env else 8

    def axis_layout(self, model_ranks: int,
                    cost_preset: str = "a800") -> dict:
        """Derive the pods×data×model widths for this hardware.

        Rules (per ``cost_preset``):

        * base: ``data = total/(pods × model)`` — every device hosts one
          pipeline rank of one FSDP shard of one pod;
        * ``a800`` on "gpu_cluster": the FSDP gather/reduce ticks are
          the per-step bandwidth hot path, so the data axis is confined
          to the NVLink island (``devices_per_host``) when it would
          span hosts and divides evenly; the displaced factor folds
          into ``pods`` (hybrid-sharded DP pays one inter-node
          all-reduce per step instead of per tick);
        * ``tpu_v5e`` on "tpu_pod": uniform ICI keeps the full pod as
          one data axis — pods map to physical pods unchanged;
        * an explicit ``data=`` wins (elastic shrink pins it) and may
          use a *subset* of the devices — survivors after a node loss.
        """
        total = self.total_devices
        pods = self.pods
        if model_ranks < 1:
            raise TopologyError(f"model_ranks must be >= 1, "
                                f"got {model_ranks}")
        if self.data is not None:
            data = self.data
            if pods * data * model_ranks > total:
                raise TopologyError(
                    f"topology {self.label()}: pods×data×model = "
                    f"{pods}×{data}×{model_ranks} = "
                    f"{pods * data * model_ranks} exceeds the {total} "
                    "devices — shrink data= or add hosts")
        else:
            if total % (pods * model_ranks) != 0:
                raise TopologyError(
                    f"topology {self.label()}: {total} devices do not "
                    f"split over pods×model = {pods}×{model_ranks}; "
                    "adjust hosts/devices_per_host or pass data= "
                    "explicitly")
            data = total // (pods * model_ranks)
            if data < 1:
                raise TopologyError(
                    f"topology {self.label()}: pods×model = "
                    f"{pods}×{model_ranks} needs at least "
                    f"{pods * model_ranks} devices, have {total}")
            if (self.kind == "gpu_cluster" and cost_preset == "a800"
                    and self._dph() > 1 and data > self._dph()
                    and data % self._dph() == 0):
                # confine FSDP to the NVLink island; displaced factor
                # becomes hybrid-sharded DP across node groups
                pods = pods * (data // self._dph())
                data = self._dph()
        return {"pods": pods, "data": data, "model": model_ranks,
                "devices_used": pods * data * model_ranks,
                "devices_total": total}

    # ------------------------------------------------------------------ #
    def ensure_devices(self) -> int:
        """Per-kind device bootstrap; returns the live device count.

        "fake_cpu" routes through :func:`repro.api.devices.
        ensure_host_devices` (the XLA fake-host-device flag must be set
        before backend init — single-process demos keep working
        untouched). Real kinds initialize ``jax.distributed`` when a
        coordinator is configured (multi-host launch), else run
        single-process on whatever the backend provides.
        """
        if self.kind == "fake_cpu":
            from repro.api.devices import ensure_host_devices
            return ensure_host_devices(self.total_devices)
        if self.hosts > 1 and self._needs_distributed_init():
            import jax
            jax.distributed.initialize(
                coordinator_address=self._coordinator(),
                num_processes=self.hosts,
                process_id=int(os.environ.get("REPRO_PROCESS_ID", "0")))
        import jax
        have = len(jax.devices())
        if have < self.pods * (self.data or 1):
            raise TopologyError(
                f"topology {self.label()} expects at least "
                f"{self.pods * (self.data or 1)} devices, backend "
                f"provides {have} — is every host up and "
                "jax.distributed initialized on each?")
        return have

    @staticmethod
    def _coordinator() -> str | None:
        return (os.environ.get("REPRO_COORDINATOR_ADDRESS")
                or os.environ.get("JAX_COORDINATOR_ADDRESS"))

    def _needs_distributed_init(self) -> bool:
        """Multi-host init only when a coordinator is configured and the
        backend is not already initialized — structurally testable
        without real hardware."""
        if self._coordinator() is None:
            return False
        import jax
        try:
            return jax.process_count() <= 1
        except RuntimeError:
            return True

    def build_mesh(self, model_ranks: int, cost_preset: str = "a800"):
        """The ``jax.Mesh`` for this topology's derived axis layout
        (3-axis with a "pod" dimension when pods > 1)."""
        import jax

        lay = self.axis_layout(model_ranks, cost_preset)
        p, d, m = lay["pods"], lay["data"], lay["model"]
        if p > 1:
            return jax.make_mesh((p, d, m), ("pod", "data", "model"))
        return jax.make_mesh((d, m), ("data", "model"))

    # ------------------------------------------------------------------ #
    def shrink(self, model_ranks: int | None = None,
               factor: int = 2) -> "Topology":
        """The elastic-restart topology: same hardware description, data
        axis divided by ``factor`` (survivor subset after a node loss).
        """
        d = self.data
        if d is None:
            if model_ranks is None:
                raise TopologyError(
                    "shrink() on a derived-data topology needs "
                    "model_ranks to resolve the current data axis")
            d = self.axis_layout(model_ranks)["data"]
        if d <= 1:
            raise TopologyError(
                f"topology {self.label()}: data axis is already 1 — "
                "nothing left to shrink (restore on fresh hardware "
                "instead)")
        return dataclasses.replace(self, data=max(1, d // factor),
                                   name=None)

    def label(self) -> str:
        base = self.name or self.kind
        axes = f"hosts={self.hosts}×{self._dph()}"
        if self.pods > 1:
            axes += f" pods={self.pods}"
        if self.data is not None:
            axes += f" data={self.data}"
        return f"{base} ({axes})"

    def describe(self, model_ranks: int | None = None,
                 cost_preset: str = "a800") -> dict:
        """Device-free summary for ``Session.describe()["topology"]``."""
        out = {
            "kind": self.kind,
            "name": self.name,
            "hosts": self.hosts,
            "devices_per_host": self._dph(),
            "pods": self.pods,
            "interconnect": self.interconnect,
            "total_devices": self.total_devices,
            "device_kind": self.device_kind,
        }
        if model_ranks is not None:
            try:
                out["layout"] = self.axis_layout(model_ranks, cost_preset)
            except TopologyError as e:
                out["layout_error"] = str(e)
        return out


# ---------------------------------------------------------------------- #
# Presets (the old launch/mesh.py constants live here now)
# ---------------------------------------------------------------------- #

TOPOLOGY_PRESETS: dict[str, Topology] = {
    # single-process CPU demos/tests; device count from $SPMD_DEVICES
    "fake_cpu": Topology(kind="fake_cpu", hosts=1, name="fake_cpu"),
    # 32 × 8-GPU NVLink nodes = 256 GPUs (the a800 cost preset's shape)
    "gpu_cluster": Topology(kind="gpu_cluster", hosts=32,
                            devices_per_host=8, name="gpu_cluster"),
    # one 16×16 v5e pod: 64 hosts × 4 chips = 256
    "tpu_pod": Topology(kind="tpu_pod", hosts=64, devices_per_host=4,
                        name="tpu_pod"),
    # two pods = 512 chips, hybrid-sharded DP across them
    "tpu_pod_x2": Topology(kind="tpu_pod", hosts=128, devices_per_host=4,
                           pods=2, name="tpu_pod_x2"),
}


def attached_topology() -> Topology:
    """The host JAX is attached to: its platform, chip count and
    ``device_kind`` as ``jax.devices()`` reports them.

    A TPU host is a "tpu_host" topology over its local chips; a CPU
    backend is "fake_cpu" over the devices it was started with (call
    :func:`repro.api.ensure_host_devices` first). Any other platform is
    refused rather than described as something it is not.
    """
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    kinds = {"cpu": "fake_cpu", "tpu": "tpu_host"}
    if platform not in kinds:
        raise TopologyError(
            f"no attached-host topology for platform {platform!r}; pass a "
            f"preset ({', '.join(sorted(TOPOLOGY_PRESETS))}) or a Topology")
    return Topology(kind=kinds[platform], hosts=jax.process_count(),
                    devices_per_host=jax.local_device_count(),
                    name="attached",
                    device_kind=devices[0].device_kind).validate()


def resolve_topology(t: Any) -> Topology | None:
    """None | preset name | Topology | kwargs dict -> validated Topology."""
    if t is None:
        return None
    if isinstance(t, Topology):
        return t.validate()
    if isinstance(t, str):
        if t not in TOPOLOGY_PRESETS:
            raise TopologyError(
                f"unknown topology preset {t!r}; known presets: "
                f"{', '.join(sorted(TOPOLOGY_PRESETS))} (or pass a "
                "Topology instance)")
        return TOPOLOGY_PRESETS[t]
    if isinstance(t, dict):
        try:
            return Topology(**t).validate()
        except TypeError as e:
            raise TopologyError(f"bad topology dict: {e}") from e
    raise TopologyError(
        f"topology must be a preset name, a Topology, or a kwargs dict; "
        f"got {type(t).__name__}")
