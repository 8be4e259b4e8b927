"""Resource model: fixed-point resource vectors and per-node accounting.

Analog of the reference's scheduling resource model
(src/ray/common/scheduling/cluster_resource_data.h — ``ResourceRequest``,
``TaskResourceInstances``, ``NodeResources``; fixed_point.h). Resources are
fixed-point (1/10000 granularity) so fractional accelerators account exactly.

TPU-first: ``TPU`` is a first-class resource alongside CPU/memory, and nodes
carry TPU topology labels (accelerator type, slice name, worker index within
the slice, ICI coordinates) so placement groups can do ICI-topology-aware
STRICT_PACK — a pod-slice bundle maps to a contiguous slice of the torus.
The reference snapshot has no TPU resource at all (SURVEY.md §2.3); its GPU
handling lives in python/ray/_private/resource_spec.py:303 and
src/ray/common/scheduling/*.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Iterable, Optional

GRANULARITY = 10000

CPU = "CPU"
GPU = "GPU"
TPU = "TPU"
MEMORY = "memory"
OBJECT_STORE_MEMORY = "object_store_memory"

PREDEFINED = (CPU, GPU, TPU, MEMORY, OBJECT_STORE_MEMORY)


def _to_fp(v: float) -> int:
    return round(v * GRANULARITY)


def _from_fp(v: int) -> float:
    return v / GRANULARITY


class ResourceSet:
    """An immutable-ish map of resource name -> fixed-point quantity."""

    __slots__ = ("_fp",)

    def __init__(self, resources: Optional[Dict[str, float]] = None, _fp=None):
        if _fp is not None:
            self._fp = _fp
        else:
            self._fp = {}
            if resources:
                for k, v in resources.items():
                    if v < 0:
                        raise ValueError(f"Negative resource {k}={v}")
                    fp = _to_fp(v)
                    if fp:
                        self._fp[k] = fp

    def get(self, name: str) -> float:
        return _from_fp(self._fp.get(name, 0))

    def get_fp(self, name: str) -> int:
        return self._fp.get(name, 0)

    def names(self) -> Iterable[str]:
        return self._fp.keys()

    def is_empty(self) -> bool:
        return not self._fp

    def to_dict(self) -> Dict[str, float]:
        return {k: _from_fp(v) for k, v in self._fp.items()}

    def covers(self, request: "ResourceSet") -> bool:
        """True if self has at least the quantities in `request`."""
        for k, v in request._fp.items():
            if self._fp.get(k, 0) < v:
                return False
        return True

    def add(self, other: "ResourceSet") -> "ResourceSet":
        fp = dict(self._fp)
        for k, v in other._fp.items():
            fp[k] = fp.get(k, 0) + v
        return ResourceSet(_fp=fp)

    def subtract(self, other: "ResourceSet") -> "ResourceSet":
        fp = dict(self._fp)
        for k, v in other._fp.items():
            nv = fp.get(k, 0) - v
            if nv < 0:
                raise ValueError(f"Resource {k} would go negative")
            if nv:
                fp[k] = nv
            else:
                fp.pop(k, None)
        return ResourceSet(_fp=fp)

    def scaled(self, factor: float) -> "ResourceSet":
        return ResourceSet(_fp={k: round(v * factor) for k, v in self._fp.items()})

    def __eq__(self, other):
        return isinstance(other, ResourceSet) and self._fp == other._fp

    def __repr__(self):
        return f"ResourceSet({self.to_dict()})"


@dataclass
class TpuTopology:
    """TPU topology attached to a node.

    ``coords`` is this host's position in the slice's host grid; ``chips``
    the number of chips local to the host. STRICT_PACK bundle scheduling uses
    these to pick hosts forming a contiguous ICI sub-torus.
    """

    accelerator_type: str = ""  # e.g. "v5p-64"
    slice_name: str = ""
    worker_index: int = 0
    num_workers: int = 1
    chips_per_host: int = 4
    coords: tuple = (0, 0, 0)

    @property
    def generation(self) -> str:
        return self.accelerator_type.split("-")[0] if self.accelerator_type else ""


@dataclass
class NodeResources:
    """Total and available resources on one node, plus labels.

    ``version`` increments on every availability change; the native
    scheduler core uses it to re-sync only dirty nodes before a
    placement decision."""

    node_id: object = None
    total: ResourceSet = field(default_factory=ResourceSet)
    available: ResourceSet = field(default_factory=ResourceSet)
    labels: Dict[str, str] = field(default_factory=dict)
    tpu: Optional[TpuTopology] = None
    version: int = 0
    # change listeners (native scheduler dirty tracking); excluded from
    # pickling — a node's resources cross the wire at registration
    listeners: list = field(default_factory=list, repr=False,
                            compare=False)

    def __getstate__(self):
        state = dict(self.__dict__)
        state["listeners"] = []
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)

    def is_feasible(self, request: ResourceSet) -> bool:
        return self.total.covers(request)

    def is_available(self, request: ResourceSet) -> bool:
        return self.available.covers(request)

    def allocate(self, request: ResourceSet):
        self.available = self.available.subtract(request)
        self.version += 1
        for cb in self.listeners:
            cb()

    def release(self, request: ResourceSet):
        # Validate BEFORE assigning: a double-release must not leave the
        # inflated availability behind (with version/listeners skipped,
        # the native scheduler table would silently disagree too).
        released = self.available.add(request)
        for k in released.names():
            if released.get_fp(k) > self.total.get_fp(k):
                raise ValueError(f"Released more {k} than total on node")
        self.available = released
        self.version += 1
        for cb in self.listeners:
            cb()

    def utilization(self) -> float:
        """Max utilization across critical resources — drives hybrid policy."""
        util = 0.0
        for k in (CPU, GPU, TPU, MEMORY):
            tot = self.total.get_fp(k)
            if tot:
                util = max(util, 1.0 - self.available.get_fp(k) / tot)
        return util


def detect_node_resources(num_cpus=None, num_tpus=None, memory=None,
                          object_store_memory=None, resources=None,
                          labels=None) -> NodeResources:
    """Autodetect this host's resources (analog of resource_spec.py).

    TPU detection never touches JAX (see ``_detect_tpu_chips``); explicit
    overrides come first.
    """
    import os

    res = dict(resources or {})
    if num_cpus is None:
        num_cpus = os.cpu_count() or 1
    res[CPU] = num_cpus
    if num_tpus is None:
        num_tpus = _detect_tpu_chips()
    if num_tpus:
        res[TPU] = num_tpus
    if memory is None:
        try:
            with open("/proc/meminfo") as f:
                for line in f:
                    if line.startswith("MemAvailable"):
                        memory = int(line.split()[1]) * 1024 // 2
                        break
        except OSError:
            memory = 4 * 1024 * 1024 * 1024
    res[MEMORY] = memory
    if object_store_memory is not None:
        res[OBJECT_STORE_MEMORY] = object_store_memory
    rs = ResourceSet(res)
    return NodeResources(total=rs, available=rs, labels=dict(labels or {}),
                         tpu=detect_tpu_topology())


# Generations whose accelerator-type suffix counts TensorCores, two to a
# chip ("v5p-64" is 32 chips); every other generation's counts chips
# ("v5litepod-4" is 4 chips).
_SUFFIX_COUNTS_CORES = ("v2", "v3", "v4", "v5p")


def _chips_from_accelerator_type(acc: str, hosts: int) -> int:
    """Chips on one host of a slice named like "v5litepod-4"."""
    gen, _, suffix = acc.lower().rpartition("-")
    if not suffix.isdigit():
        return 0
    total = int(suffix)
    if gen in _SUFFIX_COUNTS_CORES:
        total //= 2
    return max(1, total // max(1, hosts))


def _detect_tpu_chips(environ=None, dev_root: str = "/dev") -> int:
    """Count local TPU chips WITHOUT initializing any JAX backend (backend
    init grabs the accelerator and can block — the runtime must never do
    that as a side effect of ``init()``).

    The count is what the host really offers: its accelerator device
    files (``/dev/accel<n>``, or ``/dev/vfio/<n>`` on v5e and later).
    The environment can promise more than is attached (a one-chip
    machine may still say ``TPU_ACCELERATOR_TYPE=v5litepod-4``), so the
    accelerator type is only read where no device file is visible.
    A vfio group is any device bound to vfio-pci (a NIC or a GPU passed
    through looks the same), so those are counted only on a host whose
    environment names a TPU (some ``TPU_*`` variable). ``TPU_CHIPS``
    overrides everything. Nothing found means 0.
    """
    import glob
    import os

    environ = os.environ if environ is None else environ
    if environ.get("TPU_CHIPS"):
        return int(environ["TPU_CHIPS"])
    devices = glob.glob(os.path.join(dev_root, "accel[0-9]*"))
    if any(k.startswith("TPU_") for k in environ):
        devices += [p for p in glob.glob(os.path.join(dev_root, "vfio", "*"))
                    if os.path.basename(p).isdigit()]
    if devices:
        return len(devices)
    acc = environ.get("TPU_ACCELERATOR_TYPE", "")  # e.g. "v5p-64"
    hosts = len(environ.get("TPU_WORKER_HOSTNAMES", "localhost").split(","))
    return _chips_from_accelerator_type(acc, hosts)


def worker_jax_platforms(leases_tpu: bool) -> str:
    """``JAX_PLATFORMS`` for a worker process. One process per chip: only a
    worker started for a scheduling class that leases TPU chips may load
    the TPU library (it inherits the setting of the process that starts
    it, the driver or the node agent: on a TPU host that is the host's
    own, and under ``JAX_PLATFORMS=cpu`` a whole test cluster stays on
    the CPU); every other worker is held to the CPU, on nodes with chips
    too."""
    import os

    return os.environ.get("JAX_PLATFORMS", "") if leases_tpu else "cpu"


def tpu_process_env(tpu_ids) -> Dict[str, str]:
    """What libtpu must find in the environment, before it loads, for a
    process to take exactly the leased chips of a host.

    One chip: the process also declares itself a 1x1x1 slice of its own,
    the form JAX documents for several processes on one host (checked on
    a four-chip v5e host: two such processes ran side by side, each seeing
    one device). All of the host's chips: the host's own settings already
    describe them. Two chips of four are not covered: libtpu 0.0.34
    refused every bounds setting tried there ("expected 4, actual: 2"),
    and says so itself when a worker tries."""
    env = {"TPU_VISIBLE_CHIPS": ",".join(str(i) for i in tpu_ids)}
    if len(tpu_ids) == 1:
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return env


def detect_tpu_topology() -> Optional[TpuTopology]:
    import os

    acc = os.environ.get("TPU_ACCELERATOR_TYPE", "")
    if not acc:
        from .config import get_config

        acc = get_config().tpu_accelerator_type
    if not acc and not _detect_tpu_chips():
        return None
    hostname = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return TpuTopology(
        accelerator_type=acc or "unknown",
        slice_name=os.environ.get("TPU_NAME", ""),
        worker_index=int(os.environ.get("TPU_WORKER_ID", "0") or 0),
        num_workers=len(hostname.split(",")) if hostname else 1,
        chips_per_host=_detect_tpu_chips() or 4,
    )
