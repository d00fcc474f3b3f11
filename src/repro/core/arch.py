"""Architecture model — the hardware facts candidate spaces are derived from.

The paper's premise is that the best directive family (loop transform +
thread count) is a function of the *target machine*, so it must be
re-derived per architecture rather than fixed when the kernel is written.
:class:`ArchSpec` is our machine description: the handful of numbers an
emit policy (core/emit.py) needs to generate a kernel's candidate space —
vector lane width, MXU dimension, VMEM capacity, cache line, memory
bandwidth, core count.

Like :class:`~repro.fleet.fingerprint.DeviceFingerprint`, an ArchSpec is
identity, not preference: it composes into BasicParams via ``bp_entries()``
(all keys carry the ``arch_`` prefix) so emitted spaces are namespaced per
architecture and fleet merges/warm starts stay correct across machines.
"""
from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass
from typing import Any, Dict, Iterable, Mapping, Optional, Tuple

_PREFIX = "arch_"


@dataclass(frozen=True)
class ArchSpec:
    """One target architecture, as seen by the emit layer.

    ``vmem_bytes`` is the physical on-chip fast-memory capacity;
    :meth:`vmem_limit_bytes` is the scoped VMEM every ``pallas_call``
    requests from the compiler, and :meth:`vmem_budget` is what one
    kernel's single-buffered working set may plan against.
    """

    name: str
    backend: str                       # jax.default_backend() family
    lane_width: int = 128              # minor-most tile dim (VPU lanes)
    sublane_width: int = 8             # second-minor tile dim for f32
    mxu_dim: int = 128                 # systolic array edge
    vmem_bytes: int = 128 * 2**20      # on-chip vector memory capacity
    cacheline_bytes: int = 256
    hbm_bandwidth: float = 819e9       # bytes/s
    peak_flops: float = 197e12
    core_count: int = 1
    grid_overhead_s: float = 1.5e-6    # fixed cost per grid program

    BP_KEYS: Tuple[str, ...] = dataclasses.field(
        default=(
            "name", "backend", "lane_width", "sublane_width", "mxu_dim",
            "vmem_bytes", "cacheline_bytes", "hbm_bandwidth", "peak_flops",
            "core_count", "grid_overhead_s",
        ),
        init=False, repr=False, compare=False,
    )

    def vmem_limit_bytes(self) -> int:
        """Scoped VMEM a kernel asks the compiler for: three quarters of
        the physical capacity, the rest left to Mosaic's internal scratch.
        Without it the compiler holds a kernel to its much smaller default
        scope and refuses the larger emitted tiles."""
        return self.vmem_bytes * 3 // 4

    def vmem_budget(self) -> int:
        """Bytes one kernel's working set may plan to keep resident: half
        the requested limit, since the pipeline double-buffers each block."""
        return self.vmem_limit_bytes() // 2

    def bp_entries(self) -> Dict[str, Any]:
        """This arch as composable BP entries (``arch_`` prefix)."""
        return {_PREFIX + k: getattr(self, k) for k in self.BP_KEYS}

    @classmethod
    def from_bp_entries(cls, entries: Mapping[str, Any]) -> "ArchSpec":
        """Inverse of :meth:`bp_entries` — rebuild from a BP mapping."""
        kwargs = {}
        for k in (
            "name", "backend", "lane_width", "sublane_width", "mxu_dim",
            "vmem_bytes", "cacheline_bytes", "hbm_bandwidth", "peak_flops",
            "core_count", "grid_overhead_s",
        ):
            key = _PREFIX + k
            if key not in entries:
                raise KeyError(f"missing BP entry {key!r}")
            kwargs[k] = entries[key]
        return cls(**kwargs)


# Known architecture table. Interpret-mode hosts still emit TPU-shaped
# tiles — the arch model describes the Pallas *target*, with a VMEM
# budget sized so the interpreter's working sets stay cache-resident
# (an 18 MiB planning budget, no less than the historical 16 MiB
# hand-tuned cap, so every hand-ladder tile is still emitted here).
_CPU_HOST = ArchSpec(
    name="cpu_host",
    backend="cpu",
    lane_width=128,
    sublane_width=8,
    mxu_dim=128,
    vmem_bytes=48 * 2**20,
    cacheline_bytes=64,
    hbm_bandwidth=50e9,
    peak_flops=0.5e12,
    core_count=max(1, os.cpu_count() or 1),
    # interpreted pallas_call pays a large per-program cost, so the
    # overhead term must dominate block-count ranking on this target
    grid_overhead_s=2e-4,
)

_TPU_V5E = ArchSpec(
    name="tpu_v5e",
    backend="tpu",
    vmem_bytes=128 * 2**20,
    hbm_bandwidth=819e9,
    peak_flops=197e12,
)

_GPU_GENERIC = ArchSpec(
    name="gpu_generic",
    backend="gpu",
    vmem_bytes=32 * 2**20,     # smem + L2 slice a block may plan against
    cacheline_bytes=128,
    hbm_bandwidth=2000e9,
    peak_flops=100e12,
    grid_overhead_s=3e-6,
)


# TPU generations by the exact ``device_kind`` JAX reports.  A chip that is
# not listed is an error: planning its tiles against another chip's VMEM
# would emit candidates its compiler refuses.
_TPU_BY_KIND: Dict[str, ArchSpec] = {
    "TPU v5 lite": _TPU_V5E,
}


def tpu_arch(device_kind: str) -> ArchSpec:
    """The ArchSpec of one TPU generation, by its ``device_kind``."""
    try:
        return _TPU_BY_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no ArchSpec for TPU device_kind {device_kind!r}; "
            f"known: {sorted(_TPU_BY_KIND)}"
        ) from None


def detect(backend: Optional[str] = None) -> ArchSpec:
    """Resolve the ArchSpec for a backend (default: the local one)."""
    import jax

    if backend is None:
        backend = jax.default_backend()
    if backend == "tpu":
        devices = jax.devices()
        return dataclasses.replace(
            tpu_arch(devices[0].device_kind), core_count=len(devices)
        )
    if backend == "gpu":
        return dataclasses.replace(_GPU_GENERIC, core_count=len(jax.devices()))
    return _CPU_HOST


_LOCAL: Dict[str, ArchSpec] = {}


def local_arch() -> ArchSpec:
    """The local backend's ArchSpec, detected once per backend."""
    import jax

    backend = jax.default_backend()
    if backend not in _LOCAL:
        _LOCAL[backend] = detect(backend)
    return _LOCAL[backend]


def default_interpret() -> bool:
    """Pallas interpret mode runs the kernels on the CPU backend only (the
    tests); on a TPU they compile natively.  Any other backend has no
    lowering for these TPU kernels, so it is refused rather than
    silently interpreted."""
    import jax

    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas TPU kernels cannot run on {backend!r}")
    return backend == "cpu"


def arch_bp_entries(arch: Optional[ArchSpec] = None) -> Dict[str, Any]:
    """BP entries for an arch (default: the local one) — registry glue."""
    return (arch or local_arch()).bp_entries()
