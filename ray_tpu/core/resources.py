"""Resource model with first-class TPU topology.

The reference models resources as fixed-point scalar maps
(src/ray/common/scheduling/cluster_resource_data.h, fixed_point.h) and bolts
TPU awareness on via custom resources emitted by an accelerator manager
(python/ray/_private/accelerators/tpu.py:71 — chip detection :49, pod-type
:198, "TPU-<pod_type>-head" gang resource :232). Here the slice/host/chip
topology IS the core resource model: a node owns a ``TpuSliceTopology`` and
chip allocation is topology-aware (contiguous sub-grids ride the ICI mesh).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

# Fixed-point arithmetic: resources are stored as integers scaled by 1e4
# (the reference uses the same trick to avoid float drift in admission
# control — src/ray/common/scheduling/fixed_point.h).
RESOLUTION = 10_000


def to_fixed(v: float) -> int:
    return int(round(v * RESOLUTION))


def from_fixed(v: int) -> float:
    return v / RESOLUTION


class ResourceSet:
    """A non-negative resource vector keyed by resource name."""

    __slots__ = ("_r",)

    def __init__(self, resources: Optional[Dict[str, float]] = None):
        self._r: Dict[str, int] = {}
        if resources:
            for k, v in resources.items():
                fv = to_fixed(v)
                if fv < 0:
                    raise ValueError(f"negative resource {k}={v}")
                if fv:
                    self._r[k] = fv

    @classmethod
    def _from_fixed_map(cls, m: Dict[str, int]) -> "ResourceSet":
        rs = cls()
        rs._r = {k: v for k, v in m.items() if v}
        return rs

    def get(self, name: str) -> float:
        return from_fixed(self._r.get(name, 0))

    def to_dict(self) -> Dict[str, float]:
        return {k: from_fixed(v) for k, v in self._r.items()}

    def is_subset_of(self, other: "ResourceSet") -> bool:
        return all(other._r.get(k, 0) >= v for k, v in self._r.items())

    def __add__(self, other: "ResourceSet") -> "ResourceSet":
        out = dict(self._r)
        for k, v in other._r.items():
            out[k] = out.get(k, 0) + v
        return ResourceSet._from_fixed_map(out)

    def subtract_unchecked(self, other: "ResourceSet") -> "ResourceSet":
        """Subtraction that may go negative (oversubscription debt while a
        blocked worker resumes — the reference raylet does the same when
        workers blocked in ray.get are released and re-admitted)."""
        out = dict(self._r)
        for k, v in other._r.items():
            out[k] = out.get(k, 0) - v
        return ResourceSet._from_fixed_map(out)

    def __sub__(self, other: "ResourceSet") -> "ResourceSet":
        out = dict(self._r)
        for k, v in other._r.items():
            nv = out.get(k, 0) - v
            if nv < 0:
                raise ValueError(
                    f"resource {k} would go negative ({from_fixed(nv)})"
                )
            out[k] = nv
        return ResourceSet._from_fixed_map(out)

    def __bool__(self):
        return bool(self._r)

    def __eq__(self, other):
        return isinstance(other, ResourceSet) and self._r == other._r

    def __repr__(self):
        return f"ResourceSet({self.to_dict()})"


# --------------------------------------------------------------------------
# TPU topology
# --------------------------------------------------------------------------

# (chips_per_host, default grid) for known generations; grids are the
# physical ICI meshes. v5e hosts have 4 chips in a 2x2; v5p 4 chips with 3D
# torus links. We model a slice as a logical 2D grid of chips for adjacency.
_GENERATION_CHIPS_PER_HOST = {
    "v2": 4, "v3": 4, "v4": 4, "v5e": 4, "v5litepod": 4, "v5p": 4, "v6e": 4,
}

# PCI identity of a TPU chip: Google's vendor id and the device ids jax
# itself keys on (jax/_src/hardware_utils.py). Only ids that name exactly
# one generation are listed; anything else from this vendor is an error.
_GOOGLE_PCI_VENDOR = "0x1ae0"
_TPU_PCI_GENERATION = {
    "0x005e": "v4", "0x0062": "v5p", "0x0063": "v5e", "0x006f": "v6e",
}


class TpuDetectionError(RuntimeError):
    """A chip device node is present but cannot be identified."""


def _read(path: str) -> Optional[str]:
    try:
        with open(path) as f:
            return f.read().strip()
    except OSError:
        return None


def scan_tpu_chips(dev_root: str = "/dev", sys_root: str = "/sys"
                   ) -> Tuple[List[Tuple[str, str]], str]:
    """The chips THIS process could open, without touching jax/libtpu.

    A chip is a device node — ``/dev/accel<N>`` (v4 and older drivers) or
    a numeric ``/dev/vfio/<group>`` (v5e and newer) — whose PCI function
    carries Google's vendor id. The device node, not the PCI listing,
    decides the count: a sandbox handed one chip of a four-chip host
    still lists four PCI functions but exposes one node.

    Returns ``([(node, pci_device_id), ...], seen)`` where ``seen`` is a
    one-line account of what was looked at, for error messages.
    """
    import glob

    # iommu group -> (vendor, device) of the PCI functions in sysfs
    groups: Dict[str, Tuple[str, str]] = {}
    google_pci: List[str] = []
    for vp in sorted(glob.glob(
            os.path.join(sys_root, "bus/pci/devices/*/vendor"))):
        d = os.path.dirname(vp)
        vendor, device = _read(vp), _read(os.path.join(d, "device"))
        if vendor == _GOOGLE_PCI_VENDOR:
            google_pci.append(f"{os.path.basename(d)}={device}")
        link = os.path.join(d, "iommu_group")
        if os.path.exists(link):
            groups[os.path.basename(os.path.realpath(link))] = (
                vendor or "", device or "")

    nodes: List[Tuple[str, str, str]] = []     # (node, vendor, device)
    for node in sorted(glob.glob(os.path.join(dev_root, "accel*"))):
        d = os.path.join(sys_root, "class/accel", os.path.basename(node),
                         "device")
        nodes.append((node, _read(os.path.join(d, "vendor")) or "",
                      _read(os.path.join(d, "device")) or ""))
    for node in sorted(glob.glob(os.path.join(dev_root, "vfio/*"))):
        if os.path.basename(node).isdigit():   # skip the vfio control node
            nodes.append((node, *groups.get(os.path.basename(node),
                                            ("", ""))))
    chips = [(n, dev) for n, vendor, dev in nodes
             if vendor == _GOOGLE_PCI_VENDOR]
    seen = (f"device nodes under {dev_root} (accel*, vfio/<n>): "
            f"{[n for n, _, _ in nodes] or 'none'}; Google PCI functions "
            f"under {sys_root}/bus/pci/devices: {google_pci or 'none'}")
    return chips, seen


def _grid_for(num_chips: int) -> Tuple[int, int]:
    """Most-square 2D grid for n chips (ICI mesh model)."""
    best = (1, num_chips)
    d = 1
    while d * d <= num_chips:
        if num_chips % d == 0:
            best = (d, num_chips // d)
        d += 1
    return best


@dataclass(frozen=True)
class TpuChip:
    """One chip's position in the slice."""

    index: int
    host: int
    x: int
    y: int


class TpuSliceTopology:
    """A TPU slice: generation, pod type, hosts × chips, 2D ICI grid.

    The allocation primitive is *contiguous rectangles* of the chip grid —
    gang placements that ride ICI links only (the property STRICT_PACK
    bundles want). Mirrors what the reference derives from GCE metadata
    (accelerators/tpu.py:198 pod type, :232 worker count) but as a core
    scheduler structure instead of opaque custom resources.
    """

    def __init__(self, generation: str, num_chips: int = 1,
                 chips_per_host: Optional[int] = None):
        self.generation = generation
        self.num_chips = num_chips
        self.chips_per_host = chips_per_host or min(
            num_chips, _GENERATION_CHIPS_PER_HOST.get(generation, 4)
        )
        self.num_hosts = max(1, num_chips // self.chips_per_host)
        self.pod_type = f"{generation}-{num_chips}"
        self.grid = _grid_for(num_chips)
        gx, gy = self.grid
        self.chips: List[TpuChip] = [
            TpuChip(index=i, host=i // self.chips_per_host, x=i % gx, y=i // gx)
            for i in range(num_chips)
        ]
        self._free = set(range(num_chips))

    # -- detection ----------------------------------------------------------

    @classmethod
    def detect(cls, dev_root: str = "/dev", sys_root: str = "/sys"
               ) -> Optional["TpuSliceTopology"]:
        """The TPU chips of this machine, or None when it has none.

        RTPU_TPU_TOPOLOGY=<generation>-<chips> (e.g. v5e-8) replaces
        detection for scheduling tests on hosts without chips. Otherwise
        the chips are the device nodes ``scan_tpu_chips`` finds, and the
        generation is read from their PCI device id — a Google device
        this table does not know is an error, never a guess. jax is not
        imported: the driver must stay off the chip.
        """
        override = os.environ.get("RTPU_TPU_TOPOLOGY")
        if override:
            gen, _, n = override.rpartition("-")
            if not gen or not n.isdigit():
                raise ValueError(
                    f"RTPU_TPU_TOPOLOGY={override!r}: expected "
                    f"<generation>-<chips>, e.g. v5e-8")
            return cls(generation=gen, num_chips=int(n))
        chips, seen = scan_tpu_chips(dev_root, sys_root)
        if not chips:
            return None
        gens = {_TPU_PCI_GENERATION.get(dev) for _, dev in chips}
        if None in gens or len(gens) != 1:
            raise TpuDetectionError(
                f"cannot name the TPU generation of {chips}: known PCI "
                f"device ids are {_TPU_PCI_GENERATION}; {seen}")
        return cls(generation=gens.pop(), num_chips=len(chips))

    # -- allocation ---------------------------------------------------------

    def available_chips(self) -> int:
        return len(self._free)

    def allocate(self, n: int, contiguous: bool = True) -> Optional[List[int]]:
        """Allocate n chips; contiguous=True demands an ICI-adjacent
        rectangle (returns None if impossible)."""
        if n > len(self._free):
            return None
        if not contiguous or n == 1:
            picked = sorted(self._free)[:n]
            for c in picked:
                self._free.discard(c)
            return picked
        rect = self._find_rect(n)
        if rect is None:
            return None
        for c in rect:
            self._free.discard(c)
        return rect

    def _find_rect(self, n: int) -> Optional[List[int]]:
        gx, gy = self.grid
        # candidate rectangle shapes, squarest first
        shapes = []
        for w in range(1, gx + 1):
            if n % w == 0 and n // w <= gy:
                shapes.append((w, n // w))
        shapes.sort(key=lambda s: abs(s[0] - s[1]))
        by_pos = {(c.x, c.y): c.index for c in self.chips}
        for w, h in shapes:
            for oy in range(gy - h + 1):
                for ox in range(gx - w + 1):
                    cells = [
                        by_pos[(ox + dx, oy + dy)]
                        for dy in range(h)
                        for dx in range(w)
                    ]
                    if all(c in self._free for c in cells):
                        return cells
        return None

    def release(self, chips: List[int]):
        for c in chips:
            if 0 <= c < self.num_chips:
                self._free.add(c)

    def __repr__(self):
        return (f"TpuSliceTopology({self.pod_type}, grid={self.grid}, "
                f"free={len(self._free)}/{self.num_chips})")


def node_resources(num_cpus: Optional[int] = None,
                   topology: Optional[TpuSliceTopology] = None,
                   object_store_memory: int = 0) -> Dict[str, float]:
    """Total resource vector for a node (reference emits the same shape:
    CPU/TPU/memory + 'TPU-<pod>-head' for slice gang scheduling)."""
    r: Dict[str, float] = {"CPU": float(num_cpus or os.cpu_count() or 1)}
    if object_store_memory:
        r["object_store_memory"] = float(object_store_memory)
    if topology is not None:
        r["TPU"] = float(topology.num_chips)
        r[f"TPU-{topology.pod_type}-head"] = 1.0
    return r
