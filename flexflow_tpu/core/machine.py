"""TPU machine abstraction: views, resources, and hardware specs.

Re-design of the reference's MachineView/MachineResource
(reference: include/flexflow/machine_view.h:14-96) for TPU pod slices.
A MachineView keeps the reference's {start_device_id, dim[], stride[]}
shape — the search enumerates and hashes them the same way — but devices
are TPU chips on an ICI mesh instead of GPUs on nodes, and the lowering
maps a view onto axes of one global `jax.sharding.Mesh`.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import List, Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class MachineView:
    """A strided grid of device ids (reference: machine_view.h:14-35).

    device id of grid point p = start_device_id + sum_i p[i] * stride[i].
    """

    start_device_id: int
    dims: Tuple[int, ...]
    strides: Tuple[int, ...]

    def __post_init__(self):
        if len(self.dims) != len(self.strides):
            raise ValueError("dims and strides must have equal length")
        if any(d <= 0 for d in self.dims):
            raise ValueError("view dims must be positive")

    @property
    def ndims(self) -> int:
        return len(self.dims)

    @property
    def num_devices(self) -> int:
        out = 1
        for d in self.dims:
            out *= d
        return out

    def device_ids(self) -> List[int]:
        ids = []
        for point in itertools.product(*(range(d) for d in self.dims)):
            ids.append(
                self.start_device_id
                + sum(p * s for p, s in zip(point, self.strides))
            )
        return ids

    def get_device_id(self, point: Sequence[int]) -> int:
        return self.start_device_id + sum(
            p * s for p, s in zip(point, self.strides)
        )

    def hash(self) -> int:
        """Stable content hash (reference: MachineView::hash() used as the
        Legion MappingTagID; here it keys simulator/search memo tables)."""
        h = 17
        h = h * 31 + self.start_device_id
        for d, s in zip(self.dims, self.strides):
            h = h * 31 + d
            h = h * 31 + s
        return h & 0x7FFFFFFFFFFFFFFF

    @staticmethod
    def dp_view(num_devices: int) -> "MachineView":
        """1-D view over all devices (reference: the --only-data-parallel
        default view, graph.cc:1588-1613)."""
        return MachineView(0, (num_devices,), (1,))


@dataclasses.dataclass(frozen=True)
class MachineResource:
    """Device budget available to a sub-search
    (reference: machine_view.h:51-60 {num_nodes, available_gpus_per_node...}).

    For TPU: num_nodes = hosts, chips_per_node = chips per host. The Unity
    DP search splits resources vertically (fewer hosts) or horizontally
    (fewer chips per host) when exploring parallel branches
    (reference: graph.cc:252-306).
    """

    num_nodes: int
    chips_per_node: int
    start_chip_id: int = 0
    start_node_id: int = 0

    @property
    def num_chips(self) -> int:
        return self.num_nodes * self.chips_per_node

    def is_valid_view(self, view: MachineView, total_chips_per_node: int) -> bool:
        """All device ids of the view must lie inside this resource block."""
        lo = self.start_node_id * total_chips_per_node + self.start_chip_id
        for did in view.device_ids():
            node = did // total_chips_per_node
            chip = did % total_chips_per_node
            if not (
                self.start_node_id <= node < self.start_node_id + self.num_nodes
            ):
                return False
            if not (
                self.start_chip_id <= chip < self.start_chip_id + self.chips_per_node
            ):
                return False
        del lo
        return True

    def vertical_split(self, n_left: int):
        """Split by nodes (reference: graph.cc 'vertical(i)')."""
        left = dataclasses.replace(self, num_nodes=n_left)
        right = dataclasses.replace(
            self,
            num_nodes=self.num_nodes - n_left,
            start_node_id=self.start_node_id + n_left,
        )
        return left, right

    def horizontal_split(self, n_left: int):
        """Split by chips-per-node (reference: graph.cc 'horizontal(i)')."""
        left = dataclasses.replace(self, chips_per_node=n_left)
        right = dataclasses.replace(
            self,
            chips_per_node=self.chips_per_node - n_left,
            start_chip_id=self.start_chip_id + n_left,
        )
        return left, right


# Known chip specs for the analytic cost model. Values are public figures;
# they feed the simulator's roofline estimates (SURVEY §2.5 machine model).
CHIP_SPECS = {
    # name: (bf16 TFLOP/s, HBM GB/s, HBM GiB, ICI GB/s per link, ici links)
    "v4": (275.0, 1228.0, 32.0, 50.0, 6),
    "v5e": (197.0, 819.0, 16.0, 45.0, 4),
    "v5p": (459.0, 2765.0, 95.0, 100.0, 6),
    "cpu-sim": (0.2, 50.0, 16.0, 10.0, 2),
}

# `jax.devices()[0].device_kind` -> CHIP_SPECS key, the one table every
# default chip goes through. A TPU kind that is not listed is an error:
# pricing a chip the program is not on is how a 16 GB v5e got searched
# with 32 GiB of v4 memory.
DEVICE_KIND_TO_CHIP = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5e": "v5e",
    "TPU v5": "v5p",
    "TPU v5p": "v5p",
}

# off a TPU there is no chip to read: the search-without-hardware target
# stays the one the CPU tests and strategy fixtures were written against
# (`--chip` / MachineSpec(chip=...) choose another)
_NO_TPU_CHIP = "v4"


def detect_chip() -> str:
    """The CHIP_SPECS key of the chip this process runs on."""
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        return _NO_TPU_CHIP
    chip = DEVICE_KIND_TO_CHIP.get(device.device_kind)
    if chip is None:
        raise ValueError(
            f"TPU device_kind {device.device_kind!r} is not in "
            f"DEVICE_KIND_TO_CHIP ({sorted(DEVICE_KIND_TO_CHIP)}); add it "
            "with its CHIP_SPECS entry, or pass --chip to search for "
            "another machine"
        )
    return chip


@dataclasses.dataclass(frozen=True)
class MachineSpec:
    """Hardware description of the pod slice the search targets.

    Replaces the reference's SimpleMachineModel/EnhancedMachineModel inputs
    (reference: simulator.h:203-367): instead of NVLink/PCIe/NIC we model
    ICI torus links intra-slice and DCN across slices.
    """

    num_nodes: int = 1
    chips_per_node: int = 4
    chip: str = ""  # "" = detect_chip(): the chip this process runs on
    # mesh topology of the full slice, e.g. (4, 4, 2) for v4-32.
    torus: Optional[Tuple[int, ...]] = None
    dcn_bandwidth_gbps: float = 25.0  # per-host DCN GB/s
    # override the chip's HBM capacity (search-without-hardware: probe
    # feasibility against a hypothetical memory budget)
    hbm_bytes_override: Optional[int] = None

    def __post_init__(self):
        if not self.chip:
            object.__setattr__(self, "chip", detect_chip())

    @property
    def num_chips(self) -> int:
        return self.num_nodes * self.chips_per_node

    @property
    def peak_tflops(self) -> float:
        return CHIP_SPECS[self.chip][0]

    @property
    def hbm_gbps(self) -> float:
        return CHIP_SPECS[self.chip][1]

    @property
    def hbm_bytes(self) -> int:
        if self.hbm_bytes_override is not None:
            return self.hbm_bytes_override
        return int(CHIP_SPECS[self.chip][2] * (1 << 30))

    @property
    def ici_gbps(self) -> float:
        return CHIP_SPECS[self.chip][3]

    def resource(self) -> MachineResource:
        return MachineResource(self.num_nodes, self.chips_per_node)


def enumerate_machine_views(
    num_nodes: int, chips_per_node: int
) -> List[MachineView]:
    """All 1-D strided views over the chip grid
    (reference: register_all_machine_views, graph.cc:1783-1814):
    for every divisor-count of chips, contiguous and node-strided layouts.
    """
    total = num_nodes * chips_per_node
    views = []
    seen = set()

    def add(v: MachineView):
        key = (v.start_device_id, v.dims, v.strides)
        if key not in seen:
            seen.add(key)
            views.append(v)

    for ndev in range(1, total + 1):
        if total % ndev != 0:
            continue
        # contiguous runs
        for start in range(0, total - ndev + 1):
            add(MachineView(start, (ndev,), (1,)))
        # strided across nodes (one chip per node position)
        if ndev <= num_nodes and chips_per_node > 0:
            for chip in range(chips_per_node):
                add(MachineView(chip, (ndev,), (chips_per_node,)))
    return views
