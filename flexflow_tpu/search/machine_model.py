"""Machine models for the strategy search: comm-device chains and network
topology simulation.

Rebuild of the reference's machine-model hierarchy (reference:
src/runtime/machine_model.cc (1287 LoC), simulator.h:203-367;
network simulation src/runtime/network.cc (586 LoC), simulator.h:372-596)
with the comm-device classes swapped from NVLink/PCIe/NIC/membus to the
TPU stack:

  * **ICI** — chip↔chip torus links inside a slice (one device per torus
    axis, so same-axis collectives serialize while cross-axis overlap).
  * **PCIe** — chip↔host, for host-staged transfers and data loading.
  * **DCN** — host↔host NIC across slices.

Three models, mirroring the reference's:

  * `SimpleMachineModel` — two bandwidths: intra-node (ICI) and inter-node
    (DCN) (reference: SimpleMachineModel, simulator.h:203).
  * `EnhancedMachineModel` — parsed from a machine-config file; explicit
    comm devices with latency+bandwidth, per-path device chains, and
    segmented-message pipelining (reference: EnhancedMachineModel +
    machine_config_example; --machine-model-version/-file flags,
    model.cc:3650+).
  * `NetworkedMachineModel` — explicit `ConnectionMatrix` topology over
    nodes and switches with routing strategies and topology generators
    (reference: network.cc; WeightedShortestPathRoutingStrategy etc.).
    The TPU generator of interest is the torus; big-switch / fat-tree /
    fully-connected match the reference's generators for DCN studies.
"""

from __future__ import annotations

import dataclasses
import heapq
from typing import Dict, List, Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class CommDevice:
    """One communication resource (reference: CommDevice, simulator.h:133-157
    — {name, device_type, node_id, device_id, latency, bandwidth})."""

    name: str
    kind: str  # "ici" | "pcie" | "dcn" | "link" (networked)
    latency_s: float
    bandwidth_Bps: float

    def time(self, num_bytes: float) -> float:
        return self.latency_s + num_bytes / self.bandwidth_Bps


class MachineModel:
    """Abstract base (reference: MachineModel, simulator.h:203):
    get_comm_path(src, dst) + transfer-time evaluation over the path."""

    def num_chips(self) -> int:
        raise NotImplementedError

    def get_comm_path(self, src_chip: int, dst_chip: int) -> List[CommDevice]:
        raise NotImplementedError

    def transfer_time(self, src_chip: int, dst_chip: int, num_bytes: float) -> float:
        """Un-segmented: sum of device times along the chain."""
        path = self.get_comm_path(src_chip, dst_chip)
        return sum(d.time(num_bytes) for d in path)


@dataclasses.dataclass
class SimpleMachineModel(MachineModel):
    """Intra-node ICI / inter-node DCN, one bandwidth each
    (reference: SimpleMachineModel — intra-node BW / inter-node BW)."""

    num_nodes: int
    chips_per_node: int
    ici_gbps: float = 45.0
    dcn_gbps: float = 25.0
    ici_latency_s: float = 1e-6
    dcn_latency_s: float = 10e-6

    def num_chips(self) -> int:
        return self.num_nodes * self.chips_per_node

    def get_comm_path(self, src_chip: int, dst_chip: int) -> List[CommDevice]:
        if src_chip == dst_chip:
            return []
        same_node = (
            src_chip // self.chips_per_node == dst_chip // self.chips_per_node
        )
        if same_node:
            return [
                CommDevice("ici", "ici", self.ici_latency_s, self.ici_gbps * 1e9)
            ]
        return [
            CommDevice("dcn", "dcn", self.dcn_latency_s, self.dcn_gbps * 1e9)
        ]


class EnhancedMachineModel(MachineModel):
    """Config-file machine model with comm-device chains and segmented
    pipelining (reference: EnhancedMachineModel, machine_model.cc; config
    format modeled on machine_config_example).

    Config format (key = value, '#' comments):

        num_nodes = 2
        chips_per_node = 4
        ici_bandwidth_gbps = 45      # per torus link
        ici_latency_us = 1
        ici_dims = 2                 # torus axes inside a slice
        pcie_bandwidth_gbps = 32
        pcie_latency_us = 2
        dcn_bandwidth_gbps = 25
        dcn_latency_us = 10
        segment_size_mb = 16         # message segmentation unit
        inter_slice = host           # "host" (chip-pcie-dcn-pcie-chip)
                                     # or "direct" (ici-extended slices)
    """

    _KEYS = frozenset(
        {
            "num_nodes",
            "chips_per_node",
            "ici_bandwidth_gbps",
            "ici_latency_us",
            "ici_dims",
            "pcie_bandwidth_gbps",
            "pcie_latency_us",
            "dcn_bandwidth_gbps",
            "dcn_latency_us",
            "segment_size_mb",
            "inter_slice",
        }
    )

    def __init__(self, text: str):
        kv: Dict[str, str] = {}
        for line in text.splitlines():
            line = line.split("#", 1)[0].strip()
            if not line:
                continue
            if "=" not in line:
                raise ValueError(f"bad machine-config line: {line!r}")
            k, v = (s.strip() for s in line.split("=", 1))
            if k not in self._KEYS:
                raise ValueError(
                    f"unknown machine-config key {k!r}; known keys: "
                    f"{sorted(self._KEYS)}"
                )
            kv[k] = v

        def f(key, default):
            return float(kv.get(key, default))

        self.num_nodes = int(f("num_nodes", 1))
        self.chips_per_node = int(f("chips_per_node", 4))
        self.ici_dims = int(f("ici_dims", 2))
        self.segment_bytes = int(f("segment_size_mb", 16) * (1 << 20))
        self.inter_slice = kv.get("inter_slice", "host")
        if self.inter_slice not in ("host", "direct"):
            raise ValueError(f"inter_slice must be host|direct, got {self.inter_slice!r}")
        self._ici = CommDevice(
            "ici", "ici", f("ici_latency_us", 1) * 1e-6,
            f("ici_bandwidth_gbps", 45) * 1e9,
        )
        self._pcie = CommDevice(
            "pcie", "pcie", f("pcie_latency_us", 2) * 1e-6,
            f("pcie_bandwidth_gbps", 32) * 1e9,
        )
        self._dcn = CommDevice(
            "dcn", "dcn", f("dcn_latency_us", 10) * 1e-6,
            f("dcn_bandwidth_gbps", 25) * 1e9,
        )

    @classmethod
    def from_file(cls, path: str) -> "EnhancedMachineModel":
        with open(path) as fh:
            return cls(fh.read())

    def num_chips(self) -> int:
        return self.num_nodes * self.chips_per_node

    def get_comm_path(self, src_chip: int, dst_chip: int) -> List[CommDevice]:
        if src_chip == dst_chip:
            return []
        same = src_chip // self.chips_per_node == dst_chip // self.chips_per_node
        if same:
            # intra-slice: worst case crosses every torus axis once, so the
            # path is one ICI device per axis (ici_dims = 1 means a ring)
            return [self._ici] * max(1, self.ici_dims)
        if self.inter_slice == "direct":
            return [self._ici] * max(1, self.ici_dims) * 2
        return [self._pcie, self._dcn, self._pcie]

    def transfer_time(self, src_chip: int, dst_chip: int, num_bytes: float) -> float:
        """Segmented pipelining (reference: EnhancedMachineModel's
        segmented messages): the message is cut into segments that stream
        through the device chain, so total ≈ latency of the whole chain +
        (num_segments - 1 + chain_length) · slowest-segment time."""
        path = self.get_comm_path(src_chip, dst_chip)
        if not path:
            return 0.0
        nseg = max(1, -(-int(num_bytes) // self.segment_bytes))
        seg = num_bytes / nseg
        lat = sum(d.latency_s for d in path)
        slowest = max(seg / d.bandwidth_Bps for d in path)
        return lat + (nseg - 1 + len(path)) * slowest


# -- networked model ----------------------------------------------------------


@dataclasses.dataclass
class ConnectionMatrix:
    """Explicit link topology over num_nodes + num_switches vertices
    (reference: ConnectionMatrix, simulator.h:372+): conn[i][j] = number of
    parallel links i→j (0 = not connected)."""

    num_nodes: int
    num_switches: int
    conn: List[List[int]]

    @property
    def size(self) -> int:
        return self.num_nodes + self.num_switches

    def degree(self, v: int) -> int:
        return sum(1 for x in self.conn[v] if x > 0)


def torus_topology(dims: Sequence[int]) -> ConnectionMatrix:
    """TPU slice ICI torus (the generator the reference lacks; its closest
    is the flat degree-constrained generator, network.cc)."""
    import itertools

    n = 1
    for d in dims:
        n *= d
    coords = list(itertools.product(*(range(d) for d in dims)))
    index = {c: i for i, c in enumerate(coords)}
    conn = [[0] * n for _ in range(n)]
    for c in coords:
        for ax, d in enumerate(dims):
            if d <= 1:
                continue
            nb = list(c)
            nb[ax] = (nb[ax] + 1) % d
            i, j = index[c], index[tuple(nb)]
            if i != j:
                conn[i][j] += 1
                conn[j][i] += 1
    return ConnectionMatrix(n, 0, conn)


def big_switch_topology(num_nodes: int) -> ConnectionMatrix:
    """All nodes hang off one switch (reference: the 'big switch' NVSwitch /
    single-ToR abstraction)."""
    size = num_nodes + 1
    conn = [[0] * size for _ in range(size)]
    sw = num_nodes
    for i in range(num_nodes):
        conn[i][sw] = conn[sw][i] = 1
    return ConnectionMatrix(num_nodes, 1, conn)


def fully_connected_topology(num_nodes: int) -> ConnectionMatrix:
    conn = [
        [1 if i != j else 0 for j in range(num_nodes)] for i in range(num_nodes)
    ]
    return ConnectionMatrix(num_nodes, 0, conn)


def fat_tree_topology(num_nodes: int, pods: int = 2) -> ConnectionMatrix:
    """Two-level leaf/spine tree: num_nodes leaves split over `pods` leaf
    switches, all leaf switches connected to one spine (a simplified
    fat-tree in the spirit of the reference's generators)."""
    pods = max(1, min(pods, num_nodes))
    num_switches = pods + 1
    size = num_nodes + num_switches
    conn = [[0] * size for _ in range(size)]
    spine = num_nodes + pods
    for i in range(num_nodes):
        leaf = num_nodes + (i * pods) // num_nodes
        conn[i][leaf] = conn[leaf][i] = 1
    for p in range(pods):
        leaf = num_nodes + p
        conn[leaf][spine] = conn[spine][leaf] = 1
    return ConnectionMatrix(num_nodes, num_switches, conn)


class RoutingStrategy:
    """reference: routing strategies in network.cc (weighted/shortest-path
    ECMP)."""

    def route(
        self, topo: ConnectionMatrix, src: int, dst: int
    ) -> Optional[List[int]]:
        raise NotImplementedError


class ShortestPathRouting(RoutingStrategy):
    def route(self, topo, src, dst):
        if src == dst:
            return [src]
        prev = {src: None}
        q = [src]
        while q:
            v = q.pop(0)
            for w in range(topo.size):
                if topo.conn[v][w] > 0 and w not in prev:
                    prev[w] = v
                    if w == dst:
                        path = [w]
                        while prev[path[-1]] is not None:
                            path.append(prev[path[-1]])
                        return list(reversed(path))
                    q.append(w)
        return None


class WeightedShortestPathRouting(RoutingStrategy):
    """Dijkstra with link weight = 1 / multiplicity: prefers fat links
    (reference: WeightedShortestPathRoutingStrategy)."""

    def route(self, topo, src, dst):
        if src == dst:
            return [src]
        dist = {src: 0.0}
        prev: Dict[int, Optional[int]] = {src: None}
        pq = [(0.0, src)]
        while pq:
            d, v = heapq.heappop(pq)
            if v == dst:
                path = [v]
                while prev[path[-1]] is not None:
                    path.append(prev[path[-1]])
                return list(reversed(path))
            if d > dist.get(v, float("inf")):
                continue
            for w in range(topo.size):
                m = topo.conn[v][w]
                if m > 0:
                    nd = d + 1.0 / m
                    if nd < dist.get(w, float("inf")):
                        dist[w] = nd
                        prev[w] = v
                        heapq.heappush(pq, (nd, w))
        return None


class NetworkedMachineModel(MachineModel):
    """Topology-aware model: chips map onto topology nodes; transfer time
    routes through the ConnectionMatrix (reference: NetworkedMachineModel,
    simulator.h:372-596 + network.cc)."""

    def __init__(
        self,
        num_nodes: int,
        chips_per_node: int,
        topology: ConnectionMatrix,
        link_gbps: float = 25.0,
        link_latency_s: float = 5e-6,
        intra_node_gbps: float = 45.0,
        routing: Optional[RoutingStrategy] = None,
    ):
        if topology.num_nodes != num_nodes:
            raise ValueError(
                f"topology has {topology.num_nodes} nodes, expected {num_nodes}"
            )
        self.num_nodes = num_nodes
        self.chips_per_node = chips_per_node
        self.topology = topology
        self.link_gbps = link_gbps
        self.link_latency_s = link_latency_s
        self.intra_node_gbps = intra_node_gbps
        self.routing = routing or WeightedShortestPathRouting()
        self._path_cache: Dict[Tuple[int, int], Optional[List[int]]] = {}
        self._device_cache: Dict[Tuple[int, int], List[CommDevice]] = {}
        self._ici_dev = CommDevice("ici", "ici", 1e-6, intra_node_gbps * 1e9)

    def num_chips(self) -> int:
        return self.num_nodes * self.chips_per_node

    def _node_route(self, a: int, b: int) -> Optional[List[int]]:
        key = (a, b)
        if key not in self._path_cache:
            self._path_cache[key] = self.routing.route(self.topology, a, b)
        return self._path_cache[key]

    def get_comm_path(self, src_chip: int, dst_chip: int) -> List[CommDevice]:
        if src_chip == dst_chip:
            return []
        a = src_chip // self.chips_per_node
        b = dst_chip // self.chips_per_node
        if a == b:
            return [self._ici_dev]
        key = (a, b)
        cached = self._device_cache.get(key)
        if cached is not None:
            return cached
        route = self._node_route(a, b)
        if route is None:
            raise ValueError(f"no route between nodes {a} and {b}")
        devices = []
        for u, v in zip(route, route[1:]):
            mult = max(1, self.topology.conn[u][v])
            devices.append(
                CommDevice(
                    f"link{u}-{v}",
                    "link",
                    self.link_latency_s,
                    self.link_gbps * 1e9 * mult,
                )
            )
        self._device_cache[key] = devices
        return devices


def build_machine_model(config, spec) -> Optional[MachineModel]:
    """--machine-model-version dispatch (reference: graph.cc:1566-1581):
    0 = Simple (None here: the CostModel's built-in ring formulas),
    1 = Enhanced from --machine-model-file,
    2 = Networked torus of the slice."""
    version = getattr(config, "machine_model_version", 0)
    if version not in (0, 1, 2):
        raise ValueError(
            f"unknown --machine-model-version {version}; expected 0 | 1 | 2"
        )
    if version == 1:
        if not getattr(config, "machine_model_file", ""):
            raise ValueError("--machine-model-version 1 needs --machine-model-file")
        return EnhancedMachineModel.from_file(config.machine_model_file)
    if version == 2:
        topo = torus_topology((spec.num_nodes,)) if spec.num_nodes > 1 else (
            fully_connected_topology(1)
        )
        return NetworkedMachineModel(
            spec.num_nodes,
            spec.chips_per_node,
            topo,
            link_gbps=spec.dcn_bandwidth_gbps,
            intra_node_gbps=spec.ici_gbps,
        )
    return None
