"""ctypes bindings to the native C++ core (libffnative.so).

The reference keeps its search-critical machinery in C++ (graph toolkit
include/flexflow/dominators.h, event simulator src/runtime/simulator.cc,
data loader python/flexflow_dataloader.cc); this package is the TPU
rebuild's equivalent native layer. The library is built on demand with the
checked-in Makefile (native/Makefile); every entry point has a pure-Python
fallback so the framework works where no C++ toolchain exists
(set FFTPU_NO_NATIVE=1 to force the fallbacks).
"""

from __future__ import annotations

import collections
import ctypes
import hashlib
import os
import subprocess
import sys
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "build", "libffnative.so")
# wheel installs ship a prebuilt copy inside the package (setup.py
# build_py_with_native); source checkouts build via the Makefile instead
_PKG_LIB_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "libffnative.so"
)

_lib_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_failed = False
# one line saying which implementation serves this process and why;
# written by the first get_lib() (see implementation())
_status = ""

# written beside the library after a successful build: the hash of the
# sources it was built from
_STAMP_PATH = _LIB_PATH + ".srchash"


def _source_hash() -> str:
    """Hash of everything `make` reads for libffnative.so. The build is
    keyed on this, not on mtimes: a copied checkout keeps neither the
    mtimes nor any promise that native/build/ (git-ignored, so whatever
    the last machine left) matches native/src."""
    h = hashlib.sha256()
    paths = [os.path.join(_NATIVE_DIR, "Makefile")]
    for sub in ("src", "include"):
        d = os.path.join(_NATIVE_DIR, sub)
        paths += [os.path.join(d, f) for f in sorted(os.listdir(d))]
    for path in paths:
        h.update(os.path.relpath(path, _NATIVE_DIR).encode())
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def _built_from(src_hash: str) -> bool:
    if not os.path.exists(_LIB_PATH):
        return False
    try:
        with open(_STAMP_PATH) as f:
            return f.read().strip() == src_hash
    except OSError:
        return False


def _build(src_hash: str) -> None:
    # -B: objects left in build/ by another machine are as untrusted as
    # the library itself
    subprocess.run(
        ["make", "-s", "-B", "-j4"],
        cwd=_NATIVE_DIR,
        check=True,
        capture_output=True,
        timeout=300,
    )
    with open(_STAMP_PATH, "w") as f:
        f.write(src_hash + "\n")


def _declare(lib: ctypes.CDLL):
    i32p = ctypes.POINTER(ctypes.c_int32)
    f64p = ctypes.POINTER(ctypes.c_double)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.ffn_topo_sort.restype = ctypes.c_int
    lib.ffn_topo_sort.argtypes = [ctypes.c_int32, ctypes.c_int32, i32p, i32p, i32p]
    lib.ffn_imm_dominators.restype = ctypes.c_int
    lib.ffn_imm_dominators.argtypes = [ctypes.c_int32, ctypes.c_int32, i32p, i32p, i32p]
    lib.ffn_imm_post_dominators.restype = ctypes.c_int
    lib.ffn_imm_post_dominators.argtypes = [
        ctypes.c_int32, ctypes.c_int32, i32p, i32p, i32p,
    ]
    lib.ffn_transitive_reduction.restype = ctypes.c_int
    lib.ffn_transitive_reduction.argtypes = [
        ctypes.c_int32, ctypes.c_int32, i32p, i32p, u8p,
    ]
    lib.ffn_simulate.restype = ctypes.c_double
    lib.ffn_simulate.argtypes = [
        ctypes.c_int32, i32p, f64p, ctypes.c_int32, i32p, i32p,
        ctypes.c_int32, f64p, f64p,
    ]
    i64p = ctypes.POINTER(ctypes.c_int64)
    lib.ffn_loader_create.restype = ctypes.c_void_p
    lib.ffn_loader_create.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), i64p,
        ctypes.c_int32, ctypes.c_int64, ctypes.c_int64, i64p,
        ctypes.c_int32, ctypes.c_int32, ctypes.POINTER(ctypes.c_void_p),
    ]
    lib.ffn_loader_num_batches.restype = ctypes.c_int64
    lib.ffn_loader_num_batches.argtypes = [ctypes.c_void_p]
    lib.ffn_loader_borrow.restype = ctypes.c_int64
    lib.ffn_loader_borrow.argtypes = [ctypes.c_void_p]
    lib.ffn_loader_release.restype = None
    lib.ffn_loader_release.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.ffn_loader_gathered.restype = ctypes.c_int64
    lib.ffn_loader_gathered.argtypes = [ctypes.c_void_p]
    lib.ffn_loader_queue_perm.restype = None
    lib.ffn_loader_queue_perm.argtypes = [ctypes.c_void_p, i64p]
    lib.ffn_loader_reset.restype = None
    lib.ffn_loader_reset.argtypes = [ctypes.c_void_p, i64p]
    lib.ffn_loader_destroy.restype = None
    lib.ffn_loader_destroy.argtypes = [ctypes.c_void_p]
    lib.ffn_unity_dp.restype = ctypes.c_int
    lib.ffn_unity_dp.argtypes = [
        ctypes.c_int32, ctypes.c_int32, i32p, i32p, f64p,  # edges
        i64p, i64p, f64p, f64p, f64p, f64p,  # per-node scalars
        f64p, i32p,  # optimizer-update bytes basis + dp-scaling flags
        f64p,  # sparse touched-row sync bytes basis
        ctypes.c_double,  # optimizer traffic factor (2*state_factor - 1)
        ctypes.c_int32,  # allow sub-block concurrent-branch views
        ctypes.c_int32, i32p, i32p, i32p, f64p,  # measured-view LUT
        ctypes.c_int32, ctypes.c_int32,  # machine geometry
        ctypes.c_double, ctypes.c_double, ctypes.c_double, ctypes.c_double,
        ctypes.c_int32,  # sink
        i32p, i32p, f64p,  # out
    ]


def get_lib() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None when
    unavailable, and every caller then takes its pure-Python path. Says
    once on stderr which of the two serves this process."""
    global _lib, _lib_failed, _status
    if _lib is not None or _lib_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            if os.environ.get("FFTPU_NO_NATIVE"):
                raise RuntimeError("FFTPU_NO_NATIVE is set")
            if os.path.exists(_PKG_LIB_PATH):
                lib = ctypes.CDLL(_PKG_LIB_PATH)
                origin = "packaged with the wheel"
            else:
                src_hash = _source_hash()
                if _built_from(src_hash):
                    origin = f"build of sources {src_hash} reused"
                else:
                    _build(src_hash)
                    origin = f"built from sources {src_hash}"
                lib = ctypes.CDLL(_LIB_PATH)
            _declare(lib)
            _lib = lib
            _status = f"native (libffnative.so, {origin})"
        except (OSError, RuntimeError, subprocess.SubprocessError) as e:
            _lib_failed = True
            detail = getattr(e, "stderr", None) or e
            if isinstance(detail, bytes):
                detail = detail.decode(errors="replace")
            _status = f"python (native core unavailable: {detail})"
        print(f"[flexflow_tpu] search core: {_status}", file=sys.stderr)
    return _lib


def implementation() -> str:
    """Which implementation serves the graph algorithms, the simulator,
    the Unity DP and the data loader in this process: "native (...)" or
    "python (...)" with the reason."""
    get_lib()
    return _status


def _as_i32(a) -> np.ndarray:
    return np.ascontiguousarray(a, dtype=np.int32)


def _i32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _i64p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _f64p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_double))


def unity_dp(
    edges,  # [(src, dst, bytes)] with node indices 0..n-1
    batch,  # per-node sample-dim sizes (<=0: single-chip only)
    chan,  # per-node channel sizes (<=0: no 2-D views)
    flops,
    bytes_moved,
    wbytes,
    bwd_mult,
    machine_nodes: int,
    chips_per_node: int,
    peak_eff: float,
    hbm_eff: float,
    ici_eff: float,
    ici_lat: float,
    sink: int,
    ubytes=None,  # optimizer-update bytes basis (defaults to wbytes)
    u_dp_scaled=None,  # per-node 1 where update traffic divides by dp
    sbytes=None,  # sparse touched-row sync bytes (all-gather over dp)
    update_factor: float = 5.0,  # 2*state_factor - 1
    allow_subblock: bool = False,  # unity.py allow_subblock_views
    measured=None,  # [(node_idx, dp, ch, cost_s)] replacing the roofline
):
    """Native Unity DP (native/src/unity_dp.cc — the reference's
    SearchHelper::graph_cost role). Returns (cost, dp[], ch[]) or None
    when the native library is unavailable or the graph exceeds 256 nodes."""
    n = len(batch)
    lib = get_lib()
    if lib is None or n > 256 or n == 0:
        return None
    esrc = _as_i32([e[0] for e in edges])
    edst = _as_i32([e[1] for e in edges])
    ebytes = np.ascontiguousarray([e[2] for e in edges], dtype=np.float64)
    b = np.ascontiguousarray(batch, dtype=np.int64)
    c = np.ascontiguousarray(chan, dtype=np.int64)
    f = np.ascontiguousarray(flops, dtype=np.float64)
    by = np.ascontiguousarray(bytes_moved, dtype=np.float64)
    w = np.ascontiguousarray(wbytes, dtype=np.float64)
    bm = np.ascontiguousarray(bwd_mult, dtype=np.float64)
    ub = np.ascontiguousarray(
        wbytes if ubytes is None else ubytes, dtype=np.float64
    )
    us = (
        np.zeros(n, dtype=np.int32)
        if u_dp_scaled is None
        else np.ascontiguousarray(u_dp_scaled, dtype=np.int32)
    )
    sb = (
        np.zeros(n, dtype=np.float64)
        if sbytes is None
        else np.ascontiguousarray(sbytes, dtype=np.float64)
    )
    out_dp = np.empty(n, dtype=np.int32)
    out_ch = np.empty(n, dtype=np.int32)
    out_cost = np.empty(1, dtype=np.float64)
    rc = lib.ffn_unity_dp(
        n, len(edges), _i32p(esrc), _i32p(edst), _f64p(ebytes),
        _i64p(b), _i64p(c), _f64p(f), _f64p(by), _f64p(w), _f64p(bm),
        _f64p(ub), _i32p(us), _f64p(sb), update_factor, int(allow_subblock),
        len(measured or []),
        _i32p(_as_i32([m[0] for m in measured or []])),
        _i32p(_as_i32([m[1] for m in measured or []])),
        _i32p(_as_i32([m[2] for m in measured or []])),
        _f64p(
            np.ascontiguousarray(
                [m[3] for m in measured or []], dtype=np.float64
            )
        ),
        machine_nodes, chips_per_node, peak_eff, hbm_eff, ici_eff, ici_lat,
        sink, _i32p(out_dp), _i32p(out_ch), _f64p(out_cost),
    )
    if rc != 0:
        return None
    return float(out_cost[0]), out_dp.tolist(), out_ch.tolist()


# -- graph algorithms ---------------------------------------------------------


def topo_sort(n: int, edges: Sequence[Tuple[int, int]]) -> Optional[List[int]]:
    """Deterministic topological order of nodes 0..n-1; None on cycle."""
    lib = get_lib()
    src = _as_i32([e[0] for e in edges])
    dst = _as_i32([e[1] for e in edges])
    if lib is not None:
        out = np.empty(n, dtype=np.int32)
        rc = lib.ffn_topo_sort(n, len(edges), _i32p(src), _i32p(dst), _i32p(out))
        return None if rc != 0 else out.tolist()
    # fallback: Kahn with sorted ready set
    indeg = [0] * n
    adj = [[] for _ in range(n)]
    for s, d in edges:
        adj[s].append(d)
        indeg[d] += 1
    import heapq

    ready = [v for v in range(n) if indeg[v] == 0]
    heapq.heapify(ready)
    order = []
    while ready:
        v = heapq.heappop(ready)
        order.append(v)
        for w in adj[v]:
            indeg[w] -= 1
            if indeg[w] == 0:
                heapq.heappush(ready, w)
    return order if len(order) == n else None


def imm_post_dominators(
    n: int, edges: Sequence[Tuple[int, int]]
) -> Optional[List[int]]:
    """ipdom[v] (or -1 when only the virtual sink post-dominates v).

    The search's find_split_node uses this to locate sequence-split
    bottlenecks (reference: dominators.h:377, substitution.cc:1984).
    """
    lib = get_lib()
    if lib is not None:
        src = _as_i32([e[0] for e in edges])
        dst = _as_i32([e[1] for e in edges])
        out = np.empty(n, dtype=np.int32)
        rc = lib.ffn_imm_post_dominators(
            n, len(edges), _i32p(src), _i32p(dst), _i32p(out)
        )
        return None if rc != 0 else out.tolist()
    return _py_imm_post_dominators(n, edges)


def _py_imm_post_dominators(n, edges):
    """Pure-Python fallback: post-dominator sets by reverse-topo dataflow,
    then ipdom = the nearest strict post-dominator."""
    order = topo_sort(n, edges)
    if order is None:
        return None
    succ = [[] for _ in range(n)]
    for s, d in edges:
        succ[s].append(d)
    full = frozenset(range(n))
    pdom = [full] * n
    for v in reversed(order):
        if not succ[v]:
            pdom[v] = frozenset([v])
        else:
            inter = frozenset.intersection(*[pdom[s] for s in succ[v]])
            pdom[v] = inter | {v}
    index = {v: i for i, v in enumerate(order)}
    out = []
    for v in range(n):
        strict = [d for d in pdom[v] if d != v]
        # nearest = the one earliest in topo order among strict post-doms
        out.append(min(strict, key=lambda d: index[d]) if strict else -1)
    return out


def transitive_reduction(
    n: int, edges: Sequence[Tuple[int, int]]
) -> Optional[List[bool]]:
    """keep[i] per edge; False when implied by a longer path."""
    lib = get_lib()
    src = [e[0] for e in edges]
    dst = [e[1] for e in edges]
    if lib is not None:
        out = np.empty(len(edges), dtype=np.uint8)
        rc = lib.ffn_transitive_reduction(
            n, len(edges), _i32p(_as_i32(src)), _i32p(_as_i32(dst)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        )
        return None if rc != 0 else [bool(x) for x in out]
    adj = [[] for _ in range(n)]
    for s, d in edges:
        adj[s].append(d)
    keep = []
    for s, d in edges:
        seen = set()
        stack = [w for w in adj[s] if w != d]
        found = False
        while stack:
            v = stack.pop()
            if v == d:
                found = True
                break
            for w in adj[v]:
                if w not in seen:
                    seen.add(w)
                    stack.append(w)
        keep.append(not found)
    return keep


# -- event-driven simulator ---------------------------------------------------


def simulate(
    resource_of: Sequence[int],
    duration: Sequence[float],
    edges: Sequence[Tuple[int, int]],
    num_resources: int,
) -> Optional[Tuple[float, np.ndarray]]:
    """Replay a task DAG; returns (makespan, per-resource busy time).

    Native path is ffn_simulate (reference: simulate_runtime,
    simulator.cc:810-1240); fallback is an equivalent Python event loop.
    """
    n = len(resource_of)
    lib = get_lib()
    if lib is not None:
        res = _as_i32(resource_of)
        dur = np.ascontiguousarray(duration, dtype=np.float64)
        src = _as_i32([e[0] for e in edges])
        dst = _as_i32([e[1] for e in edges])
        busy = np.zeros(num_resources, dtype=np.float64)
        ms = lib.ffn_simulate(
            n, _i32p(res), dur.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            len(edges), _i32p(src), _i32p(dst), num_resources,
            busy.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), None,
        )
        return None if ms < 0 else (float(ms), busy)
    return _py_simulate(resource_of, duration, edges, num_resources)


def _py_simulate(resource_of, duration, edges, num_resources):
    import heapq

    n = len(resource_of)
    out_edges = [[] for _ in range(n)]
    unmet = [0] * n
    for s, d in edges:
        out_edges[s].append(d)
        unmet[d] += 1
    ready = [[] for _ in range(num_resources)]  # heaps of (ready_t, task)
    running = [False] * num_resources
    busy = np.zeros(num_resources)
    done_heap = []
    completed = 0
    makespan = 0.0

    def try_start(r, now):
        if running[r] or not ready[r]:
            return
        _, t = heapq.heappop(ready[r])
        end = now + duration[t]
        running[r] = True
        busy[r] += duration[t]
        heapq.heappush(done_heap, (end, t))

    for i in range(n):
        if unmet[i] == 0:
            heapq.heappush(ready[resource_of[i]], (0.0, i))
    for r in range(num_resources):
        try_start(r, 0.0)
    while done_heap:
        now, t = heapq.heappop(done_heap)
        makespan = max(makespan, now)
        completed += 1
        r = resource_of[t]
        running[r] = False
        for s in out_edges[t]:
            unmet[s] -= 1
            if unmet[s] == 0:
                heapq.heappush(ready[resource_of[s]], (now, s))
        try_start(r, now)
        for s in out_edges[t]:
            rs = resource_of[s]
            if not running[rs]:
                try_start(rs, now)
    if completed != n:
        return None
    return makespan, busy


# -- data loader --------------------------------------------------------------


def _i64_ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _alloc_slot(shape, dtype) -> np.ndarray:
    """One reusable batch buffer, on a 64-byte boundary. Where the slot
    sits decides nothing about correctness (a backend that keeps host
    memory it was handed is found out by looking, see
    `SingleDataLoader.lend`); a fixed boundary makes which way a backend
    goes the same in every run."""
    nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
    raw = np.empty(nbytes + 64, np.uint8)
    start = -raw.ctypes.data % 64
    return raw[start : start + nbytes].view(dtype).reshape(shape)


class NativeLoader:
    """Background-threaded shuffle/batch/prefetch loader (reference:
    SingleDataLoader, python/flexflow_dataloader.h:34). Falls back to
    synchronous numpy batching without the native library.

    The epoch permutation is always drawn from numpy's seeded RNG here in
    Python and handed to the C++ side, so the batch stream for a given seed
    is identical whether or not the native library loaded.

    Batches are gathered into a ring of `prefetch_depth` slots that this
    object owns and reuses. `next_batch()` copies a slot out and the
    caller owns the copy; `borrow()` lends the slot itself, until
    `release()`.

    A batch's index, and with it its slot and its lease, counts on from
    the last reset. The stream ends with the epoch unless the order of
    another was queued (`queue_perm`): then it goes on into that epoch
    with no reset, every lease kept, and the worker gathers its first
    batches beside the last of this one."""

    def __init__(
        self,
        arrays: Sequence[np.ndarray],
        batch_size: int,
        shuffle: bool = True,
        seed: int = 0,
        drop_last: bool = True,
        prefetch_depth: int = 3,
        use_lib: bool = True,
    ):
        self.arrays = [np.ascontiguousarray(a) for a in arrays]
        n = self.arrays[0].shape[0]
        for a in self.arrays:
            if a.shape[0] != n:
                raise ValueError("all arrays must share the sample dimension")
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self.depth = max(1, int(prefetch_depth))
        self._handle = None
        self._lib = get_lib() if use_lib else None
        self._perm = self._make_perm(seed)
        self._slots = None  # [depth][array]; the fallback makes them on first use
        self._lent = set()  # indices of the batches whose slot is out
        if self._lib is not None:
            self._slots = self._make_slots()
            ptrs = (ctypes.c_void_p * len(self.arrays))(
                *[a.ctypes.data for a in self.arrays]
            )
            row_bytes = (ctypes.c_int64 * len(self.arrays))(
                *[a.nbytes // n for a in self.arrays]
            )
            slot_ptrs = (ctypes.c_void_p * (self.depth * len(self.arrays)))(
                *[buf.ctypes.data for slot in self._slots for buf in slot]
            )
            self._handle = self._lib.ffn_loader_create(
                ptrs, row_bytes, len(self.arrays), n, batch_size,
                _i64_ptr(self._perm),
                1 if drop_last else 0, self.depth, slot_ptrs,
            )
        # the fallback's stream, as dataloader.cc keeps it: the next batch,
        # where `_perm`'s epoch ends, the orders of the epochs after it
        self._pos = 0
        self._perm_end = self.num_batches
        self._queued = collections.deque()

    def _make_perm(self, seed) -> np.ndarray:
        idx = np.arange(self.arrays[0].shape[0], dtype=np.int64)
        if self.shuffle:
            np.random.RandomState(seed).shuffle(idx)
        return np.ascontiguousarray(idx)

    def _make_slots(self) -> List[List[np.ndarray]]:
        return [
            [
                _alloc_slot((self.batch_size,) + a.shape[1:], a.dtype)
                for a in self.arrays
            ]
            for _ in range(self.depth)
        ]

    @property
    def num_batches(self) -> int:
        n = self.arrays[0].shape[0]
        if self._handle is not None:
            return int(self._lib.ffn_loader_num_batches(self._handle))
        return n // self.batch_size if self.drop_last else -(-n // self.batch_size)

    def _gather(self) -> int:
        """The fallback's gather, on the caller's thread: the next batch
        into its slot. Returns its index, -1 at the end of the last epoch
        an order was given for, -2 when that slot is still lent (as
        ffn_loader_borrow does)."""
        idx = self._pos
        if idx == self._perm_end and not self._queued:
            return -1
        if idx - self.depth in self._lent:
            return -2
        if idx == self._perm_end:  # the epoch's turn: on in the next order
            self._perm = self._queued.popleft()
            self._perm_end += self.num_batches
        at = idx - (self._perm_end - self.num_batches)
        rows = self._perm[at * self.batch_size : (at + 1) * self.batch_size]
        if len(rows) < self.batch_size:  # pad short final batch
            rows = np.concatenate(
                [rows, np.repeat(rows[:1], self.batch_size - len(rows))]
            )
        if self._slots is None:
            self._slots = self._make_slots()
        for a, buf in zip(self.arrays, self._slots[idx % self.depth]):
            # mode="clip": numpy stages a checked take in a block of its
            # own before it writes `out`; rows are always in range
            np.take(a, rows, axis=0, out=buf, mode="clip")
        self._pos += 1
        return idx

    def borrow(self) -> Optional[Tuple[int, List[np.ndarray]]]:
        """The next batch, lent: (its index since the last reset,
        per-array [batch_size, ...] views of a slot this loader reuses),
        or None at the end of the last epoch an order was given for. The
        views hold the batch until `release(index)` or a reset; at most
        `depth` batches can be out at once."""
        if self._handle is not None:
            idx = int(self._lib.ffn_loader_borrow(self._handle))
        else:
            idx = self._gather()
        if idx == -1:
            return None
        if idx == -2:
            raise RuntimeError(
                f"all {self.depth} batch slots are lent; release one first"
            )
        self._lent.add(idx)
        return idx, self._slots[idx % self.depth]

    def release(self, index: int):
        """Hand batch `index`'s slot back: whatever read the views is done."""
        if index in self._lent:
            self._lent.discard(index)
            if self._handle is not None:
                self._lib.ffn_loader_release(self._handle, index)

    def next_batch(self) -> Optional[List[np.ndarray]]:
        """Returns per-array [batch_size, ...] copies, or None at epoch end."""
        got = self.borrow()
        if got is None:
            return None
        index, views = got
        out = [v.copy() for v in views]
        self.release(index)
        return out

    def reset(self, seed: Optional[int] = None):
        seed = self.seed if seed is None else seed
        self.reset_perm(self._make_perm(seed))

    def reset_perm(self, perm: np.ndarray):
        """A new stream of one epoch with an explicit sample order (len ==
        num_samples), from its first batch. Takes every lent slot back and
        drops what was gathered or queued ahead."""
        self._perm = np.ascontiguousarray(perm, dtype=np.int64)
        self._pos = 0
        self._perm_end = self.num_batches
        self._queued.clear()
        self._lent.clear()
        if self._handle is not None:
            self._lib.ffn_loader_reset(self._handle, _i64_ptr(self._perm))

    def queue_perm(self, perm: np.ndarray):
        """One more epoch after the last one known, in the order `perm`:
        the stream goes on into it with no reset and every lease kept. The
        order is copied: the caller may shuffle its own in place."""
        perm = np.array(perm, dtype=np.int64)
        if perm.shape != self._perm.shape:
            raise ValueError(
                f"an order of {perm.shape} for {self._perm.shape[0]} samples"
            )
        if self._handle is not None:
            self._lib.ffn_loader_queue_perm(self._handle, _i64_ptr(perm))
        else:
            self._queued.append(perm)

    def gathered(self) -> int:
        """Batches gathered since the last reset: those with an index
        below it are ready in the ring, or were. The worker runs ahead of
        `borrow` as slots allow; the fallback gathers inside it."""
        if self._handle is not None:
            return int(self._lib.ffn_loader_gathered(self._handle))
        return self._pos

    def close(self):
        """Stops and joins the worker; what it gathered ahead is dropped."""
        if getattr(self, "_handle", None) is not None and self._lib is not None:
            self._lib.ffn_loader_destroy(self._handle)
            self._handle = None

    __del__ = close


def available() -> bool:
    return get_lib() is not None
