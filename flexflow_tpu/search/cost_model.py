"""Per-op and per-collective cost model for the strategy search.

Replaces the reference's Simulator op-cost measurement + analytic xfer cost
(reference: src/runtime/simulator.cc:532-756, src/runtime/model.cu:38-74 —
real-kernel timing cached by (OperatorParameters, MachineView)) with a
TPU-appropriate split:

  * **analytic roofline** per op: time = max(FLOPs / MXU peak, bytes / HBM
    bandwidth). This is the default so the search runs without hardware
    (reference's --search-num-workers override, model.cc:3673-3680).
  * **measured mode**: jit the op's lowered function on its *shard* shapes on
    the real chip, time it, and cache by (params_hash, shard shapes) — the
    direct analog of inner_measure_operator_cost. Under XLA an isolated-op
    time over-counts what fusion removes, so measurement is reserved for the
    big MXU ops where it is accurate (matmul/conv/attention).
  * **collective costs** from ring formulas over ICI: all-reduce moves
    2·(n-1)/n · bytes per link, all-gather/reduce-scatter (n-1)/n · bytes,
    all-to-all (n-1)/n · bytes with full bisection.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

from flexflow_tpu.core.machine import MachineSpec
from flexflow_tpu.core.parallel_tensor import ParallelTensorShape
from flexflow_tpu.core.types import DataType, OperatorType
from flexflow_tpu.ops.registry import op_flops


@dataclasses.dataclass
class OpCost:
    """reference: CostMetrics {forward_time, backward_time, sync_time,
    memory} (simulator.h:54-79). Times in seconds, memory in bytes/chip."""

    forward_time: float = 0.0
    backward_time: float = 0.0
    sync_time: float = 0.0
    memory: int = 0

    @property
    def total(self) -> float:
        return self.forward_time + self.backward_time + self.sync_time


# ops whose FLOPs dominate (MXU ops); everything else is bandwidth-bound
_MXU_OPS = {
    OperatorType.LINEAR,
    OperatorType.CONV2D,
    OperatorType.BATCHMATMUL,
    OperatorType.MULTIHEAD_ATTENTION,
}

# ops worth timing for real in measured mode: the MXU set plus Embedding,
# whose backward materializes a dense table-sized gradient the roofline
# badly mis-prices (the dominant cost of DLRM-class models)
_MEASURED_OPS = _MXU_OPS | {OperatorType.EMBEDDING}

# op family for the cross-family residual correction (calibrate.py
# --fit-family): isolated-chain measurement over/under-counts what XLA
# fuses across op boundaries by a FAMILY-shaped factor (conv towers fuse
# BN/relu/residual epilogues the chain measurement only partially sees;
# dense stacks fuse less). The fitted full-step residual per family is
# persisted in the calibration table and divided out of measured costs.
_OP_FAMILY = {
    OperatorType.CONV2D: "conv",
    OperatorType.LINEAR: "dense",
    OperatorType.BATCHMATMUL: "dense",
    # attention gets its OWN family (round 5): the isolated chunked-scan
    # measurement over-reads the in-context cost ~1.5x while plain dense
    # stacks read ~0.9x — opposite biases one shared "dense" scale was
    # splitting the difference on (scripts/probe_attn_pricing.py:
    # attn-only 1.50, mlp-only 0.92, full flagship 1.43)
    OperatorType.MULTIHEAD_ATTENTION: "attention",
    OperatorType.EMBEDDING: "embed",
}


def op_family(op_type) -> Optional[str]:
    """Family key for the measured-mode residual correction; None for ops
    that never take the measured path."""
    return _OP_FAMILY.get(op_type)


def shard_batch(input_shapes) -> Optional[int]:
    """Leading (sample) dim piece size of the first input — the batch key
    for the per-regime family correction (family_scale_for)."""
    for s in input_shapes:
        for d in s.dims:
            if not d.is_replica_dim:
                return int(d.piece_size)
    return None


def update_calibration_doc(
    path: str, updates: dict, chip: str = "", replace=(), ops_keep=None
):
    """Read-merge-atomic-write of the calibration table — the ONE home for
    this logic (CostModel flushes, calibrate.py --tune-flash/--fit-family
    all write through here). Tolerates a missing/corrupt file; a doc
    measured on a DIFFERENT chip is dropped, not relabeled (its ops/
    family_scale/flash_blocks would silently mis-tune the new chip).
    Dict-valued updates shallow-merge into the existing value so partial
    writers (a one-family --fit-family run) don't wipe sibling entries;
    keys named in `replace` are OVERWRITTEN instead. `ops_keep` (a set of
    keys) filters the 'ops' table INSIDE the lock after merging —
    calibrate.py --prune drops stale shape-signature formats and
    abandoned configs without racing a concurrent writer's fresh keys (a
    snapshot taken outside the lock could overwrite them).

    Concurrent writers (two searches sharing one table) are serialized by
    an fcntl lock on `path + ".lock"` around the read-merge-write, so
    neither loses the other's freshly measured keys. Same-host only — the
    lock does not protect a table on NFS."""
    import json
    import os

    lock = None
    try:
        import fcntl

        lock = open(path + ".lock", "w")
        fcntl.flock(lock, fcntl.LOCK_EX)
    except (ImportError, OSError):
        if lock is not None:
            lock.close()  # opened but unlockable (some network mounts)
        lock = None  # non-POSIX: single-writer assumption applies

    try:
        return _update_calibration_doc_locked(
            path, updates, chip, replace, ops_keep
        )
    finally:
        if lock is not None:
            lock.close()


def _update_calibration_doc_locked(path, updates, chip, replace, ops_keep):
    import json
    import os

    doc = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            doc = {}
    if chip and doc.get("chip") not in (None, chip):
        # dropping a foreign-chip table is correct (its entries would
        # mis-tune this chip) but must not be silent or unrecoverable:
        # chip time went into it
        import warnings

        bak = f"{path}.foreign-{doc.get('chip')}.bak"
        try:
            with open(bak, "w") as f:
                json.dump(doc, f, indent=1)
        except OSError:
            bak = "<backup failed>"
        warnings.warn(
            f"calibration table {path} was measured on chip "
            f"{doc.get('chip')!r} but this write targets {chip!r}; "
            f"dropping the foreign table (saved to {bak})",
            stacklevel=2,
        )
        doc = {}
    doc["version"] = 1
    if chip:
        doc["chip"] = chip
    for key, val in updates.items():
        if (
            key not in replace
            and isinstance(val, dict)
            and isinstance(doc.get(key), dict)
        ):
            doc[key].update(val)
        else:
            doc[key] = val
    if ops_keep is not None:
        doc["ops"] = {
            k: v for k, v in doc.get("ops", {}).items() if k in ops_keep
        }
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
    os.replace(tmp, path)
    return doc

# collective latency floor per hop (ICI); dominates small messages
_ICI_LATENCY_S = 1e-6
_DEFAULT_EFFICIENCY = 0.6  # achievable fraction of peak (MXU and ICI alike)


class CostModel:
    def __init__(
        self,
        spec: MachineSpec,
        measure: bool = False,
        efficiency: float = _DEFAULT_EFFICIENCY,
        machine_model=None,
        mixed_precision: bool = False,
        calibration_file: str = "",
        sparse_embedding: bool = True,
        family_correction: bool = True,
    ):
        """machine_model: an optional search.machine_model.MachineModel
        (Enhanced / Networked); when given, collectives are costed as ring
        steps over its actual comm paths instead of the flat ICI formulas
        (reference: the simulator routes messages over
        MachineModel::get_comm_path, simulator.cc:810+).

        mixed_precision: cost f32 tensors at 2 bytes/element — under the
        executor's bf16 mode (FFConfig.allow_mixed_precision) activations
        and matmul operands live in bfloat16, so every HBM and wire term
        halves. Master weights stay f32 for the optimizer, but the grad
        all-reduce also rides bf16; the per-element approximation is
        uniform by design and documented here."""
        self.spec = spec
        self.measure = measure
        self.efficiency = efficiency
        self.machine_model = machine_model
        self.mixed_precision = mixed_precision
        # mirror of FFConfig.sparse_embedding_update: eligible tables'
        # optimizer traffic is touched-rows-sized (sparse_update_cost)
        self.sparse_embedding = sparse_embedding
        # measured-mode cache: stable string key -> (fwd_s, bwd_s) | None
        # (reference: hash_to_operator_cost, simulator.cc:532-572). When
        # calibration_file is set the table persists across processes, so
        # one real-chip calibration run serves every later search.
        self._measured: Dict[str, Optional[Tuple[float, float]]] = {}
        self.calibration_file = calibration_file
        # per-family full-step residual (predicted/measured) fitted by
        # `calibrate.py --fit-family`; measured op costs are divided by
        # their family's factor. family_correction=False is the fitting
        # path itself (residuals must be computed without the correction).
        self.family_correction = family_correction
        self._family_scale: Dict[str, float] = {}
        # measured seconds attributed per family across this instance's
        # lifetime (fwd+bwd, post-correction) — calibrate.py --fit-family
        # reads it to split a predicted step into family vs remainder
        self.family_time: Dict[str, float] = {}
        # per-program measurement overhead (dispatch_floor); None = not
        # yet resolved this instance. _loaded_floor holds the table's
        # persisted value; dispatch_floor() min-combines it with a fresh
        # probe (contention only inflates the probe)
        self._dispatch_floor: Optional[float] = None
        self._loaded_floor: Optional[float] = None
        if calibration_file:
            self._load_calibration()

    def elem_bytes(self, shape: ParallelTensorShape) -> int:
        """Bytes per element the executor will actually move for this
        tensor (the reference hardcodes sizeof(float) throughout its
        simulator; dtype-awareness is a deliberate improvement).

        Only f32 downcasts: the executor's mm_operands casts f32 matmul
        operands to bf16 and nothing else (ops/registry.py)."""
        if self.mixed_precision and shape.dtype == DataType.FLOAT:
            return 2
        return shape.dtype.size_bytes

    def piece_bytes(self, shape: ParallelTensorShape) -> float:
        """Per-shard bytes under this cost model's precision rules."""
        return shape.piece_volume() * self.elem_bytes(shape)

    # -- collectives --------------------------------------------------------

    def _ici_time(self, bytes_on_wire: float, hops: int = 1) -> float:
        bw = self.spec.ici_gbps * 1e9 * self.efficiency
        return bytes_on_wire / bw + hops * _ICI_LATENCY_S

    def _ring_step(
        self,
        bytes_per_step: float,
        group_size: int,
        chips: Optional[Sequence[int]] = None,
    ) -> float:
        """One ring step over the machine model's paths: ring neighbors
        exchange concurrently, so the step takes as long as the slowest
        pair. `chips` are the group's actual device ids — a cross-node or
        strided group rings over its real (possibly DCN) paths; without it
        the group is assumed contiguous at the machine origin."""
        mm = self.machine_model
        if chips is None:
            chips = range(min(group_size, mm.num_chips()))
        ids = [c % mm.num_chips() for c in chips]
        worst = 0.0
        for i, src in enumerate(ids):
            dst = ids[(i + 1) % len(ids)]
            worst = max(worst, mm.transfer_time(src, dst, bytes_per_step))
        return worst

    def all_reduce(
        self, bytes_per_chip: float, group_size: int, chips=None
    ) -> float:
        if group_size <= 1 or bytes_per_chip <= 0:
            return 0.0
        if self.machine_model is not None:
            return 2 * (group_size - 1) * self._ring_step(
                bytes_per_chip / group_size, group_size, chips
            )
        wire = 2.0 * (group_size - 1) / group_size * bytes_per_chip
        return self._ici_time(wire, hops=2 * (group_size - 1))

    def all_gather(
        self, bytes_per_chip: float, group_size: int, chips=None
    ) -> float:
        if group_size <= 1 or bytes_per_chip <= 0:
            return 0.0
        if self.machine_model is not None:
            return (group_size - 1) * self._ring_step(
                bytes_per_chip, group_size, chips
            )
        wire = (group_size - 1) / group_size * bytes_per_chip * group_size
        return self._ici_time(wire, hops=group_size - 1)

    def reduce_scatter(
        self, bytes_per_chip: float, group_size: int, chips=None
    ) -> float:
        if group_size <= 1 or bytes_per_chip <= 0:
            return 0.0
        if self.machine_model is not None:
            return (group_size - 1) * self._ring_step(
                bytes_per_chip / group_size, group_size, chips
            )
        wire = (group_size - 1) / group_size * bytes_per_chip
        return self._ici_time(wire, hops=group_size - 1)

    def all_to_all(
        self, bytes_per_chip: float, group_size: int, chips=None
    ) -> float:
        if group_size <= 1 or bytes_per_chip <= 0:
            return 0.0
        if self.machine_model is not None:
            return (group_size - 1) * self._ring_step(
                bytes_per_chip / group_size, group_size, chips
            )
        wire = (group_size - 1) / group_size * bytes_per_chip
        return self._ici_time(wire, hops=group_size - 1)

    def swap_cost(self, bytes_moved: float) -> float:
        """Seconds to stage `bytes_moved` across the chip<->host link —
        the price of KV swap-to-host (serving/scheduler.py weighs it
        against estimate_recompute_step when picking swap vs recompute
        for a preemption victim). Uses the machine model's PCIe comm
        device when one is attached (NetworkedMachineModel models the
        host link explicitly); otherwise the same defaults that device
        is built from: 32 GB/s x efficiency, 2 us setup latency."""
        if bytes_moved <= 0:
            return 0.0
        pcie = getattr(self.machine_model, "_pcie", None)
        if pcie is not None:
            return pcie.latency_s + bytes_moved / pcie.bandwidth_Bps
        bw = 32.0 * 1e9 * self.efficiency
        return 2e-6 + bytes_moved / bw

    # -- compute ------------------------------------------------------------

    def _roofline(
        self, flops: float, bytes_moved: float, efficiency: float = None
    ) -> float:
        """efficiency=1.0 gives the TRUE lower bound (the measurement
        clamp); the default self.efficiency gives the cost ESTIMATE."""
        eff = self.efficiency if efficiency is None else efficiency
        t_flops = flops / (self.spec.peak_tflops * 1e12 * eff)
        t_mem = bytes_moved / (self.spec.hbm_gbps * 1e9 * eff)
        return max(t_flops, t_mem)

    def op_cost(
        self,
        node,
        input_shapes: Sequence[ParallelTensorShape],
        skip_measure: bool = False,
    ) -> OpCost:
        """Cost of one op on ONE chip's shard, fwd + bwd.

        Shard sizing: global FLOPs / total_degree of the output — per-dim
        degrees multiply into how many ways the work is split. Parallel ops
        are costed by the simulator (they are communication, not compute).
        skip_measure: don't run the isolated kernel measurement (a caller
        already has a chain measurement for this node and only needs the
        analytic memory/roofline terms)."""
        out = node.output_shapes[0] if node.output_shapes else None
        if out is None:
            return OpCost()
        degree = max(1, out.total_degree)
        flops = op_flops(node.op_type, input_shapes, node.params) / degree

        _pb = self.piece_bytes
        bytes_moved = sum(_pb(s) for s in input_shapes)
        bytes_moved += sum(_pb(s) for s in node.output_shapes)
        bytes_moved += sum(_pb(s) for s in node.weight_shapes)
        mem = sum(_pb(s) for s in node.output_shapes)
        # read once an application, kept once: by the node that owns it
        mem += sum(_pb(s) for s in node.stored_weight_shapes)

        if self.measure and not skip_measure and node.op_type in _MEASURED_OPS:
            times = self.measured_times_floor_adjusted(
                node.op_type, node.params, input_shapes, node.weight_shapes
            )
            if times is not None:
                times = self.corrected_times(
                    node.op_type, times, batch=shard_batch(input_shapes)
                )
                return OpCost(times[0], times[1], 0.0, mem)

        fwd = self._roofline(flops, bytes_moved)
        # backward: dX and dW each cost about one forward for MXU ops;
        # elementwise backward re-reads the same bytes.
        bwd = 2.0 * fwd if node.op_type in _MXU_OPS else fwd

        # conv halo exchange under a partitioned spatial dim (attribute
        # parallelism): each shard trades (kernel-1)/2 boundary rows with
        # both neighbors per step — GSPMD's windowed-op halo — fwd and
        # again (twice) for the input/weight gradients. Without this term
        # spatial splits cost exactly compute/degree and the search is
        # biased toward them.
        if node.op_type == OperatorType.CONV2D and input_shapes:
            x0 = input_shapes[0]
            kh = int(node.params.get("kernel_h", 1))
            for i, d in enumerate(x0.dims):
                if d.is_replica_dim or d.degree <= 1 or i == 0:
                    continue
                if i == 1 and x0.ndim == 4 and kh > 1:  # H dim sharded
                    w_piece = x0.dims[2].piece_size
                    c = x0.dims[3].size
                    b_piece = x0.dims[0].piece_size
                    halo_bytes = (
                        2 * (kh // 2) * b_piece * w_piece * c
                        * self.elem_bytes(x0)
                    )
                    fwd += self._ici_time(halo_bytes)
                    bwd += 2.0 * self._ici_time(halo_bytes)

        # attention under a partitioned sequence dim — two lowerings
        # (ops/attention.py seq_parallel):
        #   ring    — each device passes its K/V block around the ring
        #             (sp-1) times fwd, ~2x bwd, each hop OVERLAPPED with
        #             the previous block's score compute -> max(comp, comm)
        #   ulysses — all-to-all the seq sharding onto heads before the
        #             core and back after: 3 input pieces + 1 output piece
        #             reshard fwd (mirrored bwd), BLOCKING -> added.
        # The runtime's seq_parallel="auto" takes the ring path, so "auto"
        # costs as ring; the search flips a node to "ulysses" only where
        # this model says the blocking reshard beats the ring (short seq /
        # many heads — comm-dominated) and heads divide sp.
        if (
            node.op_type == OperatorType.MULTIHEAD_ATTENTION
            and input_shapes
        ):
            x0 = input_shapes[0]
            seq_deg = 1
            for i, d in enumerate(x0.dims):
                if not d.is_replica_dim and i == 1 and d.degree > 1:
                    seq_deg = d.degree
            if seq_deg > 1:
                mode = node.params.get("seq_parallel", "auto")
                if mode == "ulysses":
                    x_piece = x0.piece_volume() * self.elem_bytes(x0)
                    a2a_fwd = self.all_to_all(4.0 * x_piece, seq_deg)
                    fwd += a2a_fwd
                    bwd += a2a_fwd  # cotangents reshard the same way
                else:
                    kv_piece = 2 * x0.piece_volume() * self.elem_bytes(x0)
                    ring = (seq_deg - 1) * self._ici_time(kv_piece)
                    fwd = max(fwd, ring)
                    bwd = max(bwd, 2.0 * ring)
        return OpCost(fwd, bwd, 0.0, mem)

    # -- decode (serving) cost family ---------------------------------------
    #
    # The autoregressive decode step the serving engine runs
    # (flexflow_tpu.serving.engine) lives in a different cost regime than
    # the training step this model was built for: one query token turns
    # every matmul into a [b, 1, k]·[k, n] GEMV whose time is the WEIGHT
    # bytes over HBM (re-read every generated token), and attention reads
    # the slot's KV cache instead of materializing an [s, s] score block.
    # That inversion is why the serving search (search/auto.py
    # optimize_serving) picks a different strategy than training: TP over
    # heads/columns divides the dominant weight-read term, while DP at
    # batch 1 leaves chips idle. This family prices exactly that regime;
    # it is analytic-only (the measured path times training shapes).

    def decode_op_cost(
        self,
        node,
        batch: int,
        kv_len: int,
        tp: int = 1,
        page_size: int = 0,
        kernel: str = "dense",
        kv_dtype: str = "fp32",
    ) -> OpCost:
        """Forward cost of ONE decode step of this op on one chip.

        batch: in-flight sequences this chip serves (the dp shard of the
        scheduler's active set); kv_len: cache positions attended (the
        working sequence length); tp: model-axis degree sharding this
        op's weights (heads for attention, columns for linear, rows for
        embedding) — callers pass 1 for ops the candidate leaves
        replicated. memory is the per-chip steady-state footprint the
        feasibility check needs: weights/tp plus this op's KV-cache
        block (serving holds no optimizer state).

        page_size > 0 prices the block-paged cache layout
        (serving/kv_cache.PagedKVCache): a sequence at kv_len positions
        holds (and the decode step streams) ceil(kv_len / page_size)
        whole pages, so the KV term rounds UP to page granularity — the
        per-sequence rounding waste paging pays for its pool-level
        packing win, which optimize_serving's max-in-flight estimate
        prices on the other side.

        kernel selects the attention core's memory-bound term: "pallas"
        prices the flash-decode kernel path (ops/pallas/decode_kernel
        .py) — the cache bytes are read ONCE at page granularity,
        straight from the pool through the block table; "dense" (the
        fallback) prices the jnp gather path on the paged layout, which
        materializes a contiguous per-step cache view first — one extra
        write plus one extra read of the gathered bytes on top of the
        pool read, so the dense paged KV term is 3x the kernel's. On
        the contiguous layout the two paths move the same bytes and the
        term is unchanged.

        kv_dtype "int8" (paged-only, serving/kv_cache quantized pools)
        prices cache rows at 1 byte each plus one fp32 dequant-scale
        read per touched (page, head) for K and V — the bandwidth win
        that pairs with the 4x capacity win estimate_max_in_flight
        prices on the footprint side."""
        tp = max(1, tp)
        elem = lambda s: self.elem_bytes(s)  # noqa: E731
        weight_bytes = sum(
            s.volume() * elem(s) for s in node.weight_shapes
        ) / tp
        out = node.output_shapes[0] if node.output_shapes else None
        feat = out.logical_sizes[-1] if out is not None else 1
        out_elem = elem(out) if out is not None else 4
        act_bytes = float(batch) * feat * out_elem / tp
        flops = 2.0 * batch * sum(s.volume() for s in node.weight_shapes) / tp
        mem = weight_bytes if node.stored_weight_shapes else 0.0
        bytes_moved = weight_bytes + act_bytes
        if node.op_type == OperatorType.MULTIHEAD_ATTENTION:
            heads = int(node.params["num_heads"]) // tp
            head_dim = int(node.params["embed_dim"]) // max(
                1, int(node.params["num_heads"])
            )
            kv_rows = kv_len
            if page_size > 0:
                kv_rows = -(-kv_len // page_size) * page_size
            cache_elem = 1 if kv_dtype == "int8" else out_elem
            cache_bytes = 2.0 * batch * kv_rows * heads * head_dim * cache_elem
            if kv_dtype == "int8" and page_size > 0:
                # one fp32 scale per touched (page, head), K and V each
                cache_bytes += (
                    2.0 * batch * (kv_rows // page_size) * heads * 4.0
                )
            mem += cache_bytes
            if page_size > 0 and kernel != "pallas":
                # dense fallback on the paged layout: gather the pages
                # into a contiguous view (write), then attend over it
                # (read) — on top of the pool read itself
                cache_bytes *= 3.0
            bytes_moved += cache_bytes
            flops += 4.0 * batch * kv_len * heads * head_dim
        elif node.op_type == OperatorType.EMBEDDING:
            # one row gather per sequence — the table is read sparsely,
            # not streamed; weights count toward memory, not bandwidth
            dim = int(node.params["out_dim"])
            bytes_moved = float(batch) * dim * out_elem + act_bytes
            flops = 0.0
        return OpCost(
            forward_time=self._roofline(flops, bytes_moved),
            backward_time=0.0,
            memory=int(mem),
        )

    def verify_op_cost(
        self,
        node,
        batch: int,
        kv_len: int,
        k: int,
        tp: int = 1,
        page_size: int = 0,
        kernel: str = "dense",
        kv_dtype: str = "fp32",
        tree_nodes: int = 0,
    ) -> OpCost:
        """Forward cost of ONE speculative-decoding verify step of this
        op on one chip: k+1 token positions per sequence (the last
        emitted token plus k drafted tokens) scored in a single call
        (serving/engine.GenerationEngine.verify). tree_nodes > 0 prices
        the token-TREE verify instead (engine.verify_tree): the row
        width becomes 1 + tree_nodes whatever k says — a tree node
        costs exactly what a chain draft position costs (one scored
        row, one fresh cache row); only the acceptance model differs,
        and that lives in optimize_spec_tree.

        The term structure is WHY speculative decoding wins: the weight
        bytes — the decode regime's dominant cost — stream ONCE for all
        k+1 positions, exactly as in decode_op_cost; only the
        activation traffic and FLOPs scale with k+1, and attention
        additionally reads the k fresh cache rows the drafts occupy
        (page-rounded like decode when page_size > 0). So
        verify(k) << (k+1) * decode, and the gap times the measured
        acceptance rate is the speedup optimize_spec_k prices.

        kernel as in decode_op_cost: "pallas" prices the flash-verify
        kernel's single page-granular cache read; "dense" adds the
        paged gather's extra write + read of the contiguous view.
        kv_dtype "int8" as in decode_op_cost: 1-byte cache rows plus
        per-(page, head) fp32 scale reads."""
        tp = max(1, tp)
        w = (1 + int(tree_nodes)) if tree_nodes > 0 else (int(k) + 1)
        elem = lambda s: self.elem_bytes(s)  # noqa: E731
        weight_bytes = sum(
            s.volume() * elem(s) for s in node.weight_shapes
        ) / tp
        out = node.output_shapes[0] if node.output_shapes else None
        feat = out.logical_sizes[-1] if out is not None else 1
        out_elem = elem(out) if out is not None else 4
        act_bytes = float(batch) * w * feat * out_elem / tp
        flops = (
            2.0 * batch * w * sum(s.volume() for s in node.weight_shapes) / tp
        )
        mem = weight_bytes if node.stored_weight_shapes else 0.0
        bytes_moved = weight_bytes + act_bytes
        if node.op_type == OperatorType.MULTIHEAD_ATTENTION:
            heads = int(node.params["num_heads"]) // tp
            head_dim = int(node.params["embed_dim"]) // max(
                1, int(node.params["num_heads"])
            )
            kv_rows = kv_len + w
            if page_size > 0:
                kv_rows = -(-kv_rows // page_size) * page_size
            cache_elem = 1 if kv_dtype == "int8" else out_elem
            cache_bytes = 2.0 * batch * kv_rows * heads * head_dim * cache_elem
            if kv_dtype == "int8" and page_size > 0:
                cache_bytes += (
                    2.0 * batch * (kv_rows // page_size) * heads * 4.0
                )
            mem += cache_bytes
            if page_size > 0 and kernel != "pallas":
                # dense gather tax, as in decode_op_cost
                cache_bytes *= 3.0
            bytes_moved += cache_bytes
            flops += 4.0 * batch * w * (kv_len + w) * heads * head_dim
        elif node.op_type == OperatorType.EMBEDDING:
            # w row gathers per sequence, like decode's one
            dim = int(node.params["out_dim"])
            bytes_moved = float(batch) * w * dim * out_elem + act_bytes
            flops = 0.0
        return OpCost(
            forward_time=self._roofline(flops, bytes_moved),
            backward_time=0.0,
            memory=int(mem),
        )

    def adapter_delta_cost(
        self,
        batch: int,
        hidden: int,
        rank: int,
        positions: int = 1,
        tp: int = 1,
    ) -> OpCost:
        """Forward cost of the per-step multi-LoRA epilogue on one chip
        (serving/tenancy/adapters.apply_adapter_qkv/_out): per in-flight
        sequence, gather that slot's rank-`rank` A/B pages from the
        paged adapter pool and add (x @ A) @ B to each of the four
        attention projections (q, k, v, out).

        The regime matches decode: the gathers are the cost. Each of
        the 4 projections reads rank rows of A ([hidden, rank]) and B
        ([rank, hidden]) per sequence — adapter pages are slot-gathered,
        not broadcast, so the bytes scale with batch, unlike the base
        weight stream decode_op_cost prices once. FLOPs are the two
        skinny matmuls, 2·b·w·hidden·rank each side. At typical ranks
        (8-64) this is single-digit percent of the base weight read,
        which is why the identity path (`adapter_id = -1`) costs only
        the predicated add it skips. memory is the live pool pages'
        steady-state footprint share attributable to these sequences."""
        tp = max(1, tp)
        b = max(0, int(batch))
        w = max(1, int(positions))
        h = max(1, int(hidden)) // tp
        r = max(1, int(rank))
        # A + B rows for q, k, v, out — gathered per sequence, fp32
        gather_bytes = 4.0 * b * (h * r + r * h) * 4.0
        act_bytes = 4.0 * b * w * (r + h) * 4.0
        flops = 4.0 * (2.0 * b * w * h * r + 2.0 * b * w * r * h)
        return OpCost(
            forward_time=self._roofline(flops, gather_bytes + act_bytes),
            backward_time=0.0,
            memory=int(gather_bytes),
        )

    def prefill_op_cost(
        self,
        node,
        batch: int,
        seq_len: int,
        tp: int = 1,
        page_size: int = 0,
        kernel: str = "dense",
        kv_dtype: str = "fp32",
    ) -> OpCost:
        """Forward cost of ONE prefill of `seq_len` token positions of
        this op on one chip, against an empty cache — a verify step with
        kv_len 0 and w = seq_len positions, which is exactly the shape
        the engine runs (verify IS a prefill-shaped call). Exists so
        preemption-by-recompute can be priced: a preempted sequence's
        recovery bill is one prefill over prompt + generated-so-far
        (search/auto.estimate_recompute_step), the number that decides
        whether optimistic admission's extra in-flight sequences pay for
        the recompute they occasionally trigger."""
        return self.verify_op_cost(
            node,
            batch,
            kv_len=0,
            k=max(0, int(seq_len) - 1),
            tp=tp,
            page_size=page_size,
            kernel=kernel,
            kv_dtype=kv_dtype,
        )

    def prefill_chunk_cost(
        self,
        node,
        batch: int,
        cursor: int,
        chunk: int,
        tp: int = 1,
        page_size: int = 0,
        kernel: str = "dense",
        kv_dtype: str = "fp32",
    ) -> OpCost:
        """Forward cost of ONE chunked-prefill step of this op on one
        chip: `chunk` prompt positions appended at cache cursor
        `cursor` (tokens already prefilled — the staircase mask's
        query_offset). This is exactly the verify shape the engine
        routes chunks through (a chunk is a wide verify with nothing to
        accept), so it prices as verify_op_cost with kv_len = cursor
        and w = chunk positions. The whole-prompt prefill is the
        cursor=0, chunk=seq_len special case (prefill_op_cost), and the
        SUM over a prompt's chunks exceeds the monolithic cost by one
        weight-stream per extra chunk — the price auto.
        optimize_token_budget weighs against the head-of-line latency
        the chunking removes."""
        return self.verify_op_cost(
            node,
            batch,
            kv_len=int(cursor),
            k=max(0, int(chunk) - 1),
            tp=tp,
            page_size=page_size,
            kernel=kernel,
            kv_dtype=kv_dtype,
        )

    # -- measured mode ------------------------------------------------------
    #
    # The direct analog of the reference's inner_measure_operator_cost
    # (model.cu:38-74, cached per (OperatorParameters, MachineView) in
    # simulator.cc:532-572), adapted to the two TPU realities the analytic
    # path cannot capture:
    #   * XLA fusion and MXU tiling make real op time diverge from the
    #     roofline in shape-dependent ways;
    #   * the ops being priced run for microseconds, the same order as
    #     one host dispatch and one device->host readback, so a host
    #     clock around a single call measures the host. Timing is by
    #     differencing: two chained runs of n1 and n2 dispatches, each
    #     ended by ONE scalar readback, subtracted so the per-run
    #     constants cancel. Each dispatch runs _MEASURE_CHAIN
    #     scan-chained kernel applications whose inputs are
    #     data-dependent on the previous iteration (a 1e-30-scaled scalar
    #     perturbation), so XLA cannot hoist the body out of the loop.

    _MEASURE_CHAIN = 8
    # differencing needs the timed work to dominate the host's per-call
    # jitter: grow the dispatch count until the differenced window
    # exceeds _MEASURE_MIN_DIFF_S (or the cap is hit for very large ops)
    _MEASURE_MIN_DIFF_S = 0.25
    _MEASURE_MAX_CALLS = 512

    def _shard_key(
        self, op_type, params: dict, in_shapes, weight_shapes
    ) -> str:
        """Stable (across processes — no salted hash()) cache key."""
        p = ",".join(f"{k}={params[k]!r}" for k in sorted(params))
        def fmt(shapes):
            return ";".join(
                "x".join(
                    str(d.piece_size)
                    for d in s.dims
                    if not d.is_replica_dim
                )
                + ":" + s.dtype.value
                for s in shapes
            )
        return (
            f"{op_type.name}|{p}|in={fmt(in_shapes)}|w={fmt(weight_shapes)}"
            f"|mp{int(self.mixed_precision)}"
        )

    def measure_shard(
        self, op_type, params: dict, in_shapes, weight_shapes
    ) -> Optional[Tuple[float, float]]:
        """(forward_s, backward_s) of the real jitted kernel on SHARD
        shapes (each shape's piece_sizes are what one chip sees). Returns
        None when the op cannot be measured (lowering error, odd params);
        callers fall back to the roofline. One-op case of
        measure_shard_chain (shared cache/persistence policy)."""
        return self.measure_shard_chain(
            [(op_type, params, in_shapes, weight_shapes, 0)]
        )

    def family_scale_for(self, fam: str, batch=None) -> float:
        """Fitted residual scale for a family, optionally at a shard
        batch size. A float entry is the constant (geomean) scale; a
        dict entry is the per-batch-REGIME table
        ({"8": s8, "16": s16, ..., "*": geomean}) fitted by
        calibrate.py --fit-family: the conv/attention residual is
        SHAPE-dependent (conv 1.01/1.63/0.82 across bs16/32/64,
        attention 1.46/1.00/1.04 across bs8/16/32 — reproduced across
        rounds 3-5), so a constant can only center the ladder; the
        regime table zeroes each measured point and nearest-bucket
        interpolates between (round-4 VERDICT weak #6 / ask #3)."""
        entry = self._family_scale.get(fam, 1.0)
        if isinstance(entry, dict):
            star = entry.get("*", 1.0)
            if batch is None:
                return float(star) or 1.0
            buckets = [
                (abs(int(k) - batch), float(v))
                for k, v in entry.items()
                if k != "*" and float(v) > 0
            ]
            if not buckets:
                return float(star) or 1.0
            return min(buckets)[1]
        return float(entry) or 1.0

    def dispatch_floor(self) -> float:
        """Per-program overhead baked into every isolated measurement
        (XLA launch + the measurement scan's per-iteration cost),
        measured once per table by timing a compute-free elementwise
        kernel. Sub-ms kernels read as floor + compute in isolation but
        cost only compute inside the real fused step — DLRM's 8 tiny
        MLP matmuls measured ~6x their in-step cost this way (round-4
        VERDICT weak #6 / ask #7). Persisted as "dispatch_floor_s"."""
        if self._dispatch_floor is not None:
            return self._dispatch_floor
        floor = 0.0
        try:
            shape = ParallelTensorShape.make([8, 8], DataType.FLOAT)
            t = self._time_kernel(OperatorType.RELU, {}, [shape], [])
            if t is not None:
                floor = t[0]
        except Exception:
            floor = 0.0
        # contention/slow-clock windows only ever INFLATE the probe, so
        # the min across windows is the honest constant (a 68 us
        # contended reading once priced a 26 us DLRM step at 72 us)
        if self._loaded_floor is not None and self._loaded_floor > 0:
            floor = (
                min(floor, self._loaded_floor)
                if floor > 0
                else self._loaded_floor
            )
        self._dispatch_floor = floor
        if (
            self.calibration_file
            and floor > 0
            and floor != self._loaded_floor  # skip the locked rewrite
        ):
            update_calibration_doc(
                self.calibration_file,
                {"dispatch_floor_s": floor},
                chip=self.spec.chip,
            )
        return floor

    def measured_times_floor_adjusted(
        self, op_type, params, in_shapes, weight_shapes
    ) -> Optional[Tuple[float, float]]:
        """measure_shard minus the dispatch floor, clamped below by the
        analytic roofline (the floor cannot push a time under physics).
        The cache/table keeps RAW measurements; the adjustment applies at
        read so a re-measured floor retroactively corrects old entries."""
        raw = self.measure_shard(op_type, params, in_shapes, weight_shapes)
        if raw is None:
            return None
        fl = self.dispatch_floor()
        if fl <= 0:
            return raw
        f_roof, b_roof = self._shard_roofline_bounds(
            op_type, params, in_shapes, weight_shapes
        )
        return (
            max(f_roof, raw[0] - fl),
            max(b_roof, raw[1] - fl),
        )

    def _shard_roofline_bounds(
        self, op_type, params, in_shapes, weight_shapes
    ) -> Tuple[float, float]:
        """(fwd, bwd) analytic lower bounds for ONE SHARD of the op — the
        clamp under the dispatch-floor subtraction. FLOPs divide by the
        op's output sharding degree (op_flops reads global dim sizes;
        measure_shard times piece shapes — op_cost's own analytic path
        makes the same division); byte terms already use piece sizes. A
        bound that is too LOW only weakens the clamp; one that mixes the
        global basis in would replace shard measurements with up-to-
        degree-times-larger rooflines and bias the search against
        sharded candidates."""
        from flexflow_tpu.ops.registry import infer_shapes

        degree = 1
        try:
            outs, _ = infer_shapes(op_type, list(in_shapes), dict(params))
            if outs:
                degree = max(1, outs[0].total_degree)
        except Exception:
            degree = 1
        flops = op_flops(op_type, in_shapes, params) / degree
        data = sum(self.piece_bytes(s) for s in in_shapes)
        data += sum(self.piece_bytes(s) for s in weight_shapes)
        # TRUE lower bound, not the 0.6-efficiency cost ESTIMATE: a real
        # kernel can beat the estimate (bf16 MXU at high utilization) and
        # a clamp above the measurement would silently replace it
        f_roof = self._roofline(flops, data, efficiency=1.0)
        return f_roof, (2.0 if op_type in _MXU_OPS else 1.0) * f_roof

    def chain_times_floor_adjusted(
        self, specs
    ) -> Optional[Tuple[float, float]]:
        """measure_shard_chain minus ONE dispatch floor (a chain is one
        program), clamped below by the chain's summed roofline."""
        raw = self.measure_shard_chain(specs)
        if raw is None:
            return None
        fl = self.dispatch_floor()
        if fl <= 0:
            return raw
        # conservative (deliberately LOW) fused-program bound: the HEAD
        # op's shard roofline only — the fused epilogue members' bytes
        # stay on-chip, so summing their isolated rooflines could exceed
        # the real fused time and the clamp would inflate the very
        # measurement the chain fix exists to trust
        o, p, ins, ws, _c = specs[0]
        f_roof, b_roof = self._shard_roofline_bounds(o, p, ins, ws)
        return (max(f_roof, raw[0] - fl), max(b_roof, raw[1] - fl))

    def corrected_times(
        self, op_type, times: Optional[Tuple[float, float]], batch=None
    ) -> Optional[Tuple[float, float]]:
        """Divide a measured (fwd, bwd) by the op's fitted family residual
        (constant or batch-regime, family_scale_for). Callers that bypass
        op_cost (the simulator's epilogue-chain measurement — the path
        the conv residual was fitted FOR) must route their raw
        measurements through here too, passing the shard batch when they
        know it."""
        if times is None:
            return times
        fam = op_family(op_type)
        scale = 1.0
        if self.family_correction and fam:
            scale = self.family_scale_for(fam, batch)
        times = (times[0] / scale, times[1] / scale)
        if fam:
            self.family_time[fam] = (
                self.family_time.get(fam, 0.0) + times[0] + times[1]
            )
        return times

    def flush_calibration(self):
        if self.calibration_file:
            self._save_calibration()

    def measure_shard_chain(self, specs) -> Optional[Tuple[float, float]]:
        """Measure a FUSED op chain as one jitted program — the epilogue
        pattern (conv→bn→relu, matmul→add→act) that XLA compiles into one
        kernel. Isolated-op timing structurally over-counts these
        (reference: inner_measure_operator_cost has the same bias,
        model.cu:38-74 — the round-2 ResNet 1.40 pred/meas residual);
        measuring the chain together is the fix.

        specs: [(op_type, params, in_shapes, weight_shapes, chained_idx)]
        where chained_idx says which input of spec i is fed by spec i-1's
        output (ignored for spec 0). Cached/persisted like single ops."""
        if len(specs) == 1:
            # single-op keys keep the historical format so existing
            # calibration tables (calibration/v5e.json) stay valid
            key = self._shard_key(*specs[0][:4])
        else:
            key = "=>".join(
                self._shard_key(o, p, i, w) + f"@{c}"
                for o, p, i, w, c in specs
            )
        if key in self._measured:
            return self._measured[key]
        # single ops go through _time_kernel (the test/monkeypatch seam)
        times = (
            self._time_kernel(*specs[0][:4])
            if len(specs) == 1
            else self._time_kernel_chain(specs)
        )
        self._measured[key] = times
        if self.calibration_file and times is not None:
            # persist immediately: a measurement costs >= _MEASURE_MIN_DIFF_S
            # so the full-file rewrite is noise, and the search engines
            # construct throwaway CostModels that never reach an explicit
            # flush_calibration() (only calibrate.py does) — a throttle
            # here silently dropped their last few measured keys
            self.flush_calibration()
        return times

    def _time_kernel(
        self, op_type, params, in_shapes, weight_shapes
    ) -> Optional[Tuple[float, float]]:
        return self._time_kernel_chain(
            [(op_type, params, in_shapes, weight_shapes, 0)]
        )

    def _time_kernel_chain(self, specs) -> Optional[Tuple[float, float]]:
        op_type = specs[0][0]  # head op classifies the bwd-ratio fallback
        try:
            import time as _time

            import jax
            import jax.numpy as jnp
            import numpy as np
            from jax import lax

            from flexflow_tpu.ops.registry import LowerCtx, lower_op

            lowered = [
                (lower_op(o, p), c) for o, p, _i, _w, c in specs
            ]
            ctx = LowerCtx(
                train=False, rng=None, bf16_matmul=self.mixed_precision
            )

            def arr(s):
                return jnp.full(
                    tuple(
                        d.piece_size
                        for d in s.dims
                        if not d.is_replica_dim
                    ),
                    0.01,
                    s.dtype.to_jnp(),
                )

            # spec 0 takes all its inputs; later specs only their EXTRA
            # inputs (the chained one comes from the previous op)
            ins = []
            ws = []
            for si, (_o, _p, in_shapes_i, w_shapes_i, cidx) in enumerate(
                specs
            ):
                if si == 0:
                    ins.append([arr(s) for s in in_shapes_i])
                else:
                    ins.append(
                        [
                            arr(s)
                            for i, s in enumerate(in_shapes_i)
                            if i != cidx
                        ]
                    )
                ws.append([arr(s) for s in w_shapes_i])

            def as_list(x):
                return list(x) if isinstance(x, (list, tuple)) else [x]

            def perturb_first(arrs, seed):
                # perturb the first float array by a vanishing function of
                # the previous iteration's result: forces true iteration
                # dependence without changing the math measurably
                out = list(arrs)
                for i, a in enumerate(out):
                    if jnp.issubdtype(a.dtype, jnp.floating):
                        out[i] = a * (1.0 + seed * 1e-30).astype(a.dtype)
                        return out, True
                return out, False

            def apply_op(inputs, weights, seed):
                pins, done = perturb_first(inputs[0], seed)
                pws0 = list(weights[0])
                if not done:
                    pws0, _ = perturb_first(weights[0], seed)
                out = None
                outs = []
                for si, (fn, cidx) in enumerate(lowered):
                    if si == 0:
                        ins_i, ws_i = pins, pws0
                    else:
                        ins_i = list(inputs[si])
                        ins_i.insert(cidx, out)
                        ws_i = list(weights[si])
                    outs = as_list(fn(ins_i, ws_i, ctx))
                    out = outs[0]
                tot = jnp.float32(0.0)
                for o in outs:  # the chain's FINAL outputs
                    tot = tot + jnp.sum(o.astype(jnp.float32))
                return tot

            k = self._MEASURE_CHAIN
            # differentiable leaves: the head's float inputs + all
            # weights (integer inputs — embedding ids — are closed over;
            # later specs' extra inputs likewise stay constants)
            fidx = [
                i
                for i, a in enumerate(ins[0])
                if jnp.issubdtype(a.dtype, jnp.floating)
            ]
            flat_ws = [w for per in ws for w in per]
            w_split = np.cumsum([len(per) for per in ws]).tolist()

            def unflatten_ws(flat):
                out, start = [], 0
                for end in w_split:
                    out.append(list(flat[start:end]))
                    start = end
                return out

            def fwd_chain(inputs, weights):
                def body(s, _):
                    return (
                        apply_op(inputs, unflatten_ws(weights), s) * 1e-30,
                        None,
                    )

                s, _ = lax.scan(
                    body, jnp.float32(0.0), None, length=k
                )
                return s

            def bwd_chain(inputs, weights):
                def body(s, _):
                    def loss(args):
                        flt, w2 = args
                        pins = [list(p) for p in inputs]
                        for j, i2 in enumerate(fidx):
                            pins[0][i2] = flt[j]
                        return apply_op(pins, unflatten_ws(list(w2)), s)

                    val, grads = jax.value_and_grad(loss)(
                        (
                            tuple(inputs[0][i] for i in fidx),
                            tuple(weights),
                        )
                    )
                    acc = val
                    for leaf in jax.tree_util.tree_leaves(grads):
                        acc = acc + jnp.sum(leaf.astype(jnp.float32))
                    return acc * 1e-30, None

                s, _ = lax.scan(
                    body, jnp.float32(0.0), None, length=k
                )
                return s

            def timed(jitted):
                out = jitted(ins, flat_ws)  # compile + warmup
                float(np.asarray(out))

                def run(n):
                    t0 = _time.perf_counter()
                    for _ in range(n):
                        out = jitted(ins, flat_ws)
                    float(np.asarray(out))  # forces the whole chain
                    return _time.perf_counter() - t0

                n = 2
                while True:
                    t1 = run(n)
                    t2 = run(2 * n)
                    diff = t2 - t1
                    if (
                        diff > self._MEASURE_MIN_DIFF_S
                        or n >= self._MEASURE_MAX_CALLS
                    ):
                        break
                    # jump straight to a count that should clear the bar
                    grow = self._MEASURE_MIN_DIFF_S / max(diff, 1e-4)
                    n = min(
                        max(2 * n, int(n * grow) + 1),
                        self._MEASURE_MAX_CALLS,
                    )
                per_iter = diff / (n * k)
                return max(per_iter, 1e-9)

            fwd = timed(jax.jit(fwd_chain))
            if fwd > 0.1:
                # no single-op/chain shard at search scale runs 100 ms
                # (the largest legit table entry is ~20 ms) — the window
                # caught a stall (the host descheduled, or the device
                # busy with someone else's work); don't poison the table.
                # A contended 119 ms conv+bn
                # entry once multiplied into a 2.1 s ResNet prediction
                # through shape-signature reuse.
                return None
            if fwd < 1e-7:
                # below the differencing noise floor: a negative or ~zero
                # window means the measurement failed — do not poison the
                # cache/table with it (roofline fallback instead)
                return None
            if not fidx and not flat_ws:
                return (fwd, fwd)  # nothing differentiable: estimate
            total = timed(jax.jit(bwd_chain))
            if total > 0.3:
                return None  # contended during the backward window
            bwd = total - fwd
            if bwd < 0.5 * fwd:
                # bwd can't be cheaper than re-running forward; a smaller
                # difference is noise — substitute the analytic ratio
                bwd = (2.0 if op_type in _MXU_OPS else 1.0) * fwd
            return (fwd, bwd)
        except Exception:
            import os

            if os.environ.get("FFTPU_MEASURE_DEBUG"):
                raise  # surface the real error instead of a None fallback
            return None

    # -- optimizer update ----------------------------------------------------

    def update_traffic_factor(self, state_factor: float = 3.0) -> float:
        """Bytes multiplier of one optimizer update: read w + read g +
        r/w each state slot + write w = 2·state_factor − 1. THE shared
        constant — unity.py and native/src/unity_dp.cc receive it from
        here so every engine prices updates identically."""
        return 2.0 * state_factor - 1.0

    def update_time_from_bytes(
        self, weight_bytes: float, state_factor: float = 3.0
    ) -> float:
        """THE optimizer-update HBM-time formula, shared by every engine
        (mesh estimator, unity Python DP; the native solver receives the
        factor and the same effective bandwidth). weight_bytes are
        MASTER-precision bytes — optimizer state and the update walk stay
        f32 under mixed precision."""
        traffic = self.update_traffic_factor(state_factor) * weight_bytes
        return traffic / (self.spec.hbm_gbps * 1e9 * self.efficiency)

    def update_cost(
        self, weight_shape: ParallelTensorShape, state_factor: float = 3.0
    ) -> float:
        """HBM time of one parameter's optimizer update (reference models
        update tasks in its task graph, simulator.cc:810+; the NCCL/PS sync
        is costed separately)."""
        return self.update_time_from_bytes(
            weight_shape.piece_bytes(), state_factor
        )

    def sparse_embedding_op_cost(
        self, weight_shape, rows_per_step: float
    ) -> Tuple[float, float]:
        """(fwd_s, bwd_s) of an embedding on the executor's sparse fast
        path: forward gathers the batch's rows, backward builds only the
        touched-row gradient (Executor._sparse_embedding_guids never
        materializes a table-sized gradient). The measured-mode kernel
        times the registry lowering's DENSE-gradient VJP instead — wrong
        by the table/batch ratio (a 4x1M-table DLRM mis-predicts ~500x on
        the measured basis), so sparse-eligible embeddings must take this
        analytic path even in measured mode."""
        dim = weight_shape.dims[-1].piece_size
        elem = self.elem_bytes(weight_shape)
        bytes_rw = rows_per_step * dim * elem
        t = bytes_rw / (self.spec.hbm_gbps * 1e9 * self.efficiency)
        # backward touches the same rows twice (zero-init + scatter-add)
        return (t, 2.0 * t)

    def sparse_sync_cost(
        self, row_bytes_per_chip: float, group_size: int, chips=None
    ) -> float:
        """Touched-row broadcast for a sparse-eligible table whose replicas
        span `group_size` chips while the ids/cotangents are batch-sharded
        across them: GSPMD lowers the scatter-update into an all-gather of
        the (ids, rows) pairs so every replica applies the full scatter
        (Executor.sparse_step's sparse_row_update under jit). Tiny next to
        the table-sized all-reduce the fast path eliminates, but real —
        without it, dp-replicated tables would look literally free to keep
        consistent (round-5 reconciliation of the bba35f9 sparse pricing)."""
        return self.all_gather(row_bytes_per_chip, group_size, chips=chips)

    def sparse_update_cost(
        self,
        weight_shape: ParallelTensorShape,
        rows_per_step: float,
        state_factor: float = 3.0,
    ) -> float:
        """Optimizer update of a sparse-eligible embedding table
        (Executor._sparse_embedding_guids): only the batch's touched rows
        move, so traffic is rows x dim, not vocab x dim — the term that
        makes the measured 587x DLRM update win visible to the search.
        Master-precision bytes, like update_cost."""
        dim = weight_shape.dims[-1].piece_size
        elem = weight_shape.dtype.size_bytes
        return self.update_time_from_bytes(
            rows_per_step * dim * elem, state_factor
        )

    # -- calibration-table persistence --------------------------------------

    def _load_calibration(self):
        import json
        import os
        import warnings

        if not os.path.exists(self.calibration_file):
            return
        try:
            with open(self.calibration_file) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            return
        table_chip = doc.get("chip")
        if table_chip and table_chip != self.spec.chip:
            warnings.warn(
                f"calibration table {self.calibration_file} was measured "
                f"on chip {table_chip!r} but this search targets "
                f"{self.spec.chip!r}; ignoring the table",
                stacklevel=2,
            )
            return
        for key, val in doc.get("ops", {}).items():
            if val:  # failed measurements (null) are never persisted/read
                self._measured[key] = tuple(val)
        fl = doc.get("dispatch_floor_s")
        if isinstance(fl, (int, float)) and fl >= 0:
            self._loaded_floor = float(fl)
        for fam, scale in doc.get("family_scale", {}).items():
            if isinstance(scale, (int, float)) and scale > 0:
                self._family_scale[fam] = float(scale)
            elif isinstance(scale, dict) and scale:
                # per-batch-regime table (family_scale_for)
                clean = {
                    str(k): float(v)
                    for k, v in scale.items()
                    if isinstance(v, (int, float)) and v > 0
                }
                if clean:
                    self._family_scale[fam] = clean

    def _save_calibration(self):
        # merged write (update_calibration_doc): other writers own sibling
        # keys (flash_blocks from --tune-flash, family_scale from
        # --fit-family) and a measured-search flush must not clobber them;
        # a foreign-chip doc is dropped rather than relabeled
        update_calibration_doc(
            self.calibration_file,
            {
                "ops": {
                    key: list(val)
                    for key, val in self._measured.items()
                    if val is not None
                }
            },
            chip=self.spec.chip,
        )
