"""The KV cache of the serving engine: block-paged pools.

`PagedKVCache` is the PagedAttention layout (Kwon et al., SOSP'23 /
vLLM): K/V live in `[num_pages, page_size, heads * head_dim]` *pools*,
a host-side free-page allocator hands pages to sequences on demand,
and a per-slot *block table* (`[max_seqs, max_pages_per_seq]` int32,
padded with the sentinel `num_pages`) maps logical cache positions to
pool pages. A short request holds only the pages its tokens fill, so
the same byte budget admits more concurrent short requests — the
serving-capacity lever continuous batching turns into throughput.

Admission supports two policies. The default *reserve* policy is
preemption-free: a request is admitted only when the free pool covers
its worst case (`ceil((prompt + max_new_tokens) / page_size)` pages)
on top of every in-flight request's outstanding worst case, so a
mid-flight decode can ALWAYS claim its next page — no preemption/swap
path needed. The opt-in *optimistic* policy (vLLM's posture) admits on
the pages a request needs NOW and reserves nothing for its growth;
when the pool later runs dry mid-decode, `ensure_position` raises
`PagePoolExhausted` and the scheduler preempts a victim — frees its
pages and requeues it for prefill-from-recompute
(serving/scheduler.py). Optimistic slots never contribute to the
reserve ledger, so the two policies compose: reserve-admitted slots
keep their guarantee even while optimistic slots gamble.

Prompt lengths are *bucketed*: prefill pads each
admission batch's prompts up to the next bucket (powers of two by
default), so the number of compiled prefill programs is bounded by the
bucket count, not by the number of distinct prompt lengths the traffic
happens to contain.

Sharding: the cache derives its spec from the compiled model's
ParallelTensor annotations — if the strategy shards attention heads (the
head-parallel replica-dim rewrite, ops/attention.py), the cache's heads
dim rides the same mesh axis, so TP-over-heads serving (the decode
search's batch-1 winner, search/auto.py optimize_serving) keeps each
chip's cache slice local.
"""

from __future__ import annotations

import dataclasses
import hashlib
import heapq
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from flexflow_tpu.core.types import OperatorType


class PagePoolExhausted(RuntimeError):
    """The free-page pool cannot supply a page a sequence needs NOW.

    Under the reserve admission policy this means the allocator invariant
    was violated (something outside the accounting drained the pool — a
    fault, not a workload); under the optimistic policy it is an expected
    runtime condition the scheduler answers with preemption-by-recompute.
    """


def int8_page_scale(first_row_amax):
    """The dequant scale an int8 page claims from the abs-max of its FIRST
    row (per head): twice that abs-max over 127. The later rows of the
    page are quantized under it, and a row's abs-max exceeds the first
    row's about every other time: at amax / 127 such a row clips (one
    lost 53% of its largest element, and a decode step's logits moved by
    19% of their range: tests/test_prefix_cache.py); with the factor of
    two a row up to twice the first's reach is kept whole, for one of the
    seven bits."""
    return first_row_amax * (2.0 / 127.0)


def default_buckets(max_len: int, smallest: int = 16) -> Tuple[int, ...]:
    """Powers of two from `smallest` up to (and including) max_len."""
    out = []
    b = smallest
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


def default_page_size(max_len: int, target: int = 16) -> int:
    """Largest power of two <= target that divides max_len (vLLM's
    default block size is 16; halve until the geometry is divisible)."""
    ps = target
    while ps > 1 and max_len % ps:
        ps //= 2
    return ps


@dataclasses.dataclass(frozen=True)
class KVCacheSpec:
    """Static geometry of the cache, derived from the compiled model:
    `num_pages` pool pages of `page_size` positions, and per layer
    `kv_pools` pools whose row, one position's, is `num_heads * head_dim`
    wide (`cache_row`: K and V of every head, or one latent row).
    `itemsize` is the cache dtype's element width in bytes (set from the
    actual dtype at cache construction, so bytes_per_layer/total_bytes
    price bf16 caches at 2 bytes, not a hardcoded 4)."""

    layer_guids: Tuple[int, ...]  # attention node guids, topo order
    max_seqs: int
    max_len: int
    num_heads: int
    head_dim: int
    buckets: Tuple[int, ...]
    page_size: int
    num_pages: int
    itemsize: int = 4
    kv_dtype: str = "fp32"  # "fp32" | "int8"
    kv_pools: int = 2  # K and V; 1 where both are read from the same rows
    # recurrent layers (`state_row`): what a SLOT keeps of each, beside
    # the pages: float32 arrays by name, the same for every such layer
    state_guids: Tuple[int, ...] = ()
    state_shapes: Tuple[Tuple[str, Tuple[int, ...]], ...] = ()

    def bucket(self, length: int) -> int:
        """Smallest bucket >= length (prefill pad target)."""
        for b in self.buckets:
            if b >= length:
                return b
        raise ValueError(
            f"prompt length {length} exceeds max_len {self.max_len}"
        )

    @property
    def max_pages_per_seq(self) -> int:
        return self.max_len // self.page_size

    @property
    def total_rows(self) -> int:
        """Cache positions the pools can hold (pool rows)."""
        return self.num_pages * self.page_size

    @property
    def row_width(self) -> int:
        """Elements of one pool row: one position of one layer."""
        return self.num_heads * self.head_dim

    @property
    def kv_bytes_per_token(self) -> int:
        """What one more cached position costs, over all layers."""
        return (
            self.kv_pools * self.itemsize * self.row_width
            * len(self.layer_guids)
        )

    @property
    def bytes_per_layer(self) -> int:
        base = self.kv_pools * self.itemsize * self.total_rows * self.row_width
        if self.kv_dtype == "int8":
            # fp32 dequant scales ride in a side pool, one per page per
            # head for each pool — they are part of the cache's HBM
            # bill even though the token pools shrink 4x
            base += self.kv_pools * 4 * self.num_pages * self.num_heads
        return base

    @property
    def state_bytes_per_slot(self) -> int:
        """What one more slot costs in per-slot state (float32), over all
        recurrent layers; nothing for a model without one."""
        return 4 * len(self.state_guids) * sum(
            math.prod(shape) for _, shape in self.state_shapes
        )

    @property
    def total_bytes(self) -> int:
        """Whole-cache footprint across layers, the per-slot state of
        every slot included — the number optimize_serving's capacity
        estimate divides the HBM budget by."""
        return (
            self.bytes_per_layer * len(self.layer_guids)
            + self.state_bytes_per_slot * self.max_seqs
        )


def _validate_page_geometry(max_seqs, max_len, page_size, num_pages):
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    if max_len % page_size:
        raise ValueError(
            f"max_len {max_len} is not divisible by page_size {page_size}"
        )
    if num_pages < max_len // page_size:
        raise ValueError(
            f"num_pages {num_pages} cannot hold even one max_len sequence "
            f"({max_len // page_size} pages of {page_size})"
        )


#: the operator types that keep rows in the cache
CACHED_ATTENTION = (
    OperatorType.MULTIHEAD_ATTENTION, OperatorType.LATENT_ATTENTION,
)


def cache_row(node) -> Tuple[int, int, int]:
    """(pools, heads, head_dim) of what an attention node keeps of each
    position: K and V rows of heads x head_dim, or for latent attention
    ONE row of one head (keys and values are both read from it), as wide
    as `ops.attention.mla_cache_row` pads it. The one place a pool row
    is sized: the cache, the engine's writes and `optimize_serving`'s
    capacity estimate all go through KVCacheSpec built from this."""
    if node.op_type == OperatorType.LATENT_ATTENTION:
        from flexflow_tpu.ops.attention import mla_cache_row

        return 1, 1, mla_cache_row(node.params)
    heads = int(node.params["num_heads"])
    return 2, heads, int(node.params["embed_dim"]) // heads


#: the operator types that keep a fixed-size state a SLOT, not rows a token
RECURRENT = (OperatorType.LINEAR_ATTENTION,)


def state_row(node) -> Tuple[Tuple[str, Tuple[int, ...]], ...]:
    """((name, shape), ...) of the float32 arrays a recurrent node keeps
    of each sequence between steps. The one place the per-slot state is
    sized: the cache, the engine's writes and `optimize_serving`'s
    capacity estimate all go through KVCacheSpec built from this."""
    from flexflow_tpu.ops.linear_attention import kda_state_shapes

    return kda_state_shapes(node.params)


def derive_state(graph, order) -> Tuple[Tuple[int, ...], Tuple]:
    """(state_guids, state_shapes) of a graph's recurrent nodes, in
    `order`; ((), ()) without one. Every such node must agree on
    `state_row`, as attention nodes must on `cache_row`."""
    guids = tuple(g for g in order if graph.nodes[g].op_type in RECURRENT)
    rows = {state_row(graph.nodes[g]) for g in guids}
    if len(rows) > 1:
        raise ValueError(f"recurrent layers disagree on their state: {rows}")
    return guids, (rows.pop() if rows else ())


def _derive_geometry(model):
    """(layer_guids, heads, head_dim, head_axis, executor) from a
    compiled FFModel. Every attention node must agree on `cache_row` —
    one cache block size per model, like the
    reference serve stack. The sharding comes from the Wq weight's head
    dim: if the chosen strategy partitioned heads (parallel_idx -> mesh
    axis), the cache heads dim shards on that axis; otherwise the cache
    is replicated (a latent row has one head: always)."""
    if model.executor is None:
        raise RuntimeError("compile() the model before building its KV cache")
    graph = model.graph
    executor = model.executor
    guids = [
        g
        for g in executor.topo
        if graph.nodes[g].op_type in CACHED_ATTENTION
    ]
    if not guids:
        raise ValueError("model has no attention layers to cache")
    geom = set()
    head_axis = None
    for g in guids:
        node = graph.nodes[g]
        geom.add(cache_row(node))
        wq = node.weight_shapes[0] if node.weight_shapes else None
        if wq is not None and len(wq.dims) == 3:
            hd = wq.dims[1]
            if hd.degree > 1 and 0 <= hd.parallel_idx < len(
                executor.mesh_config.axis_names
            ):
                head_axis = executor.mesh_config.axis_names[hd.parallel_idx]
    if len(geom) != 1:
        raise ValueError(
            f"attention layers disagree on (pools, heads, head_dim): {geom}"
        )
    _, heads, head_dim = geom.pop()
    return guids, heads, head_dim, head_axis, executor


def _heads_sharding(executor, head_axis):
    """NamedSharding placing a pool's dim 2 (the folded heads *
    head_dim) on the strategy's head axis.

    Always place the cache on the mesh (replicated when heads are not
    sharded): uncommitted fresh zeros would give the first engine step a
    different jit signature than every later step (committed jit
    outputs) and buy a pointless recompile."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(executor.mesh, PartitionSpec(None, None, head_axis))


STATE_WHY = (
    "a slot's recurrent state is the whole of its history in one "
    "fixed-size row, written by prefill and decode only; it cannot be "
    "rolled back to an earlier position, shared between slots or resumed "
    "from pages"
)


class PagedKVCache:
    """Block-paged pools + host-side page allocator and block tables.

    Device state: one `[num_pages, page_size, heads * head_dim]` K and V
    pool per layer, or for latent attention ONE pool of latent rows
    (`spec.kv_pools`, `cache_row`) (functional: each step program returns
    the pools it was handed, rewritten, and `commit` stores them). The
    heads and their dims are folded into one, heads-major, because a
    device array's layout follows from its shape: a TPU keeps an array
    whose last dim is under 128 lanes (a head_dim of 64) pages-minor,
    not row-major, and every step program then converted each pool to
    row-major and back (two whole-pool copies a step; PERF.md, PR 27).
    This shape is row-major there, unpadded, and it is the view the
    paged kernel reads; a row is one position's K (V) of every head.
    Host state: the free-page stack, per-slot block tables (sentinel =
    `num_pages`, an out-of-bounds page id — OOB scatters drop and OOB
    gathers are masked by lengths, so sentinel entries are inert on
    device), per-slot lengths, and the reserve ledger that keeps
    admission preemption-free.

    Prefix sharing (`prefix_cache=True`): full pages whose token content
    (a chained blake2b over per-page tokens) matches a page a previous
    request registered are MAPPED into a new request's block table
    instead of recomputed — per-page refcounts track the aliasing, the
    sharer's table entries are flagged shared, and the first divergent
    write copies the page (copy-on-write inside `ensure_position`).
    Pages leave the pool only when their refcount hits zero, at which
    point their hash-index entry is invalidated too.

    int8 quantization (`spec.kv_dtype == "int8"`): the token pools hold
    int8 with one fp32 dequant scale per page per head in side pools
    (`k_scale`/`v_scale`, `[num_pages, num_heads]`). The FIRST write
    into a page fixes its scale (engine-side scatter-max, from the
    page's first row: `int8_page_scale`); later rows reuse it (values
    beyond ±127·scale clip — the documented tolerance), so a page's
    bytes depend only on its token content and
    prefix-shared pages stay bit-identical across requests.
    """

    def __init__(
        self,
        spec: KVCacheSpec,
        dtype,
        shardings=None,
        prefix_cache=False,
        placement=None,
        prefix_evict: str = "none",
        swap_bytes_budget: int = 0,
        evict_pricer=None,
    ):
        import jax
        import jax.numpy as jnp

        if prefix_evict not in ("none", "lru", "cost"):
            raise ValueError(
                f"prefix_evict must be 'none', 'lru', or 'cost', "
                f"got {prefix_evict!r}"
            )
        _validate_page_geometry(
            spec.max_seqs, spec.max_len, spec.page_size, spec.num_pages
        )
        self.quantized = spec.kv_dtype == "int8"
        if self.quantized:
            dtype = jnp.int8
        self.spec = dataclasses.replace(
            spec, itemsize=jnp.dtype(dtype).itemsize
        )
        spec = self.spec
        self.dtype = dtype
        self.prefix_cache = bool(prefix_cache)
        shape = (spec.num_pages, spec.page_size, spec.num_heads * spec.head_dim)
        self.k: Dict[int, object] = {}
        self.v: Dict[int, object] = {}
        # int8 side pools: fp32 scale per (page, head); scale == 0 marks
        # a page whose first write has not landed yet (engine scatter-max
        # claims it). Empty dicts under fp32 so the engine threads one
        # pytree shape through the jitted steps either way.
        self.k_scale: Dict[int, object] = {}
        self.v_scale: Dict[int, object] = {}
        scale_shardings = None
        if shardings is not None and self.quantized:
            from jax.sharding import NamedSharding, PartitionSpec

            # pools shard pages on dim 0 and heads on dim 2; the
            # [num_pages, heads] scale pools carry the same axes
            scale_shardings = NamedSharding(
                shardings.mesh,
                PartitionSpec(shardings.spec[0], shardings.spec[2]),
            )
        from flexflow_tpu.runtime import multihost

        def fresh(shape, dtype, sharding):
            pool = jnp.zeros(shape, dtype)
            return pool if sharding is None else multihost.place_array(pool, sharding)

        # `kv_pools == 1` (latent rows: keys and values are the same
        # rows): everything lives in `k`, and `v` / `v_scale` stay empty,
        # which every loop over them below takes as nothing to do
        for g in spec.layer_guids:
            self.k[g] = fresh(shape, dtype, shardings)
            if spec.kv_pools == 2:
                self.v[g] = fresh(shape, dtype, shardings)
            if self.quantized:
                scales = (spec.num_pages, spec.num_heads)
                self.k_scale[g] = fresh(scales, jnp.float32, scale_shardings)
                if spec.kv_pools == 2:
                    self.v_scale[g] = fresh(scales, jnp.float32, scale_shardings)
        # per-slot state of the recurrent layers, beside the pools and
        # committed with them: {layer: {name: [max_seqs, *shape]}} float32,
        # indexed by SLOT. A prefill writes an admitted slot's whole row
        # from the zero state and a decode step the rows of its active
        # slots, so `free` has no device work and a recompute rebuilds
        # it; what would have to roll a recurrence back or carry it
        # elsewhere (`_refuse_with_state`) is refused. Empty without such
        # a layer, which adds no argument to a step program.
        self.state: Dict[int, Dict[str, object]] = {}
        if spec.state_guids:
            whole = None
            if shardings is not None:
                from jax.sharding import NamedSharding, PartitionSpec

                whole = NamedSharding(shardings.mesh, PartitionSpec())
            for g in spec.state_guids:
                self.state[g] = {
                    name: fresh((spec.max_seqs,) + shape, jnp.float32, whole)
                    for name, shape in spec.state_shapes
                }
            if self.quantized:
                self._refuse_with_state("kv_dtype='int8'")
            if self.prefix_cache:
                self._refuse_with_state("prefix_cache")
        self.lengths = np.zeros(spec.max_seqs, dtype=np.int32)
        self.block_tables = np.full(
            (spec.max_seqs, spec.max_pages_per_seq),
            spec.num_pages,
            dtype=np.int32,
        )
        # HOST partition (serving/distributed.py): host h owns the
        # contiguous slot block [h*spn, (h+1)*spn) and page block
        # [h*ppn, (h+1)*ppn) — coinciding with the device shard
        # boundaries of pool dim 0 on the serving mesh's data axis, so a
        # slot's pages live with its host's devices. Admission and page
        # claims run against PER-HOST free views; num_hosts == 1
        # degenerates to the single global heap (byte-identical pop
        # order to the pre-placement allocator).
        self.placement = placement
        self.num_hosts = placement.num_hosts if placement is not None else 1
        if spec.max_seqs % self.num_hosts or spec.num_pages % self.num_hosts:
            raise ValueError(
                f"host partition: max_seqs {spec.max_seqs} and num_pages "
                f"{spec.num_pages} must both divide by num_hosts "
                f"{self.num_hosts}"
            )
        self._slots_per_host = spec.max_seqs // self.num_hosts
        self._pages_per_host = spec.num_pages // self.num_hosts
        # min-heaps: alloc pops the lowest free slot/page id (deterministic
        # reuse order), release is O(log n) heappush instead of the old
        # append + full sort. One heap pair PER HOST; `_free_slots` /
        # `_free_pages` stay bound to host 0's heaps (the SAME list
        # objects, mutated in place, never rebound) so the single-host
        # fault injector and tests keep their direct handle on the pool.
        self._free_slots_h: List[List[int]] = [
            list(
                range(h * self._slots_per_host, (h + 1) * self._slots_per_host)
            )
            for h in range(self.num_hosts)
        ]
        self._free_pages_h: List[List[int]] = [
            list(
                range(h * self._pages_per_host, (h + 1) * self._pages_per_host)
            )
            for h in range(self.num_hosts)
        ]
        self._free_slots: List[int] = self._free_slots_h[0]
        self._active: set = set()
        self._free_pages: List[int] = self._free_pages_h[0]
        # preemption-free reserve: _max_pages[s] is slot s's worst-case
        # page need (fixed at admission), _held[s] what it holds now;
        # _reserved = Σ (max - held) over active RESERVE-admitted slots —
        # pages the free list must keep back for in-flight growth.
        # Optimistic slots (admitted beyond the reserve; preempted on
        # pool exhaustion) keep _max_pages pinned to _held and never
        # touch _reserved.
        self._held = np.zeros(spec.max_seqs, dtype=np.int64)
        self._max_pages = np.zeros(spec.max_seqs, dtype=np.int64)
        self._reserved_h: List[int] = [0] * self.num_hosts
        self._optimistic: set = set()
        # prefix sharing: per-page reference counts (re-derivable from
        # the block tables — check_invariants does exactly that), the
        # per-entry shared flag (True = this mapping aliases a page some
        # other request wrote; first write through it must COW), the
        # per-slot shared-mapping count, and the content-hash index
        # (chained page key -> page id, with its exact inverse).
        # "Owned" pages (_held - _shared) are what the reserve ledger
        # prices: a shared mapping costs the pool nothing until it COWs.
        self._refcounts = np.zeros(spec.num_pages, dtype=np.int32)
        self._entry_shared = np.zeros(
            (spec.max_seqs, spec.max_pages_per_seq), dtype=bool
        )
        self._shared = np.zeros(spec.max_seqs, dtype=np.int64)
        self._prefix_index: Dict[bytes, int] = {}
        self._page_keys: Dict[int, bytes] = {}
        self.prefix_hits = 0  # admissions that mapped >= 1 shared page
        self.cow_copies = 0  # divergent writes that copied a page
        # published-prefix eviction (prefix_evict="lru"): a published
        # page whose LAST table reference drops is RETAINED — refcount 0,
        # off the free heap, still advertised by the hash index — in
        # `_pub_only` (page -> (LRU stamp, wait-for window id)) instead
        # of released. Under pool pressure the least-recently-published
        # page is unpublished and returned to the free heap BEFORE any
        # live request is swapped or preempted; a new admission matching
        # it resurrects the mapping (refcount 0 -> 1) at zero pool cost.
        # The wait-window tag mirrors limbo's discipline: an in-flight
        # step dispatched before the release may still WRITE the page's
        # pool rows, so eviction (which hands the page to a new writer)
        # waits for that window to close; read-only resurrection is
        # always safe and is not gated.
        # prefix_evict="cost" replaces the LRU victim choice with the
        # page CHEAPEST to recompute: a published page covering tokens
        # [c, c+page_size) of its chain re-prefills as one chunk at
        # cursor c (CostModel.prefill_chunk_cost), and that cost grows
        # with c — so the cost policy reclaims shallow chain pages first
        # and keeps the deep (expensive) tails warm. `evict_pricer`
        # is the (cursor, chunk) -> seconds callable api.build_scheduler
        # wires from the compiled model's cost model; None degrades to
        # the cursor itself (the same monotone order, unpriced).
        # `_page_spans` records each published page's chain-start cursor
        # at registration time — pages only store hash keys otherwise.
        self.prefix_evict = prefix_evict
        self.evict_pricer = evict_pricer
        self._page_spans: Dict[int, int] = {}
        self._pub_only: Dict[int, Tuple[int, int]] = {}
        self._evict_tick = 0
        self.prefix_evictions = 0
        # KV swap-to-host (vLLM's swap alternative to recompute): a
        # victim's committed pages are device-gathered into host numpy
        # buffers keyed by a monotonic handle; re-admission scatters
        # them into freshly claimed pages — no re-prefill. The bytes
        # ledger enforces `swap_bytes_budget` (0 = unlimited) across
        # every outstanding handle.
        self.swap_bytes_budget = int(swap_bytes_budget)
        self._swapped: Dict[int, Dict[str, object]] = {}
        self._swap_seq = 0
        self._swap_bytes_held = 0
        self.swap_outs = 0
        self.swap_ins = 0
        self.swap_bytes_total = 0
        # host-failure drain: partitions marked lost refuse admission
        # (_pick_host / alloc_shared skip them) until marked up again
        self._hosts_down: set = set()
        # in-flight window (async dispatch): while a dispatched step's
        # deferred device reads may still reference the block tables it
        # was handed, pages released by free/truncate go to _limbo
        # instead of the free heap — handing them to a new sequence
        # would let its prefill race the in-flight step's stale write.
        # Windows open at dispatch and close at reconcile IN ORDER, and
        # the steady-state pipeline (dispatch N+1, then reconcile N)
        # keeps one window open at all times — so limbo entries are
        # tagged with the NEWEST window open at release time and drain
        # as soon as that window closes, not when the (never-idle)
        # depth hits zero.
        self._window_seq = 0  # id of the most recently opened window
        self._window_closed = 0  # window ids <= this have reconciled
        self._limbo: List[Tuple[int, int]] = []  # (page, wait-for window id)

    # -- in-flight window (async dispatch) -----------------------------------

    @property
    def _inflight_depth(self) -> int:
        return self._window_seq - self._window_closed

    def begin_inflight(self) -> None:
        """Open an in-flight window: a dispatched-but-not-reconciled
        step holds a snapshot of the block tables, so any page released
        while the window is open is PINNED (moved to the limbo list,
        not the free heap) until every step dispatched before the
        release has reconciled — optimistic preemption or an EOS retire
        during the window cannot hand an in-flight page to a new
        sequence."""
        self._window_seq += 1

    def end_inflight(self) -> None:
        """Close the oldest open window (steps reconcile in dispatch
        order); limbo pages waiting only on it return to the free
        heap."""
        if self._window_closed >= self._window_seq:
            raise RuntimeError("end_inflight without a matching begin_inflight")
        self._window_closed += 1
        if self._limbo:
            kept: List[Tuple[int, int]] = []
            for p, wid in self._limbo:
                if wid <= self._window_closed:
                    heapq.heappush(self._free_pages_h[self._page_home(p)], p)
                else:
                    kept.append((p, wid))
            self._limbo = kept

    @property
    def pinned_pages(self) -> int:
        """Pages released during an open in-flight window, unavailable
        until the steps that could reference them reconcile (the async
        scheduler drains the pipeline when a claim needs them back)."""
        return len(self._limbo)

    def _release_page(self, p: int) -> None:
        if self._window_seq > self._window_closed:
            self._limbo.append((p, self._window_seq))
        else:
            heapq.heappush(self._free_pages_h[self._page_home(p)], p)

    # -- host partition ------------------------------------------------------

    @property
    def _reserved(self) -> int:
        """Total growth reserve across host partitions (read-only view;
        writes go to the owning host's `_reserved_h` entry)."""
        return sum(self._reserved_h)

    def host_of_slot(self, slot: int) -> int:
        """Which host partition owns `slot` (contiguous blocks)."""
        return int(slot) // self._slots_per_host

    def _page_home(self, p: int) -> int:
        """Which host partition owns page `p` (contiguous blocks,
        aligned with the data-axis device shards of pool dim 0)."""
        return int(p) // self._pages_per_host

    def _host_avail(self, h: int) -> int:
        """Free pages plus evictable publication-only pages minus the
        growth reserve on host `h` — the admission headroom. A
        ONE-STEP-STALE view is safe by design: pages released during an
        open in-flight window sit in limbo (not the free heap), so this
        count only under-promises; it never hands out a page an
        in-flight step could still read. Counting evictable pages here
        is what makes prefix eviction happen BEFORE any live request is
        swapped or preempted: admission and page claims see the
        headroom, and `_pop_free_page` evicts lazily when the heap runs
        dry."""
        return (
            len(self._free_pages_h[h])
            + self._evictable_count(h)
            - self._reserved_h[h]
        )

    def mark_host_down(self, h: int) -> None:
        """Mark host partition `h` lost: `_pick_host` and `alloc_shared`
        refuse it until `mark_host_up`. The partition's ledgers stay
        intact (its pool content is gone with its devices, but the
        accounting still re-derives) — the scheduler drains its RUNNING
        requests to surviving hosts."""
        if not 0 <= h < self.num_hosts:
            raise ValueError(f"host {h} outside [0, {self.num_hosts})")
        self._hosts_down.add(h)

    def mark_host_up(self, h: int) -> None:
        """Re-join a recovered host partition into admission."""
        self._hosts_down.discard(h)

    @property
    def hosts_down(self) -> frozenset:
        return frozenset(self._hosts_down)

    def _pick_host(self, need: int) -> Optional[int]:
        """Choose the admission host: any alive host with a free slot
        whose free view covers `need` pages; most headroom wins, ties to
        the lowest host id (deterministic). None when no host can
        admit."""
        best = None
        best_avail = -1
        for h in range(self.num_hosts):
            if h in self._hosts_down or not self._free_slots_h[h]:
                continue
            avail = self._host_avail(h)
            if avail >= need and avail > best_avail:
                best, best_avail = h, avail
        return best

    def free_pages_by_host(self) -> List[int]:
        """Per-host free-heap depths (telemetry / scheduler views)."""
        return [len(hp) for hp in self._free_pages_h]

    # -- page/slot management (host side) ------------------------------------

    @property
    def num_active(self) -> int:
        return len(self._active)

    @property
    def num_free(self) -> int:
        return sum(len(hs) for hs in self._free_slots_h)

    @property
    def num_free_pages(self) -> int:
        return sum(len(hp) for hp in self._free_pages_h)

    @property
    def pages_in_use(self) -> int:
        return self.spec.num_pages - self.num_free_pages

    def active_slots(self) -> List[int]:
        return sorted(self._active)

    def _pages_for(self, tokens: int) -> int:
        return -(-int(tokens) // self.spec.page_size)

    def can_admit(
        self,
        prompt_len: int = 1,
        total_len: int = 0,
        optimistic: bool = False,
    ) -> bool:
        """True when SOME host partition has a free slot AND its free
        view covers this request's page need on top of every in-flight
        reservation: the worst case (prompt + max_new_tokens) under the
        reserve policy, only the pages the prompt fills NOW under the
        optimistic one. Admission is per-host (a request's pages never
        straddle hosts), so a fragmented pod can refuse a request the
        global count would accept."""
        if optimistic:
            need = self._pages_for(prompt_len)
        else:
            need = self._pages_for(max(prompt_len, total_len))
        return self._pick_host(need) is not None

    def alloc(
        self,
        prompt_len: Optional[int] = None,
        total_len: Optional[int] = None,
        optimistic: bool = False,
        slot: Optional[int] = None,
    ) -> Optional[int]:
        """Admit a sequence: take a slot, allocate the pages its prompt
        fills now, and — under the default reserve policy — reserve
        (without allocating) the rest of its worst case. None when the
        policy refuses. `optimistic=True` reserves nothing beyond the
        prompt's pages (the slot may later hit PagePoolExhausted and be
        preempted). Omitted lengths reserve-and-fill a full max_len
        (ad-hoc engine callers). `slot` names the slot to take, on its
        own host partition, instead of the lowest free one: the draft
        model's cache follows the target's slots this way
        (serving/spec.py ModelDraftProposer); a slot that is active or
        out of range raises."""
        spec = self.spec
        if prompt_len is None:
            prompt_len = spec.max_len
        total = max(prompt_len, total_len if total_len is not None else 0)
        if total > spec.max_len:
            raise ValueError(
                f"sequence of {total} tokens exceeds max_len {spec.max_len}"
            )
        need_now = self._pages_for(prompt_len)
        max_p = self._pages_for(total)
        need = need_now if optimistic else max_p
        if slot is None:
            h = self._pick_host(need)
            if h is None:
                return None
            slot = heapq.heappop(self._free_slots_h[h])
        else:
            if not 0 <= slot < spec.max_seqs or slot in self._active:
                raise ValueError(f"slot {slot} is not a free slot")
            h = self.host_of_slot(slot)
            if h in self._hosts_down or self._host_avail(h) < need:
                return None
            self._free_slots_h[h].remove(slot)
            heapq.heapify(self._free_slots_h[h])
        self._active.add(slot)
        for i in range(need_now):
            self._install_page(slot, i, self._pop_free_page(h))
        self._held[slot] = need_now
        if optimistic:
            # no growth reserve: _max_pages tracks _held so this slot
            # contributes zero to the reserve ledger, now and forever
            self._optimistic.add(slot)
            self._max_pages[slot] = need_now
        else:
            self._max_pages[slot] = max_p
            self._reserved_h[h] += max_p - need_now
        self.lengths[slot] = 0
        return slot

    # -- prefix sharing (hashed page cache + copy-on-write) ------------------

    def _owned(self, slot: int) -> int:
        """Pages this slot holds that came from the free pool (its
        shared mappings alias pages other requests own)."""
        return int(self._held[slot]) - int(self._shared[slot])

    def _install_page(self, slot: int, pi: int, page: int) -> None:
        """Map a freshly popped page into a table entry (refcount 1)."""
        self.block_tables[slot, pi] = page
        self._refcounts[page] = 1

    def _incref(self, slot: int, pi: int, page: int) -> None:
        """Map an already-live (or publication-only retained) page as a
        SHARED entry of `slot`. Resurrecting a retained page (refcount
        0 -> 1) removes it from the eviction candidates — it is live
        again and its sharers protect it."""
        self.block_tables[slot, pi] = page
        self._refcounts[page] += 1
        self._entry_shared[slot, pi] = True
        self._shared[slot] += 1
        if page in self._pub_only:
            del self._pub_only[page]

    def _decref_page(self, page: int) -> None:
        """Drop one reference. Under prefix_evict="lru" a PUBLISHED
        page whose last reference drops is retained as an eviction
        candidate (still matchable, resurrectable at zero pool cost)
        instead of released — closing the "last owner unpublishes" gap:
        publication alone now keeps a page warm until pool pressure
        actually needs it back. Otherwise the last owner unpublishes
        the page and releases it (through the in-flight limbo when a
        dispatched step may still read it)."""
        self._refcounts[page] -= 1
        assert self._refcounts[page] >= 0
        if self._refcounts[page] == 0:
            if self.prefix_evict != "none" and page in self._page_keys:
                self._evict_tick += 1
                self._pub_only[page] = (self._evict_tick, self._window_seq)
                return
            key = self._page_keys.pop(page, None)
            if key is not None and self._prefix_index.get(key) == page:
                del self._prefix_index[key]
            self._page_spans.pop(page, None)
            self._release_page(page)

    def _evictable_count(self, h: int) -> int:
        """Publication-only pages homed on host `h` whose wait window
        has closed — claimable via `_evict_prefix_page`. Pages retained
        while an in-flight window was open stay uncounted until that
        window reconciles (same discipline as limbo: an in-flight step
        may still write their rows)."""
        if not self._pub_only:
            return 0
        return sum(
            1
            for p, (_, wid) in self._pub_only.items()
            if wid <= self._window_closed and self._page_home(p) == h
        )

    def _evict_cost(self, page: int) -> float:
        """Seconds to recompute `page` if its prefix is wanted again:
        one chunk of page_size tokens appended at the page's chain-start
        cursor. Priced through `evict_pricer` when the compiled model
        wired one; otherwise the cursor itself — the same monotone
        order (attention cost grows with cursor), just unscaled. A
        raising pricer degrades to the proxy: eviction must never fail
        because pricing did."""
        cursor = self._page_spans.get(page, 0)
        if self.evict_pricer is not None:
            try:
                return float(self.evict_pricer(cursor, self.spec.page_size))
            except Exception:
                pass
        return float(cursor)

    def _evict_prefix_page(self, h: int) -> None:
        """Evict one publication-only page homed on host `h`: unpublish
        it from the hash index and push it straight onto the free heap
        (its wait window closed, so no in-flight step can touch it).
        Victim order is the policy: "lru" takes the least-recently-
        published page; "cost" takes the page cheapest to recompute
        (`_evict_cost`), stamp-then-page-id as the deterministic
        tiebreak."""
        cands = [
            (stamp, p)
            for p, (stamp, wid) in self._pub_only.items()
            if wid <= self._window_closed and self._page_home(p) == h
        ]
        if not cands:
            raise PagePoolExhausted(
                f"host {h}: no evictable publication-only page"
            )
        if self.prefix_evict == "cost":
            _, _, page = min(
                (self._evict_cost(p), stamp, p) for stamp, p in cands
            )
        else:
            _, page = min(cands)
        del self._pub_only[page]
        self._page_spans.pop(page, None)
        key = self._page_keys.pop(page, None)
        if key is not None and self._prefix_index.get(key) == page:
            del self._prefix_index[key]
        heapq.heappush(self._free_pages_h[h], page)
        self.prefix_evictions += 1

    def _pop_free_page(self, h: int) -> int:
        """The one pop path for host `h`'s free-page heap: when the
        heap is dry, evict a publication-only prefix page to refill it
        — live requests are ALWAYS served from published-but-idle
        capacity before anyone is swapped or preempted."""
        if not self._free_pages_h[h]:
            self._evict_prefix_page(h)
        return heapq.heappop(self._free_pages_h[h])

    def _decref_entry(self, slot: int, pi: int) -> None:
        """Clear one block-table entry: sentinel the mapping, settle the
        shared flag and held count, and decref the page."""
        page = int(self.block_tables[slot, pi])
        if page == self.spec.num_pages:
            return
        self.block_tables[slot, pi] = self.spec.num_pages
        self._held[slot] -= 1
        if self._entry_shared[slot, pi]:
            self._entry_shared[slot, pi] = False
            self._shared[slot] -= 1
        self._decref_page(page)

    @staticmethod
    def _chain_key(prev: bytes, tokens) -> bytes:
        """Key of a full page holding `tokens`, chained on the previous
        page's key — equal keys mean equal page content AND equal prefix
        up to this page, which is exactly what makes the page's KV rows
        (a pure function of the tokens at and before it) reusable."""
        h = hashlib.blake2b(prev, digest_size=16)
        h.update(np.asarray(tokens, dtype=np.int64).tobytes())
        return h.digest()

    def match_prefix(self, tokens: Sequence[int]) -> List[int]:
        """Longest run of registered pages covering a prefix of `tokens`
        (full pages only — partial pages are never shared). Read-only."""
        pages: List[int] = []
        if not self.prefix_cache:
            return pages
        ps = self.spec.page_size
        key = b""
        for i in range(len(tokens) // ps):
            key = self._chain_key(key, tokens[i * ps : (i + 1) * ps])
            page = self._prefix_index.get(key)
            if page is None:
                break
            pages.append(page)
        return pages

    def register_prefix(self, slot: int, tokens: Sequence[int], upto) -> None:
        """Publish `slot`'s full pages covering tokens[:upto] in the
        hash index so later admissions can map them. Idempotent; only
        pages whose content is fully written (upto capped at the slot's
        visible length) are published, and a content collision dedupes
        to the page already in the index."""
        if not self.prefix_cache:
            return
        if slot not in self._active:
            raise ValueError(f"slot {slot} is not active")
        ps = self.spec.page_size
        upto = min(int(upto), len(tokens), int(self.lengths[slot]))
        key = b""
        for i in range(upto // ps):
            key = self._chain_key(key, tokens[i * ps : (i + 1) * ps])
            page = int(self.block_tables[slot, i])
            if page == self.spec.num_pages:
                break
            if key in self._prefix_index or page in self._page_keys:
                continue
            self._prefix_index[key] = page
            self._page_keys[page] = key
            # chain-start cursor: page i of the chain covers tokens
            # [i*ps, (i+1)*ps) — what the cost eviction policy prices
            self._page_spans[page] = i * ps

    def alloc_shared(
        self,
        tokens: Sequence[int],
        prompt_len: Optional[int] = None,
        total_len: Optional[int] = None,
        optimistic: bool = False,
    ) -> Optional[Tuple[int, int]]:
        """Admit a sequence with prefix sharing: registered pages whose
        chained content hash matches a prefix of `tokens` are MAPPED
        (refcounted) instead of allocated, and the caller receives
        `(slot, cursor)` — the cache cursor past the shared content, so
        prefill recomputes only tokens[cursor:]. At least one token is
        always left to recompute (the request needs sampling logits), so
        a whole-prompt match gets cursor len(tokens)-1 and its first
        write copy-on-writes the final shared page. `prompt_len` is the
        prompt span allocated eagerly (0 under token-budget chunking —
        chunks claim lazily); shared pages are mapped eagerly either
        way. Falls back to plain `alloc` semantics when the prefix cache
        is off (returns cursor 0). None when admission is refused."""
        spec = self.spec
        ntok = len(tokens)
        if prompt_len is None:
            prompt_len = ntok
        total = max(ntok, prompt_len, total_len if total_len is not None else 0)
        if total > spec.max_len:
            raise ValueError(
                f"sequence of {total} tokens exceeds max_len {spec.max_len}"
            )
        if not self.prefix_cache:
            slot = self.alloc(prompt_len, total, optimistic=optimistic)
            return None if slot is None else (slot, 0)
        ps = spec.page_size
        matched_all = self.match_prefix(tokens)
        # Host choice with page locality: a slot only maps shared pages
        # its OWN host's pool shard holds (the match truncates at the
        # first foreign page — cross-host prefix sharing would alias
        # pages onto another host's devices). Longest usable match wins,
        # then admission headroom, then lowest host id. Single-host runs
        # reduce to the full match and the old admission check exactly.
        best = None  # (m, avail, -h) ordering via explicit compare
        for h in range(self.num_hosts):
            if h in self._hosts_down or not self._free_slots_h[h]:
                continue
            m_h = 0
            for page in matched_all:
                if self._page_home(page) != h:
                    break
                m_h += 1
            cursor_h = min(m_h * ps, max(0, ntok - 1))
            fresh_h = max(0, self._pages_for(prompt_len) - m_h)
            max_p_h = self._pages_for(total) - (cursor_h // ps)
            need_h = fresh_h if optimistic else max_p_h
            # matched publication-only pages are about to be RESURRECTED
            # (mapped, not evicted), so the headroom they contribute as
            # eviction candidates is not really there for this admission
            avail = self._host_avail(h) - sum(
                1 for page in matched_all[:m_h] if page in self._pub_only
            )
            if avail < need_h:
                continue
            if best is None or (m_h, avail) > (best[0], best[1]):
                best = (m_h, avail, h)
        if best is None:
            return None
        m, _, h = best
        matched = matched_all[:m]
        cursor = min(m * ps, max(0, ntok - 1))
        # fresh pages popped now: the unshared remainder of the eager
        # prompt span; worst-case pool draws over the slot's lifetime:
        # every page from the cursor's page up to the total-length page
        # (the cursor page itself COWs when it is still shared — the
        # whole-prompt-match case)
        fresh_now = max(0, self._pages_for(prompt_len) - m)
        max_p = self._pages_for(total) - (cursor // ps)
        slot = heapq.heappop(self._free_slots_h[h])
        self._active.add(slot)
        for i, page in enumerate(matched):
            self._incref(slot, i, page)
        for i in range(m, m + fresh_now):
            self._install_page(slot, i, self._pop_free_page(h))
        self._held[slot] = m + fresh_now
        if optimistic:
            self._optimistic.add(slot)
            self._max_pages[slot] = fresh_now  # == owned
        else:
            self._max_pages[slot] = max_p
            self._reserved_h[h] += max_p - fresh_now
        self.lengths[slot] = cursor
        if m:
            self.prefix_hits += 1
        return slot, cursor

    def _cow_page(self, slot: int, pi: int) -> None:
        """First divergent write into a shared mapping: take the page
        over in place when this slot became its sole owner (unpublishing
        the now-divergent content), otherwise pop a fresh page, copy the
        shared page's rows (and int8 scales) across every layer pool,
        and swap the mapping — readers holding the old page see it
        untouched, and the functional pool threading orders the copy
        before any later step's reads."""
        page = int(self.block_tables[slot, pi])
        h = self.host_of_slot(slot)
        if self._refcounts[page] > 1:
            if slot in self._optimistic:
                if self._host_avail(h) < 1:
                    raise PagePoolExhausted(
                        f"free-page pool exhausted: optimistic slot {slot} "
                        f"needs a copy-on-write page but "
                        f"{len(self._free_pages_h[h])} free - "
                        f"{self._reserved_h[h]} "
                        "reserved leaves none"
                    )
            elif not self._free_pages_h[h] and not self._evictable_count(h):
                if self._limbo:
                    raise PagePoolExhausted(
                        f"free-page pool exhausted: {len(self._limbo)} pages "
                        "pinned by an in-flight step — reconcile the "
                        "pipeline to release them"
                    )
                raise PagePoolExhausted(
                    "free-page pool exhausted despite the admission reserve "
                    "— allocator invariant violated"
                )
            new = (
                heapq.heappop(self._free_pages_h[h])
                if self._free_pages_h[h]
                else self._pop_free_page(h)  # LRU-evict a retained page
            )
            # functional rebind (fresh dicts, whole-attribute swap) of
            # the pools read HERE, at call time: they are the newest
            # step's committed outputs and no queued program holds them
            # (a step program consumes the pools it is handed, so an
            # array kept from before a dispatch would be deleted). The
            # eager .at[].set() copies them, undonated, behind that step
            # on the device queue — same discipline as commit()
            def copied(pools):
                return {g: p.at[new].set(p[page]) for g, p in pools.items()}

            self.k, self.v = copied(self.k), copied(self.v)
            self.k_scale = copied(self.k_scale)
            self.v_scale = copied(self.v_scale)
            self.block_tables[slot, pi] = new
            self._refcounts[new] = 1
            self._refcounts[page] -= 1
            self.cow_copies += 1
        else:
            # sole owner now — the content is about to diverge, so the
            # index must stop advertising it
            key = self._page_keys.pop(page, None)
            if key is not None and self._prefix_index.get(key) == page:
                del self._prefix_index[key]
            self._page_spans.pop(page, None)
        self._entry_shared[slot, pi] = False
        self._shared[slot] -= 1
        if slot in self._optimistic:
            self._max_pages[slot] = self._owned(slot)
        elif self._owned(slot) <= self._max_pages[slot]:
            self._reserved_h[h] -= 1

    def ensure_position(self, slot: int, pos: int) -> None:
        """Make position `pos` of `slot` writable, claiming the next page
        from the free list when the sequence crosses a page boundary.
        For reserve-admitted slots the admission reserve guarantees the
        claim succeeds for any position inside the declared worst case;
        an optimistic slot's claim must additionally leave the reserve
        intact, and raises PagePoolExhausted when it cannot — the signal
        the scheduler answers with preemption-by-recompute. A position
        whose page is mapped but SHARED triggers the copy-on-write fork
        here — every dispatch path claims its write positions through
        this method, which is what makes it the single COW seam."""
        if slot not in self._active:
            raise ValueError(f"slot {slot} is not active")
        pi = pos // self.spec.page_size
        if self.block_tables[slot, pi] != self.spec.num_pages:
            if self._entry_shared[slot, pi]:
                self._cow_page(slot, pi)
            return
        h = self.host_of_slot(slot)
        if slot in self._optimistic:
            if self._host_avail(h) < 1:
                raise PagePoolExhausted(
                    f"free-page pool exhausted: optimistic slot {slot} "
                    f"needs a page but {len(self._free_pages_h[h])} free - "
                    f"{self._reserved_h[h]} reserved leaves none"
                )
            self._install_page(slot, pi, self._pop_free_page(h))
            self._held[slot] += 1
            self._max_pages[slot] = self._owned(slot)
            return
        if not self._free_pages_h[h] and not self._evictable_count(h):
            if self._limbo:
                raise PagePoolExhausted(
                    f"free-page pool exhausted: {len(self._limbo)} pages "
                    "pinned by an in-flight step — reconcile the pipeline "
                    "to release them"
                )
            raise PagePoolExhausted(
                "free-page pool exhausted despite the admission reserve — "
                "allocator invariant violated"
            )
        self._install_page(slot, pi, self._pop_free_page(h))
        self._held[slot] += 1
        if self._owned(slot) <= self._max_pages[slot]:
            self._reserved_h[h] -= 1

    def _refuse_with_state(self, what: str) -> None:
        """Raise, in words, where the cache holds per-slot recurrent
        state and `what` would need it rolled back, moved or shared."""
        if self.state:
            raise ValueError(
                f"{what} is not supported for a model with recurrent "
                f"layers: {STATE_WHY}"
            )

    def truncate(
        self, slot: int, new_len: int, src_rows: Optional[Sequence[int]] = None
    ) -> None:
        """Roll the slot's visible length to `new_len` and return every
        page past ceil(new_len / page_size) to the free list — the
        speculative-decode rollback (verify claims pages for all k+1
        drafted rows; acceptance keeps a prefix). Returned pages go back
        under the slot's admission reserve (`_reserved` grows by exactly
        the pages released, capped at the slot's declared worst case), so
        the preemption-free accounting holds across rollback: a future
        re-growth of this slot re-claims from a pool that still covers
        every in-flight worst case. new_len may exceed the current
        length (verify commits accepted rows through this call) but
        never the pages the slot actually holds.

        src_rows (tree-verify commit): the accepted root-to-leaf rows'
        absolute positions, compacted into [new_len - len(src_rows),
        new_len) through the block table BEFORE the dead branches' pages
        are released. They are compacted into the contiguous tail
        before the length moves, so the committed cache is
        indistinguishable from a linear decode of the accepted path (K/V
        rows are value-exact under the copy: attention context is the
        mask's job). Positions must be non-decreasing and each source
        must sit at-or-after its destination (topological node order
        guarantees both); src_rows == destinations is a no-op, so chain
        trees never touch the device. On int8
        pools the moved rows dequantize with their source page's scale
        and requantize under the destination page's; a destination page
        whose FIRST row is among the moves re-derives its scale from
        that row (the _quant_scatter claim rule), so the committed pool
        bytes match what a sequential decode of the accepted path would
        have produced up to the int8 round trip."""
        self._refuse_with_state("truncate (a speculative roll-back)")
        if slot not in self._active:
            raise ValueError(f"slot {slot} is not active")
        if not 0 <= new_len <= self.spec.max_len:
            raise ValueError(
                f"new_len {new_len} outside [0, {self.spec.max_len}]"
            )
        keep = self._pages_for(new_len)
        if keep > self._held[slot]:
            raise ValueError(
                f"new_len {new_len} needs {keep} pages but slot {slot} "
                f"holds {int(self._held[slot])}"
            )
        if src_rows is not None and len(src_rows):
            self._compact_rows(slot, new_len, src_rows)
        old_resv = max(0, int(self._max_pages[slot]) - self._owned(slot))
        for pi in range(keep, self.spec.max_pages_per_seq):
            self._decref_entry(slot, pi)
        if slot in self._optimistic:
            # released pages return to the COMMON pool, not a reserve
            self._max_pages[slot] = self._owned(slot)
        else:
            self._reserved_h[self.host_of_slot(slot)] += (
                max(0, int(self._max_pages[slot]) - self._owned(slot))
                - old_resv
            )
        self.lengths[slot] = new_len

    def _compact_rows(
        self, slot: int, new_len: int, src_rows: Sequence[int]
    ) -> None:
        """Move the accepted tree rows into the contiguous tail of the
        committed prefix, resolving positions through the block table.
        Every touched page is exclusively owned: the verify claimed (and
        COW-forked where needed) each window page via ensure_position
        before writing it, so the row copies never leak into a shared
        prefix page. Functional rebind with gather-before-scatter, as in
        _cow_page/commit — queued steps keep reading the old pools."""
        import jax.numpy as jnp

        spec = self.spec
        ps = spec.page_size
        srcs = [int(p) for p in src_rows]
        dests = list(range(new_len - len(srcs), new_len))
        if dests[0] < 0:
            raise ValueError(
                f"{len(srcs)} compacted rows do not fit under new_len "
                f"{new_len}"
            )
        sentinel = spec.num_pages

        def flat(pos: int) -> int:
            page = int(self.block_tables[slot, pos // ps])
            if page >= sentinel:
                raise ValueError(
                    f"slot {slot} position {pos} has no mapped page"
                )
            return page * ps + pos % ps

        for s, d in zip(srcs, dests):
            if not d <= s < spec.max_len:
                raise ValueError(
                    f"source row {s} outside [{d}, {spec.max_len})"
                )
        if srcs == dests:
            return
        sf = np.asarray([flat(p) for p in srcs], dtype=np.int32)
        df = np.asarray([flat(p) for p in dests], dtype=np.int32)
        src_page = sf // ps
        dst_page = df // ps
        si = jnp.asarray(sf)
        di = jnp.asarray(df)
        nk, nv = dict(self.k), dict(self.v)
        if not self.quantized:
            def moved(pool):
                flat_rows = pool.reshape(-1, spec.row_width)
                return flat_rows.at[di].set(flat_rows[si]).reshape(pool.shape)

            self.k = {g: moved(p) for g, p in nk.items()}
            self.v = {g: moved(p) for g, p in nv.items()}
            return
        # int8 pools: dequant with the source page's scale, requantize
        # under the destination page's. A destination page whose first
        # row moves re-derives its scale from that row — the same claim
        # rule _quant_scatter applies on sequential writes, so scales
        # (and bytes) come out as a linear decode of the path would
        first = (df % ps == 0)[:, None]  # [a, 1] page-initial dests
        spi = jnp.asarray(src_page)
        dpi = jnp.asarray(dst_page)
        firstj = jnp.asarray(first)
        nks, nvs = dict(self.k_scale), dict(self.v_scale)

        def requant(pool, scale):
            f = pool.reshape(-1, spec.num_heads, spec.head_dim)
            deq = f[si].astype(jnp.float32) * scale[spi][:, :, None]
            amax = jnp.max(jnp.abs(deq), axis=-1)  # [a, heads]
            cand = jnp.zeros_like(scale).at[dpi].max(
                jnp.where(firstj, int8_page_scale(amax), 0.0)
            )
            claimed = jnp.zeros_like(scale).at[dpi].max(
                jnp.where(firstj, 1.0, 0.0)
            )
            new_scale = jnp.where(claimed > 0.0, cand, scale)
            s = new_scale[dpi]  # [a, heads]
            safe = jnp.where(s > 0.0, s, 1.0)
            q = jnp.clip(
                jnp.round(deq / safe[:, :, None]), -127, 127
            ).astype(pool.dtype)
            return f.at[di].set(q).reshape(pool.shape), new_scale

        for g in nk:
            nk[g], nks[g] = requant(nk[g], nks[g])
        for g in nv:
            nv[g], nvs[g] = requant(nv[g], nvs[g])
        self.k, self.v = nk, nv
        self.k_scale, self.v_scale = nks, nvs

    def free(self, slot: int) -> None:
        if slot not in self._active:
            raise ValueError(f"slot {slot} is not active")
        self._active.remove(slot)
        owned_before = self._owned(slot)
        for pi in range(self.spec.max_pages_per_seq):
            self._decref_entry(slot, pi)
        if slot in self._optimistic:
            self._optimistic.discard(slot)
        else:
            self._reserved_h[self.host_of_slot(slot)] -= max(
                0, int(self._max_pages[slot]) - owned_before
            )
        self._held[slot] = 0
        self._max_pages[slot] = 0
        self.lengths[slot] = 0
        heapq.heappush(self._free_slots_h[self.host_of_slot(slot)], slot)

    # -- KV swap-to-host (swap vs recompute preemption) ----------------------

    def swap_bytes_for(self, slot: int) -> int:
        """Host bytes one swap-out of `slot` would stage: its held
        pages' K/V rows across every layer, plus the int8 fp32 scale
        slivers — the bytes_moved the cost model prices against one
        recompute prefill."""
        spec = self.spec
        per_page = spec.kv_pools * spec.itemsize * spec.page_size * spec.row_width
        if self.quantized:
            per_page += spec.kv_pools * 4 * spec.num_heads
        return int(self._held[slot]) * per_page * len(spec.layer_guids)

    @property
    def swapped_pages(self) -> int:
        """Pages' worth of KV currently staged in host swap buffers."""
        return sum(int(rec["pages"]) for rec in self._swapped.values())

    def _stage_pages(self, idx: np.ndarray):
        """Host copies of pages `idx` of every pool: (k, v, k_scale,
        v_scale) dicts by layer, as a swap record holds them."""
        return tuple(
            {g: np.asarray(pool[idx]) for g, pool in pools.items()}
            for pools in (self.k, self.v, self.k_scale, self.v_scale)
        )

    def swap_out(self, slot: int) -> Optional[int]:
        """Stage `slot`'s committed pages (K/V pools AND int8 scale
        slivers, in block-table order) into host buffers, free the slot,
        and return a swap handle `swap_in` restores from. Returns None —
        the caller degrades to recompute-preemption — when an in-flight
        step could still write the slot's pages (the scheduler drains
        the pipeline first, so this is a belt-and-braces refusal) or
        when `swap_bytes_budget` would be exceeded. The staged copy is
        the COMMITTED pool content, so a restore resumes decoding with
        value-identical KV rows — no re-prefill."""
        self._refuse_with_state("swap_out")
        if slot not in self._active:
            raise ValueError(f"slot {slot} is not active")
        if self._inflight_depth > 0:
            return None
        bytes_staged = self.swap_bytes_for(slot)
        if (
            self.swap_bytes_budget
            and self._swap_bytes_held + bytes_staged > self.swap_bytes_budget
        ):
            return None
        sentinel = self.spec.num_pages
        pages = [int(p) for p in self.block_tables[slot] if p != sentinel]
        idx = np.asarray(pages, dtype=np.int32)
        hk, hv, hks, hvs = self._stage_pages(idx)
        handle = self._swap_seq
        self._swap_seq += 1
        self._swapped[handle] = {
            "k": hk,
            "v": hv,
            "k_scale": hks,
            "v_scale": hvs,
            "length": int(self.lengths[slot]),
            "pages": len(pages),
            "bytes": bytes_staged,
        }
        self._swap_bytes_held += bytes_staged
        self.swap_outs += 1
        self.swap_bytes_total += bytes_staged
        self.free(slot)
        return handle

    def snapshot_swap(self, slot: int) -> Optional[Dict[str, object]]:
        """Non-destructive sibling of `swap_out` for the write-ahead
        journal: gather `slot`'s committed pages (K/V and int8 scales,
        block-table order) into a host record shaped exactly like
        `export_swap`'s — fingerprint included, so a RESTARTED engine's
        `import_swap` can adopt it — WITHOUT freeing the slot, touching
        the `_swapped` ledger, or spending swap budget (the record's
        bytes live in the journal file, not in this cache's staging
        buffers — hence no FX106/FX107 ledger discipline applies).
        Returns None while an in-flight step could still write the
        slot's pages: a snapshot of half-written rows would restore a
        torn sequence."""
        if slot not in self._active:
            raise ValueError(f"slot {slot} is not active")
        if self._inflight_depth > 0:
            return None
        sentinel = self.spec.num_pages
        pages = [int(p) for p in self.block_tables[slot] if p != sentinel]
        idx = np.asarray(pages, dtype=np.int32)
        hk, hv, hks, hvs = self._stage_pages(idx)
        return {
            "k": hk,
            "v": hv,
            "k_scale": hks,
            "v_scale": hvs,
            "length": int(self.lengths[slot]),
            "pages": len(pages),
            "bytes": self.swap_bytes_for(slot),
            "fingerprint": self._swap_fingerprint(),
        }

    def swap_in(
        self,
        handle: int,
        total_len: Optional[int] = None,
        optimistic: bool = False,
    ) -> Optional[int]:
        """Restore a swapped-out sequence: claim a fresh slot and pages
        on any alive host, scatter the staged rows back into the pools
        (functional rebind, same discipline as `_cow_page`), and set the
        slot's length to the staged length — the stream resumes with a
        plain decode, token- and logit-identical to never-swapped.
        `total_len` sizes the growth reserve exactly like `alloc`'s;
        None means no host can admit (the handle stays valid for a
        later retry or `discard_swap`)."""
        self._refuse_with_state("swap_in")
        rec = self._swapped.get(handle)
        if rec is None:
            raise KeyError(f"unknown swap handle {handle}")
        spec = self.spec
        n = int(rec["pages"])
        total = max(int(rec["length"]), total_len if total_len else 0)
        if total > spec.max_len:
            raise ValueError(
                f"sequence of {total} tokens exceeds max_len {spec.max_len}"
            )
        max_p = max(n, self._pages_for(total))
        h = self._pick_host(n if optimistic else max_p)
        if h is None:
            return None
        rec = self._swapped.pop(handle)
        self._swap_bytes_held -= int(rec["bytes"])
        slot = heapq.heappop(self._free_slots_h[h])
        self._active.add(slot)
        pages = [self._pop_free_page(h) for _ in range(n)]
        for i, page in enumerate(pages):
            self._install_page(slot, i, page)
        self._held[slot] = n
        if optimistic:
            self._optimistic.add(slot)
            self._max_pages[slot] = n
        else:
            self._max_pages[slot] = max_p
            self._reserved_h[h] += max_p - n
        self.lengths[slot] = int(rec["length"])
        if n:
            import jax.numpy as jnp

            idx = np.asarray(pages, dtype=np.int32)
            def restored(pools, staged):
                return {
                    g: p.at[idx].set(jnp.asarray(staged[g]))
                    for g, p in pools.items()
                }

            self.k = restored(self.k, rec["k"])
            self.v = restored(self.v, rec["v"])
            self.k_scale = restored(self.k_scale, rec["k_scale"])
            self.v_scale = restored(self.v_scale, rec["v_scale"])
        self.swap_ins += 1
        self.swap_bytes_total += int(rec["bytes"])
        return slot

    def discard_swap(self, handle: int) -> None:
        """Drop a staged swap record (terminal request, or a swap-in
        degraded to recompute): its host bytes return to the budget.
        Unknown handles are ignored — discard races are expected."""
        rec = self._swapped.pop(handle, None)
        if rec is not None:
            self._swap_bytes_held -= int(rec["bytes"])

    # -- cross-engine handoff (prefill tier -> decode tier) ------------------

    def _swap_fingerprint(self) -> Tuple:
        """The geometry a staged record's rows are shaped by — two
        caches exchange swap records only when these agree (heads/dim/
        page_size fix the row shape, layer_guids the per-layer keys,
        kv_dtype the int8 scale slivers)."""
        spec = self.spec
        return (
            tuple(spec.layer_guids),
            spec.page_size,
            spec.num_heads,
            spec.head_dim,
            spec.kv_dtype,
        )

    def export_swap(self, handle: int) -> Dict[str, object]:
        """Surrender a staged swap record for restoration in ANOTHER
        engine's cache (the prefill->decode handoff): pops the record —
        the handle dies here, so a staged copy can be consumed exactly
        once (fxlint FX108's contract) — returns the staged bytes to
        this cache's budget, and stamps a geometry fingerprint
        `import_swap` validates. Raises KeyError on an unknown or
        already-consumed handle: double export IS the bug class."""
        rec = self._swapped.pop(handle)
        self._swap_bytes_held -= int(rec["bytes"])
        out = dict(rec)
        out["fingerprint"] = self._swap_fingerprint()
        return out

    def import_swap(self, record: Dict[str, object]) -> Optional[int]:
        """Adopt a record `export_swap` produced on a geometry-
        compatible cache: install it under a fresh LOCAL handle (the
        source handle died at export) against this cache's swap budget.
        Returns the new handle — `swap_in` then restores it exactly
        like a locally staged victim, bit-exact rows and int8 scales
        included — or None when the budget refuses (the record stays
        the caller's, to retry or degrade to recompute). Raises
        ValueError on a geometry mismatch: restoring rows shaped by a
        different page/head layout would scatter garbage."""
        self._refuse_with_state("import_swap")
        rec = dict(record)
        fp = rec.pop("fingerprint", None)
        if fp is not None and tuple(fp) != self._swap_fingerprint():
            raise ValueError(
                f"import_swap: incompatible cache geometry {fp} vs "
                f"{self._swap_fingerprint()}"
            )
        bytes_staged = int(rec["bytes"])
        if (
            self.swap_bytes_budget
            and self._swap_bytes_held + bytes_staged > self.swap_bytes_budget
        ):
            return None
        handle = self._swap_seq
        self._swap_seq += 1
        self._swapped[handle] = rec
        self._swap_bytes_held += bytes_staged
        return handle

    @property
    def pools(self) -> Tuple:
        """What every step program is handed, donated, and returns
        rewritten, in `commit`'s order."""
        return self.k, self.v, self.k_scale, self.v_scale, self.state

    def commit(
        self,
        new_k: Dict[int, object],
        new_v: Dict[int, object],
        new_k_scale: Optional[Dict[int, object]] = None,
        new_v_scale: Optional[Dict[int, object]] = None,
        new_state: Optional[Dict[int, Dict[str, object]]] = None,
    ):
        """Swap in the pools a jitted step returned (and, under int8,
        the scale side pools the step's scatter-max may have claimed,
        and the per-slot state of a model's recurrent layers).
        The step program was handed the previous ones donated
        (engine._step_jit): they are gone, these are the only live
        pools, and nothing else may keep a pool array across a
        dispatch."""
        self.k = dict(new_k)
        self.v = dict(new_v)
        if new_k_scale is not None:
            self.k_scale = dict(new_k_scale)
        if new_v_scale is not None:
            self.v_scale = dict(new_v_scale)
        if new_state is not None:
            self.state = {g: dict(rows) for g, rows in new_state.items()}

    def telemetry_gauges(self) -> Dict[str, float]:
        """Point-in-time allocator gauges for the telemetry sampler:
        UNIQUE pages live in block tables (refcount >= 1 — a shared
        mapping rides an already-live page, so it adds to
        `kv_prefix_pages_shared`, not to live), pages pinned in the
        in-flight limbo list, free-heap depth, the reserve ledger, and
        pool occupancy. These are the SAME ledgers `check_invariants`
        audits, so live + pinned + free (+ injector-stolen) always
        covers the pool — the conservation law the KV-gauge tests
        re-derive from the block tables themselves."""
        spec = self.spec
        live = int((self._refcounts > 0).sum())
        return {
            "kv_slots_active": len(self._active),
            "kv_slots_free": self.num_free,
            "kv_rows_used": int(self.lengths.sum()),
            "kv_occupancy": live / spec.num_pages if spec.num_pages else 0.0,
            "kv_pages_live": live,
            "kv_pages_pinned": len(self._limbo),
            "kv_free_heap_depth": self.num_free_pages,
            "kv_pages_reserved": int(self._reserved),
            "kv_inflight_depth": self._inflight_depth,
            "kv_prefix_pages_shared": int(self._shared.sum()),
            "kv_swapped_pages": self.swapped_pages,
            "kv_pages_pub_only": len(self._pub_only),
        }

    def telemetry_gauges_host(self, h: int) -> Dict[str, float]:
        """The per-host slice of the allocator gauges — sampled under a
        `host` label when the placement runs more than one host
        partition. Sums across hosts equal the unlabelled series."""
        lo, hi = h * self._pages_per_host, (h + 1) * self._pages_per_host
        live = int((self._refcounts[lo:hi] > 0).sum())
        return {
            "kv_slots_active": sum(
                1 for s in self._active if self.host_of_slot(s) == h
            ),
            "kv_slots_free": len(self._free_slots_h[h]),
            "kv_pages_live": live,
            "kv_pages_pinned": sum(
                1 for p, _ in self._limbo if self._page_home(p) == h
            ),
            "kv_free_heap_depth": len(self._free_pages_h[h]),
            "kv_pages_reserved": int(self._reserved_h[h]),
            "kv_pages_pub_only": sum(
                1 for p in self._pub_only if self._page_home(p) == h
            ),
        }

    def telemetry_counters(self) -> Dict[str, int]:
        """Monotonic allocator counters for the telemetry sampler."""
        return {
            "kv_prefix_hits_total": self.prefix_hits,
            "kv_cow_copies_total": self.cow_copies,
            "kv_swap_out_total": self.swap_outs,
            "kv_swap_in_total": self.swap_ins,
            "kv_swap_bytes_total": self.swap_bytes_total,
            "kv_prefix_evictions_total": self.prefix_evictions,
        }

    def check_invariants(self, extra_free: int = 0) -> None:
        """Assert the page allocator's full accounting is consistent —
        the chaos-harness probe (tests/test_resilience.py) calls this
        after every iteration.
        `extra_free` is pages a fault injector is deliberately holding
        outside the pool (faults.FaultInjector page-steal), which the
        conservation check must count."""
        spec = self.spec
        sentinel = spec.num_pages
        refs = np.zeros(spec.num_pages, dtype=np.int64)
        owners = np.zeros(spec.num_pages, dtype=np.int64)
        for s in range(spec.max_seqs):
            row = [int(p) for p in self.block_tables[s] if p != sentinel]
            for pi in range(spec.max_pages_per_seq):
                p = int(self.block_tables[s, pi])
                if p == sentinel:
                    # shared flags only mark real mappings
                    assert not self._entry_shared[s, pi]
                    continue
                refs[p] += 1
                if not self._entry_shared[s, pi]:
                    owners[p] += 1
            # per-slot ledgers match the table; free slots hold nothing
            assert len(row) == int(self._held[s])
            assert int(self._entry_shared[s].sum()) == int(self._shared[s])
            if s not in self._active:
                assert not row and self.lengths[s] == 0
            else:
                # visible length fits in the held pages
                assert int(self.lengths[s]) <= len(row) * spec.page_size
        # the refcount ledger re-derives exactly from the live block
        # tables, and a multiply-referenced page has at most one OWNING
        # (unshared) mapping — everyone else must COW before writing
        assert np.array_equal(refs, self._refcounts.astype(np.int64))
        assert (owners <= 1).all()
        live = {p for p in range(spec.num_pages) if refs[p] > 0}
        # publication-only retained pages: refcount 0 (no table maps
        # them), still published (matchable), off the free heap — a
        # fourth disjoint population the conservation law must count.
        # They exist only under an eviction policy.
        pub_only = set(self._pub_only)
        assert not pub_only or self.prefix_evict != "none"
        for p in pub_only:
            assert refs[p] == 0
            assert p in self._page_keys
        # conservation over UNIQUE pages: live + free + in-flight limbo
        # + publication-only retained (+ injector-held) is the whole
        # pool; free/limbo/retained pages carry no references
        limbo = [p for p, _ in self._limbo]
        free_all = [p for hp in self._free_pages_h for p in hp]
        assert len(limbo) == len(set(limbo))
        assert live.isdisjoint(free_all)
        assert live.isdisjoint(limbo)
        assert set(limbo).isdisjoint(free_all)
        assert pub_only.isdisjoint(free_all)
        assert pub_only.isdisjoint(limbo)
        assert len(live) + len(free_all) + len(limbo) + len(pub_only) + (
            extra_free
        ) == spec.num_pages
        # host-partition purity: every free heap holds only its own
        # host's pages, every slot heap its own host's slots, and every
        # mapped page lives on its slot's home host (alloc_shared
        # truncates prefix matches at the first foreign page to keep
        # this true) — the property that makes per-host free views a
        # sound admission signal. Per-host conservation pins the
        # injector's stolen pages (single-host harness) to host 0.
        for h in range(self.num_hosts):
            assert all(self._page_home(p) == h for p in self._free_pages_h[h])
            assert all(
                self.host_of_slot(s) == h for s in self._free_slots_h[h]
            )
            live_h = sum(1 for p in live if self._page_home(p) == h)
            limbo_h = sum(1 for p in limbo if self._page_home(p) == h)
            pub_h = sum(1 for p in pub_only if self._page_home(p) == h)
            assert live_h + len(self._free_pages_h[h]) + limbo_h + pub_h + (
                extra_free if h == 0 else 0
            ) == self._pages_per_host
        for s in self._active:
            hs = self.host_of_slot(s)
            for p in self.block_tables[s]:
                if int(p) != sentinel:
                    assert self._page_home(int(p)) == hs
        # the hash index only advertises live or publication-only
        # retained pages, bijectively with its reverse map
        assert len(self._prefix_index) == len(self._page_keys)
        for key, p in self._prefix_index.items():
            assert self._page_keys.get(p) == key
            assert refs[p] > 0 or p in pub_only
        # limbo pages only exist while an in-flight window is open
        assert self._inflight_depth >= 0
        if self._limbo:
            assert self._inflight_depth > 0
        # the reserve ledger re-derives from the per-slot worst cases
        # over OWNED pages (shared mappings cost the pool nothing until
        # they COW — and their COW page is part of the worst case),
        # counting only reserve-admitted slots, and never promises pages
        # the pool doesn't have (limbo pages still honor the promise —
        # they return to the heap before any claim that needs them, the
        # async scheduler's drain-before-preempt rule)
        for h in range(self.num_hosts):
            resv_h = sum(
                max(0, int(self._max_pages[s]) - self._owned(s))
                for s in self._active
                if s not in self._optimistic and self.host_of_slot(s) == h
            )
            assert resv_h == self._reserved_h[h]
            limbo_h = sum(1 for p in limbo if self._page_home(p) == h)
            pub_h = sum(1 for p in pub_only if self._page_home(p) == h)
            assert 0 <= self._reserved_h[h] <= (
                len(self._free_pages_h[h])
                + limbo_h
                + pub_h
                + (extra_free if h == 0 else 0)
            )
        # optimistic slots never carry a growth reserve
        for s in self._optimistic:
            assert s in self._active
            assert int(self._max_pages[s]) == self._owned(s)
        # slot bookkeeping
        free_slots_all = [s for hs in self._free_slots_h for s in hs]
        assert self._active.isdisjoint(free_slots_all)
        assert len(self._active) + len(free_slots_all) == spec.max_seqs
        # swap ledger: the host-bytes counter re-derives from the
        # outstanding records and never exceeds the budget
        assert self._swap_bytes_held == sum(
            int(rec["bytes"]) for rec in self._swapped.values()
        )
        if self.swap_bytes_budget:
            assert self._swap_bytes_held <= self.swap_bytes_budget
        for rec in self._swapped.values():
            assert 0 <= int(rec["length"]) <= int(rec["pages"]) * spec.page_size
        # downed hosts are a subset of the partition
        assert all(0 <= h < self.num_hosts for h in self._hosts_down)

    # -- construction from a compiled model ---------------------------------

    @staticmethod
    def from_model(
        model,
        max_seqs: int,
        max_len: int,
        dtype=None,
        buckets: Optional[Sequence[int]] = None,
        page_size: int = 0,
        num_pages: int = 0,
        kv_dtype: str = "fp32",
        prefix_cache: bool = False,
        prefix_evict: str = "none",
        swap_bytes_budget: int = 0,
        evict_pricer=None,
    ) -> "PagedKVCache":
        """Derive geometry + shardings from a compiled FFModel. Defaults
        (page_size 0 / num_pages 0) pick the vLLM-style block size and a
        pool of max_seqs * max_len rows, in which every slot can reach
        max_len whatever the others hold. When the model carries a
        `serving_placement` (compile_for_serving), the cache rides the
        SERVING mesh — pages on the data axis, heads on the model axis —
        instead of the training strategy's sharding. kv_dtype "int8" selects
        the quantized pool variant (the dtype argument is ignored);
        prefix_cache=True turns the hashed prefix-page index on."""
        import jax.numpy as jnp

        if kv_dtype not in ("fp32", "int8"):
            raise ValueError(
                f"kv_dtype must be 'fp32' or 'int8', got {kv_dtype!r}"
            )
        guids, heads, head_dim, head_axis, executor = _derive_geometry(model)
        state_guids, state_shapes = derive_state(model.graph, executor.topo)
        if page_size <= 0:
            page_size = default_page_size(max_len)
        if max_len % page_size:
            raise ValueError(
                f"max_len {max_len} is not divisible by page_size {page_size}"
            )
        if num_pages <= 0:
            num_pages = max_seqs * max_len // page_size
        spec = KVCacheSpec(
            layer_guids=tuple(guids),
            max_seqs=max_seqs,
            max_len=max_len,
            num_heads=heads,
            head_dim=head_dim,
            buckets=tuple(buckets) if buckets else default_buckets(max_len),
            page_size=page_size,
            num_pages=num_pages,
            kv_dtype=kv_dtype,
            kv_pools=cache_row(model.graph.nodes[guids[0]])[0],
            state_guids=state_guids,
            state_shapes=state_shapes,
        )
        if dtype is None:
            dtype = jnp.float32
        placement = getattr(model, "serving_placement", None)
        if placement is not None:
            placement.validate_geometry(max_seqs, num_pages)
            shardings = placement.kv_sharding()
        else:
            shardings = _heads_sharding(executor, head_axis)
        return PagedKVCache(
            spec,
            dtype,
            shardings=shardings,
            prefix_cache=prefix_cache,
            placement=placement,
            prefix_evict=prefix_evict,
            swap_bytes_budget=swap_bytes_budget,
            evict_pricer=evict_pricer,
        )
