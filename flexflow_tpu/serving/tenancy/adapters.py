"""Paged multi-LoRA adapter pool — the PagedKVCache's sibling allocator.

S-LoRA/Punica posture: one base model serves thousands of tenants by
keeping each tenant's rank-r LoRA factors for the attention projections
(Q/K/V/out) resident in pooled device arrays and gathering the right
pages per batch row inside the engine's jitted steps. Paging runs over
the RANK dimension: a pool page holds ``page_rank`` rank slices, an
adapter of rank r occupies ``ceil(r / page_rank)`` pages, and the delta
``(x @ A) @ B`` sums exactly over pages because a LoRA product is a sum
over rank slices.

Allocator discipline mirrors the KV pool deliberately: a free-page heap
(`heapq` over ``_free_adapter_pages``), per-page refcounts
(``_adapter_refcounts``: 1 for the load's ownership plus 1 per attached
slot), table writes (``adapter_tables``) only inside the blessed
helpers below, and ``check_invariants`` re-deriving every ledger from
the tables — fxlint FX110 holds the mutation surface to the blessed
set the same way FX106 does for the KV allocator.

Device layout per attention layer guid (``NP`` pool pages, ``pr`` =
page_rank, ``e`` = embed, ``h``/``d`` = heads/head_dim):

- ``a_q``/``a_k``/``a_v``: ``[NP+1, e, pr]``
- ``b_q``/``b_k``/``b_v``: ``[NP+1, pr, h, d]``
- ``a_o``: ``[NP+1, h, d, pr]``; ``b_o``: ``[NP+1, pr, e]``

Row ``NP`` is the permanent zero sentinel: unused table entries point
at it, so a sentinel gather contributes exactly 0.0 and rows without an
adapter stay bit-identical through the ``jnp.where`` select in
:func:`apply_adapter_qkv` / :func:`apply_adapter_out`. The pools are
rebound functionally on every load (fresh ``.at[page].set`` arrays), so
an in-flight dispatched step keeps the arrays it captured — loads and
unloads can never tear a step that is already on the device.
"""

import heapq
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from flexflow_tpu.ops.attention import lora_delta_out, lora_delta_qkv


class AdapterPoolExhausted(RuntimeError):
    """Raised when a load needs more adapter pages than the pool holds."""


_AB_NAMES = ("a_q", "b_q", "a_k", "b_k", "a_v", "b_v", "a_o", "b_o")


@dataclass(frozen=True)
class AdapterPoolSpec:
    """Geometry of one adapter pool (all attention layers share it)."""

    layer_guids: Tuple[int, ...]
    max_seqs: int
    embed_dim: int
    num_heads: int
    head_dim: int
    max_adapters: int
    max_rank: int
    page_rank: int
    num_pages: int

    @property
    def pages_per_adapter(self) -> int:
        return -(-self.max_rank // self.page_rank)

    def pages_for(self, rank: int) -> int:
        return -(-rank // self.page_rank)


def default_page_rank(max_rank: int) -> int:
    """Auto page sizing: small enough to pack mixed ranks without
    waste, capped at 4 rank slices per page (the KV pool's "page_size
    divides max_len" posture transplanted to rank)."""
    return max(1, min(int(max_rank), 4))


class AdapterPool:
    """Paged pool of LoRA adapter factors plus the slot→adapter map the
    engine snapshots at dispatch.

    Host ledgers (mutated ONLY inside the blessed helpers — fxlint
    FX110):

    - ``adapter_tables`` [max_adapters, pages_per_adapter] int32: the
      pages backing each loaded adapter, sentinel ``num_pages`` in
      unused entries.
    - ``_free_adapter_pages``: min-heap of free page ids (lowest-first
      pops keep allocation deterministic for replay).
    - ``_adapter_refcounts`` [num_pages] int32: 1 while an adapter owns
      the page, +1 per slot attached to that adapter.
    - ``slot_adapter`` [max_seqs] int32: the adapter each slot serves
      (-1 = base model).
    """

    def __init__(self, spec: AdapterPoolSpec, dtype=jnp.float32):
        if spec.max_adapters < 1:
            raise ValueError(
                f"max_adapters must be >= 1, got {spec.max_adapters}"
            )
        if spec.max_rank < 1:
            raise ValueError(f"max_rank must be >= 1, got {spec.max_rank}")
        if spec.page_rank < 1:
            raise ValueError(f"page_rank must be >= 1, got {spec.page_rank}")
        if spec.num_pages < spec.pages_per_adapter:
            raise ValueError(
                f"num_pages {spec.num_pages} cannot hold even one "
                f"max_rank adapter ({spec.pages_per_adapter} pages)"
            )
        self.spec = spec
        self.dtype = dtype
        P = spec.pages_per_adapter
        self.adapter_tables = np.full(
            (spec.max_adapters, P), spec.num_pages, dtype=np.int32
        )
        self._free_adapter_pages: List[int] = list(range(spec.num_pages))
        heapq.heapify(self._free_adapter_pages)
        self._adapter_refcounts = np.zeros(spec.num_pages, dtype=np.int32)
        self.slot_adapter = np.full(spec.max_seqs, -1, dtype=np.int32)
        self._loaded: Dict[int, int] = {}  # adapter_id -> rank
        self.loads = 0
        self.unloads = 0
        self.attaches = 0
        self.detaches = 0
        e, h, d, pr = spec.embed_dim, spec.num_heads, spec.head_dim, spec.page_rank
        rows = spec.num_pages + 1  # + the permanent zero-sentinel row
        pools: Dict[int, Dict[str, jnp.ndarray]] = {}
        for g in spec.layer_guids:
            pools[g] = {
                "a_q": jnp.zeros((rows, e, pr), dtype=dtype),
                "b_q": jnp.zeros((rows, pr, h, d), dtype=dtype),
                "a_k": jnp.zeros((rows, e, pr), dtype=dtype),
                "b_k": jnp.zeros((rows, pr, h, d), dtype=dtype),
                "a_v": jnp.zeros((rows, e, pr), dtype=dtype),
                "b_v": jnp.zeros((rows, pr, h, d), dtype=dtype),
                "a_o": jnp.zeros((rows, h, d, pr), dtype=dtype),
                "b_o": jnp.zeros((rows, pr, e), dtype=dtype),
            }
        self._pools = pools

    # -- construction --------------------------------------------------------

    @classmethod
    def from_model(
        cls,
        model,
        max_seqs: int,
        max_adapters: int = 8,
        max_rank: int = 8,
        page_rank: int = 0,
        num_pages: int = 0,
        dtype=jnp.float32,
    ) -> "AdapterPool":
        """Build a pool sized for a compiled model: geometry comes from
        the same `_derive_geometry` the KV cache uses, so the two
        sibling allocators can never disagree on the attention shape."""
        from flexflow_tpu.serving.kv_cache import _derive_geometry

        guids, heads, head_dim, _head_axis, _executor = _derive_geometry(
            model
        )
        pr = page_rank if page_rank else default_page_rank(max_rank)
        per = -(-max_rank // pr)
        spec = AdapterPoolSpec(
            layer_guids=tuple(guids),
            max_seqs=int(max_seqs),
            embed_dim=heads * head_dim,
            num_heads=heads,
            head_dim=head_dim,
            max_adapters=int(max_adapters),
            max_rank=int(max_rank),
            page_rank=int(pr),
            num_pages=int(num_pages) if num_pages else int(max_adapters) * per,
        )
        return cls(spec, dtype=dtype)

    # -- blessed mutators (fxlint FX110) -------------------------------------

    def _pop_free_adapter_page(self) -> int:
        """The ONE path pages leave the free heap by."""
        if not self._free_adapter_pages:
            raise AdapterPoolExhausted(
                f"adapter pool dry: {self.spec.num_pages} pages all owned"
            )
        return heapq.heappop(self._free_adapter_pages)

    def _install_adapter_page(self, adapter_id: int, pi: int, page: int):
        """Bind a popped page into an adapter's table, refcount 1 (the
        load's own reference)."""
        self.adapter_tables[adapter_id, pi] = page
        self._adapter_refcounts[page] = 1

    def _free_adapter_page(self, adapter_id: int, pi: int) -> None:
        """Unbind one table entry back to the sentinel and return the
        page to the heap. Only legal at refcount 1 — unload refuses
        while any slot still holds a reference."""
        page = int(self.adapter_tables[adapter_id, pi])
        self.adapter_tables[adapter_id, pi] = self.spec.num_pages
        self._adapter_refcounts[page] = 0
        heapq.heappush(self._free_adapter_pages, page)

    def load(self, adapter_id: int, weights, scale: float = 1.0) -> None:
        """Load one adapter's factors into pooled pages.

        ``weights``: {layer_guid: {"a_q": [e, r], "b_q": [r, e], ...}}
        (2-D host matrices; ``e`` for the b/out factors is the flattened
        head space ``h*d``). Rank is inferred from the factors, alpha/
        scale folds into B here — the gather path never rescales. Pages
        are fully overwritten (final page zero-padded past the rank), so
        a recycled page can never leak a previous tenant's factors."""
        aid = int(adapter_id)
        if not 0 <= aid < self.spec.max_adapters:
            raise ValueError(
                f"adapter_id {aid} outside [0, {self.spec.max_adapters})"
            )
        if aid in self._loaded:
            raise ValueError(f"adapter {aid} already loaded (unload first)")
        missing = [g for g in self.spec.layer_guids if g not in weights]
        if missing:
            raise ValueError(f"weights missing attention layers {missing}")
        rank = int(np.asarray(weights[self.spec.layer_guids[0]]["a_q"]).shape[1])
        if not 1 <= rank <= self.spec.max_rank:
            raise ValueError(
                f"rank {rank} outside [1, {self.spec.max_rank}]"
            )
        e, h, d = self.spec.embed_dim, self.spec.num_heads, self.spec.head_dim
        pr = self.spec.page_rank
        n = self.spec.pages_for(rank)
        if len(self._free_adapter_pages) < n:
            raise AdapterPoolExhausted(
                f"adapter {aid} needs {n} pages, "
                f"{len(self._free_adapter_pages)} free"
            )
        pages = [self._pop_free_adapter_page() for _ in range(n)]
        for pi, page in enumerate(pages):
            self._install_adapter_page(aid, pi, page)
        pools = dict(self._pools)
        for g in self.spec.layer_guids:
            mats = {
                k: np.asarray(weights[g][k], dtype=np.float32)
                for k in _AB_NAMES
            }
            for k in ("a_q", "a_k", "a_v", "a_o"):
                if mats[k].shape != (e, rank):
                    raise ValueError(
                        f"layer {g} {k}: expected {(e, rank)}, "
                        f"got {mats[k].shape}"
                    )
            for k in ("b_q", "b_k", "b_v", "b_o"):
                if mats[k].shape != (rank, e):
                    raise ValueError(
                        f"layer {g} {k}: expected {(rank, e)}, "
                        f"got {mats[k].shape}"
                    )
                mats[k] = mats[k] * float(scale)
            pool = dict(pools[g])
            for pi, page in enumerate(pages):
                lo, hi = pi * pr, min(rank, (pi + 1) * pr)
                w = hi - lo
                blk = {
                    k: np.zeros(tuple(pool[k].shape[1:]), dtype=np.float32)
                    for k in _AB_NAMES
                }
                for k in ("a_q", "a_k", "a_v"):
                    blk[k][:, :w] = mats[k][:, lo:hi]
                for k in ("b_q", "b_k", "b_v"):
                    blk[k][:w] = mats[k][lo:hi].reshape(w, h, d)
                blk["a_o"][:, :, :w] = mats["a_o"][:, lo:hi].reshape(h, d, w)
                blk["b_o"][:w] = mats["b_o"][lo:hi]
                for k in _AB_NAMES:
                    pool[k] = pool[k].at[page].set(
                        jnp.asarray(blk[k], dtype=self.dtype)
                    )
            pools[g] = pool
        self._pools = pools
        self._loaded[aid] = rank
        self.loads += 1

    def unload(self, adapter_id: int) -> None:
        """Return an adapter's pages to the pool. Refuses while any slot
        is attached — the engine may still gather those pages."""
        aid = int(adapter_id)
        if aid not in self._loaded:
            raise ValueError(f"adapter {aid} is not loaded")
        n = self.spec.pages_for(self._loaded[aid])
        pages = [int(self.adapter_tables[aid, pi]) for pi in range(n)]
        if any(self._adapter_refcounts[p] != 1 for p in pages):
            attached = int((self.slot_adapter == aid).sum())
            raise RuntimeError(
                f"adapter {aid} still attached to {attached} slot(s)"
            )
        for pi in range(n):
            self._free_adapter_page(aid, pi)
        self._loaded.pop(aid)
        self.unloads += 1

    def attach(self, slot: int, adapter_id: int) -> None:
        """Point a slot at an adapter (-1 = base model) and pin its
        pages. The scheduler calls this at admission, before the slot's
        first prefill dispatch."""
        s = int(slot)
        if not 0 <= s < self.spec.max_seqs:
            raise ValueError(f"slot {s} outside [0, {self.spec.max_seqs})")
        if self.slot_adapter[s] != -1:
            raise RuntimeError(
                f"slot {s} already attached to adapter "
                f"{int(self.slot_adapter[s])} (detach first)"
            )
        aid = int(adapter_id)
        if aid == -1:
            return
        if aid not in self._loaded:
            raise ValueError(f"adapter {aid} is not loaded")
        self.slot_adapter[s] = aid
        n = self.spec.pages_for(self._loaded[aid])
        for pi in range(n):
            self._adapter_refcounts[self.adapter_tables[aid, pi]] += 1
        self.attaches += 1

    def detach(self, slot: int) -> None:
        """Release a slot's adapter reference (idempotent for base-model
        slots). The scheduler calls this wherever the slot frees —
        finalize, preemption, stage-out, evacuation."""
        s = int(slot)
        aid = int(self.slot_adapter[s])
        if aid == -1:
            return
        self.slot_adapter[s] = -1
        n = self.spec.pages_for(self._loaded[aid])
        for pi in range(n):
            self._adapter_refcounts[self.adapter_tables[aid, pi]] -= 1
        self.detaches += 1

    # -- dispatch-side views -------------------------------------------------

    @property
    def device_pools(self) -> Dict[int, Dict[str, jnp.ndarray]]:
        return self._pools

    @property
    def loaded(self) -> Dict[int, int]:
        """{adapter_id: rank} of the currently loaded adapters."""
        return dict(self._loaded)

    def slot_tables(self) -> Tuple[np.ndarray, np.ndarray]:
        """(tbl [max_seqs, P] int32, has [max_seqs] bool) for the
        slot-indexed steps (decode/verify/chunk). Fresh host
        arrays — the engine snapshots them at dispatch, so the step
        rides its own copy (FX103 discipline)."""
        has = self.slot_adapter >= 0
        tbl = np.full(
            (self.spec.max_seqs, self.spec.pages_per_adapter),
            self.spec.num_pages,
            dtype=np.int32,
        )
        rows = np.nonzero(has)[0]
        if rows.size:
            tbl[rows] = self.adapter_tables[self.slot_adapter[rows]]
        return tbl, has.copy()

    # -- invariants / telemetry ----------------------------------------------

    def check_invariants(self) -> None:
        """Re-derive every ledger from the tables (the KV allocator's
        debug contract): page ownership is disjoint, refcounts equal
        1 + attached slots, the free heap is exactly the unowned pages,
        conservation holds, and the sentinel pool row is still zero."""
        spec = self.spec
        owned: Dict[int, Tuple[int, int]] = {}
        for aid in range(spec.max_adapters):
            rank = self._loaded.get(aid)
            n = spec.pages_for(rank) if rank else 0
            for pi in range(spec.pages_per_adapter):
                page = int(self.adapter_tables[aid, pi])
                if pi < n:
                    if not 0 <= page < spec.num_pages:
                        raise AssertionError(
                            f"adapter {aid} page {pi} out of range: {page}"
                        )
                    if page in owned:
                        raise AssertionError(
                            f"page {page} owned twice: {owned[page]} and "
                            f"({aid}, {pi})"
                        )
                    owned[page] = (aid, pi)
                elif page != spec.num_pages:
                    raise AssertionError(
                        f"adapter {aid} unused entry {pi} not sentinel: "
                        f"{page}"
                    )
        expected = np.zeros(spec.num_pages, dtype=np.int32)
        for page in owned:
            expected[page] = 1
        for s in range(spec.max_seqs):
            aid = int(self.slot_adapter[s])
            if aid == -1:
                continue
            if aid not in self._loaded:
                raise AssertionError(
                    f"slot {s} attached to unloaded adapter {aid}"
                )
            for pi in range(spec.pages_for(self._loaded[aid])):
                expected[self.adapter_tables[aid, pi]] += 1
        if not np.array_equal(self._adapter_refcounts, expected):
            bad = np.nonzero(self._adapter_refcounts != expected)[0]
            raise AssertionError(
                f"adapter refcounts diverge at pages {bad.tolist()}: "
                f"have {self._adapter_refcounts[bad].tolist()}, "
                f"derived {expected[bad].tolist()}"
            )
        free = set(self._free_adapter_pages)
        if len(free) != len(self._free_adapter_pages):
            raise AssertionError("duplicate pages in the adapter free heap")
        if free & set(owned):
            raise AssertionError(
                f"pages both owned and free: {sorted(free & set(owned))}"
            )
        if len(owned) + len(free) != spec.num_pages:
            raise AssertionError(
                f"adapter page conservation broken: {len(owned)} owned + "
                f"{len(free)} free != {spec.num_pages}"
            )
        for g in spec.layer_guids:
            for k in _AB_NAMES:
                row = np.asarray(self._pools[g][k][spec.num_pages])
                if row.any():
                    raise AssertionError(
                        f"layer {g} {k}: sentinel row is not zero"
                    )

    def telemetry_gauges(self) -> Dict[str, float]:
        free = len(self._free_adapter_pages)
        return {
            "adapters_loaded": float(len(self._loaded)),
            "adapter_pages_live": float(self.spec.num_pages - free),
            "adapter_pages_free": float(free),
            "adapter_slots_attached": float(
                int((self.slot_adapter >= 0).sum())
            ),
        }

    def telemetry_counters(self) -> Dict[str, int]:
        return {
            "adapter_loads_total": self.loads,
            "adapter_unloads_total": self.unloads,
            "adapter_attaches_total": self.attaches,
            "adapter_detaches_total": self.detaches,
        }


# -- jit-side application (called inside the engine's traced steps) ----------


def apply_adapter_qkv(x, q, k, v, ad, guid):
    """Fuse the per-row LoRA deltas into the Q/K/V projections right
    after ``mha_project_qkv``. ``ad`` is None (no pool — the traced HLO
    is byte-for-byte today's engine) or ``(tbl, has, pools)``; rows with
    ``has`` False take the UNMODIFIED q/k/v elements through the select,
    so base-model rows stay bit-identical whether or not a pool rides
    the step. K/V deltas land BEFORE the cache writes — the paged/Pallas
    attention cores then read adapted history with no kernel change.
    From ``adapter_tokens`` ``ad`` carries a fourth entry, the one-hot
    ``owner`` of ``lora_delta_qkv``, and ``has`` is per token [1, s]."""
    if ad is None:
        return q, k, v
    tbl, has, pools, *owner = ad
    p = pools[guid]
    dq, dk, dv = lora_delta_qkv(
        x, tbl, p["a_q"], p["b_q"], p["a_k"], p["b_k"], p["a_v"], p["b_v"],
        *owner,
    )
    sel = has.reshape(has.shape + (1,) * (q.ndim - has.ndim))
    q = jnp.where(sel, (q.astype(jnp.float32) + dq).astype(q.dtype), q)
    k = jnp.where(sel, (k.astype(jnp.float32) + dk).astype(k.dtype), k)
    v = jnp.where(sel, (v.astype(jnp.float32) + dv).astype(v.dtype), v)
    return q, k, v


def apply_adapter_out(attn, y, ad, guid):
    """Fuse the output-projection LoRA delta after ``mha_project_out`` —
    the post-kernel epilogue: the attention core (dense or Pallas)
    already ran, untouched."""
    if ad is None:
        return y
    tbl, has, pools, *owner = ad
    p = pools[guid]
    dy = lora_delta_out(attn, tbl, p["a_o"], p["b_o"], *owner)
    sel = has.reshape(has.shape + (1,) * (y.ndim - has.ndim))
    return jnp.where(sel, (y.astype(jnp.float32) + dy).astype(y.dtype), y)


def adapter_rows(ad, slot_ids):
    """Gather a slot-indexed ``ad`` down to a compacted batch (the
    chunked-prefill impls, whose row i serves slot ``slot_ids[i]``)."""
    if ad is None:
        return None
    tbl, has, pools = ad
    return tbl[slot_ids], has[slot_ids], pools


def adapter_tokens(ad, slots):
    """A slot-indexed ``ad`` for ONE packed row whose token t serves slot
    ``slots[t]`` (the packed prefill; a padding token names the slot past
    the last, which owns nothing): ``has`` per token and the one-hot
    ``owner`` that ``lora_delta_qkv`` / ``lora_delta_out`` select by."""
    if ad is None:
        return None
    tbl, has, pools = ad
    n = tbl.shape[0]
    owner = jax.nn.one_hot(slots, n, dtype=jnp.float32)
    has = has[jnp.minimum(slots, n - 1)] & (slots < n)
    return tbl, has[None, :], pools, owner


# -- test/bench weight helper ------------------------------------------------


def make_lora_weights(spec: AdapterPoolSpec, rank: int, seed: int = 0):
    """Deterministic random LoRA factors shaped for :meth:`AdapterPool
    .load` — the tests' and bench's stand-in for real fine-tunes."""
    rng = np.random.default_rng(seed)
    e = spec.embed_dim
    weights = {}
    for g in spec.layer_guids:
        weights[g] = {
            k: rng.standard_normal((e, rank)).astype(np.float32) * 0.1
            if k.startswith("a_")
            else rng.standard_normal((rank, e)).astype(np.float32) * 0.1
            for k in _AB_NAMES
        }
    return weights
