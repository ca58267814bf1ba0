"""FX1xx — dispatch-race: mutable host state into the async jit queue.

The PR 3 bug class. ``jnp.asarray(x)`` does NOT read ``x``'s buffer at
call time: the read is deferred behind JAX's async dispatch queue. If
``x`` is live scheduler/allocator state (``cache.lengths``, paged
block tables) that the host mutates between iterations, the deferred
read races the mutation and the jitted step silently consumes a future
iteration's state — wrong-context decodes under load, unreproducible
off-peak.

The blessed idiom is ONE of:

* ``serving.engine.snapshot(attr)`` — the repo-wide snapshot helper;
* an explicit ``attr.copy()`` / ``np.array(attr)`` inside the
  ``jnp.asarray`` call.

Rules (attribute-name granularity — ``ast`` cannot resolve types, so a
mutated attribute NAME taints every load of that name; accepted
findings go to the baseline):

* **FX101** — ``jnp.asarray(...)`` whose argument contains a load of an
  attribute that is subscript-mutated somewhere in the scanned file set
  (``obj.attr[i] = ...`` / ``obj.attr[i] += ...``), with no snapshot
  wrapper between the asarray and the load.
* **FX102** — the same un-snapshotted attribute passed directly to a
  callable that was bound from ``jax.jit(...)`` (the array would be
  committed to the queue by the call itself).
* **FX103** — reconcile-phase code (a function taking an
  ``InflightStep`` — by annotation, or a parameter named ``step``/
  ``inflight``) loading a mutated attribute through a ``cache`` object
  instead of the step record. The async double-buffered engine commits
  a step's results one iteration after its dispatch; by reconcile time
  ``cache.lengths`` / ``cache.block_tables`` describe the NEXT step,
  so acceptance/rollback/emit decisions made against them are wrong
  exactly when the pipeline is full — the reconcile must read the
  ``InflightStep`` snapshot (``step.lengths``, ``step.active``,
  ``step.participants``) and nothing else. The same rule covers the
  tree-verify plan (``tree_parents`` / ``tree_plan``): the parent
  table and per-slot ``DraftTree`` travel WITH the step, so a
  scheduler-side mirror describes the NEXT iteration's trees and the
  accept walk would score this step's logits against a different
  topology.
* **FX104** — a search-trace recording call (a ``candidate``/
  ``header``/``event``/``result``/``phase`` method on an object whose
  access path names ``trace``) whose argument loads a mutated
  attribute without a copy. Trace rows are a HISTORY: the searcher
  keeps mutating its view maps / graph tables after the record is
  taken, so a captured live reference lets rows rewrite themselves
  retroactively — the exported artifact then describes a search that
  never happened. Same deferred-read shape as FX101, different queue
  (the JSONL writer instead of the jit dispatch). Pass scalars or a
  fresh ``dict(...)``/``list(...)``/``.copy()``.
* **FX105** — reconcile-phase code loading chunked-prefill progress
  state (``prefill_seq`` / ``prefill_pos`` / ``prefill_dispatched``)
  from anywhere but the step record. A chunk step's cursor travels
  WITH the step (``step.chunks[slot] = (start, size, final)``): the
  dispatcher advances the live ``prefill_dispatched`` cursor the
  moment the NEXT chunk leaves, so by reconcile time the request
  attrs describe a later dispatch — final-chunk / emit decisions made
  against them double-emit or drop the prompt's sampled token. Stores
  are the commit itself (``req.prefill_pos = start + size``) and stay
  sanctioned; loads must come through the step parameter.
* **FX106** — refcount-mutation discipline for the prefix-sharing
  allocator. With hashed prefix pages, a page's refcount is re-derived
  from every live block table (``check_invariants``), so ANY code that
  writes a ``block_tables`` entry or pushes/pops the ``_free_pages``
  heap outside the blessed allocator helpers desynchronizes refcounts
  from ownership — a shared page freed behind its sharers' backs, or a
  leaked page the conservation gauge flags forever. The blessed
  helpers (``_install_page``/``_incref``/``_decref_page``/
  ``_cow_page``/``alloc``/``alloc_shared``/``ensure_position``/
  ``truncate``/``free``/... — see ``_REFCOUNT_BLESSED``) are the ONLY
  functions allowed to touch either structure; everything else must
  route through them.
* **FX107** — swap/eviction ledger discipline for the
  pressure-degradation allocator. The host-swap table (``_swapped``:
  handle -> staged pages + bytes), the publication-only LRU
  (``_pub_only``: page -> (stamp, wait window)), and the downed host
  set (``_hosts_down``) are each audited by ``check_invariants`` —
  the swap-bytes budget, the page conservation sum, and admission
  routing all re-derive from them. A raw mutation (subscript store,
  ``del``, rebinding, or a mutating method call like ``.pop()``/
  ``.clear()``/``.add()``) outside the blessed helpers
  (``swap_out``/``swap_in``/``discard_swap``/``_incref``/
  ``_decref_page``/``_evict_prefix_page``/``mark_host_down``/
  ``mark_host_up`` — see ``_SWAP_BLESSED``) double-frees staged
  bytes, resurrects evicted pages, or routes admissions to a dead
  host. Same blessed-set machinery as FX106, different ledgers.
* **FX108** — cross-engine swap-handle lifetime (the prefill→decode
  handoff). A handle/record produced by a staging call (``swap_out``/
  ``export_swap``/``stage_out``) is a MOVE token: ``export_swap`` pops
  the source ledger entry and ``import_swap`` installs it under a
  fresh handle, so the original is dead the moment it is consumed.
  Two findings: (1) one function consumes the same staged
  handle/record variable twice (``swap_in``/``import_swap``/
  ``export_swap``/``discard_swap``) — the second consumption restores
  pages the first already owns (a KeyError at best, two engines
  decoding one stream's KV at worst); (2) handoff-phase code (a
  function with a ``src``/``source``/``src_cache``/``source_cache``/
  ``src_engine``/``source_engine`` parameter) loads live pool/table
  state (``k``/``v``/``k_scale``/``v_scale``/``block_tables``/
  ``lengths``/``_swapped``) through that parameter without a staging
  copy — the source engine keeps serving while the handoff reads, so
  a live reference ships rows the next decode step is rewriting; the
  staged record (``export_swap``'s host-side numpy copies) is the
  only sanctioned carrier across the engine boundary.
* **FX109** — tree-verify dispatch discipline: a tree-verify dispatch
  function (``tree`` + ``dispatch`` in the name) captures live
  mutated host allocator state (``lengths`` / ``block_tables`` /
  ``_free_pages``) without a snapshot — the parent table and page
  claims ride the async dispatch queue and the reconcile walks them
  an iteration later, so live allocator state handed to the jitted
  tree step (or stored on the ``InflightStep``) must cross as a
  snapshot. Scalars materialized at call time
  (``int()``/``len()``/``min()``...) are synchronous host reads and
  stay sanctioned, as do Assign/AugAssign store TARGETS (a
  dispatch-side ``cache.lengths[act] += n`` is the commit itself, not
  a capture). The tree-plan state (``tree_parents`` / ``tree_plan``)
  a reconcile reads must come off the step record (reported under
  FX103, whose extension it is).
* **FX110** — adapter-pool ledger discipline for the multi-tenant
  LoRA pool (``serving/tenancy/adapters.AdapterPool``), FX106's rule
  applied to its sibling allocator: a subscript store into an
  ``adapter_tables`` / ``slot_adapter`` / ``_adapter_refcounts``
  attribute, or a ``heapq`` push/pop reaching the
  ``_free_adapter_pages`` heap, outside the blessed pool helpers
  (``load``/``unload``/``attach``/``detach`` and the page-install/
  free seams — see ``_ADAPTER_BLESSED``). The pool's refcounts are
  1 (loaded) + 1 per attached slot and ``check_invariants``
  re-derives them from the tables, so a raw write frees an
  adapter's pages under a slot mid-decode (the gather then reads a
  recycled page: silent weight corruption, the tenant-isolation
  bug) or leaks them forever. The ledger names are disjoint from
  FX106's on purpose — the two allocators can be linted in one pass
  without cross-talk.

* **FX111** — journal-before-publish discipline for the durable
  serving journal (``serving/journal.RequestJournal``): a mutation of
  a request's ``generated`` token list (``.append``/``.extend``/
  ``.insert`` call, subscript store/delete, or rebinding the
  attribute) outside the blessed emit seam (``_emit`` — see
  ``_EMIT_BLESSED``). ``_emit`` is the single point where a token
  becomes stream-visible AND journal-noted (``journal.note``) in the
  same breath; ``_end_iteration`` then flushes the noted run as a
  commit record before the front door can publish it. A raw
  ``req.generated.append(...)`` anywhere else produces a token the
  journal never saw, so a crash-restart replays the journal and
  resumes one token short — the recovered stream silently diverges
  from what the client already received, breaking token-identical
  resume. ``__init__`` is construction, not emission (same rationale
  as FX106), and recovery code seeds ``generated`` via the Request
  constructor for exactly that reason.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from flexflow_tpu.analysis.diagnostics import (
    Diagnostic,
    collect_jitted_names,
    name_chain,
)

RULES = {
    "FX101": "mutable host attribute into jnp.asarray without a snapshot",
    "FX102": "mutable host attribute passed raw into a jitted callable",
    "FX103": "reconcile reads live cache state instead of the "
    "InflightStep snapshot",
    "FX104": "search-trace hook captures live mutable state without a "
    "copy",
    "FX105": "reconcile reads live chunk-progress attrs instead of the "
    "InflightStep chunk record",
    "FX106": "block-table write or free-heap mutation outside the "
    "blessed refcount helpers",
    "FX107": "swap/eviction ledger mutation outside the blessed "
    "allocator helpers",
    "FX108": "cross-engine swap handle consumed twice, or handoff code "
    "reading live source-engine pool state",
    "FX109": "tree-verify dispatch captures live host state",
    "FX110": "adapter-pool table/refcount write or free-heap mutation "
    "outside the blessed AdapterPool helpers",
    "FX111": "stream-visible token commit (a 'generated' list "
    "mutation) outside the blessed journal-noting emit seam",
}

#: the only functions allowed to write `block_tables` entries or touch
#: the `_free_pages` heap (FX106) — the allocator's refcount seams plus
#: the fault injector's sanctioned steal/restore pair. `__init__` is
#: construction, not mutation (same rationale as collect_mutated_attrs).
_REFCOUNT_BLESSED = {
    "__init__",
    "alloc",
    "alloc_shared",
    "ensure_position",
    "truncate",
    "free",
    "claim",
    "end_inflight",
    "_release_page",
    "_decref_entry",
    "_decref_page",
    "_incref",
    "_cow_page",
    "_install_page",
    "register_prefix",
    "_page_faults",
    "release_stolen_pages",
    # PR 14 pressure-degradation seams: eviction reroutes a retained
    # page back to the heap, _pop_free_page is the evict-or-pop gate
    # every allocation path drains, swap_in reinstalls staged pages
    "_evict_prefix_page",
    "_pop_free_page",
    "swap_in",
}

#: the only functions allowed to mutate the swap/eviction ledgers
#: (FX107): the host-swap table `_swapped`, the publication-only LRU
#: `_pub_only`, and the downed-host set `_hosts_down`. `__init__` is
#: construction, not mutation (same rationale as FX106).
_SWAP_BLESSED = {
    "__init__",
    "swap_out",
    "swap_in",
    "discard_swap",
    "_incref",
    "_decref_page",
    "_evict_prefix_page",
    "mark_host_down",
    "mark_host_up",
    # cross-engine handoff seams (FX108's domain): export pops the
    # local ledger entry, import installs under a fresh local handle
    "export_swap",
    "import_swap",
}

_SWAP_LEDGER_ATTRS = {"_swapped", "_pub_only", "_hosts_down"}

#: the only functions allowed to write the multi-LoRA pool's ledgers
#: (FX110): the load/unload/attach/detach surface the scheduler calls
#: plus the page-install/free seams they delegate to. `__init__` is
#: construction, not mutation (same rationale as FX106).
_ADAPTER_BLESSED = {
    "__init__",
    "load",
    "unload",
    "attach",
    "detach",
    "_install_adapter_page",
    "_free_adapter_page",
    "_pop_free_adapter_page",
}

#: AdapterPool's refcount-bearing ledgers — deliberately disjoint from
#: FX106's block_tables/_free_pages names so both allocators lint in
#: one pass without cross-talk
_ADAPTER_LEDGER_ATTRS = {
    "adapter_tables",
    "slot_adapter",
    "_adapter_refcounts",
}

#: the only functions allowed to mutate a request's `generated` token
#: list (FX111): `_emit` pairs the append with `journal.note` so every
#: stream-visible token is journal-noted before the front door can
#: publish it. `__init__` is construction, not emission (same
#: rationale as FX106) — recovery seeds `generated` through the
#: Request constructor.
_EMIT_BLESSED = {
    "__init__",
    "_emit",
}

#: list-method calls that grow or rewrite the `generated` token run
_GENERATED_MUTATORS = {"append", "extend", "insert"}

#: method calls that mutate a dict/set ledger in place
_SWAP_MUTATING_METHODS = {
    "pop",
    "popitem",
    "update",
    "clear",
    "setdefault",
    "add",
    "discard",
    "remove",
}

_STEP_PARAM_NAMES = {"step", "inflight"}

#: calls that PRODUCE a staged cross-engine token (handle or record):
#: the variable they bind is a move token, live until first consumption
_HANDOFF_STAGING_CALLS = {"swap_out", "export_swap", "stage_out"}

#: calls that CONSUME a staged token — each kills its argument
#: (export pops the ledger entry; import/swap_in install it; discard
#: returns the budget). A second consumption is the FX108 bug class.
_HANDOFF_CONSUMING_CALLS = {
    "swap_in",
    "import_swap",
    "export_swap",
    "discard_swap",
}

#: parameter names marking a function as handoff-phase code holding a
#: reference to the SOURCE engine/cache of a KV movement
_HANDOFF_SRC_PARAMS = {
    "src",
    "source",
    "src_cache",
    "source_cache",
    "src_engine",
    "source_engine",
}

#: live pool/table state on an engine's cache that must never cross
#: the engine boundary by reference — the staged record is the carrier
_HANDOFF_POOL_ATTRS = {
    "k",
    "v",
    "k_scale",
    "v_scale",
    "block_tables",
    "lengths",
    "_swapped",
}

#: chunked-prefill cursor state on Request — the live view a chunk
#: reconcile must never read (FX105); the snapshot is `step.chunks`
_CHUNK_PROGRESS_ATTRS = {"prefill_seq", "prefill_pos", "prefill_dispatched"}

#: host allocator state a tree-verify dispatch must snapshot before the
#: jitted tree step captures it (FX109). Deliberately NOT the full
#: mutated set: the device pools (`cache.k`/`cache.v`) are donated
#: device arrays that legitimately ride into the jit raw.
_TREE_DISPATCH_HOST_ATTRS = {
    "lengths",
    "block_tables",
    "_free_pages",
    "_free_pages_h",
}

#: single-name builtins whose call materializes a host SCALAR at call
#: time — a synchronous read, immune to the deferred-read race, so a
#: tree-verify dispatch may apply them to live state (`int(lengths[s])`)
_TREE_DISPATCH_SCALARS = {"int", "float", "bool", "len", "min", "max"}

#: tree-verify plan state on InflightStep — the dispatched parent table
#: and the per-slot DraftTree plan; the reconcile's accept walk must
#: read these through the step record, never a scheduler-side mirror
#: (FX103's tree extension)
_TREE_PLAN_ATTRS = {"tree_parents", "tree_plan"}

_ASARRAY_CHAINS = {("jnp", "asarray"), ("jax", "numpy", "asarray")}
_SNAPSHOT_NAMES = {"snapshot"}
# builtins that materialize a fresh container — a copy by construction
_COPYING_BUILTINS = {"dict", "list", "tuple", "sorted", "set", "frozenset"}

#: SearchTrace recording surface (telemetry/search_trace.py); `phase`
#: is included for its kwargs
_TRACE_METHODS = {"candidate", "header", "event", "result", "phase"}


def _is_asarray(func: ast.AST) -> bool:
    return name_chain(func) in _ASARRAY_CHAINS


def _is_snapshot_call(node: ast.Call) -> bool:
    """A call that yields an immutable copy: ``x.copy()``,
    ``np.array(x)`` (copies by default), a fresh-container builtin
    (``dict(x)``/``list(x)``/...), or the blessed ``snapshot(x)``
    helper."""
    if isinstance(node.func, ast.Attribute) and node.func.attr == "copy":
        return True
    chain = name_chain(node.func)
    if chain is None:
        return False
    if chain[-1] in _SNAPSHOT_NAMES:
        return True
    if len(chain) == 1 and chain[0] in _COPYING_BUILTINS:
        return True
    return len(chain) >= 2 and chain[-2] in ("np", "numpy") and (
        chain[-1] == "array"
    )


def collect_mutated_attrs(trees: Dict[str, ast.Module]) -> Set[str]:
    """Attribute names that are subscript-assigned anywhere in the file
    set — the in-place array writes a deferred host read can race.
    Writes inside ``__init__`` don't count: construction precedes
    sharing, so init-time population (e.g. a cache's per-layer device
    dicts) cannot race a dispatch."""
    mutated: Set[str] = set()

    def record(target: ast.AST) -> None:
        if isinstance(target, (ast.Tuple, ast.List)):
            for el in target.elts:
                record(el)
        elif isinstance(target, ast.Subscript) and isinstance(
            target.value, ast.Attribute
        ):
            mutated.add(target.value.attr)

    def visit(node: ast.AST) -> None:
        if (
            isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
            and node.name == "__init__"
        ):
            return
        if isinstance(node, ast.Assign):
            for t in node.targets:
                record(t)
        elif isinstance(node, ast.AugAssign):
            record(node.target)
        for child in ast.iter_child_nodes(node):
            visit(child)

    for tree in trees.values():
        visit(tree)
    return mutated


def _tainted_loads(
    expr: ast.AST, mutated: Set[str]
) -> List[Tuple[str, int]]:
    """(attr, line) for every load of a mutated attribute inside `expr`
    that is not protected by a snapshot wrapper."""
    found: List[Tuple[str, int]] = []

    def visit(node: ast.AST) -> None:
        if isinstance(node, ast.Call) and _is_snapshot_call(node):
            return  # everything below this call is snapshotted
        if (
            isinstance(node, ast.Attribute)
            and isinstance(node.ctx, ast.Load)
            and node.attr in mutated
        ):
            found.append((node.attr, node.lineno))
            return  # the inner chain is the same access path
        for child in ast.iter_child_nodes(node):
            visit(child)

    visit(expr)
    return found


def _annotation_names(node: ast.AST) -> Set[str]:
    """Every dotted/string name appearing in an annotation expression
    (handles Optional["InflightStep"], engine.InflightStep, etc.)."""
    names: Set[str] = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            names.add(sub.id)
        elif isinstance(sub, ast.Attribute):
            names.add(sub.attr)
        elif isinstance(sub, ast.Constant) and isinstance(sub.value, str):
            names.add(sub.value.rsplit(".", 1)[-1])
    return names


def _step_params(fn) -> Set[str]:
    """Parameter names of `fn` that carry an InflightStep — annotated
    as one, or conventionally named step/inflight. Non-empty marks the
    function as reconcile-phase code — EXCEPT dispatch-side functions
    ('dispatch' in the name): they take the snapshot, so they read live
    state by definition (e.g. decode_dispatch's `chain` step is a
    device-token source, not a commit target)."""
    if "dispatch" in fn.name:
        return set()
    params: Set[str] = set()
    args = list(fn.args.posonlyargs) + list(fn.args.args) + list(
        fn.args.kwonlyargs
    )
    for a in args:
        if a.arg in _STEP_PARAM_NAMES:
            params.add(a.arg)
        elif a.annotation is not None and (
            "InflightStep" in _annotation_names(a.annotation)
        ):
            params.add(a.arg)
    return params


def _reconcile_violations(
    fn, mutated: Set[str]
) -> List[Tuple[str, int]]:
    """(attr, line) for loads of a mutated attribute reached through a
    `cache` object inside a reconcile-phase function — live allocator/
    length state the snapshot on the step record exists to replace.
    Loads through the step parameter (step.lengths) and non-cache state
    (self.running, self.stats) are the sanctioned paths."""
    found: List[Tuple[str, int]] = []
    for node in ast.walk(fn):
        if not (
            isinstance(node, ast.Attribute)
            and isinstance(node.ctx, ast.Load)
            and node.attr in mutated
        ):
            continue
        chain = name_chain(node)
        if chain is not None and "cache" in chain[:-1]:
            found.append((node.attr, node.lineno))
    return found


def _chunk_progress_violations(
    fn, step_params: Set[str]
) -> List[Tuple[str, int]]:
    """(attr, line) for loads of chunked-prefill cursor state inside a
    reconcile-phase function that do not come through the step
    parameter. Stores (the commit: ``req.prefill_pos = start + size``)
    are the sanctioned write-back and never match; the sanctioned read
    is the step's own record (``step.chunks[slot]``)."""
    found: List[Tuple[str, int]] = []
    for node in ast.walk(fn):
        if not (
            isinstance(node, ast.Attribute)
            and isinstance(node.ctx, ast.Load)
            and node.attr in _CHUNK_PROGRESS_ATTRS
        ):
            continue
        chain = name_chain(node)
        if chain is not None and chain[0] in step_params:
            continue
        found.append((node.attr, node.lineno))
    return found


def _is_tree_dispatch(fn) -> bool:
    """Tree-verify dispatch code, by the name convention _step_params
    uses to EXEMPT dispatch functions from FX103/FX105 ('tree' +
    'dispatch'): it takes the snapshots, so it reads live state by
    definition — but what it hands the jitted tree step or stores on the
    InflightStep must be snapshotted (FX109): the parent table is read
    behind the async dispatch queue and walked again at reconcile, an
    iteration after the live tables have moved on."""
    return "tree" in fn.name and "dispatch" in fn.name


def _tree_capture_violations(
    fn, mutated: Set[str]
) -> List[Tuple[str, int]]:
    """(attr, line) for loads of live host allocator state inside a
    tree-verify dispatch function with no snapshot wrapper and no
    scalar materialization. The jitted tree step reads its captures
    behind the async dispatch queue, after this function returns, so
    every mutable host array must cross as a copy. Store targets (a
    dispatch-side ``cache.lengths[act] += n``) are the commit itself
    and never match."""
    attrs = _TREE_DISPATCH_HOST_ATTRS & mutated
    found: List[Tuple[str, int]] = []

    def visit(node: ast.AST) -> None:
        if isinstance(node, ast.Call):
            if _is_snapshot_call(node):
                return  # copied below here — that IS the snapshot
            chain = name_chain(node.func)
            if (
                chain is not None
                and len(chain) == 1
                and chain[0] in _TREE_DISPATCH_SCALARS
            ):
                return  # scalar materialized at call time: synchronous
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            # store targets are the dispatch-side commit; only the
            # VALUE can leak a live reference
            visit(node.value)
            return
        if (
            isinstance(node, ast.Attribute)
            and isinstance(node.ctx, ast.Load)
            and node.attr in attrs
        ):
            chain = name_chain(node)
            if chain is not None and "cache" in chain[:-1]:
                found.append((node.attr, node.lineno))
                return
        for child in ast.iter_child_nodes(node):
            visit(child)

    for stmt in fn.body:
        visit(stmt)
    return found


def _tree_plan_violations(
    fn, step_params: Set[str]
) -> List[Tuple[str, int]]:
    """(attr, line) for loads of tree-verify plan state
    (``tree_parents`` / ``tree_plan``) inside a reconcile-phase
    function that do not come through the step parameter. The parent
    table and the per-slot DraftTree plan travel WITH the
    InflightStep; under async double-buffering a scheduler-side mirror
    describes the NEXT iteration's trees, so an accept walk against it
    scores this step's logits on a different topology — wrong branch
    accepted, wrong rows compacted."""
    found: List[Tuple[str, int]] = []
    for node in ast.walk(fn):
        if not (
            isinstance(node, ast.Attribute)
            and isinstance(node.ctx, ast.Load)
            and node.attr in _TREE_PLAN_ATTRS
        ):
            continue
        chain = name_chain(node)
        if chain is not None and chain[0] in step_params:
            continue
        found.append((node.attr, node.lineno))
    return found


def _refcount_violations(tree: ast.Module) -> List[Tuple[str, int, str]]:
    """(description, line, offender) for refcount-bearing mutations
    outside the blessed allocator helpers: a subscript store into a
    ``block_tables`` attribute, or a ``heapq.heappush``/``heappop``
    whose argument reaches a ``_free_pages`` attribute (or a
    ``_free_pages_h`` per-host heap — the pod-serving partition of the
    same pool). Module-level code reports under the pseudo-name
    '<module>'."""
    found: List[Tuple[str, int, str]] = []

    def is_bt_store(node: ast.AST) -> bool:
        targets: List[ast.AST] = []
        if isinstance(node, ast.Assign):
            targets = list(node.targets)
        elif isinstance(node, ast.AugAssign):
            targets = [node.target]
        for t in targets:
            if isinstance(t, (ast.Tuple, ast.List)):
                targets.extend(t.elts)
            elif isinstance(t, ast.Subscript) and isinstance(
                t.value, ast.Attribute
            ) and t.value.attr == "block_tables":
                return True
        return False

    def heap_op_attr(node: ast.AST) -> Optional[str]:
        if not (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in ("heappush", "heappop")
        ):
            return None
        for arg in node.args:
            for sub in ast.walk(arg):
                if isinstance(sub, ast.Attribute) and (
                    sub.attr in ("_free_pages", "_free_pages_h")
                ):
                    return sub.attr
        return None

    def visit(node: ast.AST, owner: str) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            owner = node.name
            if owner in _REFCOUNT_BLESSED:
                return
        if is_bt_store(node):
            found.append(
                ("writes a 'block_tables' entry", node.lineno, owner)
            )
        else:
            heap = heap_op_attr(node)
            if heap is not None:
                found.append(
                    (f"mutates the '{heap}' heap", node.lineno, owner)
                )
        for child in ast.iter_child_nodes(node):
            visit(child, owner)

    visit(tree, "<module>")
    return found


def _adapter_violations(tree: ast.Module) -> List[Tuple[str, int, str]]:
    """(description, line, offender) for adapter-pool ledger mutations
    outside the blessed AdapterPool helpers (FX110): a subscript store
    (or AugAssign) into an ``adapter_tables`` / ``slot_adapter`` /
    ``_adapter_refcounts`` attribute, or a ``heapq.heappush``/
    ``heappop`` whose argument reaches the ``_free_adapter_pages``
    heap. Reads never match — ``slot_tables`` gathers
    from the ledgers freely, and ``check_invariants`` audits them.
    Module-level code reports under the pseudo-name '<module>'."""
    found: List[Tuple[str, int, str]] = []

    def ledger_store_attr(node: ast.AST) -> Optional[str]:
        targets: List[ast.AST] = []
        if isinstance(node, ast.Assign):
            targets = list(node.targets)
        elif isinstance(node, ast.AugAssign):
            targets = [node.target]
        for t in targets:
            if isinstance(t, (ast.Tuple, ast.List)):
                targets.extend(t.elts)
            elif isinstance(t, ast.Subscript) and isinstance(
                t.value, ast.Attribute
            ) and t.value.attr in _ADAPTER_LEDGER_ATTRS:
                return t.value.attr
        return None

    def heap_reached(node: ast.AST) -> bool:
        if not (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in ("heappush", "heappop")
        ):
            return False
        for arg in node.args:
            for sub in ast.walk(arg):
                if isinstance(sub, ast.Attribute) and (
                    sub.attr == "_free_adapter_pages"
                ):
                    return True
        return False

    def visit(node: ast.AST, owner: str) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            owner = node.name
            if owner in _ADAPTER_BLESSED:
                return
        attr = ledger_store_attr(node)
        if attr is not None:
            found.append(
                (f"writes the '{attr}' ledger", node.lineno, owner)
            )
        elif heap_reached(node):
            found.append(
                ("mutates the '_free_adapter_pages' heap", node.lineno,
                 owner)
            )
        for child in ast.iter_child_nodes(node):
            visit(child, owner)

    visit(tree, "<module>")
    return found


def _journal_violations(tree: ast.Module) -> List[Tuple[str, int, str]]:
    """(description, line, offender) for stream-visible token commits
    outside the blessed emit seam (FX111): an ``.append``/``.extend``/
    ``.insert`` call on a ``generated`` attribute, a subscript store or
    ``del`` into one, or rebinding the attribute itself, anywhere but
    ``_emit`` (see ``_EMIT_BLESSED``). Reads never match — the
    scheduler's length checks, the front door's publish cursor, and the
    journal's submit snapshot all read ``generated`` freely. Module-
    level code reports under the pseudo-name '<module>'."""
    found: List[Tuple[str, int, str]] = []

    def is_generated_attr(node: ast.AST) -> bool:
        return isinstance(node, ast.Attribute) and node.attr == "generated"

    def mutation_of(node: ast.AST) -> Optional[str]:
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in _GENERATED_MUTATORS
            and is_generated_attr(node.func.value)
        ):
            return f"calls .{node.func.attr}() on a 'generated' list"
        targets: List[ast.AST] = []
        if isinstance(node, ast.Assign):
            targets = list(node.targets)
        elif isinstance(node, ast.AugAssign):
            targets = [node.target]
        elif isinstance(node, ast.Delete):
            targets = list(node.targets)
        for t in targets:
            if isinstance(t, (ast.Tuple, ast.List)):
                targets.extend(t.elts)
            elif isinstance(t, ast.Subscript) and is_generated_attr(
                t.value
            ):
                return "stores into a 'generated' list slot"
            elif is_generated_attr(t):
                return "rebinds a 'generated' attribute"
        return None

    def visit(node: ast.AST, owner: str) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            owner = node.name
            if owner in _EMIT_BLESSED:
                return
        what = mutation_of(node)
        if what is not None:
            found.append((what, node.lineno, owner))
        for child in ast.iter_child_nodes(node):
            visit(child, owner)

    visit(tree, "<module>")
    return found


def _swap_violations(tree: ast.Module) -> List[Tuple[str, int, str]]:
    """(description, line, offender) for swap/eviction ledger mutations
    outside the blessed allocator helpers (FX107): subscript stores,
    ``del`` statements, attribute rebinding, or in-place mutating
    method calls reaching ``_swapped`` / ``_pub_only`` /
    ``_hosts_down``. Reads never match — resurrection checks, budget
    math, and the invariant audit all read freely."""
    found: List[Tuple[str, int, str]] = []

    def ledger_attr_of(node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Attribute) and (
            node.attr in _SWAP_LEDGER_ATTRS
        ):
            return node.attr
        return None

    def store_target_attr(t: ast.AST) -> Optional[str]:
        # `x._swapped[h] = ...` / `x._swapped = {}` / `del x._pub_only[p]`
        if isinstance(t, ast.Subscript):
            return ledger_attr_of(t.value)
        return ledger_attr_of(t)

    def visit(node: ast.AST, owner: str) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            owner = node.name
            if owner in _SWAP_BLESSED:
                return
        targets: List[ast.AST] = []
        if isinstance(node, ast.Assign):
            targets = list(node.targets)
        elif isinstance(node, (ast.AugAssign,)):
            targets = [node.target]
        elif isinstance(node, ast.Delete):
            targets = list(node.targets)
        for t in targets:
            if isinstance(t, (ast.Tuple, ast.List)):
                targets.extend(t.elts)
                continue
            attr = store_target_attr(t)
            if attr is not None:
                found.append(
                    (f"writes the '{attr}' ledger", node.lineno, owner)
                )
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in _SWAP_MUTATING_METHODS
        ):
            attr = ledger_attr_of(node.func.value)
            if attr is not None:
                found.append(
                    (
                        f"mutates the '{attr}' ledger via "
                        f".{node.func.attr}()",
                        node.lineno,
                        owner,
                    )
                )
        for child in ast.iter_child_nodes(node):
            visit(child, owner)

    visit(tree, "<module>")
    return found


def _handle_reuse_violations(fn) -> List[Tuple[str, str, int]]:
    """(variable, consumer, line) for every consumption of a staged
    handle/record variable AFTER its first — the double-restore shape
    of FX108. Name-granular within one function: a variable bound from
    a staging call (``h = cache.swap_out(slot)``, ``rec =
    cache.export_swap(h)``) is a move token; each consuming call
    taking it as an argument kills it, and a later consumption (or one
    inside a loop body, which re-runs) is reported. Rebinding from a
    fresh staging call revives the name (a loop-carried
    ``handle = stage(...)`` per iteration is the sanctioned idiom)."""
    found: List[Tuple[str, str, int]] = []
    consumed: Dict[str, int] = {}  # var -> line of first consumption
    staged: Dict[str, int] = {}  # var -> loop depth at staging

    def call_method(node: ast.Call) -> Optional[str]:
        if isinstance(node.func, ast.Attribute):
            return node.func.attr
        if isinstance(node.func, ast.Name):
            return node.func.id
        return None

    loop_depth = 0

    def visit(node: ast.AST) -> None:
        nonlocal loop_depth
        if isinstance(node, ast.Assign) and isinstance(
            node.value, ast.Call
        ):
            method = call_method(node.value)
            if method in _HANDOFF_STAGING_CALLS:
                visit(node.value)  # args may consume earlier tokens
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        staged[t.id] = loop_depth
                        consumed.pop(t.id, None)
                return
        if isinstance(node, ast.Call):
            method = call_method(node)
            if method in _HANDOFF_CONSUMING_CALLS:
                for arg in node.args:
                    if not (
                        isinstance(arg, ast.Name) and arg.id in staged
                    ):
                        continue
                    # a token staged OUTSIDE a loop but consumed inside
                    # one is consumed on every iteration — same bug as
                    # two sequential consumptions
                    if arg.id in consumed or loop_depth > staged[arg.id]:
                        found.append((arg.id, method, node.lineno))
                    consumed.setdefault(arg.id, node.lineno)
        in_loop = isinstance(node, (ast.For, ast.While, ast.AsyncFor))
        if in_loop:
            loop_depth += 1
        for child in ast.iter_child_nodes(node):
            visit(child)
        if in_loop:
            loop_depth -= 1

    for stmt in fn.body:
        visit(stmt)
    return found


def _src_params(fn) -> Set[str]:
    """Parameter names of `fn` that carry the SOURCE engine/cache of a
    handoff — by convention (src/source/src_cache/...), the same
    name-granular marking _step_params uses for reconcile code."""
    params: Set[str] = set()
    args = list(fn.args.posonlyargs) + list(fn.args.args) + list(
        fn.args.kwonlyargs
    )
    for a in args:
        if a.arg in _HANDOFF_SRC_PARAMS:
            params.add(a.arg)
    return params


def _live_source_violations(
    fn, src_params: Set[str]
) -> List[Tuple[str, int]]:
    """(attr, line) for loads of live pool/table state reached through
    a source-engine parameter without a staging copy. The copy wrappers
    _is_snapshot_call blesses (``np.array``/``.copy()``/``snapshot``)
    sanction the load — they ARE the staging — as do the staging calls
    themselves (``source.export_swap(...)`` reads `_swapped` by
    design, through a blessed method)."""
    found: List[Tuple[str, int]] = []

    def visit(node: ast.AST) -> None:
        if isinstance(node, ast.Call):
            if _is_snapshot_call(node):
                return  # copied below here: that IS the staging
            method = (
                node.func.attr
                if isinstance(node.func, ast.Attribute)
                else None
            )
            if method in _HANDOFF_STAGING_CALLS or (
                method in _HANDOFF_CONSUMING_CALLS
            ):
                return  # the blessed movement seams
        if (
            isinstance(node, ast.Attribute)
            and isinstance(node.ctx, ast.Load)
            and node.attr in _HANDOFF_POOL_ATTRS
        ):
            chain = name_chain(node)
            if chain is not None and chain[0] in src_params:
                found.append((node.attr, node.lineno))
                return
        for child in ast.iter_child_nodes(node):
            visit(child)

    for stmt in fn.body:
        visit(stmt)
    return found


def _is_trace_hook(node: ast.Call) -> bool:
    """A SearchTrace recording call: `<...>.trace.candidate(...)`,
    `trace.result(...)`, `self._trace.event(...)` — the method is one
    of the recording surface and the object path names a trace.
    `tracer` objects (telemetry/trace.py, a different API) don't
    match."""
    if not isinstance(node.func, ast.Attribute):
        return False
    if node.func.attr not in _TRACE_METHODS:
        return False
    chain = name_chain(node.func)
    if chain is None or len(chain) < 2:
        return False
    owner = chain[-2]
    return owner in ("trace", "_trace", "search_trace") or (
        owner.endswith("_trace")
    )


def run(trees: Dict[str, ast.Module]) -> List[Diagnostic]:
    mutated = collect_mutated_attrs(trees)
    diags: List[Diagnostic] = []
    for path, tree in trees.items():
        for node in ast.walk(tree):
            if not isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef)
            ):
                continue
            if _is_tree_dispatch(node):
                for attr, line in _tree_capture_violations(node, mutated):
                    diags.append(
                        Diagnostic(
                            "FX109",
                            path,
                            line,
                            f"tree-verify dispatch '{node.name}' "
                            f"captures live host attribute '{attr}' "
                            "into the jitted tree step without a "
                            "snapshot — the parent table and page "
                            "claims ride the async dispatch queue and "
                            "the reconcile walks them an iteration "
                            "later; wrap it in snapshot()/np.array or "
                            "materialize a scalar (int())",
                        )
                    )
            steps = _step_params(node)
            if not steps:
                continue
            for attr, line in _tree_plan_violations(node, steps):
                diags.append(
                    Diagnostic(
                        "FX103",
                        path,
                        line,
                        f"reconcile-phase function '{node.name}' reads "
                        f"tree-verify plan state '{attr}' off the step "
                        "record — the parent table and DraftTree plan "
                        "travel WITH their InflightStep; a scheduler-"
                        "side mirror describes the NEXT iteration's "
                        "trees under async double-buffering, so the "
                        "accept walk scores the wrong topology",
                    )
                )
            for attr, line in _reconcile_violations(node, mutated):
                diags.append(
                    Diagnostic(
                        "FX103",
                        path,
                        line,
                        f"reconcile-phase function '{node.name}' reads "
                        f"live 'cache.{attr}' — between dispatch and "
                        "reconcile that state belongs to the NEXT step; "
                        "read the InflightStep snapshot instead",
                    )
                )
            for attr, line in _chunk_progress_violations(node, steps):
                diags.append(
                    Diagnostic(
                        "FX105",
                        path,
                        line,
                        f"reconcile-phase function '{node.name}' reads "
                        f"live chunk-progress attr '{attr}' — the "
                        "dispatcher advances it for later chunks while "
                        "this step is in flight; read the step's own "
                        "cursor record (step.chunks) instead",
                    )
                )
    for path, tree in trees.items():
        for what, line, owner in _refcount_violations(tree):
            diags.append(
                Diagnostic(
                    "FX106",
                    path,
                    line,
                    f"'{owner}' {what} outside the blessed refcount "
                    "helpers — prefix-shared pages derive their "
                    "refcounts from block tables, so raw mutation "
                    "desynchronizes ownership (shared page freed under "
                    "its sharers, or leaked forever); route through "
                    "alloc/alloc_shared/ensure_position/truncate/free "
                    "or the _incref/_decref seams",
                )
            )
    for path, tree in trees.items():
        for what, line, owner in _swap_violations(tree):
            diags.append(
                Diagnostic(
                    "FX107",
                    path,
                    line,
                    f"'{owner}' {what} outside the blessed swap/"
                    "eviction helpers — check_invariants re-derives "
                    "the swap-bytes budget, page conservation, and "
                    "host routing from these ledgers, so raw mutation "
                    "double-frees staged bytes or resurrects evicted "
                    "pages; route through swap_out/swap_in/"
                    "discard_swap, the _incref/_decref_page seams, or "
                    "mark_host_down/mark_host_up",
                )
            )
    for path, tree in trees.items():
        for what, line, owner in _adapter_violations(tree):
            diags.append(
                Diagnostic(
                    "FX110",
                    path,
                    line,
                    f"'{owner}' {what} outside the blessed AdapterPool "
                    "helpers — adapter-page refcounts are 1 (loaded) "
                    "plus 1 per attached slot, so a raw write frees an "
                    "adapter's pages under a slot mid-decode (the "
                    "gather reads a recycled page: another tenant's "
                    "weights) or leaks them forever; route through "
                    "load/unload/attach/detach or the "
                    "_install_adapter_page/_free_adapter_page seams",
                )
            )
    for path, tree in trees.items():
        for what, line, owner in _journal_violations(tree):
            diags.append(
                Diagnostic(
                    "FX111",
                    path,
                    line,
                    f"'{owner}' {what} outside the blessed emit seam — "
                    "_emit pairs the append with journal.note so every "
                    "stream-visible token is journal-noted before the "
                    "front door publishes it; a raw mutation produces "
                    "a token the journal never saw, so crash-restart "
                    "replay resumes one token short and the recovered "
                    "stream silently diverges from what the client "
                    "already received",
                )
            )
    for path, tree in trees.items():
        for node in ast.walk(tree):
            if not isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef)
            ):
                continue
            for var, consumer, line in _handle_reuse_violations(node):
                diags.append(
                    Diagnostic(
                        "FX108",
                        path,
                        line,
                        f"'{node.name}' consumes staged swap token "
                        f"'{var}' again via '{consumer}' — a staged "
                        "handle/record is a move token (export pops "
                        "the source ledger, import installs it under "
                        "a fresh handle); the second consumption "
                        "restores pages another engine already owns",
                    )
                )
            srcs = _src_params(node)
            if not srcs:
                continue
            for attr, line in _live_source_violations(node, srcs):
                diags.append(
                    Diagnostic(
                        "FX108",
                        path,
                        line,
                        f"handoff-phase function '{node.name}' reads "
                        f"live source-engine state '{attr}' by "
                        "reference — the source keeps serving while "
                        "the handoff reads; stage a copy "
                        "(export_swap's host buffers, .copy(), "
                        "np.array) across the engine boundary instead",
                    )
                )
    for path, tree in trees.items():
        jitted = collect_jitted_names(tree)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if _is_asarray(node.func):
                for arg in node.args:
                    for attr, line in _tainted_loads(arg, mutated):
                        diags.append(
                            Diagnostic(
                                "FX101",
                                path,
                                line,
                                f"mutable host attribute '{attr}' flows "
                                "into jnp.asarray without a snapshot "
                                "(.copy()/np.array/snapshot) — the "
                                "deferred host read races later "
                                "mutation behind the dispatch queue",
                            )
                        )
                continue
            if _is_trace_hook(node):
                args = list(node.args) + [
                    kw.value for kw in node.keywords if kw.arg is not None
                ]
                for arg in args:
                    if isinstance(arg, ast.Starred):
                        continue
                    for attr, line in _tainted_loads(arg, mutated):
                        diags.append(
                            Diagnostic(
                                "FX104",
                                path,
                                line,
                                f"search-trace hook captures mutable "
                                f"attribute '{attr}' without a copy — "
                                "the searcher mutates it after the "
                                "record is taken, so the exported row "
                                "would rewrite itself; pass a scalar "
                                "or dict(...)/list(...)/.copy()",
                            )
                        )
                continue
            chain = name_chain(node.func)
            if chain is not None and chain[-1] in jitted:
                for arg in node.args:
                    if isinstance(arg, ast.Starred):
                        continue
                    for attr, line in _tainted_loads(arg, mutated):
                        diags.append(
                            Diagnostic(
                                "FX102",
                                path,
                                line,
                                f"mutable host attribute '{attr}' passed "
                                f"raw into jitted callable "
                                f"'{chain[-1]}' — snapshot it before "
                                "dispatch",
                            )
                        )
    return diags
