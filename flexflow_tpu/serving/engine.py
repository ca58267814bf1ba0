"""Prefill + single-token decode over a compiled FFModel.

The engine re-executes the model's compiled PCG through
`Executor.forward_values` with ONE op hook: MULTIHEAD_ATTENTION. The hook
computes the exact training projections (ops/attention.mha_project_qkv /
mha_project_out — shared code, not a reimplementation) and swaps only the
attention core:

  * **prefill**: the admitted prompts laid end to end in ONE row of
    `bucket(total)` tokens, dense attention causal inside each prompt
    (the training forward's, under a mask of segments) — and captures
    each layer's K/V, scattered token by token into the cache pages of
    the admitted slots. Each prompt's last position's logits yield its
    first generated token, so admission itself produces a token (Orca's
    iteration-level view: a prefill is just a fat iteration).
  * **decode**: one query position per slot. The new K/V row is written
    at position `lengths[slot]`, then
    `ops.attention.paged_decode_attention` runs masked one-query attention
    against the cache — the dense jnp path, or the Pallas flash-decode
    kernel (ops/pallas/decode_kernel.py) when the engine's
    `decode_kernel` mode selects it ("auto" on TPU, "pallas" forced,
    "dense" pinned).

The cache is block-paged (kv_cache.PagedKVCache): every step
routes K/V rows through the slot's block table — prefill scatters each
captured row into `page * page_size + offset` of the flattened pool
(the padding behind the last prompt is given an out-of-bounds
destination that JAX drops, so it never touches a live page),
decode writes the one new row the same way and attends via
`ops.attention.paged_decode_attention`. Block tables ride into the
jitted steps as an ordinary `[max_seqs, max_pages_per_seq]` int32
argument; the host-side allocator (PagedKVCache) mutates them between
steps, and `decode()` claims each sequence's next page BEFORE the step
when it is about to cross a page boundary (the admission reserve
guarantees that claim).

A model with latent attention (`OperatorType.LATENT_ATTENTION`,
ops/attention.py "Latent attention") is served through a second hook of
the prefill and decode programs: its cache is ONE pool a layer of latent
rows `[c | kr]` (kv_cache.cache_row), written through the same table
routing; prefill attends decompressed (the operator's plain lowering),
decode attends absorbed over the pool
(`ops.attention.paged_latent_decode_attention`: the latent Pallas kernel
or the dense gather). The other step families below are refused for such
a model at construction (`require`).

A model whose layers run several times over one set of weights
(`models/nlp.py:build_ouro`) needs nothing of its own here: every pass's
attention is a node, so `KVCacheSpec.layer_guids` holds passes x layers
cache layers behind the one block table, and a node that applies another
node's weights gets them from `Executor.forward_values`
(`Executor.weight_owner`), hooks included. What the engine adds is the
count: `weight_walk` and the `serve_weights_*`, `serve_cache_layers`,
`serve_weight_layers`, `serve_loop_passes` gauges
(`_publish_weight_walk`). Such a model keeps every step kind a multi-head
model has.

A third step family serves speculative decoding (serving/spec.py):
**verify** scores w = k+1 token positions per slot (the last emitted
token plus k drafted tokens) through the KV cache in ONE prefill-shaped
call — K/V rows for all w positions are written (table-routed
exactly like prefill), `ops.attention.paged_verify_attention`
runs the staircase-masked w-query attention, and the caller accepts a
prefix of the drafts and commits/rolls back via
`cache.truncate(slot, new_len)` (verify itself never advances lengths).

A fourth family serves **chunked prefill** (Sarathi-style, the
scheduler's `--token-budget` path): a prompt chunk is exactly a wide
verify with nothing to accept — w prompt tokens per slot scatter into
the cache at the slot's prefill cursor and attend through the SAME
staircase-masked verify path (query_offset = tokens already
prefilled), so chunked prefill is token- and logit-identical to the
monolithic prefill above. Unlike verify, chunk rows ARE the prompt —
accepted by construction — so `prefill_chunk_dispatch` advances
`cache.lengths` at dispatch (no host data dependency between a
request's consecutive chunks: they pipeline under the async loop), and
only the FINAL chunk's sampled token means anything (the scheduler
discards the rest).

That makes five step bodies (`_prefill_impl_paged`, `_decode_impl_paged`,
`_verify_impl_paged`, `_verify_tree_impl_paged`: a verify whose rows form
a draft tree, the parent table riding in as data, and
`_chunk_impl_paged`), each built into a program in one place
(`_step_jit`).

All steps are jitted with static shapes: decode always runs at
`[max_seqs, 1]`, prefill at `[1, bucket]` per bucket of the admitted
prompts' total length (an admission over the largest bucket runs several),
verify at `[max_seqs, w]` per draft width, so compile count is
1 + #buckets + #draft-widths for an entire serving session (tables
are data, not shape).

Every step program OWNS the KV pools it is handed: `_step_jit`, the one
place a program's jit is built, donates them, so the scatter writes
into the buffer it was given instead of copying the whole pool in front
of every step. The host's side of that: after a dispatch the arrays
that went in are deleted, and the only live pools are the ones
`cache.commit` stored (`_run_step`); nothing keeps a pool array across
a dispatch, and a step that fails after its program consumed the pools
raises PoolsLostError rather than run or commit a deleted one.

Every decode/verify is split into **dispatch** (enqueue the jitted
step, commit the functional cache arrays, snapshot the mutable host
state onto an `InflightStep`) and **reconcile** (block on the device
futures one call — or, under the async scheduler, one iteration —
later). `decode()`/`verify()` are the synchronous wrappers; the async
loop holds the `InflightStep` across an iteration and chains the next
step's input tokens from its `device_next` so the inter-step data
dependency resolves entirely on device.

A decode step crosses the host boundary once each way. In: the tokens,
lengths, active mask and block tables as one packed int32 argument
(`_pack_state`). Out: one int32 vector (`InflightStep.device_readback`:
the picked tokens, whether each slot's logits row is finite, the expert
layers' counts), whose copy to the host starts at dispatch and which
the reconcile waits for, once. The logits stay on the device; `decode()`
reads them because its caller asks for them. A dispatch blocks only on
a program's first run (`_dispatch`).

Greedy argmax is the default (temperature 0); temperature sampling
derives a PRNG key per (serve seed, slot, cache position), so a
request's sampled stream depends only on its slot and its own tokens —
reproducible under a fixed seed and independent of batch composition
(which requests happen to share the iteration), the property
rejection-sampling verify needs.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from collections import OrderedDict
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from flexflow_tpu.core.types import OperatorType
from flexflow_tpu.telemetry.trace import StepLog, StepRecord, span

_log = logging.getLogger(__name__)


class KernelCompileError(RuntimeError):
    """A kernel-path step failed the first time its program was
    dispatched: the kernel did not lower, compile, or complete one run.
    Not a fault to isolate or survive — neither the engine's dense
    fallback nor the scheduler's per-step isolation may absorb it,
    because then a kernel that cannot run on this device looks like a
    healthy server. Carries the compiler's message; `__cause__` is the
    original exception."""


class PoolsLostError(RuntimeError):
    """The KV pools' contents are lost: a step program consumed the pools
    it was handed (every step program owns and rewrites them in place, see
    `GenerationEngine._step_jit`) and then failed, so neither the arrays
    that went in nor any output exist. Raised instead of calling a program
    on, or committing, a deleted pool; every sequence in the cache has lost
    its KV rows, and the scheduler's step-fault path reports it like any
    other lost step. `__cause__` is the failure that cost the pools."""


#: the arguments a step program rewrites and returns, by the name every
#: `_*_impl` gives them: K and V pools, the int8 scale pools and the
#: recurrent layers' per-slot state. These, and nothing else, are donated.
_POOL_ARGNAMES = ("ck", "cv", "cks", "cvs", "cs")


def snapshot(host_state: np.ndarray):
    """Immutable device-ready snapshot of mutable host scheduler state.

    ``jnp.asarray`` defers its host-buffer read behind the async
    dispatch queue, so handing it live state the scheduler mutates
    between steps (``cache.lengths``, allocator block tables) races
    the deferred read and corrupts the step under load — the PR 3 bug
    class. Every dispatch site routes mutable host arrays through this
    ONE helper; fxlint's dispatch-race rule
    (flexflow_tpu/analysis/dispatch_race.py) recognizes exactly this
    idiom (or an explicit ``.copy()``/``np.array``) as the blessed
    snapshot and flags everything else."""
    import jax.numpy as jnp

    return jnp.asarray(np.array(host_state))


def _tree_depths(parents):
    """[b, w] depth of each draft-tree row below the root row (row 0, depth
    0): the number of its ancestors. A row accepted at depth m is
    compacted to cache position lengths + m, so that is where it stands."""
    import jax.numpy as jnp

    from flexflow_tpu.ops.attention import tree_ancestor_matrix

    return jnp.sum(tree_ancestor_matrix(parents), axis=-1, dtype=jnp.int32) - 1


class _JitCache:
    """Bounded keyed LRU over jitted step programs.

    The verify, tree-verify and chunked-prefill families each jit one
    program per shape key (draft width, row width, compact batch);
    widths churn with re-tuning and per-request budget caps, and an
    unbounded dict would keep every key's device executable alive for
    the engine's whole life. One helper owns the discipline for all
    three caches: a hit refreshes recency, a
    miss calls `trace(key)` and evicts the least-recently-used entry
    past `max_entries`. Iteration/containment/len mirror the dict so
    the compile population stays inspectable (the
    `verify_cache_entries`-style gauges)."""

    def __init__(self, trace, max_entries: int = 8):
        self._trace = trace
        self.max_entries = max_entries
        self._entries: "OrderedDict[object, object]" = OrderedDict()

    def get(self, key):
        fn = self._entries.get(key)
        if fn is None:
            fn = self._trace(key)
            self._entries[key] = fn
            while len(self._entries) > max(1, int(self.max_entries)):
                self._entries.popitem(last=False)
        else:
            self._entries.move_to_end(key)
        return fn

    def clear(self) -> None:
        self._entries.clear()

    def __len__(self) -> int:
        return len(self._entries)

    def __iter__(self):
        return iter(self._entries)

    def __contains__(self, key) -> bool:
        return key in self._entries


class _PackedChoice:
    """The routers' choice of one admission's prefill programs, as they
    left it: int32 [expert layers, 1, T, k] a program, the prompts end to
    end, on the device. `np.asarray` of it reads them back and lays them
    out [expert layers, row of the call, position, k] (-1 past a prompt's
    end), across the programs of a split admission. `stride`: a prompt
    begins on a multiple of it (`GenerationEngine._laid`)."""

    def __init__(self, programs, stride: int = 1):
        self._programs = programs  # [(device choice, the prompts' lengths)]
        self._stride = stride

    def __array__(self, dtype=None, copy=None):
        lens = [n for _, ns in self._programs for n in ns]
        packed = [np.asarray(choice) for choice, _ in self._programs]
        layers, _, _, k = packed[0].shape
        out = np.full((layers, len(lens), max(lens), k), -1, np.int32)
        row = 0
        for choice, (_, ns) in zip(packed, self._programs):
            at = 0
            for n in ns:
                out[:, row, :n] = choice[:, 0, at : at + n]
                row += 1
                at = -(-(at + n) // self._stride) * self._stride
        return out if dtype is None else out.astype(dtype)


@dataclasses.dataclass
class InflightStep:
    """One dispatched-but-not-reconciled engine step.

    The async double-buffered loop splits every decode/verify into a
    *dispatch* (enqueue the jitted step on the device queue, commit the
    functional cache arrays, return immediately) and a *reconcile*
    (block on the device outputs, emit tokens, retire requests) that
    runs one iteration later. This record is the only thing allowed to
    cross that gap: it carries an immutable HOST SNAPSHOT of everything
    the reconcile needs — the pre-step lengths, the active mask, the
    participating Request identities — so reconcile logic never reads
    live scheduler/cache state the host has since mutated (fxlint FX103
    enforces exactly that discipline), plus the device futures the
    reconcile blocks on.
    """

    kind: str  # "decode" | "verify" | "verify_tree" | "chunk"
    # the program's entry in `engine.step_log`: its stamps (the call, the
    # enqueue, the read) are the overlap accounting's and the trace's
    record: StepRecord
    active: np.ndarray  # bool [max_seqs] — slots the step ran for
    lengths: np.ndarray  # int32 [max_seqs] — cache lengths BEFORE the step
    host_tokens: Optional[np.ndarray] = None  # decode: host-view input tokens
    draft_lens: Optional[np.ndarray] = None  # verify/chunk: rows per slot
    # chunked prefill: slot -> (start, size, final) — the prefill-cursor
    # snapshot the commit phase reads INSTEAD of live Request attrs
    # (fxlint FX105 holds reconcile code to this record)
    chunks: Optional[Dict[int, tuple]] = None
    # chunked prefill: slot -> prompt tokens at dispatch — what the
    # commit phase hands register_prefix (same FX105 discipline: the
    # prompt is immutable per request, but the SLOT can turn over while
    # the step is in flight, so even this read rides the snapshot)
    chunk_seqs: Optional[Dict[int, list]] = None
    # device futures (JAX arrays still computing behind the queue)
    device_next: object = None  # decode: sampled tokens [max_seqs]
    # [max_seqs, V] or [max_seqs, w, V]. Of a decode step they stay on the
    # device unless someone asks (`GenerationEngine.decode`): the
    # scheduler's reconcile reads `device_readback` and nothing else
    device_logits: object = None
    # decode: everything the reconcile brings to the host, as ONE int32
    # vector whose copy was started at dispatch: the sampled tokens
    # [max_seqs], whether each slot's logits row is finite [max_seqs],
    # then the counts of a model with expert layers
    # (`GenerationEngine._count_fields`)
    device_readback: object = None
    # scheduler-side snapshot: slot -> Request identity at dispatch,
    # verify draft plan, and the dispatching iteration (fault keying)
    participants: Dict[int, object] = dataclasses.field(default_factory=dict)
    plan: Optional[Dict[int, list]] = None
    iteration: int = -1
    # tree verify (kind "verify_tree"): the per-row parent table the
    # step was dispatched with (host copy of the device operand) and
    # slot -> DraftTree plan. Both are SNAPSHOTS taken at dispatch —
    # the reconcile walks the tree and compacts the cache against
    # THESE, never a live proposer/scheduler tree the host has since
    # rebuilt (fxlint FX103/FX109 hold tree-reconcile code to the step
    # record).
    tree_parents: Optional[np.ndarray] = None  # int32 [max_seqs, w]
    tree_plan: Optional[Dict[int, object]] = None  # slot -> DraftTree


class PendingPrefill(NamedTuple):
    """An admission's prefill programs between `prefill_dispatch` and
    `prefill_reconcile`."""

    outs: list  # a program's outputs a group, on the device
    records: List[StepRecord]  # a group's program, in the step log
    groups: List[Tuple[int, int]]  # [lo, hi) of the admission's prompts


class GenerationEngine:
    """Step functions over (params, cache); all scheduling lives in
    serving.scheduler."""

    def __init__(
        self,
        model,
        cache,
        temperature: float = 0.0,
        seed: int = 0,
        decode_kernel: str = "auto",
        injector=None,
        telemetry=None,
        adapters=None,
    ):
        from flexflow_tpu.ops.pallas.decode_kernel import MODES

        if model.executor is None:
            raise RuntimeError("compile() the model before serving")
        if decode_kernel not in MODES:
            raise ValueError(
                f"decode_kernel must be one of {MODES}, got {decode_kernel!r}"
            )
        self.model = model
        self.executor = model.executor
        self.cache = cache
        self.temperature = float(temperature)
        self.seed = int(seed)
        # resilience: a faults.FaultInjector seam before kernel-path
        # dispatches, plus the fallback ledger the chaos bench reads
        self.injector = injector
        self.kernel_fallbacks = 0
        self.kernel_fallback_error: str = ""
        # kernel-path programs that have completed a step: only these can
        # have a run-time fault (see _dispatch)
        self._kernel_programs_run: set = set()
        # telemetry (flexflow_tpu.telemetry.Telemetry): None when
        # disabled. `_tracer` is what every `span` below is handed: the
        # bundle's Chrome tracer, or None (the span is then only the
        # profiler's annotation)
        self.telemetry = (
            telemetry
            if telemetry is not None and getattr(telemetry, "enabled", False)
            else None
        )
        self._tracer = getattr(self.telemetry, "tracer", None)
        # every program this engine dispatches, and the requests they ran
        # for (telemetry/trace.py; always on, reachable by `step_logs()`)
        self.step_log = StepLog()
        # counted inside the span that does the work, mirrored into
        # SchedulerStats at each iteration's end: blocking reads of a
        # device value and the bytes they brought to the host; prompt
        # tokens prefilled, the bucket(total) tokens of the rows they were
        # packed into, and the prefill programs dispatched
        self.device_syncs = 0
        self.readback_bytes = 0
        self.prefill_tokens_real = 0
        self.prefill_tokens_padded = 0
        self.prefill_programs = 0
        # how the decode/verify attention core runs (threaded into every
        # ops.attention call below): "auto" = Pallas decode kernel on TPU
        # when the geometry supports() it, "pallas" = force the kernel
        # (interpret mode off-TPU), "dense" = always the jnp paths. A
        # trace-time constant: each engine owns its jitted steps, so two
        # engines with different modes coexist in one process.
        self.decode_kernel = decode_kernel
        # on a serving mesh the placement decides where a kernel may run
        # (ServingPlacement.kernel_head_shard): per head shard under
        # shard_map, or not at all when pages are sharded over data
        self._head_shard = None
        placement = getattr(model, "serving_placement", None)
        if placement is not None:
            shard = placement.kernel_head_shard()
            if shard == "dense":
                if decode_kernel == "pallas":
                    raise ValueError(
                        "decode_kernel='pallas' cannot run on this mesh: "
                        + placement.describe()
                    )
                if decode_kernel != "dense":
                    _log.warning("%s", placement.describe())
                self.decode_kernel = "dense"
            else:
                self._head_shard = shard
        self._publish_kernel_block()
        self._publish_weight_walk()
        self._publish_state()
        # multi-tenant LoRA (serving.tenancy.adapters.AdapterPool):
        # None keeps every traced step byte-for-byte the base engine —
        # the adapter argument is simply never passed, so no select or
        # gather enters the HLO. With a pool, every step carries a
        # (tables, has, pools) pytree snapshotted at dispatch; rows
        # whose slot serves the base model (adapter_id -1) ride a
        # jnp.where select that returns the unmodified projection
        # elements, which is what the bit-identity gates pin down.
        self.adapters = adapters
        graph = model.graph
        inputs = [
            graph.nodes[g]
            for g in self.executor.topo
            if graph.nodes[g].op_type == OperatorType.INPUT
            and not graph.nodes[g].inputs
        ]
        if len(inputs) != 1:
            raise ValueError(
                "serving needs a single token-id input tensor, model has "
                f"{len(inputs)} inputs"
            )
        self.input_name = inputs[0].name
        for g in cache.spec.layer_guids:
            node = graph.nodes[g]
            if not node.params.get("causal", False):
                raise ValueError(
                    f"attention node '{node.name}' is not causal; "
                    "autoregressive serving needs causal=True"
                )
            refs = {(r.guid, r.out_idx) for r in node.inputs}
            if len(refs) != 1:
                raise ValueError(
                    f"attention node '{node.name}' is cross-attention; "
                    "the KV-cache engine supports self-attention only"
                )
        # a model whose attention depends on positions (rotary) or carries
        # weights beyond the projections (QK-norm): every step program
        # below hands `mha_project_qkv` the node's params and the
        # positions of the rows it writes. What cannot do that is refused
        # here, not served position-free.
        from flexflow_tpu.ops.attention import is_positional

        self._positional = any(
            is_positional(graph.nodes[g].params) for g in cache.spec.layer_guids
        )
        if self._positional and adapters is not None:
            raise ValueError(
                "adapters (multi-LoRA) are not supported for a model with "
                "rotary positions or QK-norm: the LoRA deltas are added to "
                "q and k after mha_project_qkv has normalised and rotated "
                "them, which is not the adapted model; serve it without "
                "ServeConfig.adapters"
            )
        # latent attention (ops/attention.py, "Latent attention"): ONE pool
        # a layer of [c | kr] rows, written by the prefill and decode
        # programs through the same `dest` scatter as K and V rows, and
        # attended decompressed (prefill) or absorbed over the pool
        # (decode). What has not been taken through those helpers and
        # tested is refused here, in words, not served by the operator's
        # plain lowering with no cache behind it.
        self._latent = tuple(
            g for g in cache.spec.layer_guids
            if graph.nodes[g].op_type == OperatorType.LATENT_ATTENTION
        )
        self._refused: Dict[str, str] = {}
        if self._latent:
            if len(self._latent) != len(cache.spec.layer_guids):
                raise ValueError(
                    "a model that mixes latent attention with multi-head "
                    "attention is not supported: the cache keeps one kind of "
                    "row for all layers"
                )
            for asked, what in (
                (cache.quantized, "kv_dtype='int8' (a page's scale is per "
                 "head, and a latent row has none to scale by)"),
                (cache.prefix_cache, "prefix_cache (a shared prefix is "
                 "resumed by a chunk step)"),
                (placement is not None, "a serving mesh (the latent row has "
                 "one head and cannot be sharded by heads)"),
            ):
                if asked:
                    raise ValueError(
                        f"{what} is not supported for a model with latent "
                        "attention: only prefill and decode read and "
                        "write the latent pool"
                    )
            why = (
                " steps are not supported for a model with latent attention: "
                "only prefill and decode read and write the latent pool"
            )
            self._refused = {
                "verify": "verify (speculative decoding)" + why,
                "verify_tree": "tree verify (speculative decoding)" + why,
                "chunk": "chunked-prefill and prefix-suffix" + why,
                "draft": "draft-model" + why,
            }
        # recurrent layers (ops/linear_attention.py): a fixed-size state a
        # SLOT (`cache.state`), written whole by the prefill program and
        # advanced by the single-step decode program. Nothing else reads
        # or writes it, and nothing can roll it back: the rest is refused
        # here, in words, as for the latent pool.
        self._recurrent = tuple(cache.spec.state_guids)
        self._state_chunk = 1
        if self._recurrent:
            chunks = {graph.nodes[g].params["chunk"] for g in self._recurrent}
            if len(chunks) != 1:
                raise ValueError(
                    f"recurrent layers disagree on their chunk: {chunks}"
                )
            (self._state_chunk,) = chunks
            uneven = [
                b for b in cache.spec.buckets if b % self._state_chunk
            ]
            if uneven:
                raise ValueError(
                    f"prefill buckets {uneven} are not whole chunks of "
                    f"{self._state_chunk} tokens: a model with recurrent "
                    "layers lays every prompt on a chunk boundary"
                )
            from flexflow_tpu.serving.kv_cache import STATE_WHY

            why = " not supported for a model with recurrent layers: " + STATE_WHY
            for asked, what in (
                (placement is not None, "a serving mesh is"),
                (adapters is not None, "adapters (multi-LoRA) are"),
            ):
                if asked:
                    raise ValueError(what + why)
            self._refused.update({
                "verify": "verify steps (speculative decoding) are" + why,
                "verify_tree": "tree-verify steps (speculative decoding) "
                "are" + why,
                "chunk": "chunked-prefill and prefix-suffix steps are" + why,
                "draft": "draft-model steps are" + why,
                "swap": "kv_swap (swap_out / swap_in) is" + why,
            })
        # expert layers (ops/moe.py sparse_moe): the prefill and decode
        # programs return their row and touched-expert counts beside the
        # logits, read in the same readback; without one, nothing more.
        # A layer that holds a share of its experts counts the live rows
        # it left to the others too (one int32 vector either way,
        # `_count_fields` naming its entries), and its programs leave the
        # routers' choice on the device (`moe_choice`)
        self._moe_guids = tuple(
            g for g in self.executor.topo
            if graph.nodes[g].op_type == OperatorType.SPARSE_MOE
        )
        shares = {
            graph.nodes[g].params.get("experts_held") is not None
            for g in self._moe_guids
        }
        if len(shares) > 1:
            raise ValueError(
                "expert layers that hold a share beside layers that hold "
                "every expert are not supported: their counts differ"
            )
        self._count_fields = ("moe_rows", "moe_experts_touched") if shares else ()
        self._moe_share = True in shares
        if self._moe_share:
            self._count_fields += ("moe_rows_absent",)
        # a shared expert (a gated MLP beside an expert layer, on the same
        # input) runs under the `moe.shared` scope in the step programs
        routed_inputs = {graph.nodes[g].inputs[0] for g in self._moe_guids}
        self._shared_guids = frozenset(
            g for g in self.executor.topo
            if graph.nodes[g].op_type == OperatorType.GATED_MLP
            and graph.nodes[g].inputs[0] in routed_inputs
        )
        # of a model whose layers hold a share: the experts every token
        # picked in the last single-step decode program (int32 [expert
        # layers, max_seqs, 1, k], idle slots included) and in the last
        # admission's prefill programs ([expert layers, row of the call,
        # position, k] to `np.asarray`: `_PackedChoice`), left on the
        # device (nothing reads it back but who asks)
        self.moe_choice: Dict[str, object] = {}
        self.moe_rows_prefill = 0
        self.moe_rows_decode = 0
        self.moe_experts_touched_prefill = 0
        self.moe_experts_touched_decode = 0
        self.moe_rows_absent_prefill = 0
        self.moe_rows_absent_decode = 0
        # the prefill and decode programs dispatched whose expert layers'
        # products came from ops/pallas/grouped_matmul.py, and what each
        # traced program's layers took (`_forward_logits` notes it at
        # trace time: the choice is `ops.moe.expert_products`')
        self.moe_kernel_programs_prefill = 0
        self.moe_kernel_programs_decode = 0
        self._moe_kernel_traced: Dict[tuple, bool] = {}
        self.mla_rows_read_decode = 0
        # recurrent layers: (live slot, layer) rows the decode steps
        # advanced, and (request, layer) rows the prefills wrote from zero
        self.state_rows_decode = 0
        self.state_resets_prefill = 0
        # the decode steps dispatched whose recurrent layers all advanced
        # their state through ops/pallas/kda_step.py, the prefill programs
        # whose recurrent layers all scanned through ops/pallas/kda_scan.py,
        # and what each traced program's took (`linear_attention.
        # kda_step_live`'s and `kda_chunked_rows`' choice)
        self.kda_kernel_programs_decode = 0
        self.kda_kernel_programs_prefill = 0
        self._kda_kernel_traced: Dict[tuple, bool] = {}
        self._logits_ref = self.executor.logits_ref
        # per-iteration dynamic seq truncation is a training knob; a stale
        # value would truncate serving activations mid-stack
        self.executor.set_seq_length(None)
        # step programs that found the pools they were handed consumed by
        # the call (the donation engaged: the rows were written in place),
        # and those that found them still alive (the backend declined it
        # and copied). One host check of one pool leaf after each dispatch
        self.pool_steps_donated = 0
        self.pool_steps_copied = 0
        self._decode_jit = self._step_jit(self._decode_impl_paged)
        # the newest decode step's sampled tokens, on the device: what a
        # step without a chain hands the program in their place
        self._last_next = None
        # one jitted prefill per length bucket / one jitted verify per
        # draft width (jit caches by shape anyway; the explicit caches
        # make the compile-count contract inspectable). The verify,
        # tree-verify and chunk caches are bounded LRUs (_JitCache):
        # draft widths vary with optimize_spec_k re-tuning and
        # per-request budget caps, chunk widths with the token budget —
        # unbounded dicts kept every key's jitted program (and its
        # device executable) alive for the engine's whole life.
        self._prefill_cache: Dict[int, object] = {}
        self._verify_cache = _JitCache(
            lambda w: self._step_jit(self._verify_impl_paged)
        )
        # chunked-prefill programs, one per compact batch shape (B, w) —
        # the scheduler pads widths to multiples of chunk_size, so the
        # population is budget/chunk_size distinct widths at most
        self._chunk_cache = _JitCache(
            lambda key: self._step_jit(self._chunk_impl_paged)
        )
        # tree-verify programs, one per row width w = 1 + tree nodes.
        # Kept apart from `_verify_cache` because the tree impl carries
        # an extra parent-table operand; the scheduler pins a single
        # node budget, so the steady-state population is one entry
        self._tree_cache = _JitCache(
            lambda w: self._step_jit(self._verify_tree_impl_paged)
        )

    def _step_jit(self, impl):
        """The one place a step program's jit is built: `impl`, jitted
        with the pools it rewrites and returns DONATED. XLA may
        not write into a parameter it does not own, so an undonated pool
        is copied whole into the output buffer in front of every
        scatter; a donated one is updated in place. Parameters, adapter
        pools (immutable, shared by steps in flight), tables, lengths
        and tokens stay the caller's. By name, since the pools' positions
        differ between the programs."""
        import jax

        return jax.jit(impl, donate_argnames=_POOL_ARGNAMES)

    @property
    def verify_cache_entries(self) -> int:
        """Live jitted verify programs (LRU-bounded by
        `verify_cache_max`) — surfaced as a SchedulerStats field so a
        width-churning workload's compile footprint is observable."""
        return len(self._verify_cache)

    @property
    def tree_cache_entries(self) -> int:
        """Live jitted tree-verify programs — the `verify_cache_entries`
        twin for the tree-width family."""
        return len(self._tree_cache)

    @property
    def verify_cache_max(self) -> int:
        return self._verify_cache.max_entries

    @verify_cache_max.setter
    def verify_cache_max(self, n: int) -> None:
        self._verify_cache.max_entries = int(n)

    @property
    def chunk_cache_max(self) -> int:
        return self._chunk_cache.max_entries

    @chunk_cache_max.setter
    def chunk_cache_max(self, n: int) -> None:
        self._chunk_cache.max_entries = int(n)

    @property
    def _attn_core(self) -> dict:
        """How every cache-attention call below runs its core: the kernel
        mode (read at trace time — a run-time fallback flips it to dense
        and re-traces) and where a kernel must be shard_mapped."""
        return {"kernel": self.decode_kernel, "head_shard": self._head_shard}

    def _verify_fn(self, w: int):
        """The jitted verify program for draft width `w` (LRU-managed
        by the shared _JitCache)."""
        self.require("verify")
        return self._verify_cache.get(w)

    def _tree_fn(self, w: int):
        """The jitted tree-verify program for row width `w` (root + tree
        nodes) — same keyed-LRU discipline as `_verify_fn`."""
        self.require("verify_tree")
        return self._tree_cache.get(w)

    def _chunk_fn(self, key):
        """The jitted chunked-prefill program for compact batch shape
        `key` = (B, w) — same keyed-LRU discipline as `_verify_fn`."""
        self.require("chunk")
        return self._chunk_cache.get(key)

    def require(self, *kinds: str) -> None:
        """Raise, in words, if this engine refuses one of the step `kinds`
        ("verify", "verify_tree", "chunk", "draft": what a model with
        latent attention is not served through; "swap" besides for one
        with recurrent layers). `build_scheduler` asks
        before a request is admitted; the program getters above ask again."""
        for kind in kinds:
            if kind in self._refused:
                raise ValueError(self._refused[kind])

    # -- adapter gather args (multi-LoRA) ------------------------------------

    def _adapter_slot_args(self):
        """() without a pool, else a 1-tuple holding the slot-indexed
        (tables, has, pools) adapter gather for the decode/verify/
        chunk steps. The host tables snapshot at dispatch
        (FX103: the step rides its own copy — scheduler attach/detach
        between iterations never mutates an in-flight step's view); the
        device pools are immutable arrays, rebound wholesale by loads,
        so the step keeps whatever pool generation it captured."""
        if self.adapters is None:
            return ()
        tbl, has = self.adapters.slot_tables()
        return (
            (snapshot(tbl), snapshot(has), self.adapters.device_pools),
        )

    # -- kernel-failure fallback ---------------------------------------------

    def _run_step(
        self, site: str, step_fn, params, inputs, adapter_args=(),
        program=None, kernel_path: bool = True, *, record: StepRecord,
    ):
        """Call one step program on the live pools, commit the pools it
        returns, and hand back the rest of its outputs. `record` enters
        `step_log` here, stamped around the call: all five step bodies
        are dispatched through this one place.

        Every step program has this shape: `(params, *inputs, ck, cv,
        cks, cvs, cs [, ad])` in, `(ck', cv', cks', cvs', cs', ...)` out,
        with the pools donated (`_step_jit`). So after the call the
        arrays that went in are gone, and the only live pools are the
        ones `commit` stores here: nothing else may keep a pool array
        across a dispatch. `step_fn()` resolves the jitted program at
        call time, so that a kernel fallback's rebuilt program is what a
        retry runs. `kernel_path=False` for the prefill, whose program
        never holds a decode kernel and is not forced at dispatch."""
        cache = self.cache
        # one leaf stands for all: they are donated, and deleted, together
        witness = next(iter(cache.k.values()))
        if witness.is_deleted():
            raise PoolsLostError(
                f"{site} step not dispatched: the KV pools were consumed "
                "by an earlier step program that failed"
            )
        pools = cache.pools
        args = (params, *inputs, *pools, *adapter_args)

        def call():
            return step_fn()(*args)

        t_call = time.perf_counter()
        out = (
            self._dispatch(site, call, program, witness)
            if kernel_path
            else call()
        )
        self.step_log.enqueued(record, t_call, time.perf_counter())
        if witness.is_deleted():
            self.pool_steps_donated += 1
        else:
            self.pool_steps_copied += 1
        cache.commit(*out[: len(pools)])
        return out[len(pools):]

    def _dispatch(self, site: str, call, program, witness):
        """Run one jitted decode/verify step. On the dense paths this is
        just `call()`. On a Pallas-kernel path the FIRST dispatch of a
        program forces its outputs: that is where its kernel lowers,
        compiles and runs for the first time, and a kernel that cannot is
        not a fault to survive: it raises KernelCompileError with the
        compiler's message, because answering it with dense is how a
        kernel that never ran on the chip looked healthy. `program` names
        the compiled program behind `call` (the site plus its shape key;
        defaults to the site); every program's first dispatch belongs in
        a warm-up.

        After that nothing blocks here: the host enqueues and goes on,
        and a fault the device raises surfaces where the step's outputs
        are read (`_readback`), as PoolsLostError. A fault raised by the
        call itself (injected through the chaos seam, or thrown by a
        program that has already run) permanently falls the engine back
        to the dense paths and retries the step once. Serving survives a
        broken kernel at the cost of the dense path's speed; the fallback
        is recorded in `kernel_fallbacks` / `kernel_fallback_error` and
        logged.

        The retry calls the same closure over the same pools, so it
        stands only while they do: a fault raised before the program ran
        (the chaos seam, a trace or compile error) leaves them intact.
        One that surfaces after the call consumed them (`witness`, a pool
        leaf that went in, is deleted) raises PoolsLostError instead."""
        import jax

        from flexflow_tpu.serving.faults import KernelFault

        if self.decode_kernel == "dense":
            return call()
        program = site if program is None else program
        first = program not in self._kernel_programs_run
        try:
            if self.injector is not None:
                self.injector.maybe_kernel_fault(site)
            out = call()
            if first:
                with span(f"scheduler.step.{site}.wait", self._tracer):
                    jax.block_until_ready(out)
                    self.device_syncs += 1
        except Exception as e:
            if first and not isinstance(e, KernelFault):
                raise KernelCompileError(
                    f"{site} step with decode_kernel="
                    f"{self.decode_kernel!r} failed on the first dispatch "
                    f"of program {program!r}: {e}"
                ) from e
            if witness.is_deleted():
                raise PoolsLostError(
                    f"{site} step failed after its program consumed the "
                    f"KV pools, so there is nothing to retry on: {e!r}"
                ) from e
            self._fall_back_to_dense(e)
            return call()
        self._kernel_programs_run.add(program)
        return out

    def _publish_kernel_block(self) -> None:
        """What one turn of the paged decode kernel's block loop handles
        at this cache's geometry (ops/pallas/decode_kernel.paged_block: pages a
        block, rows a block, VMEM bytes; the decode step's, w = 1), as
        `serve_decode_kernel_block_*` gauges, once: a trace of the kernel
        is read against them. Nothing where the kernel is not taken."""
        from flexflow_tpu.ops.pallas import decode_kernel as dk

        spec = self.cache.spec
        heads = spec.num_heads
        if self._head_shard is not None:
            mesh, axis = self._head_shard
            heads //= mesh.shape[axis]
        self.kernel_block = None
        if spec.kv_pools == 1:
            # a latent pool: one row for all the model's query heads
            node = self.model.graph.nodes[spec.layer_guids[0]]
            if dk.supports_latent(
                self.decode_kernel, spec.row_width, spec.page_size
            ):
                self.kernel_block = dk.latent_block(
                    node.params["num_heads"], spec.row_width,
                    node.params["kv_lora_rank"], spec.page_size,
                    spec.max_pages_per_seq, spec.itemsize,
                )
        elif dk.use_kernel(
            self.decode_kernel, 1, 0, spec.head_dim,
            page_size=spec.page_size, kv_dtype=spec.kv_dtype, heads=heads,
        ):
            self.kernel_block = dk.paged_block(
                1, heads, spec.head_dim, spec.page_size,
                spec.max_pages_per_seq, spec.itemsize,
            )
        if self.telemetry is None or self.kernel_block is None:
            return
        for field, what in (
            ("pages", "logical pages"),
            ("rows", "cache rows"),
            ("vmem_bytes", "VMEM bytes"),
        ):
            self.telemetry.registry.gauge(
                f"serve_decode_kernel_block_{field}",
                help=f"{what} the paged decode kernel handles at a time "
                "(from the cache geometry)",
            ).set(getattr(self.kernel_block, field))

    def _publish_weight_walk(self) -> None:
        """What a step reads of the weights against what is kept, as
        `weight_walk` and `serve_*` gauges, once: the bytes of `params`
        (each array once), the bytes a step applies (every node's
        weights, its owner's where it applies another node's:
        `Executor.weight_owner`), the cache's layers and how many of them
        own their weights, and the most often a weight is applied in one
        step (1 unless layers run again)."""
        ex = self.executor
        nbytes = {
            g: sum(int(w.nbytes) for w in ws)
            for g, ws in self.model.params.items()
        }
        uses: Dict[int, int] = {}
        for g in ex.topo:
            owner = ex.weight_owner.get(g, g)
            if owner in nbytes:
                uses[owner] = uses.get(owner, 0) + 1
        layers = self.cache.spec.layer_guids
        self.weight_walk = {
            "weights_stored_bytes": sum(nbytes.values()),
            "weights_applied_bytes": sum(nbytes[g] * n for g, n in uses.items()),
            "cache_layers": len(layers),
            "weight_layers": len({ex.weight_owner.get(g, g) for g in layers}),
            "loop_passes": max(uses.values(), default=1),
        }
        if self.telemetry is None:
            return
        for name, value in self.weight_walk.items():
            self.telemetry.registry.gauge(
                f"serve_{name}",
                help="of the model as served (engine._publish_weight_walk)",
            ).set(value)

    def _publish_state(self) -> None:
        """The recurrent layers and the bytes of per-slot state all slots
        keep of them, as `serve_state_layers` / `serve_state_bytes`
        gauges, once; nothing for a model without such a layer."""
        spec = self.cache.spec
        if self.telemetry is None or not spec.state_guids:
            return
        for name, value in (
            ("layers", len(spec.state_guids)),
            ("bytes", spec.state_bytes_per_slot * spec.max_seqs),
        ):
            self.telemetry.registry.gauge(
                f"serve_state_{name}",
                help="of the recurrent layers' per-slot state "
                "(engine._publish_state)",
            ).set(value)

    def _fall_back_to_dense(self, error) -> None:
        self.kernel_fallbacks += 1
        self.kernel_fallback_error = repr(error)
        _log.warning(
            "Pallas %s kernel path failed; serving continues on the dense "
            "attention paths for the life of this engine: %r",
            self.decode_kernel, error,
        )
        if self.telemetry is not None:
            self.telemetry.registry.counter(
                "serve_kernel_fallbacks_total",
                help="Pallas dispatch failures answered by permanent "
                "dense fallback",
            ).inc()
            self.telemetry.tracer.instant(
                "kernel_fallback", "engine", args={"error": repr(error)}
            )
        self.decode_kernel = "dense"
        # the jitted steps baked the failed mode in at trace time;
        # rebuild them so the retry traces the dense attention cores
        # (prefill never touches the kernel, so its cache stands)
        self._decode_jit = self._step_jit(self._decode_impl_paged)
        self._verify_cache.clear()
        self._chunk_cache.clear()
        self._tree_cache.clear()

    def _readback(self, kind: str, *arrays, records=()):
        """Bring a step's device outputs to the host: one blocking read
        each, counted where it happens, and the stamps of the read on the
        `records` of the programs it closes. This is where a fault the
        device raised while running the step's program surfaces
        (`_dispatch` blocks on a program's first run only), and by then
        the program has consumed the pools it was handed:
        PoolsLostError."""
        with span(f"scheduler.step.{kind}.readback", self._tracer):
            t_read = time.perf_counter()
            try:
                out = [np.asarray(a) for a in arrays]
            except Exception as e:
                raise PoolsLostError(
                    f"{kind} step failed after its program consumed the "
                    f"KV pools: {e!r}"
                ) from e
            finally:
                self.step_log.read(records, t_read, time.perf_counter())
            self.device_syncs += len(out)
            self.readback_bytes += sum(a.nbytes for a in out)
        return out

    def _claim_rows(self, widths) -> None:
        """Claim every page a step's fresh rows touch BEFORE the jitted
        step: slot s writes positions lengths[s] .. lengths[s] +
        widths[s] - 1 (host-side allocator; the admission reserve
        guarantees the claims, and a shared page forks here)."""
        for slot in np.nonzero(widths)[0]:
            start = int(self.cache.lengths[slot])
            for p in range(start, start + int(widths[slot])):
                self.cache.ensure_position(int(slot), p)

    # -- shared forward ------------------------------------------------------

    def _positions(self, make):
        """The positions a step program hands `mha_project_qkv`: `make()`
        for a positional model, None otherwise (so that a model without
        rotary or QK-norm traces exactly the program it always did)."""
        return make() if self._positional else None

    def _forward_logits(
        self, params, tokens, hook, moe_counts=None, latent_hook=None,
        share=None, state_hook=None, program=None,
    ):
        """`moe_counts`: a list that receives the int32 counts of every
        expert layer (`ops.moe.sparse_moe`), for the programs that
        return them; the layer itself is the executor's lowering.
        `latent_hook`: what stands in for a latent-attention node, from
        the programs that serve one. `share` (`_share`): what the layers
        of a model that holds a share of its experts are handed besides.
        `state_hook`: what stands in for a recurrent (linear-attention)
        node, from the programs that keep its per-slot state. `program`:
        the key under which `_moe_kernel_traced` keeps whether this
        program's expert layers took the grouped-matmul kernel."""
        import jax

        hooks = {OperatorType.MULTIHEAD_ATTENTION: hook}
        if latent_hook is not None:
            hooks[OperatorType.LATENT_ATTENTION] = latent_hook
        if state_hook is not None:
            hooks[OperatorType.LINEAR_ATTENTION] = state_hook
        if moe_counts is not None and self._moe_guids:
            from flexflow_tpu.ops.moe import sparse_moe

            took = []

            def count(node, ins, ws, ctx):
                y, counts = sparse_moe(
                    ins[0], ws, node.params, ctx, grad=False, took=took,
                    **(share or {}),
                )
                moe_counts.append(counts)
                return [y]

            hooks[OperatorType.SPARSE_MOE] = count
        if self._shared_guids:
            lowered = self.executor._lowered

            def gated(node, ins, ws, ctx):
                if node.guid not in self._shared_guids:
                    return lowered[node.guid](ins, ws, ctx)
                with jax.named_scope("moe.shared"):
                    return lowered[node.guid](ins, ws, ctx)

            hooks[OperatorType.GATED_MLP] = gated
        values = self.executor.forward_values(
            params,
            {self.input_name: tokens},
            rng=None,
            train=False,
            op_hooks=hooks,
            constrain=False,
        )
        if program is not None and moe_counts:
            self._moe_kernel_traced[program] = all(took)
        return values[(self._logits_ref.guid, self._logits_ref.out_idx)]

    def _share(self, live):
        """What `_forward_logits` hands the expert layers of a model that
        holds a share of its experts (None otherwise): which tokens are
        someone's, `live` bool [rows, positions], and the list that
        receives each layer's choice."""
        return {"live": live, "chosen": []} if self._moe_share else None

    @staticmethod
    def _step_counts(moe_counts, share=None):
        """What a step program returns beside its tokens and logits (the
        prefill appends it to its outputs, the decode step packs the
        counts into its readback): () for a model without expert layers,
        else the int32 sum over layers of their counts (`_count_fields`
        names the entries), and after it, from a model that holds a
        share, every layer's choice stacked."""
        import jax.numpy as jnp

        out = (sum(moe_counts[1:], moe_counts[0]),) if moe_counts else ()
        if share is not None:
            out += (jnp.stack(share["chosen"]),)
        return out

    def _keep_choice(self, out):
        """A decode program's outputs without the routers' choice that
        `_step_counts` appended last for a model that holds a share: it
        stays on the device as `moe_choice["decode"]`."""
        if self._moe_share:
            *out, self.moe_choice["decode"] = out
        return out

    def _count(self, kind: str, counts) -> None:
        for name, got in zip(self._count_fields, counts):
            attr = f"{name}_{kind}"
            setattr(self, attr, getattr(self, attr) + int(got))

    def _write_latent(self, pool, latent, dest):
        """Scatter latent rows [..., rank + rope] into the layer's ONE
        pool, padded with zeros to the pool's row (`cache_row`)."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope("mla.project"):
            pad = self.cache.spec.row_width - latent.shape[-1]
            rows = jnp.pad(latent, [(0, 0)] * (latent.ndim - 1) + [(0, pad)])
            return self._write_rows(pool, rows, dest)

    def _pick(self, logits, slots, positions):
        """logits [n, vocab] -> token ids [n]. Greedy at temperature 0,
        else categorical under a PER-ROW key derived as
        fold_in(fold_in(PRNGKey(seed), slot), position) — `positions` is
        the cache position each sampled token will occupy. The draw for
        a slot therefore depends only on (seed, slot, position), never
        on the global step counter or on which other requests share the
        batch: a fixed seed replays the same stream even when admission
        timing shifts, the reproducibility rejection-sampling verify
        builds on."""
        import jax
        import jax.numpy as jnp

        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        base = jax.random.PRNGKey(self.seed)
        temp = self.temperature

        def one(slot, pos, row):
            key = jax.random.fold_in(jax.random.fold_in(base, slot), pos)
            return jax.random.categorical(
                key, row.astype(jnp.float32) / temp
            )

        return jax.vmap(one)(slots, positions, logits).astype(jnp.int32)

    # -- pool writes ---------------------------------------------------------

    def _write_rows(self, pool, new, dest):
        """Scatter K (V) rows `new` [..., heads, head_dim] into a paged
        `pool` at flat row indices `dest` (out-of-bounds rows drop). A
        pool row is one position's K (V) of every head."""
        width = self.cache.spec.num_heads * self.cache.spec.head_dim
        rows = new.astype(pool.dtype).reshape(-1, width)
        return pool.reshape(-1, width).at[dest].set(rows).reshape(pool.shape)


    def _quant_scatter(self, pool, scale, rows, dest):
        """Quantize `rows` [N, heads, head_dim] into the int8 `pool` at
        flat row indices `dest` [N] (out-of-bounds rows drop, exactly
        like the fp32 scatter). A page's fp32 scale is claimed exactly
        once, from the abs-max of its FIRST row (position page_size·p;
        `kv_cache.int8_page_scale`):
        sequential streaming guarantees a fresh page's first write
        contains that row, and the first row's content is a pure
        function of the token history — so the scale (and therefore the
        page's bytes) comes out identical no matter how the writes were
        batched into chunks, which request recomputed them, or whether
        the page arrived via COW (the copied scale equals what a fresh
        recompute would derive). Pages whose scale is already set (> 0)
        keep it; rows beyond ±127·scale clip — the documented int8
        tolerance. Returns (pool', scale', dequantized_rows): the round
        trip through int8, for callers (prefill) whose attention must
        read exactly what a later pool reader will see."""
        import jax.numpy as jnp

        from flexflow_tpu.serving.kv_cache import int8_page_scale

        spec = self.cache.spec
        page = dest // spec.page_size  # OOB dest -> OOB page, dropped
        f32 = rows.astype(jnp.float32)
        amax = jnp.max(jnp.abs(f32), axis=-1)  # [N, heads]
        first = (dest % spec.page_size == 0)[:, None]  # page-initial rows
        cand = jnp.zeros_like(scale).at[page].max(
            jnp.where(first, int8_page_scale(amax), 0.0), mode="drop"
        )
        # a batch that writes a page's first row (RE)DERIVES its scale —
        # never trust a stored value then: freed pages keep stale scales
        # on device, and a reallocated page must quantize from its new
        # content, not its previous tenant's
        claimed = jnp.zeros_like(scale).at[page].max(
            jnp.where(first, 1.0, 0.0), mode="drop"
        )
        new_scale = jnp.where(claimed > 0.0, cand, scale)
        s = new_scale[jnp.clip(page, 0, spec.num_pages - 1)]  # [N, heads]
        safe = jnp.where(s > 0.0, s, 1.0)
        q = jnp.clip(jnp.round(f32 / safe[:, :, None]), -127, 127).astype(
            pool.dtype
        )
        flat = pool.reshape(-1, spec.num_heads * spec.head_dim)
        deq = q.astype(jnp.float32) * jnp.where(
            s > 0.0, s, 0.0
        )[:, :, None]
        return (
            flat.at[dest]
            .set(q.reshape(q.shape[0], -1), mode="drop")
            .reshape(pool.shape),
            new_scale,
            deq,
        )

    # -- prefill -------------------------------------------------------------

    #: rows of a prefill's packed per-token layout, then of its per-request one
    _TOKEN_ROWS = 4  # token id, segment, position, pool destination
    _REQUEST_ROWS = 3  # slot, prompt length, index of the prompt's last token

    def _prefill_impl_paged(
        self, params, layout, requests, ck, cv, cks, cvs, cs, ad=None,
    ):
        """One prefill program over the admitted prompts laid END TO END
        in one row of T tokens (T a prefill bucket, of the prompts' TOTAL).

        `layout` int32 [_TOKEN_ROWS, T], per token: its id; its `segment`,
        the request of this call it belongs to (max_seqs for the padding
        behind the last prompt); its `position` in its own prompt; and
        `dest`, the flat pool row its K/V go to, `page * page_size +
        offset` through the slot's block table (out of bounds for padding,
        which JAX drops: nothing is written outside the prompts' pages).
        `requests` int32 [_REQUEST_ROWS, max_seqs], per request of the
        call: its slot (max_seqs for the rows past the last; it seeds the
        sampling key and names the adapter), its prompt's length (1 there)
        and where its last token stands in the row.

        Attention is causal INSIDE a segment: a token sees the tokens of
        its own prompt at or before its position and no other's, so a
        prompt's rows, logits and token do not depend on what it was
        packed with. An expert layer sorts T x k rows. `ad` is the
        optional slot-indexed adapter gather (tables, has, pools): None
        leaves the traced HLO exactly the base engine's. Returns (ck',
        cv', cks', cvs', next_tokens [max_seqs], last_logits [max_seqs,
        V]: each request's last position, in the call's order), and for a
        model with expert layers their int32 counts after them (and the
        routers' choice [expert layers, 1, T, k], `_step_counts`).

        A model with recurrent layers (`cs`: their per-slot state) has its
        prompts laid so that each BEGINS on a chunk boundary
        (`_prefill_group`), so a chunk of the recurrence holds one
        prompt's tokens only: the carried state is zeroed where a chunk
        starts a prompt, padding is made a no-op, the convolutions' taps
        stop at the prompt's first token, and each request's state after
        its last chunk and its last inputs are written, whole, to its
        slot's row (what the row held is never read)."""
        import jax
        import jax.numpy as jnp

        from flexflow_tpu.ops.attention import (
            mha_project_qkv,
            mha_project_out,
            mla_decompressed,
            mla_project,
            mla_project_out,
            scaled_dot_product_attention,
        )
        from flexflow_tpu.ops.linear_attention import (
            kda_chunked_rows,
            kda_conv,
            kda_out,
            kda_project,
        )
        from flexflow_tpu.serving.tenancy.adapters import (
            adapter_tokens,
            apply_adapter_out,
            apply_adapter_qkv,
        )

        spec = self.cache.spec
        with jax.named_scope("step.unpack"):
            tokens, segment, position, dest = layout
            slot_ids, prompt_lens, last_at = requests
            live = segment < spec.max_seqs
            allowed = (
                (segment[:, None] == segment[None, :])
                & (position[None, :] <= position[:, None])
            )[None]
            ad = adapter_tokens(
                ad, jnp.where(live, slot_ids[segment], spec.max_seqs)
            )
            positions = self._positions(lambda: position[None, :])
            tokens = tokens[None, :]
            share = self._share(live[None, :])
        quant = self.cache.quantized
        new_k, new_v = {}, {}
        new_ks, new_vs = dict(cks), dict(cvs)
        new_s = dict(cs)

        def state_hook(node, ins, ws, ctx):
            g, p = node.guid, node.params
            chunk, kernel = p["chunk"], p["conv_kernel"]
            qkv, decay, beta, z = kda_project(ins[0], ws, p, ctx)
            with jax.named_scope("kda.conv"):
                back = jnp.arange(1, kernel)
                taps = (position[:, None] >= back)[None]
                tails = jnp.zeros((1, kernel - 1, qkv.shape[-1]), qkv.dtype)
            q, k, v, _ = kda_conv(qkv, tails, ws, p, taps)
            with jax.named_scope("kda.scan"):
                fresh = (position[::chunk] == 0)[None]
            # each request's state after its last chunk: its slot's whole
            # row (rows past the last request name slot max_seqs, which
            # is dropped)
            o, rows, took = kda_chunked_rows(
                q, k, v, decay, beta, cs[g]["S"], live, fresh, slot_ids,
                last_at, chunk, ctx,
            )
            kda_took.append(took)
            with jax.named_scope("kda.scan"):
                # and its last inputs (zeros where the prompt is shorter
                # than the kernel)
                ago = back[::-1] - 1  # oldest first: K - 2 .. 0 tokens back
                at = last_at[:, None] - ago
                seen = (prompt_lens[:, None] > ago)[..., None]
                kept = jnp.where(seen, qkv[0, jnp.maximum(at, 0)], 0)
                new_s[g] = {
                    "S": rows,
                    "conv": cs[g]["conv"].at[slot_ids].set(
                        kept.astype(jnp.float32), mode="drop"
                    ),
                }
            return [kda_out(o, z, ws, p, ctx, ins[0].dtype)]

        def hook(node, ins, ws, ctx):
            g = node.guid
            use_bias = node.params.get("bias", True)
            q, k, v = mha_project_qkv(
                ins, ws, ctx, use_bias=use_bias, params=node.params,
                positions=positions,
            )
            q, k, v = apply_adapter_qkv(ins[0], q, k, v, ad, g)
            if quant:
                # scatter inside the hook and attend over the int8
                # ROUND TRIP: a prefix-shared admission later reads
                # these rows dequantized from the pool, so the logits
                # computed here must come from the same lossy values or
                # shared and unshared streams would diverge
                kr = k.reshape(-1, spec.num_heads, spec.head_dim)
                vr = v.reshape(-1, spec.num_heads, spec.head_dim)
                new_k[g], new_ks[g], k_deq = self._quant_scatter(
                    ck[g], cks[g], kr, dest
                )
                new_v[g], new_vs[g], v_deq = self._quant_scatter(
                    cv[g], cvs[g], vr, dest
                )
                k = k_deq.reshape(k.shape).astype(k.dtype)
                v = v_deq.reshape(v.shape).astype(v.dtype)
            else:
                new_k[g] = self._write_rows(ck[g], k, dest)
                new_v[g] = self._write_rows(cv[g], v, dest)
            attn = scaled_dot_product_attention(q, k, v, allowed=allowed)
            out = mha_project_out(
                attn, ws, ctx, ins[0].dtype, use_bias=use_bias
            )
            return [apply_adapter_out(attn, out, ad, g)]

        def latent_hook(node, ins, ws, ctx):
            # the operator's plain lowering (decompressed), and the rows
            q_nope, q_rope, latent = mla_project(
                ins[0], ws, node.params, ctx, positions
            )
            new_k[node.guid] = self._write_latent(ck[node.guid], latent, dest)
            attn = mla_decompressed(
                q_nope, q_rope, latent, ws, node.params, ctx, allowed
            )
            return [mla_project_out(attn, ws, ctx, ins[0].dtype)]

        moe = []
        kda_took = []  # whether each recurrent layer's scan took the kernel
        logits = self._forward_logits(
            params, tokens, hook, moe,
            latent_hook if self._latent else None, share,
            state_hook if self._recurrent else None,
            program=("prefill", tokens.shape[1]),
        )
        self._kda_kernel_traced[("prefill", tokens.shape[1])] = bool(
            kda_took
        ) and all(kda_took)
        with jax.named_scope("step.pick"):
            last = logits[0, last_at]
            nxt = self._pick(last, slot_ids, prompt_lens)
            counts = self._step_counts(moe, share)
        return new_k, new_v, new_ks, new_vs, new_s, nxt, last, *counts

    def prefill(
        self,
        params,
        prompts: Sequence[Sequence[int]],
        slots: Sequence[int],
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Run one admission; writes the cache in place (commit) and
        updates slot lengths. The prompts are packed end to end into one
        row of `bucket(total)` tokens, one program (`_prefill_impl_paged`);
        an admission whose total exceeds the largest bucket is split, in
        the order given, into consecutive groups that fit, one program a
        group, all dispatched before anything is read back. Returns
        (next_tokens [n], last_logits [n, V]) in request order. The
        synchronous wrapper of `prefill_dispatch` + `prefill_reconcile`,
        between which the overlapped loop dispatches a decode step."""
        return self.prefill_reconcile(
            self.prefill_dispatch(params, prompts, slots)
        )

    def prefill_dispatch(
        self,
        params,
        prompts: Sequence[Sequence[int]],
        slots: Sequence[int],
    ) -> PendingPrefill:
        """Enqueue one admission's programs WITHOUT reading anything
        back: the cache arrays commit and the slots' lengths are set
        here. Returns what `prefill_reconcile` reads, a program's outputs
        a group, still on the device, their copies to the host started,
        beside each program's record and its group of the prompts."""
        spec = self.cache.spec
        n = len(prompts)
        if n == 0:
            raise ValueError("prefill needs at least one prompt")
        if n > spec.max_seqs:
            raise ValueError(f"{n} prompts > max_seqs {spec.max_seqs}")
        groups, lo, total = [], 0, 0
        for i, p in enumerate(prompts):
            if not 0 < len(p) <= spec.max_len:
                raise ValueError(
                    f"prompt length {len(p)} outside (0, {spec.max_len}]"
                )
            laid = self._laid(len(p))
            spec.bucket(laid)  # raises for a prompt no bucket holds
            if total + laid > spec.buckets[-1]:
                groups.append((lo, i))
                lo, total = i, 0
            total += laid
        groups.append((lo, n))
        outs, records, choices = [], [], []
        for lo, hi in groups:
            rec, (nxt, last, *moe) = self._prefill_group(
                params, prompts[lo:hi], slots[lo:hi]
            )
            records.append(rec)
            if self._moe_share:
                *moe, choice = moe
                choices.append((choice, [len(p) for p in prompts[lo:hi]]))
            outs.append((nxt[: hi - lo], last[: hi - lo], *moe))
            for a in outs[-1]:
                a.copy_to_host_async()
        if self._moe_share:
            self.moe_choice["prefill"] = _PackedChoice(
                choices, self._state_chunk
            )
        return PendingPrefill(outs, records, groups)

    def prefill_reconcile(
        self, pending: PendingPrefill
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Wait for a dispatched admission's tokens, logits and counts:
        (next_tokens [n], last_logits [n, V]) in request order."""
        outs = pending.outs
        host = self._readback(
            "prefill", *(a for out in outs for a in out),
            records=pending.records,
        )
        width = len(outs[0])  # tokens, logits, and the counts if any
        host = [host[i : i + width] for i in range(0, len(host), width)]
        for _, _, *counts in host:
            if counts:
                self._count("prefill", counts[0])
        return (
            np.concatenate([nxt for nxt, *_ in host]),
            np.concatenate([last for _, last, *_ in host]),
        )

    def _laid(self, n: int) -> int:
        """The tokens a prompt of `n` takes of a packed row: itself, and
        for a model with recurrent layers the padding up to the next
        chunk boundary, where the next prompt begins."""
        return -(-n // self._state_chunk) * self._state_chunk

    def _prefill_group(self, params, prompts, slots):
        """Pack `prompts` (whose total fits the largest bucket) into one
        row and dispatch its program: its record, and
        `_prefill_impl_paged`'s outputs
        behind the pools, still on the device. Padding, behind the last
        prompt or up to a chunk boundary between two (`_laid`), is
        segment max_seqs with an out-of-bounds destination."""
        import jax.numpy as jnp

        spec = self.cache.spec
        ps = spec.page_size
        lens = [len(p) for p in prompts]
        total = sum(lens)
        bucket = spec.bucket(sum(self._laid(n) for n in lens))
        with span(
            "scheduler.step.prefill.pack", self._tracer,
            {"prompts": len(prompts), "bucket": bucket},
        ):
            layout = np.zeros((self._TOKEN_ROWS, bucket), dtype=np.int32)
            layout[1] = spec.max_seqs
            layout[3] = spec.total_rows
            requests = np.zeros(
                (self._REQUEST_ROWS, spec.max_seqs), dtype=np.int32
            )
            requests[0, len(prompts):] = spec.max_seqs
            requests[1, len(prompts):] = 1
            at = 0
            for i, (p, s, n) in enumerate(zip(prompts, slots, lens)):
                pos = np.arange(n)
                layout[0, at : at + n] = np.asarray(p, dtype=np.int32)
                layout[1, at : at + n] = i
                layout[2, at : at + n] = pos
                layout[3, at : at + n] = (
                    self.cache.block_tables[s, pos // ps] * ps + pos % ps
                )
                requests[:, i] = (s, n, at + n - 1)
                at += self._laid(n)
            self.prefill_tokens_real += total
            self.state_resets_prefill += len(prompts) * len(self._recurrent)
            self.prefill_tokens_padded += bucket
            self.prefill_programs += 1
            fn = self._prefill_cache.get(bucket)
            if fn is None:
                fn = self._step_jit(self._prefill_impl_paged)
                self._prefill_cache[bucket] = fn
            inputs = (jnp.asarray(layout), jnp.asarray(requests))
        with span("scheduler.step.prefill.dispatch", self._tracer):
            rec = StepRecord("prefill", rows=total, bucket=bucket)
            out = self._run_step(
                "prefill", lambda: fn, params, inputs,
                self._adapter_slot_args(), kernel_path=False, record=rec,
            )
            self.moe_kernel_programs_prefill += self._moe_kernel_traced.get(
                ("prefill", bucket), False
            )
            self.kda_kernel_programs_prefill += self._kda_kernel_traced.get(
                ("prefill", bucket), False
            )
            for s, n in zip(slots, lens):
                self.cache.lengths[s] = n
        return rec, out

    def prefill_suffix(
        self,
        params,
        prompts: Sequence[Sequence[int]],
        slots: Sequence[int],
        cursors: Sequence[int],
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Prefill only tokens[cursor:] of each prompt — the admission
        path for prefix-shared requests. The shared pages already hold
        positions [0, cursor) (alloc_shared mapped them and parked
        cache.lengths at the cursor), so this runs ONE chunked-prefill
        step over the unshared suffixes: the chunk core's staircase
        mask with query_offset = cursor reads the shared pages through
        the block table and agrees with the monolithic prefill to
        float32 rounding (the same rows under the same mask, in another
        program), and the sampled token
        lands at each request's FULL prompt length — the same _pick key
        the monolithic path uses. Returns (next_tokens [n],
        last_logits [n, V]) in request order."""
        spec = self.cache.spec
        if not prompts:
            raise ValueError("prefill_suffix needs at least one prompt")
        suffixes = []
        for p, c in zip(prompts, cursors):
            c = int(c)
            if not 0 <= c < len(p):
                raise ValueError(
                    f"cursor {c} outside [0, {len(p)}) — at least one "
                    "prompt token must be recomputed for sampling logits"
                )
            suffixes.append(list(p[c:]))
        w = max(len(sfx) for sfx in suffixes)
        with span(
            "scheduler.step.prefill_suffix", self._tracer,
            {"prompts": len(prompts), "width": w},
        ):
            tokens = np.zeros((spec.max_seqs, w), dtype=np.int32)
            chunk_lens = np.zeros(spec.max_seqs, dtype=np.int32)
            for sfx, s in zip(suffixes, slots):
                tokens[s, : len(sfx)] = np.asarray(sfx, dtype=np.int32)
                chunk_lens[s] = len(sfx)
            nxt, logits = self.prefill_chunk(params, tokens, chunk_lens)
        return (
            np.asarray([nxt[s] for s in slots]),
            np.stack([logits[s] for s in slots]),
        )

    # -- decode --------------------------------------------------------------

    #: columns of a decode step's packed host state in front of the slot's
    #: block table: the host's view of the last token, whether the token
    #: comes from the chained step instead, the cache length, and activity
    _STATE_COLUMNS = 4

    def _decode_impl_paged(
        self, params, chained, state, ck, cv, cks, cvs, cs, ad=None,
    ):
        """The decode step's jit target: one forward over tokens
        [max_seqs, 1] plus the per-slot sample (the sampled token will be
        written at cache position lengths + 1).

        Everything the host knows about the step arrives as ONE int32
        array, `state` [max_seqs, _STATE_COLUMNS + max_pages_per_seq]
        (`_pack_state`: token, chain flag, length, active, block table),
        and everything it decides on leaves as one: after the pools, the
        sampled tokens [max_seqs], the logits [max_seqs, V] (read by who
        asks), and `readback`, int32 [2 * max_seqs + counts]: the tokens
        again, whether each slot's logits row is finite, and the expert
        layers' counts. `chained`: int32 [max_seqs] on the device, an
        earlier step's sampled tokens, taken where the chain flag is set
        so that consecutive steps' data dependency stays on the device;
        always there (`decode_dispatch` hands a step without a chain the
        newest tokens it has, and the flags are all 0), so a model has
        ONE decode program whoever feeds a slot.

        lengths [max_seqs] = cache position the incoming token is
        written at. The new K/V row scatters into `tables[slot, lengths
        // page_size] * page_size + lengths % page_size` of the flattened
        pool; inactive slots are routed to an out-of-bounds destination
        (dropped). `ad=None` (no adapter pool) leaves the traced HLO
        byte-for-byte what it was before multi-LoRA existed. `cs`: the
        recurrent layers' per-slot state; a step advances the rows of its
        `active` slots and hands every other row back bit-equal."""
        import jax
        import jax.numpy as jnp

        from flexflow_tpu.ops.attention import (
            mha_project_qkv,
            mha_project_out,
            mla_absorb_query,
            mla_absorb_values,
            mla_project,
            mla_project_out,
            paged_decode_attention,
            paged_latent_decode_attention,
        )
        from flexflow_tpu.ops.linear_attention import (
            kda_conv,
            kda_out,
            kda_project,
            kda_step_live,
        )
        from flexflow_tpu.serving.tenancy.adapters import (
            apply_adapter_out,
            apply_adapter_qkv,
        )

        spec = self.cache.spec
        ps = spec.page_size
        oob = spec.num_pages * ps
        quant = self.cache.quantized
        new_k = dict(ck)
        new_v = dict(cv)
        new_ks, new_vs = dict(cks), dict(cvs)
        new_s = dict(cs)
        with jax.named_scope("step.unpack"):
            tokens, from_chain, lengths = state[:, 0], state[:, 1], state[:, 2]
            active = state[:, 3] != 0
            tables = state[:, self._STATE_COLUMNS:]
            tokens = jnp.where(from_chain != 0, chained, tokens)[:, None]
            share = self._share(active[:, None])
            page = jnp.take_along_axis(
                tables, (lengths // ps)[:, None], axis=1
            )[:, 0]
            dest = jnp.where(active, page * ps + lengths % ps, oob)
            positions = self._positions(lambda: lengths[:, None])

        def hook(node, ins, ws, ctx):
            g = node.guid
            use_bias = node.params.get("bias", True)
            q, k, v = mha_project_qkv(
                ins, ws, ctx, use_bias=use_bias, params=node.params,
                positions=positions,
            )
            # adapted K/V go INTO the pool (delta precedes the scatter),
            # so the attention core (dense or Pallas) reads adapter-aware
            # pages unchanged: the out-projection delta below is the only
            # post-kernel epilogue
            q, k, v = apply_adapter_qkv(ins[0], q, k, v, ad, g)
            if quant:
                kc, new_ks[g], _ = self._quant_scatter(
                    ck[g], cks[g], k[:, 0], dest
                )
                vc, new_vs[g], _ = self._quant_scatter(
                    cv[g], cvs[g], v[:, 0], dest
                )
                attn = paged_decode_attention(
                    q, kc, vc, tables, lengths, **self._attn_core,
                    k_scale=new_ks[g], v_scale=new_vs[g],
                )
            else:
                kc = self._write_rows(ck[g], k, dest)
                vc = self._write_rows(cv[g], v, dest)
                attn = paged_decode_attention(
                    q, kc, vc, tables, lengths, **self._attn_core
                )
            new_k[g] = kc
            new_v[g] = vc
            out = mha_project_out(
                attn, ws, ctx, ins[0].dtype, use_bias=use_bias
            )
            return [apply_adapter_out(attn, out, ad, g)]

        def latent_hook(node, ins, ws, ctx):
            # absorbed: the one new row is written, and every head
            # attends over the latent rows themselves, through the pool
            g, p = node.guid, node.params
            q_nope, q_rope, latent = mla_project(ins[0], ws, p, ctx, positions)
            new_k[g] = self._write_latent(ck[g], latent, dest)
            attended = paged_latent_decode_attention(
                mla_absorb_query(q_nope, q_rope, ws, p, ctx, spec.row_width),
                new_k[g], tables, lengths, p["kv_lora_rank"],
                (p["qk_nope_head_dim"] + p["qk_rope_head_dim"]) ** -0.5,
                kernel=self.decode_kernel,
            )
            attn = mla_absorb_values(attended, ws, p, ctx)
            return [mla_project_out(attn, ws, ctx, ins[0].dtype)]

        def state_hook(node, ins, ws, ctx):
            # one token a slot: the convolutions over the slot's tails,
            # one step of the recurrence on the slot's state
            g, p = node.guid, node.params
            qkv, decay, beta, z = kda_project(ins[0], ws, p, ctx)
            q, k, v, tails = kda_conv(qkv, cs[g]["conv"], ws, p)
            o, state, kernel = kda_step_live(
                q[:, 0], k[:, 0], v[:, 0], decay[:, 0], beta[:, 0],
                cs[g]["S"], active, ctx,
            )
            kda_took.append(kernel)
            with jax.named_scope("kda.step"):
                new_s[g] = {
                    "S": state,
                    "conv": jnp.where(
                        active[:, None, None], tails, cs[g]["conv"]
                    ),
                }
            return [kda_out(o[:, None], z, ws, p, ctx, ins[0].dtype)]

        moe = []  # receives the expert layers' counts
        kda_took = []  # whether each recurrent layer's step took the kernel
        logits = self._forward_logits(
            params, tokens, hook, moe, latent_hook if self._latent else None,
            share, state_hook if self._recurrent else None,
            program=("decode",),
        )
        self._kda_kernel_traced[("decode",)] = bool(kda_took) and all(kda_took)
        with jax.named_scope("step.pick"):
            logits = logits[:, -1, :]
            slots = jnp.arange(lengths.shape[0])
            nxt = self._pick(logits, slots, lengths + 1)
            extra = self._step_counts(moe, share)
            n = bool(self._count_fields)  # the counts, then the choice
            readback = jnp.concatenate(
                [
                    nxt,
                    jnp.isfinite(logits).all(-1).astype(jnp.int32),
                    *extra[:n],
                ]
            )
        return (
            new_k, new_v, new_ks, new_vs, new_s, nxt, logits, readback,
            *extra[n:],
        )

    def _chain_feed(self, params):
        """The `chained` argument of a decode step that chains on nothing:
        the newest decode step's tokens, so that the argument is placed as
        a chain's is and jit keeps one executable for the one program.
        Before any step has run, zeros placed where the program's outputs
        will be: with the weights (committed to their device, or
        replicated over their mesh)."""
        if self._last_next is None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec

            zeros = np.zeros(self.cache.spec.max_seqs, dtype=np.int32)
            leaf = jax.tree_util.tree_leaves(params)[0]
            if getattr(leaf, "committed", False):
                where = leaf.sharding
                if isinstance(where, NamedSharding):
                    where = NamedSharding(where.mesh, PartitionSpec())
                self._last_next = jax.device_put(zeros, where)
            else:
                self._last_next = jax.numpy.asarray(zeros)
        return self._last_next

    def _pack_state(self, tokens, from_chain, active) -> np.ndarray:
        """A decode step's host state as the ONE int32 array
        `_decode_impl_paged` takes apart: per slot the last token, the
        chain flag, the cache length, activity, then the block table. A
        fresh array each call; the lengths and tables in it are the
        host's mutable state, so it goes to the device through
        `snapshot()` like them."""
        cache = self.cache
        c = self._STATE_COLUMNS
        state = np.empty(
            (cache.spec.max_seqs, c + cache.block_tables.shape[1]),
            dtype=np.int32,
        )
        state[:, 0] = tokens
        state[:, 1] = from_chain
        state[:, 2] = cache.lengths
        state[:, 3] = active
        state[:, c:] = cache.block_tables
        return state

    def decode_dispatch(
        self,
        params,
        tokens: np.ndarray,
        active_mask: np.ndarray,
        chain: Optional[InflightStep] = None,
        chain_mask: Optional[np.ndarray] = None,
    ) -> InflightStep:
        """Enqueue one decode iteration WITHOUT blocking on its outputs
        (but for a program's first dispatch on a kernel path, which
        `_dispatch` forces: a warm-up's).

        tokens [max_seqs] (last emitted token per slot; free slots can
        carry anything), active_mask [max_seqs] bool. They cross to the
        device with the lengths and the block tables as one packed
        argument (`_pack_state`). The functional cache arrays commit
        immediately (they are device futures — the next dispatch chains
        on them on-device) and active lengths bump, so the host's view
        is reserved-one-step-ahead. What the reconcile will read, the
        step's `device_readback`, starts its copy to the host here, right
        behind the program; the sampled tokens and the logits stay device
        futures on the returned InflightStep.

        `chain` + `chain_mask` pipeline two decode steps with no host
        round-trip: where chain_mask is set, the input token comes from
        the in-flight `chain` step's device_next instead of the host
        `tokens` row — the data dependency between step N and N+1
        resolves entirely on device (the program's second argument).
        A step without a chain runs the same program: it is handed the
        newest decode step's tokens (`_chain_feed`), which its all-zero
        chain flags leave unread."""
        active = np.asarray(active_mask, dtype=bool)
        # the next page, for any sequence about to cross a page boundary
        self._claim_rows(active.astype(np.int32))
        host_tokens = np.asarray(tokens, dtype=np.int32)
        mask = (
            np.asarray(chain_mask, dtype=bool)
            if chain is not None and chain_mask is not None
            else np.zeros_like(active)
        )
        chained = (
            chain.device_next if chain is not None
            else self._chain_feed(params)
        )
        lengths_snap = np.array(self.cache.lengths)
        rec = StepRecord("decode", rows=int(active.sum()))
        # snapshot(): lengths += 1 below, and allocator table edits
        # between iterations, mutate behind the async dispatch queue
        nxt, logits, readback = self._keep_choice(
            self._run_step(
                "decode",
                lambda: self._decode_jit,
                params,
                (chained, snapshot(self._pack_state(host_tokens, mask, active))),
                self._adapter_slot_args(),
                record=rec,
            ),
        )
        self._last_next = nxt
        readback.copy_to_host_async()
        self.moe_kernel_programs_decode += self._moe_kernel_traced.get(
            ("decode",), False
        )
        self.cache.lengths[active] += 1
        if self._latent:
            # the live latent rows this step attends, the new one included
            self.mla_rows_read_decode += len(self._latent) * int(
                self.cache.lengths[active].sum()
            )
        # the slots' rows of per-slot state this step advances
        self.state_rows_decode += len(self._recurrent) * int(active.sum())
        self.kda_kernel_programs_decode += self._kda_kernel_traced.get(
            ("decode",), False
        )
        # the in-flight window pins pages this step's snapshot tables
        # reference; decode_reconcile closes it
        self.cache.begin_inflight()
        return InflightStep(
            kind="decode",
            record=rec,
            active=active.copy(),
            lengths=lengths_snap,
            host_tokens=host_tokens,
            device_next=nxt,
            device_logits=logits,
            device_readback=readback,
        )

    def decode_reconcile(
        self, step: InflightStep
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Wait, once, for the one small array a dispatched decode step
        started copying to the host (`step.device_readback`), and close
        the step's in-flight window. Returns (next_tokens [max_seqs]
        int32, finite [max_seqs] bool: whether the slot's logits row is
        finite) as host arrays; the logits themselves stay on the device
        (`step.device_logits`) for who asks. Everything else the caller
        needs lives on the step record's snapshots — by the time this
        runs, live cache/scheduler state is one iteration ahead."""
        try:
            (got,) = self._readback(
                "decode", step.device_readback, records=(step.record,)
            )
        finally:
            self.cache.end_inflight()
        n = step.active.shape[0]
        if self._count_fields:
            self._count("decode", got[2 * n:])
        return got[:n], got[n: 2 * n] != 0

    def decode(
        self,
        params,
        tokens: np.ndarray,
        active_mask: np.ndarray,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """One decode iteration over every slot — the synchronous wrapper
        (dispatch + immediate reconcile); the async loop calls the two
        halves an iteration apart. Writes the cache, bumps active
        lengths, returns (next_tokens [max_seqs], logits [max_seqs, V]):
        this caller asks for the logits, so it reads them."""
        step = self.decode_dispatch(params, tokens, active_mask)
        nxt, _ = self.decode_reconcile(step)
        (logits,) = self._readback("decode", step.device_logits)
        return nxt, logits

    # -- verify (speculative decoding) ---------------------------------------

    def _verify_scatter_dest(self, w, lengths, draft_lens, tables, jnp):
        """Flattened-pool destinations [batch * w] for the verify
        write: row j of batch row b lands at cache position
        lengths[b] + j when j < draft_lens[b] and the position is
        inside max_len; every other row routes out of bounds (JAX
        drops OOB scatter rows), so pad rows, inactive slots, and
        overflow never touch live cache. `tables` rows arrive
        batch-aligned (slot-indexed, or gathered to a COMPACT batch's
        rows by the chunked-prefill path)."""
        spec = self.cache.spec
        pos = lengths[:, None] + jnp.arange(w)[None, :]  # [batch, w]
        valid = (jnp.arange(w)[None, :] < draft_lens[:, None]) & (
            pos < spec.max_len
        )
        ps = spec.page_size
        page_idx = jnp.clip(pos // ps, 0, spec.max_pages_per_seq - 1)
        entry = jnp.take_along_axis(tables, page_idx, axis=1)
        # sentinel entries (num_pages) already land past the pool
        flat = entry * ps + pos % ps
        return jnp.where(valid, flat, spec.num_pages * ps).reshape(-1)

    def _verify_impl_paged(
        self, params, tokens, lengths, draft_lens, tables, ck, cv, cks, cvs,
        cs, ad=None,
    ):
        """tokens [max_seqs, w] int32 — column 0 is each slot's last
        emitted (not yet cached) token, columns 1..draft_lens-1 the
        drafted continuation; lengths [max_seqs] = cache length BEFORE
        the step; draft_lens [max_seqs] = real rows per slot (0 for
        inactive slots). Writes all w K/V rows through the block tables
        into the flattened pools (masked via OOB scatter), runs
        staircase-masked verify attention
        (ops.attention.paged_verify_attention), and returns (ck', cv',
        cks', cvs', logits [max_seqs, w, V]) — logits[s, j] is the
        model's distribution for the token FOLLOWING tokens[s, j].
        Lengths are NOT advanced; acceptance commits via
        cache.truncate. Under int8 pools the w
        fresh rows quantize through `_quant_scatter` and the per-page
        scales ride along to the attention gather."""
        import jax
        import jax.numpy as jnp

        from flexflow_tpu.ops.attention import (
            mha_project_qkv,
            mha_project_out,
            paged_verify_attention,
        )
        from flexflow_tpu.serving.tenancy.adapters import (
            apply_adapter_out,
            apply_adapter_qkv,
        )

        spec = self.cache.spec
        quant = self.cache.quantized
        with jax.named_scope("step.unpack"):
            dest = self._verify_scatter_dest(
                tokens.shape[1], lengths, draft_lens, tables, jnp
            )
            positions = self._positions(
                lambda: lengths[:, None]
                + jnp.arange(tokens.shape[1])[None, :]
            )
        new_k = dict(ck)
        new_v = dict(cv)
        new_ks = dict(cks)
        new_vs = dict(cvs)

        def hook(node, ins, ws, ctx):
            g = node.guid
            use_bias = node.params.get("bias", True)
            q, k, v = mha_project_qkv(
                ins, ws, ctx, use_bias=use_bias, params=node.params,
                positions=positions,
            )
            q, k, v = apply_adapter_qkv(ins[0], q, k, v, ad, g)
            if quant:
                kc, new_ks[g], _ = self._quant_scatter(
                    ck[g],
                    cks[g],
                    k.reshape(-1, spec.num_heads, spec.head_dim),
                    dest,
                )
                vc, new_vs[g], _ = self._quant_scatter(
                    cv[g],
                    cvs[g],
                    v.reshape(-1, spec.num_heads, spec.head_dim),
                    dest,
                )
                new_k[g] = kc
                new_v[g] = vc
                attn = paged_verify_attention(
                    q,
                    kc,
                    vc,
                    tables,
                    lengths,
                    **self._attn_core,
                    k_scale=new_ks[g],
                    v_scale=new_vs[g],
                )
            else:
                kc = self._write_rows(ck[g], k, dest)
                vc = self._write_rows(cv[g], v, dest)
                new_k[g] = kc
                new_v[g] = vc
                attn = paged_verify_attention(
                    q, kc, vc, tables, lengths, **self._attn_core
                )
            out = mha_project_out(
                attn, ws, ctx, ins[0].dtype, use_bias=use_bias
            )
            return [apply_adapter_out(attn, out, ad, g)]

        logits = self._forward_logits(params, tokens, hook)
        return new_k, new_v, new_ks, new_vs, cs, logits

    def _verify_tree_impl_paged(
        self, params, tokens, lengths, draft_lens, parents, tables, ck, cv,
        cks, cvs, cs, ad=None,
    ):
        """Tree twin of _verify_impl_paged: tokens [max_seqs, w] where
        column 0 is the slot's last emitted token (the tree ROOT's
        input) and columns 1..w-1 are draft-tree nodes in topological
        order; parents [max_seqs, w] int32 gives each row's parent ROW
        index (-1 for row 0). The per-token ancestor mask replaces the
        staircase: row j attends the committed prefix plus its own
        root-to-j chain only, so every branch scores exactly as if it
        were the lone continuation. K/V rows still land at positions
        lengths + j — branch tokens occupy scattered rows that
        cache.truncate(slot, new_len, src_rows) later compacts."""
        import jax
        import jax.numpy as jnp

        from flexflow_tpu.ops.attention import (
            mha_project_qkv,
            mha_project_out,
            paged_verify_attention,
        )
        from flexflow_tpu.serving.tenancy.adapters import (
            apply_adapter_out,
            apply_adapter_qkv,
        )

        spec = self.cache.spec
        quant = self.cache.quantized
        with jax.named_scope("step.unpack"):
            dest = self._verify_scatter_dest(
                tokens.shape[1], lengths, draft_lens, tables, jnp
            )
            positions = self._positions(
                lambda: lengths[:, None] + _tree_depths(parents)
            )
        new_k = dict(ck)
        new_v = dict(cv)
        new_ks = dict(cks)
        new_vs = dict(cvs)

        def hook(node, ins, ws, ctx):
            g = node.guid
            use_bias = node.params.get("bias", True)
            q, k, v = mha_project_qkv(
                ins, ws, ctx, use_bias=use_bias, params=node.params,
                positions=positions,
            )
            q, k, v = apply_adapter_qkv(ins[0], q, k, v, ad, g)
            if quant:
                kc, new_ks[g], _ = self._quant_scatter(
                    ck[g],
                    cks[g],
                    k.reshape(-1, spec.num_heads, spec.head_dim),
                    dest,
                )
                vc, new_vs[g], _ = self._quant_scatter(
                    cv[g],
                    cvs[g],
                    v.reshape(-1, spec.num_heads, spec.head_dim),
                    dest,
                )
                new_k[g] = kc
                new_v[g] = vc
                attn = paged_verify_attention(
                    q,
                    kc,
                    vc,
                    tables,
                    lengths,
                    **self._attn_core,
                    k_scale=new_ks[g],
                    v_scale=new_vs[g],
                    tree_parents=parents,
                )
            else:
                kc = self._write_rows(ck[g], k, dest)
                vc = self._write_rows(cv[g], v, dest)
                new_k[g] = kc
                new_v[g] = vc
                attn = paged_verify_attention(
                    q,
                    kc,
                    vc,
                    tables,
                    lengths,
                    **self._attn_core,
                    tree_parents=parents,
                )
            out = mha_project_out(
                attn, ws, ctx, ins[0].dtype, use_bias=use_bias
            )
            return [apply_adapter_out(attn, out, ad, g)]

        logits = self._forward_logits(params, tokens, hook)
        return new_k, new_v, new_ks, new_vs, cs, logits

    def verify_dispatch(
        self,
        params,
        tokens: np.ndarray,
        draft_lens: np.ndarray,
    ) -> InflightStep:
        """Enqueue one verify step (SpecInfer's scoring call) WITHOUT
        blocking on its logits. tokens [max_seqs, w]: column 0 is the
        slot's last emitted token (the one plain decode would feed),
        columns 1..draft_lens[s]-1 its drafted continuation; rows with
        draft_lens 0 are inactive. Writes the w K/V rows into the cache
        (paged slots claim the pages those rows need first — the
        admission reserve covers them as long as the caller keeps
        drafts inside the request's declared worst case) but does NOT
        advance lengths: `verify_reconcile` hands back the logits
        [max_seqs, w, V], and the caller accepts a prefix of the drafts
        against the step's SNAPSHOT lengths, committing/rolling back
        with cache.truncate(slot, new_len). One jitted program per
        draft width w, LRU-cached (`verify_cache_max`)."""
        import jax.numpy as jnp

        spec = self.cache.spec
        tokens = np.asarray(tokens, dtype=np.int32)
        draft_lens = np.asarray(draft_lens, dtype=np.int32)
        if tokens.ndim != 2 or tokens.shape[0] != spec.max_seqs:
            raise ValueError(
                f"tokens must be [max_seqs={spec.max_seqs}, w], "
                f"got {tokens.shape}"
            )
        w = tokens.shape[1]
        if w < 1:
            raise ValueError("verify needs at least one token column")
        if draft_lens.shape != (spec.max_seqs,):
            raise ValueError("draft_lens must be [max_seqs]")
        for slot in np.nonzero(draft_lens)[0]:
            need = int(self.cache.lengths[slot]) + int(draft_lens[slot])
            if draft_lens[slot] > w or need > spec.max_len:
                raise ValueError(
                    f"slot {int(slot)}: draft_lens {int(draft_lens[slot])} "
                    f"overruns width {w} or max_len {spec.max_len}"
                )
        self._claim_rows(draft_lens)
        lengths_snap = np.array(self.cache.lengths)
        # snapshot() lengths/tables: the caller truncates the cache
        # right after the reconcile, and jnp.asarray's host read is
        # deferred behind the dispatch queue — see decode_dispatch()
        rec = StepRecord("verify", rows=int(np.count_nonzero(draft_lens)))
        (logits,) = self._run_step(
            "verify",
            lambda: self._verify_fn(w),
            params,
            (
                jnp.asarray(tokens),
                snapshot(self.cache.lengths),
                jnp.asarray(draft_lens),
                snapshot(self.cache.block_tables),
            ),
            self._adapter_slot_args(),
            program=("verify", w),
            record=rec,
        )
        self.cache.begin_inflight()
        return InflightStep(
            kind="verify",
            record=rec,
            active=np.asarray(draft_lens) > 0,
            lengths=lengths_snap,
            draft_lens=np.array(draft_lens),
            device_logits=logits,
        )

    def verify_reconcile(self, step: InflightStep) -> np.ndarray:
        """Block on a dispatched verify step's logits and close its
        in-flight window. Acceptance/rollback decisions belong to the
        caller, made against the step record's SNAPSHOT lengths."""
        try:
            return self._readback(
                step.kind, step.device_logits, records=(step.record,)
            )[0]
        finally:
            self.cache.end_inflight()

    def verify(
        self,
        params,
        tokens: np.ndarray,
        draft_lens: np.ndarray,
    ) -> np.ndarray:
        """Synchronous verify (dispatch + immediate reconcile): returns
        the logits [max_seqs, w, V] as a host array."""
        return self.verify_reconcile(
            self.verify_dispatch(params, tokens, draft_lens)
        )

    def verify_tree_dispatch(
        self,
        params,
        tokens: np.ndarray,
        draft_lens: np.ndarray,
        parents: np.ndarray,
    ) -> InflightStep:
        """Enqueue one tree-verify step (SpecInfer's tree-scoring call)
        WITHOUT blocking. tokens [max_seqs, w]: column 0 the slot's last
        emitted token, columns 1..draft_lens[s]-1 its draft-TREE nodes
        in topological order; parents [max_seqs, w] int32 maps each row
        to its parent row (-1 for the root, identity-chain padding past
        draft_lens). The ancestor mask is built from `parents` INSIDE
        the jitted step, so one compiled program serves every tree
        topology of width w. Page claims, cache commit, and the
        no-length-advance contract match verify_dispatch exactly; the
        returned step carries `tree_parents` (a host snapshot of the
        dispatched table) for the reconcile's tree walk."""
        import jax.numpy as jnp

        spec = self.cache.spec
        tokens = np.asarray(tokens, dtype=np.int32)
        draft_lens = np.asarray(draft_lens, dtype=np.int32)
        parents = np.asarray(parents, dtype=np.int32)
        if tokens.ndim != 2 or tokens.shape[0] != spec.max_seqs:
            raise ValueError(
                f"tokens must be [max_seqs={spec.max_seqs}, w], "
                f"got {tokens.shape}"
            )
        w = tokens.shape[1]
        if w < 1:
            raise ValueError("verify needs at least one token column")
        if draft_lens.shape != (spec.max_seqs,):
            raise ValueError("draft_lens must be [max_seqs]")
        if parents.shape != tokens.shape:
            raise ValueError(
                f"parents must match tokens shape {tokens.shape}, "
                f"got {parents.shape}"
            )
        if np.any(parents >= np.arange(w)[None, :]):
            raise ValueError(
                "parents must be topological: parents[:, j] < j"
            )
        for slot in np.nonzero(draft_lens)[0]:
            need = int(self.cache.lengths[slot]) + int(draft_lens[slot])
            if draft_lens[slot] > w or need > spec.max_len:
                raise ValueError(
                    f"slot {int(slot)}: draft_lens {int(draft_lens[slot])} "
                    f"overruns width {w} or max_len {spec.max_len}"
                )
        self._claim_rows(draft_lens)
        lengths_snap = np.array(self.cache.lengths)
        rec = StepRecord(
            "verify_tree", rows=int(np.count_nonzero(draft_lens))
        )
        (logits,) = self._run_step(
            "verify",
            lambda: self._tree_fn(w),
            params,
            (
                jnp.asarray(tokens),
                snapshot(self.cache.lengths),
                jnp.asarray(draft_lens),
                jnp.asarray(parents),
                snapshot(self.cache.block_tables),
            ),
            self._adapter_slot_args(),
            program=("tree", w),
            record=rec,
        )
        self.cache.begin_inflight()
        return InflightStep(
            kind="verify_tree",
            record=rec,
            active=np.asarray(draft_lens) > 0,
            lengths=lengths_snap,
            draft_lens=np.array(draft_lens),
            device_logits=logits,
            tree_parents=np.array(parents),
        )

    def verify_tree(
        self,
        params,
        tokens: np.ndarray,
        draft_lens: np.ndarray,
        parents: np.ndarray,
    ) -> np.ndarray:
        """Synchronous tree verify: returns logits [max_seqs, w, V] as a
        host array (reconcile shares verify_reconcile — the tree walk is
        the caller's, made against the step's snapshots)."""
        return self.verify_reconcile(
            self.verify_tree_dispatch(params, tokens, draft_lens, parents)
        )

    # -- chunked prefill -----------------------------------------------------

    def _chunk_impl_paged(
        self, params, tokens, slot_ids, all_lengths, chunk_lens, tables,
        ck, cv, cks, cvs, cs, ad=None,
    ):
        """tokens [B, w] int32 — the next chunk_lens[b] PROMPT tokens
        of each ACTIVE prefilling slot slot_ids[b] (0-padded);
        all_lengths [max_seqs] = every slot's cache cursor and tables
        [max_seqs, pages] the full block tables (the impl gathers its
        own rows, so dest and attention both see batch-aligned tables).
        The batch is COMPACTED to chunking
        slots: a lone long prompt streaming through the budget costs
        B=1 rows of transformer compute per chunk step instead of
        max_seqs. The verify core is otherwise verbatim — staircase mask
        with query_offset = cursor gives exact causal prefill
        semantics, and the same fp32 accumulation / -1e30 fill keeps
        chunked prefill logit-identical to the monolithic path (each
        batch row's reduction is independent, so compaction cannot
        move a logit) — plus the monolithic prefill's tail: the last
        valid position's logits are sampled at position cursor + chunk
        (== prompt length on the final chunk, so the first generated
        token matches _prefill_impl_paged's exactly). Returns (ck', cv',
        cks', cvs', next_tokens [B], last_logits [B, V]) in compact
        order; prefill_chunk_reconcile scatters them back to
        slot-indexed arrays."""
        import jax
        import jax.numpy as jnp

        from flexflow_tpu.ops.attention import (
            mha_project_qkv,
            mha_project_out,
            paged_verify_attention,
        )
        from flexflow_tpu.serving.tenancy.adapters import (
            adapter_rows,
            apply_adapter_out,
            apply_adapter_qkv,
        )

        spec = self.cache.spec
        w = tokens.shape[1]
        with jax.named_scope("step.unpack"):
            lengths = all_lengths[slot_ids]  # [B] cursor per active slot
            ad = adapter_rows(ad, slot_ids)
            tables_g = tables[slot_ids]  # [B, pages] batch-aligned
            dest = self._verify_scatter_dest(
                w, lengths, chunk_lens, tables_g, jnp
            )
            positions = self._positions(
                lambda: lengths[:, None] + jnp.arange(w)[None, :]
            )
        quant = self.cache.quantized
        new_k = dict(ck)
        new_v = dict(cv)
        new_ks = dict(cks)
        new_vs = dict(cvs)

        def hook(node, ins, ws, ctx):
            g = node.guid
            use_bias = node.params.get("bias", True)
            q, k, v = mha_project_qkv(
                ins, ws, ctx, use_bias=use_bias, params=node.params,
                positions=positions,
            )
            q, k, v = apply_adapter_qkv(ins[0], q, k, v, ad, g)
            if quant:
                kc, new_ks[g], _ = self._quant_scatter(
                    ck[g],
                    cks[g],
                    k.reshape(-1, spec.num_heads, spec.head_dim),
                    dest,
                )
                vc, new_vs[g], _ = self._quant_scatter(
                    cv[g],
                    cvs[g],
                    v.reshape(-1, spec.num_heads, spec.head_dim),
                    dest,
                )
                new_k[g] = kc
                new_v[g] = vc
                attn = paged_verify_attention(
                    q,
                    kc,
                    vc,
                    tables_g,
                    lengths,
                    **self._attn_core,
                    k_scale=new_ks[g],
                    v_scale=new_vs[g],
                )
            else:
                kc = self._write_rows(ck[g], k, dest)
                vc = self._write_rows(cv[g], v, dest)
                new_k[g] = kc
                new_v[g] = vc
                attn = paged_verify_attention(
                    q, kc, vc, tables_g, lengths, **self._attn_core
                )
            out = mha_project_out(
                attn, ws, ctx, ins[0].dtype, use_bias=use_bias
            )
            return [apply_adapter_out(attn, out, ad, g)]

        logits = self._forward_logits(params, tokens, hook)
        with jax.named_scope("step.pick"):
            last = jnp.take_along_axis(
                logits,
                jnp.clip(chunk_lens - 1, 0, w - 1)[:, None, None],
                axis=1,
            )[:, 0]
            nxt = self._pick(last, slot_ids, lengths + chunk_lens)
        return new_k, new_v, new_ks, new_vs, cs, nxt, last

    def prefill_chunk_dispatch(
        self,
        params,
        tokens: np.ndarray,
        chunk_lens: np.ndarray,
    ) -> InflightStep:
        """Enqueue one chunked-prefill step WITHOUT blocking on its
        outputs. tokens [max_seqs, w]: the next chunk_lens[s] prompt
        tokens per chunking slot (rows with chunk_lens 0 are inactive).
        Writes the chunk K/V rows at each slot's cursor (paged slots
        claim the pages those rows need first) and — unlike verify —
        ADVANCES lengths at dispatch: the rows are prompt tokens,
        accepted by construction, so the next chunk for the same slot
        can dispatch before this one reconciles (chunks pipeline with
        no host data dependency). The sampled token on the returned
        step is meaningful only for a slot's FINAL chunk; the caller
        decides which via its own cursor snapshot (InflightStep.chunks,
        filled by the scheduler)."""
        import jax.numpy as jnp

        self.require("chunk")
        spec = self.cache.spec
        tokens = np.asarray(tokens, dtype=np.int32)
        chunk_lens = np.asarray(chunk_lens, dtype=np.int32)
        if tokens.ndim != 2 or tokens.shape[0] != spec.max_seqs:
            raise ValueError(
                f"tokens must be [max_seqs={spec.max_seqs}, w], "
                f"got {tokens.shape}"
            )
        w = tokens.shape[1]
        if w < 1:
            raise ValueError("chunk step needs at least one token column")
        if chunk_lens.shape != (spec.max_seqs,):
            raise ValueError("chunk_lens must be [max_seqs]")
        for slot in np.nonzero(chunk_lens)[0]:
            need = int(self.cache.lengths[slot]) + int(chunk_lens[slot])
            if chunk_lens[slot] > w or need > spec.max_len:
                raise ValueError(
                    f"slot {int(slot)}: chunk_lens {int(chunk_lens[slot])} "
                    f"overruns width {w} or max_len {spec.max_len}"
                )
        slot_ids = np.nonzero(chunk_lens)[0]
        if slot_ids.size == 0:
            raise ValueError("chunk step needs at least one active slot")
        self._claim_rows(chunk_lens)
        lengths_snap = np.array(self.cache.lengths)
        # snapshot() lengths/tables: the cursor bump below mutates
        # lengths right after dispatch, and jnp.asarray's host read is
        # deferred behind the dispatch queue — see decode_dispatch().
        # The batch compacts to the chunking slots (tokens/chunk_lens
        # rows); the jitted impl gathers its lengths/tables rows from
        # the full snapshots by slot_ids.
        rec = StepRecord("chunk", rows=int(chunk_lens.sum()))
        nxt, last = self._run_step(
            "chunk",
            lambda: self._chunk_fn((slot_ids.size, w)),
            params,
            (
                jnp.asarray(tokens[slot_ids]),
                jnp.asarray(slot_ids.astype(np.int32)),
                snapshot(self.cache.lengths),
                jnp.asarray(chunk_lens[slot_ids]),
                snapshot(self.cache.block_tables),
            ),
            self._adapter_slot_args(),
            program=("chunk", slot_ids.size, w),
            record=rec,
        )
        # prompt rows are committed by construction — advance the
        # cursors now so the NEXT chunk step dispatches against them
        active = chunk_lens > 0
        self.cache.lengths[active] += chunk_lens[active]
        self.cache.begin_inflight()
        return InflightStep(
            kind="chunk",
            record=rec,
            active=np.array(active, dtype=bool),
            lengths=lengths_snap,
            draft_lens=np.array(chunk_lens),
            device_next=nxt,
            device_logits=last,
        )

    def prefill_chunk_reconcile(
        self, step: InflightStep
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Block on a dispatched chunk step's device outputs and close
        its in-flight window. The device arrays are in compact-batch
        order; this scatters them back to slot-indexed (next_tokens
        [max_seqs], logits [max_seqs, V]) via the step's own active
        mask — rows for slots that were not chunking are zero. Only
        final-chunk rows carry meaning either way; the caller's cursor
        snapshot on the step record says which."""
        try:
            nxt_c, logits_c = self._readback(
                "chunk", step.device_next, step.device_logits,
                records=(step.record,),
            )
        finally:
            self.cache.end_inflight()
        spec = self.cache.spec
        slot_ids = np.nonzero(step.active)[0]  # == dispatch's compaction
        nxt = np.zeros(spec.max_seqs, dtype=nxt_c.dtype)
        logits = np.zeros(
            (spec.max_seqs, logits_c.shape[-1]), dtype=logits_c.dtype
        )
        nxt[slot_ids] = nxt_c
        logits[slot_ids] = logits_c
        return nxt, logits

    def prefill_chunk(
        self,
        params,
        tokens: np.ndarray,
        chunk_lens: np.ndarray,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Synchronous chunk step (dispatch + immediate reconcile)."""
        return self.prefill_chunk_reconcile(
            self.prefill_chunk_dispatch(params, tokens, chunk_lens)
        )
