"""Speculative decoding (SpecInfer, Miao et al., ASPLOS 2024).

Decode is weight-bandwidth-bound (`CostModel.decode_op_cost`): every
generated token re-reads the whole weight set for ONE token of progress.
Speculative decoding buys more tokens per weight read — a cheap *draft*
proposes k continuation tokens, the target model scores all k+1
positions in one prefill-shaped **verify** call
(`GenerationEngine.verify`), and an acceptance rule keeps the longest
prefix the target agrees with plus one bonus token from the target's own
distribution. Greedy acceptance is exact-match, so greedy speculative
decode is token-for-token identical to plain greedy decode — the draft
only changes WHEN tokens arrive, never WHICH; under temperature the
rejection-sampling rule preserves the target distribution the same way.

Two draft sources implement the `DraftProposer` protocol:

* `NGramDraftProposer` — weight-free prompt-lookup (the "assisted
  generation" n-gram trick): find the most recent earlier occurrence of
  the sequence's trailing n-gram and propose what followed it. Free to
  run, surprisingly effective on repetitive continuations, and the CI
  preset (no second model to build).
* `ModelDraftProposer` — SpecInfer's small-model draft: a second
  compiled `build_decoder_lm` with its OWN PagedKVCache +
  GenerationEngine, kept slot-aligned with the target
  (`PagedKVCache.alloc(slot=...)`) and rolled back
  with the same `truncate` API the target uses. The draft always
  decodes greedily, so its proposal is a point mass and the same
  acceptance rule covers both proposers.

Rollback is the cache-side half of the protocol: verify writes K/V rows
for ALL k+1 positions; `cache.truncate(slot, new_len)` then commits the
accepted prefix: the visible length moves (stale rows are masked) and
the pages past the accepted length return to the free pool under the
admission-reserve accounting.

The scheduler side lives in serving/scheduler.py (`proposer=`/`spec_k=`
on either scheduler class); `optimize_spec_k` (search/auto.py) picks k
from a measured acceptance rate via `CostModel.verify_op_cost`.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


# -- acceptance --------------------------------------------------------------


def _rng(seed: int, slot: int, pos: int, sub: int) -> np.random.Generator:
    """Deterministic per-(seed, slot, position, draw) stream — the host
    mirror of the engine's fold_in(fold_in(key, slot), pos) discipline,
    so rejection sampling is reproducible and independent of batch
    composition."""
    return np.random.default_rng([seed & 0x7FFFFFFF, slot, pos, sub])


def _softmax(row: np.ndarray) -> np.ndarray:
    row = row.astype(np.float64)
    row = row - row.max()
    e = np.exp(row)
    return e / e.sum()


def accept_drafts(
    row_logits: np.ndarray,
    drafts: Sequence[int],
    temperature: float = 0.0,
    seed: int = 0,
    slot: int = 0,
    base_len: int = 0,
) -> Tuple[int, List[int]]:
    """Acceptance rule for one slot's verify output. row_logits
    [w >= len(drafts)+1, vocab] — row j is the target's distribution for
    the token following verify input j (input 0 is the last emitted
    token, inputs 1.. are the drafts). Returns (accepted, emitted):
    `accepted` drafts survive and `emitted` is drafts[:accepted] plus
    ONE token from the target itself (the correction at the first
    rejection, or the bonus after a full accept) — so every verify emits
    at least one token and plain decode is the drafts=[] special case.

    temperature 0: greedy exact-match (argmax), which makes speculative
    greedy decode token-identical to plain greedy decode. temperature >
    0: rejection sampling against the point-mass proposal both proposers
    emit (draft q is a delta): accept d with probability p(d); on
    rejection resample from p with d zeroed out (= norm(max(0, p - q)))
    — the Leviathan/Chen rule, which preserves the target distribution.
    base_len is the cache position of the last emitted token; it seeds
    the per-position RNG streams."""
    k = len(drafts)
    if temperature <= 0.0:
        preds = np.argmax(row_logits[: k + 1], axis=-1)
        accepted = 0
        while accepted < k and int(drafts[accepted]) == int(preds[accepted]):
            accepted += 1
        return accepted, [int(t) for t in drafts[:accepted]] + [
            int(preds[accepted])
        ]
    emitted: List[int] = []
    for i in range(k):
        p = _softmax(row_logits[i] / temperature)
        d = int(drafts[i])
        # position the decided token will occupy: base_len + 1 + i
        u = _rng(seed, slot, base_len + 1 + i, 0).random()
        if u <= p[d]:
            emitted.append(d)
            continue
        residual = p.copy()
        residual[d] = 0.0
        total = residual.sum()
        if total <= 0.0:  # p was a delta at d — accept after all
            emitted.append(d)
            continue
        t = int(
            _rng(seed, slot, base_len + 1 + i, 1).choice(
                residual.size, p=residual / total
            )
        )
        emitted.append(t)
        return i, emitted
    p = _softmax(row_logits[k] / temperature)
    t = int(_rng(seed, slot, base_len + 1 + k, 0).choice(p.size, p=p))
    emitted.append(t)
    return k, emitted


# -- token trees (SpecInfer tree-verify) --------------------------------------


@dataclasses.dataclass
class DraftTree:
    """One slot's branching draft: a token tree rooted at the LAST
    EMITTED token (the root is implicit — it is verify row 0 and never
    appears in the node lists). tokens[i] is node i's token; parents[i]
    is its parent NODE index, -1 for children of the root. Nodes are
    topologically ordered (every parent index < its child's index) —
    `from_chains` builds them that way, and the verify mask
    (ops/attention.tree_ancestor_matrix), the acceptance walk, and the
    truncate compaction all rely on it. Node i occupies verify row
    1 + i and cache position lengths[slot] + 1 + i during the verify.

    A single chain (parents == [-1, 0, 1, ...]) is the degenerate tree
    the linear spec path already handles — schedulers route it through
    the existing staircase program so branch-1 trees stay bit-identical
    to linear speculative decoding."""

    tokens: List[int]
    parents: List[int]

    def __post_init__(self):
        if len(self.tokens) != len(self.parents):
            raise ValueError("tokens and parents must have equal length")
        for i, p in enumerate(self.parents):
            if not -1 <= p < i:
                raise ValueError(
                    f"node {i}: parent {p} breaks topological order"
                )

    @classmethod
    def from_chains(cls, chains: Sequence[Sequence[int]]) -> "DraftTree":
        """Trie-merge candidate chains, deduping shared prefixes: two
        chains agreeing on their first j tokens share j nodes and
        branch at the divergence — the dedup that makes a tree cheaper
        to verify than its chains separately. Chain order is
        deterministic (first chain's nodes come first), so the same
        chains always produce the same tree."""
        tokens: List[int] = []
        parents: List[int] = []
        kids: Dict[int, Dict[int, int]] = {}
        for chain in chains:
            cur = -1
            for t in chain:
                t = int(t)
                node = kids.setdefault(cur, {}).get(t)
                if node is None:
                    node = len(tokens)
                    tokens.append(t)
                    parents.append(cur)
                    kids[cur][t] = node
                cur = node
        return cls(tokens, parents)

    @property
    def nodes(self) -> int:
        return len(self.tokens)

    def depth(self) -> int:
        """Longest root-to-leaf path in nodes (the linear-k
        equivalent: a chain of k drafts has depth k)."""
        best = 0
        d = [0] * len(self.tokens)
        for i, p in enumerate(self.parents):
            d[i] = 1 if p < 0 else d[p] + 1
            best = max(best, d[i])
        return best

    def children(self, node: int) -> List[int]:
        """Child node indices of `node` (-1 = the root), in proposal
        order — the acceptance walk's candidate order, which is what
        keeps branch-1 trees draw-for-draw identical to the linear
        rejection-sampling path."""
        return [i for i, p in enumerate(self.parents) if p == node]

    def is_chain(self) -> bool:
        return all(p == i - 1 for i, p in enumerate(self.parents))

    def chains(self) -> List[List[int]]:
        """Root-to-leaf token paths (testing/debugging view)."""
        kids_of: Dict[int, List[int]] = {}
        for i, p in enumerate(self.parents):
            kids_of.setdefault(p, []).append(i)
        out: List[List[int]] = []

        def walk(node: int, path: List[int]) -> None:
            ks = kids_of.get(node, [])
            if not ks:
                out.append(path)
                return
            for c in ks:
                walk(c, path + [int(self.tokens[c])])

        walk(-1, [])
        return [p for p in out if p]

    def row_parents(self, w: Optional[int] = None) -> List[int]:
        """Per-VERIFY-ROW parent table of width `w` (>= 1 + nodes):
        row 0 is the root (-1), row 1 + i is node i, padding rows chain
        (parent j - 1) so their mask degenerates to the staircase. This
        is the [w] slice the engine stacks into the [max_seqs, w]
        tree_parents operand."""
        n = len(self.tokens)
        w = 1 + n if w is None else int(w)
        if w < 1 + n:
            raise ValueError(f"width {w} < 1 + {n} nodes")
        rows = [-1] + [0 if p < 0 else 1 + p for p in self.parents]
        rows += list(range(n, w - 1))  # chain padding: row j's parent j-1
        return rows

    def prune(
        self,
        max_nodes: Optional[int] = None,
        max_depth: Optional[int] = None,
    ) -> "DraftTree":
        """Drop nodes past a depth and/or node budget (token-budget and
        horizon caps at dispatch). Topological order means keeping a
        prefix of the node list keeps every survivor's parent, and the
        depth filter keeps ancestors by construction (depth(parent) <
        depth(child))."""
        d = [0] * len(self.tokens)
        for i, p in enumerate(self.parents):
            d[i] = 1 if p < 0 else d[p] + 1
        idx_map: Dict[int, int] = {}
        tokens: List[int] = []
        parents: List[int] = []
        for i, p in enumerate(self.parents):
            if max_nodes is not None and len(tokens) >= max_nodes:
                break
            if max_depth is not None and d[i] > max_depth:
                continue
            if p >= 0 and p not in idx_map:
                continue  # orphaned by the node cap
            idx_map[i] = len(tokens)
            tokens.append(int(self.tokens[i]))
            parents.append(-1 if p < 0 else idx_map[p])
        return DraftTree(tokens, parents)


def accept_tree(
    row_logits: np.ndarray,
    tree: DraftTree,
    temperature: float = 0.0,
    seed: int = 0,
    slot: int = 0,
    base_len: int = 0,
) -> Tuple[List[int], List[int]]:
    """Tree acceptance for one slot's verify output — the multi-branch
    generalization of accept_drafts. row_logits [w >= 1 + nodes, vocab]:
    row 0 is the target's distribution after the last emitted token,
    row 1 + i its distribution after node i's root-to-node path.
    Returns (path, emitted): `path` is the surviving root-to-leaf node
    index prefix (the rows truncate compacts into the cache) and
    `emitted` is its tokens plus ONE token from the target (the
    correction where the tree ran out of matching children, or the
    bonus at a fully-accepted leaf) — every verify emits at least one
    token, exactly like the linear rule.

    temperature 0: walk greedily — descend to the child whose token
    equals the argmax; the emitted stream is argmax-after-committed-
    prefix at every step, so greedy tree spec is token-identical to
    plain greedy decode. temperature > 0: multi-candidate rejection
    sampling (SpecInfer / Leviathan-Chen): at each node, candidates are
    tried in proposal order against the running residual r (initially
    p) — candidate c accepts with probability r[c]/sum(r), a rejection
    zeroes r[c] — and if all candidates reject, the correction samples
    from the final residual. With one candidate this is draw-for-draw
    the accept_drafts rule (same per-(seed, slot, position) RNG
    streams: sub 0 for the first candidate, 1 for the correction,
    2+ordinal for later candidates, and the leaf bonus reuses sub 0 at
    the one-past-leaf position, exactly like the linear bonus), so
    branch-1 trees reproduce linear spec decoding bit-for-bit."""
    if temperature <= 0.0:
        path: List[int] = []
        emitted: List[int] = []
        cur = -1
        while True:
            row = 0 if cur < 0 else 1 + cur
            pred = int(np.argmax(row_logits[row]))
            emitted.append(pred)
            nxt = None
            for c in tree.children(cur):
                if int(tree.tokens[c]) == pred:
                    nxt = c
                    break
            if nxt is None:
                return path, emitted
            path.append(nxt)
            cur = nxt
    path = []
    emitted = []
    cur = -1
    depth = 0
    while True:
        row = 0 if cur < 0 else 1 + cur
        # position the decided token will occupy: base_len + 1 + depth
        pos = base_len + 1 + depth
        p = _softmax(row_logits[row] / temperature)
        kids = tree.children(cur)
        if not kids:  # fully-accepted leaf: bonus from the target
            t = int(_rng(seed, slot, pos, 0).choice(p.size, p=p))
            emitted.append(t)
            return path, emitted
        residual = p.copy()
        accepted_node = None
        for ordinal, c in enumerate(kids):
            d = int(tree.tokens[c])
            total = residual.sum()
            if total <= 0.0:  # p was a delta on rejected candidates
                accepted_node = c
                break
            u = _rng(
                seed, slot, pos, 0 if ordinal == 0 else 2 + ordinal
            ).random()
            # ordinal 0 compares against p[d] itself (total == 1), the
            # EXACT comparison accept_drafts makes — not p[d]/sum(p),
            # whose float64 rounding could flip a knife-edge draw
            thresh = residual[d] if ordinal == 0 else residual[d] / total
            if u <= thresh:
                accepted_node = c
                break
            residual[d] = 0.0
        if accepted_node is None:
            total = residual.sum()
            if total <= 0.0:  # delta at the last rejected candidate
                accepted_node = kids[-1]
            else:
                t = int(
                    _rng(seed, slot, pos, 1).choice(
                        residual.size, p=residual / total
                    )
                )
                emitted.append(t)
                return path, emitted
        path.append(accepted_node)
        emitted.append(int(tree.tokens[accepted_node]))
        cur = accepted_node
        depth += 1


# -- draft proposers ----------------------------------------------------------


class DraftProposer:
    """Protocol for draft sources. `propose` maps running slots to draft
    token lists (up to k each; shorter or empty is fine — the verify
    degrades to plain decode). The lifecycle hooks exist for proposers
    with their own cache state (ModelDraftProposer); the base
    implementations are no-ops so stateless proposers only implement
    propose(). `retire` fires for EVERY slot release — terminal
    statuses and preemptions alike (a preempted request re-enters via
    `admit` with its recompute history).

    `stateless` marks proposers whose drafts are a pure function of the
    token sequence they are shown — no per-slot cache to keep
    consistent. The async engine only pre-drafts (proposing for verify
    N+1 against N's PREDICTED outcome, while N is still in flight) on
    stateless proposers, through `propose_sequences`: a misprediction
    there costs nothing to roll back, where a stateful proposer would
    have fed phantom tokens into its draft cache."""

    stateless = False

    def telemetry_counters(self) -> Dict[str, int]:
        """Monotone proposer-side counters for the metrics registry
        (`serve_draft_*` series) — the per-iteration sampler mirrors
        them via set_monotonic, so a proposer only needs to keep plain
        int ledgers. Base: nothing to report."""
        return {}

    def admit(self, requests: Sequence) -> None:  # pragma: no cover
        pass

    def retire(self, request) -> None:  # pragma: no cover
        pass

    def rollback(self, slot: int, new_len: int) -> None:  # pragma: no cover
        pass

    def propose(self, running: Dict[int, object], k: int) -> Dict[int, List[int]]:
        raise NotImplementedError

    def propose_trees(
        self, running: Dict[int, object], k: int, branch: int
    ) -> Dict[int, DraftTree]:
        """Branching drafts for tree verification: up to `branch`
        candidate chains of up to k tokens per slot, deduped on shared
        prefixes into one DraftTree. The base implementation wraps
        propose() — a single chain IS the branch == 1 tree — so every
        proposer supports tree mode; proposers with a real notion of
        alternates override it to emit wider trees."""
        out: Dict[int, DraftTree] = {}
        for slot, drafts in self.propose(running, k).items():
            tree = DraftTree.from_chains([drafts])
            if tree.nodes:
                out[slot] = tree
        return out

    def propose_sequences(
        self, seqs: Dict[int, List[int]], k: int
    ) -> Dict[int, List[int]]:
        """Draft up to k continuation tokens for explicit token
        sequences (slot -> prompt+generated+predicted history) instead
        of live Request state. Stateless proposers implement this; the
        default refuses so stateful proposers are never pre-drafted."""
        raise NotImplementedError(
            "propose_sequences is only available on stateless proposers"
        )


class NGramDraftProposer(DraftProposer):
    """Weight-free prompt-lookup draft: propose the continuation that
    followed the most recent earlier occurrence of the sequence's
    trailing `n`-gram (prompt + generated so far). Repetitive text —
    code, structured output, or a greedy model that has entered a cycle
    — yields near-1 acceptance for zero draft cost; novel text yields no
    match and the iteration degrades to plain decode. `max_history`
    bounds the backward scan so long sequences stay O(max_history)."""

    stateless = True

    def __init__(self, n: int = 2, max_history: int = 4096):
        if n < 1:
            raise ValueError("n-gram size must be >= 1")
        self.n = int(n)
        self.max_history = int(max_history)
        # telemetry ledgers: lookups attempted vs lookups that found a
        # continuation — the hit rate is the "is prompt-lookup even
        # firing on this workload" signal, upstream of acceptance
        self.lookups = 0
        self.lookup_hits = 0

    def telemetry_counters(self) -> Dict[str, int]:
        return {
            "serve_draft_lookups_total": self.lookups,
            "serve_draft_lookup_hits_total": self.lookup_hits,
        }

    def _lookup(self, seq: List[int], k: int) -> List[int]:
        if len(seq) > self.max_history:
            seq = seq[-self.max_history :]
        n = self.n
        if len(seq) <= n:
            return []
        tail = seq[-n:]
        # most recent earlier occurrence wins (locality: loops and
        # copied spans repeat their NEAREST context)
        for i in range(len(seq) - n - 1, -1, -1):
            if seq[i : i + n] == tail:
                return [int(t) for t in seq[i + n : i + n + k]]
        return []

    def _lookup_chains(
        self, seq: List[int], k: int, branch: int
    ) -> List[List[int]]:
        """Up to `branch` DISTINCT continuations from distinct earlier
        occurrences of the trailing n-gram, most recent first — the
        first chain is exactly what _lookup returns, so branch == 1
        tree proposals match linear proposals chain-for-chain. Distinct
        matches that disagree early give the tree its branches; matches
        that agree merge in DraftTree.from_chains."""
        if len(seq) > self.max_history:
            seq = seq[-self.max_history :]
        n = self.n
        if len(seq) <= n:
            return []
        tail = seq[-n:]
        chains: List[List[int]] = []
        for i in range(len(seq) - n - 1, -1, -1):
            if seq[i : i + n] == tail:
                cont = [int(t) for t in seq[i + n : i + n + k]]
                if cont and cont not in chains:
                    chains.append(cont)
                if len(chains) >= branch:
                    break
        return chains

    def propose_trees(
        self, running, k: int, branch: int
    ) -> Dict[int, DraftTree]:
        return self.propose_tree_sequences(
            {
                slot: list(req.prompt) + list(req.generated)
                for slot, req in running.items()
            },
            k,
            branch,
        )

    def propose_tree_sequences(
        self, seqs: Dict[int, List[int]], k: int, branch: int
    ) -> Dict[int, DraftTree]:
        """Tree analog of propose_sequences (stateless, so usable for
        pre-proposal the same way)."""
        out: Dict[int, DraftTree] = {}
        for slot, seq in seqs.items():
            self.lookups += 1
            chains = self._lookup_chains(list(seq), k, branch)
            if chains:
                self.lookup_hits += 1
                out[slot] = DraftTree.from_chains(chains)
        return out

    def propose(self, running, k: int) -> Dict[int, List[int]]:
        return self.propose_sequences(
            {
                slot: list(req.prompt) + list(req.generated)
                for slot, req in running.items()
            },
            k,
        )

    def propose_sequences(
        self, seqs: Dict[int, List[int]], k: int
    ) -> Dict[int, List[int]]:
        out: Dict[int, List[int]] = {}
        for slot, seq in seqs.items():
            self.lookups += 1
            cont = self._lookup(list(seq), k)
            if cont:
                self.lookup_hits += 1
                out[slot] = cont
        return out


class ModelDraftProposer(DraftProposer):
    """Small-model draft (SpecInfer's SSM): a second compiled decoder LM
    with its own PagedKVCache (default geometry: every slot can reach
    max_len) and GenerationEngine, taking the target's slot ids through
    `PagedKVCache.alloc(slot=...)`. Drafting is k greedy decode
    steps of the draft engine; between verify iterations the draft cache
    is rolled back to the target's accepted length with the same
    `truncate` call, and the next propose() replays whatever accepted
    tokens the draft cache is missing (catch-up feeds) before drafting
    fresh ones — so draft state always extends a prefix of the target's
    committed history, never a rejected branch.

    The draft model must share the target's vocabulary. The draft engine
    always runs greedily (temperature 0), making its proposal a point
    mass — the acceptance rule in accept_drafts covers point-mass
    proposals exactly."""

    def __init__(
        self,
        draft_model,
        max_seqs: int,
        max_len: int,
        buckets=None,
        decode_kernel: str = "auto",
    ):
        from flexflow_tpu.serving.engine import GenerationEngine
        from flexflow_tpu.serving.kv_cache import PagedKVCache

        self.model = draft_model
        self.cache = PagedKVCache.from_model(
            draft_model, max_seqs=max_seqs, max_len=max_len, buckets=buckets
        )
        # the draft's k decode steps live in the same memory-bound regime
        # as the target's — the Pallas decode-kernel toggle rides along
        self.engine = GenerationEngine(
            draft_model, self.cache, temperature=0.0,
            decode_kernel=decode_kernel,
        )
        self.engine.require("draft")
        self.params = draft_model.params
        # telemetry ledgers: draft-engine decode steps, split into
        # catch-up feeds (replaying tokens the target committed) vs
        # fresh draft tokens — the catch-up share is the price of a
        # rollback, invisible in acceptance_rate alone
        self.draft_steps = 0
        self.catchup_feeds = 0
        self.draft_tokens = 0

    def telemetry_counters(self) -> Dict[str, int]:
        return {
            "serve_draft_steps_total": self.draft_steps,
            "serve_draft_catchup_feeds_total": self.catchup_feeds,
            "serve_draft_tokens_total": self.draft_tokens,
        }

    # -- lifecycle -----------------------------------------------------------

    def admit(self, requests) -> None:
        """Mirror the target's admission: take the SAME slot ids (with
        the whole of max_len reserved: the draft pool holds that for
        every slot, so the draft never runs dry before the target) and
        prefill the draft cache with each request's committed history —
        the prompt, plus any tokens already generated when a preempted
        request re-admits for recompute (serving/scheduler.py); feeding
        them here in one prefill is the draft-side recompute that would
        otherwise replay token-by-token as catch-up feeds. The
        prefill's own next-token output is unused — drafts start from
        the target's last emitted token at the next propose()."""
        histories = [list(r.prompt) + list(r.generated) for r in requests]
        for req, hist in zip(requests, histories):
            taken = self.cache.alloc(
                len(hist), self.cache.spec.max_len, slot=req.slot
            )
            if taken is None:
                raise RuntimeError(
                    f"draft cache refused slot {req.slot}: its pool is "
                    "smaller than max_seqs * max_len"
                )
        self.engine.prefill(
            self.params, histories, [r.slot for r in requests]
        )

    def retire(self, request) -> None:
        self.cache.free(request.slot)

    def rollback(self, slot: int, new_len: int) -> None:
        """Keep the prefix of the draft cache that matches the target's
        committed history. The draft may hold FEWER positions than the
        target committed (full-accept: the last draft token was never
        written to the draft cache) — the gap is replayed as catch-up
        feeds in the next propose()."""
        self.cache.truncate(
            slot, min(int(new_len), int(self.cache.lengths[slot]))
        )

    # -- drafting ------------------------------------------------------------

    def propose(self, running, k: int) -> Dict[int, List[int]]:
        if not running or k < 1:
            return {}
        spec = self.cache.spec
        # per-slot feed script: first the committed tokens the draft
        # cache hasn't seen yet (always at least the last emitted token),
        # then the draft's own greedy continuations
        pending: Dict[int, List[int]] = {}
        drafts: Dict[int, List[int]] = {}
        for slot, req in running.items():
            hist = list(req.prompt) + list(req.generated)
            done = int(self.cache.lengths[slot])
            pending[slot] = [int(t) for t in hist[done:]]
            drafts[slot] = []
        while True:
            feeds: Dict[int, int] = {}
            for slot in running:
                if int(self.cache.lengths[slot]) >= spec.max_len:
                    continue  # draft cache horizon reached
                if pending[slot]:
                    feeds[slot] = pending[slot][0]
                elif drafts[slot] and len(drafts[slot]) < k:
                    feeds[slot] = drafts[slot][-1]
            if not feeds:
                break
            tokens = np.zeros(spec.max_seqs, dtype=np.int32)
            active = np.zeros(spec.max_seqs, dtype=bool)
            for slot, tok in feeds.items():
                tokens[slot] = tok
                active[slot] = True
            nxt, _ = self.engine.decode(self.params, tokens, active)
            self.draft_steps += 1
            for slot in feeds:
                if pending[slot]:
                    pending[slot].pop(0)
                    self.catchup_feeds += 1
                    if pending[slot]:
                        continue  # catch-up feed: prediction is known
                self.draft_tokens += 1
                drafts[slot].append(int(nxt[slot]))
        return {s: d for s, d in drafts.items() if d}

    def propose_trees(
        self, running, k: int, branch: int
    ) -> Dict[int, DraftTree]:
        """Tree drafts from the draft model: the greedy spine propose()
        would emit, plus up to branch - 1 single-node ALTERNATES at the
        root — the runners-up of the draft's first fresh distribution.
        Root alternates are where tree verification pays most (a
        mispredicted first token kills a whole linear chain), and they
        cost no extra draft decode steps: the alternate tokens fall out
        of the same logits row the spine's first token came from, and
        they never enter the draft cache (only the spine is fed back),
        so rollback stays the linear protocol."""
        if not running or k < 1:
            return {}
        spec = self.cache.spec
        pending: Dict[int, List[int]] = {}
        drafts: Dict[int, List[int]] = {}
        root_logits: Dict[int, np.ndarray] = {}
        for slot, req in running.items():
            hist = list(req.prompt) + list(req.generated)
            done = int(self.cache.lengths[slot])
            pending[slot] = [int(t) for t in hist[done:]]
            drafts[slot] = []
        while True:
            feeds: Dict[int, int] = {}
            for slot in running:
                if int(self.cache.lengths[slot]) >= spec.max_len:
                    continue
                if pending[slot]:
                    feeds[slot] = pending[slot][0]
                elif drafts[slot] and len(drafts[slot]) < k:
                    feeds[slot] = drafts[slot][-1]
            if not feeds:
                break
            tokens = np.zeros(spec.max_seqs, dtype=np.int32)
            active = np.zeros(spec.max_seqs, dtype=bool)
            for slot, tok in feeds.items():
                tokens[slot] = tok
                active[slot] = True
            nxt, logits = self.engine.decode(self.params, tokens, active)
            self.draft_steps += 1
            for slot in feeds:
                if pending[slot]:
                    pending[slot].pop(0)
                    self.catchup_feeds += 1
                    if pending[slot]:
                        continue
                self.draft_tokens += 1
                if not drafts[slot]:
                    root_logits[slot] = np.asarray(logits[slot])
                drafts[slot].append(int(nxt[slot]))
        out: Dict[int, DraftTree] = {}
        for slot, spine in drafts.items():
            if not spine:
                continue
            chains: List[List[int]] = [list(spine)]
            row = root_logits.get(slot)
            if row is not None and branch > 1:
                for t in np.argsort(row)[::-1]:
                    if len(chains) >= branch:
                        break
                    if int(t) != spine[0]:
                        chains.append([int(t)])
            out[slot] = DraftTree.from_chains(chains)
        return out
