"""Token-tree speculative verification (spec_branch > 1): greedy tree
spec is token-for-token identical to plain greedy decode across the
whole serving matrix ({slot, paged} x {fp32, int8} x {sync, async} x
prefix x chunked x {dense, pallas}), branch-1 chain trees bit-match the
linear verify path (logits AND draws), tree-verify row logits agree
numerically with per-chain linear verifies, the acceptance walk picks
the longest surviving root-to-leaf path (greedy and rejection-sampled),
truncate's src_rows compaction commits a scattered accepted branch into
contiguous cache rows with dead-branch pages returned under reserve
accounting, the n-gram/model proposers emit deduped branching drafts,
and the cost
family (verify_op_cost tree_nodes / optimize_spec_tree) prices the tree
shape. All CPU-fast (tier 1)."""

import numpy as np
import pytest

import jax

from flexflow_tpu import (
    DataType,
    FFConfig,
    FFModel,
    LossType,
    SGDOptimizer,
)
from flexflow_tpu.models import build_decoder_lm
from flexflow_tpu.serving import (
    DraftTree,
    NGramDraftProposer,
    Request,
    ServeConfig,
    accept_drafts,
    accept_tree,
    build_scheduler,
)
from tests.conftest import page_geometry

pytestmark = pytest.mark.serving

VOCAB = 50


def _lm(seed=0, hidden=32, layers=2, heads=4, ff=64, vocab=VOCAB):
    cfg = FFConfig(batch_size=4, seed=seed)
    model = FFModel(cfg)
    tok = model.create_tensor([4, 32], dtype=DataType.INT32, name="tokens")
    build_decoder_lm(
        model, tok, vocab_size=vocab, hidden=hidden, num_heads=heads,
        num_layers=layers, ff_dim=ff,
    )
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
        devices=jax.devices()[:1],
    )
    return model


@pytest.fixture(scope="module")
def lm():
    return _lm()


@pytest.fixture(scope="module")
def draft_lm():
    # smaller and differently seeded: a REAL draft (imperfect agreement)
    return _lm(seed=3, hidden=16, layers=1, ff=32)


PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 3, 1, 2], [7], [11, 12]]


# -- greedy equivalence across the serving matrix ------------------------------

# the cross-product legs ride the serving-spec-tree CI job (no "not
# slow" filter there); tier-1 keeps one leg per mechanism
_MATRIX = [
    pytest.param({"kv_page_size": 32}, id="one_page-dense-sync"),
    pytest.param({}, id="paged-dense-sync"),
    pytest.param({"kv_dtype": "int8"},
                 id="paged-int8", marks=pytest.mark.slow),
    pytest.param({"serve_async": True}, id="paged-async"),
    pytest.param({"kv_page_size": 32, "serve_async": True},
                 id="one_page-async", marks=pytest.mark.slow),
    pytest.param({"prefix_cache": True},
                 id="paged-prefix", marks=pytest.mark.slow),
    pytest.param({"token_budget": 10, "chunk_size": 4,
                  "decode_kernel": "dense"},
                 id="paged-chunked", marks=pytest.mark.slow),
    pytest.param({"decode_kernel": "pallas"}, id="paged-pallas"),
    pytest.param({"kv_page_size": 32, "decode_kernel": "pallas"},
                 id="one_page-pallas", marks=pytest.mark.slow),
]


@pytest.mark.parametrize("serve_kw", _MATRIX)
def test_greedy_tree_spec_equals_plain(lm, serve_kw):
    """The core contract on every serving path: greedy token-tree
    speculation emits EXACTLY the plain greedy stream — branching
    drafts change when tokens arrive, never which."""
    plain = lm.generate(
        PROMPTS,
        max_new_tokens=8,
        serve_config=ServeConfig(max_seqs=2, max_seq_len=32, **serve_kw),
    )
    tree = lm.generate(
        PROMPTS,
        max_new_tokens=8,
        serve_config=ServeConfig(
            max_seqs=2, max_seq_len=32, spec_draft="ngram", spec_k=3,
            spec_branch=2, **serve_kw,
        ),
    )
    assert tree == plain


@pytest.mark.parametrize("layout", ["one_page", "paged"])
@pytest.mark.parametrize(
    "branch", [2, pytest.param(3, marks=pytest.mark.slow)])
def test_model_draft_tree_equals_plain(lm, draft_lm, layout, branch):
    """Model-draft trees (greedy spine + draft-free root alternates)
    preserve the greedy stream at every branching factor."""
    plain = lm.generate(
        PROMPTS,
        max_new_tokens=8,
        serve_config=ServeConfig(max_seqs=2, max_seq_len=32,
                                 **page_geometry(layout, 32)),
    )
    tree = lm.generate(
        PROMPTS,
        max_new_tokens=8,
        serve_config=ServeConfig(
            max_seqs=2, max_seq_len=32, **page_geometry(layout, 32),
            spec_draft="model", spec_k=3, spec_branch=branch,
        ),
        draft_model=draft_lm,
    )
    assert tree == plain


# -- branch-1 / chain identity to the linear verify path ----------------------


@pytest.mark.parametrize("layout", ["one_page", "paged"])
@pytest.mark.parametrize("kernel", ["dense", "pallas"])
def test_chain_tree_verify_bit_matches_linear(lm, layout, kernel):
    """A depth-k, branch-1 tree (chain parents) produces BIT-IDENTICAL
    logits to the linear verify of the same drafts — the ancestor mask
    degenerates to the staircase, at both page geometries and kernels."""
    prompt = [3, 1, 4, 1, 5]
    _, eng, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=2, max_seq_len=32, **page_geometry(layout, 32),
                    decode_kernel=kernel),
    )
    slot = cache.alloc(len(prompt), len(prompt) + 8)
    nxt, _ = eng.prefill(lm.params, [prompt], [slot])
    drafts = [7, 2, 9]
    vt = np.zeros((cache.spec.max_seqs, 4), dtype=np.int32)
    vt[slot, 0] = int(nxt[0])
    vt[slot, 1:] = drafts
    dl = np.zeros(cache.spec.max_seqs, dtype=np.int32)
    dl[slot] = 4
    linear = eng.verify(lm.params, vt.copy(), dl.copy())
    chain = DraftTree.from_chains([drafts])
    assert chain.is_chain()
    parents = np.tile(
        np.arange(-1, 3, dtype=np.int32), (cache.spec.max_seqs, 1)
    )
    parents[slot] = chain.row_parents(4)
    tree = eng.verify_tree(lm.params, vt.copy(), dl.copy(), parents)
    assert np.array_equal(tree[slot, :4], linear[slot, :4])
    # and the acceptance walks make the same decision draw-for-draw
    t = DraftTree.from_chains([drafts])
    path, em_tree = accept_tree(tree[slot], t)
    acc, em_lin = accept_drafts(linear[slot, :4], drafts)
    assert len(path) == acc and em_tree == em_lin


@pytest.mark.parametrize("layout", ["one_page", "paged"])
def test_tree_verify_logits_match_per_chain_linear(lm, layout):
    """Each root-to-node path in a BRANCHING tree scores its token
    against the same distribution a linear verify of that chain alone
    produces (numerically — scattered rows change fp reduction order).
    This is the tree mask doing its job: a node attends to its
    ancestors and the committed prefix, never to a sibling branch."""
    prompt = [3, 1, 4, 1, 5]
    _, eng, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=2, max_seq_len=32, **page_geometry(layout, 32)),
    )
    slot = cache.alloc(len(prompt), len(prompt) + 8)
    nxt, _ = eng.prefill(lm.params, [prompt], [slot])
    root = int(nxt[0])
    # chains [a, b, c] and [a, d]: nodes a(-1) b(0) c(1) d(0)
    a, b, c, d = 7, 2, 9, 5
    tree = DraftTree.from_chains([[a, b, c], [a, d]])
    assert tree.tokens == [a, b, c, d]
    assert tree.parents == [-1, 0, 1, 0]
    w = 1 + len(tree.tokens)
    vt = np.zeros((cache.spec.max_seqs, w), dtype=np.int32)
    vt[slot, 0] = root
    vt[slot, 1:] = tree.tokens
    dl = np.zeros(cache.spec.max_seqs, dtype=np.int32)
    dl[slot] = w
    parents = np.tile(
        np.arange(-1, w - 1, dtype=np.int32), (cache.spec.max_seqs, 1)
    )
    parents[slot] = tree.row_parents(w)
    tlogits = eng.verify_tree(lm.params, vt, dl, parents)

    def linear_ref(chain):
        lt = np.zeros((cache.spec.max_seqs, 1 + len(chain)), dtype=np.int32)
        lt[slot, 0] = root
        lt[slot, 1:] = chain
        ld = np.zeros(cache.spec.max_seqs, dtype=np.int32)
        ld[slot] = 1 + len(chain)
        return eng.verify(lm.params, lt, ld)[slot]

    ref_abc = linear_ref([a, b, c])  # rows 0..3 <-> tree rows 0,1,2,3
    ref_ad = linear_ref([a, d])      # rows 0..2 <-> tree rows 0,1,4
    np.testing.assert_allclose(tlogits[slot, :4], ref_abc[:4], atol=1e-4)
    np.testing.assert_allclose(tlogits[slot, 4], ref_ad[2], atol=1e-4)


def test_tree_commit_compacts_accepted_branch_and_continues(lm):
    """Committing an accepted branch whose rows are SCATTERED (the
    surviving chain was not the first one proposed) compacts them into
    contiguous cache rows; continuing plain decode from the compacted
    cache reproduces the plain greedy stream, and the dead branch's
    pages return to the pool under the slot's reserve."""
    prompt = [3, 1, 4]
    ref = lm.generate(
        [prompt], max_new_tokens=6,
        serve_config=ServeConfig(max_seqs=1, max_seq_len=32,
                                 kv_page_size=4),
    )[0]
    _, eng, cache = build_scheduler(
        lm, ServeConfig(max_seqs=1, max_seq_len=32,
                        kv_page_size=4)
    )
    slot = cache.alloc(len(prompt), len(prompt) + 8)
    nxt, _ = eng.prefill(lm.params, [prompt], [slot])
    assert int(nxt[0]) == ref[0]
    # first chain is garbage, SECOND chain is the true continuation:
    # the accepted path lives in scattered rows and must be compacted
    bad = [(t + 1) % VOCAB for t in ref[1:3]]
    good = ref[1:3]
    tree = DraftTree.from_chains([bad, good])
    assert not tree.is_chain()
    w = 1 + len(tree.tokens)
    vt = np.zeros((1, w), dtype=np.int32)
    vt[0, 0] = ref[0]
    vt[0, 1:] = tree.tokens
    parents = np.array([tree.row_parents(w)], dtype=np.int32)
    old_len = int(cache.lengths[slot])
    free_before = cache.num_free_pages
    logits = eng.verify_tree(
        lm.params, vt, np.array([w], dtype=np.int32), parents
    )
    path, emitted = accept_tree(logits[0], tree)
    # the good branch survives in full: its 2 tokens + the bonus
    assert [tree.tokens[n] for n in path] == good
    assert emitted == ref[1:4]
    cache.truncate(
        slot, old_len + len(path) + 1,
        src_rows=[old_len + 1 + n for n in path],
    )
    assert int(cache.lengths[slot]) == old_len + len(path) + 1
    # dead rows' pages are back (the verify grew the slot by w rows)
    assert cache.num_free_pages >= free_before - 1
    assert cache._reserved <= cache.num_free_pages
    # plain decode from the compacted cache picks up the exact stream:
    # ref[0] (root) + 2 accepted + bonus + 2 decoded = all 6 of ref
    toks = [emitted[-1]]
    for _ in range(2):
        step_next, _ = eng.decode(
            lm.params, np.array([toks[-1]], dtype=np.int32),
            np.array([True]),
        )
        toks.append(int(step_next[0]))
    assert [ref[0]] + emitted[:-1] + toks == ref


# -- acceptance walk -----------------------------------------------------------


def test_accept_tree_greedy_longest_surviving_branch():
    """The greedy walk descends to the child matching the argmax at
    every level and emits the correction (or bonus) from the target —
    the longest surviving root-to-leaf prefix wins."""
    # tree: level 1 candidates [3, 4]; under 3, level 2 candidates [7]
    tree = DraftTree.from_chains([[3, 7], [4]])
    logits = np.zeros((1 + len(tree.tokens), 10), dtype=np.float32)
    logits[0, 3] = 5.0  # after root -> 3: node 0 survives, node 2 dies
    logits[1, 7] = 5.0  # after 3 -> 7: node 1 survives
    logits[2, 2] = 5.0  # after 7 -> 2: the bonus
    acc_path, em = accept_tree(logits, tree)
    assert acc_path == [0, 1] and em == [3, 7, 2]
    # argmax prefers the OTHER branch: path switches, first chain dies
    logits2 = np.zeros_like(logits)
    logits2[0, 4] = 5.0  # after root -> 4: node 2 survives
    logits2[3, 9] = 5.0  # after 4 -> 9: the bonus off node 2's row
    acc_path, em = accept_tree(logits2, tree)
    assert acc_path == [2] and em == [4, 9]
    # nothing survives: the correction is plain decode's token
    logits3 = np.zeros_like(logits)
    logits3[0, 8] = 5.0
    acc_path, em = accept_tree(logits3, tree)
    assert acc_path == [] and em == [8]
    # empty tree = plain decode
    acc_path, em = accept_tree(logits3, DraftTree([], []))
    assert acc_path == [] and em == [8]


@pytest.mark.parametrize("temperature", [0.0, 0.9])
def test_accept_tree_chain_is_accept_drafts(temperature):
    """On a chain tree, accept_tree is draw-for-draw accept_drafts —
    same greedy walk, same per-(seed, slot, position) RNG streams —
    for every (seed, slot, base_len)."""
    rng = np.random.default_rng(12)
    for trial in range(6):
        k = 1 + trial % 4
        logits = rng.normal(size=(k + 1, 16)).astype(np.float32) * 3.0
        drafts = [int(x) for x in rng.integers(0, 16, size=k)]
        tree = DraftTree.from_chains([drafts])
        for seed, slot, base in ((0, 0, 5), (7, 3, 11), (42, 1, 2)):
            path, em_t = accept_tree(
                logits, tree, temperature=temperature, seed=seed,
                slot=slot, base_len=base,
            )
            acc, em_l = accept_drafts(
                logits, drafts, temperature=temperature, seed=seed,
                slot=slot, base_len=base,
            )
            assert (len(path), em_t) == (acc, em_l)
            assert path == list(range(len(path)))


def test_accept_tree_sampling_preserves_certainty():
    """Near-delta target distributions: a matching candidate in ANY
    branch is accepted (later ordinals ride the residual rule), a tree
    of mismatches yields the certain correction — and every draw
    replays deterministically."""
    logits = np.full((3, 8), -30.0, dtype=np.float32)
    logits[0, 4] = 30.0  # target is certain of 4 after the root
    logits[1, 6] = 30.0
    # candidate order [3, 4]: ordinal 0 rejects, ordinal 1 accepts the
    # certain token via the zeroed-residual rule
    tree = DraftTree.from_chains([[3], [4]])
    path, em = accept_tree(logits, tree, temperature=1.0, seed=0, slot=0,
                           base_len=5)
    assert [tree.tokens[n] for n in path] == [4]
    assert em[0] == 4 and len(em) == 2  # accepted + bonus off node 1's row
    # all candidates wrong: the correction is the certain token
    tree_bad = DraftTree.from_chains([[3], [7]])
    path, em = accept_tree(logits, tree_bad, temperature=1.0, seed=0,
                           slot=0, base_len=5)
    assert path == [] and em == [4]
    again = accept_tree(logits, tree_bad, temperature=1.0, seed=0, slot=0,
                        base_len=5)
    assert again == (path, em)


def test_accept_tree_sampling_matches_target_distribution():
    """The multi-candidate rejection rule preserves the target
    distribution: with p uniform on {4, 6}, the first emitted token is
    4 about half the time — whether the candidates cover {4, 6} (accept
    path) or are pure junk (correction path samples the residual)."""
    logits = np.full((3, 8), -30.0, dtype=np.float32)
    logits[0, 4] = 1.0
    logits[0, 6] = 1.0  # p approx uniform on {4, 6}
    logits[1, 2] = 30.0
    logits[2, 2] = 30.0
    for tree in (
        DraftTree.from_chains([[4], [6]]),  # candidates cover the mass
        DraftTree.from_chains([[3], [7]]),  # junk: correction samples
    ):
        hits, n = 0, 400
        for seed in range(n):
            _, em = accept_tree(logits, tree, temperature=1.0, seed=seed,
                                slot=0, base_len=9)
            assert em[0] in (4, 6)
            hits += em[0] == 4
        # binomial(400, ~0.5): 5 sigma is 50
        assert abs(hits - n / 2) < 50, (tree.tokens, hits)


# -- DraftTree structure -------------------------------------------------------


def test_draft_tree_from_chains_dedups_shared_prefixes():
    tree = DraftTree.from_chains([[5, 6, 7], [5, 6, 8], [9]])
    assert tree.tokens == [5, 6, 7, 8, 9]
    assert tree.parents == [-1, 0, 1, 1, -1]
    assert tree.depth() == 3
    assert not tree.is_chain()
    assert tree.chains() == [[5, 6, 7], [5, 6, 8], [9]]
    assert tree.children(-1) == [0, 4]
    assert tree.children(1) == [2, 3]
    # identical chains collapse entirely
    assert DraftTree.from_chains([[1, 2], [1, 2]]).tokens == [1, 2]
    # deterministic: same chains, same tree
    again = DraftTree.from_chains([[5, 6, 7], [5, 6, 8], [9]])
    assert again.tokens == tree.tokens and again.parents == tree.parents


def test_draft_tree_row_parents_and_prune():
    tree = DraftTree.from_chains([[5, 6, 7], [5, 6, 8], [9]])
    # row 0 root, rows 1..5 nodes, padding rows chain off the end
    assert tree.row_parents() == [-1, 0, 1, 2, 2, 0]
    assert tree.row_parents(8) == [-1, 0, 1, 2, 2, 0, 5, 6]
    with pytest.raises(ValueError, match="width"):
        tree.row_parents(3)
    # node-budget prune keeps a topological prefix (parents survive)
    p = tree.prune(max_nodes=3)
    assert p.tokens == [5, 6, 7] and p.parents == [-1, 0, 1]
    # depth prune keeps whole levels
    p = tree.prune(max_depth=1)
    assert p.tokens == [5, 9] and p.parents == [-1, -1]
    p = tree.prune(max_nodes=0)
    assert p.tokens == [] and p.depth() == 0
    assert tree.prune().tokens == tree.tokens  # no caps: unchanged


def test_ngram_lookup_chains_branch_on_distinct_continuations():
    class R:
        def __init__(self, prompt, generated):
            self.prompt = prompt
            self.generated = generated

    p = NGramDraftProposer(n=2)
    # [5, 6] occurred twice with different continuations: 9... and 3...
    seq = [5, 6, 9, 2, 5, 6, 3, 1, 5, 6]
    trees = p.propose_trees({0: R(seq, [])}, k=2, branch=2)
    tree = trees[0]
    heads = [tree.tokens[c] for c in tree.children(-1)]
    assert sorted(heads) == [3, 9]  # both continuations drafted
    # branch 1 reduces to the linear proposal, chain-for-chain
    lin = p.propose({0: R(seq, [])}, k=2)
    t1 = p.propose_trees({0: R(seq, [])}, k=2, branch=1)[0]
    assert t1.is_chain() and t1.tokens == lin[0]
    # no earlier occurrence -> no tree
    assert p.propose_trees({0: R([1, 2, 3], [])}, k=2, branch=2) == {}


# -- scheduler: allocator invariants, stats, telemetry, EOS -------------------


def _check_allocator_invariants(cache):
    spec = cache.spec
    live = [
        int(p)
        for row in cache.block_tables
        for p in row
        if p != spec.num_pages
    ]
    assert len(live) == len(set(live))  # no double allocation
    assert set(live).isdisjoint(cache._free_pages)
    assert len(live) + cache.num_free_pages == spec.num_pages
    assert 0 <= cache._reserved <= cache.num_free_pages


def test_allocator_invariants_through_tree_schedule(lm):
    """Page allocator invariants hold at EVERY iteration of a tree-spec
    schedule — verify claims pages for all tree rows, the commit
    compacts the accepted branch and returns dead-branch pages — and
    the pool drains to empty."""
    sched, _, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=3, max_seq_len=32,
                    kv_page_size=4, spec_draft="ngram", spec_k=3,
                    spec_branch=3),
    )
    for i, n in enumerate([2, 9, 4, 1, 7, 3, 5, 8, 2, 6]):
        sched.submit(Request(
            rid=i,
            prompt=[(i * 7 + j) % (VOCAB - 1) + 1 for j in range(1 + i % 5)],
            max_new_tokens=n,
        ))
    while sched.queue or sched.running:
        sched.step()
        _check_allocator_invariants(cache)
    assert len(sched.finished) == 10
    assert all(len(r.generated) == r.max_new_tokens for r in sched.finished)
    assert cache.pages_in_use == 0
    assert cache.num_free_pages == cache.spec.num_pages
    assert cache._reserved == 0
    s = sched.stats
    assert s.tree_verify_steps > 0 and s.decode_steps == 0
    assert s.tree_verify_steps == s.verify_steps
    # nodes >= depth: proposed counts DEPTH so acceptance_rate keeps
    # its per-level meaning under trees
    assert s.tree_nodes_proposed >= s.draft_tokens_proposed > 0
    assert s.draft_tokens_accepted <= s.draft_tokens_proposed
    assert 0.0 <= s.acceptance_rate <= 1.0


def test_tree_telemetry_series(lm):
    """Tree-mode runs record the node counter and the accepted-path
    histogram in the shared registry."""
    sched, _, _ = build_scheduler(
        lm,
        ServeConfig(max_seqs=2, max_seq_len=32, spec_draft="ngram",
                    spec_k=3, spec_branch=2, telemetry=True),
    )
    sched.run([
        Request(rid=i, prompt=[1 + i, 2], max_new_tokens=10)
        for i in range(3)
    ])
    reg = sched.telemetry.registry
    nodes = reg.get("serve_spec_tree_nodes_total")
    assert nodes is not None and nodes.value > 0
    hist = reg.get("serve_spec_tree_accepted_path_len")
    assert hist is not None and hist.count > 0
    assert sched.stats.tree_nodes_proposed == nodes.value


@pytest.mark.parametrize("layout", ["one_page", "paged"])
def test_eos_mid_tree_verify_retires_at_eos(lm, layout):
    """EOS inside an accepted branch retires the request AT the EOS
    position — nothing past it is emitted, the slot recycles clean."""
    base_sc = ServeConfig(
        max_seqs=1, max_seq_len=32, **page_geometry(layout, 32)
    )
    base = lm.generate([[1, 2, 3]], max_new_tokens=10,
                       serve_config=base_sc)[0]
    eos = next(t for i, t in enumerate(base) if i >= 2)
    cut = base.index(eos)
    sched, _, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=1, max_seq_len=32, **page_geometry(layout, 32),
                    spec_draft="ngram", spec_k=3, spec_branch=2),
    )
    done = sched.run([
        Request(rid=0, prompt=[1, 2, 3], max_new_tokens=10, eos_token=eos),
        Request(rid=1, prompt=[5, 6], max_new_tokens=2),
    ])
    r0 = next(r for r in done if r.rid == 0)
    assert r0.generated == base[: cut + 1]
    assert r0.generated[-1] == eos and eos not in r0.generated[:-1]
    r1 = next(r for r in done if r.rid == 1)
    assert len(r1.generated) == 2
    assert cache.num_active == 0
    assert cache.pages_in_use == 0


def test_tree_accepts_more_per_verify_than_equal_budget_chain(lm):
    """The count the spec-tree CI gate held: at the SAME verify budget
    (1 + 12 scored rows a slot and step) a depth-4 x branch-3 n-gram
    tree accepts at least 1.2 times the draft tokens per verify step of
    a k = 12 chain (a rejected first candidate no longer kills the whole
    draft), and both emit plain greedy decode's streams."""
    def run(**spec):
        sched, _, _ = build_scheduler(
            lm, ServeConfig(max_seqs=4, max_seq_len=64, **spec)
        )
        done = sched.run([
            Request(rid=i, max_new_tokens=48,
                    prompt=[(i * 5 + j) % VOCAB for j in range(1 + i % 4)])
            for i in range(8)
        ])
        assert all(r.ok for r in done)
        return {r.rid: r.generated for r in done}, sched.stats

    plain, _ = run()
    chain, cs = run(spec_draft="ngram", spec_k=12)
    tree, ts = run(spec_draft="ngram", spec_k=4, spec_branch=3)
    assert chain == plain and tree == plain
    assert cs.verify_steps > 0 and ts.tree_verify_steps > 0
    per_verify = lambda st: st.draft_tokens_accepted / st.verify_steps
    assert per_verify(ts) >= 1.2 * per_verify(cs), (
        per_verify(ts), per_verify(cs)
    )


@pytest.mark.slow  # runs in the serving-spec-tree CI job
def test_tree_sampling_reproducible(lm):
    """Rejection-sampled tree verification replays exactly under a
    fixed seed, and a different seed actually changes the draw."""
    sc = dict(max_seqs=2, max_seq_len=32, temperature=0.8, seed=7,
              spec_draft="ngram", spec_k=3, spec_branch=2)
    a = lm.generate([[1, 2], [3, 4, 5]], 6, serve_config=ServeConfig(**sc))
    b = lm.generate([[1, 2], [3, 4, 5]], 6, serve_config=ServeConfig(**sc))
    assert a == b
    c = lm.generate(
        [[1, 2], [3, 4, 5]], 6,
        serve_config=ServeConfig(**{**sc, "seed": 13}),
    )
    assert c != a


# -- config wiring -------------------------------------------------------------


def test_spec_branch_flags_parse():
    cfg = FFConfig.parse_args(
        ["--spec-draft", "ngram", "--spec-k", "3", "--spec-branch", "4"]
    )
    sc = ServeConfig.from_config(cfg)
    assert sc.spec_branch == 4 and sc.spec_k == 3
    # default: linear chains
    assert ServeConfig.from_config(FFConfig.parse_args([])).spec_branch == 1
    with pytest.raises(ValueError, match="spec_branch"):
        ServeConfig(spec_draft="ngram", spec_branch=0)


# -- tree-shape cost model -----------------------------------------------------


def _graph(hidden=1024, heads=16, layers=4, ff=4096, vocab=512):
    m = FFModel(FFConfig(batch_size=4))
    tok = m.create_tensor([4, 128], dtype=DataType.INT32, name="tokens")
    build_decoder_lm(m, tok, vocab_size=vocab, hidden=hidden,
                     num_heads=heads, num_layers=layers, ff_dim=ff)
    return m.graph


def test_verify_op_cost_tree_nodes():
    """A tree node is priced exactly like a chain draft position — the
    verify scores 1 + nodes rows either way — so tree_nodes = n costs
    what k = n costs."""
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.cost_model import CostModel

    graph = _graph(hidden=64, heads=4, layers=1, ff=128, vocab=128)
    cm = CostModel(MachineSpec(num_nodes=1, chips_per_node=1, chip="v5e"))
    mha = next(
        n for n in graph.nodes.values()
        if n.op_type.name == "MULTIHEAD_ATTENTION"
    )
    by_k = cm.verify_op_cost(mha, batch=1, kv_len=512, k=6)
    by_tree = cm.verify_op_cost(mha, batch=1, kv_len=512, k=1, tree_nodes=6)
    assert by_tree.forward_time == by_k.forward_time
    wide = cm.verify_op_cost(mha, batch=1, kv_len=512, k=1, tree_nodes=12)
    assert wide.forward_time > by_tree.forward_time


def test_optimize_spec_tree_follows_acceptance():
    """The tree optimizer subsumes the linear one: zero acceptance ->
    no speculation; at any acceptance its pick is at least as good as
    optimize_spec_k's chain (the (d, 1) candidates ARE the chains);
    mid acceptance is where branching pays most."""
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.auto import (
        expected_accepted_tokens,
        expected_accepted_tree_tokens,
        optimize_spec_k,
        optimize_spec_tree,
    )

    graph = _graph()
    spec = MachineSpec(num_nodes=1, chips_per_node=1, chip="v5e")
    none = optimize_spec_tree(graph, spec, acceptance_rate=0.0)
    assert none.depth == 0 and none.branch == 1 and none.speedup == 1.0
    low = optimize_spec_tree(graph, spec, acceptance_rate=0.3)
    high = optimize_spec_tree(graph, spec, acceptance_rate=0.9)
    assert low.speedup > 1.0 and high.speedup > low.speedup
    # the tree never loses to the chain at the same acceptance
    for alpha in (0.3, 0.5, 0.9):
        chain = optimize_spec_k(graph, spec, acceptance_rate=alpha)
        tree = optimize_spec_tree(graph, spec, acceptance_rate=alpha)
        assert tree.speedup >= chain.speedup
    # mid-acceptance: branching beats the chain outright (a rejected
    # first token no longer kills the whole draft)
    mid_tree = optimize_spec_tree(graph, spec, acceptance_rate=0.5)
    mid_chain = optimize_spec_k(graph, spec, acceptance_rate=0.5)
    assert mid_tree.branch > 1
    assert mid_tree.speedup > mid_chain.speedup
    assert mid_tree.nodes == mid_tree.depth * mid_tree.branch
    assert "tokens/step" in mid_tree.describe()
    # a model draft charges depth draft steps (branching is draft-free)
    draft = _graph(hidden=128, heads=4, layers=1, ff=512)
    with_draft = optimize_spec_tree(
        graph, spec, acceptance_rate=0.9, draft_graph=draft
    )
    assert 1.0 < with_draft.speedup < high.speedup
    # E[path] sanity: branch 1 is the linear expectation exactly
    assert expected_accepted_tree_tokens(0.5, 4, 1) == pytest.approx(
        expected_accepted_tokens(0.5, 4)
    )
    assert expected_accepted_tree_tokens(0.5, 4, 4) > (
        expected_accepted_tokens(0.5, 4)
    )
    assert expected_accepted_tree_tokens(1.0, 6, 2) == 6.0
    assert expected_accepted_tree_tokens(0.0, 6, 4) == 0.0
