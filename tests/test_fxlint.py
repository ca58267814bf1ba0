"""fxlint (flexflow_tpu.analysis): fixture-based positive/negative
coverage for every AST rule family, the repo-is-clean contract (HEAD
lints clean against the checked-in baseline, and the dispatch-race
family is clean with NO baseline at all), the seeded-bug self-test
(re-introducing the PR 3 race — dropping the snapshot on a dispatch
path — must produce a finding, the property the CI job re-proves on
every run), the baseline workflow, and the snapshot() helper's copy
semantics. All pure-host/CPU-fast (tier 1)."""

import os
import shutil

import numpy as np
import pytest

from flexflow_tpu.analysis.cli import check_strategy_files, main, run_rules

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO_ROOT, "flexflow_tpu")
FIXTURES = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fixtures", "fxlint"
)
BASELINE = os.path.join(REPO_ROOT, "fxlint_baseline.txt")

pytestmark = pytest.mark.analysis


def _by_file(diags):
    out = {}
    for d in diags:
        out.setdefault(os.path.basename(d.path), []).append(d.rule_id)
    return out


# -- dispatch-race (FX1xx) ----------------------------------------------------


def test_dispatch_race_fixtures():
    diags = _by_file(
        run_rules([os.path.join(FIXTURES, "dispatch")], ["dispatch-race"])
    )
    # seeded violations flagged: two raw asarray reads + one raw jit arg
    assert diags.get("bad.py", []).count("FX101") == 2
    assert diags.get("bad.py", []).count("FX102") == 1
    # blessed idioms (.copy(), np.array, snapshot(), fresh locals) silent
    assert "good.py" not in diags


def test_dispatch_race_clean_on_head():
    """The satellite contract: the baseline ships EMPTY for the
    dispatch-race family — HEAD has zero findings even without a
    baseline."""
    diags = run_rules([PACKAGE], ["dispatch-race"])
    assert diags == [], [d.format() for d in diags]


def test_seeded_pr3_race_is_caught(tmp_path):
    """Re-introduce the PR 3 bug (drop the snapshot on a decode
    dispatch path) in a scratch copy: fxlint must flag it. This is the
    same transformation the CI self-test step applies to a scratch
    checkout."""
    src_path = os.path.join(PACKAGE, "serving", "engine.py")
    with open(src_path) as f:
        src = f.read()
    seeded = src.replace(
        "snapshot(self.cache.lengths)",
        "jnp.asarray(self.cache.lengths)",
        1,
    )
    assert seeded != src, (
        "engine.py no longer snapshots cache.lengths via snapshot() — "
        "update this test AND the CI fxlint self-test recipe together"
    )
    scratch = tmp_path / "engine.py"
    scratch.write_text(seeded)
    diags = run_rules([str(tmp_path)], ["dispatch-race"])
    assert any(
        d.rule_id == "FX101" and "lengths" in d.message for d in diags
    ), [d.format() for d in diags]
    # the unmodified file stays clean
    clean = tmp_path / "clean"
    clean.mkdir()
    shutil.copy(src_path, clean / "engine.py")
    assert run_rules([str(clean)], ["dispatch-race"]) == []


def test_seeded_block_table_race_is_caught(tmp_path):
    src_path = os.path.join(PACKAGE, "serving", "engine.py")
    with open(src_path) as f:
        src = f.read()
    n_sites = src.count("snapshot(self.cache.block_tables)")
    assert n_sites >= 2
    seeded = src.replace(
        "snapshot(self.cache.block_tables)",
        "jnp.asarray(self.cache.block_tables)",
    )
    assert seeded != src
    (tmp_path / "engine.py").write_text(seeded)
    # the block-table MUTATIONS live in the allocator, not the engine —
    # scan both, like a full-checkout lint does
    shutil.copy(
        os.path.join(PACKAGE, "serving", "kv_cache.py"),
        tmp_path / "kv_cache.py",
    )
    diags = run_rules([str(tmp_path)], ["dispatch-race"])
    assert sum(
        d.rule_id == "FX101" and "block_tables" in d.message for d in diags
    ) == n_sites


def test_reconcile_snapshot_fixtures():
    """FX103: reconcile-phase code (functions taking an InflightStep)
    reading live cache state instead of the step's snapshot — the bug
    class the async double-buffered engine creates."""
    diags = _by_file(
        run_rules([os.path.join(FIXTURES, "dispatch")], ["dispatch-race"])
    )
    assert diags.get("reconcile_bad.py", []).count("FX103") == 2
    # snapshot reads (step.lengths), non-cache state (self.running), and
    # dispatch-side functions stay silent
    assert "reconcile_good.py" not in diags


def test_chunk_progress_fixtures():
    """FX105: reconcile-phase code reading live chunked-prefill cursor
    state (prefill_seq/prefill_pos/prefill_dispatched) instead of the
    step's own chunk record — the partial-prefill variant of FX103."""
    diags = _by_file(
        run_rules([os.path.join(FIXTURES, "dispatch")], ["dispatch-race"])
    )
    assert diags.get("chunk_bad.py", []).count("FX105") == 3
    # step.chunks reads, the Store write-back, planning helpers and
    # dispatch-side builders stay silent
    assert "chunk_good.py" not in diags


def test_seeded_chunk_progress_bypass_is_caught(tmp_path):
    """Re-introduce the bug FX105 exists for: make the chunk commit
    decide 'final chunk?' from the LIVE prefill cursor — which the
    dispatcher already advanced for the next in-flight chunk — instead
    of the step's own (start, size, final) record."""
    src_path = os.path.join(PACKAGE, "serving", "scheduler.py")
    with open(src_path) as f:
        src = f.read()
    seeded = src.replace(
        "            if final:\n"
        "                self._chunk_unlocked.add(slot)\n",
        "            if req.prefill_pos >= len(req.prefill_seq):\n"
        "                self._chunk_unlocked.add(slot)\n",
        1,
    )
    assert seeded != src, (
        "scheduler.py's chunk commit no longer gates the final-chunk "
        "emit on the step record — update this test alongside the "
        "refactor"
    )
    (tmp_path / "scheduler.py").write_text(seeded)
    diags = run_rules([str(tmp_path)], ["dispatch-race"])
    assert any(
        d.rule_id == "FX105" and "prefill_" in d.message for d in diags
    ), [d.format() for d in diags]
    # the unmodified scheduler stays clean
    clean = tmp_path / "clean"
    clean.mkdir()
    shutil.copy(src_path, clean / "scheduler.py")
    shutil.copy(
        os.path.join(PACKAGE, "serving", "kv_cache.py"),
        clean / "kv_cache.py",
    )
    assert run_rules([str(clean)], ["dispatch-race"]) == [], [
        d.format() for d in run_rules([str(clean)], ["dispatch-race"])
    ]


def test_refcount_discipline_fixtures():
    """FX106: block-table writes and free-heap mutations outside the
    blessed allocator helpers — the discipline that keeps prefix-page
    refcounts derivable from the live tables."""
    diags = _by_file(
        run_rules([os.path.join(FIXTURES, "refcount")], ["dispatch-race"])
    )
    # steal_page (table write), drop_pages (table write + heap push),
    # grab_free (heap pop)
    assert diags.get("bad.py", []).count("FX106") == 4, diags
    # blessed helpers, __init__ population, reads, unrelated heaps silent
    assert "good.py" not in diags


def test_seeded_refcount_bypass_is_caught(tmp_path):
    """Re-introduce the bug FX106 exists for: demote the COW helper to
    an unblessed name so its table write and free-heap pop become raw
    mutations — fxlint must flag both; the unmodified allocator stays
    clean."""
    src_path = os.path.join(PACKAGE, "serving", "kv_cache.py")
    with open(src_path) as f:
        src = f.read()
    seeded = src.replace("def _cow_page(", "def unblessed_cow_page(", 1)
    assert seeded != src, (
        "kv_cache.py no longer defines _cow_page — update this test "
        "AND the CI fxlint self-test recipe together"
    )
    (tmp_path / "kv_cache.py").write_text(seeded)
    diags = run_rules([str(tmp_path)], ["dispatch-race"])
    hits = [d for d in diags if d.rule_id == "FX106"]
    assert any("block_tables" in d.message for d in hits), [
        d.format() for d in diags
    ]
    assert any("_free_pages" in d.message for d in hits), [
        d.format() for d in diags
    ]
    clean = tmp_path / "clean"
    clean.mkdir()
    shutil.copy(src_path, clean / "kv_cache.py")
    assert run_rules([str(clean)], ["dispatch-race"]) == [], [
        d.format() for d in run_rules([str(clean)], ["dispatch-race"])
    ]


def test_swap_ledger_discipline_fixtures():
    """FX107: swap/eviction ledger mutations (_swapped host-swap table,
    _pub_only publication LRU, _hosts_down routing set) outside the
    blessed allocator helpers — the discipline that keeps the
    swap-bytes budget and eviction audit derivable."""
    diags = _by_file(
        run_rules([os.path.join(FIXTURES, "swap")], ["dispatch-race"])
    )
    # forge/drop/leak/wipe the swap table (4), pin/resurrect/flush the
    # publication LRU (3), kill/revive a host (2)
    assert diags.get("bad.py", []).count("FX107") == 9, diags
    # blessed helpers, __init__ population, audit reads, same-named
    # locals all silent
    assert "good.py" not in diags


def test_seeded_swap_bypass_is_caught(tmp_path):
    """Re-introduce the bug FX107 exists for: demote discard_swap to an
    unblessed name so its ledger pop becomes a raw mutation — fxlint
    must flag it; the unmodified allocator stays clean (covered again
    by test_dispatch_race_clean_on_head over the real package)."""
    src_path = os.path.join(PACKAGE, "serving", "kv_cache.py")
    with open(src_path) as f:
        src = f.read()
    seeded = src.replace("def discard_swap(", "def rogue_discard(", 1)
    assert seeded != src, (
        "kv_cache.py no longer defines discard_swap — update this test "
        "AND the FX107 blessed set together"
    )
    (tmp_path / "kv_cache.py").write_text(seeded)
    diags = run_rules([str(tmp_path)], ["dispatch-race"])
    hits = [d for d in diags if d.rule_id == "FX107"]
    assert any("_swapped" in d.message for d in hits), [
        d.format() for d in diags
    ]


def test_adapter_ledger_discipline_fixtures():
    """FX110: multi-LoRA adapter-pool ledger mutations (adapter_tables,
    slot_adapter bindings, _adapter_refcounts, the _free_adapter_pages
    heap) outside the blessed AdapterPool helpers — the discipline that
    keeps per-tenant adapter pages from being freed under a live slot's
    gather."""
    diags = _by_file(
        run_rules([os.path.join(FIXTURES, "adapters")], ["dispatch-race"])
    )
    # hijack_slot (slot binding), forge_page (table write),
    # cook_refcount (refcount bump), drop_pages (heap push),
    # grab_free (heap pop)
    assert diags.get("bad.py", []).count("FX110") == 5, diags
    # blessed helpers, __init__ population, gather reads, local heaps
    # all silent
    assert "good.py" not in diags


def test_seeded_adapter_bypass_is_caught(tmp_path):
    """Re-introduce the bug FX110 exists for: demote the page-free
    helper to an unblessed name so its table write, refcount zero, and
    heap push become raw mutations — fxlint must flag all three ledger
    families; the unmodified pool stays clean (re-proved over the real
    package by test_dispatch_race_clean_on_head)."""
    src_path = os.path.join(PACKAGE, "serving", "tenancy", "adapters.py")
    with open(src_path) as f:
        src = f.read()
    seeded = src.replace(
        "def _free_adapter_page(", "def rogue_free_page(", 1
    )
    assert seeded != src, (
        "adapters.py no longer defines _free_adapter_page — update "
        "this test AND the FX110 blessed set together"
    )
    (tmp_path / "adapters.py").write_text(seeded)
    diags = run_rules([str(tmp_path)], ["dispatch-race"])
    hits = [d for d in diags if d.rule_id == "FX110"]
    assert any("adapter_tables" in d.message for d in hits), [
        d.format() for d in diags
    ]
    assert any("_free_adapter_pages" in d.message for d in hits), [
        d.format() for d in diags
    ]
    clean = tmp_path / "clean"
    clean.mkdir()
    shutil.copy(src_path, clean / "adapters.py")
    assert run_rules([str(clean)], ["dispatch-race"]) == [], [
        d.format() for d in run_rules([str(clean)], ["dispatch-race"])
    ]


def test_journal_emit_discipline_fixtures():
    """FX111: `generated` token-list mutations outside the blessed
    `_emit` seam — the discipline that keeps every stream-visible
    token journal-noted before the front door publishes it, so a
    crash-restart replays to exactly the tokens the client saw."""
    diags = _by_file(
        run_rules([os.path.join(FIXTURES, "journal")], ["dispatch-race"])
    )
    # backdoor append, draft-run extend, prefix insert, tail rewrite,
    # tail delete, wholesale rebind
    assert diags.get("bad.py", []).count("FX111") == 6, diags
    # the _emit seam, __init__ construction, constructor-seeded
    # recovery, publish-cursor/length reads, same-named locals silent
    assert "good.py" not in diags


def test_seeded_journal_bypass_is_caught(tmp_path):
    """Re-introduce the bug FX111 exists for: demote the emit seam to
    an unblessed name so its `generated` append becomes a raw
    stream-visible commit the journal never notes — fxlint must flag
    it; the unmodified scheduler stays clean (re-proved over the real
    package by test_dispatch_race_clean_on_head)."""
    src_path = os.path.join(PACKAGE, "serving", "scheduler.py")
    with open(src_path) as f:
        src = f.read()
    seeded = src.replace("def _emit(", "def rogue_emit(", 1)
    assert seeded != src, (
        "scheduler.py no longer defines _emit — update this test AND "
        "the FX111 blessed set together"
    )
    (tmp_path / "scheduler.py").write_text(seeded)
    shutil.copy(
        os.path.join(PACKAGE, "serving", "kv_cache.py"),
        tmp_path / "kv_cache.py",
    )
    diags = run_rules([str(tmp_path)], ["dispatch-race"])
    assert any(
        d.rule_id == "FX111" and "generated" in d.message for d in diags
    ), [d.format() for d in diags]
    # the unmodified pair stays clean
    clean = tmp_path / "clean"
    clean.mkdir()
    shutil.copy(src_path, clean / "scheduler.py")
    shutil.copy(
        os.path.join(PACKAGE, "serving", "kv_cache.py"),
        clean / "kv_cache.py",
    )
    assert run_rules([str(clean)], ["dispatch-race"]) == [], [
        d.format() for d in run_rules([str(clean)], ["dispatch-race"])
    ]


def test_handoff_lifetime_fixtures():
    """FX108: cross-engine swap handles/records consumed more than once
    (the staged copy is a MOVE token — export pops the source ledger,
    so a replay restores pages another engine already owns), and
    handoff code reading live source-engine pool state by reference
    while that engine keeps serving."""
    diags = _by_file(
        run_rules([os.path.join(FIXTURES, "handoff")], ["dispatch-race"])
    )
    # double import, discard-after-export, loop replay, tail double (4
    # reuse) + live k/v refs, live table + cursor, live ledger (5
    # live-source)
    assert diags.get("bad.py", []).count("FX108") == 9, diags
    # single-consumption moves, loop-carried fresh tokens, staged
    # copies, blessed seams, own-pool reads all silent
    assert "good.py" not in diags


def test_seeded_handoff_replay_is_caught(tmp_path):
    """Re-introduce the bug FX108 exists for: make the pipeline's
    install step restore the SAME exported record twice (the retry
    shape that forgets export already moved the pages) — fxlint must
    flag it; the unmodified frontend stays clean (re-proven over the
    whole package by test_dispatch_race_clean_on_head)."""
    src_path = os.path.join(
        PACKAGE, "serving", "frontend", "handoff.py"
    )
    with open(src_path) as f:
        src = f.read()
    seeded = src.replace(
        "            record = self.prefill_cache.export_swap(handle)\n",
        "            record = self.prefill_cache.export_swap(handle)\n"
        "            self.prefill_cache.discard_swap(handle)\n",
        1,
    )
    assert seeded != src, (
        "handoff.py's _drain_ready no longer calls export_swap(handle) "
        "— update this seeding recipe alongside the refactor"
    )
    (tmp_path / "handoff.py").write_text(seeded)
    diags = run_rules([str(tmp_path)], ["dispatch-race"])
    assert any(
        d.rule_id == "FX108" and "handle" in d.message for d in diags
    ), [d.format() for d in diags]
    # the unmodified pipeline stays clean
    clean = tmp_path / "clean"
    clean.mkdir()
    shutil.copy(src_path, clean / "handoff.py")
    assert run_rules([str(clean)], ["dispatch-race"]) == [], [
        d.format() for d in run_rules([str(clean)], ["dispatch-race"])
    ]


def test_search_trace_hook_fixtures():
    """FX104: search-trace recording calls capturing live mutable
    state — a captured reference lets exported rows rewrite themselves
    after the searcher mutates its tables."""
    diags = _by_file(
        run_rules(
            [os.path.join(FIXTURES, "search_trace")], ["dispatch-race"]
        )
    )
    assert diags.get("bad.py", []).count("FX104") == 3
    # fresh dict()/copy()/scalars and the (different-API) Tracer silent
    assert "good.py" not in diags


def test_seeded_search_trace_violation_is_caught(tmp_path):
    """Seed an FX104 violation into the REAL search-trace hook
    (unity.py's _trace_leaf): capture the live _views_cache — mutated
    by valid_views after records are taken — in the candidate row. The
    lint must flag it; the unmodified file stays clean."""
    src_path = os.path.join(PACKAGE, "search", "unity.py")
    with open(src_path) as f:
        src = f.read()
    seeded = src.replace(
        "            name=op_name,\n",
        "            name=op_name,\n"
        "            views=self._views_cache,\n",
        1,
    )
    assert seeded != src, (
        "unity.py's _trace_leaf no longer passes name=op_name — update "
        "this seeding recipe alongside the refactor"
    )
    (tmp_path / "unity.py").write_text(seeded)
    diags = run_rules([str(tmp_path)], ["dispatch-race"])
    assert any(
        d.rule_id == "FX104" and "_views_cache" in d.message
        for d in diags
    ), [d.format() for d in diags]
    # the unmodified searcher stays clean
    clean = tmp_path / "clean"
    clean.mkdir()
    shutil.copy(src_path, clean / "unity.py")
    assert run_rules([str(clean)], ["dispatch-race"]) == [], [
        d.format() for d in run_rules([str(clean)], ["dispatch-race"])
    ]


def test_seeded_reconcile_bypass_is_caught(tmp_path):
    """Re-introduce the async-reconcile bug FX103 exists for: make the
    verify commit read LIVE cache lengths (one iteration ahead under
    the pipeline) instead of the InflightStep snapshot."""
    src_path = os.path.join(PACKAGE, "serving", "scheduler.py")
    with open(src_path) as f:
        src = f.read()
    seeded = src.replace(
        "old_len = int(step.lengths[slot])",
        "old_len = int(self.cache.lengths[slot])",
        1,
    )
    assert seeded != src, (
        "scheduler.py's verify commit no longer reads the step snapshot "
        "— update this test alongside the refactor"
    )
    (tmp_path / "scheduler.py").write_text(seeded)
    # the lengths MUTATIONS live in the allocator/engine — scan both,
    # like a full-checkout lint does
    shutil.copy(
        os.path.join(PACKAGE, "serving", "kv_cache.py"),
        tmp_path / "kv_cache.py",
    )
    diags = run_rules([str(tmp_path)], ["dispatch-race"])
    assert any(
        d.rule_id == "FX103" and "lengths" in d.message for d in diags
    ), [d.format() for d in diags]
    # the unmodified pair stays clean
    clean = tmp_path / "clean"
    clean.mkdir()
    shutil.copy(src_path, clean / "scheduler.py")
    shutil.copy(
        os.path.join(PACKAGE, "serving", "kv_cache.py"),
        clean / "kv_cache.py",
    )
    assert run_rules([str(clean)], ["dispatch-race"]) == []


def test_tree_fixtures():
    """Token-tree verify discipline — (a) FX109: a tree-verify
    dispatch capturing live allocator state into the jitted tree step,
    (b) FX103: a tree reconcile reading the dispatched parent table /
    DraftTree plan from a scheduler-side mirror instead of the step
    record."""
    diags = _by_file(
        run_rules([os.path.join(FIXTURES, "tree")], ["dispatch-race"])
    )
    # raw lengths + raw block tables into the tree step (2 × FX109),
    # mirror-read tree_parents + tree_plan (2 × FX103)
    assert diags.get("bad.py", []).count("FX109") == 2, diags
    assert diags.get("bad.py", []).count("FX103") == 2, diags
    # snapshot carriers, int() scalars, and step-record reads silent
    assert "good.py" not in diags


def test_seeded_tree_capture_is_caught(tmp_path):
    """Re-introduce the bug FX109 exists for: hand
    the jitted tree step the LIVE length table instead of the snapshot
    — the step reads it behind the async dispatch queue and the
    reconcile's accept walk runs an iteration later. fxlint must flag
    it; the unmodified engine stays clean."""
    src_path = os.path.join(PACKAGE, "serving", "engine.py")
    with open(src_path) as f:
        src = f.read()
    seeded = src.replace(
        "                snapshot(self.cache.lengths),\n"
        "                jnp.asarray(draft_lens),\n"
        "                jnp.asarray(parents),\n",
        "                self.cache.lengths,\n"
        "                jnp.asarray(draft_lens),\n"
        "                jnp.asarray(parents),\n",
        1,
    )
    assert seeded != src, (
        "engine.py's verify_tree_dispatch no longer snapshots "
        "cache.lengths next to the parents operand — update this "
        "seeding recipe alongside the refactor"
    )
    (tmp_path / "engine.py").write_text(seeded)
    diags = run_rules([str(tmp_path)], ["dispatch-race"])
    assert any(
        d.rule_id == "FX109"
        and "tree-verify dispatch" in d.message
        and "lengths" in d.message
        for d in diags
    ), [d.format() for d in diags]
    # the unmodified engine stays clean
    clean = tmp_path / "clean"
    clean.mkdir()
    shutil.copy(src_path, clean / "engine.py")
    assert run_rules([str(clean)], ["dispatch-race"]) == [], [
        d.format() for d in run_rules([str(clean)], ["dispatch-race"])
    ]


def test_seeded_tree_plan_mirror_read_is_caught(tmp_path):
    """Re-introduce the bug the tree FX103 extension exists for: make
    the tree commit walk a scheduler-side plan mirror instead of the
    plan that traveled with the step."""
    src_path = os.path.join(PACKAGE, "serving", "scheduler.py")
    with open(src_path) as f:
        src = f.read()
    seeded = src.replace(
        "for slot in sorted(step.tree_plan):",
        "for slot in sorted(self._last_tree_plan.tree_plan):",
        1,
    )
    assert seeded != src, (
        "scheduler.py's _commit_verify_tree no longer iterates "
        "step.tree_plan — update this seeding recipe alongside the "
        "refactor"
    )
    (tmp_path / "scheduler.py").write_text(seeded)
    shutil.copy(
        os.path.join(PACKAGE, "serving", "kv_cache.py"),
        tmp_path / "kv_cache.py",
    )
    diags = run_rules([str(tmp_path)], ["dispatch-race"])
    assert any(
        d.rule_id == "FX103" and "tree_plan" in d.message for d in diags
    ), [d.format() for d in diags]
    # the unmodified pair stays clean
    clean = tmp_path / "clean"
    clean.mkdir()
    shutil.copy(src_path, clean / "scheduler.py")
    shutil.copy(
        os.path.join(PACKAGE, "serving", "kv_cache.py"),
        clean / "kv_cache.py",
    )
    assert run_rules([str(clean)], ["dispatch-race"]) == [], [
        d.format() for d in run_rules([str(clean)], ["dispatch-race"])
    ]


# -- retrace-storm (FX2xx) ----------------------------------------------------


def test_retrace_fixtures():
    diags = _by_file(
        run_rules([os.path.join(FIXTURES, "retrace")], ["retrace-storm"])
    )
    bad = diags.get("bad.py", [])
    for rule in ("FX201", "FX202", "FX203", "FX204"):
        assert rule in bad, (rule, bad)
    assert "good.py" not in diags


# -- pallas-gate (FX4xx) ------------------------------------------------------


def test_pallas_gate_fixtures_positive():
    diags = run_rules([os.path.join(FIXTURES, "gate_bad")], ["pallas-gate"])
    by_file = _by_file(diags)
    assert "FX401" in by_file.get("kernel_nogate.py", [])
    # SUBLANES drift is reported on both disagreeing modules
    assert "FX402" in by_file.get("kernel_nogate.py", [])
    assert "FX402" in by_file.get("kernel_driftgate.py", [])
    # _MAX_W defined but unenforced by supports()
    assert any(
        d.rule_id == "FX402" and "_MAX_W" in d.message for d in diags
    )
    assert "FX403" in by_file.get("caller_ungated.py", [])


def test_pallas_gate_fixtures_negative():
    diags = run_rules([os.path.join(FIXTURES, "gate_good")], ["pallas-gate"])
    assert diags == [], [d.format() for d in diags]


def test_pallas_gate_clean_on_head():
    """ops/pallas and every kernel caller obey the gate contract."""
    diags = run_rules([PACKAGE], ["pallas-gate"])
    assert diags == [], [d.format() for d in diags]


# -- repo/baseline contract ---------------------------------------------------


def test_repo_lints_clean_against_baseline():
    """The CI gate: all families over the whole package, every finding
    baselined — fxlint exits 0 on HEAD."""
    rc = main([PACKAGE, "--baseline", BASELINE])
    assert rc == 0


def test_baseline_workflow(tmp_path):
    bad = tmp_path / "mod.py"
    bad.write_text(
        "import jax\n"
        "import jax.numpy as jnp\n"
        "class C:\n"
        "    def mutate(self):\n"
        "        self.state[0] = 1\n"
        "    def dispatch(self):\n"
        "        return jnp.asarray(self.state)\n"
    )
    baseline = tmp_path / "baseline.txt"
    # new finding, no baseline -> fail
    assert main([str(tmp_path), "--baseline", str(baseline)]) == 1
    # accept it -> pass
    assert (
        main([str(tmp_path), "--baseline", str(baseline), "--update-baseline"])
        == 0
    )
    assert main([str(tmp_path), "--baseline", str(baseline)]) == 0
    # a NEW violation still fails against the old baseline
    bad2 = tmp_path / "mod2.py"
    bad2.write_text(
        "import jax.numpy as jnp\n"
        "class D:\n"
        "    def mutate(self):\n"
        "        self.other[0] = 1\n"
        "    def dispatch(self):\n"
        "        return jnp.asarray(self.other)\n"
    )
    assert main([str(tmp_path), "--baseline", str(baseline)]) == 1
    # --no-baseline ignores the accepted set entirely
    os.remove(str(bad2))
    assert main([str(tmp_path), "--baseline", str(baseline), "--no-baseline"]) == 1


def test_unparseable_file_is_a_finding(tmp_path):
    (tmp_path / "broken.py").write_text("def f(:\n")
    diags = run_rules([str(tmp_path)])
    assert [d.rule_id for d in diags] == ["FX000"]


def test_cli_list_rules_and_unknown_family(capsys):
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("FX101", "FX201", "FX301", "FX401"):
        assert rid in out
    with pytest.raises(SystemExit):
        run_rules([PACKAGE], ["no-such-family"])


# -- strategy replay (FX3xx via CLI) ------------------------------------------


def test_strategy_file_replay(tmp_path):
    import json

    good = tmp_path / "good.json"
    good.write_text(
        json.dumps(
            {
                "version": 1,
                "kind": "tp",
                "dp": 2,
                "tp": 2,
                "sites": [{"kind": "attention", "names": ["mha"]}],
            }
        )
    )
    assert check_strategy_files([str(good)]) == []
    bad = tmp_path / "bad.json"
    bad.write_text(
        json.dumps(
            {
                "version": 1,
                "kind": "warp",  # unknown strategy kind
                "dp": 0,  # degree below 1
                "sites": [{"kind": "hologram", "names": []}],
            }
        )
    )
    rules = [d.rule_id for d in check_strategy_files([str(bad)])]
    assert "FX306" in rules and "FX307" in rules
    assert main(["--strategy", str(bad), "--baseline", str(tmp_path / "b")]) == 1
    unreadable = tmp_path / "nope.json"
    unreadable.write_text("{not json")
    assert [d.rule_id for d in check_strategy_files([str(unreadable)])] == [
        "FX000"
    ]


# -- the snapshot() helper ----------------------------------------------------


def test_snapshot_is_an_immutable_copy():
    from flexflow_tpu.serving.engine import snapshot

    host = np.arange(8, dtype=np.int32)
    snap = snapshot(host)
    host[:] = -1  # the post-dispatch mutation the race needs
    np.testing.assert_array_equal(
        np.asarray(snap), np.arange(8, dtype=np.int32)
    )
