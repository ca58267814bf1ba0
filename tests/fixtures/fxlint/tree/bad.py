"""FX109/FX103 positives — token-tree verify violations.

A tree-verify dispatch captures live allocator state into the jitted
tree step (FX109), and a tree reconcile
reads the dispatched parent table / DraftTree plan from a
scheduler-side mirror instead of the step record (FX103).
"""


class BadScheduler:
    def advance(self, slot):
        # makes `lengths` a mutated attribute for the scanned file set
        self.cache.lengths[slot] += 1

    def alloc(self, slot, page):
        # blessed FX106 name — only here to make `block_tables` mutated
        self.cache.block_tables[slot] = page

    def verify_tree_dispatch(self, params, tokens, parents):
        # FX109: the live length table rides into the jitted tree step
        # — read behind the async dispatch queue, an iteration stale
        step_args = (params, tokens, self.cache.lengths, parents)
        # FX109: live block tables bound raw for the tree's page claims
        tables = self.cache.block_tables
        return self._tree_fn(*step_args), tables

    def commit_tree(self, step, logits):
        # FX103: parent table read from a scheduler-side mirror — the
        # accept walk scores this step's logits on the NEXT iteration's
        # topology
        parents = self._last_tree.tree_parents
        # FX103: same for the per-slot DraftTree plan
        plan = self._pending_plan.tree_plan
        return logits, parents, plan
