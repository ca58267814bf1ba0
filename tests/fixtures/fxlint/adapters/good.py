"""FX110 negative space: the blessed AdapterPool helpers own these
mutations, reads are always sanctioned, and unrelated heaps/attrs
don't match."""

import heapq


class WellBehavedPool:
    def __init__(self):
        # construction precedes sharing — init-time population is fine
        self.adapter_tables = {}
        self.adapter_tables[0, 0] = 3
        self.slot_adapter = [-1, -1]
        self._adapter_refcounts = [0, 0, 0]
        self._free_adapter_pages = [1, 2]

    def load(self, aid, pages):
        # a blessed helper IS the mutation seam
        for pi, _ in enumerate(pages):
            self._install_adapter_page(aid, pi,
                                       self._pop_free_adapter_page())

    def _pop_free_adapter_page(self):
        return heapq.heappop(self._free_adapter_pages)

    def _install_adapter_page(self, aid, pi, page):
        self.adapter_tables[aid, pi] = page
        self._adapter_refcounts[page] = 1

    def _free_adapter_page(self, aid, pi):
        page = int(self.adapter_tables[aid, pi])
        self.adapter_tables[aid, pi] = -1
        self._adapter_refcounts[page] = 0
        heapq.heappush(self._free_adapter_pages, page)

    def attach(self, slot, aid):
        self.slot_adapter[slot] = aid
        self._adapter_refcounts[self.adapter_tables[aid, 0]] += 1

    def detach(self, slot):
        aid = self.slot_adapter[slot]
        self.slot_adapter[slot] = -1
        self._adapter_refcounts[self.adapter_tables[aid, 0]] -= 1

    def unload(self, aid):
        self._free_adapter_page(aid, 0)


class InnocentBystander:
    def gather_tables(self, pool, slots):
        # loads never match — slot_tables builds its gather
        # tables by READING the ledgers into fresh locals
        tbl = {}
        for i, s in enumerate(slots):
            tbl[i] = pool.adapter_tables[pool.slot_adapter[s]]
        return tbl

    def audit(self, pool, page):
        return int(pool._adapter_refcounts[page])

    def own_heap(self):
        # heap ops on plain locals / other attrs are out of scope
        pq = []
        heapq.heappush(pq, 3)
        return heapq.heappop(pq)
