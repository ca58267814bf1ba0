"""Data loading (reference: python/flexflow_dataloader.{h,cc,cu} —
SingleDataLoader keeps the full dataset in zero-copy memory and
index-launches per-shard batch copies; SURVEY §2.7).

TPU-native version: the dataset lives in host RAM as numpy arrays; each
step takes a global batch and `jax.device_put`s it with the input's
NamedSharding, so each chip receives exactly its shard (the same
host→device movement pattern, without the Legion tasks). Batch assembly
(shuffle + row gather) runs on the native threaded loader
(native/src/dataloader.cc via flexflow_tpu.native.NativeLoader) when the
C++ core is available, so the next batch is prefetched while the chip is
still executing the current step — the role the reference's background
CPU load tasks played. The gather writes into a ring of reused slots, and
`fit()` transfers straight out of the slot (`borrow_batch` / `lend`):
after the gather no batch is copied again on the host. When another epoch
follows (`begin_epoch(follows=True)`) the ring goes on into it by itself:
its first batches are gathered beside this epoch's last steps."""

from __future__ import annotations

import collections
import contextlib
import time
from typing import Dict, Optional

import numpy as np


def _aliases(placed, view: np.ndarray) -> bool:
    """Whether the device array `placed` IS the host memory of `view`.
    Only a backend whose device memory is the host's can do that (the CPU
    backend does, for a buffer on a 64-byte boundary), so the platform is
    asked first: reading a buffer's address on an accelerator would wait
    for the transfer."""
    shards = getattr(placed, "addressable_shards", None)
    if not shards or shards[0].device.platform != "cpu":
        return False
    lo = view.ctypes.data
    return any(
        lo <= s.data.unsafe_buffer_pointer() < lo + view.nbytes for s in shards
    )


class SingleDataLoader:
    """Full-dataset-resident loader with sequential batches
    (reference: flexflow_dataloader.h:34-107).

    Two ways to take a batch. `next_batch()` returns arrays the caller
    owns. `borrow_batch()` lends views of a slot the loader reuses, which
    is what `fit()` does: it places the views on the device, tells the
    loader what it made (`lend`), and the loader refills that slot only
    after those device arrays are ready, so no batch-sized block is
    allocated or copied on the host per step.

    Two ways to start an epoch. `reset()` starts from nothing: every
    lease ended, the ring rewound. `begin_epoch(follows)` is `fit()`'s:
    its first call is a reset, and each says whether another epoch comes
    right after. That epoch's order is then drawn at once (one shuffle an
    epoch from `_rng`, in the sequence a reset an epoch draws them) and
    queued in the ring, whose batches count on across the turn: the
    epoch's first batch may be borrowed before its `begin_epoch`, which
    then rewinds nothing and ends no lease."""

    def __init__(
        self,
        arrays: Dict[str, np.ndarray],
        batch_size: int,
        shuffle: bool = False,
        seed: int = 0,
        drop_last: bool = True,
        use_native: bool = True,
    ):
        sizes = {k: len(v) for k, v in arrays.items()}
        if len(set(sizes.values())) != 1:
            raise ValueError(f"dataset arrays disagree on length: {sizes}")
        self.arrays = arrays
        self.num_samples = next(iter(sizes.values()))
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        self._rng = np.random.RandomState(seed)
        # the last order drawn, and whether its epoch is yet to begin
        # (drawn ahead and queued in the ring: what the next reset starts
        # from, so that no draw is skipped)
        self._order = np.arange(self.num_samples)
        self._drawn_ahead = False
        self._pos = 0
        self._keys = list(arrays.keys())
        self._ring = None
        # leases: (batch index, the device arrays made from its slot),
        # oldest first, and the batch borrowed but not yet lent
        self._out = collections.deque()
        self._pending = None
        self.batches_borrowed = 0
        self.batches_copied = 0
        self.lease_wait_s = 0.0
        self.batches_gathered_ahead = 0
        # ring indices: where this epoch began, and the next batch to take
        self._epoch_start = 0
        self._taken = 0
        # The ring of reused slots (and, with the C++ core, the worker
        # thread that gathers ahead into it): only for full-batch epochs
        # (drop_last) so that every batch has the slot's shape, and only
        # when at least one full batch exists. The permutation always
        # comes from this object's numpy RNG, so batches are
        # bit-identical with or without the native library.
        if drop_last and self.num_samples >= batch_size:
            from flexflow_tpu import native as _native_mod

            self._ring = _native_mod.NativeLoader(
                [arrays[k] for k in self._keys],
                batch_size,
                shuffle=False,  # identity, as `_order`; reset() supplies the rest
                seed=seed,
                drop_last=drop_last,
                use_lib=use_native,
            )

    @property
    def num_batches(self) -> int:
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size

    def _draw_order(self):
        """The order of the epoch after the last one drawn: one shuffle of
        it, and nothing else draws from `_rng`. A copy is shuffled: the
        ring may still be reading the last one."""
        if self.shuffle:
            self._order = self._order.copy()
            self._rng.shuffle(self._order)

    def _end_leases(self):
        self._pending = None
        while self._out:
            self._return_oldest()

    def reset(self):
        """An epoch from its first batch, with nothing kept: every lease
        ended, whatever the ring gathered ahead dropped."""
        self._pos = 0
        if not self._drawn_ahead:
            self._draw_order()
        self._drawn_ahead = False
        if self._ring is not None:
            # the ring rewinds under every lease: end them first
            self._end_leases()
            self._ring.reset_perm(self._order)
            self._epoch_start = self._taken = 0

    def begin_epoch(self, follows: bool = False):
        """`fit()`'s turn of an epoch. `follows` promises that another
        epoch comes right after this one, begun here too: a ring learns
        its order now and goes on into it, and that epoch's `begin_epoch`
        finds it begun, this epoch's batches all taken and perhaps the
        first of the next. Anything else is a reset. Batches gathered
        ahead and never asked for go with `close()` or the next reset.
        Returns whether the ring goes on: whether the next epoch's first
        batch may be borrowed before its `begin_epoch`."""
        rolled = (
            self._drawn_ahead
            and self._taken >= self._epoch_start + self.num_batches
        )
        if rolled:
            self._drawn_ahead = False
            self._epoch_start += self.num_batches
            self.batches_gathered_ahead += (
                self._ring.gathered() - self._epoch_start
            )
        else:
            self.reset()
        if follows and self._ring is not None:
            self._draw_order()
            self._drawn_ahead = True
            self._ring.queue_perm(self._order)
        return self._drawn_ahead

    def close(self):
        """Ends every lease, after the wait for each, and joins the
        ring's worker; what it gathered ahead is dropped."""
        if self._ring is not None:
            self._end_leases()
            self._ring.close()

    def _take(self):
        """The ring's next batch, lent: (index, views)."""
        got = self._ring.borrow()
        if got is None:  # epoch rollover
            # an order drawn ahead was the stream's last epoch: it has run
            self._drawn_ahead = False
            self.reset()
            got = self._ring.borrow()
        self._taken = got[0] + 1
        return got

    def next_batch(self) -> Dict[str, np.ndarray]:
        if self._ring is not None:
            index, views = self._take()
            out = {k: v.copy() for k, v in zip(self._keys, views)}
            self._ring.release(index)
            return out
        remaining = self.num_samples - self._pos
        if remaining < self.batch_size and (self.drop_last or remaining == 0):
            self.reset()
        take = self.batch_size
        if not self.drop_last:
            take = min(take, self.num_samples - self._pos)
        idx = self._order[self._pos : self._pos + take]
        self._pos += take
        return {k: v[idx] for k, v in self.arrays.items()}

    # -- lending -------------------------------------------------------------

    def _return_oldest(self, waiting=contextlib.nullcontext):
        import jax

        index, placed = self._out.popleft()
        with waiting():
            t0 = time.perf_counter()
            jax.block_until_ready(placed)
            self.lease_wait_s += time.perf_counter() - t0
        self._ring.release(index)

    def borrow_batch(self, waiting=contextlib.nullcontext) -> Dict[str, np.ndarray]:
        """The next batch as views of a slot the loader reuses. Place them
        on the device and pass the result to `lend` before asking for
        another. One transfer stays in flight, the batch lent just before
        this call; older ones had a whole step to finish, and their slots
        go back to the ring here, after a wait (entered through
        `waiting`, a context manager factory) for their device arrays.
        Without a ring (`drop_last=False`) the arrays are the caller's."""
        if self._ring is None:
            self.batches_copied += 1
            return self.next_batch()
        if self._pending is not None:  # borrowed and never lent: unread
            self._ring.release(self._pending[0])
            self._pending = None
        while len(self._out) > max(0, self._ring.depth - 2):
            self._return_oldest(waiting)
        self._pending = self._take()
        return dict(zip(self._keys, self._pending[1]))

    def lend(self, placed: Dict[str, object]) -> Dict[str, object]:
        """`placed` is what the caller made on the device from the last
        borrowed batch (name -> array). Its slot is refilled only after
        every one of them is ready. Returns what to use in their place:
        the same arrays, except that one the backend made by keeping the
        slot's memory instead of copying it is replaced by a copy of its
        own, since the slot will be overwritten while the array lives."""
        if self._pending is None:
            return placed
        from flexflow_tpu.runtime.multihost import place_array

        index, views = self._pending
        self._pending = None
        placed = dict(placed)
        kept = False
        for name, view in zip(self._keys, views):
            arr = placed.get(name)
            if arr is not None and _aliases(arr, view):
                placed[name] = place_array(view.copy(), arr.sharding)
                kept = True
        self.batches_copied += kept
        self.batches_borrowed += not kept
        self._out.append((index, list(placed.values())))
        return placed

    def take_counts(self):
        """(batches borrowed, batches copied, seconds waited for leases,
        batches the ring had gathered before their epoch began) since the
        last call."""
        got = (
            self.batches_borrowed, self.batches_copied, self.lease_wait_s,
            self.batches_gathered_ahead,
        )
        self.batches_borrowed = self.batches_copied = 0
        self.batches_gathered_ahead = 0
        self.lease_wait_s = 0.0
        return got

    def __iter__(self):
        self.reset()
        for _ in range(self.num_batches):
            yield self.next_batch()


def synthetic_dataset(
    input_specs: Dict[str, tuple],
    num_samples: int,
    seed: int = 0,
) -> Dict[str, np.ndarray]:
    """Random data matching {name: (shape_without_batch, np.dtype, high)}.

    Integer dtypes draw uniform ints in [0, high); floats draw N(0, 1).
    """
    rng = np.random.RandomState(seed)
    out = {}
    for name, (shape, dtype, high) in input_specs.items():
        full = (num_samples,) + tuple(shape)
        if np.issubdtype(np.dtype(dtype), np.integer):
            out[name] = rng.randint(0, max(1, int(high)), size=full).astype(dtype)
        else:
            out[name] = rng.randn(*full).astype(dtype)
    return out
