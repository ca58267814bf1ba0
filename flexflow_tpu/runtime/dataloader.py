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
after the gather no batch is copied again on the host."""

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
    allocated or copied on the host per step."""

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
        self._order = np.arange(self.num_samples)
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

    def reset(self):
        self._pos = 0
        if self.shuffle:
            self._rng.shuffle(self._order)
        if self._ring is not None:
            # the ring rewinds under every lease: end them first
            self._pending = None
            while self._out:
                self._return_oldest()
            self._ring.reset_perm(self._order)

    def next_batch(self) -> Dict[str, np.ndarray]:
        if self._ring is not None:
            bufs = self._ring.next_batch()
            if bufs is None:  # epoch rollover
                self.reset()
                bufs = self._ring.next_batch()
            return dict(zip(self._keys, bufs))
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
        got = self._ring.borrow()
        if got is None:  # epoch rollover
            self.reset()
            got = self._ring.borrow()
        self._pending = got
        return dict(zip(self._keys, got[1]))

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
        """(batches borrowed, batches copied, seconds waited for leases)
        since the last call."""
        got = (self.batches_borrowed, self.batches_copied, self.lease_wait_s)
        self.batches_borrowed = self.batches_copied = 0
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
