// Threaded host-side data loader.
//
// Native rebuild of the reference's C++ SingleDataLoader
// (reference: python/flexflow_dataloader.{h,cc} — full dataset resident in
// host memory, next_batch copies per-shard slices toward the device). On
// TPU the device transfer is JAX's job; the native layer owns what the
// reference's CPU tasks owned: epoch shuffling, row gather into contiguous
// batch buffers, and background prefetch so the accelerator never waits on
// Python-side batch assembly.
//
// Ownership: the caller keeps the source arrays AND the batch buffers
// (the ring of slots, `depth` x `num_arrays` pointers handed to
// ffn_loader_create) alive for the loader's lifetime. A slot is lent, not
// copied: ffn_loader_borrow hands the caller the slot that holds the next
// batch, and this thread writes to it again only after
// ffn_loader_release (or a reset). The caller may hold several slots at
// once, for as long as a device transfer reads them; the worker gathers
// ahead into whatever slots are free.
//
// Epochs: a batch's index, and with it its slot and its lease, counts on
// from the last reset and does not rewind at an epoch's turn. The caller
// may hand over the order of the epoch after the last one known
// (ffn_loader_queue_perm); the worker then goes from an epoch's last batch
// straight on to the next epoch's first, as slots are released, and the
// caller borrows across the turn as inside an epoch. With no order queued
// the stream ends with the epoch, until a reset.

#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <mutex>
#include <numeric>
#include <thread>
#include <vector>

namespace {

struct Slot {
  std::vector<uint8_t*> buffers;  // one per array, the caller's memory
  int64_t index = -1;             // batch index since the last reset
  enum { FREE, READY, LENT } state = FREE;
};

struct Loader {
  std::vector<const uint8_t*> arrays;
  std::vector<int64_t> row_bytes;  // bytes per sample, per array
  int64_t num_samples = 0;
  int64_t batch_size = 0;
  bool drop_last = true;

  // Sample order of the epoch the worker is gathering, and of the epochs
  // after it. Always supplied by the caller (the Python wrapper shuffles
  // with numpy's seeded RNG) so that the batch stream is bit-identical
  // with and without the native library. The worker reads `perm` outside
  // the lock, so only the worker (or a reset, which waits for it) writes it.
  std::vector<int64_t> perm;
  std::deque<std::vector<int64_t>> queued;
  int64_t num_batches = 0;  // per epoch

  // Batch i lives in slot i % slots.size(): FREE until the worker has
  // gathered it, READY until the caller borrows it, LENT until released.
  std::vector<Slot> slots;
  int64_t produced = 0;    // next batch index the worker will fill
  int64_t taken = 0;       // next batch index the caller will borrow
  int64_t perm_end = 0;    // where `perm`'s epoch ends
  int64_t stream_end = 0;  // where the last queued epoch ends
  bool filling = false;  // worker is copying outside the lock
  bool stop = false;
  std::thread worker;
  std::mutex mu;
  std::condition_variable cv_produce, cv_consume;

  Slot& slot_of(int64_t batch_idx) {
    return slots[(size_t)(batch_idx % (int64_t)slots.size())];
  }

  std::vector<int64_t> order(const int64_t* p) const {
    std::vector<int64_t> out((size_t)num_samples);
    if (p)
      std::memcpy(out.data(), p, sizeof(int64_t) * num_samples);
    else
      std::iota(out.begin(), out.end(), 0);
    return out;
  }

  // A stream of one epoch in order `p`, from its first batch.
  void start(const int64_t* p) {
    perm = order(p);
    queued.clear();
    produced = taken = 0;
    perm_end = stream_end = num_batches;
  }

  // Batch `in_epoch` of `perm`'s epoch into slot `s`.
  void fill(Slot* s, int64_t in_epoch) {
    int64_t begin = in_epoch * batch_size;
    int64_t rows = std::min(batch_size, num_samples - begin);
    for (size_t a = 0; a < arrays.size(); ++a) {
      int64_t rb = row_bytes[a];
      uint8_t* dst = s->buffers[a];
      for (int64_t r = 0; r < rows; ++r)
        std::memcpy(dst + r * rb, arrays[a] + perm[begin + r] * rb,
                    (size_t)rb);
      // pad a short final batch by repeating row 0 (static shapes for XLA)
      for (int64_t r = rows; r < batch_size; ++r)
        std::memcpy(dst + r * rb, arrays[a] + perm[begin] * rb, (size_t)rb);
    }
  }

  void run() {
    for (;;) {
      std::unique_lock<std::mutex> lk(mu);
      cv_produce.wait(lk, [&] {
        return stop || (produced < stream_end &&
                        slot_of(produced).state == Slot::FREE);
      });
      if (stop) return;
      if (produced == perm_end) {  // the epoch's turn: on in the next order
        perm = std::move(queued.front());
        queued.pop_front();
        perm_end += num_batches;
      }
      int64_t idx = produced;
      Slot* slot = &slot_of(idx);
      int64_t in_epoch = idx - (perm_end - num_batches);
      filling = true;
      lk.unlock();
      fill(slot, in_epoch);
      lk.lock();
      filling = false;
      // A reset waits for `filling` to clear, so it runs after this
      // publish and rewinds it with everything else.
      slot->index = idx;
      slot->state = Slot::READY;
      produced++;
      cv_consume.notify_all();
    }
  }
};

}  // namespace

extern "C" {

// arrays[i] points at num_samples rows of row_bytes[i] bytes each.
// perm (nullable -> identity) gives the epoch's sample order.
// slot_ptrs[s * num_arrays + i] points at batch_size * row_bytes[i]
// writable bytes: array i's buffer in slot s, for s < depth.
void* ffn_loader_create(const void** arrays, const int64_t* row_bytes,
                        int32_t num_arrays, int64_t num_samples,
                        int64_t batch_size, const int64_t* perm,
                        int32_t drop_last, int32_t depth, void** slot_ptrs) {
  if (num_arrays <= 0 || num_samples <= 0 || batch_size <= 0 || depth <= 0)
    return nullptr;
  Loader* L = new Loader();
  for (int32_t i = 0; i < num_arrays; ++i) {
    L->arrays.push_back((const uint8_t*)arrays[i]);
    L->row_bytes.push_back(row_bytes[i]);
  }
  L->num_samples = num_samples;
  L->batch_size = batch_size;
  L->drop_last = drop_last != 0;
  L->num_batches = drop_last ? num_samples / batch_size
                             : (num_samples + batch_size - 1) / batch_size;
  L->start(perm);
  L->slots.resize((size_t)depth);
  for (int32_t s = 0; s < depth; ++s)
    for (int32_t i = 0; i < num_arrays; ++i)
      L->slots[(size_t)s].buffers.push_back(
          (uint8_t*)slot_ptrs[s * num_arrays + i]);
  L->worker = std::thread([L] { L->run(); });
  return L;
}

int64_t ffn_loader_num_batches(void* loader) {
  return ((Loader*)loader)->num_batches;
}

// Blocks until the next batch is gathered and lends its slot to the
// caller. Returns the batch index, counted from the last reset (its slot
// is index % depth, its place in its epoch index % num_batches), -1 at the
// end of the last epoch an order was given for, or -2 when the slot this
// batch needs is still lent: the caller would be waiting for itself.
int64_t ffn_loader_borrow(void* loader) {
  Loader* L = (Loader*)loader;
  std::unique_lock<std::mutex> lk(L->mu);
  if (L->taken >= L->stream_end) return -1;
  Slot& s = L->slot_of(L->taken);
  if (s.state == Slot::LENT) return -2;
  L->cv_consume.wait(lk, [&] { return s.state == Slot::READY; });
  s.state = Slot::LENT;
  return L->taken++;
}

// The caller has finished reading batch `batch_idx`'s slot (every
// transfer out of it has completed): the worker may gather into it again.
void ffn_loader_release(void* loader, int64_t batch_idx) {
  Loader* L = (Loader*)loader;
  std::unique_lock<std::mutex> lk(L->mu);
  Slot& s = L->slot_of(batch_idx);
  if (s.state == Slot::LENT && s.index == batch_idx) {
    s.state = Slot::FREE;
    L->cv_produce.notify_all();
  }
}

// Batches gathered since the last reset: those below it are READY or were.
int64_t ffn_loader_gathered(void* loader) {
  Loader* L = (Loader*)loader;
  std::unique_lock<std::mutex> lk(L->mu);
  return L->produced;
}

// One more epoch after the last one known, in the order `perm` (copied):
// the stream goes on into it with no reset, and every lease stays.
void ffn_loader_queue_perm(void* loader, const int64_t* perm) {
  Loader* L = (Loader*)loader;
  std::unique_lock<std::mutex> lk(L->mu);
  L->queued.push_back(L->order(perm));
  L->stream_end += L->num_batches;
  L->cv_produce.notify_all();
}

// A new stream: install the caller's sample order and restart prefetching
// from batch 0, dropping whatever was gathered or queued ahead. Every lent
// slot is taken back: the caller has to be done with all of them.
void ffn_loader_reset(void* loader, const int64_t* perm) {
  Loader* L = (Loader*)loader;
  std::unique_lock<std::mutex> lk(L->mu);
  // Wait until the worker is parked on the condition variable (not copying
  // outside the lock) before touching the permutation or counters.
  L->cv_consume.wait(lk, [&] { return !L->filling; });
  L->start(perm);
  for (auto& s : L->slots) {
    s.state = Slot::FREE;
    s.index = -1;
  }
  L->cv_produce.notify_all();
}

void ffn_loader_destroy(void* loader) {
  Loader* L = (Loader*)loader;
  {
    std::unique_lock<std::mutex> lk(L->mu);
    L->stop = true;
    L->cv_produce.notify_all();
  }
  L->worker.join();
  delete L;
}

}  // extern "C"
