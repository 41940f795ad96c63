// sparknet_tpu native data runtime.
//
// The reference embeds its hot loops in native code behind a C shim
// (SURVEY.md §1-2: Caffe C++ engine + libccaffe-style C ABI under
// JavaCPP; reference mount empty, no file:line). The TPU-native split
// keeps *compute* in XLA but moves the host-side data plane — decode,
// shuffle, crop/mirror/mean transform, batch assembly, prefetch — into
// this library so the accelerator never waits on the Python interpreter.
//
// C ABI only (consumed via ctypes, no pybind11 in the image):
//   sn_cifar_decode       — CIFAR binary records -> NHWC uint8 + labels
//   sn_transform_batch    — uint8 NHWC -> cropped/mirrored/mean-sub f32
//   sn_loader_create/next/destroy — threaded prefetching batch loader
//   sn_buffer_release     — give back the batch memory sn_loader_next lent
//   sn_loader_stats       — the loader's cumulative counters (always on)
//   sn_version            — ABI version stamp
//
// Who owns a batch's memory: the loader's buffer pool allocates a batch
// buffer once and it then cycles — a worker writes every element of it in
// place, the in-order queue holds it, sn_loader_next lends the buffer
// itself to the caller (nothing is copied), and sn_buffer_release, which
// the caller makes when its last reference to that memory is gone, puts it
// back for a worker to rewrite.  A buffer is never rewritten while lent; a
// worker that finds none free allocates one more, so the set grows to what
// the caller holds plus what the loader works ahead and stays there.  The
// pool outlives the loader while buffers are lent: sn_loader_destroy never
// frees memory under a reader.
//
// Determinism: every random decision derives from splitmix64(seed,
// epoch, index) counters, never from thread scheduling — a batch stream
// is reproducible for a given seed regardless of thread count (the same
// lineage contract as the Python ShardedDataset path).

#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <mutex>
#include <new>
#include <thread>
#include <vector>

extern "C" {

int sn_version() { return 3; }

static inline int64_t now_ns() {
  return std::chrono::duration_cast<std::chrono::nanoseconds>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

// ---------------------------------------------------------------------------
// RNG: splitmix64 -> bounded ints / floats. Counter-based, stateless.
// ---------------------------------------------------------------------------
static inline uint64_t splitmix64(uint64_t x) {
  x += 0x9E3779B97f4A7C15ULL;
  x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9ULL;
  x = (x ^ (x >> 27)) * 0x94D049BB133111EBULL;
  return x ^ (x >> 31);
}

static inline uint64_t rng_at(uint64_t seed, uint64_t a, uint64_t b) {
  return splitmix64(splitmix64(seed ^ (a * 0x9E3779B97F4A7C15ULL)) ^ b);
}

// ---------------------------------------------------------------------------
// CIFAR binary decode: records of [label u8][3072 bytes CHW] -> NHWC.
// ---------------------------------------------------------------------------
void sn_cifar_decode(const uint8_t* raw, int n_records, uint8_t* out_images,
                     int32_t* out_labels) {
  const int rec = 3073, hw = 32 * 32;
  for (int i = 0; i < n_records; ++i) {
    const uint8_t* r = raw + (int64_t)i * rec;
    out_labels[i] = (int32_t)r[0];
    const uint8_t* chw = r + 1;
    uint8_t* img = out_images + (int64_t)i * hw * 3;
    for (int p = 0; p < hw; ++p) {
      img[p * 3 + 0] = chw[p];            // R plane
      img[p * 3 + 1] = chw[hw + p];       // G plane
      img[p * 3 + 2] = chw[2 * hw + p];   // B plane
    }
  }
}

// ---------------------------------------------------------------------------
// Transform: NHWC uint8 -> f32 with Caffe transform_param semantics:
// (optional train-mode random crop + mirror, else center crop), minus
// per-pixel mean image (crop-aligned) or per-channel mean values, times
// scale. Mirrors sparknet_tpu/data/preprocess.py.
// ---------------------------------------------------------------------------
static void transform_one(const uint8_t* img, int h, int w, int c, int crop,
                          int train, int mirror_on, uint64_t rseed,
                          const float* mean_image /*h*w*c or null*/,
                          const float* mean_channel /*c or null*/, float scale,
                          float* out) {
  int ch = crop > 0 ? crop : h, cw = crop > 0 ? crop : w;
  int off_h = 0, off_w = 0, do_mirror = 0;
  if (crop > 0 && (h > ch || w > cw)) {
    if (train) {
      off_h = (int)(rng_at(rseed, 1, 0) % (uint64_t)(h - ch + 1));
      off_w = (int)(rng_at(rseed, 2, 0) % (uint64_t)(w - cw + 1));
    } else {
      off_h = (h - ch) / 2;
      off_w = (w - cw) / 2;
    }
  }
  if (train && mirror_on) do_mirror = (int)(rng_at(rseed, 3, 0) & 1u);
  for (int y = 0; y < ch; ++y) {
    for (int x = 0; x < cw; ++x) {
      int sx = do_mirror ? (cw - 1 - x) : x;
      const uint8_t* src = img + (((int64_t)(y + off_h) * w) + (sx + off_w)) * c;
      float* dst = out + (((int64_t)y * cw) + x) * c;
      for (int k = 0; k < c; ++k) {
        float v = (float)src[k];
        // both means subtract when both are set (preprocess.py order:
        // mean_image first, then mean_values, then scale)
        if (mean_image)
          v -= mean_image[(((int64_t)(y + off_h) * w) + (sx + off_w)) * c + k];
        if (mean_channel) v -= mean_channel[k];
        dst[k] = v * scale;
      }
    }
  }
}

void sn_transform_batch(const uint8_t* in, int n, int h, int w, int c,
                        int crop, int train, int mirror_on, uint64_t seed,
                        const float* mean_image, const float* mean_channel,
                        float scale, float* out, int num_threads) {
  if (crop > h || crop > w) return;  // wrappers validate and raise first
  int ch = crop > 0 ? crop : h, cw = crop > 0 ? crop : w;
  int64_t in_sz = (int64_t)h * w * c, out_sz = (int64_t)ch * cw * c;
  int nt = num_threads > 0 ? num_threads : 1;
  if (nt > n) nt = n > 0 ? n : 1;
  std::vector<std::thread> ts;
  std::atomic<int> next(0);
  auto work = [&]() {
    for (int i = next.fetch_add(1); i < n; i = next.fetch_add(1)) {
      transform_one(in + i * in_sz, h, w, c, crop, train, mirror_on,
                    rng_at(seed, 0xA5A5, (uint64_t)i), mean_image,
                    mean_channel, scale, out + i * out_sz);
    }
  };
  for (int t = 0; t < nt; ++t) ts.emplace_back(work);
  for (auto& t : ts) t.join();
}

// ---------------------------------------------------------------------------
// Batch buffers: allocated once (first touch of fresh pages costs twenty
// times the write itself at 633 MB), then reused.  Shared by the loader and
// by every buffer it has lent: whoever lets go last deletes the pool.
// ---------------------------------------------------------------------------
struct BufferPool {
  size_t bytes;
  std::mutex mu;
  std::vector<float*> free;  // newest last: the working set stays small
  int64_t out = 0;           // with a worker, in the queue, or lent
  bool closed = false;       // the loader is gone: what comes back is freed

  // A free buffer, or a new one where none is (*fresh). Not zeroed: the
  // taker writes every element.
  float* take(bool* fresh) {
    {
      std::lock_guard<std::mutex> lk(mu);
      ++out;
      *fresh = free.empty();
      if (!*fresh) {
        float* buf = free.back();
        free.pop_back();
        return buf;
      }
    }
    void* buf = nullptr;
    // 64: what XLA:CPU asks of host memory it aliases instead of copying
    if (posix_memalign(&buf, 64, bytes ? bytes : 64) != 0)
      throw std::bad_alloc();
    return (float*)buf;
  }

  void give_back(float* buf) {
    bool last;
    {
      std::lock_guard<std::mutex> lk(mu);
      --out;
      if (closed) std::free(buf); else free.push_back(buf);
      last = closed && out == 0;
    }
    if (last) delete this;
  }

  // The loader's own buffers are back; those still lent come back later.
  void close() {
    bool last;
    {
      std::lock_guard<std::mutex> lk(mu);
      closed = true;
      for (float* buf : free) std::free(buf);
      free.clear();
      last = out == 0;
    }
    if (last) delete this;
  }
};

// ---------------------------------------------------------------------------
// Prefetching loader: owns a copy of the dataset; worker threads build
// shuffled, transformed batches ahead of the consumer into a bounded
// queue. Batch order and contents are functions of (seed, epoch, batch
// index) only.
// ---------------------------------------------------------------------------
struct Loader {
  std::vector<uint8_t> images;
  std::vector<int32_t> labels;
  int n, h, w, c, batch, crop, mirror_on, train;
  std::vector<float> mean_image, mean_channel;
  float scale;
  uint64_t seed;
  int queue_cap;

  // deterministic work assignment
  std::atomic<int64_t> next_batch{0};
  int64_t batches_per_epoch;

  BufferPool* pool;
  struct Ready {
    int64_t index;
    float* data;  // one of pool's buffers
    std::vector<int32_t> labels;
  };
  std::mutex mu;
  std::condition_variable cv_put, cv_get;
  std::deque<Ready> queue;
  int64_t next_out = 0;  // consumer expects batches in index order
  std::vector<std::thread> workers;
  std::atomic<bool> stop{false};

  // Cumulative counters, read by sn_loader_stats in this order.  Always
  // on: six clock reads a batch beside the batch's pixel work.
  enum {
    BATCHES_BUILT,     // batches a worker finished building
    BUILD_NS,          // worker time inside build(), all threads together
    PUT_WAIT_NS,       // worker time blocked on a full window (back-pressure)
    BATCHES_TAKEN,     // batches handed to the consumer
    GET_WAIT_NS,       // consumer time until its in-order batch was ready
    COPY_NS,           // consumer time in the hand-over (the labels' copy), unlocked
    DEPTH_ON_ARRIVAL,  // sum of the queue's depth as each consumer call arrived
    BUFFERS_ALLOCATED, // batch buffers allocated; every other batch reused one
    N_STATS
  };
  std::atomic<int64_t> stats[N_STATS] = {};

  int ch() const { return crop > 0 ? crop : h; }
  int cw() const { return crop > 0 ? crop : w; }

  void perm_index(int64_t epoch, int64_t i, int64_t* out_idx) const {
    // Per-epoch deterministic shuffle without materialising a
    // permutation array: a 4-round Feistel network over the smallest
    // even bit-width covering n (bijective on [0, 2^width)), with
    // cycle-walking back into [0, n).
    int width = 2;
    while ((1ULL << width) < (uint64_t)n) width += 2;
    int half = width / 2;
    uint64_t mask = (1ULL << half) - 1;
    uint64_t k = splitmix64(seed ^ (uint64_t)(epoch + 1));
    uint64_t x = (uint64_t)i;
    do {
      for (int r = 0; r < 4; ++r) {
        uint64_t left = x >> half, right = x & mask;
        uint64_t f = splitmix64(right ^ (k + (uint64_t)r)) & mask;
        x = (right << half) | (left ^ f);
      }
    } while (x >= (uint64_t)n);
    *out_idx = (int64_t)x;
  }

  void build(int64_t bidx, Ready& out) {
    int64_t epoch = bidx / batches_per_epoch;
    int64_t off = (bidx % batches_per_epoch) * batch;
    out.index = bidx;
    bool fresh;
    out.data = pool->take(&fresh);
    if (fresh) stats[BUFFERS_ALLOCATED] += 1;
    out.labels.resize(batch);
    // every element of the buffer is written below: batch x ch x cw x c
    for (int j = 0; j < batch; ++j) {
      int64_t src;
      perm_index(epoch, off + j, &src);
      out.labels[j] = labels[src];
      transform_one(
          images.data() + src * (int64_t)h * w * c, h, w, c, crop, train,
          mirror_on, rng_at(seed, (uint64_t)epoch + 17, (uint64_t)(off + j)),
          mean_image.empty() ? nullptr : mean_image.data(),
          mean_channel.empty() ? nullptr : mean_channel.data(), scale,
          out.data + (int64_t)j * ch() * cw() * c);
    }
  }

  void worker() {
    while (!stop.load()) {
      int64_t bidx = next_batch.fetch_add(1);
      Ready r;
      int64_t t0 = now_ns();
      build(bidx, r);
      int64_t t1 = now_ns();
      stats[BUILD_NS] += t1 - t0;
      stats[BATCHES_BUILT] += 1;
      std::unique_lock<std::mutex> lk(mu);
      // admit by index window, not queue size: the worker holding the
      // next in-order batch must always be able to enqueue, or the
      // consumer (which pops strictly in order) deadlocks against
      // workers parked on later batches
      cv_put.wait(lk, [&] {
        return stop.load() || bidx < next_out + queue_cap;
      });
      stats[PUT_WAIT_NS] += now_ns() - t1;
      if (stop.load()) {
        pool->give_back(r.data);
        return;
      }
      queue.push_back(std::move(r));
      cv_get.notify_all();
    }
  }
};

void* sn_loader_create(const uint8_t* images, const int32_t* labels, int n,
                       int h, int w, int c, int batch, int crop, int train,
                       int mirror_on, const float* mean_image,
                       const float* mean_channel, float scale, uint64_t seed,
                       int num_threads, int queue_cap) {
  if (n <= 0 || batch <= 0 || batch > n) return nullptr;
  if (crop > h || crop > w) return nullptr;
  Loader* L = new Loader();
  L->images.assign(images, images + (int64_t)n * h * w * c);
  L->labels.assign(labels, labels + n);
  L->n = n; L->h = h; L->w = w; L->c = c;
  L->batch = batch; L->crop = crop; L->train = train;
  L->mirror_on = mirror_on; L->scale = scale; L->seed = seed;
  L->queue_cap = queue_cap > 0 ? queue_cap : 4;
  L->pool = new BufferPool();
  L->pool->bytes = sizeof(float) * (size_t)batch * L->ch() * L->cw() * c;
  if (mean_image)
    L->mean_image.assign(mean_image, mean_image + (int64_t)h * w * c);
  if (mean_channel) L->mean_channel.assign(mean_channel, mean_channel + c);
  L->batches_per_epoch = n / batch;  // drop remainder, like the apps
  int nt = num_threads > 0 ? num_threads : 2;
  for (int t = 0; t < nt; ++t)
    L->workers.emplace_back([L] { L->worker(); });
  return (void*)L;
}

// Blocks until the next in-order batch is ready; returns 0 on success.
// Lends the batch's own buffer: *out_data is valid, and is not rewritten,
// until sn_buffer_release(*out_pool, *out_data), which the caller makes
// exactly once, from any thread, before or after sn_loader_destroy.
int sn_loader_next(void* handle, float** out_data, void** out_pool,
                   int32_t* out_labels) {
  Loader* L = (Loader*)handle;
  if (!L) return -1;
  int64_t t0 = now_ns();
  std::unique_lock<std::mutex> lk(L->mu);
  L->stats[Loader::DEPTH_ON_ARRIVAL] += (int64_t)L->queue.size();
  for (;;) {
    for (size_t i = 0; i < L->queue.size(); ++i) {
      if (L->queue[i].index == L->next_out) {
        Loader::Ready r = std::move(L->queue[i]);
        L->queue.erase(L->queue.begin() + i);
        L->next_out++;
        lk.unlock();
        L->cv_put.notify_all();
        int64_t t1 = now_ns();
        *out_data = r.data;
        *out_pool = (void*)L->pool;
        std::memcpy(out_labels, r.labels.data(),
                    r.labels.size() * sizeof(int32_t));
        L->stats[Loader::GET_WAIT_NS] += t1 - t0;
        L->stats[Loader::COPY_NS] += now_ns() - t1;
        L->stats[Loader::BATCHES_TAKEN] += 1;
        return 0;
      }
    }
    if (L->stop.load()) return -2;
    L->cv_get.wait(lk);
  }
}

void sn_buffer_release(void* pool, float* data) {
  if (pool && data) ((BufferPool*)pool)->give_back(data);
}

// Copies the first min(n, N_STATS) cumulative counters (Loader's enum
// order) into out; returns how many the library has.
int sn_loader_stats(void* handle, int64_t* out, int n) {
  Loader* L = (Loader*)handle;
  if (!L) return -1;
  for (int i = 0; i < n && i < Loader::N_STATS; ++i) out[i] = L->stats[i].load();
  return Loader::N_STATS;
}

void sn_loader_destroy(void* handle) {
  Loader* L = (Loader*)handle;
  if (!L) return;
  {
    std::lock_guard<std::mutex> lk(L->mu);
    L->stop.store(true);
  }
  L->cv_put.notify_all();
  L->cv_get.notify_all();
  for (auto& t : L->workers) t.join();
  for (auto& r : L->queue) L->pool->give_back(r.data);
  L->pool->close();
  delete L;
}

}  // extern "C"
