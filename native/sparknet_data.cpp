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
// How a batch is built: all the loader's threads build the same batch,
// the oldest unfinished one.  Each takes runs of images from the batch's
// cursor and writes them into the batch's buffer; the thread that writes
// the last image enqueues the batch.  A thread that finds no image left to
// hand out starts the next batch (no barrier), but only inside the
// in-order window (index < next_out + queue_cap), and parks otherwise: the
// admission test comes before a buffer is taken, so the batches in build
// and the ready ones together never pass queue_cap.  An image is written
// row by row (Transform::row): crop offsets and mirror are decided once per
// image, the means once per row, and the row itself is one flat loop over
// cw*c contiguous elements that the compiler vectorises.
//
// Who owns a batch's memory: the loader's buffer pool allocates a batch
// buffer once and it then cycles — the threads write every element of it in
// place, the in-order queue holds it, sn_loader_next lends the buffer
// itself to the caller (nothing is copied), and sn_buffer_release, which
// the caller makes when its last reference to that memory is gone, puts it
// back to be rewritten.  A buffer is never rewritten while lent; a batch
// started while none is free allocates one more, so the set grows to what
// the caller holds plus the window and stays there.  The pool outlives the
// loader while buffers are lent: sn_loader_destroy never frees memory under
// a reader.
//
// Determinism: every random decision derives from splitmix64(seed,
// epoch, index) counters, never from thread scheduling, and the float
// expression of an element is ((float)src - mean_image - mean_channel) *
// scale in that order whatever the row length — a batch stream is
// reproducible byte for byte for a given seed regardless of thread count
// (the same lineage contract as the Python ShardedDataset path).

#include <algorithm>
#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <memory>
#include <mutex>
#include <new>
#include <thread>
#include <vector>

extern "C" int sn_version() { return 4; }
// the same number as a name: sparknet_tpu.native looks for it in the file
// before loading it, and rebuilds a library that lacks it
extern "C" int sn_abi_4() { return 4; }

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
extern "C"
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

// A Transform holds what is the same for every image of a loader or of a
// call: the sizes, the per-channel mean as a row-long pattern, and the mean
// image a second time with its rows' pixels reversed, so that a mirrored
// row reads its means front to back like any other.
struct Transform {
  int h, w, c, crop, train, mirror_on;
  float scale;
  const float* mean_image;      // h*w*c, or null
  std::vector<float> mean_row;  // cw*c: mean_channel repeated, or empty
  std::vector<float> mean_image_mirrored;  // with mean_image, where rows mirror

  Transform(int h, int w, int c, int crop, int train, int mirror_on,
            const float* mean_image, const float* mean_channel, float scale)
      : h(h), w(w), c(c), crop(crop), train(train), mirror_on(mirror_on),
        scale(scale), mean_image(mean_image) {
    if (mean_channel) {
      mean_row.resize((size_t)cw() * c);
      for (size_t i = 0; i < mean_row.size(); ++i)
        mean_row[i] = mean_channel[i % c];
    }
    if (mean_image && train && mirror_on) {
      mean_image_mirrored.resize((size_t)h * w * c);
      for (int64_t y = 0; y < h; ++y)
        for (int64_t x = 0; x < w; ++x)
          std::copy_n(mean_image + (y * w + (w - 1 - x)) * c, c,
                      mean_image_mirrored.data() + (y * w + x) * c);
    }
  }

  int ch() const { return crop > 0 ? crop : h; }
  int cw() const { return crop > 0 ? crop : w; }
  int64_t in_size() const { return (int64_t)h * w * c; }
  int64_t out_size() const { return (int64_t)ch() * cw() * c; }

  // One row of n contiguous elements.  Both means subtract when both are
  // set (preprocess.py order: mean_image first, then mean_values, then
  // scale); which are set is fixed outside the loop.
  template <bool MI, bool MC>
  static void row(const uint8_t* __restrict__ src, const float* __restrict__ mi,
                  const float* __restrict__ mc, float scale,
                  float* __restrict__ dst, int n) {
    for (int i = 0; i < n; ++i) {
      float v = (float)src[i];
      if (MI) v -= mi[i];
      if (MC) v -= mc[i];
      dst[i] = v * scale;
    }
  }

  // dst's pixel x is src's pixel cw-1-x: the pixels (groups of c) turn
  // round, not the bytes.  The usual channel counts get a copy of known size.
  template <int C>
  static void reverse(const uint8_t* src, uint8_t* dst, int cw, int c) {
    if (C) c = C;
    for (int x = 0; x < cw; ++x)
      std::memcpy(dst + x * c, src + (cw - 1 - x) * c, c);
  }
  void reverse_pixels(const uint8_t* src, uint8_t* dst, int cw) const {
    switch (c) {
      case 1: return reverse<1>(src, dst, cw, c);
      case 3: return reverse<3>(src, dst, cw, c);
      case 4: return reverse<4>(src, dst, cw, c);
      default: return reverse<0>(src, dst, cw, c);
    }
  }

  template <bool MI, bool MC>
  void image(const uint8_t* img, uint64_t rseed, uint8_t* scratch,
             float* out) const {
    int ch = this->ch(), cw = this->cw(), n = cw * c;
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
    // output pixel x of a mirrored row is source pixel off_w + cw-1-x: in
    // the mirrored mean image that is column w - off_w - cw + x
    const float* means = do_mirror ? mean_image_mirrored.data() : mean_image;
    int mean_off = do_mirror ? w - off_w - cw : off_w;
    for (int y = 0; y < ch; ++y) {
      int64_t line = (int64_t)(y + off_h) * w;
      const uint8_t* src = img + (line + off_w) * c;
      if (do_mirror) {
        reverse_pixels(src, scratch, cw);
        src = scratch;
      }
      row<MI, MC>(src, MI ? means + (line + mean_off) * c : nullptr,
                  mean_row.data(), scale, out + (int64_t)y * n, n);
    }
  }

  // scratch: cw*c bytes of the calling thread's own
  void operator()(const uint8_t* img, uint64_t rseed, uint8_t* scratch,
                  float* out) const {
    bool mc = !mean_row.empty();
    if (mean_image)
      mc ? image<true, true>(img, rseed, scratch, out)
         : image<true, false>(img, rseed, scratch, out);
    else
      mc ? image<false, true>(img, rseed, scratch, out)
         : image<false, false>(img, rseed, scratch, out);
  }
};

extern "C"
void sn_transform_batch(const uint8_t* in, int n, int h, int w, int c,
                        int crop, int train, int mirror_on, uint64_t seed,
                        const float* mean_image, const float* mean_channel,
                        float scale, float* out, int num_threads) {
  if (crop > h || crop > w) return;  // wrappers validate and raise first
  const Transform tf(h, w, c, crop, train, mirror_on, mean_image, mean_channel,
                     scale);
  int nt = num_threads > 0 ? num_threads : 1;
  if (nt > n) nt = n > 0 ? n : 1;
  std::vector<std::thread> ts;
  std::atomic<int> next(0);
  auto work = [&]() {
    std::vector<uint8_t> scratch((size_t)tf.cw() * c);
    for (int i = next.fetch_add(1); i < n; i = next.fetch_add(1)) {
      tf(in + i * tf.in_size(), rng_at(seed, 0xA5A5, (uint64_t)i),
         scratch.data(), out + i * tf.out_size());
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
// Prefetching loader: owns a copy of the dataset; its threads build
// shuffled, transformed batches ahead of the consumer into a bounded
// queue, all of them on one batch at a time. Batch order and contents are
// functions of (seed, epoch, batch index) only.
// ---------------------------------------------------------------------------
struct Loader {
  std::vector<uint8_t> images;
  std::vector<int32_t> labels;
  int n, batch;
  std::vector<float> mean_image;
  std::unique_ptr<Transform> tf;  // over mean_image
  uint64_t seed;
  int queue_cap;
  int64_t batches_per_epoch;
  int run;  // images a thread takes at a time: few, so the tail is short

  BufferPool* pool;
  struct Batch {
    int64_t index;
    float* data;  // one of pool's buffers
    std::vector<int32_t> labels;
    std::atomic<int> next{0};  // the next image to hand out
    std::atomic<int> left{0};  // images not yet written
    std::atomic<int64_t> build_ns{0};  // thread time inside write()
    int64_t started_ns;
  };
  // shared: a thread may still hold the batch it last worked on, to find
  // its cursor exhausted, after the consumer has taken the batch
  using BatchRef = std::shared_ptr<Batch>;
  std::mutex mu;
  std::condition_variable cv_put, cv_get;
  std::deque<BatchRef> building;  // oldest first; under mu
  std::deque<BatchRef> queue;     // built, awaiting their turn; under mu
  int64_t next_start = 0;       // the next batch to start; under mu
  int64_t next_out = 0;         // consumer expects batches in index order
  std::vector<std::thread> workers;
  std::atomic<bool> stop{false};

  // Cumulative counters, read by sn_loader_stats in this order.  Always
  // on: two clock reads a run of images beside the run's pixel work.
  enum {
    BATCHES_BUILT,     // batches whose last image was written
    BUILD_NS,          // thread time in the pixel work, all threads together
    PUT_WAIT_NS,       // thread time parked on a full window (back-pressure)
    BATCHES_TAKEN,     // batches handed to the consumer
    GET_WAIT_NS,       // consumer time until its in-order batch was ready
    COPY_NS,           // consumer time in the hand-over (the labels' copy), unlocked
    DEPTH_ON_ARRIVAL,  // sum of the queue's depth as each consumer call arrived
    BUFFERS_ALLOCATED, // batch buffers allocated; every other batch reused one
    THREADS,           // the threads building batches
    BUILD_WALL_NS,     // per batch, started to last image written, summed:
                       // BUILD_NS over it is the threads that really worked
    N_STATS
  };
  std::atomic<int64_t> stats[N_STATS] = {};

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

  // Images [j0, j1) of batch b: every element of their part of the buffer.
  void write(Batch* b, int j0, int j1, uint8_t* scratch) {
    int64_t epoch = b->index / batches_per_epoch;
    int64_t off = (b->index % batches_per_epoch) * batch;
    for (int j = j0; j < j1; ++j) {
      int64_t src;
      perm_index(epoch, off + j, &src);
      b->labels[j] = labels[src];
      (*tf)(images.data() + src * tf->in_size(),
            rng_at(seed, (uint64_t)epoch + 17, (uint64_t)(off + j)), scratch,
            b->data + j * tf->out_size());
    }
  }

  // The batch to work on: the oldest in build with an image left to hand
  // out, else the next one, started once the window admits it.  Admit by
  // index window, not queue size: the next in-order batch can then always
  // be built and enqueued, and the consumer (which pops strictly in order)
  // never waits behind later ones.  Null once stopped.
  BatchRef current() {
    std::unique_lock<std::mutex> lk(mu);
    for (;;) {
      if (stop.load()) return nullptr;
      for (const BatchRef& b : building)
        if (b->next.load() < batch) return b;
      if (next_start < next_out + queue_cap) break;
      int64_t t0 = now_ns();
      cv_put.wait(lk);
      stats[PUT_WAIT_NS] += now_ns() - t0;
    }
    BatchRef b = std::make_shared<Batch>();
    b->index = next_start++;
    bool fresh;
    b->data = pool->take(&fresh);
    if (fresh) stats[BUFFERS_ALLOCATED] += 1;
    b->labels.resize(batch);
    b->left.store(batch);
    b->started_ns = now_ns();
    building.push_back(b);
    cv_put.notify_all();  // the parked threads join in
    return b;
  }

  void worker() {
    std::vector<uint8_t> scratch((size_t)tf->cw() * tf->c);
    while (BatchRef b = current()) {
      for (;;) {
        int j0 = b->next.fetch_add(run);
        if (j0 >= batch || stop.load()) break;
        int j1 = std::min(j0 + run, batch);
        int64_t t0 = now_ns();
        write(b.get(), j0, j1, scratch.data());
        int64_t t1 = now_ns();
        b->build_ns += t1 - t0;
        if (b->left.fetch_sub(j1 - j0) != j1 - j0) continue;
        // this thread wrote the batch's last image: the batch's counters
        // move together, as the batch is done
        stats[BUILD_NS] += b->build_ns.load();
        stats[BUILD_WALL_NS] += t1 - b->started_ns;
        stats[BATCHES_BUILT] += 1;
        std::lock_guard<std::mutex> lk(mu);
        building.erase(std::find(building.begin(), building.end(), b));
        queue.push_back(b);
        cv_get.notify_all();
        break;
      }
    }
  }
};

extern "C"
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
  L->n = n; L->batch = batch; L->seed = seed;
  L->queue_cap = queue_cap > 0 ? queue_cap : 4;
  if (mean_image)
    L->mean_image.assign(mean_image, mean_image + (int64_t)h * w * c);
  L->tf.reset(new Transform(h, w, c, crop, train, mirror_on,
                            mean_image ? L->mean_image.data() : nullptr,
                            mean_channel, scale));
  L->pool = new BufferPool();
  L->pool->bytes = sizeof(float) * (size_t)batch * L->tf->out_size();
  L->batches_per_epoch = n / batch;  // drop remainder, like the apps
  int nt = num_threads > 0 ? num_threads : 2;
  L->stats[Loader::THREADS] = nt;
  // a run of about 32 K elements (tens of microseconds), and at least
  // four runs a thread so the batch's tail stays short
  int64_t by_size = (32768 + L->tf->out_size() - 1) / L->tf->out_size();
  L->run = (int)std::max<int64_t>(
      1, std::min<int64_t>(by_size, batch / (4 * nt)));
  for (int t = 0; t < nt; ++t)
    L->workers.emplace_back([L] { L->worker(); });
  return (void*)L;
}

// Blocks until the next in-order batch is ready; returns 0 on success.
// Lends the batch's own buffer: *out_data is valid, and is not rewritten,
// until sn_buffer_release(*out_pool, *out_data), which the caller makes
// exactly once, from any thread, before or after sn_loader_destroy.
extern "C"
int sn_loader_next(void* handle, float** out_data, void** out_pool,
                   int32_t* out_labels) {
  Loader* L = (Loader*)handle;
  if (!L) return -1;
  int64_t t0 = now_ns();
  std::unique_lock<std::mutex> lk(L->mu);
  L->stats[Loader::DEPTH_ON_ARRIVAL] += (int64_t)L->queue.size();
  for (;;) {
    for (size_t i = 0; i < L->queue.size(); ++i) {
      if (L->queue[i]->index == L->next_out) {
        Loader::BatchRef r = L->queue[i];
        L->queue.erase(L->queue.begin() + i);
        L->next_out++;
        lk.unlock();
        L->cv_put.notify_all();
        int64_t t1 = now_ns();
        *out_data = r->data;
        *out_pool = (void*)L->pool;
        std::memcpy(out_labels, r->labels.data(),
                    r->labels.size() * sizeof(int32_t));
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

extern "C" void sn_buffer_release(void* pool, float* data) {
  if (pool && data) ((BufferPool*)pool)->give_back(data);
}

// Copies the first min(n, N_STATS) cumulative counters (Loader's enum
// order) into out; returns how many the library has.
extern "C" int sn_loader_stats(void* handle, int64_t* out, int n) {
  Loader* L = (Loader*)handle;
  if (!L) return -1;
  for (int i = 0; i < n && i < Loader::N_STATS; ++i) out[i] = L->stats[i].load();
  return Loader::N_STATS;
}

extern "C" void sn_loader_destroy(void* handle) {
  Loader* L = (Loader*)handle;
  if (!L) return;
  {
    std::lock_guard<std::mutex> lk(L->mu);
    L->stop.store(true);
  }
  L->cv_put.notify_all();
  L->cv_get.notify_all();
  for (auto& t : L->workers) t.join();
  for (auto* held : {&L->building, &L->queue})
    for (const Loader::BatchRef& b : *held) L->pool->give_back(b->data);
  L->pool->close();
  delete L;
}

