// Native layer-commit pipeline: tar content framing + dual SHA-256 +
// deterministic gzip, one pass, no Python on the per-byte path.
//
// The reference streams layer tars through two SHA-256 digesters and
// pgzip via goroutine fan-out (lib/builder/step/common.go:35-64,
// lib/stream/multi_writer.go:25). CPython's equivalent pays interpreter
// overhead per write; this sink takes pre-rendered tar header blocks
// from Python (byte-identical PAX headers via TarInfo.tobuf) but reads
// file content, pads entries, hashes the tar stream, compresses, hashes
// the gzip stream, and writes the blob file entirely in native code.
//
// Output bytes are identical to the Python pipeline for both backends:
//   zlib-<level>        : gzip header 1f 8b 08 00 0*4 <xfl> ff + one
//                         continuous deflate stream (memLevel 8) + crc32/
//                         isize trailer, as CPython
//                         gzip.GzipFile(mtime=0, filename="").
//   pgzip-<level>-<blk> : fixed header 1f 8b 08 00 0*4 00 ff + blockwise
//                         deflate segments (Z_SYNC_FLUSH, last Z_FINISH),
//                         as native/pgzip.cpp / PgzipWriter.
//
// C ABI (ctypes):
//   lsk_new(out_fd, pgzip, level, block_size, nthreads) -> handle
//                                    (writes to a dup of out_fd, its own
//                                    until lsk_free: the caller may close
//                                    out_fd whenever it likes)
//   lsk_write(h, data, n)            raw tar bytes (headers, inline data)
//   lsk_write_file(h, path, size)    file content + 512-byte padding
//   lsk_finish(h, tar_sha32, gz_sha32, &gz_size, &tar_size)
//   lsk_compress_seconds(h)          seconds the gzip stream kept a thread busy
//   lsk_wait_seconds(h)              seconds the caller was blocked on that stream
//   lsk_free(h)                      also stops a sink that was never finished
// All int-returning calls: 0 = ok, negative = error.
//
// Threads. The caller's thread runs the tap, the tar digest and the CRC.
// The zlib backend deflates on one thread of its own behind a bounded
// ring (the fan-out of common.go:35-64, as the Python LayerSink's
// compressor thread): the caller fills fixed-size slots and only waits
// when all of them are full, so a commit costs max(gzip, producer) where
// both ran in line. The pgzip backend deflates blocks on its pool.

#include <dlfcn.h>
#include <fcntl.h>
#include <pthread.h>
#include <sys/mman.h>
#include <unistd.h>
#include <zlib.h>

#include <atomic>
#include <cerrno>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <mutex>
#include <new>
#include <thread>
#include <vector>

#include "deflate_common.h"
#include "sha256_common.h"

namespace {

using makisu_native::DeflateSlice;
using makisu_native::Digest256;
using makisu_native::GzipTrailer;

struct BlockJob {
  std::vector<uint8_t> in;
  std::vector<uint8_t> out;
  bool last = false;
  bool done = false;
  bool failed = false;
};

struct Sink {
  int fd = -1;
  bool pgzip = false;
  int level = 6;
  size_t block_size = 0;
  Digest256 tar_sha;  // uncompressed tar stream (diffID)
  Digest256 gz_sha;   // compressed blob (registry digest)
  // Optional tap: every uncompressed tar byte is also handed to this
  // callback (the TPU chunker consumes the stream for CDC while the
  // native pipeline owns framing/hashing/compression). Invoked on the
  // lsk_write/lsk_write_file caller's thread.
  void (*tap)(const uint8_t*, size_t, void*) = nullptr;
  void* tap_user = nullptr;
  uint64_t gz_size = 0;
  uint64_t tar_size = 0;
  uLong crc = 0;          // crc32 of the uncompressed stream (trailer)
  bool failed = false;
  bool zinit = false;
  // Seconds the gzip stream kept a thread busy: the zlib backend's
  // deflate with the blob's digest and write (as the Python sink's
  // compressor thread counts them), the pgzip backend's block deflates
  // summed over its lanes. Guarded by mu where workers run.
  double compress_s = 0;
  // Seconds the caller was blocked on the zlib stream: on a full ring
  // in consume, on the drain in finish. Caller's thread only.
  double wait_s = 0;

  static double since(std::chrono::steady_clock::time_point t0) {
    return std::chrono::duration<double>(
               std::chrono::steady_clock::now() - t0).count();
  }

  // zlib backend: one continuous deflate stream, run by workers[0]
  // over a ring of kSlots slots (16 MiB a sink). The caller fills slot
  // head % kSlots and publishes it (++head) when it is full or the
  // stream ends; the compressor deflates slot tail % kSlots and lets go
  // of it (++tail). head, tail, slot_len, zended and stopping are
  // guarded by mu; cv_work says "a slot or the end is there", cv_done
  // "a slot is free, or the stream failed".
  static constexpr size_t kSlotBytes = 256 * 1024;
  static constexpr size_t kSlots = 64;
  z_stream zs;
  std::vector<uint8_t> zbuf;
  // Mapped for the sink's life and given back with it: a small layer
  // touches the pages it fills, and no allocator keeps 16 MiB a thread
  // that ever committed a layer.
  uint8_t* ring = nullptr;
  uint32_t slot_len[kSlots];
  uint64_t head = 0;
  uint64_t tail = 0;
  size_t fill = 0;          // bytes in the slot being filled (caller only)
  bool slot_ours = false;   // tail has let go of that slot (caller only)
  bool zended = false;      // no slot follows: deflate Z_FINISH and leave
  // The compressor hit a deflate or write(2) error. Atomic so that every
  // lsk_write sees it, also one that touches no slot boundary.
  std::atomic<bool> zfailed{false};

  // pgzip backend: blockwise jobs compressed by a pool, written in order.
  std::vector<uint8_t> pending;
  std::deque<BlockJob*> jobs;         // submission order (writeback)
  std::deque<BlockJob*> claim_queue;  // awaiting a worker
  std::mutex mu;
  std::condition_variable cv_work, cv_done;
  std::vector<std::thread> workers;
  bool stopping = false;

  ~Sink() {
    if (!workers.empty()) {
      {
        std::lock_guard<std::mutex> lock(mu);
        stopping = true;
      }
      cv_work.notify_all();
      for (auto& t : workers) t.join();
    }
    for (auto* j : jobs) delete j;
    if (zinit) deflateEnd(&zs);
    if (ring) ::munmap(ring, kSlots * kSlotBytes);
    if (fd >= 0) ::close(fd);
  }

  bool write_fd(const uint8_t* data, size_t n) {
    gz_sha.update(data, n);
    gz_size += n;
    size_t off = 0;
    while (off < n) {
      ssize_t w = ::write(fd, data + off, n - off);
      if (w < 0) {
        if (errno == EINTR) continue;
        return false;
      }
      off += static_cast<size_t>(w);
    }
    return true;
  }

  bool write_gzip_header() {
    if (pgzip) {
      if (!write_fd(makisu_native::kPgzipHeader, 10)) return false;
    } else {
      // CPython gzip.GzipFile header: XFL reflects the level.
      uint8_t xfl = level == 9 ? 2 : (level == 1 ? 4 : 0);
      const uint8_t header[10] = {0x1f, 0x8b, 0x08, 0, 0,
                                  0,    0,    0,    xfl, 0xff};
      if (!write_fd(header, 10)) return false;
    }
    if (!pgzip) {
      std::memset(&zs, 0, sizeof(zs));
      if (deflateInit2(&zs, level, Z_DEFLATED, -15, 8,
                       Z_DEFAULT_STRATEGY) != Z_OK) {
        return false;
      }
      zinit = true;
      zbuf.resize(256 * 1024);
    }
    return true;
  }

  bool zlib_deflate(const uint8_t* data, size_t n, bool finish) {
    zs.next_in = const_cast<Bytef*>(data);
    zs.avail_in = static_cast<uInt>(n);
    for (;;) {
      zs.next_out = zbuf.data();
      zs.avail_out = static_cast<uInt>(zbuf.size());
      int rc = deflate(&zs, finish ? Z_FINISH : Z_NO_FLUSH);
      if (rc == Z_STREAM_ERROR) return false;
      size_t got = zbuf.size() - zs.avail_out;
      if (got && !write_fd(zbuf.data(), got)) return false;
      if (finish) {
        if (rc == Z_STREAM_END) return true;
        continue;  // more output pending
      }
      if (zs.avail_in == 0) return true;
    }
  }

  // The compressor thread: today's in-line deflate, slot by slot.
  void zlib_loop() {
    ::pthread_setname_np(::pthread_self(), "lsk-zlib");  // top -H, /proc
    for (;;) {
      bool fin;
      const uint8_t* data = nullptr;
      size_t n = 0;
      {
        std::unique_lock<std::mutex> lock(mu);
        cv_work.wait(lock,
                     [&] { return stopping || tail < head || zended; });
        if (stopping) return;  // freed unfinished: what is queued is dropped
        fin = tail == head;
        if (!fin) {
          data = ring + (tail % kSlots) * kSlotBytes;
          n = slot_len[tail % kSlots];
        }
      }
      auto t0 = std::chrono::steady_clock::now();
      bool ok = zlib_deflate(data, n, fin);
      double busy = since(t0);
      {
        std::lock_guard<std::mutex> lock(mu);
        compress_s += busy;
        if (!ok) zfailed = true;
        else if (!fin) ++tail;
      }
      cv_done.notify_all();
      if (!ok || fin) return;
    }
  }

  // Where the caller may put the next bytes of the stream: the rest of
  // the slot being filled. Blocks only while all slots are full; false
  // once the compressor has failed.
  bool ring_reserve(uint8_t** dst, size_t* room) {
    if (!slot_ours) {
      std::unique_lock<std::mutex> lock(mu);
      if (head - tail == kSlots && !zfailed) {
        auto t0 = std::chrono::steady_clock::now();
        cv_done.wait(lock,
                     [&] { return head - tail < kSlots || zfailed; });
        wait_s += since(t0);
      }
      if (zfailed) return false;
      slot_ours = true;
    }
    *dst = ring + (head % kSlots) * kSlotBytes + fill;
    *room = kSlotBytes - fill;
    return true;
  }

  // n more bytes stand behind ring_reserve's pointer. A full slot goes to
  // the compressor, at the stream's end (flush) also one in part.
  void ring_commit(size_t n, bool flush = false) {
    fill += n;
    if (fill < kSlotBytes && !(flush && fill)) return;
    {
      std::lock_guard<std::mutex> lock(mu);
      slot_len[head % kSlots] = static_cast<uint32_t>(fill);
      ++head;
    }
    cv_work.notify_one();
    fill = 0;
    slot_ours = false;
  }

  // Small writes (a header an entry) fill the current slot and cost no
  // hand-off each.
  bool ring_put(const uint8_t* data, size_t n) {
    while (n > 0) {
      uint8_t* dst;
      size_t room;
      if (!ring_reserve(&dst, &room)) return false;
      size_t k = n < room ? n : room;
      std::memcpy(dst, data, k);
      ring_commit(k);
      data += k;
      n -= k;
    }
    return true;
  }

  void worker_loop() {
    for (;;) {
      BlockJob* job = nullptr;
      {
        std::unique_lock<std::mutex> lock(mu);
        cv_work.wait(lock,
                     [&] { return stopping || !claim_queue.empty(); });
        if (claim_queue.empty()) return;  // stopping
        job = claim_queue.front();
        claim_queue.pop_front();
      }
      auto t0 = std::chrono::steady_clock::now();
      bool ok = DeflateSlice(job->in.data(), job->in.size(), level,
                              job->last, job->out);
      double busy = since(t0);
      {
        std::lock_guard<std::mutex> lock(mu);
        compress_s += busy;
        job->done = true;
        job->failed = !ok;
      }
      cv_done.notify_all();
    }
  }

  bool pgzip_submit(std::vector<uint8_t>&& data, bool last) {
    auto* job = new BlockJob();
    job->in = std::move(data);
    job->last = last;
    if (workers.empty()) {
      auto t0 = std::chrono::steady_clock::now();
      job->failed = !DeflateSlice(job->in.data(), job->in.size(), level,
                                   job->last, job->out);
      compress_s += since(t0);
      job->done = true;
      jobs.push_back(job);
    } else {
      {
        std::lock_guard<std::mutex> lock(mu);
        jobs.push_back(job);
        claim_queue.push_back(job);
      }
      cv_work.notify_one();
    }
    return drain(/*all=*/false);
  }

  // Write completed jobs in order; with all=true, wait for everything.
  // Without it, only pop already-done fronts, blocking solely when the
  // in-flight count exceeds the memory bound.
  bool drain(bool all) {
    size_t cap = workers.empty() ? 0 : workers.size() * 2 + 2;
    for (;;) {
      BlockJob* front = nullptr;
      {
        std::unique_lock<std::mutex> lock(mu);
        if (jobs.empty()) return true;
        if (!all && !jobs.front()->done && jobs.size() <= cap) return true;
        cv_done.wait(lock, [&] { return jobs.front()->done; });
        front = jobs.front();
        jobs.pop_front();
      }
      bool ok = !front->failed &&
                write_fd(front->out.data(), front->out.size());
      delete front;
      if (!ok) return false;
    }
  }

  // Every uncompressed tar byte is counted here exactly once, in stream
  // order, on the caller's thread (the tap is a Python callback).
  void account(const uint8_t* data, size_t n) {
    if (tap) tap(data, n, tap_user);
    tar_sha.update(data, n);
    tar_size += n;
    size_t off = 0;  // crc32 takes uInt lengths; chunk for safety
    while (off < n) {
      uInt step = static_cast<uInt>(
          (n - off) < (1u << 30) ? (n - off) : (1u << 30));
      crc = crc32(crc, data + off, step);
      off += step;
    }
  }

  bool consume(const uint8_t* data, size_t n) {
    if (failed || zfailed) return false;
    account(data, n);
    if (!pgzip) return ring_put(data, n);
    pending.insert(pending.end(), data, data + n);
    while (pending.size() >= block_size) {
      std::vector<uint8_t> blk(pending.begin(),
                               pending.begin() + block_size);
      pending.erase(pending.begin(), pending.begin() + block_size);
      if (!pgzip_submit(std::move(blk), false)) return false;
    }
    return true;
  }

  // read(2) of at most room of a file's remaining bytes: what it gave.
  static ssize_t read_some(int fd, uint8_t* dst, size_t room,
                           uint64_t remaining) {
    size_t want = remaining < room ? static_cast<size_t>(remaining) : room;
    for (;;) {
      ssize_t got = ::read(fd, dst, want);
      if (got >= 0 || errno != EINTR) return got;
    }
  }

  // One regular file's content, then its padding to 512. zlib reads
  // straight into the ring (no second copy of a file's content); pgzip
  // copies into its blocks anyway and reads through a buffer. 0, or -1
  // the sink failed, -2 the read did, -3 the file ends before `size`.
  int consume_file(int src, uint64_t size) {
    uint64_t remaining = size;
    if (pgzip) {
      static thread_local std::vector<uint8_t> scratch(256 * 1024);
      while (remaining > 0) {
        ssize_t got = read_some(src, scratch.data(), scratch.size(),
                                remaining);
        if (got <= 0) return got < 0 ? -2 : -3;
        if (!consume(scratch.data(), static_cast<size_t>(got))) return -1;
        remaining -= static_cast<uint64_t>(got);
      }
    } else {
      while (remaining > 0) {
        uint8_t* dst;
        size_t room;
        if (failed || zfailed || !ring_reserve(&dst, &room)) return -1;
        ssize_t got = read_some(src, dst, room, remaining);
        if (got <= 0) return got < 0 ? -2 : -3;
        account(dst, static_cast<size_t>(got));
        ring_commit(static_cast<size_t>(got));
        remaining -= static_cast<uint64_t>(got);
      }
    }
    static const uint8_t zeros[512] = {0};
    size_t pad = (512 - (size % 512)) % 512;
    return !pad || consume(zeros, pad) ? 0 : -1;
  }

  bool finish_stream() {
    if (pgzip) {
      if (!pgzip_submit(std::move(pending), true)) return false;
      pending.clear();
      if (!drain(/*all=*/true)) return false;
    } else {
      if (workers.empty()) return false;  // finished before
      ring_commit(0, /*flush=*/true);
      {
        std::lock_guard<std::mutex> lock(mu);
        zended = true;
      }
      cv_work.notify_one();
      auto t0 = std::chrono::steady_clock::now();
      workers[0].join();  // the drain: what the ring still held
      wait_s += since(t0);
      workers.clear();
      if (zfailed) return false;
    }
    uint8_t trailer[8];
    GzipTrailer(static_cast<uint32_t>(crc), tar_size, trailer);
    return write_fd(trailer, 8);
  }
};

}  // namespace

extern "C" {

int lsk_abi_version() { return 1; }

void* lsk_new(int out_fd, int pgzip, int level, size_t block_size,
              int nthreads) {
  if (level < 0 || level > 9 || (pgzip && block_size == 0)) return nullptr;
  auto* s = new (std::nothrow) Sink();
  if (!s) return nullptr;
  // A fd of its own: what a thread of this sink writes can never land in
  // a file that took the caller's number after the caller closed it.
  s->fd = ::fcntl(out_fd, F_DUPFD_CLOEXEC, 0);
  s->pgzip = pgzip != 0;
  s->level = level;
  s->block_size = block_size;
  if (s->fd < 0 || !s->write_gzip_header()) {
    delete s;
    return nullptr;
  }
  try {
    if (!s->pgzip) {
      void* ring = ::mmap(nullptr, Sink::kSlots * Sink::kSlotBytes,
                          PROT_READ | PROT_WRITE,
                          MAP_PRIVATE | MAP_ANONYMOUS, -1, 0);
      if (ring == MAP_FAILED) throw std::bad_alloc();
      s->ring = static_cast<uint8_t*>(ring);
      s->workers.emplace_back([s] { s->zlib_loop(); });
    } else if (nthreads > 1) {
      s->workers.reserve(nthreads);
      for (int i = 0; i < nthreads; ++i) {
        s->workers.emplace_back([s] { s->worker_loop(); });
      }
    }
  } catch (const std::exception&) {  // no memory, or no thread to be had
    delete s;
    return nullptr;
  }
  return s;
}

// Install an uncompressed-stream tap (NULL clears). Must be set before
// any write; the callback fires synchronously on the writer's thread.
void lsk_set_tap(void* handle,
                 void (*fn)(const uint8_t*, size_t, void*),
                 void* user) {
  auto* s = static_cast<Sink*>(handle);
  s->tap = fn;
  s->tap_user = user;
}

int lsk_write(void* handle, const uint8_t* data, size_t n) {
  auto* s = static_cast<Sink*>(handle);
  if (!s->consume(data, n)) {
    s->failed = true;
    return -1;
  }
  return 0;
}

// Stream one regular file's content into the tar, then its 512 padding.
// `size` is the header's size field; a file that shrank since stat is an
// error (the tar framing would be corrupt).
int lsk_write_file(void* handle, const char* path, uint64_t size) {
  auto* s = static_cast<Sink*>(handle);
  int fd = ::open(path, O_RDONLY | O_CLOEXEC);
  if (fd < 0) return -2;
  int rc = s->consume_file(fd, size);
  ::close(fd);
  if (rc == -1) s->failed = true;
  return rc;
}

int lsk_finish(void* handle, uint8_t tar_sha[32], uint8_t gz_sha[32],
               uint64_t* gz_size, uint64_t* tar_size) {
  auto* s = static_cast<Sink*>(handle);
  if (s->failed || !s->finish_stream()) return -1;
  s->tar_sha.final(tar_sha);
  s->gz_sha.final(gz_sha);
  *gz_size = s->gz_size;
  *tar_size = s->tar_size;
  return 0;
}

// After lsk_finish (every lane is idle then).
double lsk_compress_seconds(void* handle) {
  auto* s = static_cast<Sink*>(handle);
  std::lock_guard<std::mutex> lock(s->mu);
  return s->compress_s;
}

// After lsk_finish: seconds the caller was blocked on the zlib stream (a
// full ring in lsk_write / lsk_write_file, the drain in lsk_finish). Near
// 0 where the producer is the brake, near the stream's seconds less the
// producer's own where gzip is. 0 for pgzip.
double lsk_wait_seconds(void* handle) {
  return static_cast<Sink*>(handle)->wait_s;
}

// Also for a sink that was never finished (a build that died between
// two entries): the compressor thread is stopped and joined.
void lsk_free(void* handle) { delete static_cast<Sink*>(handle); }

}  // extern "C"
