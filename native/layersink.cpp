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
//   lsk_write_entries(h, n, headers, header_offsets, paths, sizes,
//                     &failed_index) a batch of entries in stream order:
//                                    each entry's rendered header, then
//                                    for a path of size > 0 its content
//                                    and its padding to 512; the batch's
//                                    files of up to 8 MiB are read ahead
//                                    on the sink's reader threads
//   lsk_finish(h, tar_sha32, gz_sha32, &gz_size, &tar_size)
//   lsk_compress_seconds(h)          seconds the gzip stream kept a thread busy,
//                                    summed over the pgzip lanes
//   lsk_wall_seconds(h)              seconds the stream had a block queued or
//                                    deflating (zlib: lsk_compress_seconds)
//   lsk_wait_seconds(h)              seconds the caller was blocked on that stream
//   lsk_blob_write_seconds(h)        seconds the caller digested and wrote
//                                    compressed blocks itself (pgzip; zlib: 0)
//   lsk_prefetch_stats(h, &read_wait_s, counts[3])
//                                    seconds the caller was blocked on a
//                                    reader; files it found ready, waited
//                                    for, streamed itself
//   lsk_free(h)                      also stops a sink that was never finished
// All int-returning calls: 0 = ok, negative = error.
//
// Threads. The caller's thread runs the tap, the tar digest and the CRC.
// The tap is a Python callback, and every call of it hands the
// interpreter lock over: it gets the stream through a staging buffer,
// once a filled 256 KiB and once at the end of every extern call, so
// each call returns with the tap having seen all it wrote.
// The zlib backend deflates on one thread of its own behind a bounded
// ring (the fan-out of common.go:35-64, as the Python LayerSink's
// compressor thread): the caller fills fixed-size slots and only waits
// when all of them are full, so a commit costs max(gzip, producer) where
// both ran in line. The pgzip backend deflates blocks on its pool
// (nthreads lanes; one lane deflates in line); the caller takes the done
// blocks in order, digests and writes them itself, and waits for the pool
// only with more than 2 * lanes + 2 blocks in flight and at the end.
// lsk_write_entries is what tells the sink of files before it must write
// them: at a batch's start its files of up to 8 MiB go to the sink's
// reader threads ("lsk-read", at most kReaders a sink, started by the
// first batch that has two such files, joined by lsk_finish or
// lsk_free), which open, read to the header's size and
// close each, in entry order, into a second ring of 16 MiB; the caller
// takes them in order and waits only for the one it needs. A larger
// file, a batch with fewer than two, or a sink that could start no
// reader is opened, streamed and closed on the caller's thread.

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
  // lsk_write/lsk_write_entries caller's thread.
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
  // Seconds the caller was blocked on the gzip stream. zlib: on a full
  // ring in consume, on the drain in finish. pgzip: in drain(), on the
  // oldest block in flight, over the cap and at the end. Caller's thread
  // only.
  double wait_s = 0;
  // pgzip: seconds during which the stream had at least one block queued
  // or deflating (a lane's busy seconds are compress_s; with several
  // lanes at once this is their union, not their sum). inflight counts
  // the blocks submitted and not yet deflated; both guarded by mu.
  double wall_s = 0;
  size_t inflight = 0;
  std::chrono::steady_clock::time_point wall_t0;
  // pgzip: seconds the caller spent in write_fd under drain(): the
  // blob's digest and write(2), which the zlib backend's compressor
  // thread does. Caller's thread only.
  double blob_write_s = 0;

  // The tap's staging buffer (made by lsk_set_tap): account() fills it
  // and hands it over full, tap_flush() in part.
  static constexpr size_t kTapBytes = 256 * 1024;
  std::vector<uint8_t> tap_buf;
  size_t tap_fill = 0;

  // What lsk_prefetch_stats reports; caller's thread only. read_wait_s:
  // seconds blocked on a reader that had not finished the next file.
  // Regular files with content by how their bytes came: a reader had
  // them ready; the caller waited for a reader; the caller streamed
  // them itself.
  double read_wait_s = 0;
  uint64_t files_ready = 0;
  uint64_t files_waited = 0;
  uint64_t files_streamed = 0;

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

  // Read-ahead for lsk_write_entries. rjobs holds the current batch's
  // files for the readers, in entry order. A reader claims rjobs[rnext]
  // once the read ring has room for the whole file, reads it, marks it
  // done; the caller takes the jobs in the same order and gives their
  // room back. The ring is cut and freed in entry order, so ralloc and
  // rfree are positions in an endless stream that lies at pos % kReadRing;
  // a file that would straddle the ring's end starts at 0 and the tail it
  // skipped is freed with it. With every earlier job given back the ring
  // is empty and any file of kReadAheadMax fits, so the caller never
  // waits for a job no reader can claim. All of it is guarded by rmu;
  // rcv_job says "a job may be claimable, or stop", rcv_done "a job is
  // done".
  static constexpr int kReaders = 4;
  static constexpr uint64_t kReadAheadMax = 8 * 1024 * 1024;
  static constexpr size_t kReadRing = 16 * 1024 * 1024;
  struct ReadJob {
    const char* path;   // the caller's, for the length of its call
    uint64_t size;
    size_t off = 0;     // where in rring
    uint64_t span = 0;  // ring positions taken: size and a skipped tail
    bool done = false;
    int rc = 0;         // as consume_file: -2 unreadable, -3 too short
  };
  uint8_t* rring = nullptr;
  std::vector<ReadJob> rjobs;
  size_t rnext = 0;     // the next job a reader may claim
  size_t rreading = 0;  // jobs claimed and not done
  uint64_t ralloc = 0;
  uint64_t rfree = 0;
  bool rstop = false;
  std::mutex rmu;
  std::condition_variable rcv_job, rcv_done;
  std::vector<std::thread> readers;

  ~Sink() {
    stop_readers();
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
    if (rring) ::munmap(rring, kReadRing);
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
        if (--inflight == 0) wall_s += since(wall_t0);
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
      double busy = since(t0);
      compress_s += busy;
      wall_s += busy;
      job->done = true;
      jobs.push_back(job);
    } else {
      {
        std::lock_guard<std::mutex> lock(mu);
        if (inflight++ == 0) wall_t0 = std::chrono::steady_clock::now();
        jobs.push_back(job);
        claim_queue.push_back(job);
      }
      cv_work.notify_one();
    }
    return drain(/*all=*/false);
  }

  // Write completed jobs in order; with all=true, wait for everything.
  // Without it, only pop already-done fronts, blocking solely when the
  // in-flight count exceeds the memory bound. What the caller waits for
  // the pool is wait_s, what it digests and writes is blob_write_s.
  bool drain(bool all) {
    size_t cap = workers.empty() ? 0 : workers.size() * 2 + 2;
    for (;;) {
      BlockJob* front = nullptr;
      {
        std::unique_lock<std::mutex> lock(mu);
        if (jobs.empty()) return true;
        if (!jobs.front()->done) {
          if (!all && jobs.size() <= cap) return true;
          auto t0 = std::chrono::steady_clock::now();
          cv_done.wait(lock, [&] { return jobs.front()->done; });
          wait_s += since(t0);
        }
        front = jobs.front();
        jobs.pop_front();
      }
      auto t0 = std::chrono::steady_clock::now();
      bool ok = !front->failed &&
                write_fd(front->out.data(), front->out.size());
      blob_write_s += since(t0);
      delete front;
      if (!ok) return false;
    }
  }

  void tap_flush() {
    if (tap && tap_fill) tap(tap_buf.data(), tap_fill, tap_user);
    tap_fill = 0;
  }

  // Every uncompressed tar byte is counted here exactly once, in stream
  // order, on the caller's thread (the tap gets it through tap_buf).
  void account(const uint8_t* data, size_t n) {
    for (size_t off = 0; tap && off < n;) {
      size_t room = kTapBytes - tap_fill;
      size_t k = n - off < room ? n - off : room;
      std::memcpy(tap_buf.data() + tap_fill, data + off, k);
      tap_fill += k;
      off += k;
      if (tap_fill == kTapBytes) tap_flush();
    }
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
    while (n > 0) {  // a file read ahead arrives whole: block by block
      size_t room = block_size - pending.size();
      size_t k = n < room ? n : room;
      pending.insert(pending.end(), data, data + k);
      data += k;
      n -= k;
      if (pending.size() == block_size) {
        if (!pgzip_submit(std::move(pending), false)) return false;
        pending.clear();
      }
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
    return pad_to_block(size) ? 0 : -1;
  }

  bool pad_to_block(uint64_t size) {
    static const uint8_t zeros[512] = {0};
    size_t pad = (512 - (size % 512)) % 512;
    return !pad || consume(zeros, pad);
  }

  // One file opened, streamed and closed by the caller itself.
  int file_streamed(const char* path, uint64_t size) {
    int src = ::open(path, O_RDONLY | O_CLOEXEC);
    if (src < 0) return -2;
    ++files_streamed;
    int rc = consume_file(src, size);
    ::close(src);
    return rc;
  }

  // A reader's whole job: the file's first `size` bytes into dst.
  static int read_whole(const char* path, uint8_t* dst, uint64_t size) {
    int src = ::open(path, O_RDONLY | O_CLOEXEC);
    if (src < 0) return -2;
    int rc = 0;
    for (uint64_t have = 0; have < size && rc == 0;) {
      ssize_t got = read_some(src, dst + have, size - have, size - have);
      if (got <= 0) rc = got < 0 ? -2 : -3;
      else have += static_cast<uint64_t>(got);
    }
    ::close(src);
    return rc;
  }

  // With rmu held: whether the read ring has room for the next job, and
  // where.
  bool ring_fits(ReadJob& job) {
    size_t at = ralloc % kReadRing;
    uint64_t skip = at + job.size > kReadRing ? kReadRing - at : 0;
    if (ralloc + skip + job.size - rfree > kReadRing) return false;
    job.off = skip ? 0 : at;
    job.span = skip + job.size;
    return true;
  }

  void reader_loop() {
    ::pthread_setname_np(::pthread_self(), "lsk-read");
    std::unique_lock<std::mutex> lock(rmu);
    for (;;) {
      rcv_job.wait(lock, [&] {
        return rstop || (rnext < rjobs.size() && ring_fits(rjobs[rnext]));
      });
      if (rstop) return;
      ReadJob& job = rjobs[rnext++];
      ralloc += job.span;
      ++rreading;
      lock.unlock();
      rcv_job.notify_one();  // the room left may do for the next job too
      int rc = read_whole(job.path, rring + job.off, job.size);
      lock.lock();
      job.rc = rc;
      job.done = true;
      --rreading;
      rcv_done.notify_all();
    }
  }

  // This sink's reader threads (per sink, with no cap across a process:
  // 16 sinks at once read alike with one of 16 and with none, PERF.md
  // section 6, PR 40). false: none to be had, the caller streams every
  // file.
  bool start_readers() {
    if (!readers.empty()) return true;
    if (!rring) {
      void* m = ::mmap(nullptr, kReadRing, PROT_READ | PROT_WRITE,
                       MAP_PRIVATE | MAP_ANONYMOUS, -1, 0);
      if (m == MAP_FAILED) return false;
      rring = static_cast<uint8_t*>(m);
    }
    rstop = false;  // none is running: stop_readers joined them all
    try {
      while (static_cast<int>(readers.size()) < kReaders) {
        readers.emplace_back([this] { reader_loop(); });
      }
    } catch (const std::exception&) {  // no thread to be had
    }
    return !readers.empty();
  }

  void stop_readers() {
    if (readers.empty()) return;
    {
      std::lock_guard<std::mutex> lock(rmu);
      rstop = true;
    }
    rcv_job.notify_all();
    for (auto& t : readers) t.join();
    readers.clear();
  }

  // The batch is over, well or not: no reader touches its paths again.
  void end_batch() {
    std::unique_lock<std::mutex> lock(rmu);
    rnext = rjobs.size();
    rcv_done.wait(lock, [&] { return rreading == 0; });
    rjobs.clear();
    rnext = 0;
    ralloc = rfree = 0;
  }

  // The batch's j-th file for the readers: its content and padding into
  // the stream, its room in the read ring given back. Blocks while a
  // reader has it in hand.
  int file_from_reader(size_t j) {
    const uint8_t* data;
    uint64_t size;
    int rc;
    {
      std::unique_lock<std::mutex> lock(rmu);
      ReadJob& job = rjobs[j];
      if (job.done) {
        ++files_ready;
      } else {
        ++files_waited;
        auto t0 = std::chrono::steady_clock::now();
        rcv_done.wait(lock, [&] { return job.done; });
        read_wait_s += since(t0);
      }
      data = rring + job.off;
      size = job.size;
      rc = job.rc;
    }
    if (rc == 0 && !(consume(data, size) && pad_to_block(size))) rc = -1;
    {
      std::lock_guard<std::mutex> lock(rmu);
      rfree += rjobs[j].span;
    }
    rcv_job.notify_one();
    return rc;
  }

  // lsk_write_entries. The rc of consume_file, and the index of the
  // entry at fault in *failed_index.
  int write_entries(size_t n, const uint8_t* headers,
                    const uint64_t* header_offsets,
                    const char* const* paths, const uint64_t* sizes,
                    int64_t* failed_index) {
    auto for_readers = [&](size_t i) {
      return paths[i] && sizes[i] > 0 && sizes[i] <= kReadAheadMax;
    };
    size_t eligible = 0;
    for (size_t i = 0; i < n; ++i) eligible += for_readers(i);
    bool ahead = eligible >= 2 && start_readers();
    if (ahead) {
      {
        std::lock_guard<std::mutex> lock(rmu);
        rjobs.reserve(eligible);
        for (size_t i = 0; i < n; ++i) {
          if (for_readers(i)) rjobs.push_back(ReadJob{paths[i], sizes[i]});
        }
      }
      rcv_job.notify_all();
    }
    int rc = 0;
    for (size_t i = 0, j = 0; i < n && rc == 0; ++i) {
      if (!consume(headers + header_offsets[i],
                   header_offsets[i + 1] - header_offsets[i])) {
        rc = -1;
      } else if (paths[i] && sizes[i] > 0) {
        rc = ahead && for_readers(i) ? file_from_reader(j++)
                                     : file_streamed(paths[i], sizes[i]);
      }
      if (rc != 0) *failed_index = static_cast<int64_t>(i);
    }
    if (ahead) end_batch();
    tap_flush();
    if (rc != 0) failed = true;  // the stream ends in the middle of an entry
    return rc;
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

int lsk_abi_version() { return 3; }

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
  if (fn) s->tap_buf.resize(Sink::kTapBytes);
}

int lsk_write(void* handle, const uint8_t* data, size_t n) {
  auto* s = static_cast<Sink*>(handle);
  bool ok = s->consume(data, n);
  s->tap_flush();
  if (!ok) s->failed = true;
  return ok ? 0 : -1;
}

// A batch of n entries in stream order, in one call. Entry i's rendered
// header is headers[header_offsets[i] .. header_offsets[i + 1]); where
// paths[i] is not NULL and sizes[i] > 0 the file's first sizes[i] bytes
// and the padding to 512 follow; `sizes[i]` is the header's size field,
// and a file that shrank since stat is an error (the tar framing would be
// corrupt). 0, or -1 the sink failed, -2 a file could not be read, -3 it
// ends before its size, with the index of the entry at fault in
// *failed_index: nothing of a later entry has reached the stream, and the
// sink stays failed.
int lsk_write_entries(void* handle, size_t n, const uint8_t* headers,
                      const uint64_t* header_offsets,
                      const char* const* paths, const uint64_t* sizes,
                      int64_t* failed_index) {
  return static_cast<Sink*>(handle)->write_entries(
      n, headers, header_offsets, paths, sizes, failed_index);
}

int lsk_finish(void* handle, uint8_t tar_sha[32], uint8_t gz_sha[32],
               uint64_t* gz_size, uint64_t* tar_size) {
  auto* s = static_cast<Sink*>(handle);
  s->stop_readers();
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

// After lsk_finish: seconds the stream had a block queued or deflating.
// pgzip: the wall time of the pool's work, where lsk_compress_seconds is
// its CPU time; zlib: the one compressor thread's busy seconds.
double lsk_wall_seconds(void* handle) {
  auto* s = static_cast<Sink*>(handle);
  std::lock_guard<std::mutex> lock(s->mu);
  return s->pgzip ? s->wall_s : s->compress_s;
}

// After lsk_finish: seconds the caller was blocked on the gzip stream.
// zlib: a full ring in lsk_write / lsk_write_entries, the drain in
// lsk_finish. pgzip: the oldest block in flight not yet deflated, with
// more than 2 * lanes + 2 in flight or in lsk_finish. Near 0 where the
// producer is the brake, near the stream's seconds less the producer's own
// where gzip is.
double lsk_wait_seconds(void* handle) {
  return static_cast<Sink*>(handle)->wait_s;
}

// After lsk_finish: seconds the caller itself digested and wrote
// compressed blocks (pgzip: write_fd under drain, in lsk_write,
// lsk_write_entries and lsk_finish). 0 for zlib, whose compressor thread
// does both.
double lsk_blob_write_seconds(void* handle) {
  return static_cast<Sink*>(handle)->blob_write_s;
}

// Any time, on the caller's thread. read_wait_s: seconds the caller was
// blocked on a reader that had not finished the file it needed next.
// counts: regular files with content that a reader had ready, that the
// caller waited for, that the caller streamed itself (over 8 MiB, a batch
// with fewer than two files for the readers, no thread to be had).
void lsk_prefetch_stats(void* handle, double* read_wait_s,
                        uint64_t counts[3]) {
  auto* s = static_cast<Sink*>(handle);
  *read_wait_s = s->read_wait_s;
  counts[0] = s->files_ready;
  counts[1] = s->files_waited;
  counts[2] = s->files_streamed;
}

// Also for a sink that was never finished (a build that died between
// two entries): the compressor and reader threads are stopped and joined.
void lsk_free(void* handle) { delete static_cast<Sink*>(handle); }

}  // extern "C"
