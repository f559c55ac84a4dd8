// Where each watched thread of this process is, asked of the kernel by a
// thread that never touches the interpreter.
//
// A Python thread that looks at its siblings takes the interpreter lock
// to look, and its own release of the lock wakes the very waiter it is
// about to read: from inside the process every thread then looks the
// same. The kernel knows (/proc/self/task/<tid>/syscall, schedstat), a
// thread group may read its own members, and this reader holds no lock
// the program knows of.
//
// One thread (`tsk-reader`, started by the first tsk_watch, parked on a
// condition while no slot is watched) wakes every BEAT_NS and, for each
// watched slot, preads both files and adds to the slot's row of a table
// of doubles that the caller maps once and reads as memory:
//
//   tid        the watched thread (0: the slot is free; a thread that
//              is gone frees its slot at the first read that fails)
//   run        exact, from schedstat: seconds on a CPU since the watch
//   runqueue   exact, from schedstat: seconds runnable with no CPU
//   system     from `stat` where that is the source: the part of run
//              spent in the kernel (stime), which on a sandboxed kernel
//              that handles a file-system call on the caller's own
//              thread is where the mount's seconds are; else 0
//   then the sampled states, each sample worth the seconds since the
//   slot's last one (a late beat is not undercounted):
//   running    `running`: on a CPU or waiting for one
//   interpreter_lock  futex on a word within LOCK_SPAN bytes of the
//              reference address tsk_calibrate found
//   wait       any other futex, poll/select/epoll, wait4/waitid,
//              nanosleep: a wait the program wrote
//   fs         blocked in a path, directory, descriptor or data call
//   socket     send*/recv*/accept*/connect
//   other      ioctl, mmap, a page fault (-1), anything unlisted
//
// Source. `syscall` (x86-64 numbers, compiled in) where it opens and
// reads; else the state letter of `stat` (R running, D fs: a 9p request
// waits killable and shows as D; anything else other; run is then
// utime + stime in clock ticks, system its stime, and runqueue stays 0
// where there is no schedstat); else none. The
// vitals row says which, with the reference address, its sightings and
// what the reader itself cost.
//
// C ABI (ctypes):
//   tsk_abi_version()
//   tsk_table() -> double[tsk_slots() * 10], a row as enum Col below
//   tsk_vitals() -> double[TSK_VITALS]: source (2 syscall, 1 stat,
//        0 none), schedstat readable (1/0), reference address (0 unset),
//        sightings, beats, reads, seconds busy in beats
//   tsk_probe()               decide the source on the caller's own tid
//   tsk_calibrate(tid_a, tid_b, ms) -> sightings; samples the two every
//        millisecond on the caller's thread and keeps the futex address
//        it saw them blocked on most often as the reference (unset
//        under MIN_SIGHTINGS)
//   tsk_watch(tid) -> slot or -1 (table full); tsk_unwatch(slot, tid)
//   tsk_test_refuse(mask)     tests: 1 `syscall`, 2 `schedstat` answer EPERM

#include <cerrno>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <ctime>

#include <fcntl.h>
#include <pthread.h>
#include <sys/syscall.h>
#include <unistd.h>

namespace {

constexpr int SLOTS = 64;
constexpr long BEAT_NS = 10 * 1000 * 1000;
constexpr uint64_t LOCK_SPAN = 256;
constexpr int MIN_SIGHTINGS = 5;

enum Col { C_TID, C_RUN, C_RUNQUEUE, C_SYSTEM, C_RUNNING, C_LOCK, C_WAIT, C_FS,
           C_SOCKET, C_OTHER, COLS };
enum Vital { V_SOURCE, V_SCHED, V_LOCK_REF, V_SIGHTINGS, V_BEATS, V_READS,
             V_BUSY_S, VITALS };
enum Source { SRC_NONE = 0, SRC_STAT = 1, SRC_SYSCALL = 2 };

double g_table[SLOTS * COLS];
double g_vitals[VITALS];

struct Slot {
    int tid = 0;          // 0: free
    bool opened = false;  // the reader opens at its next beat
    int source = SRC_NONE;
    int fd_state = -1;    // syscall or stat
    int fd_sched = -1;    // schedstat, -1 where it is not there
    double t_last = 0.0;
    double run0 = 0.0, runqueue0 = 0.0, system0 = 0.0;
};

Slot g_slots[SLOTS];
int g_watched = 0;
bool g_reader_started = false;
int g_refuse = 0;  // tests: 1 `syscall`, 2 `schedstat` cannot be opened
uint64_t g_lock_ref = 0;
pthread_mutex_t g_mu = PTHREAD_MUTEX_INITIALIZER;
pthread_cond_t g_cv = PTHREAD_COND_INITIALIZER;

double now_s() {
    timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return ts.tv_sec + ts.tv_nsec * 1e-9;
}

int open_task_file(int tid, const char* name) {
#if !defined(__x86_64__)
    if (!strcmp(name, "syscall")) { errno = ENOENT; return -1; }
#endif
    if ((g_refuse & 1 && !strcmp(name, "syscall"))
        || (g_refuse & 2 && !strcmp(name, "schedstat"))) {
        errno = EPERM;
        return -1;
    }
    char path[64];
    snprintf(path, sizeof path, "/proc/self/task/%d/%s", tid, name);
    return open(path, O_RDONLY | O_CLOEXEC);
}

// One pread from the file's start, NUL-terminated; -1 where it failed
// or the file was empty.
ssize_t read_small(int fd, char* buf, size_t cap) {
    ssize_t n = pread(fd, buf, cap - 1, 0);
    if (n <= 0) return -1;
    buf[n] = 0;
    g_vitals[V_READS] += 1;
    return n;
}

// x86-64 system call numbers, by what a thread blocked in one is
// waiting for.
Col classify_syscall(long nr, uint64_t arg0) {
    switch (nr) {
    case 202:  // futex
        if (g_lock_ref && arg0 + LOCK_SPAN > g_lock_ref
            && arg0 < g_lock_ref + LOCK_SPAN)
            return C_LOCK;
        return C_WAIT;
    case 449:                               // futex_waitv
    case 7: case 271: case 23: case 270:    // poll ppoll select pselect6
    case 232: case 281: case 441:           // epoll_wait, _pwait, _pwait2
    case 61: case 247:                      // wait4 waitid
    case 35: case 230: case 34: case 128:   // nanosleep clock_nanosleep
                                            // pause rt_sigtimedwait
        return C_WAIT;
    case 0: case 1: case 17: case 18: case 19: case 20:  // read write
    case 295: case 296: case 327: case 328:  // p{read,write}{64,v,v2}
    case 2: case 85: case 257: case 437: case 3:  // open* creat close
    case 4: case 5: case 6: case 262: case 332:   // the stat family
    case 21: case 269: case 439:            // access faccessat{,2}
    case 78: case 217: case 8:              // getdents{,64} lseek
    case 40: case 326: case 275: case 276:  // sendfile copy_file_range
                                            // splice tee
    case 87: case 263: case 82: case 264: case 316:  // unlink* rename*
    case 83: case 258: case 84: case 133: case 259:  // mkdir* rmdir mknod*
    case 86: case 265: case 88: case 266: case 89: case 267:  // link*
                                            // symlink* readlink*
    case 90: case 91: case 268: case 452:   // chmod fchmod fchmodat{,2}
    case 92: case 93: case 94: case 260:    // chown fchown lchown fchownat
    case 132: case 235: case 261: case 280:  // utime utimes futimesat
                                            // utimensat
    case 74: case 75: case 162: case 306: case 277:  // fsync fdatasync
                                            // sync syncfs sync_file_range
    case 76: case 77: case 285: case 73:    // truncate ftruncate
                                            // fallocate flock
        return C_FS;
    case 42: case 43: case 288:             // connect accept accept4
    case 44: case 45: case 46: case 47:     // sendto recvfrom sendmsg
                                            // recvmsg
    case 299: case 307:                     // recvmmsg sendmmsg
        return C_SOCKET;
    default:
        return C_OTHER;
    }
}

// The state of one line of `syscall`; *futex_addr is the futex's word
// where the thread is blocked in one, else 0.
Col classify_syscall_line(const char* line, uint64_t* futex_addr) {
    *futex_addr = 0;
    if (!strncmp(line, "running", 7)) return C_RUNNING;
    char* end = nullptr;
    long nr = strtol(line, &end, 10);
    uint64_t arg0 = end && *end ? strtoull(end, nullptr, 16) : 0;
    if (nr == 202) *futex_addr = arg0;
    return classify_syscall(nr, nr == 202 ? arg0 : 0);
}

// After "pid (comm) ": the letter, utime + stime and stime in seconds.
bool parse_stat(const char* text, char* letter, double* cpu_s,
                double* system_s) {
    const char* p = strrchr(text, ')');
    if (!p || !p[1] || !p[2]) return false;
    *letter = p[2];
    p += 3;
    // Fields 4..13 lie between the letter and utime.
    for (int skipped = 0; skipped < 10; ++skipped) {
        while (*p == ' ') ++p;
        while (*p && *p != ' ') ++p;
    }
    unsigned long long utime = 0, stime = 0;
    if (sscanf(p, "%llu %llu", &utime, &stime) != 2) return false;
    static const double tick = 1.0 / sysconf(_SC_CLK_TCK);
    *cpu_s = (utime + stime) * tick;
    *system_s = stime * tick;
    return true;
}

void close_slot(Slot& s) {
    if (s.fd_state >= 0) close(s.fd_state);
    if (s.fd_sched >= 0) close(s.fd_sched);
    s = Slot();
}

// With g_mu held. Frees the slot (and the row's tid) for good.
void free_slot(int i) {
    if (!g_slots[i].tid) return;
    close_slot(g_slots[i]);
    g_table[i * COLS + C_TID] = 0;
    --g_watched;
}

// Opens a thread's files: `syscall` where it may be read, else `stat`.
bool open_slot(Slot& s) {
    s.source = SRC_SYSCALL;
    s.fd_state = open_task_file(s.tid, "syscall");
    if (s.fd_state >= 0) {
        // The permission to read is checked at the read, not the open.
        char buf[256];
        if (pread(s.fd_state, buf, sizeof buf, 0) < 0
            && (errno == EPERM || errno == EACCES || errno == ENOSYS)) {
            close(s.fd_state);
            s.fd_state = -1;
        }
    }
    if (s.fd_state < 0) {
        s.source = SRC_STAT;
        s.fd_state = open_task_file(s.tid, "stat");
    }
    if (s.fd_state < 0) {
        s.source = SRC_NONE;
        return false;
    }
    s.fd_sched = open_task_file(s.tid, "schedstat");
    s.opened = true;
    return true;
}

bool read_sched(const Slot& s, double* run, double* runqueue) {
    char buf[128];
    if (s.fd_sched < 0 || read_small(s.fd_sched, buf, sizeof buf) < 0)
        return false;
    unsigned long long run_ns = 0, wait_ns = 0;
    if (sscanf(buf, "%llu %llu", &run_ns, &wait_ns) != 2) return false;
    *run = run_ns * 1e-9;
    *runqueue = wait_ns * 1e-9;
    return true;
}

void note_source(const Slot& s) {
    g_vitals[V_SOURCE] = s.source;
    g_vitals[V_SCHED] = s.fd_sched >= 0 ? 1 : 0;
}

// One beat of one slot, with g_mu held; false where the thread is gone.
bool sample_slot(int i) {
    Slot& s = g_slots[i];
    double* row = g_table + i * COLS;
    const bool first = !s.opened;
    if (first) {
        if (!open_slot(s)) return false;
        note_source(s);
    }
    char buf[512];
    if (read_small(s.fd_state, buf, sizeof buf) < 0) return false;
    const double now = now_s();
    Col state = C_OTHER;
    double run = 0.0, runqueue = 0.0, system = 0.0;
    if (s.source == SRC_SYSCALL) {
        uint64_t addr;
        state = classify_syscall_line(buf, &addr);
        if (s.fd_sched >= 0 && !read_sched(s, &run, &runqueue))
            return false;
    } else {
        char letter = 'S';
        double cpu_s = 0.0;
        if (!parse_stat(buf, &letter, &cpu_s, &system)) return false;
        if (letter == 'Z' || letter == 'X') return false;
        state = letter == 'R' ? C_RUNNING : letter == 'D' ? C_FS : C_OTHER;
        run = cpu_s;
        if (s.fd_sched >= 0 && !read_sched(s, &run, &runqueue))
            return false;
    }
    if (first) {
        s.run0 = run;
        s.runqueue0 = runqueue;
        s.system0 = system;
    } else {
        row[state] += now - s.t_last;
        row[C_RUN] = run - s.run0;
        row[C_RUNQUEUE] = runqueue - s.runqueue0;
        row[C_SYSTEM] = system - s.system0;
    }
    s.t_last = now;
    return true;
}

void* reader_main(void*) {
#if defined(__linux__)
    pthread_setname_np(pthread_self(), "tsk-reader");
#endif
    timespec next;
    clock_gettime(CLOCK_MONOTONIC, &next);
    for (;;) {
        pthread_mutex_lock(&g_mu);
        bool parked = false;
        while (g_watched == 0) {
            parked = true;
            pthread_cond_wait(&g_cv, &g_mu);
        }
        const double t0 = now_s();
        for (int i = 0; i < SLOTS; ++i)
            if (g_slots[i].tid && !sample_slot(i)) free_slot(i);
        g_vitals[V_BEATS] += 1;
        g_vitals[V_BUSY_S] += now_s() - t0;
        pthread_mutex_unlock(&g_mu);
        if (parked) clock_gettime(CLOCK_MONOTONIC, &next);
        next.tv_nsec += BEAT_NS;
        if (next.tv_nsec >= 1000000000L) {
            next.tv_nsec -= 1000000000L;
            next.tv_sec += 1;
        }
        timespec now;
        clock_gettime(CLOCK_MONOTONIC, &now);
        if (now.tv_sec > next.tv_sec
            || (now.tv_sec == next.tv_sec && now.tv_nsec > next.tv_nsec))
            next = now;  // a beat ran long: no burst to catch up
        else
            clock_nanosleep(CLOCK_MONOTONIC, TIMER_ABSTIME, &next, nullptr);
    }
    return nullptr;
}

}  // namespace

extern "C" {

int tsk_abi_version() { return 1; }
double* tsk_table() { return g_table; }
double* tsk_vitals() { return g_vitals; }
int tsk_slots() { return SLOTS; }

void tsk_probe() {
    Slot s;
    s.tid = static_cast<int>(syscall(SYS_gettid));
    pthread_mutex_lock(&g_mu);
    open_slot(s);
    note_source(s);
    close_slot(s);
    pthread_mutex_unlock(&g_mu);
}

int tsk_calibrate(int tid_a, int tid_b, int ms) {
    struct Seen { uint64_t addr; int n; };
    Seen seen[16] = {};
    int fds[2] = {open_task_file(tid_a, "syscall"),
                  open_task_file(tid_b, "syscall")};
    const double until = now_s() + ms * 1e-3;
    const timespec nap = {0, 1000000};
    char buf[256];
    while (fds[0] >= 0 && fds[1] >= 0 && now_s() < until) {
        for (int fd : fds) {
            if (read_small(fd, buf, sizeof buf) < 0) continue;
            uint64_t addr;
            classify_syscall_line(buf, &addr);
            if (!addr) continue;
            for (Seen& e : seen) {
                if (e.addr == addr || !e.n) {
                    e.addr = addr;
                    ++e.n;
                    break;
                }
            }
        }
        nanosleep(&nap, nullptr);
    }
    for (int fd : fds)
        if (fd >= 0) close(fd);
    Seen best = {};
    for (const Seen& e : seen)
        if (e.n > best.n) best = e;
    // The lock's condition, mutex and hand-over pair lie in one struct:
    // what was seen near the commonest word is the lock too.
    int sightings = 0;
    for (const Seen& e : seen)
        if (best.n && e.addr + LOCK_SPAN > best.addr
            && e.addr < best.addr + LOCK_SPAN)
            sightings += e.n;
    pthread_mutex_lock(&g_mu);
    g_lock_ref = sightings >= MIN_SIGHTINGS ? best.addr : 0;
    g_vitals[V_LOCK_REF] = static_cast<double>(g_lock_ref);
    g_vitals[V_SIGHTINGS] = sightings;
    pthread_mutex_unlock(&g_mu);
    return sightings;
}

int tsk_watch(int tid) {
    if (tid <= 0) return -1;
    pthread_mutex_lock(&g_mu);
    int slot = -1;
    for (int i = 0; i < SLOTS && slot < 0; ++i)
        if (!g_slots[i].tid) slot = i;
    if (slot >= 0) {
        if (!g_reader_started) {
            pthread_t t;
            pthread_attr_t attr;
            pthread_attr_init(&attr);
            pthread_attr_setdetachstate(&attr, PTHREAD_CREATE_DETACHED);
            g_reader_started =
                pthread_create(&t, &attr, reader_main, nullptr) == 0;
            pthread_attr_destroy(&attr);
        }
        if (!g_reader_started) {
            slot = -1;
        } else {
            g_slots[slot].tid = tid;
            memset(g_table + slot * COLS, 0, COLS * sizeof(double));
            g_table[slot * COLS + C_TID] = tid;
            ++g_watched;
            pthread_cond_signal(&g_cv);
        }
    }
    pthread_mutex_unlock(&g_mu);
    return slot;
}

void tsk_unwatch(int slot, int tid) {
    if (slot < 0 || slot >= SLOTS) return;
    pthread_mutex_lock(&g_mu);
    // The reader may have freed the slot (the thread went) and a later
    // watch taken it: only its own thread's watcher frees it.
    if (g_slots[slot].tid == tid) free_slot(slot);
    pthread_mutex_unlock(&g_mu);
}

void tsk_test_refuse(int mask) {
    pthread_mutex_lock(&g_mu);
    g_refuse = mask;
    pthread_mutex_unlock(&g_mu);
}

}  // extern "C"
