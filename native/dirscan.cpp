// One directory, read whole by one foreign call.
//
// CPython lets the interpreter lock go around every readdir and every
// lstat, so a directory listed from Python with each child's lstat is
// two hand-backs of the lock an entry, each followed by a wait to get it
// back among the other builds' threads. Here the caller crosses once: the
// directory is opened, every name and type read and (where asked) every
// child lstat-ed with the lock free, and the answer comes back packed in
// the caller's own two buffers, which Python unpacks without a system
// call.
//
// No state: every call opens and closes its own directory stream and
// writes only what it was handed. Safe from any thread. (The one word
// the library keeps is the tests' hook below, null outside them.)
//
// C ABI (ctypes):
//   dsc_abi_version()
//   dsc_read(path, want_stat, records, records_cap, names, names_cap,
//            out[2]) -> 0, and out = {children, bytes of names};
//        DSC_SMALL: a buffer was too small for the directory, nothing in
//        them counts, and out says what it holds (the caller grows both
//        and calls again);
//        DSC_RANGE: a child's time does not fit 64 bits of nanoseconds
//        (the caller asks the file system itself);
//        else an errno (> 0): the directory could not be opened or read,
//        or a child's lstat failed with anything but "it is gone".
//
//   dsc_test_before_lstat(fn)   tests: fn(name) is called before each
//        child's lstat (null: none), on the reading thread
//
//   names: each child's name as the directory has it (raw bytes), each
//        followed by one NUL, in the directory's own order, "." and ".."
//        left out.
//   records, want_stat 0: one byte a child, its d_type (DT_UNKNOWN
//        resolved by an lstat, as os.DirEntry.is_dir would).
//   records, want_stat 1: RECORD_FIELDS 8-byte fields a child, the 19 of
//        os.stat_result in its own order (the seconds as integers, as
//        doubles computed as CPython computes them, and in nanoseconds;
//        st_mode holds the type). A child gone between the read of its
//        name and its lstat is left out of both buffers, as if the
//        listing had run a moment later.

#include <atomic>
#include <cerrno>
#include <cstdint>
#include <cstring>

#include <dirent.h>
#include <fcntl.h>
#include <sys/stat.h>
#include <sys/sysmacros.h>
#include <unistd.h>

namespace {

constexpr int DSC_SMALL = -1;
constexpr int DSC_RANGE = -2;
constexpr int RECORD_FIELDS = 19;

std::atomic<void (*)(const char*)> g_before_lstat{nullptr};

union Field {
    int64_t i;
    uint64_t u;
    double d;
};

bool nanoseconds(const timespec& ts, int64_t* out) {
    int64_t ns;
    if (__builtin_mul_overflow((int64_t)ts.tv_sec, (int64_t)1000000000, &ns)
        || __builtin_add_overflow(ns, (int64_t)ts.tv_nsec, &ns))
        return false;
    *out = ns;
    return true;
}

bool fill(const struct stat& st, Field* f) {
    f[0].i = st.st_mode;
    f[1].u = st.st_ino;
    f[2].u = st.st_dev;
    f[3].i = st.st_nlink;
    f[4].i = st.st_uid;
    f[5].i = st.st_gid;
    f[6].i = st.st_size;
    const timespec* times[3] = {&st.st_atim, &st.st_mtim, &st.st_ctim};
    for (int k = 0; k < 3; k++) {
        f[7 + k].i = times[k]->tv_sec;
        // posixmodule.c fill_time: sec + 1e-9 * nsec, in doubles.
        f[10 + k].d = times[k]->tv_sec + 1e-9 * times[k]->tv_nsec;
        if (!nanoseconds(*times[k], &f[13 + k].i)) return false;
    }
    f[16].i = st.st_blksize;
    f[17].i = st.st_blocks;
    f[18].u = st.st_rdev;
    return true;
}

}  // namespace

extern "C" {

int dsc_abi_version() { return 1; }

void dsc_test_before_lstat(void (*fn)(const char*)) { g_before_lstat = fn; }

int dsc_read(const char* path, int want_stat, uint8_t* records,
             size_t records_cap, char* names, size_t names_cap,
             uint64_t* out) {
    int fd = open(path, O_RDONLY | O_DIRECTORY | O_CLOEXEC);
    if (fd < 0) return errno;
    DIR* dir = fdopendir(fd);
    if (!dir) {
        int e = errno;
        close(fd);
        return e;
    }
    const size_t record = want_stat ? RECORD_FIELDS * sizeof(Field) : 1;
    size_t count = 0, names_used = 0;
    bool fits = true;
    int rc = 0;
    for (;;) {
        errno = 0;
        dirent* ent = readdir(dir);
        if (!ent) {
            rc = errno;
            break;
        }
        const char* name = ent->d_name;
        if (name[0] == '.' && (!name[1] || (name[1] == '.' && !name[2])))
            continue;
        size_t len = strlen(name) + 1;
        if ((count + 1) * record > records_cap
            || names_used + len > names_cap)
            fits = false;  // keep reading: the caller learns what it holds
        if (fits) {
            int d_type = ent->d_type;
            if (want_stat || d_type == DT_UNKNOWN) {
                struct stat st;
                if (auto hook = g_before_lstat.load(std::memory_order_relaxed))
                    hook(name);
                if (fstatat(fd, name, &st, AT_SYMLINK_NOFOLLOW) != 0) {
                    if (errno == ENOENT) continue;
                    rc = errno;
                    break;
                }
                if (!want_stat) {
                    d_type = IFTODT(st.st_mode);
                } else if (!fill(st, (Field*)(records + count * record))) {
                    rc = DSC_RANGE;
                    break;
                }
            }
            if (!want_stat) records[count] = (uint8_t)d_type;
            memcpy(names + names_used, name, len);
        }
        count++;
        names_used += len;
    }
    closedir(dir);
    out[0] = count;
    out[1] = names_used;
    return rc ? rc : (fits ? 0 : DSC_SMALL);
}

}  // extern "C"
