"""Milliseconds a file-system call on the machine's temporary directory,
over 2,000 files of 14 KB in 100 directories: what a member of a layer
unpacked under ``--root`` is made of (PERF.md §6, PR 42). Run it where
the builds run: ``chiprun -- python3 benchmarks/fs_calls.py``."""
import collections
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FILES, DIRS, BATCH = 2000, 100, 250  # a batch stays under any fd limit
WIDE = 300  # entries of the one directory read whole (PERF.md §5, PR 53)
DATA = b"x" * 14000


def _scandir_lstat(path):
    with os.scandir(path) as it:
        return [(entry.name, entry.stat(follow_symlinks=False))
                for entry in it]


def _mkdir_again(path):
    try:
        os.mkdir(path)
    except FileExistsError:
        pass


def main():
    root = tempfile.mkdtemp()
    dirs = [f"{root}/d{k:03d}" for k in range(DIRS)]
    paths = [f"{dirs[k % DIRS]}/f{k}" for k in range(FILES)]
    parents = [os.path.dirname(p) for p in paths]
    seconds = collections.Counter()
    calls = collections.Counter()

    def lap(name, fn, items):
        t0 = time.perf_counter()
        out = [fn(it) for it in items]
        seconds[name] += time.perf_counter() - t0
        calls[name] += len(items)
        return out

    lap("mkdir", os.mkdir, dirs)
    lap("lexists_miss", os.path.lexists, paths)
    lap("stat_dir_hit", os.stat, parents)
    for k in range(0, FILES, BATCH):
        fds = lap("open_creat_excl", lambda p: os.open(
            p, os.O_WRONLY | os.O_CREAT | os.O_EXCL | os.O_CLOEXEC, 0o666),
            paths[k:k + BATCH])
        lap("write_14k", lambda fd: os.write(fd, DATA), fds)
        lap("fstat", os.fstat, fds)
        lap("fchmod", lambda fd: os.fchmod(fd, 0o644), fds)
        lap("fchown", lambda fd: os.fchown(fd, os.getuid(), os.getgid()), fds)
        lap("futimens", lambda fd: os.utime(fd, (1000, 1000)), fds)
        lap("close", os.close, fds)
    lap("chmod_path", lambda p: os.chmod(p, 0o640), paths)
    lap("lchown_path", lambda p: os.lchown(p, os.getuid(), os.getgid()),
        paths)
    lap("utime_path", lambda p: os.utime(p, (2000, 2000)), paths)
    lap("lstat_hit", os.lstat, paths)
    # The same lstat asked of the open directory, which walks one name
    # where the path walks five components.
    for d in dirs:
        dir_fd = os.open(d, os.O_RDONLY | os.O_DIRECTORY)
        lap("fstatat_hit", lambda name: os.stat(
            name, dir_fd=dir_fd, follow_symlinks=False), os.listdir(d))
        os.close(dir_fd)
    # A directory of WIDE files read whole: scandir and an lstat a
    # child from Python, and the native reader's one call.
    wide = f"{root}/wide"
    os.mkdir(wide)
    for k in range(WIDE):
        with open(f"{wide}/f{k:03d}", "wb") as f:
            f.write(DATA)
    lap(f"scandir_lstat_{WIDE}", _scandir_lstat, [wide] * 20)
    from makisu_tpu import native
    reader = native.dir_reader()
    if reader is not None:
        lap(f"native_dir_lstat_{WIDE}", lambda p: reader.read(p, True),
            [wide] * 20)
        lap(f"native_dir_types_{WIDE}", lambda p: reader.read(p, False),
            [wide] * 20)
    shutil.rmtree(wide)
    lap("isdir_file", os.path.isdir, paths)
    lap("mkdir_eexist", _mkdir_again, parents)
    lap("open_wb_trunc_close", lambda p: open(p, "wb").close(), paths)
    lap("unlink", os.remove, paths)
    lap("rmdir", os.rmdir, dirs)
    shutil.rmtree(root, ignore_errors=True)
    print("[fs_calls] ms a call in", tempfile.gettempdir(),
          {k: round(seconds[k] / calls[k] * 1e3, 4) for k in seconds})


if __name__ == "__main__":
    main()
