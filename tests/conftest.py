"""Test harness: run all JAX work on a virtual 8-device CPU mesh.

Mirrors the reference's hermetic test strategy (SURVEY.md §4): no real
registry, no real TPU needed. Env vars must be set before jax imports.
"""

import os

# Tier-1 runs on CPUs only, wherever it runs: on a machine with a chip
# an unpinned JAX would take the chip for the test process (one process
# owns a chip at a time) and trace the TPU routes, which these tests
# check in interpret mode and against the compile-only topology
# instead. XLA_FLAGS is read when the first backend initializes; the
# platform goes through jax.config as well as the environment (which
# child processes inherit), so a plugin that imported jax before this
# file cannot have fixed it already.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Compiled executables are reused across test processes: the program's
# own cache site (makisu_tpu/ops/__init__.py) places them in
# <checkout>/.jax_cache unless JAX_COMPILATION_CACHE_DIR says otherwise.
# XLA:CPU programs under half a second are cheaper to recompile than to
# write.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
# The device-session ledger (utils/deviceprobe.py) must never write
# into the repo's benchmarks/device_sessions from a test run; tests
# that exercise it point the env var at a tmp dir explicitly.
os.environ.setdefault("MAKISU_TPU_DEVICE_SESSIONS_DIR", "")


import pytest  # noqa: E402

from makisu_tpu.utils import mountinfo  # noqa: E402


@pytest.fixture(autouse=True)
def _no_mounts():
    """Tmp build roots must not inherit the host mount table's skip
    rules (one definition for every suite; tests needing specific
    mountpoints override inside the test body)."""
    mountinfo.set_mountpoints_for_testing(set())
    yield
    mountinfo.set_mountpoints_for_testing(None)


@pytest.fixture(autouse=True, scope="module")
def _shared_hash_stays_in_its_file():
    """A ``WorkerServer`` turns the process's TPU hashers to the shared
    hash service for good (``MAKISU_TPU_SHARED_HASH``). What one test
    file's workers set must not decide the route of the next file's
    builds: which files share a process is the scheduler's choice."""
    before = os.environ.get("MAKISU_TPU_SHARED_HASH")
    yield
    if before is None:
        os.environ.pop("MAKISU_TPU_SHARED_HASH", None)
    else:
        os.environ["MAKISU_TPU_SHARED_HASH"] = before


def cas_entry_path(root, name: str) -> str:
    """Where the CAS directory ``root`` keeps ``name``, for a test that
    ages, corrupts or removes an entry behind the store's back. The
    one place in ``tests/`` (outside ``test_storage.py``, which tests
    the layout itself) that knows: it asks the owner. The entry is a
    file of its own when this returns, whichever form it had."""
    from makisu_tpu.storage import cas
    handle = cas.CASDir(str(root))
    segment = handle._lookup(name) is not None
    # A segment entry has no file of its own to age, corrupt or remove:
    # it is made a loose one first (what ``CASStore.path`` does), and
    # the process's live stores hear of it.
    path = handle._loosen(name)
    if segment:
        for live in cas.live_stores():
            live.refresh()
    return path


def cas_index_bytes(root) -> int:
    """Bytes of the CAS directory ``root`` that are the layout's own
    and no entry's: the segment indexes. What a census of entries does
    not count and ``du`` does."""
    from makisu_tpu.storage import cas
    seg_dir = cas.CASDir(str(root))._seg_dir
    if not os.path.isdir(seg_dir):
        return 0
    return sum(os.path.getsize(os.path.join(seg_dir, fn))
               for fn in os.listdir(seg_dir) if fn.endswith(".idx"))


def committed_layer(storage: str, blob_path: str, chunks,
                    backend_id: str = ""):
    """The gzip blob at ``blob_path``, whose stream ``chunks`` (offset,
    length, sha256 triples) tile, entered into ``storage``'s layer
    store as a commit leaves it: ``(digest pair, LayerCommit)`` for a
    cache manager's ``push_cache``."""
    import hashlib
    from makisu_tpu.chunker.hasher import ChunkFingerprint, LayerCommit
    from makisu_tpu.docker.image import (
        MEDIA_TYPE_LAYER, Descriptor, Digest, DigestPair)
    from makisu_tpu.storage import ImageStore
    with open(blob_path, "rb") as f:
        blob = f.read()
    gz_hex = hashlib.sha256(blob).hexdigest()
    ImageStore(storage).layers.link_file(gz_hex, blob_path)
    pair = DigestPair(
        # Any digest that follows the content will do for the tar's.
        tar_digest=Digest.from_hex(hashlib.sha256(
            b"".join(h.encode() for _, _, h in chunks)).hexdigest()),
        gzip_descriptor=Descriptor(MEDIA_TYPE_LAYER, len(blob),
                                   Digest.from_hex(gz_hex)))
    return pair, LayerCommit(
        pair, [ChunkFingerprint(*c) for c in chunks], backend_id)


class _FsCalls:
    """Stand-in for the ``os`` module inside ``storage/cas.py``: counts
    every file-system call the store issues (``os.path`` probes
    included; the builtin ``open`` a loose entry is read through counts
    as ``open_read``, the ``pread`` of a segment entry or of an index as
    ``pread``), notes any made by a thread that holds the store's
    lock, and can make the k-th call of a name fail. The store's
    background LRU seed (its own thread, once a store) is not on any
    caller's path and is left out."""

    _FS = ("open", "write", "pread", "close", "rename", "mkdir",
           "makedirs", "unlink", "link", "listdir", "stat")
    _FS_PATH = ("isfile", "isdir", "exists", "lexists", "getsize",
                "getmtime")

    class _OwnedLock:
        def __init__(self):
            import threading
            self._lock = threading.Lock()
            self.owner = None

        def __enter__(self):
            import threading
            self._lock.acquire()
            self.owner = threading.get_ident()

        def __exit__(self, *exc):
            self.owner = None
            self._lock.release()

    def __init__(self, store):
        import collections
        import threading
        self._threading = threading
        self.calls = collections.Counter()
        self.under_lock = []          # names of calls made under the lock
        self.fail_at = {}             # name -> (k, exception)
        self._count_lock = threading.Lock()
        self.lock = store._lock = self._OwnedLock()
        self.path = self._Path(self)

    def _wrap(self, name, fn):
        def call(*args, **kwargs):
            if self._threading.current_thread().name == "cas-lru-seed":
                return fn(*args, **kwargs)
            with self._count_lock:
                self.calls[name] += 1
                nth = self.calls[name]
                if self.lock.owner == self._threading.get_ident():
                    self.under_lock.append(name)
            fail = self.fail_at.get(name)
            if fail is not None and fail[0] == nth:
                raise fail[1]
            return fn(*args, **kwargs)
        return call

    class _Path:
        def __init__(self, outer):
            self._outer = outer

        def __getattr__(self, name):
            fn = getattr(os.path, name)
            if name in _FsCalls._FS_PATH:
                return self._outer._wrap(name, fn)
            return fn

    def __getattr__(self, name):
        fn = getattr(os, name)
        if name in self._FS:
            return self._wrap(name, fn)
        return fn

    def total(self) -> int:
        return sum(self.calls.values())


@pytest.fixture
def fs_calls(monkeypatch):
    """``fs_calls(store)`` swaps the ``os`` that ``storage/cas.py`` sees
    for a counting one (from this point on) and instruments the
    store's lock; returns the recorder."""
    from makisu_tpu.storage import cas as cas_mod

    def install(store):
        recorder = _FsCalls(store)
        monkeypatch.setattr(cas_mod, "os", recorder)
        monkeypatch.setattr(cas_mod, "open",
                            recorder._wrap("open_read", open),
                            raising=False)
        return recorder
    return install


@pytest.fixture(params=["native", "python"])
def dir_route(request, monkeypatch):
    """The test runs once a route a directory is read from disk by
    (``snapshot/walk.py:_read_dir``): with the ``libdirscan.so`` this
    tree builds, and with none (``dir_reader()`` answers ``None``, as
    where ``MAKISU_TPU_NATIVE_DIR`` holds no library)."""
    from makisu_tpu import native
    if request.param == "python":
        monkeypatch.setattr(native, "dir_reader", lambda: None)
    elif native.dir_reader() is None:
        pytest.skip("libdirscan.so cannot be built or loaded here")
    return request.param


def _store_tree(root: str) -> dict[str, tuple[int, bytes]]:
    """What a store directory holds: relative path -> (mode, bytes) per
    file, ``"<dir>/" -> (0, b"")`` per empty directory."""
    out = {}
    for dirpath, dirs, files in os.walk(root):
        for name in files:
            path = os.path.join(dirpath, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = (
                    os.stat(path).st_mode & 0o777, f.read())
        for name in dirs:
            path = os.path.join(dirpath, name)
            if not os.listdir(path):
                out[os.path.relpath(path, root) + "/"] = (0, b"")
    return out


@pytest.fixture
def store_tree():
    return _store_tree
