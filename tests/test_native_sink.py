"""Native layer pipeline (native/layersink.cpp): byte-identity with the
Python pipeline is cache-identity-bearing — layer digests must not
depend on which sink produced them."""

import functools
import gc
import hashlib
import io
import json
import os
import random
import tarfile
import threading
import zlib

import pytest

from makisu_tpu import native
from makisu_tpu.chunker.hasher import LayerSink, NativeLayerSink, _TPUSink

pytestmark = pytest.mark.skipif(
    not native.layersink_available(),
    reason="native layersink not built")


def _tree(tmp_path):
    """A tree exercising the tar corner cases: empty files, large files,
    long (>100 char) names, unicode names, symlinks, hardlinks, dirs."""
    root = tmp_path / "tree"
    root.mkdir()
    (root / "empty").write_bytes(b"")
    (root / "small").write_bytes(b"hello world\n")
    import random
    rnd = random.Random(7)
    (root / "big.bin").write_bytes(rnd.randbytes(700_001))
    deep = root / ("d" * 60) / ("e" * 60)
    deep.mkdir(parents=True)
    (deep / ("f" * 80 + ".txt")).write_bytes(b"long name content")
    (root / "café.txt").write_bytes(b"unicode")
    (root / "link").symlink_to("small")
    os.link(root / "small", root / "hard")
    os.chmod(root / "small", 0o640)
    return root


def _entries(root):
    """Deterministic TarInfo list for the tree (same input, both sinks)."""
    from makisu_tpu.snapshot.walk import tarinfo_from_stat, walk
    from makisu_tpu.utils import pathutils
    inodes = {}
    out = []
    def one(path, st):
        if path == str(root):
            return
        name = pathutils.rel_path(pathutils.trim_root(path, str(root)))
        hdr = tarinfo_from_stat(path, name, str(root))
        if hdr.isreg():
            if st.st_ino in inodes:
                hdr.type = tarfile.LNKTYPE
                hdr.linkname = inodes[st.st_ino]
                hdr.size = 0
            else:
                inodes[st.st_ino] = hdr.name
        out.append((path, hdr))
    walk(str(root), None, one)
    return out


def _layer(root):
    """The tree as a layer: ``Layer.commit`` writes it in sorted path
    order, by the batch into a native writer and entry by entry into a
    ``tarfile.TarFile``."""
    from makisu_tpu.snapshot.layer import Layer
    layer = Layer()
    for src, hdr in _entries(root):
        layer.add_header(src, "/" + hdr.name, hdr)
    return layer


def _commit(sink_cls, root, path, backend_id):
    layer = _layer(root)
    with open(path, "wb") as f:
        sink = sink_cls(f, backend_id=backend_id)
        with sink.open_tar() as tw:
            layer.commit(tw)
        return sink.finish()


# The zlib backend's ring (native/layersink.cpp: kSlotBytes, kSlots).
_SLOT = 256 * 1024
_RING = 64 * _SLOT


@functools.cache
def _text_block():
    """256 KiB of seeded words: longer than deflate's window, so a
    repeat of it compresses like text again, each level its own way."""
    rnd = random.Random(2)
    vocab = [rnd.randbytes(rnd.randint(2, 9)).hex().encode()
             for _ in range(500)]
    return b" ".join(rnd.choices(vocab, k=40_000))[:256 * 1024]


def _stream_bytes(n, seed=11):
    """``n`` seeded bytes: a quarter incompressible, a quarter text,
    the rest a repeated 8 KiB block (deflate runs through 50 MB of it
    fast)."""
    rnd = random.Random(seed)
    head = rnd.randbytes(n // 4)
    text = _text_block()
    text = (text * (n // 4 // len(text) + 1))[:n // 4]
    block = rnd.randbytes(8192)
    tail = n - len(head) - len(text)
    return head + text + (block * (tail // len(block) + 1))[:tail]


def _feed(handle, tmp_path, plan):
    """Write a plan of ``("write", bytes)`` and ``("file", bytes)``
    steps into a native handle, a file as a batch of one entry with no
    header (content, then padding to 512; the sink streams a lone file
    on the caller's thread); yields the stream bytes sent after each
    step."""
    sent = 0
    for i, (kind, data) in enumerate(plan):
        if kind == "file":
            src = tmp_path / f"src{i}"
            src.write_bytes(data)
            handle.write_entries([b""], [str(src)], [len(data)])
        else:
            handle.write(data)
        sent += len(data) + (-len(data) % 512 if kind == "file" else 0)
        yield sent


def _tar_of(plan):
    """The stream a plan stands for."""
    return b"".join(
        d + (b"\0" * (-len(d) % 512) if kind == "file" else b"")
        for kind, d in plan)


def _commit_raw(sink_cls, tmp_path, path, backend_id, plan):
    """Commit a plan's stream: the native sink is fed step by step,
    the Python one is written the same bytes."""
    with open(path, "wb") as f:
        sink = sink_cls(f, backend_id=backend_id)
        if sink_cls is NativeLayerSink:
            for _ in _feed(sink._handle, tmp_path, plan):
                pass
        else:
            data = _tar_of(plan)
            for off in range(0, len(data), 1 << 20):
                sink.write(data[off:off + (1 << 20)])
        return sink.finish()


def _interleaved_plan():
    """Headers of 512-1,536 bytes between files whose sizes put the
    slot edges inside a header, inside a file and on a boundary; more
    than the ring holds in all."""
    rnd = random.Random(5)
    plan = []
    for size in (0, 1, 511, _SLOT - 512, _SLOT, 3 * _SLOT + 7,
                 _RING + 12_345, 700_001):
        plan.append(("write", rnd.randbytes(512 * rnd.randint(1, 3))))
        plan.append(("file", _stream_bytes(size, seed=size)))
    plan.append(("write", b"\0" * 1024))
    return plan


_STREAMS = {
    "tree": None,
    "0": [],
    "1": [("write", b"x")],
    "slot-1": [("write", _SLOT - 1)],
    "slot": [("write", _SLOT)],
    "slot+1": [("write", _SLOT + 1)],
    "ring+slot": [("write", _RING + _SLOT)],
    "3-rings": [("write", 3 * _RING + 12_345)],
    "one-file-3-rings": [("file", 3 * _RING + 12_345)],
    "headers-and-files": _interleaved_plan,
}


def _plan(stream):
    plan = _STREAMS[stream]
    if callable(plan):
        return plan()
    return [(kind, _stream_bytes(d) if isinstance(d, int) else d)
            for kind, d in plan]


@pytest.mark.parametrize(
    "backend_id,stream",
    [(f"zlib-{level}", "tree") for level in range(1, 10)]
    + [("pgzip-6-131072", "tree")]
    + [("zlib-6", stream) for stream in _STREAMS if stream != "tree"]
    + [("zlib-1", "headers-and-files"), ("zlib-9", "ring+slot"),
       ("pgzip-6-131072", "headers-and-files")])
def test_native_matches_python_bytes_and_digests(tmp_path, backend_id,
                                                 stream):
    """Whatever the sink, the same blob: for every zlib level, and for
    streams that end before, on and after the edges of the native
    sink's ring (its compressor thread takes the stream slot by slot)."""
    if backend_id.startswith("pgzip") and not native.pgzip_available():
        pytest.skip("pgzip not built")
    py_path = str(tmp_path / "py.tar.gz")
    nat_path = str(tmp_path / "native.tar.gz")
    if stream == "tree":
        root = _tree(tmp_path)
        py = _commit(LayerSink, root, py_path, backend_id)
        nat = _commit(NativeLayerSink, root, nat_path, backend_id)
    else:
        plan = _plan(stream)
        py = _commit_raw(LayerSink, tmp_path, py_path, backend_id, plan)
        nat = _commit_raw(NativeLayerSink, tmp_path, nat_path, backend_id,
                          plan)
    with open(py_path, "rb") as f:
        py_bytes = f.read()
    with open(nat_path, "rb") as f:
        nat_bytes = f.read()
    assert py_bytes == nat_bytes
    assert py.digest_pair.tar_digest == nat.digest_pair.tar_digest
    assert (py.digest_pair.gzip_descriptor.digest
            == nat.digest_pair.gzip_descriptor.digest)
    assert (py.digest_pair.gzip_descriptor.size
            == nat.digest_pair.gzip_descriptor.size)
    # Self-consistency: the reported digests describe the actual bytes.
    assert hashlib.sha256(nat_bytes).hexdigest() \
        == nat.digest_pair.gzip_descriptor.digest.hex()
    if stream != "tree":
        tar = _tar_of(plan)
        assert zlib.decompress(nat_bytes, 31) == tar
        assert hashlib.sha256(tar).hexdigest() \
            == nat.digest_pair.tar_digest.hex()


@pytest.mark.parametrize("sink_cls", [LayerSink, NativeLayerSink],
                         ids=["python", "native"])
@pytest.mark.parametrize("backend_id", ["zlib-6", "pgzip-6-131072"])
def test_either_sink_reports_its_compress_seconds(tmp_path, sink_cls,
                                                  backend_id):
    """``makisu_commit_stage_busy_seconds{stage="compress"}`` grows by
    what the layer's gzip stream spent, whichever sink committed it
    (the native one keeps the seconds in C++)."""
    if backend_id.startswith("pgzip") and not native.pgzip_available():
        pytest.skip("pgzip not built")
    from makisu_tpu.utils import metrics
    registry = metrics.MetricsRegistry()
    token = metrics.set_build_registry(registry)
    try:
        _commit(sink_cls, _tree(tmp_path), str(tmp_path / "out.tar.gz"),
                backend_id)
    finally:
        metrics.reset_build_registry(token)
    busy = registry.counter_by_label(metrics.COMMIT_STAGE_BUSY, "stage")
    assert 0 < busy["compress"] < 5
    # What the writer spent blocked on the native sink's stream (under
    # zlib the drain at least; under pgzip the pool's oldest block, over
    # the cap and in ``finish``: 0 only where one lane deflates in
    # line): beside ``compress``, never a part of it. ``compress_wall``
    # is the stream's wall time (one thread: ``compress`` itself; a
    # pool: no more than its summed busy seconds), ``blob_write`` what
    # the writer digested and wrote itself, which only the pool leaves
    # to it. The Python sink has none of the three.
    if sink_cls is NativeLayerSink:
        assert 0 <= busy["compress_wait"] < 5
        assert 0 < busy["compress_wall"] <= busy["compress"] + 1e-9
        if backend_id.startswith("zlib"):
            assert busy["compress_wait"] > 0
            assert busy["compress_wall"] == busy["compress"]
            assert "blob_write" not in busy
        else:
            assert 0 < busy["blob_write"] < 5
    else:
        assert not {"compress_wait", "compress_wall", "blob_write"} \
            & set(busy)


def test_native_archive_is_valid_tar(tmp_path):
    root = _tree(tmp_path)
    out = str(tmp_path / "check.tar.gz")
    _commit(NativeLayerSink, root, out, "zlib-6")
    names = []
    with tarfile.open(out, "r:gz") as tf:
        for m in tf:
            names.append(m.name)
            if m.isreg() and m.name.endswith("small"):
                assert tf.extractfile(m).read() == b"hello world\n"
    assert any("café" in n for n in names)
    assert any(len(n) > 150 for n in names)  # pax long-name entry worked


def test_native_sink_selected_for_real_files(tmp_path):
    from makisu_tpu.chunker import CPUHasher
    with open(tmp_path / "out.gz", "wb") as f:
        sink = CPUHasher().open_layer(f)
        assert isinstance(sink, NativeLayerSink)
    # BytesIO (no fileno) falls back to the Python sink.
    assert isinstance(CPUHasher().open_layer(io.BytesIO()), LayerSink)


def test_native_sink_env_opt_out(tmp_path, monkeypatch):
    from makisu_tpu.chunker import CPUHasher
    monkeypatch.setenv("MAKISU_TPU_NATIVE_SINK", "0")
    with open(tmp_path / "out.gz", "wb") as f:
        assert isinstance(CPUHasher().open_layer(f), LayerSink)


def test_native_sink_error_on_shrunk_file(tmp_path):
    root = tmp_path / "r"
    root.mkdir()
    victim = root / "shrinks"
    victim.write_bytes(b"x" * 1000)
    hdr = tarfile.TarInfo("shrinks")
    hdr.size = 1000
    hdr.mode = 0o644
    victim.write_bytes(b"x")  # shrank after stat
    with open(tmp_path / "out.gz", "wb") as f:
        sink = NativeLayerSink(f, backend_id="zlib-6")
        tw = sink.open_tar()
        with pytest.raises(OSError, match="shrank"):
            tw.add_entries([(hdr, str(victim))])
        sink.abort()


def test_native_tpu_sink_matches_python_chunks(tmp_path, monkeypatch):
    """TPU hasher over the native pipeline: digests AND chunk
    fingerprints must match the pure-Python path exactly (the tap hands
    the chunker the same uncompressed stream)."""
    from makisu_tpu.chunker import TPUHasher

    root = _tree(tmp_path)

    def commit(native_on, out_name):
        monkeypatch.setenv("MAKISU_TPU_NATIVE_SINK",
                           "1" if native_on else "0")
        path = str(tmp_path / out_name)
        layer = _layer(root)
        with open(path, "wb") as f:
            sink = TPUHasher().open_layer(f, backend_id="zlib-6")
            assert isinstance(sink, NativeLayerSink) == native_on
            with sink.open_tar() as tw:
                layer.commit(tw)
            return sink.finish(), path

    py, py_path = commit(False, "py.tgz")
    nat, nat_path = commit(True, "nat.tgz")
    with open(py_path, "rb") as f:
        py_bytes = f.read()
    with open(nat_path, "rb") as f:
        nat_bytes = f.read()
    assert py_bytes == nat_bytes
    assert py.digest_pair == nat.digest_pair
    assert py.chunks == nat.chunks
    assert nat.chunks  # fingerprints actually produced


def test_native_tap_errors_fail_the_build(tmp_path):
    """A dying chunker must fail the commit — silently missing tap
    bytes would persist wrong cache-identity fingerprints."""
    sink = None
    with open(tmp_path / "out.gz", "wb") as f:
        sink = NativeLayerSink.__new__(NativeLayerSink)
        # Assemble manually with a session whose update explodes.
        from makisu_tpu import native as native_mod
        sink.backend_id = "zlib-6"
        sink._handle = native_mod.LayerSinkHandle(f.fileno(), "zlib", 6)

        class BadSession:
            def update(self, data):
                raise RuntimeError("device fell over")

            def finish(self):
                return []

        sink._session = BadSession()
        sink._handle.set_tap(sink._session.update)
        with pytest.raises(RuntimeError, match="chunk tap failed"):
            sink.write(b"x" * 100)


def _within(seconds, fn):
    """``fn()`` on a thread of its own: what it returned or raised, or
    a failure if it has not come back in ``seconds`` (a hang, which a
    call on this thread would turn into a stuck test run)."""
    box = []

    def run():
        try:
            box.append((fn(), None))
        except Exception as e:  # noqa: BLE001 - handed to the caller
            box.append((None, e))
    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(seconds)
    assert not t.is_alive(), f"still blocked after {seconds} s"
    return box[0]


@pytest.mark.parametrize("backend", ["zlib", "pgzip"])
def test_tap_sees_every_byte_once_in_order_on_the_callers_thread(
        tmp_path, backend):
    """The tap is the chunker's intake (a Python callback, clocked on
    the building thread): it runs synchronously in the writer's calls,
    never on the sink's compressor thread, also for a file's bytes
    that the sink read straight into the ring."""
    if backend == "pgzip" and not native.pgzip_available():
        pytest.skip("pgzip not built")
    seen, idents = [], set()

    def tap(data):
        idents.add(threading.get_ident())
        seen.append(data)

    plan = _interleaved_plan()
    with open(tmp_path / "out.gz", "wb") as f:
        handle = native.LayerSinkHandle(f.fileno(), backend, 6)
        handle.set_tap(tap)
        for sent in _feed(handle, tmp_path, plan):
            # Synchronous: a call's bytes have been seen when it returns.
            assert sum(map(len, seen)) == sent
        tar_hex, _, _, tar_size = handle.finish()
        handle.close()
    assert idents == {threading.get_ident()}
    tar = b"".join(seen)
    assert len(tar) == tar_size == sent > _RING
    assert hashlib.sha256(tar).hexdigest() == tar_hex
    assert tar == _tar_of(plan)


def _broken_pipe_handle():
    """A zlib sink whose output took the gzip header and fails every
    write after it (EPIPE: CPython ignores SIGPIPE): the error happens
    on the sink's compressor thread."""
    r, w = os.pipe()
    handle = native.LayerSinkHandle(w, "zlib", 6)
    os.close(r)
    return handle, w


@pytest.mark.parametrize("where", ["header", "write", "write_entries",
                                   "finish"])
def test_failing_output_fd_fails_the_sink_and_does_not_hang(tmp_path,
                                                            where):
    """An output that stops taking bytes fails the commit: ``lsk_new``
    where not even the header goes out, else the first ``write`` /
    ``write_entries`` after the compressor thread met the error (not
    blocked on a ring nobody empties any more), ``finish`` at the
    latest; and the failed sink can be closed."""
    if where == "header":
        with open("/dev/full", "wb", buffering=0) as f:
            with pytest.raises(RuntimeError, match="lsk_new failed"):
                native.LayerSinkHandle(f.fileno(), "zlib", 6)
        return
    handle, w = _broken_pipe_handle()
    try:
        if where == "finish":
            handle.write(b"x" * 1000)  # stays in its slot: no error yet
            _, err = _within(30, handle.finish)
            assert isinstance(err, RuntimeError)
            assert "finish failed" in str(err)
        else:
            # Incompressible, three times what the ring holds: a writer
            # that only waited for a free slot would wait for ever.
            data = random.Random(3).randbytes(1 << 20)
            src = tmp_path / "src"
            src.write_bytes(data)

            def feed():
                for _ in range(3 * _RING // len(data)):
                    if where == "write":
                        handle.write(data)
                    else:
                        handle.write_entries([b""], [str(src)],
                                             [len(data)])
            _, err = _within(60, feed)
            assert isinstance(err, RuntimeError)
            assert "write failed" in str(err)
            # It stays failed: the next call of either kind, then finish.
            with pytest.raises(RuntimeError, match="write failed"):
                handle.write(b"y")
            with pytest.raises(RuntimeError, match="write failed"):
                handle.write_entries([b""], [str(src)], [len(data)])
            _, err = _within(30, handle.finish)
            assert isinstance(err, RuntimeError)
        _within(30, handle.close)
    finally:
        os.close(w)


@pytest.mark.parametrize("fed", [0, 1000, 3 * _SLOT + 5, _RING + _SLOT])
def test_close_on_an_unfinished_sink_returns(tmp_path, fed):
    """A build that dies in ``write_diffs`` never calls ``finish``:
    ``close`` (``__del__`` calls it) stops and joins the compressor
    thread whatever the ring still holds, and the fd stays the
    caller's."""
    with open(tmp_path / "out.gz", "wb") as f:
        handle = native.LayerSinkHandle(f.fileno(), "zlib", 6)
        data = _stream_bytes(fed)
        for off in range(0, fed, 1 << 20):
            handle.write(data[off:off + (1 << 20)])
        _within(30, handle.close)
        with pytest.raises(RuntimeError, match="already closed"):
            handle.write(b"x")
        handle.close()  # idempotent
        f.write(b"still open")


def _sink_threads(name):
    """Live threads of native sinks, by the name they take."""
    gc.collect()  # a sink some earlier test dropped in a cycle
    live = 0
    for task in os.listdir("/proc/self/task"):
        try:
            with open(f"/proc/self/task/{task}/comm") as f:
                live += f.read().strip() == name
        except OSError:  # a thread that ended meanwhile
            pass
    return live


def _compressor_threads():
    return _sink_threads("lsk-zlib")


def _more_than_the_ring_of_text():
    """Slots that deflate far slower than a writer fills them."""
    return _text_block() * (_RING // _SLOT + 8)


def _stays_empty(path, number):
    """The next file opened takes the fd number ``out`` had; nothing a
    sink still had queued for that number may land in it."""
    with open(path, "wb") as other:
        assert other.fileno() == number
        threading.Event().wait(0.3)
        assert os.fstat(other.fileno()).st_size == 0


class _Died(Exception):
    pass


@pytest.mark.parametrize("dies_in", ["write_diffs", "tar_close", "finish"])
def test_a_commit_that_dies_stops_the_sink_before_out_closes(tmp_path,
                                                             dies_in):
    """``commit_layer``'s order: ``with out:`` around ``with
    sink.open_tar()`` and ``sink.finish()``. Whichever of them raises
    (a shrunk file, the chunker's tap, the device drain), the sink's
    compressor thread is joined and its handle freed before ``out``
    closes, though the ring is still full of work."""

    class Session:
        dies_in = None

        def update(self, data):
            if self.dies_in == "tar_close":
                raise _Died

        def finish(self):
            if self.dies_in == "finish":
                raise _Died
            return []

    text = _more_than_the_ring_of_text()
    (tmp_path / "text").write_bytes(text)
    threads = _compressor_threads()
    session = Session()
    with pytest.raises((_Died, RuntimeError)):
        with open(tmp_path / "out.gz", "wb") as out:
            number = out.fileno()
            sink = NativeLayerSink(out, backend_id="zlib-9",
                                   session=session)
            with sink.open_tar() as tw:
                assert _compressor_threads() == threads + 1
                hdr = tarfile.TarInfo("text")
                hdr.size = len(text)
                # slots deflate slowly
                tw.add_entries([(hdr, str(tmp_path / "text"))])
                if dies_in == "write_diffs":
                    raise _Died
                session.dies_in = dies_in
            sink.finish()
    assert sink._handle._handle is None
    assert _compressor_threads() == threads
    _stays_empty(tmp_path / "other", number)


def test_the_sink_writes_to_a_fd_of_its_own(tmp_path):
    """``lsk_new`` dups the caller's fd: a caller that closes its own
    under a live sink (no Python caller does) loses nothing, and the
    file that takes the number next gets nothing."""
    text = _more_than_the_ring_of_text()
    fd = os.open(tmp_path / "out.gz", os.O_WRONLY | os.O_CREAT, 0o644)
    handle = native.LayerSinkHandle(fd, "zlib", 9)
    handle.write(text)
    os.close(fd)
    _stays_empty(tmp_path / "other", fd)
    handle.write(b"tail")
    tar_hex, gz_hex, gz_size, tar_size = handle.finish()
    handle.close()
    blob = (tmp_path / "out.gz").read_bytes()
    assert len(blob) == gz_size
    assert hashlib.sha256(blob).hexdigest() == gz_hex
    assert zlib.decompress(blob, 31) == text + b"tail"


# -- a library is this tree's, or it is absent ---------------------------------

# library -> (the module's handle, its failed flag, the ABI call, a
# symbol the module binds, the question callers ask).
_LIBRARIES = {
    "libpgzip": ("_lib", "_load_failed", "pgz_abi_version", "pgz_blocks",
                 native.pgzip_available),
    "liblayersink": ("_lsk_lib", "_lsk_failed", "lsk_abi_version",
                     "lsk_prefetch_stats", native.layersink_available),
    "libgear": ("_gear_lib", "_gear_failed", "gear_abi_version",
                "gear_sha256_batch", native.gear_scan_available),
}


class _AnotherTrees:
    """A built library as one from another tree would load: a symbol
    the module binds is missing, or the ABI call answers another
    number. The rest is there."""

    def __init__(self, lib, hidden, abi_fn):
        self._lib, self._hidden, self._abi_fn = lib, hidden, abi_fn

    def __getattr__(self, name):
        if name == self._hidden:
            raise AttributeError(f"undefined symbol: {name}")
        if name == self._abi_fn:
            return lambda: 99
        return getattr(self._lib, name)


@pytest.mark.parametrize("fault", ["abi", "symbol"])
@pytest.mark.parametrize("library", list(_LIBRARIES))
def test_a_library_that_is_not_this_trees_is_refused_whole(
        tmp_path, monkeypatch, library, fault):
    """Another ABI number, or one bound symbol missing: the library is
    unavailable (no half-loaded state), the log says so once with the
    command that rebuilds it, and a layer commits through what stands
    in for it (the Python sink and its stdlib deflate, the ``xla``
    chunk route) to the tar digest, blob digest and chunk list of the
    native build."""
    import ctypes

    from makisu_tpu.chunker import TPUHasher, route
    from makisu_tpu.utils import logging as log
    if not native.pgzip_available() or not native.gear_scan_available():
        pytest.skip("native libraries not built")
    layer = _layer(_tree(tmp_path))
    backend_id = "pgzip-6-131072"
    assert route.chunk_route().native
    want, want_blob, _ = _commit_layer(layer, tmp_path / "native.gz",
                                       backend_id)

    handle, failed, abi_fn, symbol, available = _LIBRARIES[library]
    monkeypatch.setattr(native, handle, None)
    monkeypatch.setattr(native, failed, False)
    cdll = ctypes.CDLL
    monkeypatch.setattr(
        ctypes, "CDLL",
        lambda path, *a, **k: cdll(path, *a, **k)
        if f"{library}.so" not in str(path)
        else _AnotherTrees(cdll(path), *(
            (None, abi_fn) if fault == "abi" else (symbol, None))))
    said = []
    monkeypatch.setattr(log, "error",
                        lambda msg, *a: said.append(msg % a))
    assert not available() and not available()
    assert getattr(native, handle) is None
    [line] = said
    assert f"{library}.so" in line and "make -C native clean all" in line
    assert ("99" if fault == "abi" else symbol) in line

    if library == "libpgzip":
        # The native sink deflates its blocks itself: the library's
        # stand-in is the Python sink's stdlib codec.
        monkeypatch.setenv("MAKISU_TPU_NATIVE_SINK", "0")
    sink_cls = NativeLayerSink if library == "libgear" else _TPUSink
    assert route.chunk_route().native == (library != "libgear")
    got, got_blob, _ = _commit_layer(layer, tmp_path / "other.gz",
                                     backend_id, sink_cls=sink_cls)
    assert got_blob == want_blob
    assert got.digest_pair == want.digest_pair
    assert got.chunks == want.chunks and got.chunks


# -- entries by the batch (lsk_write_entries) ---------------------------------

_READ_AHEAD_MAX = 8 * 1024 * 1024   # native/layersink.cpp: kReadAheadMax
_TAP = 256 * 1024                   # native/layersink.cpp: kTapBytes
_READERS = 4                        # native/layersink.cpp: kReaders


def _batch_layer(tmp_path):
    """``_tree``'s corner cases (an empty file, a name long enough for
    a PAX header, a symlink, a hard link, directories) with 300 small
    files, a file over what the sink reads ahead and a whiteout: a
    layer of two batches, and how many of its files have content."""
    from makisu_tpu.snapshot.layer import Layer
    root = _tree(tmp_path)
    rnd = random.Random(40)
    (root / "over.bin").write_bytes(rnd.randbytes(_READ_AHEAD_MAX + 1))
    (root / "many").mkdir()
    for i in range(300):
        (root / "many" / f"s{i:03d}").write_bytes(
            rnd.randbytes(rnd.choice([1, 511, 512, 513, 3_000, 70_000])))
    layer = Layer()
    for src, hdr in _entries(root):
        layer.add_header(src, "/" + hdr.name, hdr)
    layer.add_whiteout("/many/gone")  # sorts among the small files
    with_content = sum(1 for e in layer.entries.values()
                       if e.header()[1] is not None)
    assert len(layer) > 256 and with_content == 305
    return layer, with_content


class _Recorder:
    """A chunk session that keeps what the tap handed it."""

    def __init__(self):
        self.pieces = []

    def update(self, data):
        self.pieces.append(data)

    def finish(self):
        return []


def _commit_layer(layer, path, backend_id, session=None,
                  sink_cls=NativeLayerSink):
    """``Layer.commit`` through a sink of ``sink_cls``, the native one
    or the Python one (``_TPUSink``): the TPU hasher's own choice,
    unless a ``session`` is given. The commit, the blob, the writer."""
    from makisu_tpu.chunker import TPUHasher
    with open(path, "wb") as f:
        if session is None:
            sink = TPUHasher().open_layer(f, backend_id=backend_id)
            assert isinstance(sink, sink_cls)
        elif sink_cls is NativeLayerSink:
            sink = NativeLayerSink(f, backend_id=backend_id,
                                   session=session)
        else:
            sink = sink_cls(f, session, backend_id=backend_id)
        with sink.open_tar() as tw:
            layer.commit(tw)
        commit = sink.finish()
    with open(path, "rb") as f:
        return commit, f.read(), tw


_BACKENDS = ["zlib-6", "pgzip-6-131072"]


@pytest.mark.parametrize("backend_id", _BACKENDS)
def test_the_batch_path_gives_the_per_entry_paths_bytes(
        tmp_path, monkeypatch, backend_id):
    """Tar bytes, blob bytes, both digests and the chunk list are the
    same whether the layer's entries cross into the native sink a
    batch at a time or go one by one into the Python sink's
    ``tarfile.TarFile`` (the reference that stays), and they are what
    ``hashlib`` and ``zlib`` say of the tar."""
    if backend_id.startswith("pgzip") and not native.pgzip_available():
        pytest.skip("pgzip not built")
    layer, _ = _batch_layer(tmp_path)
    batch, batch_blob, tw = _commit_layer(layer, tmp_path / "batch.gz",
                                          backend_id)
    assert not isinstance(tw, tarfile.TarFile)
    monkeypatch.setenv("MAKISU_TPU_NATIVE_SINK", "0")
    entry, entry_blob, ptw = _commit_layer(layer, tmp_path / "entry.gz",
                                           backend_id, sink_cls=_TPUSink)
    assert isinstance(ptw, tarfile.TarFile)
    assert batch_blob == entry_blob
    assert batch.digest_pair == entry.digest_pair
    assert batch.chunks == entry.chunks and batch.chunks
    tar = zlib.decompress(batch_blob, 31)
    assert len(tar) == tw.offset
    assert hashlib.sha256(tar).hexdigest() \
        == batch.digest_pair.tar_digest.hex()
    assert hashlib.sha256(batch_blob).hexdigest() \
        == batch.digest_pair.gzip_descriptor.digest.hex()
    at = 0
    for chunk in batch.chunks:  # they tile the tar, each its sha-256
        assert chunk.offset == at
        assert hashlib.sha256(
            tar[at:at + chunk.length]).hexdigest() == chunk.hex_digest
        at += chunk.length
    assert at == len(tar)
    with tarfile.open(fileobj=io.BytesIO(batch_blob), mode="r:gz") as tf:
        members = {m.name: m for m in tf}
        assert len(members) == len(layer)
        assert members["many/.wh.gone"].size == 0
        assert members["small"].islnk() and members["link"].issym()
        assert tf.extractfile(members["over.bin"]).read() \
            == random.Random(40).randbytes(_READ_AHEAD_MAX + 1)


@pytest.mark.parametrize("backend_id", _BACKENDS)
def test_the_tap_is_called_by_the_slot_not_by_the_piece(tmp_path,
                                                        backend_id):
    """By the batch the tap gets the very stream the Python sink's
    chunker gets write by write, every byte once and in order, a
    filled 256 KiB at a time and once at the end of a call: in far
    fewer calls."""
    if backend_id.startswith("pgzip") and not native.pgzip_available():
        pytest.skip("pgzip not built")
    layer, _ = _batch_layer(tmp_path)
    by_batch, by_entry = _Recorder(), _Recorder()
    commit, blob, _ = _commit_layer(layer, tmp_path / "batch.gz",
                                    backend_id, session=by_batch)
    _commit_layer(layer, tmp_path / "entry.gz", backend_id,
                  session=by_entry, sink_cls=_TPUSink)
    tar = b"".join(by_batch.pieces)
    assert tar == b"".join(by_entry.pieces) == zlib.decompress(blob, 31)
    assert hashlib.sha256(tar).hexdigest() \
        == commit.digest_pair.tar_digest.hex()
    assert max(map(len, by_batch.pieces)) == _TAP
    # A call a filled slot, one more at the end of each of the two
    # batches and of the archive's end; the Python sink's tarfile
    # writes a header and then its content 16 KiB at a time.
    assert len(by_batch.pieces) <= len(tar) // _TAP + 3
    assert len(by_entry.pieces) > 2 * len(layer)


def _small_files(tmp_path, n=12, size=5_000):
    """``n`` files of ``size`` marked bytes, as a batch for
    ``add_entries``: [(tarinfo, path)]."""
    batch = []
    for i in range(n):
        path = tmp_path / f"f{i:02d}"
        path.write_bytes(((b"<file %02d>" % i) * (size // 9 + 1))[:size])
        hdr = tarfile.TarInfo(f"f{i:02d}")
        hdr.size = size
        hdr.mode = 0o644
        batch.append((hdr, str(path)))
    return batch


@pytest.mark.parametrize("fault", ["missing", "shrunk", "grown"])
def test_a_file_that_changed_under_a_batch_does_what_it_did_entry_by_entry(
        tmp_path, fault):
    """A file that is gone or shrank below its header raises ``OSError``
    naming its path, nothing of a later entry has reached the stream
    and the sink stays failed; a file that grew gives its first
    ``size`` bytes."""
    batch = _small_files(tmp_path)
    victim = batch[5][1]
    if fault == "missing":
        os.unlink(victim)
    elif fault == "shrunk":
        with open(victim, "wb") as f:
            f.write(b"x")
    else:
        with open(victim, "ab") as f:
            f.write(b"<grown>" * 1000)
    recorder = _Recorder()
    with open(tmp_path / "out.gz", "wb") as out:
        sink = NativeLayerSink(out, backend_id="zlib-6", session=recorder)
        tw = sink.open_tar()
        if fault == "grown":
            tw.add_entries(batch)
            tw.close()
            sink.finish()
        else:
            with pytest.raises(OSError) as err:
                tw.add_entries(batch)
            assert victim in str(err.value)
            assert ("shrank below its header size 5000"
                    in str(err.value)) == (fault == "shrunk")
            assert ("could not read" in str(err.value)) \
                == (fault == "missing")
            with pytest.raises(RuntimeError, match="write failed"):
                tw.add_entries(batch[6:])
            with pytest.raises(RuntimeError, match="write failed"):
                sink._handle.write(b"more")
            with pytest.raises(RuntimeError, match="finish failed"):
                sink._handle.finish()
            sink.abort()
    stream = b"".join(recorder.pieces)
    assert b"<file 04>" in stream and b"f05" in stream
    if fault == "grown":
        with tarfile.open(tmp_path / "out.gz") as tf:
            assert [m.size for m in tf] == [5_000] * 12
            assert b"<grown>" not in tf.extractfile("f05").read()
            assert tf.extractfile("f06").read().startswith(b"<file 06>")
    else:
        assert b"<file 06>" not in stream and b"f06" not in stream


def test_a_tap_that_raises_fails_the_commit_at_the_batch_call(tmp_path):
    batch = _small_files(tmp_path, n=80, size=10_000)  # three tap slots

    class BadSession(_Recorder):
        def update(self, data):
            raise RuntimeError("device fell over")

    with open(tmp_path / "out.gz", "wb") as out:
        sink = NativeLayerSink(out, backend_id="zlib-6",
                               session=BadSession())
        with pytest.raises(RuntimeError, match="chunk tap failed"):
            sink.open_tar().add_entries(batch)
        sink.abort()


def test_abort_in_the_middle_of_a_batch_joins_the_readers(tmp_path):
    """A commit that dies in a batch (a file is gone) leaves through
    the writer's ``__exit__``, which aborts the sink: no thread of it
    is alive after, no descriptor open, and ``out`` closes on nothing."""
    batch = _small_files(tmp_path, n=40)
    os.unlink(batch[30][1])
    readers = _sink_threads("lsk-read")
    compressors = _compressor_threads()
    fds = len(os.listdir("/proc/self/fd"))
    with open(tmp_path / "out.gz", "wb") as out:
        sink = NativeLayerSink(out, backend_id="zlib-6")
        with pytest.raises(OSError, match="f30"):
            with sink.open_tar() as tw:
                tw.add_entries(batch[:20])
                assert _sink_threads("lsk-read") == readers + _READERS
                tw.add_entries(batch[20:])
        assert _sink_threads("lsk-read") == readers
        assert _compressor_threads() == compressors
        assert len(os.listdir("/proc/self/fd")) == fds + 1  # ``out``
        number = out.fileno()
    _stays_empty(tmp_path / "other", number)


@pytest.mark.parametrize("backend_id", _BACKENDS)
def test_prefetch_counts_add_up_to_the_files_with_content_once_a_layer(
        tmp_path, backend_id):
    """``ready`` + ``waited`` + ``streamed`` is every regular file with
    content, added at the sink's ``finish``; the one file over 8 MiB
    streams on the writer's thread; the seconds blocked on a reader
    are a stage of their own."""
    if backend_id.startswith("pgzip") and not native.pgzip_available():
        pytest.skip("pgzip not built")
    from makisu_tpu.utils import metrics
    layer, with_content = _batch_layer(tmp_path)
    registry = metrics.MetricsRegistry()
    token = metrics.set_build_registry(registry)
    try:
        with open(tmp_path / "out.gz", "wb") as f:
            sink = NativeLayerSink(f, backend_id=backend_id)
            with sink.open_tar() as tw:
                layer.commit(tw)
            assert registry.counter_total(
                metrics.SINK_PREFETCH_FILES_TOTAL) == 0
            assert sink._handle.prefetch_stats()[3] == 1
            sink.finish()
    finally:
        metrics.reset_build_registry(token)
    files = registry.counter_by_label(metrics.SINK_PREFETCH_FILES_TOTAL,
                                      "result")
    assert set(files) <= {"ready", "waited", "streamed"}
    assert files["streamed"] == 1
    assert sum(files.values()) == with_content
    busy = registry.counter_by_label(metrics.COMMIT_STAGE_BUSY, "stage")
    assert 0 <= busy["read_wait"] < 5


def test_a_batch_of_one_file_streams_it_and_starts_no_reader(tmp_path):
    """What the sink adapts to is what it sees: a batch with fewer than
    two files for the readers goes the per-entry way."""
    batch = _small_files(tmp_path, n=3)
    readers = _sink_threads("lsk-read")
    with open(tmp_path / "out.gz", "wb") as out:
        sink = NativeLayerSink(out, backend_id="zlib-6")
        with sink.open_tar() as tw:
            for item in batch:
                tw.add_entries([(tarfile.TarInfo("d"), None), item])
            assert _sink_threads("lsk-read") == readers
            assert sink._handle.prefetch_stats()[1:] == (0, 0, 3)
        sink.finish()
    with tarfile.open(tmp_path / "out.gz") as tf:
        assert [m.name for m in tf] == ["d", "f00", "d", "f01", "d", "f02"]


def test_many_threads_commit_batches_at_once(tmp_path):
    """Twelve building threads, each with a sink and readers of its
    own, on an interpreter that switches often: every blob is the one
    a lone commit gives, no sink runs more than its four readers, and
    none is left."""
    import sys
    import time
    batch = _small_files(tmp_path, n=40, size=30_000)

    def commit(i):
        with open(tmp_path / f"t{i}.gz", "wb") as out:
            sink = NativeLayerSink(out, backend_id="zlib-6",
                                   session=_Recorder())
            with sink.open_tar() as tw:
                for at in range(0, len(batch), 8):
                    tw.add_entries(batch[at:at + 8])
            return sink.finish().digest_pair

    alone = commit(0)
    baseline = _sink_threads("lsk-read")
    results, most = [], 0
    workers = [threading.Thread(target=lambda i=i: results.append(commit(i)),
                                daemon=True) for i in range(1, 13)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in workers:
            t.start()
        deadline = time.monotonic() + 60
        while any(t.is_alive() for t in workers):
            assert time.monotonic() < deadline, "a commit hangs"
            most = max(most, _sink_threads("lsk-read") - baseline)
            time.sleep(0.005)
    finally:
        sys.setswitchinterval(interval)
    assert results == [alone] * 12
    # (How many the polling saw is the scheduler's: none, when the
    # commits end between two looks.)
    assert most <= 12 * _READERS
    assert _sink_threads("lsk-read") == baseline


# What the parent commit (86bb072, zlib in line with the writer) gave
# for the stream of ``_interleaved_plan`` (recorded by running
# ``_golden_row`` on a checkout of it, PR 33): level -> (tar digest,
# blob digest under zlib _GOLDEN_ZLIB, blob size), then the chunk list
# the TPU hasher's tap cut from it (count, sha-256 of its JSON). The
# compressor thread may change none of them.
_GOLDEN_ZLIB = "1.2.13"
_GOLDEN = {
    1: ("d6cd5810637b32bfa9e7df35fec2b062"
        "ad6c7ca280aa3898e6d1c835e84ea4b8",
        "e6794471c2c25e1644b4e9ebbc696853"
        "55bb53806867b8f9832c6cdd12ca253a", 5_960_146),
    2: ("d6cd5810637b32bfa9e7df35fec2b062"
        "ad6c7ca280aa3898e6d1c835e84ea4b8",
        "88744b587b645742c63f65fb7e25a8b9"
        "7d62637f07be227e76b24c8ff36c6bec", 5_984_673),
    3: ("d6cd5810637b32bfa9e7df35fec2b062"
        "ad6c7ca280aa3898e6d1c835e84ea4b8",
        "bc0275e3611b658c95ca5c80e57e1905"
        "fb4cfd72e6bbcab0f5eb6a23f1eac490", 5_981_434),
    4: ("d6cd5810637b32bfa9e7df35fec2b062"
        "ad6c7ca280aa3898e6d1c835e84ea4b8",
        "eddf27f2f92cb2e224b2e3698a1db6dd"
        "eebc6f3dfba06d7696c6f2c55ea0038e", 5_755_627),
    5: ("d6cd5810637b32bfa9e7df35fec2b062"
        "ad6c7ca280aa3898e6d1c835e84ea4b8",
        "54f384db98200ee5f81b6e3acb24c28d"
        "b1f5afcd313a509575e1871080597f50", 5_759_262),
    6: ("d6cd5810637b32bfa9e7df35fec2b062"
        "ad6c7ca280aa3898e6d1c835e84ea4b8",
        "f0fabc4bff9ad49d14e84fcfc7b29986"
        "85029e34ff6cfcf65010ba6f89a5579d", 5_759_303),
    7: ("d6cd5810637b32bfa9e7df35fec2b062"
        "ad6c7ca280aa3898e6d1c835e84ea4b8",
        "f0fabc4bff9ad49d14e84fcfc7b29986"
        "85029e34ff6cfcf65010ba6f89a5579d", 5_759_303),
    8: ("d6cd5810637b32bfa9e7df35fec2b062"
        "ad6c7ca280aa3898e6d1c835e84ea4b8",
        "f0fabc4bff9ad49d14e84fcfc7b29986"
        "85029e34ff6cfcf65010ba6f89a5579d", 5_759_303),
    9: ("d6cd5810637b32bfa9e7df35fec2b062"
        "ad6c7ca280aa3898e6d1c835e84ea4b8",
        "ab6d67aa9d904ce07b8419a75940ab3c"
        "577bb0b78dfa2e0f63c90dbb7fac0aa1", 5_759_303),
}
_GOLDEN_CHUNKS = (
    1_865, "7d6c64c11e868693a33856701e5d16b6"
    "2e030dc435a9e5e6cd7faddbc29c21b6")


def _golden_row(tmp_path, level, hasher=None):
    from makisu_tpu.chunker import CPUHasher
    with open(tmp_path / f"golden{level}.gz", "wb") as f:
        sink = (hasher or CPUHasher()).open_layer(
            f, backend_id=f"zlib-{level}")
        assert isinstance(sink, NativeLayerSink)
        for _ in _feed(sink._handle, tmp_path, _interleaved_plan()):
            pass
        commit = sink.finish()
    pair = commit.digest_pair
    chunks = [[c.offset, c.length, c.hex_digest] for c in commit.chunks]
    return ((pair.tar_digest.hex(), pair.gzip_descriptor.digest.hex(),
             pair.gzip_descriptor.size),
            (len(chunks),
             hashlib.sha256(json.dumps(chunks).encode()).hexdigest()))


@pytest.mark.parametrize("level", range(1, 10))
def test_digests_sizes_and_chunks_are_the_parents(tmp_path, level):
    from makisu_tpu.chunker import TPUHasher
    (tar, blob, size), chunks = _golden_row(
        tmp_path, level, TPUHasher() if level == 6 else None)
    assert tar == _GOLDEN[level][0]
    if level == 6:
        assert chunks == _GOLDEN_CHUNKS
    # The blob is zlib's; another zlib may deflate otherwise.
    if zlib.ZLIB_RUNTIME_VERSION == _GOLDEN_ZLIB:
        assert (blob, size) == _GOLDEN[level][1:]


def test_zlib0_never_chooses_native(tmp_path):
    """zlib level 0 stored-block framing is write-granularity-dependent,
    and the C++ pipeline writes at a different granularity than the
    pinned Python path — the sink selector must refuse native there or
    cache identity splits by host capability (advisor round-2 medium)."""
    from makisu_tpu.chunker.hasher import CPUHasher, TPUHasher, _use_native
    with open(tmp_path / "out.tar.gz", "wb") as f:
        assert _use_native(f, "zlib-6")  # control: fd + native available
        assert not _use_native(f, "zlib-0")
        assert isinstance(CPUHasher().open_layer(f, backend_id="zlib-0"),
                          LayerSink)
        sink = TPUHasher().open_layer(f, backend_id="zlib-0")
        assert isinstance(sink, LayerSink)
        assert not isinstance(sink, NativeLayerSink)
