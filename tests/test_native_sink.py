"""Native layer pipeline (native/layersink.cpp): byte-identity with the
Python pipeline is cache-identity-bearing — layer digests must not
depend on which sink produced them."""

import hashlib
import io
import os
import tarfile

import pytest

from makisu_tpu import native, tario
from makisu_tpu.chunker.hasher import LayerSink, NativeLayerSink

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


def _commit(sink_cls, root, path, backend_id):
    entries = _entries(root)
    with open(path, "wb") as f:
        sink = sink_cls(f, backend_id=backend_id)
        with sink.open_tar() as tw:
            for src, hdr in entries:
                tario.write_entry(tw, src, hdr)
        return sink.finish()


@pytest.mark.parametrize("backend_id", ["zlib-6", "zlib-1", "zlib-9",
                                        "pgzip-6-131072"])
def test_native_matches_python_bytes_and_digests(tmp_path, backend_id):
    if backend_id.startswith("pgzip") and not native.pgzip_available():
        pytest.skip("pgzip not built")
    root = _tree(tmp_path)
    py_path = str(tmp_path / "py.tar.gz")
    nat_path = str(tmp_path / "native.tar.gz")
    py = _commit(LayerSink, root, py_path, backend_id)
    nat = _commit(NativeLayerSink, root, nat_path, backend_id)
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
            tw.add_path(hdr, str(victim))


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
        entries = _entries(root)
        with open(path, "wb") as f:
            sink = TPUHasher().open_layer(f, backend_id="zlib-6")
            if native_on:
                assert isinstance(sink, NativeLayerSink)
            with sink.open_tar() as tw:
                for src, hdr in entries:
                    tario.write_entry(tw, src, hdr)
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
