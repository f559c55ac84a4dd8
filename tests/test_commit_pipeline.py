"""Multicore layer-commit pipeline: determinism and stage mechanics.

The tentpole invariant: the pipeline's worker count is a PERFORMANCE
knob, never an identity knob. Committing the same context with
``--hash-workers 1`` and ``--hash-workers 8`` must produce identical
layer tar bytes, identical gzip blobs, identical chunk boundaries, and
identical ``LayerCommit`` digests — chunk fingerprints are cache keys,
so any divergence would split the distributed cache by host core
count.

Also the CI marker for the fastest route: the native gear scan +
pgzip compression path runs here end to end, so the production-speed
pipeline is exercised by tier-1, not just the pure-Python fallbacks.
"""

import contextlib
import hashlib
import os
import tarfile

import numpy as np
import pytest

from makisu_tpu import native, tario
from makisu_tpu.chunker import get_hasher
from makisu_tpu.chunker.cdc import BLOCK, ChunkSession
from makisu_tpu.snapshot.layer import Layer, _ReadAhead
from makisu_tpu.utils import concurrency, metrics


@contextlib.contextmanager
def hash_workers(n):
    token = concurrency.set_hash_workers(n)
    try:
        yield
    finally:
        concurrency.reset_hash_workers(token)


def _tree(tmp_path, seed=7):
    """A context with enough content to cross chunk/block boundaries:
    one multi-MB file (many CDC chunks), a spread of small files (the
    read-ahead pool's bread and butter), and the tar corner cases."""
    root = tmp_path / f"tree{seed}"
    root.mkdir()
    rnd = np.random.default_rng(seed)
    (root / "big.bin").write_bytes(
        rnd.integers(0, 256, size=5_000_000, dtype=np.uint8).tobytes())
    for i in range(40):
        (root / f"f{i:02d}.dat").write_bytes(
            rnd.integers(0, 256, size=3_000 + 731 * i,
                         dtype=np.uint8).tobytes())
    (root / "empty").write_bytes(b"")
    sub = root / "sub"
    sub.mkdir()
    (sub / "nested.txt").write_bytes(b"nested content\n")
    (root / "link").symlink_to("empty")
    return root


def _layer_for(root):
    from makisu_tpu.snapshot.walk import tarinfo_from_stat, walk
    from makisu_tpu.utils import pathutils
    layer = Layer()
    entries = []

    def one(path, st):
        if path == str(root):
            return
        dst = pathutils.trim_root(path, str(root))
        hdr = tarinfo_from_stat(path, pathutils.rel_path(dst), str(root))
        entries.append((path, dst, hdr))

    walk(str(root), None, one)
    for path, dst, hdr in entries:
        layer.add_header(path, dst, hdr)
    return layer


def _commit(root, path, backend_id, workers, hasher="tpu"):
    layer = _layer_for(root)
    with hash_workers(workers):
        with open(path, "wb") as out:
            sink = get_hasher(hasher).open_layer(out,
                                                 backend_id=backend_id)
            with sink.open_tar() as tw:
                layer.commit(tw, workers=workers)
            return sink.finish()


def _identity(commit, path):
    with open(path, "rb") as f:
        blob = f.read()
    return (
        str(commit.digest_pair.tar_digest),
        str(commit.digest_pair.gzip_descriptor.digest),
        commit.digest_pair.gzip_descriptor.size,
        [(c.offset, c.length, c.hex_digest) for c in commit.chunks],
        hashlib.sha256(blob).hexdigest(),
    )


@pytest.mark.skipif(not native.gear_scan_available(),
                    reason="libgear.so not built")
@pytest.mark.parametrize("backend_id", ["zlib-6", "pgzip-6-131072"])
def test_commit_identical_across_worker_counts(tmp_path, backend_id):
    """workers=1 vs workers=8 through the full sink (native pipeline
    when available, incl. the pgzip route): identical layer tar bytes,
    blob bytes, digests, and chunk fingerprints."""
    if backend_id.startswith("pgzip") and not native.pgzip_available():
        pytest.skip("pgzip not built")
    root = _tree(tmp_path)
    serial = str(tmp_path / "serial.tar.gz")
    pooled = str(tmp_path / "pooled.tar.gz")
    c1 = _commit(root, serial, backend_id, workers=1)
    c8 = _commit(root, pooled, backend_id, workers=8)
    assert c1.chunks, "TPU hasher must produce chunk fingerprints"
    assert _identity(c1, serial) == _identity(c8, pooled)


@pytest.mark.skipif(not native.gear_scan_available(),
                    reason="libgear.so not built")
def test_commit_identical_python_sink_buffer_readahead(tmp_path,
                                                       monkeypatch):
    """The pure-Python sink takes the BUFFER read-ahead mode
    (prefetched bytes handed to tarfile directly); bytes must still be
    identical to the serial commit."""
    monkeypatch.setenv("MAKISU_TPU_NATIVE_SINK", "0")
    root = _tree(tmp_path, seed=9)
    serial = str(tmp_path / "serial.tar.gz")
    pooled = str(tmp_path / "pooled.tar.gz")
    c1 = _commit(root, serial, "zlib-6", workers=1)
    c8 = _commit(root, pooled, "zlib-6", workers=8)
    assert _identity(c1, serial) == _identity(c8, pooled)


@pytest.mark.skipif(not native.gear_scan_available(),
                    reason="libgear.so not built")
def test_chunk_session_identity_across_workers():
    """Direct ChunkSession sweep over a stream crossing the 4MiB
    dispatch block: pooled scans + batched SHA yield the exact serial
    boundaries and digests (awkward feed sizes included)."""
    rng = np.random.default_rng(21)
    payload = rng.integers(0, 256, size=BLOCK + 333_333,
                           dtype=np.uint8).tobytes()
    s1 = ChunkSession(workers=1)
    s1.update(payload)
    serial = s1.finish()
    s8 = ChunkSession(workers=8)
    for i in range(0, len(payload), 100_001):
        s8.update(payload[i:i + 100_001])
    pooled = s8.finish()
    assert [(c.offset, c.length, c.hex) for c in serial] == \
        [(c.offset, c.length, c.hex) for c in pooled]
    for c in pooled[:3] + pooled[-3:]:
        assert hashlib.sha256(
            payload[c.offset:c.offset + c.length]).digest() == c.digest


@pytest.mark.skipif(not native.sha_batch_available(),
                    reason="libgear.so sha batch not built")
@pytest.mark.parametrize("level", ["scalar", "striped", "simd"])
def test_chunk_session_identity_across_isa_levels(level):
    """The MAKISU_TPU_NATIVE_ISA ladder is a throughput knob only:
    every ISA level × worker count must reproduce the auto route's
    exact chunk boundaries and digests (the byte-identity the CI
    fastest-route step sweeps with the env knob)."""
    if native.isa_route() is None:
        pytest.skip("ISA dispatch ABI unavailable")
    rng = np.random.default_rng(27)
    payload = rng.integers(0, 256, size=2_000_000,
                           dtype=np.uint8).tobytes()
    try:
        native.set_native_isa("auto")
        s = ChunkSession(workers=1)
        s.update(payload)
        ref = [(c.offset, c.length, c.hex) for c in s.finish()]
        assert ref
        native.set_native_isa(level)
        for workers in (1, 4):
            s = ChunkSession(workers=workers)
            for i in range(0, len(payload), 100_001):
                s.update(payload[i:i + 100_001])
            got = [(c.offset, c.length, c.hex) for c in s.finish()]
            assert got == ref, (level, workers)
    finally:
        native.set_native_isa("auto")


@pytest.mark.skipif(not native.sha_batch_available(),
                    reason="libgear.so sha batch not built")
def test_native_sha256_batch_matches_hashlib():
    rng = np.random.default_rng(3)
    datas = [rng.integers(0, 256, size=s, dtype=np.uint8).tobytes()
             for s in (0, 1, 55, 64, 65, 8191, 65_536)]
    digests = native.sha256_batch(b"".join(datas),
                                  [len(d) for d in datas])
    for d, got in zip(datas, digests):
        assert hashlib.sha256(d).digest() == got.tobytes()


def test_read_ahead_buffer_and_fallback(tmp_path):
    from makisu_tpu.snapshot.walk import tarinfo_from_stat
    good = tmp_path / "good.bin"
    good.write_bytes(b"g" * 10_000)
    shrunk = tmp_path / "shrunk.bin"
    shrunk.write_bytes(b"s" * 5_000)

    def entry(p):
        from makisu_tpu.snapshot.layer import ContentEntry
        hdr = tarinfo_from_stat(str(p), p.name, str(tmp_path))
        return ContentEntry(str(p), "/" + p.name, hdr)

    e_good, e_shrunk = entry(good), entry(shrunk)
    e_shrunk.hdr.size = 9_999  # header no longer matches the content
    ra = _ReadAhead([("/good.bin", e_good), ("/shrunk.bin", e_shrunk)],
                    workers=4)
    assert ra.take("/good.bin") == b"g" * 10_000
    # Mismatched size: advisory prefetch yields None — the writer falls
    # back to streaming, which owns that failure mode.
    assert ra.take("/shrunk.bin") is None
    assert ra.take("/never-queued") is None
    ra.close()


class _BatchWriter:
    """A tar writer that has ``add_entries`` and keeps what it got."""

    def __init__(self):
        self.batches = []
        self.offset = 0

    def add_entries(self, items):
        self.batches.append(list(items))


def _header_layer(sizes):
    """A layer of regular files that exist as headers only."""
    layer = Layer()
    for i, size in enumerate(sizes):
        hdr = tarfile.TarInfo(f"f{i:04d}")
        hdr.size = size
        layer.add_header(f"/nowhere/f{i:04d}", f"/f{i:04d}", hdr)
    return layer


def test_layer_commit_hands_a_batch_writer_runs_of_256_entries():
    """A writer that has ``add_entries`` gets the sorted entries in
    runs of at most 256, whiteouts and header-only entries in their
    sorted place with no path, and no entry goes through ``addfile``."""
    layer = _header_layer([100] * 600)
    layer.entries["/f0003"].hdr.size = 0          # an empty file
    layer.entries["/f0005"].hdr.type = tarfile.DIRTYPE
    layer.add_whiteout("/f0004x")
    tw = _BatchWriter()
    layer.commit(tw, workers=8)
    assert [len(b) for b in tw.batches] == [256, 256, 89]
    flat = [item for b in tw.batches for item in b]
    names = [f"f{i:04d}" for i in range(600)]
    names.insert(5, ".wh.f0004x")  # where the deleted path sorts
    assert [hdr.name for hdr, _ in flat] == names
    paths = {hdr.name: path for hdr, path in flat}
    assert paths["f0002"] == "/nowhere/f0002"
    assert paths["f0003"] is None and paths["f0005"] is None
    assert paths[".wh.f0004x"] is None


def test_layer_commit_closes_a_batch_at_16_mib_of_content():
    """A run also ends with the entry that takes its content to 16 MiB
    (what the sink's read-ahead ring holds): a huge file closes the
    batch it is in, header-only entries count for nothing."""
    mib = 1 << 20
    layer = _header_layer([6 * mib, 6 * mib, 6 * mib, 1, 64 * mib, 2, 3])
    layer.entries["/f0005"].hdr.type = tarfile.SYMTYPE  # size is no content
    tw = _BatchWriter()
    layer.commit(tw)
    assert [[hdr.name for hdr, _ in b] for b in tw.batches] == [
        ["f0000", "f0001", "f0002"], ["f0003", "f0004"],
        ["f0005", "f0006"]]


@pytest.mark.skipif(not native.layersink_available()
                    or not native.gear_scan_available(),
                    reason="native libraries not built")
@pytest.mark.parametrize("backend_id", ["zlib-6", "pgzip-6-131072"])
def test_commit_by_the_batch_makes_no_read_ahead_and_the_same_layer(
        tmp_path, monkeypatch, backend_id):
    """Through the native sink ``Layer.commit`` makes no ``_ReadAhead``
    (the sink reads ahead on threads of its own) and hands the writer
    two batches; tar, blob, digests and chunks are those of the Python
    sink (``MAKISU_TPU_NATIVE_SINK=0``), which takes the entries one
    by one with the read-ahead handing it their bytes."""
    if backend_id.startswith("pgzip") and not native.pgzip_available():
        pytest.skip("pgzip not built")
    from makisu_tpu.snapshot import layer as layer_mod
    root = _tree(tmp_path)
    for i in range(300):
        (root / "sub" / f"s{i:03d}").write_bytes(b"%03d" % i * (i + 1))
    made, batches = [], []
    init = _ReadAhead.__init__
    monkeypatch.setattr(
        layer_mod._ReadAhead, "__init__",
        lambda self, items, workers: (
            made.append(len(items)), init(self, items, workers))[1])
    from makisu_tpu.chunker.hasher import _NativeTarWriter
    add = _NativeTarWriter.add_entries
    monkeypatch.setattr(
        _NativeTarWriter, "add_entries",
        lambda self, items: (batches.append(len(items)),
                             add(self, items))[1])
    batched = str(tmp_path / "batched.tar.gz")
    by_batch = _commit(root, batched, backend_id, workers=8)
    assert made == []
    assert batches == [256, sum(batches) - 256]
    monkeypatch.setenv("MAKISU_TPU_NATIVE_SINK", "0")
    entrywise = str(tmp_path / "entrywise.tar.gz")
    by_entry = _commit(root, entrywise, backend_id, workers=8)
    assert len(batches) == 2 and made == [342]  # the files with content
    assert by_batch.chunks
    assert _identity(by_batch, batched) == _identity(by_entry, entrywise)


@pytest.mark.skipif(not native.sha_batch_available(),
                    reason="libgear.so sha batch not built")
def test_stage_metrics_recorded_for_pooled_commit():
    """With workers > 1 the per-stage busy counters land in the build
    registry — the series `makisu-tpu report` ranks to name the
    bottleneck."""
    reg = metrics.MetricsRegistry()
    token = metrics.set_build_registry(reg)
    try:
        rng = np.random.default_rng(5)
        payload = rng.integers(0, 256, size=6_000_000,
                               dtype=np.uint8).tobytes()
        s = ChunkSession(workers=4)
        s.update(payload)
        assert s.finish()
    finally:
        metrics.reset_build_registry(token)
    assert reg.counter_total(metrics.COMMIT_STAGE_BUSY,
                             stage="gear_scan") > 0
    assert reg.counter_total(metrics.COMMIT_STAGE_BUSY,
                             stage="chunk_sha") > 0
    assert reg.counter_total("makisu_bytes_hashed_total",
                             backend="native") == len(payload)


def test_report_names_commit_bottleneck():
    from makisu_tpu.utils import traceexport
    reg = metrics.MetricsRegistry()
    token = metrics.set_build_registry(reg)
    try:
        with metrics.span("build"):
            metrics.stage_busy_add("tar_write", 1.5)
            metrics.stage_busy_add("chunk_sha", 4.0)
            metrics.stage_busy_add("compress", 0.5)
    finally:
        metrics.reset_build_registry(token)
    text = traceexport.render_report(reg.report())
    lines = text.splitlines()
    idx = lines.index("commit pipeline stages (busy time):")
    assert "chunk_sha" in lines[idx + 1]
    assert "bottleneck" in lines[idx + 1]


def test_hash_workers_config(monkeypatch):
    monkeypatch.setenv("MAKISU_TPU_HASH_WORKERS", "3")
    assert concurrency.hash_workers() == 3
    token = concurrency.set_hash_workers(5)
    assert concurrency.hash_workers() == 5
    concurrency.reset_hash_workers(token)
    assert concurrency.hash_workers() == 3
    monkeypatch.setenv("MAKISU_TPU_HASH_WORKERS", "junk")
    assert concurrency.hash_workers() == concurrency.default_hash_workers()


def test_hash_linger_config(monkeypatch):
    monkeypatch.setenv("MAKISU_TPU_HASH_LINGER_MS", "7.5")
    assert concurrency.hash_linger_ms() == 7.5
    concurrency.set_hash_linger_ms(1.25)
    try:
        assert concurrency.hash_linger_ms() == 1.25
        from makisu_tpu.chunker.service import HashService
        svc = HashService()
        try:
            assert svc.linger == pytest.approx(0.00125)
        finally:
            svc.close()
    finally:
        concurrency.set_hash_linger_ms(None)
    assert concurrency.hash_linger_ms() == 7.5


@contextlib.contextmanager
def compress_workers(n):
    token = concurrency.set_compress_workers(n)
    try:
        yield
    finally:
        concurrency.reset_compress_workers(token)


def test_block_gzip_writer_identical_at_every_worker_count():
    """The block-parallel compress stage's tentpole invariant: lane
    count is a THROUGHPUT knob — output bytes are a pure function of
    (content, level, block size) at workers 1/4/8, and they decompress
    back to the input."""
    import gzip as gzip_mod
    import io
    rng = np.random.default_rng(33)
    payload = rng.integers(0, 256, size=3_000_000,
                           dtype=np.uint8).tobytes()
    outs = {}
    for workers in (1, 4, 8):
        buf = io.BytesIO()
        w = tario.BlockGzipWriter(buf, level=6, block_size=131072,
                                  workers=workers)
        for i in range(0, len(payload), 37_001):  # ragged writes
            w.write(payload[i:i + 37_001])
        w.close()
        outs[workers] = buf.getvalue()
    assert outs[1] == outs[4] == outs[8]
    assert gzip_mod.decompress(outs[1]) == payload


@pytest.mark.skipif(not native.pgzip_available(),
                    reason="libpgzip.so not built")
def test_block_codecs_byte_identical():
    """The stdlib-zlib codec and the native multi-block entry emit the
    SAME slice bytes — the equivalence that makes pgzip backend ids
    replayable on hosts without the native library (cache identity
    must not depend on which codec ran). Swept over the seams: empty,
    sub-block, exact block multiples, ragged tails."""
    if not native.pgzip_available():
        pytest.skip("libpgzip.so predates the multi-block entry")
    rng = np.random.default_rng(37)
    blob = rng.integers(0, 256, size=131072 * 3 + 17,
                        dtype=np.uint8).tobytes()
    for n in (0, 1, 5_000, 131072, 131072 * 2, 131072 * 2 + 5,
              len(blob)):
        data = blob[:n]
        assert native.deflate_blocks(data, 6, 131072, True) == \
            tario._py_deflate_blocks(data, 6, 131072, True), n
    # Non-final batches (whole blocks only) too.
    data = blob[:131072 * 2]
    assert native.deflate_blocks(data, 6, 131072, False) == \
        tario._py_deflate_blocks(data, 6, 131072, False)
    # And the writer's stitched stream matches the one-shot native
    # compressor (the framing contract layersink.cpp shares).
    import io
    buf = io.BytesIO()
    w = tario.BlockGzipWriter(buf, level=6, block_size=131072,
                              workers=4)
    w.write(blob)
    w.close()
    with io.BytesIO() as legacy:
        with native.PgzipWriter(legacy, level=6) as lw:
            lw.write(blob)
        assert buf.getvalue() == legacy.getvalue()


@pytest.mark.skipif(not native.gear_scan_available(),
                    reason="libgear.so not built")
@pytest.mark.parametrize("backend_id", ["zlib-6", "pgzip-6-131072"])
def test_commit_identical_across_compress_worker_counts(tmp_path,
                                                        backend_id):
    """Full-sink sweep over the COMPRESS workers knob (the block-
    parallel deflate stage): digests identical at lanes 1 vs 4 on both
    backends — zlib's continuous stream by construction, pgzip's block
    stream by the _BlockBuffer determinism contract."""
    root = _tree(tmp_path, seed=13)
    ident = {}
    for lanes in (1, 4):
        path = str(tmp_path / f"lanes{lanes}.tar.gz")
        with compress_workers(lanes):
            commit = _commit(root, path, backend_id, workers=4)
        ident[lanes] = _identity(commit, path)
    assert ident[1] == ident[4]


def test_compress_stage_busy_recorded_for_block_writer():
    """The block-parallel stage feeds the same stage-busy series the
    report's bottleneck ranking reads (lane tasks self-report)."""
    import io
    reg = metrics.MetricsRegistry()
    token = metrics.set_build_registry(reg)
    try:
        rng = np.random.default_rng(41)
        payload = rng.integers(0, 256, size=2_000_000,
                               dtype=np.uint8).tobytes()
        w = tario.BlockGzipWriter(io.BytesIO(), level=6,
                                  block_size=131072, workers=4)
        w.write(payload)
        w.close()
    finally:
        metrics.reset_build_registry(token)
    assert reg.counter_total(metrics.COMMIT_STAGE_BUSY,
                             stage=metrics.COMPRESS_STAGE) > 0
    assert reg.counter_total(metrics.COMPRESS_BLOCKS,
                             backend="pgzip") >= 16


def test_compress_workers_config(monkeypatch):
    monkeypatch.setenv("MAKISU_TPU_COMPRESS_WORKERS", "3")
    assert concurrency.compress_workers() == 3
    token = concurrency.set_compress_workers(5)
    assert concurrency.compress_workers() == 5
    concurrency.reset_compress_workers(token)
    assert concurrency.compress_workers() == 3
    monkeypatch.setenv("MAKISU_TPU_COMPRESS_WORKERS", "junk")
    assert concurrency.compress_workers() == \
        concurrency.default_compress_workers()


def test_gzip_backend_auto_resolves_concrete():
    resolved = tario.resolve_backend("auto")
    assert resolved == ("pgzip" if native.pgzip_available() else "zlib")
    backend_id = tario.make_backend_id("auto", "default")
    # Only concrete backends enter cache identity.
    assert backend_id.startswith(resolved)
    assert tario.backend_id_usable(backend_id)
    assert tario.resolve_backend("zlib") == "zlib"


def test_exists_prefetch_memo(tmp_path):
    from makisu_tpu.cache.chunks import ChunkStore
    store = ChunkStore(str(tmp_path / "cas"))
    store.PROBE_BATCH = 2  # probes batch (default 256/task); force one
    data = b"chunk-bytes" * 100
    digest = hashlib.sha256(data).hexdigest()
    store.put(digest, data)
    missing = hashlib.sha256(b"absent").hexdigest()
    store.note_fingerprint(digest)
    store.note_fingerprint(missing)
    concurrency.hash_pool().submit(lambda: None).result()  # drain
    import time
    for _ in range(100):
        with store._memo_lock:
            if store._exists_memo.get(digest):
                break
        time.sleep(0.01)
    assert store._probed(digest) is True
    # The probe looked and found nothing: index_layer writes without a
    # second stat (a stale miss costs a write of identical bytes).
    assert store._probed(missing) is False
    store.reset_fingerprint_memo()
    assert store._probed(digest) is None  # index_layer's window stats


def test_observer_streams_fingerprints_from_session():
    from makisu_tpu.chunker import cdc
    seen = []
    token = cdc.set_chunk_observer(seen.append)
    try:
        rng = np.random.default_rng(11)
        payload = rng.integers(0, 256, size=600_000,
                               dtype=np.uint8).tobytes()
        s = ChunkSession(workers=1)
        s.update(payload)
        chunks = s.finish()
    finally:
        cdc.reset_chunk_observer(token)
    assert sorted(seen) == sorted(c.hex for c in chunks)


def test_pooled_route_respects_serial_floor(monkeypatch):
    """workers=1 must be EXACTLY the serial pipeline: no pool, classic
    inline hashing."""
    s = ChunkSession(workers=1)
    assert s._pool is None
    # And the sub-4-core default keeps small hosts serial.
    monkeypatch.setattr(os, "cpu_count", lambda: 2)
    assert concurrency.default_hash_workers() == 1
    monkeypatch.setattr(os, "cpu_count", lambda: 16)
    assert concurrency.default_hash_workers() == 8


# -- the streamed probe on the device routes (PR 31) -------------------------

@pytest.fixture
def device_formulation(monkeypatch):
    """The device routes on the JAX CPU backend (the shapes of
    test_spans_plane.py, so the compiled programs are shared)."""
    monkeypatch.setenv("MAKISU_TPU_CHUNK_NATIVE", "0")
    monkeypatch.setenv("MAKISU_TPU_PALLAS", "1")


def _observed_session(seed, service=None):
    """A session over 300,000 random bytes whose observer was bound in
    the context only while it was constructed: (session, what the
    observer saw)."""
    from makisu_tpu.chunker import cdc
    seen = []
    token = cdc.set_chunk_observer(seen.append)
    try:
        session = ChunkSession(block=128 * 1024, service=service)
    finally:
        cdc.reset_chunk_observer(token)
    session.update(np.random.default_rng(seed).integers(
        0, 256, size=300_000, dtype=np.uint8).tobytes())
    return session, seen


def _wait_for(n, seen):
    """A shared-service future wakes its waiter before it runs its
    callbacks: the last digests can land just after finish()."""
    import time
    deadline = time.monotonic() + 10
    while len(seen) < n and time.monotonic() < deadline:
        time.sleep(0.01)


def test_lane_batcher_route_streams_every_digest_once(device_formulation):
    session, seen = _observed_session(31)
    assert session._batchers and not session._native
    chunks = session.finish()
    assert len(chunks) > 20
    assert sorted(seen) == sorted(c.hex for c in chunks)
    assert len(set(seen)) == len(seen)


def test_lane_batcher_route_without_an_observer_notifies_nobody(
        device_formulation):
    from makisu_tpu.chunker import cdc
    token = cdc.set_chunk_observer(None)  # whatever an earlier test left
    try:
        session = ChunkSession(block=128 * 1024)
    finally:
        cdc.reset_chunk_observer(token)
    assert session._batchers
    assert all(b.notify is None for b in session._batchers)


def test_service_route_streams_each_sessions_digests_to_its_own_observer(
        device_formulation):
    import threading
    from makisu_tpu.chunker.service import HashService
    service = HashService(linger_seconds=0.02)
    try:
        sessions = [_observed_session(seed, service) for seed in (32, 33)]
        results = [None, None]

        def finish(i):
            results[i] = sessions[i][0].finish()

        threads = [threading.Thread(target=finish, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for (session, seen), chunks in zip(sessions, results):
            assert session.service is service and not session._batchers
            assert len(chunks) > 20
            _wait_for(len(chunks), seen)
            assert sorted(seen) == sorted(c.hex for c in chunks)
        assert not set(sessions[0][1]) & set(sessions[1][1])
    finally:
        service.close()


def test_service_route_failure_reaches_finish_not_the_observer(
        device_formulation, monkeypatch):
    from makisu_tpu.chunker import route as route_mod
    from makisu_tpu.chunker.service import HashService

    def boom(*a, **k):
        raise RuntimeError("lane program refused (simulated)")

    monkeypatch.setattr(route_mod, "hash_lanes", boom)
    monkeypatch.delenv("MAKISU_TPU_CHUNK_STRICT", raising=False)
    service = HashService(linger_seconds=0.02)
    try:
        session, seen = _observed_session(34, service)
        with pytest.raises(RuntimeError, match="refused"):
            session.finish()
        assert seen == []
    finally:
        service.close()


def test_streamed_digests_fill_the_stores_memo_on_a_device_route(
        tmp_path, device_formulation):
    """End to end for the store: a second session over the same bytes
    on the shared-service route leaves the memo saying True for every
    chunk the first stored, and index_layer then stats nothing."""
    import gzip
    from makisu_tpu.cache.chunks import ChunkStore
    from makisu_tpu.chunker import cdc
    from makisu_tpu.chunker.service import HashService
    payload = np.random.default_rng(35).integers(
        0, 256, size=300_000, dtype=np.uint8).tobytes()
    blob = tmp_path / "layer.gz"
    blob.write_bytes(gzip.compress(payload, mtime=0))
    store = ChunkStore(str(tmp_path / "chunks"))
    store.PROBE_BATCH = 8
    service = HashService(linger_seconds=0.02)
    try:
        for expect_added in (True, False):
            token = cdc.set_chunk_observer(store.note_fingerprint)
            try:
                session = ChunkSession(block=128 * 1024, service=service)
            finally:
                cdc.reset_chunk_observer(token)
            session.update(payload)
            triples = [(c.offset, c.length, c.hex) for c in session.finish()]
            concurrency.hash_pool().submit(lambda: None).result()
            import time
            deadline = time.monotonic() + 10
            full = len(triples) - len(triples) % store.PROBE_BATCH
            while (sum(store._probed(h) is not None for _, _, h in triples)
                   < full and time.monotonic() < deadline):
                time.sleep(0.01)
            registry = metrics.MetricsRegistry()
            token = metrics.set_build_registry(registry)
            try:
                added = store.index_layer(str(blob), triples)
            finally:
                metrics.reset_build_registry(token)
                store.reset_fingerprint_memo()
            by = registry.counter_by_label(
                "makisu_chunk_exists_prefetch_total", "result")
            if expect_added:
                assert added == [h for _, _, h in triples]
                assert by.get("miss", 0) >= full and "hit" not in by
            else:
                assert added == []
                assert by.get("hit", 0) >= full and "miss" not in by
            assert by.get("probe", 0) <= len(triples) - full
    finally:
        service.close()
