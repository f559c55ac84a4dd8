"""A cached layer's blob reaches the tar parser in blocks
(``tario.BlockInflater`` under ``tario.layer_tar``): the reader against
``gzip.GzipFile``, the apply against ``GzipFile`` + ``tarfile`` stream
mode, and the count of ``decompress`` calls a layer takes."""

import gzip
import io
import os
import sys
import tarfile
import time

import pytest

PERFBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")
sys.path.insert(0, PERFBENCH)

from pbharness import cells, stats  # noqa: E402

from makisu_tpu import tario  # noqa: E402
from makisu_tpu.snapshot import MemFS  # noqa: E402
from makisu_tpu.utils import metrics  # noqa: E402

READ, BLOCK = 9_000, 30_000


@pytest.fixture
def small_blocks(monkeypatch):
    monkeypatch.setattr(tario.BlockInflater, "READ", READ)
    monkeypatch.setattr(tario.BlockInflater, "BLOCK", BLOCK)


def _payload(n: int, seed: int) -> bytes:
    """Half of it random, half one repeated line."""
    import random
    noise = random.Random(seed).randbytes(n // 2)
    line = b"one line of a layer, again and again\n"
    return noise + (line * (n // len(line) + 1))[:n - len(noise)]


def _pgzip(payload: bytes) -> bytes:
    out = io.BytesIO()
    w = tario.BlockGzipWriter(out, level=6, block_size=16 * 1024)
    w.write(payload)
    w.close()
    return out.getvalue()


_BLOBS = {
    "zlib0": lambda: gzip.compress(_payload(200_000, 1), 0, mtime=0),
    "zlib6": lambda: gzip.compress(_payload(200_000, 2), 6, mtime=0),
    "pgzip": lambda: _pgzip(_payload(200_000, 3)),
    "two_members": lambda: (
        gzip.compress(_payload(70_000, 4), 6, mtime=0) + b"\0" * 10_240
        + gzip.compress(_payload(50_000, 5), 6, mtime=0)),
    # One repeated line inflates by more than BLOCK / READ, so every
    # block is full and the stream ends where the fourth does.
    "block_multiple": lambda: gzip.compress(
        (b"the same line\n" * BLOCK)[:4 * BLOCK], 6, mtime=0),
}


def _block_starts(blob: bytes) -> list[int]:
    """Where the reader's blocks begin: a block is what one read of the
    blob inflates to, BLOCK at most."""
    stream, starts = tario.BlockInflater(io.BytesIO(blob)), []
    while stream._next():
        starts.append(stream._start)
    return starts


def _stdlib_error(blob: bytes):
    try:
        gzip.GzipFile(fileobj=io.BytesIO(blob)).read()
    except Exception as e:  # noqa: BLE001 - its class is the answer
        return type(e)
    raise AssertionError("the stdlib read the spoiled blob whole")


def _whole(blob, want):
    stream = tario.gzip_reader(io.BytesIO(blob))
    assert isinstance(stream, tario.BlockInflater)
    assert stream.read() == want and stream.tell() == len(want)
    assert stream.read(10) == b"" and stream.read() == b""
    # No block is inflated for less than it holds: a call a read of the
    # blob or a block of the stream, never one a `read(n)`.
    assert stream.reads <= len(blob) // READ + len(want) // BLOCK + 4


def _small_reads(blob, want):
    stream = tario.gzip_reader(io.BytesIO(blob))
    got = []
    while piece := stream.read(512):
        assert len(piece) == 512 or stream.tell() == len(want)
        got.append(piece)
        assert stream.tell() == sum(map(len, got))
    assert b"".join(got) == want
    assert stream.reads <= len(blob) // READ + len(want) // BLOCK + 4


def _forward_seek(blob, want):
    stream = tario.gzip_reader(io.BytesIO(blob))
    assert stream.tell() == 0
    assert stream.read(100) == want[:100]
    # Within the block, over three blocks, and to a block's last byte.
    for target in (700, 3 * BLOCK + 17, 4 * BLOCK - 1):
        target = min(target, len(want) - 40)
        assert stream.seek(target) == target == stream.tell()
        assert stream.read(40) == want[target:target + 40]
    # Past the end: nothing to read, and finish() still says how long.
    stream.seek(len(want) + 1_000)
    assert stream.read(1) == b""
    assert stream.finish() == len(want)
    with pytest.raises(io.UnsupportedOperation):
        stream.seek(-1, io.SEEK_END)


def _step_back(blob, want):
    """What ``TarFile.next`` does after a body it did not read: seek to
    the next header less one byte, read that byte. Also where the
    header is a block's first byte, and where the position is already
    there."""
    starts = _block_starts(blob)
    assert len(starts) >= 4 and starts[0] == 0
    stream = tario.gzip_reader(io.BytesIO(blob))
    stream.read(10)
    for offset in (starts[1], starts[3]):
        stream.seek(offset - 1)
        assert stream.read(1) == want[offset - 1:offset]
        assert stream.read(512) == want[offset:offset + 512]
    # The position is a block's first byte already, its block is the
    # current one, and the byte before it is asked for: it comes from
    # the block before, which is gone but for its tail.
    first = starts[1]
    stream = tario.gzip_reader(io.BytesIO(blob))
    assert stream.read(first + 1) == want[:first + 1]
    assert stream._start == first
    stream.seek(first - 1)
    assert stream.read(3) == want[first - 1:first + 2]
    tail = tario.BlockInflater.TAIL
    stream.seek(first - tail)
    assert stream.read(tail + 88) == want[first - tail:first + 88]
    with pytest.raises(io.UnsupportedOperation):
        stream.seek(first - tail - 1)


def _truncated(blob, want):
    cut = blob[:-20]
    assert _stdlib_error(cut) is EOFError
    stream = tario.gzip_reader(io.BytesIO(cut))
    with pytest.raises(EOFError):
        stream.read()
    stream = tario.gzip_reader(io.BytesIO(cut))
    with pytest.raises(EOFError):
        stream.finish()


def _flipped_crc(blob, want):
    spoiled = bytearray(blob)
    spoiled[-8] ^= 0xFF
    spoiled = bytes(spoiled)
    assert _stdlib_error(spoiled) is gzip.BadGzipFile
    stream = tario.gzip_reader(io.BytesIO(spoiled))
    with pytest.raises(gzip.BadGzipFile):
        stream.read()
    # Nobody asked for the last bytes: the trailer is still read.
    stream = tario.gzip_reader(io.BytesIO(spoiled))
    assert stream.read(1_000) == want[:1_000]
    with pytest.raises(gzip.BadGzipFile):
        stream.finish()


@pytest.mark.parametrize("aspect", [_whole, _small_reads, _forward_seek,
                                    _step_back, _truncated, _flipped_crc],
                         ids=lambda f: f.__name__.strip("_"))
@pytest.mark.parametrize("kind", sorted(_BLOBS))
def test_block_inflater_reads_as_gzipfile_does(small_blocks, kind, aspect):
    blob = _BLOBS[kind]()
    want = gzip.GzipFile(fileobj=io.BytesIO(blob)).read()
    assert len(want) >= 4 * BLOCK
    if kind == "block_multiple":
        assert _block_starts(blob) == [0, BLOCK, 2 * BLOCK, 3 * BLOCK]
    aspect(blob, want)


def test_gzip_reader_keeps_gzipfile_for_an_unseekable_input():
    blob = gzip.compress(b"x" * 5_000, mtime=0)

    class Pipe:
        def __init__(self):
            self._f = io.BytesIO(blob)

        def read(self, n=-1):
            return self._f.read(n)

    stream = tario.gzip_reader(Pipe())
    assert isinstance(stream, gzip.GzipFile)
    assert stream.read() == b"x" * 5_000


# -- the apply ---------------------------------------------------------------

def _member(name, typ=tarfile.REGTYPE, data=b"", link="", mode=0o644,
            mtime=1_500_000_000):
    ti = tarfile.TarInfo(name)
    ti.type, ti.mode, ti.mtime, ti.linkname = typ, mode, mtime, link
    ti.size = len(data)
    return ti, data


def _long(n: int, stem: str) -> str:
    return "/".join([stem * 12] * (n // (len(stem) * 12) + 1))[:n]


_LAYERS = {
    "pax_long_name": (tarfile.PAX_FORMAT, lambda: [
        _member("d", tarfile.DIRTYPE, mode=0o755),
        _member("d/" + _long(300, "nm"), data=b"pax " * 700),
        _member("d/after", data=b"after")]),
    "gnu_long_link": (tarfile.GNU_FORMAT, lambda: [
        _member("d", tarfile.DIRTYPE, mode=0o755),
        _member("d/" + _long(200, "tg"), data=b"target"),
        _member("d/ln", tarfile.SYMTYPE, link=_long(200, "tg")),
        _member("d/" + _long(180, "gn"), data=b"gnu " * 300)]),
    "hardlink_before_target": (tarfile.GNU_FORMAT, lambda: [
        _member("ln", tarfile.LNKTYPE, link="orig"),
        _member("orig", data=b"data " * 1_000),
        _member("z", data=b"z")]),
    "symlink": (tarfile.GNU_FORMAT, lambda: [
        _member("bin", tarfile.DIRTYPE, mode=0o755),
        _member("bin/sh", data=b"#!/bin/sh\n", mode=0o755),
        _member("bin/rel", tarfile.SYMTYPE, link="sh"),
        _member("bin/abs", tarfile.SYMTYPE, link="/bin/sh")]),
    "whiteout": (tarfile.GNU_FORMAT, lambda: [
        _member("below", tarfile.DIRTYPE, mode=0o755),
        _member("below/.wh.victim"),
        _member("below/stays", data=b"new")]),
    "empty_file": (tarfile.GNU_FORMAT, lambda: [
        _member("empty"), _member("full", data=b"f" * 700),
        _member("empty_last")]),
    "last_member_512k": (tarfile.GNU_FORMAT, lambda: [
        _member("first", data=b"1" * 100),
        _member("blocks", data=_payload(512 * 130, 7)),
        _member("last", data=_payload(512 * 117, 8))]),
    "small_members_3000": (tarfile.GNU_FORMAT, lambda: [
        _member(f"pkg{i % 40:02d}", tarfile.DIRTYPE, mode=0o755)
        for i in range(40)] + [
        _member(f"pkg{i % 40:02d}/f{i}.py", data=b"x = %d\n" % i * (i % 9))
        for i in range(3_000)]),
}


def _layer_blob(fmt, members) -> bytes:
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w", format=fmt) as tw:
        for ti, data in members:
            tw.addfile(ti, io.BytesIO(data) if data else None)
    return gzip.compress(buf.getvalue(), 6, mtime=0)


def _seed_root(root) -> None:
    """What a lower layer left: the whiteout's victim."""
    (root / "below").mkdir()
    (root / "below" / "victim").write_text("gone soon")
    (root / "below" / "stays").write_text("old")
    for path in (root / "below" / "victim", root / "below" / "stays",
                 root / "below", root):
        os.utime(path, (1_400_000_000, 1_400_000_000))


def _apply(root, blob: bytes, how: str, untar: bool):
    root.mkdir()
    _seed_root(root)
    fs = MemFS(str(root), blacklist=[], sync_wait=0.0)
    with tarfile.open(fileobj=io.BytesIO(), mode="w|") as tw:
        fs.add_layer_by_scan(tw)    # the tree holds the seeded root
    record: list = []
    if how == "stdlib":
        with gzip.GzipFile(fileobj=io.BytesIO(blob)) as gz, \
                tarfile.open(fileobj=gz, mode="r|") as tf:
            fs.update_from_tar(tf, untar, record=record, chain_key="k")
    else:
        with tario.gzip_reader(io.BytesIO(blob)) as gz, \
                tario.layer_tar(gz) as tf:
            assert tf.fileobj is gz     # mode "r:": no stream between
            fs.update_from_tar(tf, untar, record=record, chain_key="k")
    return fs, record


def _header(hdr: tarfile.TarInfo) -> dict:
    return {k: v for k, v in hdr.get_info().items()
            if k not in ("chksum",)} | {"pax": dict(hdr.pax_headers)}


def _tree(fs: MemFS, root: str) -> dict:
    out = {}

    def rec(node, path):
        out[path] = (node.src.replace(root, "<root>"), node.dst,
                     _header(node.hdr))
        for name, child in node.children.items():
            rec(child, path + "/" + name)
    rec(fs.tree, "")
    return out


def _ops(record, root: str) -> list:
    return [("whiteout", e.deleted) if not hasattr(e, "hdr") else
            (e.src.replace(root, "<root>"), e.dst, _header(e.hdr))
            for e in record]


def _on_disk(root) -> dict:
    out = {}
    for base, dirs, files in os.walk(root):
        for name in dirs + files:
            path = os.path.join(base, name)
            st = os.lstat(path)
            content = None
            if os.path.islink(path):
                content = os.path.relpath(
                    os.path.join(base, os.readlink(path)), root)
            elif os.path.isfile(path):
                with open(path, "rb") as f:
                    content = f.read()
            # A directory no member names is made beside its first
            # file and keeps the clock's time: "now" on either side.
            mtime = int(st.st_mtime)
            out[os.path.relpath(path, root)] = (
                st.st_mode, "now" if mtime > time.time() - 3_600 else mtime,
                st.st_nlink > 1, content)
    return out


@pytest.mark.parametrize("untar", [False, True], ids=["fold", "untar"])
@pytest.mark.parametrize("case", sorted(_LAYERS))
def test_apply_from_blocks_equals_the_stream_parse(tmp_path, small_blocks,
                                                   case, untar):
    fmt, members = _LAYERS[case]
    blob = _layer_blob(fmt, members())
    old_fs, old_rec = _apply(tmp_path / "old", blob, "stdlib", untar)
    new_fs, new_rec = _apply(tmp_path / "new", blob, "blocks", untar)
    old_root, new_root = str(tmp_path / "old"), str(tmp_path / "new")
    assert _ops(new_rec, new_root) == _ops(old_rec, old_root)
    assert len(new_rec) >= len(members()) - 1
    assert _tree(new_fs, new_root) == _tree(old_fs, old_root)
    assert new_fs.applied_chain == old_fs.applied_chain
    assert _on_disk(new_root) == _on_disk(old_root)
    if untar and case == "whiteout":
        assert not os.path.lexists(tmp_path / "new" / "below" / "victim")


@pytest.mark.parametrize("how", ["truncated", "crc", "tar_cut_short"])
def test_apply_fails_on_a_blob_that_is_not_whole(tmp_path, small_blocks,
                                                 how):
    """The trailer is read by the time the apply counts, also where the
    parser had its end-of-archive before the stream's end."""
    fmt, members = _LAYERS["last_member_512k"]
    blob = bytearray(_layer_blob(fmt, members()))
    if how == "truncated":
        del blob[-20:]
        want = EOFError
    elif how == "crc":
        blob[-8] ^= 0xFF
        want = gzip.BadGzipFile
    else:
        # A whole gzip stream of a tar that ends inside its last body.
        tar = gzip.decompress(bytes(blob))
        blob = gzip.compress(tar[:len(tar) - 10_240 - 512 * 60], mtime=0)
        want = tarfile.ReadError
    with pytest.raises(want):
        _apply(tmp_path / "root", bytes(blob), "blocks", False)


# -- the count ---------------------------------------------------------------

def _apply_through_the_node(tmp_path, blob: bytes, registry) -> MemFS:
    """``BuildNode._apply_layer`` of ``blob`` as a stored layer, with
    no cache manager and no session, its counters in ``registry``."""
    import types

    from makisu_tpu.builder.node import BuildNode
    from makisu_tpu.docker.image import Descriptor, Digest, DigestPair
    (tmp_path / "blob").write_bytes(blob)
    os.makedirs(tmp_path / "root", exist_ok=True)
    fs = MemFS(str(tmp_path / "root"), blacklist=[], sync_wait=0.0)
    layers = types.SimpleNamespace(
        open=lambda h: open(tmp_path / "blob", "rb"))
    node = BuildNode.__new__(BuildNode)
    node.ctx = types.SimpleNamespace(
        memfs=fs, session=None,
        image_store=types.SimpleNamespace(layers=layers))
    digest = Digest.from_hex("ab" * 32)
    pair = DigestPair(tar_digest=digest, gzip_descriptor=Descriptor(
        media_type="application/vnd.docker.image.rootfs.diff.tar.gzip",
        size=len(blob), digest=digest))
    token = metrics.set_build_registry(registry)
    try:
        node._apply_layer(pair, False, None)
    finally:
        metrics.reset_build_registry(token)
    return fs


def _layer_of_32_mib() -> bytes:
    return _layer_blob(tarfile.GNU_FORMAT, [
        _member("app", tarfile.DIRTYPE, mode=0o755)] + [
        _member(f"app/f{i:03d}.bin", data=_payload(1 << 20, i))
        for i in range(32)])


def test_a_32_mib_layer_is_inflated_in_fewer_than_100_calls(tmp_path):
    """The counter grows by the reader's calls, the span carries them,
    and they are tens where the stream parse over ``GzipFile`` made
    thousands of reads."""
    registry = metrics.MetricsRegistry()
    fs = _apply_through_the_node(tmp_path, _layer_of_32_mib(), registry)
    reads = registry.counter_total(metrics.LAYER_INFLATE_READS_TOTAL)
    assert 8 <= reads < 100

    def spans(s):
        yield s
        for child in s.children:
            yield from spans(child)
    inflate = [s for s in spans(registry.root)
               if s.name == "apply_layer.inflate"]
    assert len(inflate) == 1
    assert int(inflate[0].attrs["reads"]) == reads
    assert registry.counter_by_label(
        metrics.LAYER_REPLAY_TOTAL, "result") == {"inflate": 1.0}
    assert len(fs.tree.children["app"].children) == 32


def test_a_failed_apply_counts_no_reads(tmp_path):
    registry = metrics.MetricsRegistry()
    blob = _layer_of_32_mib()
    with pytest.raises(EOFError):
        _apply_through_the_node(tmp_path, blob[:-20], registry)
    assert registry.counter_total(metrics.LAYER_INFLATE_READS_TOTAL) == 0
    assert registry.counter_by_label(
        metrics.LAYER_REPLAY_TOTAL, "result") == {}


@pytest.mark.parametrize("side", ["change", "parent", "nothing_inflated",
                                  "untraced"])
def test_the_benchmarks_reader_divides_reads_by_layers(tmp_path, side):
    """``perfbench/readers/apply_inflate_reads_per_layer.py`` on the
    worker's exposition before and after two applies; ``None`` from a
    program without the series, where no layer was inflated, and
    where the run read no counters."""
    import types
    read = cells._load_module(os.path.join(
        PERFBENCH, "readers", "apply_inflate_reads_per_layer.py")).read
    registry = metrics.MetricsRegistry()
    fmt, members = _LAYERS["last_member_512k"]
    blob = _layer_blob(fmt, members())
    _apply_through_the_node(tmp_path, blob, registry)
    before = stats.parse_prometheus(metrics.render_prometheus(registry))
    for _ in range(2):
        _apply_through_the_node(tmp_path, blob, registry)
    after = stats.parse_prometheus(metrics.render_prometheus(registry))
    a_layer = registry.counter_total(metrics.LAYER_INFLATE_READS_TOTAL) / 3
    run = types.SimpleNamespace(counters_open=before, counters_close=after)
    if side == "change":
        assert read(run) == a_layer >= 1
        return
    if side == "parent":
        run.counters_open, run.counters_close = (
            {k: v for k, v in c.items()
             if k[0] != metrics.LAYER_INFLATE_READS_TOTAL}
            for c in (before, after))
    elif side == "nothing_inflated":
        run.counters_open = after
    else:
        run.counters_open = run.counters_close = None
    assert read(run) is None
