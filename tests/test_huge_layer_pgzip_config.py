"""The ``huge-layer-pgzip`` configuration as it is shipped
(``perfbench/configs/huge-layer-pgzip.json``): it is ``huge-layer`` key
for key but the compressor; the same tree at 2 x 1 MiB builds through a
worker with the file's own flags, cold and after one edit, at 1, 2 and
8 compressor lanes and through the native and the Python sink, to one
blob, and is held to the configuration's own reference
(``perfbench/reference/cdc_pgzip.py``), which refuses a blob that is
not the block gzip of its own tar; the zlib route's blob is what the
parent commit wrote; the stages and spans this configuration brought
are there and add up; its four readers read a run record.

Needs no C compiler: a case that needs ``liblayersink.so`` skips where
it does not load, the rest run through the Python sink."""

import hashlib
import io
import itertools
import json
import os
import shutil
import sys
import time
import zlib

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
PERFBENCH = os.path.join(CHECKOUT, "perfbench")
sys.path.insert(0, PERFBENCH)

from pbharness import cells, check, driver, gen, stats  # noqa: E402

from makisu_tpu import native, tario  # noqa: E402
from makisu_tpu.chunker.hasher import LayerSink, NativeLayerSink  # noqa: E402
from makisu_tpu.utils import metrics, traceexport  # noqa: E402
from makisu_tpu.worker import WorkerClient, WorkerServer  # noqa: E402


def _load(*parts):
    with open(os.path.join(CHECKOUT, *parts), encoding="utf-8") as f:
        return json.load(f)


CONFIG = _load("perfbench", "configs", "huge-layer-pgzip.json")
ZLIB_CONFIG = _load("perfbench", "configs", "huge-layer.json")
BENCHMARK = _load("BENCHMARK.json")
EDIT = _load("perfbench", "traffic", "edit.json")
CELL, PAIR = "huge-layer-pgzip-edit", "huge-layer-edit"
REF = cells._load_module(os.path.join(PERFBENCH, "reference",
                                      "cdc_pgzip.py"))
NEW_READERS = ("compress_wall_s_per_build", "compress_lanes_busy_mean",
               "blob_write_s_per_build", "sink_device_drain_s_per_build")
BUSY = metrics.COMMIT_STAGE_BUSY
NATIVE = pytest.mark.skipif(not native.layersink_available(),
                            reason="liblayersink.so does not load")
_OLD = 1_600_000_000


# -- (a) the file, and its entries in BENCHMARK.json ------------------------


def _equals_huge_layer_but_the_compressor():
    differ = {k for k in set(CONFIG) | set(ZLIB_CONFIG)
              if CONFIG.get(k) != ZLIB_CONFIG.get(k)}
    assert differ == {"name", "source", "deployment", "guarantees",
                      "reduced_why", "assumed", "reference", "build_flags"}
    for key in ("context", "files", "file_bytes", "total_bytes",
                "layer_tar_bytes", "lanes", "worker", "templates",
                "source_scale"):
        assert CONFIG[key] == ZLIB_CONFIG[key], key
    assert CONFIG["file_bytes"] == 67108864 and CONFIG["lanes"] == 1
    assert CONFIG["build_flags"] == ["--hasher", "tpu", "--gzip-backend",
                                     "pgzip"]
    assert CONFIG["reference"] == "cdc_pgzip"


def _states_what_it_adds_to_huge_layer():
    assert CONFIG["guarantees"][:3] == ZLIB_CONFIG["guarantees"]
    added = " ".join(CONFIG["guarantees"][3:])
    for words in ("one gzip member", "pure function", "number of lanes",
                  "bit for bit"):
        assert words in added
    assert CONFIG["reduced_why"].startswith(ZLIB_CONFIG["reduced_why"])
    for key, value in ZLIB_CONFIG["assumed"].items():
        assert CONFIG["assumed"][key] == value
    assert "131,072" in CONFIG["assumed"]["block_size"]
    assert str(tario._PGZIP_BLOCK) == "131072" == str(REF.BLOCK)
    assert CONFIG["assumed"]["level"].startswith("6")
    assert tario.COMPRESSION_LEVELS["default"] == 6 == REF.LEVEL
    assert "min(8, os.cpu_count())" in CONFIG["assumed"]["compress_lanes"]
    assert "gzip.go:26-47" in CONFIG["source"]
    assert "multi-core host" in CONFIG["deployment"]


def _entry_and_cell_in_benchmark():
    entry = BENCHMARK["configs"][7]     # the last at its PR
    assert entry["name"] == "huge-layer-pgzip"
    assert entry["file"] == "perfbench/configs/huge-layer-pgzip.json"
    assert entry["reduced"] == ["file_bytes", "total_bytes"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert "gzip.go:26-47" in entry["source"]
    cell = BENCHMARK["workloads"][9]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "huge-layer-pgzip", "edit", 1)
    assert len(cell["why"]) <= 200
    assert len(BENCHMARK["configs"]) == 9     # PR 50 appended one
    assert len(BENCHMARK["workloads"]) == 11
    assert all(w["chips"] == 1 for w in BENCHMARK["workloads"])
    assert len({c["source"] for c in BENCHMARK["configs"]}) == 9
    assert (EDIT["count"], EDIT["prime_cold"], EDIT["prime_rebuilds"]) \
        == ("started", True, 1)


def _four_metrics_appended_with_their_cells():
    per_layer = BENCHMARK["per_layer"]
    # PR 48 added one after, PR 49 one, PR 50 four, PR 51 one, PR 52 four,
    # PR 53 one.
    assert [m["name"] for m in per_layer[61:65]] == list(NEW_READERS)
    assert len(per_layer) == 77
    by_name = {m["name"]: m for m in per_layer}
    commit = by_name["tar_write_s_per_build"]["layer"]
    want = {
        "compress_wall_s_per_build": ("s", "lower", "program_counter",
                                      [CELL, PAIR]),
        "compress_lanes_busy_mean": ("1", "higher", "program_counter",
                                     [CELL, PAIR]),
        "blob_write_s_per_build": ("s", "lower", "program_counter", [CELL]),
        "sink_device_drain_s_per_build": ("s", "lower", "program_span",
                                          [CELL, PAIR])}
    for name, (unit, better, source, listed) in want.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": commit, "moves": "build_p50_s", "workloads": listed}
    readers = os.listdir(os.path.join(PERFBENCH, "readers"))
    assert len([r for r in readers if r.endswith(".py")]) == 84


def _cell_joins_the_lists_of_its_pair():
    """Every standing metric ``huge-layer-edit`` reports, but the five
    that are to be retired, has the new cell appended last; the storage
    pair goes together or not at all."""
    retired = {"sync_wait_share_pct", "chunk_store_share_pct",
               "commit_share_pct", "device_mb_per_build",
               "idle_unspanned_pct"}
    storage = {"new_chunk_bytes_share_pct"}
    for m in BENCHMARK["per_layer"][:61]:
        # PR 50's cell was appended after this one.
        listed = [w for w in m["workloads"] if w != "monorepo-farm-churn"]
        if m["name"] in retired or PAIR not in listed:
            assert CELL not in listed, m["name"]
        elif m["name"] not in storage:
            assert listed[-1] == CELL, m["name"]
            assert listed.count(CELL) == 1
    [stored] = [m for m in BENCHMARK["end_to_end"]
                if m["name"] == "stored_per_user_byte"]
    [share] = [m for m in BENCHMARK["per_layer"]
               if m["name"] == "new_chunk_bytes_share_pct"]
    assert (CELL in stored["workloads"]) == (CELL in share["workloads"])
    for m in BENCHMARK["end_to_end"]:
        assert m["bound"] == {"build_p50_s": 0.25, "build_mb_per_s": 0.15,
                              "stored_per_user_byte": 0.025,
                              "setup_s": 0.25}[m["name"]]
    assert BENCHMARK["run_seconds"] == 45


def _cell_reports_what_its_pair_reports_and_the_four():
    path = os.path.join(CHECKOUT, "BENCHMARK.json")
    ours, theirs = cells.Cell(path, CELL), cells.Cell(path, PAIR)
    assert ours.reference.__name__ == "perfbench_cdc_pgzip"
    assert {"build_p50_s", "setup_s"} \
        <= {m["name"] for m in ours.end_to_end()}
    mine = {m["name"] for m in ours.per_layer()}
    pair = {m["name"] for m in theirs.per_layer()}
    assert set(NEW_READERS) <= mine
    assert pair - mine <= {"idle_unspanned_pct", "new_chunk_bytes_share_pct"}
    assert mine - pair == {"blob_write_s_per_build"}
    assert {"sha_hbm_roofline", "gear_hbm_roofline",
            "compress_wait_s_per_build", "device_idle_pct"} <= mine
    for name in mine:
        assert callable(ours.reader(name)), name


@pytest.mark.parametrize("statement", [
    _equals_huge_layer_but_the_compressor, _states_what_it_adds_to_huge_layer,
    _entry_and_cell_in_benchmark, _four_metrics_appended_with_their_cells,
    _cell_joins_the_lists_of_its_pair,
    _cell_reports_what_its_pair_reports_and_the_four],
    ids=lambda f: f.__name__.strip("_"))
def test_configuration_and_cell_are_declared(statement):
    statement()


# -- the reference's own framing --------------------------------------------


@pytest.mark.parametrize("n", [0, 1, 1000, 131071, 131072, 131073,
                               2 * 131072, 300_000])
def test_block_gzip_is_one_member_and_what_the_program_writes(n):
    """Any gunzip inflates it to the tar; the program's pure-Python
    codec and its block writer, at one lane and at three, write the
    same bytes (a tar of whole blocks ends in an empty finished
    slice)."""
    tar = (np.random.default_rng(n).bytes(n // 2) + b"weights and text " * n)[:n]
    blob = REF.block_gzip(tar)
    assert zlib.decompress(blob, 31) == tar
    assert blob[:10] == bytes([0x1f, 0x8b, 8, 0, 0, 0, 0, 0, 0, 0xff])
    assert blob[-8:] == zlib.crc32(tar).to_bytes(4, "little") \
        + (n & 0xFFFFFFFF).to_bytes(4, "little")
    assert blob[10:-8] == tario._py_deflate_blocks(tar, 6, 131072, last=True)
    if n and n % 131072 == 0:
        assert blob[-10:-8] == b"\x03\x00"
    for workers in (1, 3):
        out = io.BytesIO()
        with tario.BlockGzipWriter(out, 6, 131072, workers=workers) as w:
            w.write(tar)
        assert out.getvalue() == blob
    assert REF.block_gzip(tar, block=65536) != blob or n <= 65536
    with pytest.raises(ValueError):
        REF.block_gzip(tar, block=0)


def test_reference_is_the_slab_reference_and_independent():
    with open(os.path.join(PERFBENCH, "reference", "cdc_pgzip.py"),
              encoding="utf-8") as f:
        source = f.read()
    assert "import makisu_tpu" not in source
    assert "from makisu_tpu" not in source
    for name in ("cut_points", "candidates", "sha256_hex", "file_sha256_hex",
                 "tar_members", "tree_members", "REGTYPE", "gear_table"):
        assert getattr(REF, name) is getattr(REF._slab, name)
    assert REF.inflate is not REF._slab.inflate


# -- (b) the same tree at 2 x 1 MiB, through a worker ------------------------


def _scaled_context():
    context = json.loads(json.dumps(CONFIG["context"]))
    [layer] = context["layers"]
    layer["bytes"] = 2 << 20
    return context


def _age(tree, when=_OLD):
    for parent, _, names in os.walk(tree, topdown=False):
        for name in names:
            os.utime(os.path.join(parent, name), (when, when))
        os.utime(parent, (when, when))


class _Worker:
    def __init__(self, work):
        self.work = work
        self.server = WorkerServer(
            os.path.join(work, "w.sock"), max_concurrent_builds=int(
                CONFIG["worker"]["max_concurrent_builds"]))
        self.thread = self.server.serve_background()
        self.client = WorkerClient(self.server.socket_path)
        deadline = time.monotonic() + 60
        while not self.client.ready():
            assert time.monotonic() < deadline
            time.sleep(0.02)
        self.built = 0

    def counters(self):
        return stats.parse_prometheus(self.client.metrics())

    def build(self, context_dir, storage, flags, top=()):
        root = os.path.join(self.work, f"root{self.built}")
        os.makedirs(root)
        b = driver.Build(lane=0, index=self.built, kind="rebuild",
                         tag=f"hugepgzip/t:b{self.built}",
                         context=context_dir, storage=storage,
                         context_bytes=0)
        before = self.counters()
        b.exit_code = self.client.build(
            ["--log-level", "error", *top, "build", context_dir, "-t", b.tag,
             "--storage", storage, "--root", root] + list(flags))
        b.terminal = dict(self.client.last_build)
        events = list(self.client.last_events)
        b.spans = [(e.get("name"), e.get("duration")) for e in events
                   if e.get("type") == "span_end"]
        self.built += 1
        shutil.rmtree(root, ignore_errors=True)
        return {"build": b, "events": events,
                "counters": (before, self.counters())}

    def close(self):
        self.server.shutdown()
        self.server.server_close()
        self.thread.join(timeout=5)


def _blob_path(b):
    manifest, _, _ = check.Checker(None, {})._manifest(b)
    [layer] = manifest["layers"]
    hexd = layer["digest"].split(":", 1)[1]
    return layer["digest"], os.path.join(b.storage, "layers", hexd[:2], hexd)


def _held(context, result):
    checker = check.Checker(REF, context)
    checker.check_build(result["build"], tree_is_current=True)
    result["check"] = checker
    result["digest"], result["blob"] = _blob_path(result["build"])
    return result


LANES = (1, 2, 8)
SINKS = ("native", "python")
COMBOS = [pytest.param(sink, lanes, id=f"{sink}-{lanes}",
                       marks=[NATIVE] if sink == "native" else [])
          for sink, lanes in itertools.product(SINKS, LANES)]


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """One worker. The scaled tree built cold into a storage a
    combination of sink and lanes, each held to the reference while
    its tree is the tree on disk; then one edit and a rebuild into each
    storage; last one build of the edited tree with the default
    backend."""
    work = str(tmp_path_factory.mktemp("hugepgzip"))
    context = _scaled_context()
    out = {"context": context, "work": work}
    ctx = os.path.join(work, "ctx")
    gen.make_tree(context, ctx, 47)
    _age(ctx)
    flags = CONFIG["build_flags"]
    combos = [(sink, lanes) for sink in SINKS for lanes in LANES
              if sink == "python" or native.layersink_available()]
    patch = pytest.MonkeyPatch()
    worker = _Worker(work)

    def build(sink, lanes, build_flags=flags):
        if sink == "python":
            patch.setenv("MAKISU_TPU_NATIVE_SINK", "0")
        else:
            patch.delenv("MAKISU_TPU_NATIVE_SINK", raising=False)
        return _held(context, worker.build(
            ctx, os.path.join(work, f"storage-{sink}-{lanes}"), build_flags,
            top=("--compress-workers", str(lanes))))
    try:
        for combo in combos:
            out["cold", *combo] = build(*combo)
        out["touched"] = gen.apply_edit(
            EDIT["edit"], context, ctx, np.random.default_rng([47, 0, 7]),
            "000001")
        _age(ctx, _OLD + 100)
        for combo in combos:
            out["edited", *combo] = build(*combo)
        patch.delenv("MAKISU_TPU_NATIVE_SINK", raising=False)
        out["zlib"] = worker.build(ctx, os.path.join(work, "storage-zlib"),
                                   ZLIB_CONFIG["build_flags"])
    finally:
        patch.undo()
        worker.close()
    return out


@pytest.mark.parametrize("sink,lanes", COMBOS)
@pytest.mark.parametrize("which", ["cold", "edited"])
def test_build_held_to_the_pgzip_reference(built, which, sink, lanes):
    result = built[which, sink, lanes]
    assert result["build"].exit_code == 0
    checker = result["check"]
    for count, limit in check.LIMITS.items():
        assert checker.found[count] == 0 == limit, (count, checker.notes)
    assert checker.verdict()
    assert checker.checked["builds"] == 1 and checker.checked["layers"] == 1
    assert checker.checked["members"] == 2
    assert checker.checked["chunks"] > 150


@pytest.mark.parametrize("which", ["cold", "edited"])
def test_one_blob_whatever_the_lanes_and_the_sink(built, which):
    """The guarantee the configuration states: a pure function of the
    tar, the level and the block size."""
    results = [v for k, v in built.items()
               if isinstance(k, tuple) and k[0] == which]
    assert len(results) in (3, 6)
    assert len({r["digest"] for r in results}) == 1
    [blob] = {open(r["blob"], "rb").read() for r in results}
    tar = zlib.decompress(blob, 31)
    assert blob == REF.block_gzip(tar)
    assert len(tar) > 2 << 20
    assert built["touched"] == 1
    assert built["cold", "python", 1]["digest"] \
        != built["edited", "python", 1]["digest"]


# -- (c) the reference decides it --------------------------------------------


def _zlib6(tar):
    out = io.BytesIO()
    with tario.gzip_writer(out, backend_id="zlib-6") as w:
        w.write(tar)
    return out.getvalue()


def _one_byte_flipped_in_a_middle_block(tar):
    blob = bytearray(REF.block_gzip(tar))
    blob[len(blob) // 2] ^= 0x01
    return bytes(blob)


_WRONG_BLOBS = {
    "zlib-6": _zlib6,
    "another-block-size": lambda tar: REF.block_gzip(tar, block=65536),
    "another-level": lambda tar: REF.block_gzip(tar, level=1),
    "byte-flipped": _one_byte_flipped_in_a_middle_block,
    "bytes-after-the-trailer": lambda tar: REF.block_gzip(tar) + b"\0",
}


@pytest.mark.parametrize("wrong", list(_WRONG_BLOBS))
def test_a_blob_that_is_not_the_block_gzip_of_its_tar_is_incorrect(
        built, tmp_path, wrong):
    """``inflate`` raises ``ValueError`` and ``Checker`` counts the
    layer under ``missing_outputs``, whose limit is 0: the cell reads
    ``correct`` false."""
    result = built["edited", "python", 2]
    good = result["blob"]
    tar = REF.inflate(good)
    storage = str(tmp_path / "storage")
    shutil.copytree(result["build"].storage, storage)
    bad = os.path.join(storage, os.path.relpath(
        good, result["build"].storage))
    os.unlink(bad)
    with open(bad, "wb") as f:
        f.write(_WRONG_BLOBS[wrong](tar))
    if wrong in ("zlib-6", "another-block-size", "another-level"):
        # A sound gzip member of the same tar: only its bytes differ.
        with open(bad, "rb") as f:
            assert zlib.decompress(f.read(), 31) == tar
    with pytest.raises(ValueError):
        REF.inflate(bad)
    b = driver.Build(**{**vars(result["build"]), "storage": storage})
    checker = check.Checker(REF, built["context"])
    checker.check_build(b, tree_is_current=True)
    assert checker.found["missing_outputs"] == 1
    assert not checker.verdict()
    # The slab reference, which holds no blob to its tar's framing,
    # reads the same storage as a blob whose digest is wrong at most.
    assert REF._slab.inflate(good) == tar


# What the parent commit (99b01e0) wrote for these bytes through its
# native sink and its Python sink, backend ``zlib-6``: recorded by
# running ``_zlib_blob_digest`` on a checkout of it.
_ZLIB_BLOB_SHA256 = \
    "a7b70f39eb7520afea2c9e43fac833c107197a8af27e07f4542d589668549ef6"


def _stream():
    return np.random.default_rng(47).bytes(300_000) \
        + b"".join(b"line %06d of the dataset\n" % i for i in range(40_000))


def _zlib_blob_digest(sink_cls, path):
    with open(path, "wb") as out:
        sink = sink_cls(out, backend_id="zlib-6")
        data = _stream()
        for off in range(0, len(data), 100_000):
            sink.write(data[off:off + 100_000])
        commit = sink.finish()
    with open(path, "rb") as f:
        blob = f.read()
    assert commit.digest_pair.gzip_descriptor.digest.hex() \
        == hashlib.sha256(blob).hexdigest()
    assert zlib.decompress(blob, 31) == data
    return hashlib.sha256(blob).hexdigest()


@pytest.mark.parametrize("sink_cls", [
    pytest.param(NativeLayerSink, marks=NATIVE, id="native"),
    pytest.param(LayerSink, id="python")])
def test_zlib_route_writes_the_parents_bytes(tmp_path, sink_cls):
    assert _zlib_blob_digest(sink_cls, str(tmp_path / "blob.gz")) \
        == _ZLIB_BLOB_SHA256


def test_default_backend_is_still_one_zlib_stream(built):
    """The build with ``huge-layer``'s own flags stores a blob that is
    the stdlib's level-6 stream of its tar, not block gzip."""
    b = built["zlib"]["build"]
    assert b.exit_code == 0
    _, blob = _blob_path(b)
    tar = REF._slab.inflate(blob)
    assert open(blob, "rb").read() == _zlib6(tar)
    with pytest.raises(ValueError):
        REF.inflate(blob)
    assert tar == REF.inflate(built["edited", "python", 1]["blob"])


# -- (d) the stages and the spans --------------------------------------------


def _grown(result, stage):
    before, after = result["counters"]
    if not any(name == BUSY and ("stage", stage) in labels
               for name, labels in after):
        return None
    return stats.counter_delta(before, after, BUSY, stage=stage)


def _span_tree(result):
    """{span_id: (name, parent_id, duration)} of a build's events."""
    starts = {e["span_id"]: e for e in result["events"]
              if e.get("type") == "span_start"}
    return {e["span_id"]: (e["name"], starts[e["span_id"]]["parent_id"],
                           float(e["duration"]))
            for e in result["events"] if e.get("type") == "span_end"}


@NATIVE
@pytest.mark.parametrize("lanes", LANES)
def test_pgzip_commit_reports_wall_wait_and_blob_write(built, lanes):
    result = built["cold", "native", lanes]
    [commit] = [d for name, d in result["build"].spans
                if name == "commit_layer"]
    busy, wall, waited, blob_write = (
        _grown(result, stage) for stage in
        ("compress", "compress_wall", "compress_wait", "blob_write"))
    assert 0 < wall <= busy + 1e-9
    assert 0 <= waited and 0 < blob_write
    assert waited + blob_write <= commit
    assert wall <= commit
    if lanes == 1:
        # One lane deflates in line: nothing to wait for, and the
        # stream's wall time is its busy time.
        assert waited == 0 and wall == pytest.approx(busy)


@NATIVE
def test_zlib_commit_has_no_blob_write_and_wall_is_compress(built):
    result = built["zlib"]
    # The worker's counters are the process's: the series is there
    # since the pgzip builds before this one, and did not grow.
    assert not _grown(result, "blob_write")
    assert 0 < _grown(result, "compress_wall") \
        == pytest.approx(_grown(result, "compress"))
    assert _grown(result, "compress_wait") > 0


@pytest.mark.parametrize("which", [
    pytest.param(("cold", "native", 8), marks=NATIVE, id="native-pgzip"),
    pytest.param(("cold", "python", 8), id="python-pgzip"),
    pytest.param("zlib", id="zlib")])
def test_sink_finish_has_two_children(built, which):
    tree = _span_tree(built[which])
    [(finish_id, finish)] = [(i, s) for i, s in tree.items()
                             if s[0] == "sink_finish"]
    children = [s for s in tree.values() if s[1] == finish_id]
    assert [s[0] for s in children] == ["sink_finish.stream_join",
                                        "sink_finish.device_drain"]
    assert sum(s[2] for s in children) <= finish[2] + 1e-6
    assert tree[finish[1]][0] == "commit_layer"
    for name, _, _ in children:
        assert traceexport.phase_of(name) == "hash"


# -- (e) the readers, on a run record made by hand ---------------------------


def _series(name, value, **labels):
    return (name, tuple(sorted(labels.items()))), value


def _record(tmp_path, with_program_side):
    def counted(spans, ok=True):
        b = driver.Build(lane=0, index=0, kind="rebuild", tag="", context="",
                         storage="", context_bytes=1,
                         exit_code=0 if ok else 1, terminal={"x": 1})
        b.spans = spans
        return b
    r = driver.Run(cell=None, seed=1, seconds=45.0, trace=True,
                   work_dir=str(tmp_path))
    spans = [("commit_layer", 2.0), ("sink_finish", 0.5),
             ("sink_finish.stream_join", 0.1),
             ("sink_finish.device_drain", 0.375)]
    r.counted = [counted(spans), counted(spans),
                 counted([("sink_finish.device_drain", 99.0)], ok=False)]
    r.builds = list(r.counted)
    r.counters_open = dict([
        _series(BUSY, 1.0, stage="compress"),
        _series(BUSY, 1.0, stage="compress_wall"),
        _series(BUSY, 0.5, stage="blob_write")])
    r.counters_close = dict([
        _series(BUSY, 10.0, stage="compress"),
        _series(BUSY, 4.0, stage="compress_wall"),
        _series(BUSY, 1.25, stage="blob_write"),
        _series(BUSY, 7.0, stage="tar_write")])
    if not with_program_side:
        for b in r.counted:
            b.spans = [("commit_layer", 2.0), ("sink_finish", 0.5)]
        old = dict([_series(BUSY, 8.0, stage="compress"),
                    _series(BUSY, 3.0, stage="compress_wait")])
        r.counters_open, r.counters_close = dict(old), dict(old)
    return r


@pytest.mark.parametrize("metric,want", [
    ("compress_wall_s_per_build", 3.0 / 3),
    ("compress_lanes_busy_mean", 9.0 / 3.0),
    ("blob_write_s_per_build", 0.75 / 3),
    ("sink_device_drain_s_per_build", 0.375)])
def test_reader_reads_a_run_record(tmp_path, metric, want):
    cell = cells.Cell(os.path.join(CHECKOUT, "BENCHMARK.json"), CELL)
    read = cell.reader(metric)
    assert read(_record(tmp_path, True)) == pytest.approx(want)
    assert read(_record(tmp_path, False)) is None
    untraced = _record(tmp_path, True)
    untraced.counters_open = untraced.counters_close = None
    untraced.counted = []
    assert read(untraced) is None


def test_lanes_busy_mean_of_a_stream_that_never_had_work_is_none(tmp_path):
    record = _record(tmp_path, True)
    record.counters_close[(BUSY, (("stage", "compress_wall"),))] = 1.0
    read = cells.Cell(os.path.join(CHECKOUT, "BENCHMARK.json"),
                      CELL).reader("compress_lanes_busy_mean")
    assert read(record) is None
