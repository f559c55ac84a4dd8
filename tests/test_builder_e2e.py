"""End-to-end build tests: Dockerfile → layers + manifest, no network.

Mirrors the reference's builder suite strategy (build_plan_test.go,
build_stage_test.go: full plans on fixture contexts with fake caches).
"""

import gzip
import io
import json
import os
import tarfile

import pytest

from makisu_tpu import cli
from makisu_tpu.builder import BuildPlan
from makisu_tpu.cache import CacheManager, MemoryStore, NoopCacheManager
from makisu_tpu.context import BuildContext
from makisu_tpu.docker.image import ImageConfig, ImageName
from makisu_tpu.dockerfile import parse_file
from makisu_tpu.storage import ImageStore


@pytest.fixture
def env(tmp_path):
    """(root, context, store, make_ctx) fixture bundle."""
    root = tmp_path / "root"
    root.mkdir()
    ctx_dir = tmp_path / "context"
    ctx_dir.mkdir()
    (ctx_dir / "hello.txt").write_text("hello world\n")
    (ctx_dir / "app").mkdir()
    (ctx_dir / "app" / "main.py").write_text("print('hi')\n")
    store = ImageStore(str(tmp_path / "store"))

    def make_ctx():
        return BuildContext(str(root), str(ctx_dir), store, sync_wait=0.0)

    return root, ctx_dir, store, make_ctx


def run_build(make_ctx, dockerfile_text, *, modify_fs=False, cache=None,
              target="", build_args=None, force_commit=False):
    stages = parse_file(dockerfile_text, build_args)
    ctx = make_ctx()
    plan = BuildPlan(ctx, ImageName("", "test/app", "latest"), [],
                     cache or NoopCacheManager(), stages,
                     allow_modify_fs=modify_fs, force_commit=force_commit,
                     stage_target=target)
    return plan.execute(), ctx


def read_layer(store, descriptor):
    with store.layers.open(descriptor.digest.hex()) as f:
        data = gzip.decompress(f.read())
    with tarfile.open(fileobj=io.BytesIO(data), mode="r|") as tf:
        return {m.name: m for m in tf}


def load_config(store, manifest) -> ImageConfig:
    with store.layers.open(manifest.config.digest.hex()) as f:
        return ImageConfig.from_json(json.load(f))


DOCKERFILE_SIMPLE = """
FROM scratch
COPY hello.txt /hello.txt
COPY app /srv/app/
ENV GREETING=hi
LABEL team=build
EXPOSE 8080
ENTRYPOINT ["/bin/app"]
CMD ["serve"]
"""


def test_simple_build_produces_manifest_and_layers(env):
    root, ctx_dir, store, make_ctx = env
    manifest, _ = run_build(make_ctx, DOCKERFILE_SIMPLE)
    # Two COPY layers (each committed separately? no — copies batch into
    # the final forced commit). At least one layer must exist.
    assert manifest.layers
    config = load_config(store, manifest)
    assert config.config.entrypoint == ["/bin/app"]
    assert config.config.cmd == ["serve"]
    assert config.config.labels == {"team": "build"}
    assert "8080/tcp" in config.config.exposed_ports
    assert "GREETING=hi" in config.config.env
    assert len(config.rootfs.diff_ids) == len(manifest.layers)
    # The last layer carries both copies.
    members = {}
    for desc in manifest.layers:
        members.update(read_layer(store, desc))
    assert "hello.txt" in members
    assert "srv/app/main.py" in members


def test_layer_digests_are_correct(env):
    root, ctx_dir, store, make_ctx = env
    manifest, _ = run_build(make_ctx, "FROM scratch\nCOPY hello.txt /h\n")
    desc = manifest.layers[-1]
    with store.layers.open(desc.digest.hex()) as f:
        blob = f.read()
    import hashlib
    assert hashlib.sha256(blob).hexdigest() == desc.digest.hex()
    assert desc.size == len(blob)
    config = load_config(store, manifest)
    tar_bytes = gzip.decompress(blob)
    assert config.rootfs.diff_ids[-1].split(":")[1] == \
        hashlib.sha256(tar_bytes).hexdigest()


def test_workdir_and_relative_copy(env):
    root, ctx_dir, store, make_ctx = env
    manifest, _ = run_build(
        make_ctx, "FROM scratch\nWORKDIR /srv\nCOPY hello.txt greeting\n")
    config = load_config(store, manifest)
    assert config.config.working_dir == "/srv"
    members = {}
    for desc in manifest.layers:
        members.update(read_layer(store, desc))
    assert "srv/greeting" in members


def test_build_args_flow(env):
    root, ctx_dir, store, make_ctx = env
    df = "ARG VER\nFROM scratch\nARG VER\nLABEL version=$VER\n"
    manifest, _ = run_build(make_ctx, df, build_args={"VER": "1.2.3"})
    config = load_config(store, manifest)
    assert config.config.labels == {"version": "1.2.3"}


def test_target_stage_stops_early(env):
    root, ctx_dir, store, make_ctx = env
    df = ("FROM scratch AS base\nLABEL stage=base\n"
          "FROM scratch AS final\nLABEL stage=final\n")
    manifest, _ = run_build(make_ctx, df, target="base")
    config = load_config(store, manifest)
    assert config.config.labels == {"stage": "base"}


def test_unknown_target_rejected(env):
    root, ctx_dir, store, make_ctx = env
    with pytest.raises(ValueError):
        run_build(make_ctx, "FROM scratch\n", target="nope")


def test_multistage_copy_from(env):
    root, ctx_dir, store, make_ctx = env
    df = ("FROM scratch AS builder\n"
          "COPY hello.txt /out/artifact\n"
          "FROM scratch\n"
          "COPY --from=builder /out/artifact /deploy/artifact\n")
    manifest, _ = run_build(make_ctx, df, modify_fs=True)
    members = {}
    for desc in manifest.layers:
        members.update(read_layer(store, desc))
    assert "deploy/artifact" in members


def test_multistage_without_modifyfs_rejected(env):
    root, ctx_dir, store, make_ctx = env
    df = ("FROM scratch AS a\nCOPY hello.txt /x\n"
          "FROM scratch\nCOPY --from=a /x /y\n")
    with pytest.raises(ValueError):
        run_build(make_ctx, df)


def test_cache_roundtrip_skips_execution(env):
    root, ctx_dir, store, make_ctx = env
    kv = MemoryStore()
    df = "FROM scratch\nCOPY hello.txt /h\nLABEL x=y #!COMMIT\n"

    cache1 = CacheManager(kv, store)
    manifest1, _ = run_build(make_ctx, df, cache=cache1)
    cache1.wait_for_push()
    assert kv._data  # entries recorded

    cache2 = CacheManager(kv, store)
    manifest2, ctx2 = run_build(make_ctx, df, cache=cache2)
    assert [str(l.digest) for l in manifest1.layers] == \
        [str(l.digest) for l in manifest2.layers]


def test_explicit_commit_controls_layers(env):
    root, ctx_dir, store, make_ctx = env
    df_implicit = ("FROM scratch\nCOPY hello.txt /a\nCOPY hello.txt /b\n")
    m1, _ = run_build(make_ctx, df_implicit)
    # Implicit mode: copies fold into the final forced commit → 1 layer.
    assert len(m1.layers) == 1

    df_explicit = ("FROM scratch\nCOPY hello.txt /a #!COMMIT\n"
                   "COPY hello.txt /b #!COMMIT\n")
    m2, _ = run_build(make_ctx, df_explicit)
    assert len(m2.layers) == 2


def test_force_commit_layers_every_step(env):
    root, ctx_dir, store, make_ctx = env
    df = "FROM scratch\nCOPY hello.txt /a\nCOPY hello.txt /b\n"
    manifest, _ = run_build(make_ctx, df, force_commit=True)
    assert len(manifest.layers) == 2


def test_tpu_hasher_build_records_chunks(env, tmp_path):
    root, ctx_dir, store, make_ctx = env
    from makisu_tpu.chunker import TPUHasher

    def make_tpu_ctx():
        ctx = make_ctx()
        ctx.hasher = TPUHasher()
        return ctx

    kv = MemoryStore()
    cache = CacheManager(kv, store)
    manifest, _ = run_build(make_tpu_ctx, "FROM scratch\nCOPY app /app/\n",
                            cache=cache)
    cache.wait_for_push()
    entries = [json.loads(v) for v in kv._data.values()
               if v != "MAKISU_TPU_CACHE_EMPTY"]
    assert any("chunks" in e for e in entries)


def test_cache_manager_thread_safety(tmp_path):
    """Concurrent push/pull against one manager (the reference runs its
    storage suites under stress; -race parity for our threaded paths)."""
    import threading

    from makisu_tpu.cache import CacheManager, MemoryStore
    from makisu_tpu.cache.manager import CacheMiss
    from makisu_tpu.docker.image import (
        MEDIA_TYPE_LAYER,
        Descriptor,
        Digest,
        DigestPair,
    )
    from makisu_tpu.storage import ImageStore

    store = ImageStore(str(tmp_path / "s"))
    mgr = CacheManager(MemoryStore(), store)
    errors = []

    def pusher(i):
        try:
            for j in range(20):
                blob = f"{i}-{j}".encode()
                digest = Digest.of_bytes(blob)
                store.layers.write_bytes(digest.hex(), blob)
                pair = DigestPair(digest, Descriptor(
                    MEDIA_TYPE_LAYER, len(blob), digest))
                mgr.push_cache(f"id-{i}-{j}", pair)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    def puller(i):
        try:
            for j in range(20):
                try:
                    mgr.pull_cache(f"id-{i}-{j}")
                except CacheMiss:
                    pass
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=fn, args=(i,))
               for i in range(4) for fn in (pusher, puller)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    mgr.wait_for_push()
    assert not errors
    assert mgr.pull_cache("id-0-0") is not None


def test_fs_store_merges_across_instances(tmp_path):
    """Two FSStore instances over one file (worker + CLI sharing a
    storage dir) must not clobber each other's entries."""
    from makisu_tpu.cache import FSStore
    path = str(tmp_path / "kv.json")
    a = FSStore(path)
    b = FSStore(path)
    a.put("from-a", "1")
    b.put("from-b", "2")
    fresh = FSStore(path)
    assert fresh.get("from-a") == "1"
    assert fresh.get("from-b") == "2"


def test_builds_are_reproducible(tmp_path):
    """Two independent builds of the same context produce byte-identical
    layer blobs (mtime-preserving copies + deterministic gzip) — a
    property docker builds lack. RUN layers are exempt (execution
    timestamps); this covers COPY/metadata builds."""
    ctx_dir = tmp_path / "ctx"
    ctx_dir.mkdir()
    (ctx_dir / "app.py").write_text("print('x')\n")
    (ctx_dir / "lib").mkdir()
    (ctx_dir / "lib" / "util.py").write_text("pass\n")
    df = ("FROM scratch\nCOPY . /app/\nENV A=1\n"
          'ENTRYPOINT ["python", "/app/app.py"]\n')

    def build_once(name):
        root = tmp_path / f"root-{name}"
        root.mkdir()
        store = ImageStore(str(tmp_path / f"store-{name}"))
        ctx = BuildContext(str(root), str(ctx_dir), store, sync_wait=0.0)
        plan = BuildPlan(ctx, ImageName("", "repro/app", name), [],
                         NoopCacheManager(), parse_file(df),
                         allow_modify_fs=False, force_commit=False)
        manifest = plan.execute()
        return [str(l.digest) for l in manifest.layers]

    assert build_once("one") == build_once("two")


def test_synthesized_ancestor_dirs_are_timeless(tmp_path):
    """COPY . /app/ synthesizes /app from no source tree; its header
    must carry epoch mtime, not the wall clock — otherwise two builds
    of identical inputs straddling a second boundary produce different
    layer bytes (caught live: the reproducibility test above only
    passed when both builds landed in the same second)."""
    import tarfile as tf

    ctx_dir = tmp_path / "ctx"
    ctx_dir.mkdir()
    (ctx_dir / "app.py").write_text("print('x')\n")
    root = tmp_path / "root"
    root.mkdir()
    store = ImageStore(str(tmp_path / "store"))
    ctx = BuildContext(str(root), str(ctx_dir), store, sync_wait=0.0)
    plan = BuildPlan(ctx, ImageName("", "repro/tless", "t"), [],
                     NoopCacheManager(),
                     parse_file("FROM scratch\nCOPY . /app/deep/\n"),
                     allow_modify_fs=False, force_commit=True)
    manifest = plan.execute()
    hex_digest = manifest.layers[0].digest.hex()
    with store.layers.open(hex_digest) as f:
        with tf.open(fileobj=f, mode="r:gz") as tar:
            by_name = {m.name.rstrip("/"): m for m in tar.getmembers()}
    assert by_name["app"].mtime == 0
    assert by_name["app/deep"].mtime == 0
    # The real file keeps its source mtime (mtime-preserving copies).
    assert by_name["app/deep/app.py"].mtime == int(
        (ctx_dir / "app.py").stat().st_mtime)


# ---------------------------------------------------------------------------
# Deferred application of cached layers: a cached layer reaches the
# MemFS tree when a later step reads the tree, and never when none does.
# ---------------------------------------------------------------------------

_BASE_THEN_SRC = "FROM scratch\nCOPY base /app/\nCOPY src /app/\n"


class _Drive:
    """Builds ``tmp_path/ctx`` through the CLI, one storage and one
    root a name, and counts what the builds open."""

    def __init__(self, tmp_path, monkeypatch):
        from makisu_tpu.builder import stage
        from makisu_tpu.snapshot import MemFS
        from makisu_tpu.storage.cas import CASDir
        self.tmp_path = tmp_path
        self.ctx = tmp_path / "ctx"
        self.blob_opens: list[str] = []
        self.tar_merges = 0
        self.builds = 0
        # One clock reading for `created` and the history: two builds
        # of one image then write one config, byte for byte.
        monkeypatch.setattr(stage, "_now_iso",
                            lambda: "2026-01-01T00:00:00.000000Z")
        cas_open, merge = CASDir.open, MemFS.update_from_tar
        drive = self

        def counted_open(self, name):
            drive.blob_opens.append(name)
            return cas_open(self, name)

        def counted_merge(self, *args, **kwargs):
            drive.tar_merges += 1
            return merge(self, *args, **kwargs)
        monkeypatch.setattr(CASDir, "open", counted_open)
        monkeypatch.setattr(MemFS, "update_from_tar", counted_merge)

    def context(self, dockerfile=_BASE_THEN_SRC):
        """``base`` makes /app/sub and /app/same.txt; ``src`` writes
        over same.txt and adds under sub."""
        files = {"base/same.txt": "from base\n", "base/sub/kept.txt": "k\n",
                 "src/same.txt": "from src, longer\n",
                 "src/sub/added.txt": "a\n"}
        for name, text in files.items():
            path = self.ctx / name
            path.parent.mkdir(parents=True, exist_ok=True)
            path.write_text(text)
            os.utime(path, (1_700_000_000, 1_700_000_000))
        (self.ctx / "Dockerfile").write_text(dockerfile)

    def edit(self, name, text):
        (self.ctx / name).write_text(text)
        os.utime(self.ctx / name, (1_700_000_100, 1_700_000_100))

    def build(self, name, *flags, hasher="cpu"):
        """Returns (image, report): the image is every digest that
        names it (manifest's config and layers, the config's diff_ids);
        the counts are of this build alone."""
        self.builds += 1
        self.blob_opens.clear()
        self.tar_merges = 0
        report = self.tmp_path / f"report{self.builds}.json"
        root = self.tmp_path / f"root-{name}"
        root.mkdir(exist_ok=True)
        tag = f"deferred/{name}:{self.builds}"
        assert cli.main([
            "--log-level", "error", "--metrics-out", str(report),
            "build", str(self.ctx), "-t", tag, "--hasher", hasher,
            "--storage", str(self.tmp_path / f"storage-{name}"),
            "--root", str(root), *flags]) == 0
        opened = list(self.blob_opens)
        with ImageStore(str(self.tmp_path / f"storage-{name}")) as store:
            manifest = store.manifests.load(ImageName.parse(tag))
            config = load_config(store, manifest)
            self.layer_files = [read_layer(store, d)
                                for d in manifest.layers]
        layers = [d.digest.hex() for d in manifest.layers]
        self.layer_opens = [h for h in opened if h in layers]
        with open(report, encoding="utf-8") as f:
            report = json.load(f)
        return ({"config": manifest.config.digest.hex(), "layers": layers,
                 "sizes": [d.size for d in manifest.layers],
                 "diff_ids": list(config.rootfs.diff_ids)}, report)


def _replays(report):
    return {s["labels"]["result"]: s["value"] for s in
            report["counters"].get("makisu_layer_replay_total", [])}


def _span_parents(report, name):
    """The name of the parent of each span called ``name``."""
    found = []

    def walk(span, parent):
        if span["name"] == name:
            found.append(parent)
        for child in span.get("children", []):
            walk(child, span["name"])
    for top in report["spans"]:
        walk(top, "")
    return found


def _applied(report):
    return sum(s["value"] for s in report["counters"].get(
        "makisu_cached_layers_applied_total", []))


@pytest.fixture
def drive(tmp_path, monkeypatch):
    return _Drive(tmp_path, monkeypatch)


@pytest.fixture
def eager():
    """Switches deferral off: what ``defer`` is handed runs at once.
    The oracle that deferred builds are held to, digest for digest."""
    from makisu_tpu.snapshot import MemFS
    patch = pytest.MonkeyPatch()

    def switch(on):
        if on:
            patch.setattr(MemFS, "defer",
                          lambda self, digest_hex, apply: apply())
        else:
            patch.undo()
    yield switch
    patch.undo()


@pytest.mark.parametrize("hasher", ["cpu", "tpu"])
def test_fully_cached_rebuild_opens_no_layer_blob(drive, hasher):
    """Every step a cache hit: nothing reads the tree, so neither
    layer's blob is opened, inflated or parsed, each is counted
    ``unread`` once, and the image is the image of the build before."""
    drive.context()
    first, report = drive.build("a", hasher=hasher)
    assert _replays(report) == {} and len(first["layers"]) == 2
    for _ in range(2):  # without, then with, a resident session
        again, report = drive.build("a", hasher=hasher)
        assert again == first
        assert drive.layer_opens == [] and drive.tar_merges == 0
        assert _replays(report) == {"unread": 2}
        assert _applied(report) == 0


def test_rebuilt_last_layer_equals_eager_application(drive, eager):
    """Cached ``base``, rebuilt ``src`` that overwrites a file of
    base's and adds under a directory base made: the commit flushes
    the cached layer first, so the new layer is, digest for digest,
    the one an application at once gives. First by inflating the blob,
    then (third build of the chain) from the session's memo."""

    def chain(name):
        """A cold build, then two rebuilds with ``src`` edited."""
        drive.context()
        images = [drive.build(name)[0]]
        for n, result in enumerate(["inflate", "memo"]):
            drive.edit("src/same.txt", f"from src, edit {n}\n")
            drive.edit("src/sub/added.txt", "a" * (n + 2))
            image, report = drive.build(name)
            images.append(image)
            assert _replays(report) == {result: 1}
            assert _applied(report) == 1
            assert len(drive.layer_opens) == (result == "inflate")
        return images, report

    images, report = chain("deferred")
    assert _span_parents(report, "apply_layer") == ["commit_layer"]
    cold, *edited = images
    for image in edited:
        assert image["layers"][0] == cold["layers"][0]
        assert image["layers"][1] != cold["layers"][1]
    # Ancestors base made ride in the new layer as base left them.
    assert {"app", "app/same.txt", "app/sub", "app/sub/added.txt"} \
        <= set(drive.layer_files[1])
    eager(True)
    images_eager, report = chain("eager")
    assert _span_parents(report, "apply_layer") == ["step"]
    assert images_eager == images


@pytest.mark.parametrize("case", ["run_after_cached_copy",
                                  "copied_from_stage"])
def test_layers_needed_on_disk_are_applied_at_once(drive, case):
    """``--modifyfs`` stages whose disk is read (a ``RUN``, a later
    ``COPY --from``) defer nothing: the cached layer is unpacked at its
    own step, and the step after it finds the files."""
    if case == "run_after_cached_copy":
        dockerfiles = [
            "FROM scratch\nCOPY base /app/\n"
            f"RUN cat app/same.txt > seen{n}.txt\n" for n in (1, 2)]
        found = "seen2.txt"
    else:
        dockerfiles = [
            "FROM scratch AS builder\nCOPY base /out/\nFROM scratch\n"
            f"COPY --from=builder /out/same.txt /deploy/{n}.txt\n"
            for n in (1, 2)]
        found = "deploy/2.txt"
    drive.context(dockerfiles[0])
    drive.build("a", "--modifyfs")
    drive.context(dockerfiles[1])
    image, report = drive.build("a", "--modifyfs")
    assert _replays(report) == {"inflate": 1} and _applied(report) == 1
    assert drive.tar_merges == 1
    assert _span_parents(report, "apply_layer") == ["step"]
    member = drive.layer_files[-1][found]
    assert member.size == len("from base\n")
