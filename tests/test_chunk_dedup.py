"""Chunk-granular cache dedup tests: the headline capability the
reference lacks (whole-layer cache only)."""

import json
import os

import pytest

from conftest import cas_entry_path, committed_layer

from makisu_tpu.builder import BuildPlan
from makisu_tpu.cache import CacheManager, MemoryStore
from makisu_tpu.cache.chunks import ChunkStore, attach_chunk_dedup
from makisu_tpu.chunker import TPUHasher
from makisu_tpu.context import BuildContext
from makisu_tpu.docker.image import ImageName
from makisu_tpu.dockerfile import parse_file
from makisu_tpu.storage import ImageStore


def build(tmp_path, tag, kv, chunk_root, store_name, payload: bytes):
    """One builder instance with its own layer store but shared KV and
    shared chunk store (simulating two machines + distributed planes)."""
    ctx_dir = tmp_path / f"ctx-{tag}"
    if not ctx_dir.exists():
        ctx_dir.mkdir()
        (ctx_dir / "blob.bin").write_bytes(payload)
    root = tmp_path / f"root-{tag}"
    root.mkdir(exist_ok=True)
    store = ImageStore(str(tmp_path / store_name))
    ctx = BuildContext(str(root), str(ctx_dir), store,
                       hasher=TPUHasher(), sync_wait=0.0)
    mgr = CacheManager(kv, store)
    attach_chunk_dedup(mgr, str(chunk_root))
    stages = parse_file("FROM scratch\nCOPY blob.bin /blob.bin\n")
    plan = BuildPlan(ctx, ImageName("", "t/dedup", tag), [], mgr, stages,
                     allow_modify_fs=False, force_commit=True)
    manifest = plan.execute()
    mgr.wait_for_push()
    return manifest, store, mgr


def test_layer_reconstitution_across_builders(tmp_path):
    import numpy as np
    payload = np.random.default_rng(0).integers(
        0, 256, size=150_000, dtype=np.uint8).tobytes()
    kv = MemoryStore()
    chunk_root = tmp_path / "chunks"

    # Builder A: populates KV + chunk store.
    manifest_a, store_a, _ = build(tmp_path, "a", kv, chunk_root,
                                   "store-a", payload)
    # Builder B: fresh layer store, same KV + chunks, same context bytes.
    # Its cache pull must reconstitute the layer without the blob.
    ctx_dir = tmp_path / "ctx-a"  # same content → same cache IDs
    root = tmp_path / "root-b"
    root.mkdir()
    store_b = ImageStore(str(tmp_path / "store-b"))
    ctx = BuildContext(str(root), str(ctx_dir), store_b,
                       hasher=TPUHasher(), sync_wait=0.0)
    mgr = CacheManager(kv, store_b)
    attach_chunk_dedup(mgr, str(chunk_root))
    stages = parse_file("FROM scratch\nCOPY blob.bin /blob.bin\n")
    plan = BuildPlan(ctx, ImageName("", "t/dedup", "b"), [], mgr, stages,
                     allow_modify_fs=False, force_commit=True)
    manifest_b = plan.execute()
    assert [str(l.digest) for l in manifest_a.layers] == \
        [str(l.digest) for l in manifest_b.layers]
    # Lazy contract: the build itself never produced the gzip blob (the
    # layer applied straight from chunks — no transfer, no gzip work)...
    hex_digest = manifest_b.layers[0].digest.hex()
    assert not store_b.layers.exists(hex_digest)
    # ...and materialization on demand (export/push-upload paths)
    # rebuilds it from chunks, byte-identical to A's blob.
    mgr.materialize_pending()
    assert store_b.layers.exists(hex_digest)
    with store_b.layers.open(hex_digest) as fb:
        with store_a.layers.open(hex_digest) as fa:
            assert fb.read() == fa.read()


def test_warm_rebuild_after_edit_moves_no_blob_bytes(tmp_path):
    """The north-star scenario end to end: builder A builds v2 (1% edit
    of v1) and pushes; builder B — who built v1, so holds v1's chunks —
    rebuilds v2. B's build must (a) hit the cache, (b) transfer only
    the NOVEL chunks (never the blob), (c) apply the layer without
    creating the gzip blob at all, and (d) push with zero blob uploads
    (the registry already has A's blob). The reference's whole-layer
    cache transfers the full blob for the same rebuild."""
    import numpy as np

    from makisu_tpu.registry import RegistryClient, RegistryFixture
    from makisu_tpu.storage import ImageStore as IS

    rng = np.random.default_rng(3)
    v1 = rng.integers(0, 256, size=600_000, dtype=np.uint8).tobytes()
    v2 = v1[:5_000] + b"EDITEDEDIT" + v1[5_000:]  # ~1% novelty w/ shift
    kv = MemoryStore()
    fixture = RegistryFixture()

    def one_build(tag, store_name, chunk_name, payload, push=False):
        ctx_dir = tmp_path / f"ctx-{tag}"
        ctx_dir.mkdir(exist_ok=True)
        (ctx_dir / "blob.bin").write_bytes(payload)
        root = tmp_path / f"root-{tag}"
        root.mkdir(exist_ok=True)
        store = IS(str(tmp_path / store_name))
        client = RegistryClient(store, "registry.test", "cache/ns",
                                transport=fixture)
        ctx = BuildContext(str(root), str(ctx_dir), store,
                           hasher=TPUHasher(), sync_wait=0.0)
        mgr = CacheManager(kv, store, registry_client=client)
        attach_chunk_dedup(mgr, str(tmp_path / chunk_name))
        stages = parse_file("FROM scratch\nCOPY blob.bin /blob.bin\n")
        plan = BuildPlan(ctx, ImageName("", "t/ns", tag), [], mgr,
                        stages, allow_modify_fs=False, force_commit=True)
        manifest = plan.execute()
        mgr.wait_for_push()
        if push:
            push_client = RegistryClient(store, "registry.test",
                                         "cache/ns", transport=fixture)
            push_client.materialize_blob = mgr.materialize
            for layer in manifest.layers:
                push_client.push_layer(layer.digest)
        return manifest, store, mgr

    # B builds v1 first (its chunk store now holds v1's chunks).
    one_build("b-v1", "store-b", "chunks-b", v1)
    # A builds v2 and pushes blob + chunks + KV entries.
    m_a, _, _ = one_build("a-v2", "store-a", "chunks-a", v2, push=True)
    layer_hex = m_a.layers[0].digest.hex()
    assert layer_hex in fixture.blobs

    # B rebuilds v2. Count the blob traffic its build generates.
    before = len(fixture.requests)
    m_b, store_b, _ = one_build("b-v2", "store-b", "chunks-b", v2,
                                push=True)
    new_requests = fixture.requests[before:]
    assert [str(l.digest) for l in m_b.layers] == \
        [str(l.digest) for l in m_a.layers]
    # (b) the layer blob was never downloaded...
    blob_gets = [u for m, u in new_requests
                 if m == "GET" and layer_hex in u]
    assert blob_gets == []
    # ...novel chunks were (a strict subset of the layer's chunks).
    chunk_gets = [u for m, u in new_requests
                  if m == "GET" and "/blobs/sha256:" in u]
    assert 0 < len(chunk_gets) < 20
    # (c) B never produced the gzip blob locally...
    assert not store_b.layers.exists(layer_hex)
    # (d) ...and pushed nothing: the registry had every blob already.
    uploads = [u for m, u in new_requests
               if m in ("POST", "PATCH", "PUT") and "/blobs/" in u]
    assert uploads == []


def test_lazy_cache_disabled_restores_eager_pull(tmp_path, monkeypatch):
    """MAKISU_TPU_LAZY_CACHE=0: a cache hit transfers the blob at pull
    time, exactly the old (and reference) behavior."""
    import numpy as np

    from makisu_tpu.registry import RegistryClient, RegistryFixture
    from makisu_tpu.storage import ImageStore as IS

    monkeypatch.setenv("MAKISU_TPU_LAZY_CACHE", "0")
    payload = np.random.default_rng(4).integers(
        0, 256, size=200_000, dtype=np.uint8).tobytes()
    kv = MemoryStore()
    fixture = RegistryFixture()
    ctx_dir = tmp_path / "ctx"
    ctx_dir.mkdir()
    (ctx_dir / "blob.bin").write_bytes(payload)

    def one_builder(tag, store_name):
        root = tmp_path / f"root-{tag}"
        root.mkdir(exist_ok=True)
        store = IS(str(tmp_path / store_name))
        client = RegistryClient(store, "registry.test", "cache/eager",
                                transport=fixture)
        ctx = BuildContext(str(root), str(ctx_dir), store,
                           hasher=TPUHasher(), sync_wait=0.0)
        mgr = CacheManager(kv, store, registry_client=client)
        stages = parse_file("FROM scratch\nCOPY blob.bin /blob.bin\n")
        plan = BuildPlan(ctx, ImageName("", "t/eager", tag), [], mgr,
                         stages, allow_modify_fs=False,
                         force_commit=True)
        manifest = plan.execute()
        mgr.wait_for_push()
        for layer in manifest.layers:
            RegistryClient(store, "registry.test", "cache/eager",
                           transport=fixture).push_layer(layer.digest)
        return manifest, store

    m1, _ = one_builder("a", "store-a")
    m2, store_b = one_builder("b", "store-b")
    assert [str(l.digest) for l in m1.layers] == \
        [str(l.digest) for l in m2.layers]
    # Eager: the blob IS local right after the build.
    assert store_b.layers.exists(m2.layers[0].digest.hex())


def test_lazy_disabled_applies_to_chunk_route(tmp_path, monkeypatch):
    """MAKISU_TPU_LAZY_CACHE=0 with chunk dedup attached: the hit is
    still chunk-served (no blob transfer) but materializes EAGERLY at
    pull time, honoring the documented kill switch (r4 advice, low
    #2)."""
    import numpy as np
    monkeypatch.setenv("MAKISU_TPU_LAZY_CACHE", "0")
    payload = np.random.default_rng(7).integers(
        0, 256, size=150_000, dtype=np.uint8).tobytes()
    kv = MemoryStore()
    chunk_root = tmp_path / "chunks"
    manifest_a, _, _ = build(tmp_path, "a", kv, chunk_root,
                             "store-a", payload)
    # Builder B, same KV + chunk root: hits the chunk route.
    ctx_dir = tmp_path / "ctx-a"
    root = tmp_path / "root-b"
    root.mkdir()
    store_b = ImageStore(str(tmp_path / "store-b"))
    ctx = BuildContext(str(root), str(ctx_dir), store_b,
                       hasher=TPUHasher(), sync_wait=0.0)
    mgr = CacheManager(kv, store_b)
    attach_chunk_dedup(mgr, str(chunk_root))
    stages = parse_file("FROM scratch\nCOPY blob.bin /blob.bin\n")
    plan = BuildPlan(ctx, ImageName("", "t/dedup", "b"), [], mgr, stages,
                     allow_modify_fs=False, force_commit=True)
    manifest_b = plan.execute()
    assert [str(l.digest) for l in manifest_b.layers] == \
        [str(l.digest) for l in manifest_a.layers]
    # Eager: the blob exists locally right after the build, with no
    # materialize_pending() call — reconstituted from chunks at pull.
    assert store_b.layers.exists(manifest_b.layers[0].digest.hex())


def test_unusable_gzip_backend_degrades_to_miss_at_pull(tmp_path):
    """A cache entry recording a compression backend THIS process
    cannot replay must not be accepted on the chunk route: byte-exact
    reconstitution is unpromisable, so the pull falls to the blob
    route, whose HEAD check degrades a blobless hit to a miss — the
    build re-executes instead of failing later at export/push time
    (r4 advice, medium)."""
    import numpy as np

    from makisu_tpu.registry import RegistryClient, RegistryFixture
    from makisu_tpu.storage import ImageStore as IS

    payload = np.random.default_rng(9).integers(
        0, 256, size=150_000, dtype=np.uint8).tobytes()
    kv = MemoryStore()
    fixture = RegistryFixture()
    ctx_dir = tmp_path / "ctx"
    ctx_dir.mkdir()
    (ctx_dir / "blob.bin").write_bytes(payload)
    chunk_root = tmp_path / "chunks"

    def one_builder(tag, store_name):
        root = tmp_path / f"root-{tag}"
        root.mkdir(exist_ok=True)
        store = IS(str(tmp_path / store_name))
        client = RegistryClient(store, "registry.test", "cache/gzb",
                                transport=fixture)
        ctx = BuildContext(str(root), str(ctx_dir), store,
                           hasher=TPUHasher(), sync_wait=0.0)
        mgr = CacheManager(kv, store, registry_client=client)
        attach_chunk_dedup(mgr, str(chunk_root))
        stages = parse_file("FROM scratch\nCOPY blob.bin /blob.bin\n")
        plan = BuildPlan(ctx, ImageName("", "t/gzb", tag), [], mgr,
                         stages, allow_modify_fs=False,
                         force_commit=True)
        manifest = plan.execute()
        mgr.wait_for_push()
        return manifest, store, mgr

    manifest_a, _, _ = one_builder("a", "store-a")
    # The layer blob was never pushed to the registry — only chunks
    # (background push) and KV entries exist. Sabotage every entry's
    # recorded gzip identity to a backend no process has.
    with kv._lock:
        for key, raw in list(kv._data.items()):
            try:
                entry = json.loads(raw)
            except json.JSONDecodeError:
                continue  # EMPTY sentinel
            if isinstance(entry, dict) and "gz" in entry:
                entry["gz"] = "zstd-6"
                kv._data[key] = json.dumps(entry,
                                           separators=(",", ":"))
    # Builder B: chunks are all local (shared root), but the entry is
    # unreplayable and the registry lacks the blob → miss → re-execute.
    manifest_b, store_b, mgr_b = one_builder("b", "store-b")
    assert [str(l.digest) for l in manifest_b.layers] == \
        [str(l.digest) for l in manifest_a.layers]
    # Because the step re-executed, the blob is locally committed and
    # every export path works — nothing deferred onto a promise the
    # process can't keep.
    mgr_b.materialize_pending()
    assert store_b.layers.exists(manifest_b.layers[0].digest.hex())


def test_ensure_available_fetches_repeated_digest_once(tmp_path):
    """A digest appearing at several offsets in one layer fetches once,
    not once per occurrence (r4 advice, low #3)."""
    from makisu_tpu.docker.image import Digest

    store = ChunkStore(str(tmp_path / "chunks"))
    fetched = []

    class CountingRegistry:
        def pull_layer(self, digest):
            fetched.append(digest.hex())
            store.put(digest.hex(), b"x" * 10)

    import hashlib as hl
    hex_digest = hl.sha256(b"x" * 10).hexdigest()
    store.registry = CountingRegistry()
    store._fetch_remote = (
        lambda h: (store.registry.pull_layer(Digest.from_hex(h)), True)[1])
    chunks = [(0, 10, hex_digest), (10, 10, hex_digest),
              (20, 10, hex_digest)]
    assert store.ensure_available(chunks)
    assert fetched == [hex_digest]


def _registry_builder(tmp_path, kv, fixture, tag, store_name,
                      chunk_name, payload, repo="t/packs"):
    """One registry-attached builder; returns (manifest, store, mgr)."""
    from makisu_tpu.registry import RegistryClient
    from makisu_tpu.storage import ImageStore as IS

    ctx_dir = tmp_path / f"ctx-{tag}"
    ctx_dir.mkdir(exist_ok=True)
    (ctx_dir / "blob.bin").write_bytes(payload)
    root = tmp_path / f"root-{tag}"
    root.mkdir(exist_ok=True)
    store = IS(str(tmp_path / store_name))
    client = RegistryClient(store, "registry.test", repo,
                            transport=fixture)
    ctx = BuildContext(str(root), str(ctx_dir), store,
                       hasher=TPUHasher(), sync_wait=0.0)
    mgr = CacheManager(kv, store, registry_client=client)
    attach_chunk_dedup(mgr, str(tmp_path / chunk_name))
    stages = parse_file("FROM scratch\nCOPY blob.bin /blob.bin\n")
    plan = BuildPlan(ctx, ImageName("", repo, tag), [], mgr, stages,
                     allow_modify_fs=False, force_commit=True)
    manifest = plan.execute()
    mgr.wait_for_push()
    return manifest, store, mgr


def test_pack_wire_format_cuts_round_trips(tmp_path):
    """Chunks cross the wire grouped into pack blobs: a consumer with
    NO local chunks fetches a few packs, not one blob per ~8KiB chunk.
    Round trips, not bytes, dominate small-blob transfer — this is what
    makes chunk dedup usable at 100k-chunk layer scale."""
    import numpy as np

    from makisu_tpu.registry import RegistryFixture

    payload = np.random.default_rng(21).integers(
        0, 256, size=600_000, dtype=np.uint8).tobytes()
    kv = MemoryStore()
    fixture = RegistryFixture()

    # Builder A: pushes entry + packs (~70 chunks at 8KiB avg).
    m_a, _, _ = _registry_builder(tmp_path, kv, fixture, "a", "store-a",
                                  "chunks-a", payload)
    # The pack mapping landed on the KV entry.
    entries = [json.loads(v) for v in kv._data.values()
               if isinstance(v, str) and v.startswith("{")]
    packed = [e for e in entries if e.get("packs")]
    assert packed, "entry should record the chunk->pack mapping"
    n_chunks = len(packed[0]["chunks"])
    assert n_chunks > 20
    mapped = {i for _, members in packed[0]["packs"] for i in members}
    assert mapped == set(range(n_chunks))  # first build: all chunks new

    # Builder B: fresh chunk store, shared KV -> must fetch everything.
    before = len(fixture.requests)
    m_b, store_b, _ = _registry_builder(tmp_path, kv, fixture, "b",
                                        "store-b", "chunks-b", payload)
    assert [str(l.digest) for l in m_b.layers] == \
        [str(l.digest) for l in m_a.layers]
    blob_gets = [u for m, u in fixture.requests[before:]
                 if m == "GET" and "/blobs/sha256:" in u]
    # One pack (600KB < 8MB target) — not ~70 per-chunk GETs.
    assert len(blob_gets) <= 3, blob_gets
    # And the hit is real: the layer applied without the gzip blob.
    assert not store_b.layers.exists(m_b.layers[0].digest.hex())


def test_pack_fetch_verifies_and_degrades_on_corruption(tmp_path):
    """A corrupt pack must not poison the chunk CAS: members are
    digest-verified at carve time, corrupt ones stay missing, and the
    pull degrades to the per-chunk/blob route."""
    import numpy as np

    from makisu_tpu.registry import RegistryFixture

    payload = np.random.default_rng(22).integers(
        0, 256, size=300_000, dtype=np.uint8).tobytes()
    kv = MemoryStore()
    fixture = RegistryFixture()
    m_a, _, _ = _registry_builder(tmp_path, kv, fixture, "a", "store-a",
                                  "chunks-a", payload, repo="t/corrupt")
    layer_hex = m_a.layers[0].digest.hex()
    # Push A's blob so the blob route can save the day.
    from makisu_tpu.registry import RegistryClient
    from makisu_tpu.storage import ImageStore as IS
    push_client = RegistryClient(IS(str(tmp_path / "store-a")),
                                 "registry.test", "t/corrupt",
                                 transport=fixture)
    push_client.push_layer(m_a.layers[0].digest)
    # Corrupt every pack blob in the registry (keep sizes).
    entries = [json.loads(v) for v in kv._data.values()
               if isinstance(v, str) and v.startswith("{")]
    pack_hexes = {p for e in entries for p, _ in e.get("packs", [])}
    assert pack_hexes
    for pack_hex in pack_hexes:
        blob = fixture.blobs[pack_hex]
        fixture.blobs[pack_hex] = b"\x00" * len(blob)

    # Builder B: pack fetch fails verification -> falls through; the
    # build must still succeed (blob route) and never cache bad bytes.
    m_b, _, mgr_b = _registry_builder(tmp_path, kv, fixture, "b",
                                      "store-b", "chunks-b", payload,
                                      repo="t/corrupt")
    assert [str(l.digest) for l in m_b.layers] == \
        [str(l.digest) for l in m_a.layers]
    chunk_cas = ChunkStore(str(tmp_path / "chunks-b")).cas
    for e in entries:
        for _, _, hex_digest in e.get("chunks", []):
            if chunk_cas.exists(hex_digest):
                with chunk_cas.open(hex_digest) as f:
                    data = f.read()
                import hashlib as hl
                assert hl.sha256(data).hexdigest() == hex_digest


def test_single_member_pack_aliases_its_chunk_safely(tmp_path):
    """A pack with one member has the member's own bytes and therefore
    the member's own DIGEST — pack cleanup must not delete the chunk it
    aliases (producer side), and a consumer's whole-pack fetch must
    leave the chunk present."""
    import gzip as gz
    import hashlib as hl

    from makisu_tpu.docker.image import Digest

    data = b"q" * 5000
    chunk_hex = hl.sha256(data).hexdigest()
    blob = gz.compress(data, mtime=0)
    blob_path = tmp_path / "layer.gz"
    blob_path.write_bytes(blob)
    store = ChunkStore(str(tmp_path / "chunks"))
    triples = [(0, len(data), chunk_hex)]
    added = store.index_layer(str(blob_path), triples)
    assert added == [chunk_hex]
    packs = store.build_packs(triples, added)
    assert len(packs) == 1 and packs[0][0] == chunk_hex  # the alias
    store.drop_local_packs(packs)
    assert store.cas.exists(chunk_hex)  # producer kept its chunk

    # Consumer: fresh store; whole-pack fetch (single member = 100%
    # needed) must store the chunk and not delete it afterwards.
    consumer = ChunkStore(str(tmp_path / "chunks2"))

    class OneBlobRegistry:
        def pull_layer(self, digest):
            assert digest.hex() == chunk_hex
            consumer.cas.write_bytes(chunk_hex, data)

        def pull_blob_range(self, digest, start, end):
            return None  # force the whole-pack branch

    consumer.registry = OneBlobRegistry()
    assert consumer.ensure_available(triples,
                                     [[chunk_hex, [0]]])
    assert consumer.cas.exists(chunk_hex)


def test_pack_roundtrip_property_randomized(tmp_path):
    """Randomized pack-plane property: for arbitrary chunk layouts
    (sizes, duplicate digests, added-subsets), build_packs + a
    fixture-registry fetch through ensure_available reproduces every
    added chunk bit-exactly, for whole-pack AND ranged regimes."""
    import gzip as gz
    import hashlib as hl
    import random

    from makisu_tpu.docker.image import Digest

    rnd = random.Random(77)
    for trial in range(6):
        sizes = [rnd.randint(1, 30_000) for _ in range(rnd.randint(1, 60))]
        blobs = []
        # Some duplicate contents (same digest at several offsets).
        for i, n in enumerate(sizes):
            if i > 2 and rnd.random() < 0.2:
                blobs.append(blobs[rnd.randrange(i)])
            else:
                blobs.append(rnd.randbytes(sizes[i]))
        stream = b"".join(blobs)
        triples, pos = [], 0
        for data in blobs:
            triples.append((pos, len(data),
                            hl.sha256(data).hexdigest()))
            pos += len(data)
        blob_path = tmp_path / f"layer{trial}.gz"
        blob_path.write_bytes(gz.compress(stream, mtime=0))

        producer = ChunkStore(str(tmp_path / f"prod{trial}"))
        added = producer.index_layer(str(blob_path), triples)
        packs = producer.build_packs(triples, added)
        # Every added digest appears in exactly one pack; members map
        # to the recorded indices.
        mapped = [triples[i][2] for _, members in packs
                  for i in members]
        assert sorted(mapped) == sorted(added)

        # Serve packs from an in-memory "registry"; consumer carves.
        pack_bytes = {p: producer.get(p) for p, _ in packs}
        producer.drop_local_packs(packs)
        consumer = ChunkStore(str(tmp_path / f"cons{trial}"))

        class PackRegistry:
            def pull_layer(self, digest):
                consumer.cas.write_bytes(digest.hex(),
                                         pack_bytes[digest.hex()])

            def pull_blob_range(self, digest, start, end):
                if trial % 2:  # alternate regimes
                    return None  # force whole-pack
                return "partial", pack_bytes[digest.hex()][start:end]

        consumer.registry = PackRegistry()
        assert consumer.ensure_available(
            triples, [[p, members] for p, members in packs])
        for offset, length, hex_digest in triples:
            data = consumer.get(hex_digest)
            assert hl.sha256(data).hexdigest() == hex_digest
            assert data == stream[offset:offset + length]


def test_packs_disabled_restores_per_chunk_blobs(tmp_path, monkeypatch):
    """MAKISU_TPU_CHUNK_PACKS=0: chunks push individually (the v1 wire
    format) and consumers fetch them individually."""
    import numpy as np

    from makisu_tpu.registry import RegistryFixture

    monkeypatch.setenv("MAKISU_TPU_CHUNK_PACKS", "0")
    payload = np.random.default_rng(23).integers(
        0, 256, size=200_000, dtype=np.uint8).tobytes()
    kv = MemoryStore()
    fixture = RegistryFixture()
    m_a, _, _ = _registry_builder(tmp_path, kv, fixture, "a", "store-a",
                                  "chunks-a", payload, repo="t/nopack")
    entries = [json.loads(v) for v in kv._data.values()
               if isinstance(v, str) and v.startswith("{")]
    assert not any(e.get("packs") for e in entries)
    before = len(fixture.requests)
    m_b, _, _ = _registry_builder(tmp_path, kv, fixture, "b", "store-b",
                                  "chunks-b", payload, repo="t/nopack")
    assert [str(l.digest) for l in m_b.layers] == \
        [str(l.digest) for l in m_a.layers]
    blob_gets = [u for m, u in fixture.requests[before:]
                 if m == "GET" and "/blobs/sha256:" in u]
    assert len(blob_gets) > 10  # one per chunk, the old wire shape


def test_chunk_coverage_after_small_edit(tmp_path):
    """Insert bytes near the front of a large file: most chunk bytes must
    be reusable (the >=3x warm-hit-rate story vs whole-layer caching)."""
    import numpy as np
    payload = np.random.default_rng(1).integers(
        0, 256, size=400_000, dtype=np.uint8).tobytes()
    kv = MemoryStore()
    chunk_root = tmp_path / "chunks"
    build(tmp_path, "a", kv, chunk_root, "store-1", payload)

    edited = payload[:500] + b"EDIT" + payload[500:]
    _, _, mgr = build(tmp_path, "edited", kv, chunk_root, "store-2",
                      edited)
    entries = [json.loads(v) for v in kv._data.values()
               if v != "MAKISU_TPU_CACHE_EMPTY"]
    chunked = [e for e in entries if "chunks" in e]
    assert chunked
    # Whole-layer dedup would reuse 0 bytes (layer digest changed);
    # chunk coverage of the edited layer should be mostly reusable.
    store = ChunkStore(str(chunk_root))
    best = max(store.coverage([tuple(c) for c in e["chunks"]])
               for e in chunked)
    assert best > 0.5


def test_reconstitute_refuses_missing_chunk(tmp_path):
    import hashlib

    from makisu_tpu.docker.image import (
        MEDIA_TYPE_LAYER,
        Descriptor,
        Digest,
        DigestPair,
    )
    store = ChunkStore(str(tmp_path / "chunks"))
    data = b"x" * 1000
    store.put(hashlib.sha256(data).hexdigest(), data)
    pair = DigestPair(Digest.of_bytes(data * 2),
                      Descriptor(MEDIA_TYPE_LAYER, 0, Digest.of_bytes(b"")))
    chunks = [(0, 1000, hashlib.sha256(data).hexdigest()),
              (1000, 1000, "ab" * 32)]  # second chunk missing
    assert store.reconstitute(pair, chunks) is None


def test_chunk_put_verifies_digest(tmp_path):
    store = ChunkStore(str(tmp_path / "chunks"))
    with pytest.raises(ValueError):
        store.put("00" * 32, b"not matching")


def test_chunks_distribute_through_registry_plane(tmp_path):
    """Two builders with SEPARATE chunk stores sharing only KV + registry:
    chunk blobs travel via the registry blob protocol."""
    import numpy as np

    from makisu_tpu.registry import RegistryClient, RegistryFixture
    from makisu_tpu.storage import ImageStore as IS

    payload = np.random.default_rng(3).integers(
        0, 256, size=120_000, dtype=np.uint8).tobytes()
    kv = MemoryStore()
    fixture = RegistryFixture()
    ctx_dir = tmp_path / "ctx"
    ctx_dir.mkdir()
    (ctx_dir / "blob.bin").write_bytes(payload)

    def one_builder(tag, store_name, chunk_name):
        root = tmp_path / f"root-{tag}"
        root.mkdir(exist_ok=True)
        store = IS(str(tmp_path / store_name))
        client = RegistryClient(store, "registry.test", "cache/chunks",
                                transport=fixture)
        ctx = BuildContext(str(root), str(ctx_dir), store,
                           hasher=TPUHasher(), sync_wait=0.0)
        mgr = CacheManager(kv, store, registry_client=client)
        attach_chunk_dedup(mgr, str(tmp_path / chunk_name))
        stages = parse_file("FROM scratch\nCOPY blob.bin /blob.bin\n")
        plan = BuildPlan(ctx, ImageName("", "t/remote", tag), [], mgr,
                         stages, allow_modify_fs=False, force_commit=True)
        manifest = plan.execute()
        mgr.wait_for_push()
        return manifest, store, mgr

    m1, _, _ = one_builder("a", "store-a", "chunks-a")
    assert fixture.blobs  # chunks + layers pushed to the registry
    # Builder B: empty layer store AND empty chunk store. Simulate the
    # layer blob being evicted from the registry (only chunks remain) so
    # reconstitution is the only path.
    layer_hex = m1.layers[0].digest.hex()
    evicted = fixture.blobs.pop(layer_hex)
    m2, store_b, mgr_b = one_builder("b", "store-b", "chunks-b")
    assert [str(l.digest) for l in m1.layers] == \
        [str(l.digest) for l in m2.layers]
    # Lazy contract: the build applied the layer from registry-fetched
    # chunks without producing the blob; materialization rebuilds it
    # byte-identical even though the registry no longer has it.
    assert not store_b.layers.exists(layer_hex)
    mgr_b.materialize_pending()
    assert store_b.layers.exists(layer_hex)
    with store_b.layers.open(layer_hex) as f:
        assert f.read() == evicted  # byte-identical reconstitution


def test_chunks_survive_registry_gc(tmp_path):
    """Registry GC deletes unreferenced blobs; the per-layer chunk-pin
    manifest must keep chunk blobs referenced so chunk-based
    reconstitution still works afterwards (the distributed chunk cache
    must not silently evaporate)."""
    import numpy as np

    from makisu_tpu.registry import RegistryClient, RegistryFixture
    from makisu_tpu.storage import ImageStore as IS

    payload = np.random.default_rng(9).integers(
        0, 256, size=150_000, dtype=np.uint8).tobytes()
    kv = MemoryStore()
    fixture = RegistryFixture()
    ctx_dir = tmp_path / "ctx"
    ctx_dir.mkdir()
    (ctx_dir / "blob.bin").write_bytes(payload)

    def one_builder(tag, store_name, chunk_name):
        root = tmp_path / f"root-{tag}"
        root.mkdir(exist_ok=True)
        store = IS(str(tmp_path / store_name))
        client = RegistryClient(store, "registry.test", "cache/gc",
                                transport=fixture)
        ctx = BuildContext(str(root), str(ctx_dir), store,
                           hasher=TPUHasher(), sync_wait=0.0)
        mgr = CacheManager(kv, store, registry_client=client)
        attach_chunk_dedup(mgr, str(tmp_path / chunk_name))
        stages = parse_file("FROM scratch\nCOPY blob.bin /blob.bin\n")
        plan = BuildPlan(ctx, ImageName("", "t/gc", tag), [], mgr,
                         stages, allow_modify_fs=False, force_commit=True)
        manifest = plan.execute()
        mgr.wait_for_push()
        return manifest, store, mgr

    m1, _, _ = one_builder("a", "store-a", "chunks-a")
    # A pin manifest exists for the layer (pack-route namespace: packs
    # are the wire format, so the pin references pack blobs).
    layer_hex = m1.layers[0].digest.hex()
    pin_tag = f"cache/gc:makisu-packs-{layer_hex[:40]}"
    assert pin_tag in fixture.manifests
    # The layer blob itself is unreferenced (no image manifest was
    # pushed) — GC deletes it. Chunk blobs survive via the pin.
    removed = fixture.gc()
    assert layer_hex in removed
    assert layer_hex not in fixture.blobs
    assert fixture.blobs  # pinned chunks survived
    # A fresh builder reconstitutes the layer purely from GC-surviving
    # chunks (lazily — materialization produces the actual blob).
    m2, store_b, mgr_b = one_builder("b", "store-b", "chunks-b")
    assert [str(l.digest) for l in m1.layers] == \
        [str(l.digest) for l in m2.layers]
    mgr_b.materialize_pending()
    assert store_b.layers.exists(layer_hex)


def test_reconstitute_streams_with_bounded_memory(tmp_path):
    """The warm-cache reconstitution path (BASELINE config 4: 10GB
    layers) must not materialize the layer: peak Python heap growth
    while rebuilding a 64MiB layer stays bounded by chunk size, not
    layer size (matching index_layer's streaming discipline)."""
    import hashlib
    import io
    import os
    import tracemalloc

    import numpy as np

    from makisu_tpu import tario
    from makisu_tpu.cache.chunks import ChunkStore
    from makisu_tpu.docker.image import (
        MEDIA_TYPE_LAYER,
        Descriptor,
        Digest,
        DigestPair,
    )

    total = 64 * 1024 * 1024
    chunk_len = 256 * 1024
    payload = np.random.default_rng(7).integers(
        0, 256, size=total, dtype=np.uint8).tobytes()
    backend = "zlib-1"
    buf = io.BytesIO()
    with tario.gzip_writer(buf, backend_id=backend) as gz:
        gz.write(payload)
    blob = buf.getvalue()
    pair = DigestPair(
        tar_digest=Digest.of_bytes(payload),
        gzip_descriptor=Descriptor(MEDIA_TYPE_LAYER, len(blob),
                                   Digest.of_bytes(blob)))
    store = ChunkStore(str(tmp_path / "chunks"))
    triples = []
    for off in range(0, total, chunk_len):
        piece = payload[off:off + chunk_len]
        hex_digest = hashlib.sha256(piece).hexdigest()
        store.put(hex_digest, piece)
        triples.append((off, len(piece), hex_digest))
    del payload, buf

    tracemalloc.start()
    tracemalloc.reset_peak()
    path = store.reconstitute_to_path(pair, triples, gz_backend=backend)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert path is not None
    try:
        with open(path, "rb") as f:
            assert f.read() == blob
    finally:
        os.unlink(path)
    # 16MiB headroom for a 64MiB layer: fails loudly if anyone
    # reintroduces whole-layer buffering.
    assert peak < 16 * 1024 * 1024, f"peak heap {peak} bytes"


def test_strict_registry_degrades_chunk_dedup_not_builds(tmp_path):
    """A policy-enforcing registry that rejects the chunk-pin manifest's
    custom media type (MANIFEST_INVALID) must cost only the distributed
    chunk dedup — never the build. After GC evaporates the unpinned
    chunks, a fresh builder falls back to building from context and
    produces the identical image."""
    import numpy as np

    from makisu_tpu.registry import RegistryClient, RegistryFixture
    from makisu_tpu.storage import ImageStore as IS

    payload = np.random.default_rng(21).integers(
        0, 256, size=150_000, dtype=np.uint8).tobytes()
    kv = MemoryStore()
    fixture = RegistryFixture(strict_media_types=True)
    ctx_dir = tmp_path / "ctx"
    ctx_dir.mkdir()
    (ctx_dir / "blob.bin").write_bytes(payload)

    def one_builder(tag, store_name, chunk_name):
        root = tmp_path / f"root-{tag}"
        root.mkdir(exist_ok=True)
        store = IS(str(tmp_path / store_name))
        client = RegistryClient(store, "registry.test", "cache/strict",
                                transport=fixture)
        ctx = BuildContext(str(root), str(ctx_dir), store,
                           hasher=TPUHasher(), sync_wait=0.0)
        mgr = CacheManager(kv, store, registry_client=client)
        attach_chunk_dedup(mgr, str(tmp_path / chunk_name))
        stages = parse_file("FROM scratch\nCOPY blob.bin /blob.bin\n")
        plan = BuildPlan(ctx, ImageName("", "t/strict", tag), [], mgr,
                         stages, allow_modify_fs=False, force_commit=True)
        manifest = plan.execute()
        mgr.wait_for_push()
        return manifest, store

    m1, _ = one_builder("a", "store-a", "chunks-a")
    layer_hex = m1.layers[0].digest.hex()
    # The pin was REJECTED: no pin manifest landed.
    pin_tag = f"cache/strict:makisu-chunks-{layer_hex[:40]}"
    assert pin_tag not in fixture.manifests
    # GC therefore deletes chunks and layer alike — dedup fully degraded.
    fixture.gc()
    assert not fixture.blobs
    # A fresh builder still succeeds (rebuild from context) and produces
    # the byte-identical image.
    m2, store_b = one_builder("b", "store-b", "chunks-b")
    assert [str(l.digest) for l in m1.layers] == \
        [str(l.digest) for l in m2.layers]
    assert store_b.layers.exists(layer_hex)


def _degrade_build(tmp_path, tag, root_name, storage_name, payload):
    ctx_dir = tmp_path / f"ctx-{tag}"
    ctx_dir.mkdir()
    (ctx_dir / "blob.bin").write_bytes(payload)
    root = tmp_path / root_name
    root.mkdir()
    store = ImageStore(str(tmp_path / storage_name))
    kv = MemoryStore()
    ctx = BuildContext(str(root), str(ctx_dir), store,
                       hasher=TPUHasher(), sync_wait=0.0)
    mgr = CacheManager(kv, store)
    stages = parse_file("FROM scratch\nCOPY blob.bin /blob.bin\n")
    plan = BuildPlan(ctx, ImageName("", "t/degrade", tag), [], mgr,
                     stages, allow_modify_fs=False, force_commit=True)
    manifest = plan.execute()
    mgr.wait_for_push()
    return manifest, kv


def _assert_no_chunks(kv):
    entries = [v for v in kv._data.values() if "sha256" in v]
    assert entries
    for v in entries:
        assert not json.loads(v).get("chunks")


def test_device_failure_degrades_chunking_not_build(tmp_path, monkeypatch):
    """A device failure MID-STREAM (device lost, OOM) fails the build
    with its reason. Under MAKISU_TPU_CHUNK_STRICT=0 it costs only
    chunk dedup instead: the layer commits with an empty chunk list,
    the cache entry has no chunks, and the BUILD succeeds. The payload
    exceeds the 4MiB dispatch block so the failure fires from update(),
    the advertised mid-stream case."""
    # Device-failure simulation: pin the XLA route (the native
    # CPU route never touches the device and cannot fail this way).
    monkeypatch.setenv("MAKISU_TPU_CHUNK_NATIVE", "0")
    from makisu_tpu.chunker.cdc import BLOCK
    from makisu_tpu.ops import gear

    def boom(*a, **k):
        raise RuntimeError("XLA device lost (simulated)")

    payload = b"payload " * (BLOCK // 8 + 50_000)  # > one dispatch block
    monkeypatch.setattr(gear, "gear_bitmap", boom)
    # The default, option unset: the simulated device loss fails the
    # build (surfacing either directly or wrapped by the native sink's
    # tap).
    monkeypatch.delenv("MAKISU_TPU_CHUNK_STRICT", raising=False)
    with pytest.raises(RuntimeError, match="device lost|chunk tap failed"):
        _degrade_build(tmp_path, "strict", "root-s", "store-s", payload)

    # The operator asked for it: build succeeds, no chunks recorded.
    monkeypatch.setenv("MAKISU_TPU_CHUNK_STRICT", "0")
    manifest, kv = _degrade_build(tmp_path, "degraded", "root-d",
                                  "store-d", payload)
    assert manifest.layers  # the image really was built
    _assert_no_chunks(kv)


def test_device_failure_in_lane_hashing_degrades(tmp_path, monkeypatch):
    """Same discipline when the GEAR scan works but the SHA-256 lane
    hashing dies (the 'lane hashing' drain stage)."""
    # Device-failure simulation: pin the XLA route (the native
    # CPU route never touches the device and cannot fail this way).
    monkeypatch.setenv("MAKISU_TPU_CHUNK_NATIVE", "0")
    from makisu_tpu.ops import sha256 as sha_mod

    def boom(*a, **k):
        raise RuntimeError("XLA device lost during lane hashing")

    monkeypatch.setattr(sha_mod, "sha256_lanes", boom)
    monkeypatch.setenv("MAKISU_TPU_CHUNK_STRICT", "0")
    manifest, kv = _degrade_build(tmp_path, "lanes", "root-l", "store-l",
                                  b"payload " * 30_000)
    assert manifest.layers
    _assert_no_chunks(kv)


def test_degraded_session_ignores_further_updates(monkeypatch):
    """After degrading, update() is a no-op (no re-dispatch, no staging
    growth) and finish() returns []."""
    # Device-failure simulation: pin the XLA route (the native
    # CPU route never touches the device and cannot fail this way).
    monkeypatch.setenv("MAKISU_TPU_CHUNK_NATIVE", "0")
    from makisu_tpu.chunker.cdc import ChunkSession
    from makisu_tpu.ops import gear

    calls = []

    def boom(*a, **k):
        calls.append(1)
        raise RuntimeError("device lost")

    monkeypatch.setattr(gear, "gear_bitmap", boom)
    monkeypatch.setenv("MAKISU_TPU_CHUNK_STRICT", "0")
    session = ChunkSession(block=1024)
    session.update(b"x" * 4096)
    assert session._degraded is not None
    assert len(calls) == 1
    session.update(b"y" * 8192)  # ignored, not re-dispatched
    assert len(calls) == 1
    assert not session._staging
    assert session.finish() == []


# -- bulk ingest through index_layer (PR 25) ---------------------------------

def _chunk_list(pieces: list[bytes]) -> list[tuple[int, int, str]]:
    """The (offset, length, sha256) list of ``pieces`` end to end."""
    import hashlib
    chunks, pos = [], 0
    for piece in pieces:
        chunks.append((pos, len(piece), hashlib.sha256(piece).hexdigest()))
        pos += len(piece)
    return chunks


def _layer(tmp_path, pieces: list[bytes], name="layer.gz"):
    """A gzip blob whose stream is ``pieces`` end to end, with its
    chunk list."""
    import gzip
    path = tmp_path / name
    path.write_bytes(gzip.compress(b"".join(pieces), mtime=0))
    return str(path), _chunk_list(pieces)


def _pieces(n: int, seed: int = 0) -> list[bytes]:
    import random
    rng = random.Random(seed)
    return [rng.randbytes(rng.randrange(2_000, 66_000)) for _ in range(n)]


def _batches(chunks) -> int:
    """Batches ``index_layer`` cuts a layer of new chunks into."""
    n = held = 0
    for _, length, _ in chunks:
        held += length
        if held >= ChunkStore.INGEST_BATCH_BYTES:
            n, held = n + 1, 0
    return n + bool(held)


@pytest.mark.parametrize("writers", [1, ChunkStore.INGEST_WRITERS])
def test_index_layer_six_calls_a_batch_none_under_lock(
        tmp_path, fs_calls, monkeypatch, writers):
    """A layer of N new chunks into a fresh store, per batch of the
    window and not per chunk: the segment and its index opened, a write
    each, a close each; no stat (a fresh store answers every probe from
    memory), no rename, no shard; a segment pair created where no free
    one was (one with one writer); every call made with the store's
    lock free."""
    from makisu_tpu.utils import metrics
    monkeypatch.setattr(ChunkStore, "INGEST_WRITERS", writers)
    monkeypatch.setattr(ChunkStore, "INGEST_BATCH_BYTES", 4 * 65536)
    path, chunks = _layer(tmp_path, _pieces(90))
    batches = _batches(chunks)
    assert batches >= 8
    store = ChunkStore(str(tmp_path / "chunks"))
    rec = fs_calls(store.cas)
    registry = metrics.MetricsRegistry()
    token = metrics.set_build_registry(registry)
    stats = {}
    try:
        added = store.index_layer(path, chunks, stats)
    finally:
        metrics.reset_build_registry(token)
    assert added == [h for _, _, h in chunks]
    created = registry.counter_by_label(
        metrics.CHUNK_STORE_FILES_CREATED, "kind")
    assert created["segment"] == created["index"] and created["loose"] == 0
    assert 1 <= created["segment"] <= writers
    # A writer that finds no _seg/ makes it and creates again.
    assert 1 <= rec.calls["makedirs"] <= writers
    assert rec.calls.pop("open") == 2 * batches + rec.calls.pop("makedirs")
    assert dict(rec.calls) == {"write": 2 * batches, "close": 2 * batches}
    assert rec.under_lock == []
    assert 1 <= stats["ingest_window"] <= writers
    assert len(_cas_tree_files(store.cas.root)) == 2 * created["segment"]
    # The same layer again: a lookup a chunk, no call, nothing created.
    rec = fs_calls(store.cas)
    token = metrics.set_build_registry(registry)
    try:
        assert store.index_layer(path, chunks) == []
    finally:
        metrics.reset_build_registry(token)
    assert dict(rec.calls) == {} and rec.under_lock == []
    assert registry.counter_by_label(
        metrics.CHUNK_STORE_FILES_CREATED, "kind") == created


def _cas_tree_files(root: str) -> list[str]:
    return [os.path.join(parent, fn)
            for parent, _, files in os.walk(root) for fn in files]


def test_index_layer_trusts_a_streamed_miss(tmp_path, fs_calls):
    """Digests the commit's streamed probe already looked for cost no
    second look: one append for the new chunks (six calls, into the
    segment the process has), none for a stored one."""
    import time
    path, chunks = _layer(tmp_path, _pieces(12, seed=3))
    store = ChunkStore(str(tmp_path / "chunks"))
    store.PROBE_BATCH = 4
    store.index_layer(*_layer(tmp_path, _pieces(12, seed=3)[:4], "old.gz"))
    for _, _, h in chunks:
        store.note_fingerprint(h)
    deadline = time.monotonic() + 10  # the probes ride the commit pool
    while (any(store._probed(h) is None for _, _, h in chunks)
           and time.monotonic() < deadline):
        time.sleep(0.01)
    assert [store._probed(h) for _, _, h in chunks] == [True] * 4 + [False] * 8
    rec = fs_calls(store.cas)
    assert store.index_layer(path, chunks) == [h for _, _, h in chunks[4:]]
    assert rec.calls["isfile"] == 0
    assert dict(rec.calls) == {"open": 2, "write": 2, "close": 2}


def test_index_layer_writes_a_repeated_digest_once(tmp_path, fs_calls):
    from makisu_tpu.utils import metrics
    a, b, c = _pieces(3, seed=1)
    path, chunks = _layer(tmp_path, [a, b, a, c, b, a])
    store = ChunkStore(str(tmp_path / "chunks"))
    rec = fs_calls(store.cas)
    registry = metrics.MetricsRegistry()
    token = metrics.set_build_registry(registry)
    try:
        added = store.index_layer(path, chunks)
    finally:
        metrics.reset_build_registry(token)
    assert added == [chunks[0][2], chunks[1][2], chunks[3][2]]
    assert rec.calls["rename"] == rec.calls["isfile"] == 0
    assert rec.calls["write"] == 2               # one batch: three entries
    assert registry.counter_by_label(metrics.CHUNK_INGEST, "result") == {
        "written": 3.0, "raced": 3.0}
    assert store.get(chunks[0][2]) == a


@pytest.mark.parametrize("failing", ["open", "payload", "records"])
def test_index_layer_write_failure_raises_and_leaves_no_partial_chunk(
        tmp_path, fs_calls, store_tree, monkeypatch, failing):
    """The open of a segment, the write of a batch's payloads or the
    write of its records fails: the pass raises, what is stored is what
    whole batches stored, and every name reads the bytes it hashes."""
    import errno
    import hashlib
    from makisu_tpu.storage import cas as cas_mod
    monkeypatch.setattr(ChunkStore, "INGEST_BATCH_BYTES", 2 * 65536)
    path, chunks = _layer(tmp_path, _pieces(60, seed=2))
    store = ChunkStore(str(tmp_path / "chunks"))
    rec = fs_calls(store.cas)
    name, k = {"open": ("open", 9), "payload": ("write", 7),
               "records": ("write", 8)}[failing]
    rec.fail_at[name] = (k, OSError(errno.ENOSPC, "no space"))
    with pytest.raises(OSError) as err:
        store.index_layer(path, chunks)
    assert err.value.errno == errno.ENOSPC
    assert store_tree(store.cas.root)["_tmp/"] == (0, b"")
    bare = cas_mod.CASDir(store.cas.root)
    stored = bare.keys()
    assert 0 < len(stored) < len(chunks)
    for h in stored:
        assert hashlib.sha256(bare.read(h)).hexdigest() == h
    # What the failed call left is put right by the next one.
    rec.fail_at.clear()
    store.index_layer(path, chunks)
    assert sorted(cas_mod.CASDir(store.cas.root).keys()) == sorted(
        {h for _, _, h in chunks})
    assert store.coverage(chunks) == 1.0


def test_index_layer_content_mismatch_stores_nothing_wrong(tmp_path):
    pieces = _pieces(6, seed=4)
    path, chunks = _layer(tmp_path, pieces)
    chunks[3] = (chunks[3][0], chunks[3][1], "ab" * 32)
    store = ChunkStore(str(tmp_path / "chunks"))
    with pytest.raises(ValueError):
        store.index_layer(path, chunks)
    assert not store.cas.exists("ab" * 32)


def test_index_layer_holds_the_entry_cap_and_pins(tmp_path):
    store = ChunkStore(str(tmp_path / "chunks"), max_entries=8)
    first = _layer(tmp_path, _pieces(4, seed=5), "first.gz")
    pinned = first[1][0][2]
    store.index_layer(*first)
    for i, (_, _, h) in enumerate(first[1]):
        store.cas._last_access[h] = float(i)     # the pinned one oldest
    with store.pins.pinned("chunks", pinned):
        added = store.index_layer(*_layer(tmp_path, _pieces(12, seed=6)))
    assert len(added) == 12
    keys = set(store.cas.keys())
    assert len(keys) == 8 and pinned in keys
    assert not keys & {h for _, _, h in first[1][1:]}


def _held_by(root: str) -> dict[str, bytes]:
    """What a CAS directory holds, asked of the owner of its layout:
    name -> bytes for every entry a bare handle walks."""
    from makisu_tpu.storage import cas as cas_mod
    bare = cas_mod.CASDir(root)
    return {name: bare.read(name) for name, _, _ in bare.walk()}


def test_index_layer_store_equals_the_one_file_a_chunk_layout(
        tmp_path, store_tree):
    """The names and bytes the parent's put-by-put path left for the
    same layer, built here with hashlib and read through the layout's
    owner; on disk, segments and an empty ``_tmp/``, no file a chunk,
    and the bytes of the chunks plus 56 a record."""
    import hashlib
    pieces = _pieces(70, seed=7)
    pieces += pieces[:5]                           # repeats change nothing
    path, chunks = _layer(tmp_path, pieces)
    store = ChunkStore(str(tmp_path / "chunks"))
    store.index_layer(path, chunks)
    golden = {hashlib.sha256(piece).hexdigest(): piece for piece in pieces}
    assert _held_by(store.cas.root) == golden
    tree = store_tree(store.cas.root)
    assert tree.pop("_tmp/") == (0, b"")
    assert 2 <= len(tree) <= 2 * ChunkStore.INGEST_WRITERS
    assert sum(len(data) for _, data in tree.values()) == sum(
        len(piece) + 56 for piece in golden.values())


# -- index_layer's one block pass (PR 31) ------------------------------------

def _blob(tmp_path, backend: str, payload: bytes, name="layer.gz") -> str:
    """``payload`` as the commit path's gzip writers write it."""
    from makisu_tpu import tario
    path = tmp_path / name
    with open(path, "wb") as f:
        w = tario.gzip_writer(f, backend_id=tario.make_backend_id(
            backend, "default"))
        w.write(payload)
        w.close()
    return str(path)


def _parents_loop(chunks, memo, stored):
    """What the parent's chunk-at-a-time loop gives for a chunk list, a
    memo and the digests the store holds: (added, prefetch tally,
    ingest tally, stats made)."""
    import collections
    prefetch, handed, added, stats = collections.Counter(), [], [], 0
    for _, _, h in chunks:
        known = memo.get(h)
        if known:
            prefetch["hit"] += 1
        elif h in handed:
            prefetch["raced"] += 1
        else:
            handed.append(h)
            prefetch["probe" if known is None else "miss"] += 1
            stats += known is None
            if not (known is None and h in stored):
                added.append(h)
    ingest = {"written": len(added),
              "present": len(handed) - len(added) + prefetch["hit"],
              "raced": prefetch.pop("raced", 0)}
    return (added, {k: float(v) for k, v in prefetch.items() if v},
            {k: float(v) for k, v in ingest.items() if v}, stats)


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks smaller than the largest chunk, so spans straddle two and
    three of them and whole blocks are stepped over."""
    from makisu_tpu.cache import chunks as chunks_mod
    monkeypatch.setattr(chunks_mod._Inflated, "READ", 9_000)
    monkeypatch.setattr(chunks_mod._Inflated, "BLOCK", 30_000)


_MEMOS = {
    # name -> (share of the layer's chunks the store holds beforehand,
    #          what the memo says of chunk i of n, given it is stored)
    "all_true": (1.0, lambda i, held: True),
    "all_false": (0.0, lambda i, held: False),
    "empty": (0.5, lambda i, held: None),
    "mixed": (0.5, lambda i, held: (held, None, held)[i % 3]),
    "repeated": (0.3, lambda i, held: (None, held)[i % 2]),
}


@pytest.mark.parametrize("backend", ["zlib", "pgzip"])
@pytest.mark.parametrize("memo_kind", sorted(_MEMOS))
def test_index_layer_one_pass_equals_the_parents_loop(
        tmp_path, fs_calls, store_tree, small_blocks, memo_kind, backend):
    import hashlib
    from makisu_tpu.utils import metrics
    pieces = _pieces(48, seed=11)
    if memo_kind == "repeated":
        pieces = pieces[:30] + pieces[5:12] + pieces[30:] + pieces[:3]
    held_share, says = _MEMOS[memo_kind]
    chunks = _chunk_list(pieces)
    path = _blob(tmp_path, backend, b"".join(pieces))
    store = ChunkStore(str(tmp_path / "chunks"))
    distinct = list(dict.fromkeys(h for _, _, h in chunks))
    stored = set(distinct[:int(len(distinct) * held_share)])
    by_digest = {hashlib.sha256(p).hexdigest(): p for p in pieces}
    for h in stored:
        store.cas.put(h, by_digest[h])
    memo = {h: says(i, h in stored) for i, h in enumerate(distinct)}
    memo = {h: said for h, said in memo.items() if said is not None}
    with store._memo_lock:
        store._exists_memo.update(memo)
    want_added, want_prefetch, want_ingest, want_stats = _parents_loop(
        chunks, memo, stored)

    rec = fs_calls(store.cas)
    registry = metrics.MetricsRegistry()
    token = metrics.set_build_registry(registry)
    stats = {}
    try:
        added = store.index_layer(path, chunks, stats)
    finally:
        metrics.reset_build_registry(token)
    assert added == want_added
    assert registry.counter_by_label(
        "makisu_chunk_exists_prefetch_total", "result") == want_prefetch
    assert registry.counter_by_label(
        metrics.CHUNK_INGEST, "result") == want_ingest
    assert registry.counter_by_label(
        "makisu_chunks_indexed_total", "result") == {}
    # The parent's loop paid a stat where nobody had looked; a store
    # whose entries are all in segments answers those from memory.
    assert want_stats >= 0 and rec.calls["isfile"] == 0
    assert rec.calls["rename"] == 0
    assert bool(rec.calls["write"]) == bool(want_added)
    if memo_kind == "all_true":
        assert stats["ingest_window"] == 0 and rec.total() == 0
    else:
        assert stats["ingest_window"] >= 1
    assert _held_by(store.cas.root) == {
        h: by_digest[h] for h in stored | set(want_added)}


def _spoil(path: str, how: str) -> None:
    with open(path, "rb") as f:
        blob = bytearray(f.read())
    if how == "truncated":
        del blob[-20:]              # the trailer and the stream's end
    elif how == "crc":
        blob[-8] ^= 0xFF            # CRC32's first byte
    elif how == "isize":
        blob[-1] ^= 0x01            # ISIZE's last byte
    elif how == "midstream":
        blob[len(blob) // 2] ^= 0x55
    elif how == "garbage_after":
        blob += b"not a gzip member"
    with open(path, "wb") as f:
        f.write(blob)


@pytest.mark.parametrize("backend", ["zlib", "pgzip"])
@pytest.mark.parametrize("memo_all_true", [True, False])
@pytest.mark.parametrize("how", ["truncated", "crc", "isize", "midstream",
                                 "garbage_after", "list_past_end"])
def test_index_layer_raises_on_a_blob_that_is_not_whole(
        tmp_path, small_blocks, how, memo_all_true, backend):
    """Every byte is inflated and the trailer verified whether or not
    anybody wanted the bytes: with every chunk found stored (no slice
    taken) as with none."""
    import zlib
    pieces = _pieces(24, seed=12)
    chunks = _chunk_list(pieces)
    path = _blob(tmp_path, backend, b"".join(pieces))
    store = ChunkStore(str(tmp_path / "chunks"))
    store.index_layer(path, chunks)            # the whole blob is fine
    if how == "list_past_end":
        end = chunks[-1][0] + chunks[-1][1]
        chunks.append((end, 4_000, chunks[0][2]))
    else:
        _spoil(path, how)
    if memo_all_true:
        with store._memo_lock:
            store._exists_memo.update({h: True for _, _, h in chunks})
    with pytest.raises((ValueError, EOFError, zlib.error, OSError)):
        store.index_layer(path, chunks)


@pytest.mark.parametrize("tail", ["zeros", "member"])
def test_inflated_reads_what_follows_a_member_as_gzipfile_does(
        tmp_path, small_blocks, tail):
    import gzip
    import io
    from makisu_tpu.cache.chunks import _Inflated
    first, second = (b"".join(_pieces(20, seed=s)) for s in (13, 14))
    blob = gzip.compress(first, mtime=0) + {
        "zeros": b"\0" * 20_000,
        "member": b"\0" * 512 + gzip.compress(second, mtime=0)}[tail]
    want = gzip.GzipFile(fileobj=io.BytesIO(blob)).read()
    assert want == first + (second if tail == "member" else b"")
    stream = _Inflated(io.BytesIO(blob))
    assert stream.take(1_000, 35_000) == want[1_000:36_000]
    assert stream.take(len(want) - 10, 10) == want[-10:]
    assert stream.finish() == len(want)
    with pytest.raises(ValueError):
        _Inflated(io.BytesIO(blob)).take(len(want) - 5, 10)


def test_note_fingerprint_from_many_threads_claims_each_digest_once(
        tmp_path):
    """The device routes notify from the hash service's threads as well
    as from pool workers: 32 notifiers, every digest noted by two of
    them, a switch interval that interleaves them. Each digest is
    claimed once and answered rightly; only a tail under PROBE_BATCH
    stays unanswered."""
    import hashlib
    import sys
    import threading
    import time
    from makisu_tpu.utils import concurrency
    store = ChunkStore(str(tmp_path / "chunks"))
    store.PROBE_BATCH = 16
    digests = [hashlib.sha256(b"%d" % i).hexdigest() for i in range(3000)]
    for i, h in enumerate(digests[::3]):
        store.cas.put(h, b"%d" % (3 * i))
    submitted = []
    pool = concurrency.hash_pool()
    real_submit = pool.submit

    def counting_submit(fn, *a, **k):
        submitted.append(fn)
        return real_submit(fn, *a, **k)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    pool.submit = counting_submit
    try:
        threads = [threading.Thread(
            target=lambda k=k: [store.note_fingerprint(h)
                                for h in digests[k % 16::16]])
            for k in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
    finally:
        del pool.submit
        sys.setswitchinterval(interval)
    assert len(submitted) == len(digests) // 16      # each claimed once
    deadline = time.monotonic() + 30
    while (sum(store._probed(h) is None for h in digests) >= 16
           and time.monotonic() < deadline):
        time.sleep(0.01)
    answers = [store._probed(h) for h in digests]
    assert sum(a is None for a in answers) == len(digests) % 16
    assert all(a is None or a == (i % 3 == 0)
               for i, a in enumerate(answers))


# -- the recipe publisher fed from index_layer's pass (PR 48) ----------------

@pytest.fixture
def publishing(monkeypatch):
    """Publishing on, as in a worker; packs of 1 MB (the least a pack
    may be) so that a layer of a few MB fills several; the process's
    serve stores dropped before and after."""
    from makisu_tpu.serve import server as serve_server
    monkeypatch.setenv("MAKISU_TPU_SERVE", "1")
    monkeypatch.setenv("MAKISU_TPU_PACK_TARGET_MB", "1")
    serve_server.reset_stores()
    yield serve_server
    serve_server.reset_stores()


def _fed_manager(storage: str):
    """A cache manager with chunk dedup attached over ``storage``, its
    chunk store at ``<storage>/chunks`` as a build's is."""
    mgr = CacheManager(MemoryStore(), ImageStore(storage))
    attach_chunk_dedup(mgr, os.path.join(storage, "chunks"))
    return mgr


def _recipe_store(storage: str):
    from makisu_tpu.serve import server as serve_server
    return serve_server.register_store(storage)


def _serve_tree(store_tree, storage: str) -> dict:
    return {rel: data for rel, (_, data) in
            store_tree(os.path.join(storage, "serve")).items()}


def _publish_threads() -> list:
    import threading
    return [t for t in threading.enumerate()
            if t.name.startswith("recipepub-")]


_PUBLISH_STATES = {
    # name -> (pieces of the layer, given the 48 drawn;
    #          which of its distinct chunks the store holds beforehand;
    #          which of those the streamed probe found (memo True);
    #          which an earlier layer's pack holds already)
    "all_new": (lambda p: p, lambda i: False, lambda i: False,
                lambda i: False),
    "stored_unpacked": (lambda p: p, lambda i: True, lambda i: True,
                        lambda i: False),
    "mixed": (lambda p: p, lambda i: i % 3 != 0, lambda i: i % 2 == 0,
              lambda i: False),
    "repeated": (lambda p: p[:30] + p[5:12] + p[30:] + p[:3],
                 lambda i: i % 5 == 0, lambda i: True, lambda i: False),
    "in_another_pack": (lambda p: p, lambda i: i % 2 == 0,
                        lambda i: i % 4 == 0, lambda i: i % 3 == 0),
    "over_three_packs": (lambda p: p + _pieces(90, seed=22),
                         lambda i: i % 7 == 0, lambda i: True,
                         lambda i: False),
}


@pytest.mark.parametrize("backend", ["zlib", "pgzip"])
@pytest.mark.parametrize("state", sorted(_PUBLISH_STATES))
def test_publish_fed_from_the_pass_equals_the_read_back(
        tmp_path, store_tree, small_blocks, publishing, state, backend):
    """For one layer and one state of the stores, the route a build
    takes (push_cache: the publication opened before index_layer, fed
    from its pass, finished on the recipepub thread) and the read-back
    route (index_layer, then RecipeStore.publish reading every novel
    chunk from the store) leave the same serve/ tree byte for byte:
    zpacks, pack tables, recipe; and the same chunk store."""
    import hashlib
    from makisu_tpu import tario
    from makisu_tpu.serve import recipe as recipe_mod
    shape, held, probed, packed = _PUBLISH_STATES[state]
    pieces = shape(_pieces(48, seed=21))
    chunks = _chunk_list(pieces)
    backend_id = tario.make_backend_id(backend, "default")
    blob = _blob(tmp_path, backend, b"".join(pieces))
    by_digest = {hashlib.sha256(p).hexdigest(): p for p in pieces}
    distinct = list(by_digest)
    earlier = [h for i, h in enumerate(distinct) if packed(i)]
    trees = {}
    for route in ("fed", "read_back"):
        storage = str(tmp_path / route)
        mgr = _fed_manager(storage)
        store = mgr.chunk_store
        rs = _recipe_store(storage)
        if earlier:
            # An earlier layer, published whole, holds these in its pack.
            for h in earlier:
                store.put(h, by_digest[h])
            other = _chunk_list([by_digest[h] for h in earlier])
            other_blob = _blob(tmp_path, backend, b"".join(
                by_digest[h] for h in earlier), f"other-{route}.gz")
            other_pair, _ = committed_layer(storage, other_blob, other)
            assert rs.publish(other_pair, other, backend_id, store)
        for i, h in enumerate(distinct):
            if held(i) and not packed(i):
                store.put(h, by_digest[h])
        with store._memo_lock:
            store._exists_memo.update({
                h: True for i, h in enumerate(distinct)
                if (held(i) or packed(i)) and probed(i)})
        pair, commit = committed_layer(storage, blob, chunks, backend_id)
        if route == "fed":
            mgr.push_cache("cache-id", pair, commit)
            mgr.wait_for_push()
        else:
            store.index_layer(blob, chunks)
            assert rs.publish(pair, chunks, backend_id, store)
        doc = rs.recipe(pair.gzip_descriptor.digest.hex())
        assert recipe_mod.verify(doc, key=b"")
        assert [row[0] for row in doc["chunks"]] == [
            h for _, _, h in chunks]
        trees[route] = (_serve_tree(store_tree, storage),
                        _held_by(store.cas.root))
    assert trees["fed"] == trees["read_back"]
    packs = [rel for rel in trees["fed"][0] if rel.startswith("zpacks/")]
    assert len(packs) > (3 if state == "over_three_packs" else 1)
    assert not _publish_threads()


@pytest.mark.parametrize("state", ["cold", "stored_unpacked"])
def test_publish_reads_no_chunk_file_the_pass_sliced(
        tmp_path, fs_calls, publishing, state):
    """A cold layer's publish opens no file of the chunk store for
    reading: its bytes reach the packs from the pass. A chunk the
    probe found stored, in no pack yet, is opened once."""
    from makisu_tpu.utils import metrics
    pieces = _pieces(60, seed=23)
    blob, chunks = _layer(tmp_path, pieces)
    storage = str(tmp_path / "storage")
    mgr = _fed_manager(storage)
    store = mgr.chunk_store
    stored = chunks[::4] if state == "stored_unpacked" else []
    for (_, _, h), piece in zip(chunks[::4], pieces[::4]):
        if stored:
            store.put(h, piece)
    with store._memo_lock:
        store._exists_memo.update({h: True for _, _, h in stored})
    pair, commit = committed_layer(storage, blob, chunks)
    rec = fs_calls(store.cas)
    registry = metrics.MetricsRegistry()
    token = metrics.set_build_registry(registry)
    try:
        mgr.push_cache("cache-id", pair, commit)
        mgr.wait_for_push()
    finally:
        metrics.reset_build_registry(token)
    # A stored chunk is a segment entry: its read is one pread.
    assert rec.calls["open_read"] == 0
    assert rec.calls["pread"] == len(stored)
    assert rec.calls["rename"] == 0 and rec.calls["write"] >= 2
    from_store = float(sum(n for _, n, _ in stored))
    want = {"pass": float(sum(n for _, n, _ in chunks)) - from_store}
    if stored:
        want["store"] = from_store
    assert registry.counter_by_label(
        metrics.SERVE_PACK_SOURCE_BYTES, "source") == want
    (span,) = [s for s in registry.report()["spans"]
               if s["name"] == "recipe_publish"]
    assert span["attrs"]["novel"] == str(len(chunks))
    assert span["attrs"]["fed"] == str(len(chunks) - len(stored))
    doc = _recipe_store(storage).recipe(
        pair.gzip_descriptor.digest.hex())
    assert len(doc["chunks"]) == len(chunks)


def test_publishing_off_index_layer_has_no_observer(
        tmp_path, monkeypatch):
    """A one-shot build (MAKISU_TPU_SERVE=0): no publication, no
    thread, no serve/ directory."""
    monkeypatch.setenv("MAKISU_TPU_SERVE", "0")
    blob, chunks = _layer(tmp_path, _pieces(12, seed=24))
    storage = str(tmp_path / "storage")
    mgr = _fed_manager(storage)
    seen = []
    real = mgr.chunk_store.index_layer
    monkeypatch.setattr(
        mgr.chunk_store, "index_layer",
        lambda *a, **k: seen.append(k.get("observer")) or real(*a, **k))
    pair, commit = committed_layer(storage, blob, chunks)
    mgr.push_cache("cache-id", pair, commit)
    assert seen == [None]
    assert [t.name for t in mgr._pushes] == ["cachepush-cache-id"]
    mgr.wait_for_push()
    assert not os.path.exists(os.path.join(storage, "serve"))


@pytest.mark.parametrize("failing", [
    "truncated", "crc", "writer_oserror", "lying_digest_early",
    "lying_digest_late"])
def test_publish_is_abandoned_where_the_pass_fails(
        tmp_path, fs_calls, store_tree, publishing, failing):
    """index_layer raising mid-stream (a blob that is not whole, a
    writer's OSError, a slice that does not hash to its fingerprint:
    refused where it is made, before a pack takes it) fails the push,
    leaves no recipe and no pack table, and the recipepub thread ends.
    Packs filled before the failure may have left a .zst, which no
    table names."""
    import errno
    import zlib
    pieces = _pieces(100, seed=25)           # ~3.4 MB: three packs
    blob, chunks = _layer(tmp_path, pieces)
    storage = str(tmp_path / "storage")
    mgr = _fed_manager(storage)
    rec = fs_calls(mgr.chunk_store.cas)
    if failing in ("truncated", "crc"):
        _spoil(blob, failing)
    elif failing == "writer_oserror":
        rec.fail_at["write"] = (6, OSError(errno.ENOSPC, "no space"))
    else:
        at = 2 if failing == "lying_digest_early" else 90
        chunks[at] = (chunks[at][0], chunks[at][1], "ab" * 32)
    pair, commit = committed_layer(storage, blob, chunks)
    with pytest.raises((ValueError, EOFError, zlib.error, OSError)):
        mgr.push_cache("cache-id", pair, commit)
    # A pack was handed over before the late failures: the thread is
    # among the pushes the build joins, and it ends.
    assert ("recipepub-cache-id" in [t.name for t in mgr._pushes]) == (
        failing != "lying_digest_early")
    mgr.wait_for_push()
    assert not _publish_threads()
    serve = _serve_tree(store_tree, storage)
    assert not [rel for rel in serve if not rel.startswith("zpacks/")]
    if failing == "lying_digest_early":
        assert not serve                     # nothing entered a pack
    rs = _recipe_store(storage)
    assert rs.recipe(pair.gzip_descriptor.digest.hex()) is None
    assert rs.stats()["packs"] == 0
    assert not mgr.chunk_store.cas.exists("ab" * 32)
