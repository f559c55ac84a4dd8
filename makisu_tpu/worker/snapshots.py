"""Chunk-addressed session snapshots: durable, portable warm state.

A resident build session (worker/session.py) is the repo's biggest
perf asset — and it dies with the process. This module serializes a
session's memos into **shards** stored through the existing chunk CAS
(cache/chunks.py), indexed by a small JSON **recipe** under
``<storage>/serve/snapshots/<key>.json``:

- ``scan``: the context-scan memo — (source, checksum_in) →
  (checksum_out, files, bytes) transitions;
- ``stat/<n>``: the stat/content-ID cache entries for this context,
  bucketed by rel-path hash so one touched file re-chunks one bucket,
  not 100k entries;
- ``walk/<n>``: the mtime-walk baseline's stat signatures, bucketed
  the same way — the certification point a restored session deltas
  against, so the snapshot→restore gap is covered at exactly the trust
  level the live mtime-walk fallback already has;
- ``layer/<key>``: one shard per MemFS layer-replay memo entry, keyed
  by (applied-chain, digest) — content-addressed, so identical layers
  dedupe across sessions and workers for free.

Shard docs serialize deterministically (sorted keys), so an unchanged
shard hashes to the chunk it already has: ``finish_build`` checkpoints
in O(changed shards), and an idle session checkpoints for the cost of
a few ``exists`` stats. The recipe carries the full invalidation
story — portable flag identity, ISA route, capture time — and
:func:`try_restore` enforces it (``flag_identity`` / ``isa_change`` /
``stale``) before any shard byte is trusted, so a restored session's
digests stay byte-identical to a cold build.

Restored stat-cache entries keep their original ``hashed_at``
timestamps: the racily-clean discipline and the per-lookup stat
comparison apply to them unchanged, so a deliberately stale restored
entry re-stats and re-hashes — never replays.

The chunk fetch on restore rides :meth:`ChunkStore.ensure_available`,
i.e. the same fleet peer wire / ranged-pack path every other chunk
miss uses — which is what makes fleet **prewarm** one recipe POST: the
target stages the recipe and pulls the missing shard chunks from the
source worker before the build arrives.
"""

from __future__ import annotations

import hashlib
import json
import os
import tarfile
import time
import zlib

from makisu_tpu.utils import fileio, metrics
from makisu_tpu.utils import logging as log
from makisu_tpu.utils import pathutils

SNAPSHOT_SCHEMA = "makisu-tpu.session-snapshot.v1"
SNAPSHOT_SUBDIR = os.path.join("serve", "snapshots")

# Rel-path hash buckets for the stat and walk shards: enough that one
# touched file re-serializes ~1/16th of a big table, few enough that an
# idle checkpoint's existence probe stays a handful of stats.
STAT_BUCKETS = 16
WALK_BUCKETS = 16

# TarInfo fields that round-trip through a layer shard. Offsets and
# sparse maps are stream-position state that replay never consults.
_TAR_FIELDS = ("name", "mode", "uid", "gid", "size", "mtime",
               "linkname", "uname", "gname", "devmajor", "devminor")


def snapshots_dir(storage_dir: str) -> str:
    return os.path.join(os.path.abspath(storage_dir), SNAPSHOT_SUBDIR)


def snap_key(context_dir: str, portable_identity: str) -> str:
    """Recipe filename key: one recipe per (context, portable flag
    identity) — a checkpoint overwrites its predecessor atomically."""
    blob = (pathutils.real_path(os.path.abspath(context_dir))
            + "\n" + portable_identity).encode()
    return hashlib.sha256(blob).hexdigest()


def _bucket(rel: str, buckets: int) -> int:
    return zlib.crc32(rel.encode("utf-8", "surrogateescape")) % buckets


def _dumps(doc) -> bytes:
    return json.dumps(doc, sort_keys=True,
                      separators=(",", ":")).encode()


# -- TarInfo / layer-entry round-trip ---------------------------------------


def _tarinfo_to_doc(hdr: tarfile.TarInfo) -> dict:
    doc = {f: getattr(hdr, f) for f in _TAR_FIELDS}
    doc["type"] = hdr.type.decode("latin-1")
    if hdr.pax_headers:
        doc["pax"] = {str(k): str(v)
                      for k, v in hdr.pax_headers.items()}
    return doc


def _tarinfo_from_doc(doc: dict) -> tarfile.TarInfo:
    hdr = tarfile.TarInfo()
    for f in _TAR_FIELDS:
        if f in doc:
            setattr(hdr, f, doc[f])
    hdr.type = str(doc.get("type", "0")).encode("latin-1")
    pax = doc.get("pax")
    if isinstance(pax, dict):
        hdr.pax_headers = {str(k): str(v) for k, v in pax.items()}
    return hdr


def _entries_to_doc(entries: list) -> list:
    from makisu_tpu.snapshot.layer import ContentEntry, WhiteoutEntry
    out = []
    for e in entries:
        if isinstance(e, WhiteoutEntry):
            out.append({"wh": e.deleted})
        elif isinstance(e, ContentEntry):
            out.append({"src": e.src, "dst": e.dst,
                        "hdr": _tarinfo_to_doc(e.hdr)})
        else:
            raise ValueError(f"unknown layer entry {type(e)!r}")
    return out


def _entries_from_doc(doc: list) -> list:
    from makisu_tpu.snapshot.layer import ContentEntry, WhiteoutEntry
    out = []
    for row in doc:
        if "wh" in row:
            out.append(WhiteoutEntry(str(row["wh"])))
        else:
            out.append(ContentEntry(str(row["src"]), str(row["dst"]),
                                    _tarinfo_from_doc(row["hdr"])))
    return out


# -- the store --------------------------------------------------------------


class SnapshotStore:
    """One storage dir's snapshot plane: recipes under
    ``serve/snapshots/``, shard bytes in the shared chunk CAS."""

    def __init__(self, storage_dir: str) -> None:
        self.storage_dir = os.path.abspath(storage_dir)
        self.dir = snapshots_dir(storage_dir)
        self._chunks = None

    def chunk_store(self):
        if self._chunks is None:
            from makisu_tpu.cache.chunks import (ChunkStore,
                                                 register_serving_store)
            self._chunks = ChunkStore(
                os.path.join(self.storage_dir, "chunks"))
            # Snapshot shards must be fetchable by fleet siblings over
            # GET /chunks/<fp> (the prewarm pull), even when no build
            # ever attached chunk dedup for this storage (cpu-hasher
            # builds write snapshots too). Registration is idempotent
            # per CAS root, and the worker's served-root scoping still
            # gates which in-process sibling may serve it.
            register_serving_store(self._chunks)
        return self._chunks

    def recipe_path(self, key: str) -> str:
        return os.path.join(self.dir, f"{key}.json")

    def write_recipe(self, recipe: dict) -> str:
        key = snap_key(recipe["context"], recipe["portable_identity"])
        os.makedirs(self.dir, exist_ok=True)
        fileio.write_json_atomic(self.recipe_path(key), recipe)
        return key

    def load(self, context_dir: str,
             portable_identity: str) -> dict | None:
        return self._read(self.recipe_path(
            snap_key(context_dir, portable_identity)))

    def load_for_context(self, context_dir: str) -> dict | None:
        """Newest recipe for a context regardless of identity — the
        prewarm pull path, where the front door knows the context key
        but not the resolved flag identity."""
        key = pathutils.real_path(os.path.abspath(context_dir))
        best = None
        try:
            names = os.listdir(self.dir)
        except OSError:
            return None
        for name in names:
            if not name.endswith(".json") or name.endswith(".tmp"):
                continue
            doc = self._read(os.path.join(self.dir, name))
            if doc is None or doc.get("context") != key:
                continue
            if best is None or (doc.get("saved_at", 0)
                                > best.get("saved_at", 0)):
                best = doc
        return best

    @staticmethod
    def _read(path: str) -> dict | None:
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            return None
        if not isinstance(doc, dict) \
                or doc.get("schema") != SNAPSHOT_SCHEMA \
                or not isinstance(doc.get("shards"), dict):
            return None
        return doc

    def shard_plan(self, recipe: dict) -> list[tuple[int, int, str]]:
        """The recipe's chunk plan in ``ensure_available`` shape."""
        plan = []
        for row in recipe.get("shards", {}).values():
            plan.append((0, int(row.get("bytes", 0)),
                         str(row.get("chunk", ""))))
        return plan

    def stage(self, recipe: dict) -> tuple[bool, str]:
        """Adopt a foreign recipe (fleet prewarm push): persist it
        locally and pull any missing shard chunks over the peer wire.
        Returns ``(ok, reason)`` — a failed stage leaves no recipe
        behind, so a later restore attempt can't trust a plan whose
        bytes never arrived."""
        if not isinstance(recipe, dict) \
                or recipe.get("schema") != SNAPSHOT_SCHEMA \
                or not isinstance(recipe.get("shards"), dict) \
                or not recipe.get("context") \
                or not recipe.get("portable_identity"):
            return False, "schema"
        plan = self.shard_plan(recipe)
        if not all(h and len(h) == 64 for _, _, h in plan):
            return False, "schema"
        if not self.chunk_store().ensure_available(plan):
            return False, "chunks_unavailable"
        self.write_recipe(recipe)
        return True, ""


# -- checkpoint write -------------------------------------------------------

# Watcher-mode sessions keep a dedicated persistence baseline (the
# live session needs no walk at all); it refreshes once this many
# watcher-observed dirty paths accumulate, bounding the restore-time
# over-dirtying a stale baseline costs to one bounded re-scan.
BASELINE_REFRESH_PATHS = 4096


def _layer_shard_name(key: tuple) -> str:
    chain, digest = key
    return "layer/" + hashlib.sha256(
        f"{chain}:{digest}".encode()).hexdigest()[:32]


def write_snapshot(session, storage_dir: str) -> dict | None:
    """Checkpoint one session into the chunk CAS. Incremental: shards
    whose dirty flag is clear carry their previous chunk forward
    without re-serializing; re-serialized shards that hash to an
    existing chunk skip the put. Never raises — a checkpoint that
    cannot land costs durability, not the build."""
    try:
        return _write_snapshot(session, storage_dir)
    except Exception as exc:  # noqa: BLE001 - advisory by contract
        metrics.counter_add(metrics.SESSION_SNAPSHOT_WRITES,
                            result="error")
        log.warning("session snapshot write failed for %s: %s",
                    session.context_dir, exc)
        return None


def _write_snapshot(session, storage_dir: str) -> dict | None:
    if not session.portable_identity:
        return None
    store = SnapshotStore(storage_dir)
    chunks = store.chunk_store()
    carried: dict[str, list] = dict(session._snap_shards)
    shards: dict[str, dict] = {}
    written = reused = 0

    def put_shard(name: str, doc) -> None:
        nonlocal written, reused
        blob = _dumps(doc)
        hex_digest = hashlib.sha256(blob).hexdigest()
        if chunks.cas.exists(hex_digest):
            reused += len(blob)
        else:
            chunks.put(hex_digest, blob)
            written += len(blob)
        shards[name] = {"chunk": hex_digest, "bytes": len(blob)}

    def carry(name: str) -> bool:
        row = carried.get(name)
        if not row:
            return False
        shards[name] = {"chunk": row["chunk"],
                        "bytes": row["bytes"]}
        return True

    # scan memo: one shard, rewritten only after scan_store/clear.
    if session._snap_scan_dirty or not carry("scan"):
        put_shard("scan", [[src, cin, out, files, nbytes]
                           for (src, cin), (out, files, nbytes)
                           in session.scan_memo.items()])
        session._snap_scan_dirty = False

    # stat/content-ID cache: bucketed by rel-path hash; only buckets
    # holding a mutated key re-serialize.
    cache = session.content_ids
    if cache is not None and hasattr(cache, "namespace_items"):
        mutated = cache.drain_mutations()
        dirty = ({_bucket(rel, STAT_BUCKETS) for rel in mutated}
                 if not session._snap_stat_all
                 else set(range(STAT_BUCKETS)))
        items = None
        for b in range(STAT_BUCKETS):
            name = f"stat/{b}"
            if b not in dirty and carry(name):
                continue
            if items is None:
                items = [{} for _ in range(STAT_BUCKETS)]
                for rel, entry in cache.namespace_items().items():
                    items[_bucket(rel, STAT_BUCKETS)][rel] = entry
            put_shard(name, items[b])
        session._snap_stat_all = False

    # walk baseline: the certification point a restored session deltas
    # against. mtime-walk sessions persist the live begin-build
    # baseline (already current); watcher sessions keep a dedicated
    # one, refreshed only when accumulated churn makes the restore-time
    # delta too conservative.
    baseline = session.snapshot
    if session.watcher is not None and session.watcher.healthy:
        if session._snap_baseline is None and baseline is not None:
            # A restored-then-watched session already holds a current
            # walk baseline (the restore-gap delta refreshed it) —
            # adopt it instead of paying a fresh walk.
            session._snap_baseline = baseline
        if (session._snap_baseline is None
                or session._snap_gap_paths > BASELINE_REFRESH_PATHS):
            import importlib
            # `makisu_tpu.snapshot` exports a *function* named walk
            # that shadows the submodule on a from-import.
            walk_mod = importlib.import_module(
                "makisu_tpu.snapshot.walk")
            # At a build's own checkpoint the build's listing replays
            # what its passes statted (the watcher was armed before
            # the first of them); a forced checkpoint has none.
            baseline = walk_mod.snapshot_tree(
                session.context_dir, session._walk_blacklist,
                session.build_listing)
            session._snap_baseline = baseline
            session._snap_gap_paths = 0
            session._snap_walk_all = True
        else:
            baseline = session._snap_baseline
    walk_doc = None
    if baseline is not None:
        walk_doc = {"root": baseline.root,
                    "captured_ns": baseline.captured_ns,
                    "est_bytes": baseline.est_bytes,
                    "fresh": sorted(baseline.fresh)}
        dirty = ({_bucket(p, WALK_BUCKETS)
                  for p in session._snap_walk_dirty}
                 if not session._snap_walk_all
                 else set(range(WALK_BUCKETS)))
        sigs = None
        for b in range(WALK_BUCKETS):
            name = f"walk/{b}"
            if b not in dirty and carry(name):
                continue
            if sigs is None:
                sigs = [{} for _ in range(WALK_BUCKETS)]
                for path, sig in baseline.sigs.items():
                    sigs[_bucket(path, WALK_BUCKETS)][path] = list(sig)
            put_shard(name, sigs[b])
        session._snap_walk_all = False
        session._snap_walk_dirty.clear()

    # layer-replay memo: one content-keyed shard per entry; carried
    # names ARE the dedup, and evicted memos simply drop out of the
    # recipe (their chunks age out of the CAS by LRU like any other).
    layer_index = {}
    for key, entries in session.layer_replay.items():
        name = _layer_shard_name(key)
        layer_index[name] = list(key)
        if not carry(name):
            put_shard(name, _entries_to_doc(entries))

    recipe = {
        "schema": SNAPSHOT_SCHEMA,
        "context": pathutils.real_path(session.context_dir),
        "identity": session.identity,
        "portable_identity": session.portable_identity,
        "isa": session.isa,
        "ignore_sig": session._ignore_sig,
        "exact": bool(session.exact and walk_doc is not None),
        "builds": session.builds,
        "saved_at": time.time(),
        "pending_dirty": sorted(session.pending_dirty),
        "walk": walk_doc,
        "layer_keys": layer_index,
        "shards": shards,
    }
    store.write_recipe(recipe)
    session._snap_shards = {n: dict(r) for n, r in shards.items()}
    metrics.counter_add(metrics.SESSION_SNAPSHOT_WRITES, result="ok")
    if written:
        metrics.counter_add(metrics.SESSION_SNAPSHOT_CHUNK_BYTES,
                            written, result="written")
    if reused:
        metrics.counter_add(metrics.SESSION_SNAPSHOT_CHUNK_BYTES,
                            reused, result="reused")
    return recipe


# -- restore ----------------------------------------------------------------


def try_restore(context_dir: str, identity: str, storage_dir: str,
                portable_identity: str):
    """Rebuild a session from the local snapshot plane. Returns
    ``(session, "")`` on success, ``(None, "")`` when no recipe exists
    (a plain cold miss, not a failure), or ``(None, reason)`` on a
    refusal/error — the reasons mirror the live invalidation story, so
    a snapshot can never outlive the checks a resident session obeys."""
    store = SnapshotStore(storage_dir)
    recipe = store.load(context_dir, portable_identity)
    if recipe is None:
        # Identity-keyed miss: fall back to any recipe for the context
        # so identity drift refuses LOUDLY (flag_identity) instead of
        # silently rebuilding cold.
        recipe = store.load_for_context(context_dir)
        if recipe is None:
            return None, ""
    return restore_from_recipe(store, recipe, context_dir, identity,
                               portable_identity)


def restore_from_recipe(store: SnapshotStore, recipe: dict,
                        context_dir: str, identity: str,
                        portable_identity: str):
    from makisu_tpu.worker import session as session_mod
    key = pathutils.real_path(os.path.abspath(context_dir))
    if recipe.get("context") != key:
        return None, "context_mismatch"
    if recipe.get("portable_identity") != portable_identity:
        return None, "flag_identity"
    if recipe.get("isa") != session_mod._isa_identity():
        return None, "isa_change"
    age = time.time() - float(recipe.get("saved_at", 0) or 0)
    if age > session_mod.session_ttl():
        return None, "stale"
    chunks = store.chunk_store()
    if not chunks.ensure_available(store.shard_plan(recipe)):
        return None, "chunks_unavailable"
    try:
        return _materialize(store, recipe, context_dir,
                            identity), ""
    except Exception as exc:  # noqa: BLE001 - never fail the build
        log.warning("session snapshot restore failed for %s: %s",
                    context_dir, exc)
        return None, "corrupt"


def _load_shard(chunks, recipe: dict, name: str):
    row = recipe["shards"].get(name)
    if row is None:
        return None
    return json.loads(chunks.get(str(row["chunk"])).decode())


def _materialize(store: SnapshotStore, recipe: dict,
                 context_dir: str, identity: str):
    import importlib

    from makisu_tpu.worker import session as session_mod
    walk_mod = importlib.import_module("makisu_tpu.snapshot.walk")
    chunks = store.chunk_store()
    session = session_mod.BuildSession(context_dir, identity)
    session.portable_identity = recipe["portable_identity"]
    session.builds = int(recipe.get("builds", 0) or 0)
    session._ignore_sig = recipe.get("ignore_sig")
    session.pending_dirty = {str(p) for p in
                             recipe.get("pending_dirty") or []}

    scan = _load_shard(chunks, recipe, "scan") or []
    for src, cin, out, files, nbytes in scan:
        session.scan_memo[(str(src), int(cin))] = (
            int(out), int(files), int(nbytes))

    stat_entries: dict[str, list] = {}
    for b in range(STAT_BUCKETS):
        shard = _load_shard(chunks, recipe, f"stat/{b}")
        if isinstance(shard, dict):
            stat_entries.update(shard)
    session._restored_stat_entries = stat_entries or None

    walk_doc = recipe.get("walk")
    if isinstance(walk_doc, dict) and recipe.get("exact"):
        sigs: dict[str, tuple] = {}
        for b in range(WALK_BUCKETS):
            shard = _load_shard(chunks, recipe, f"walk/{b}")
            if isinstance(shard, dict):
                for path, sig in shard.items():
                    sigs[str(path)] = tuple(sig)
        session.snapshot = walk_mod.TreeSnapshot(
            str(walk_doc.get("root", context_dir)),
            int(walk_doc.get("captured_ns", 0) or 0),
            sigs,
            {str(p) for p in walk_doc.get("fresh") or []},
            int(walk_doc.get("est_bytes", 0) or 0))
        session.exact = True
        session._gap_delta_pending = True

    layer_keys = recipe.get("layer_keys") or {}
    for name, key in layer_keys.items():
        doc = _load_shard(chunks, recipe, name)
        if doc is None or not isinstance(key, list) or len(key) != 2:
            continue
        session.replay_store((str(key[0]), str(key[1])),
                             _entries_from_doc(doc))

    # The restored shards ARE the last checkpoint: carry their chunks
    # forward so the first post-restore checkpoint is incremental too.
    session._snap_shards = {n: dict(r) for n, r
                            in recipe["shards"].items()}
    session._snap_scan_dirty = False
    session._snap_stat_all = True  # local cache may hold extra keys
    session._snap_walk_all = False
    session.restored = True
    session._restore_fresh = True
    return session
