"""Chunk-granular layer dedup.

The reference's cache maps one cache ID to one whole layer blob
(lib/cache/cache_manager.go:39-40): any content change re-transfers the
entire layer. Here every cache entry written by the TPU hasher also
carries the layer's content-defined chunk list (offset, length, sha256 of
the uncompressed tar stream). Because the gzip writer is deterministic
(tario.gzip_writer pins mtime/filename/level), a layer blob is a pure
function of its chunk bytes — so a builder that misses the layer blob but
holds the chunks (from *any* earlier layer that shared them) rebuilds the
blob locally, byte-identical, transferring only novel chunks.

Chunk blobs live in a CAS keyed by chunk digest; remote distribution
rides the same registry blob plane the layer cache already uses.
"""

from __future__ import annotations

import collections
import hashlib
import os

import json

from makisu_tpu import tario
from makisu_tpu.docker.image import Digest, DigestPair
from makisu_tpu.registry import transfer
from makisu_tpu.storage import cas as cas_mod
from makisu_tpu.storage import contentstore
from makisu_tpu.utils import events
from makisu_tpu.utils import ledger
from makisu_tpu.utils import logging as log
from makisu_tpu.utils import metrics

# Chunk blobs carry their own media type in pin manifests (raw
# uncompressed tar-stream slices, not gzip layers).
CHUNK_MEDIA_TYPE = "application/vnd.makisu-tpu.chunk.v1"

# A pack is the wire/registry form of many chunks: the concatenation of
# a layer's NEWLY-ADDED chunk bytes, carved back into individual chunks
# by the consumer. Chunks average ~8KiB (dedup granularity wants them
# small); shipping each as its own registry blob costs one HTTP round
# trip per 8KiB — a 4GB layer would be ~500k PUTs, and round trips, not
# bytes, dominate. Packs amortize that to one request per ~8MB while
# the LOCAL store keeps chunk granularity (fingerprints, dedup, and
# reconstitution are unchanged).
PACK_MEDIA_TYPE = "application/vnd.makisu-tpu.chunkpack.v1"

# Chunks per pin manifest: ~140 bytes/descriptor keeps each manifest
# near 2.8MB, under distribution's 4MiB payload cap.
PIN_SHARD_CHUNKS = 20_000


def packs_enabled() -> bool:
    """MAKISU_TPU_CHUNK_PACKS=0 restores per-chunk blob pushes (debug /
    registries that mishandle large opaque blobs)."""
    return os.environ.get("MAKISU_TPU_CHUNK_PACKS", "1") == "1"


def pack_target_bytes() -> int:
    """Target pack size (MAKISU_TPU_PACK_TARGET_MB, default 8MB): large
    enough that request overhead amortizes, small enough that a
    consumer's whole-pack fetch over-reads little and HEAD-skip dedup
    between successive pushes keeps useful granularity. Floored at 1MB:
    a target under the chunk size would silently degenerate to one pack
    per chunk — the per-chunk PUT storm packs exist to eliminate."""
    try:
        target = int(float(os.environ.get(
            "MAKISU_TPU_PACK_TARGET_MB", "8")) * 1e6)
    except ValueError:
        return 8_000_000
    return max(target, 1_000_000)


# -- serving (the worker's GET /chunks/<fp>) ---------------------------------

def register_serving_store(store: "ChunkStore") -> None:
    """Enter a store's CAS among the process's live stores
    (storage/cas.py keeps them, by real path of the root): the worker's
    read-only peer-exchange endpoint serves chunk bytes out of
    whichever holds them, and the evictor, the census and tier refetch
    reach the same object for that root."""
    cas_mod.register_live(store.cas)


def open_served_chunk(hex_digest: str, roots=None):
    """Open ``hex_digest`` from a registered store (the worker's
    ``GET /chunks/<fp>`` backend): returns an open file object or None.
    Local CAS only — serving a peer must never trigger our OWN remote
    fetch (a fleet of workers each proxying the miss onward would
    amplify one cold chunk into N registry round trips).

    ``roots`` (realpath'd CAS roots) scopes the lookup to the stores a
    particular worker actually owns: in an in-process fleet the
    registry is shared by every worker, and serving a sibling's bytes
    would fake the cross-host exchange the endpoint models (the same
    per-machine honesty the per-server session managers give)."""
    for store in cas_mod.live_stores(roots):
        try:
            return store.open(hex_digest)
        except FileNotFoundError:
            continue
    return None


# index_layer's one pass reads the blob through the tree's one block
# inflater (its tests patch READ and BLOCK under this name).
_Inflated = tario.BlockInflater


def plan_pack_runs(rows, missing, gap=None, whole_fraction=None,
                   pack_sizes=None):
    """Group the missing chunks' pack spans into coalesced fetch runs.

    The ONE definition of ranged-fetch economics, shared by the
    registry pack path (``_fetch_from_packs``) and the serve/peer
    plane (``serve/client.py``) — a tuning change here moves every
    wire at once, none can drift.

    ``rows`` are ``(fp, length, pack_hex, pack_off)`` rows (recipe
    rows or pack member tables); returns ``(run_jobs, whole_jobs)``
    where ``run_jobs`` is ``[(pack_hex, [run, ...])]`` with each run a
    list of ``(pack_off, length, fp)`` spans sorted + coalesced (span
    gap ≤ ``gap``), and ``whole_jobs`` names packs worth fetching
    whole (needed fraction > ``whole_fraction`` of the pack's known
    extent; pass ``whole_fraction=-1`` to force every pack whole — the
    Range-less-transport degradation). Pure function — the
    coalescing-correctness tests drive it directly."""
    if gap is None:
        gap = ChunkStore.PACK_RUN_GAP
    if whole_fraction is None:
        whole_fraction = ChunkStore.PACK_WHOLE_FETCH_FRACTION
    by_pack: dict[str, dict[str, tuple[int, int]]] = {}
    extents: dict[str, int] = dict(pack_sizes or {})
    for fp, length, pack_hex, pack_off in rows:
        extents[pack_hex] = max(extents.get(pack_hex, 0),
                                int(pack_off) + int(length))
        if fp in missing:
            by_pack.setdefault(pack_hex, {}).setdefault(
                fp, (int(pack_off), int(length)))
    run_jobs: list[tuple[str, list]] = []
    whole_jobs: list[str] = []
    for pack_hex, wanted in sorted(by_pack.items()):
        spans = sorted((off, length, fp)
                       for fp, (off, length) in wanted.items())
        needed = sum(length for _, length, _ in spans)
        if needed > extents[pack_hex] * whole_fraction:
            whole_jobs.append(pack_hex)
            continue
        runs: list[list] = []
        for span in spans:
            if (runs and span[0] - (runs[-1][-1][0] + runs[-1][-1][1])
                    <= gap):
                runs[-1].append(span)
            else:
                runs.append([span])
        run_jobs.append((pack_hex, runs))
    return run_jobs, whole_jobs


def plan_frame_runs(frames, spans, gap=None):
    """Map missing raw spans of ONE pack onto its seekable-zstd frame
    index: which frames must be fetched, coalesced into ranged runs
    over COMPRESSED bytes.

    Lives beside :func:`plan_pack_runs` for the same reason that
    function lives here — ranged-fetch economics have one definition,
    and the serve client and the peer plane both ride it. ``frames``
    are ``(raw_off, raw_len, z_off, z_len)`` rows (a recipe's
    ``zpacks`` entry); ``spans`` are ``(raw_off, length, fp)`` missing
    spans within the pack. Returns a list of runs, each a list of
    frame rows whose compressed extents are adjacent or within ``gap``
    bytes (the same over-fetch-vs-round-trip tradeoff as the raw
    wire). Pure function — the planning tests drive it directly."""
    import bisect
    if gap is None:
        gap = ChunkStore.PACK_RUN_GAP
    rows = sorted([int(r[0]), int(r[1]), int(r[2]), int(r[3])]
                  for r in frames)
    starts = [r[0] for r in rows]
    needed: set[int] = set()
    for off, length, _fp in spans:
        end = int(off) + int(length)
        i = max(bisect.bisect_right(starts, int(off)) - 1, 0)
        while i < len(rows) and rows[i][0] < end:
            if rows[i][0] + rows[i][1] > int(off):
                needed.add(i)
            i += 1
    runs: list[list] = []
    for i in sorted(needed):
        row = rows[i]
        if runs and row[2] - (runs[-1][-1][2] + runs[-1][-1][3]) <= gap:
            runs[-1].append(row)
        else:
            runs.append([row])
    return runs


class ChunkStore:
    """CAS of uncompressed-stream chunks, keyed by hex sha256.

    With a registry client attached, chunks also ride the registry's
    blob plane (a chunk digest is a valid blob digest): new chunks push
    on index, missing chunks fetch on demand — the DCN-distributed half
    of chunk dedup, reusing the same infrastructure as layer blobs.
    """

    def __init__(self, root: str, max_entries: int | None = None) -> None:
        if max_entries is None:
            # Sized for the north-star scale: a 4GB layer is ~500k
            # chunks at the 8KiB average, and BOTH halves of dedup
            # depend on retention — build_packs reads added chunks back
            # from this CAS, and a warm rebuild's coverage is whatever
            # survived here. Eviction below the largest layer's chunk
            # count silently turns dedup off for exactly the layers it
            # exists for (MAKISU_TPU_CHUNK_CAS_ENTRIES tunes it).
            try:
                max_entries = int(os.environ.get(
                    "MAKISU_TPU_CHUNK_CAS_ENTRIES", str(1 << 20)))
            except ValueError:
                max_entries = 1 << 20  # cache sizing never fails builds
        self.cas = cas_mod.CASStore(root, max_entries)
        # Refcount plane: reads pin their chunk for their duration, the
        # budget evictor and the CAS's own count-LRU both honor pins
        # (storage/contentstore.py keys the board by storage dir, so
        # the worker's serve plane and this store share one board).
        self.pins = contentstore.board_for_chunk_root(root)
        self.cas.pin_check = self.pins.chunk_pinned
        self.registry = None  # attach via set_remote()
        # Fingerprint-streamed existence memo (note_fingerprint): the
        # commit pipeline reports each chunk digest as it is hashed,
        # and the dedup lookup (one CAS stat per chunk — a 500k-stat
        # storm on a 4GB layer) runs on the commit pool DURING the
        # commit instead of serially inside index_layer afterwards.
        import threading
        self._exists_memo: dict[str, bool | None] = {}
        self._probe_queue: list[str] = []
        self._memo_gen = 0  # bumped by reset; stale probes discard
        self._memo_lock = threading.Lock()

    def set_remote(self, layer_client) -> None:
        """Attach a registry client; chunk blobs transfer straight into
        this CAS (the client template supplies registry/auth/transport)."""
        if layer_client is None:
            self.registry = None
            return
        from makisu_tpu.registry.client import RegistryClient

        class _CASOnlyStore:
            """Just enough ImageStore surface for blob transfers."""

            def __init__(self, cas) -> None:
                self.layers = cas

        self.registry = RegistryClient(
            _CASOnlyStore(self.cas), layer_client.registry,
            layer_client.repository, config=layer_client.config,
            transport=layer_client.transport)
        # Passing transport explicitly makes the new client treat it as
        # injected and pin cross-origin redirects to it; mirror the
        # layer client's actual redirect policy instead (public-CA
        # transport for S3/GCS-backed registries, unless
        # trust_redirects / a genuinely injected transport says
        # otherwise).
        self.registry.cdn_transport = layer_client.cdn_transport

    def has(self, hex_digest: str) -> bool:
        if self.cas.exists(hex_digest):
            return True
        # A demoted chunk promotes back from its pack's compressed
        # twin before the registry is asked (local decompress beats a
        # WAN round trip; also the only route when no registry is
        # attached — the worker's serve path after budget eviction).
        if contentstore.refetch_for_chunk_root(
                self.cas.root, [hex_digest], {}):
            return True
        if self.registry is not None:
            return self._fetch_remote(hex_digest)
        return False

    # -- streaming existence prefetch ---------------------------------

    # Digests per pooled probe task: one task per chunk would
    # reintroduce the per-chunk submission overhead the commit
    # pipeline just removed (a 4GB layer is ~500k chunks).
    PROBE_BATCH = 256

    def note_fingerprint(self, hex_digest: str) -> None:
        """Chunk-fingerprint observer (chunker.cdc.set_chunk_observer):
        called from the commit pipeline as each chunk digest resolves.
        Existence stats batch onto the commit pool and memoize for
        index_layer; a tail shorter than PROBE_BATCH simply never
        probes (advisory — index_layer's window makes the stat).
        Thread-safe, never raises."""
        with self._memo_lock:
            if hex_digest in self._exists_memo:
                return
            self._exists_memo[hex_digest] = None  # claimed; stat fills
            self._probe_queue.append(hex_digest)
            if len(self._probe_queue) < self.PROBE_BATCH:
                return
            batch, self._probe_queue = self._probe_queue, []
            gen = self._memo_gen

        def probe(batch=batch, gen=gen) -> None:
            found = {}
            for h in batch:
                try:
                    found[h] = self.cas.exists(h)
                except Exception:  # noqa: BLE001 - advisory stat
                    return
            with self._memo_lock:
                if self._memo_gen != gen:
                    # reset_fingerprint_memo ran while this batch was
                    # queued: its answers belong to the PREVIOUS window
                    # and must not repopulate the cleared memo.
                    return
                self._exists_memo.update(found)
        from makisu_tpu.utils import concurrency
        # Plain submit (no context copy): the probe touches no
        # telemetry, and a copy per batch on the hot path buys nothing.
        # check: allow(ctx-propagation)
        concurrency.hash_pool().submit(probe)

    def _probed(self, hex_digest: str) -> bool | None:
        """What the streamed probe knows of a digest: True (stored),
        False (it looked, and the chunk was not there) or None (it never
        looked). index_layer takes both answers as they are: a True
        cannot outlive its commit (reset_fingerprint_memo), and a False
        that has gone stale, because another build stored the chunk
        since, costs one write of identical bytes over it, never a
        missing chunk. A digest the commit itself stores twice (repeated
        within the layer) is index_layer's own to catch."""
        with self._memo_lock:
            return self._exists_memo.get(hex_digest)

    def _probed_each(self, chunks: list[tuple[int, int, str]]):
        """``(chunk, _probed(its digest))`` for each chunk in order,
        looked up PROBE_BATCH chunks a lock round: the answers of
        probes still on the pool when index_layer starts are seen as
        they land."""
        for i in range(0, len(chunks), self.PROBE_BATCH):
            group = chunks[i:i + self.PROBE_BATCH]
            with self._memo_lock:
                known = [self._exists_memo.get(h) for _, _, h in group]
            yield from zip(group, known)

    def reset_fingerprint_memo(self) -> None:
        """Drop the streamed memo. Called after every index_layer
        (push_cache): a memoized True must not outlive the commit that
        prefetched it, or CAS eviction between layers could make
        index_layer skip storing a chunk it no longer holds."""
        with self._memo_lock:
            self._exists_memo.clear()
            self._probe_queue = []
            self._memo_gen += 1  # in-flight probe batches discard
        # The same holds for the store's own index of its segments: a
        # hit must not outlive a delete or a reclaim by another handle
        # or process. One listing a commit, not a stat a chunk.
        self.cas.refresh()

    def push_remote(self, hex_digest: str) -> None:
        if self.registry is not None:
            self.registry.push_layer(Digest.from_hex(hex_digest))

    def pin_remote(self, layer_hex: str,
                   chunks: list[tuple[int, int, str]]) -> None:
        """PUT a per-layer chunk manifest so the registry sees every
        chunk blob referenced. Without this, chunks ride the blob plane
        unreferenced by any manifest and every registry's garbage
        collector eventually deletes them, silently evaporating the
        distributed half of chunk dedup.

        The pin is one or more schema2 manifests (tags
        ``makisu-chunks-<layer>[-<shard>]``) whose layers are the chunk
        blobs and whose config records the pinned layer. Large layers
        shard across multiple pin manifests so no single manifest
        exceeds registries' payload limits (distribution caps manifests
        at 4MiB; a multi-GB layer has 100k+ chunks). Deleting the tags
        un-pins the chunks — cache retirement maps onto normal registry
        tag lifecycle."""
        if self.registry is None or not chunks:
            return
        self._pin_shards(layer_hex,
                         [(length, hex_digest)
                          for _, length, hex_digest in chunks],
                         CHUNK_MEDIA_TYPE, "makisu-chunks")

    def _pin_shards(self, layer_hex: str,
                    blobs: list[tuple[int, str]],
                    media_type: str, tag_prefix: str) -> None:
        """Shared pin machinery: tag one or more manifests referencing
        ``blobs`` ((length, hex) pairs) so the registry's GC sees them."""
        from makisu_tpu.docker.image import (
            MEDIA_TYPE_CONFIG,
            Descriptor,
            DistributionManifest,
        )
        config_blob = json.dumps(
            {"makisuTpuChunkPin": layer_hex},
            separators=(",", ":")).encode()
        config_hex = hashlib.sha256(config_blob).hexdigest()
        if not self.cas.exists(config_hex):
            self.cas.write_bytes(config_hex, config_blob)
        self.registry.push_layer(Digest.from_hex(config_hex))
        config_desc = Descriptor(MEDIA_TYPE_CONFIG, len(config_blob),
                                 Digest.from_hex(config_hex))
        for shard_index, start in enumerate(
                range(0, len(blobs), PIN_SHARD_CHUNKS)):
            shard = blobs[start:start + PIN_SHARD_CHUNKS]
            manifest = DistributionManifest(
                config=config_desc,
                layers=[Descriptor(media_type, length,
                                   Digest.from_hex(hex_digest))
                        for length, hex_digest in shard])
            tag = f"{tag_prefix}-{layer_hex[:40]}"
            if start:
                tag += f"-{shard_index}"
            self._push_pin_manifest(tag, manifest, shard)

    def _push_pin_manifest(self, tag: str, manifest, shard) -> None:
        from makisu_tpu.utils.httputil import HTTPError
        try:
            self.registry.push_manifest(tag, manifest)
        except HTTPError as e:
            # BLOB_UNKNOWN (in the error body): chunks reused from
            # earlier layers were never pushed to THIS repo. Upload
            # them (HEAD-skips existing ones) and retry once. Anything
            # else — auth, media-type/size rejection, NAME_UNKNOWN —
            # cannot be fixed by pushing blobs; propagate instead of
            # sweeping up to PIN_SHARD_CHUNKS network round-trips.
            if b"BLOB_UNKNOWN" not in e.body:
                raise
            for _, hex_digest in shard:
                self.push_remote(hex_digest)
            self.registry.push_manifest(tag, manifest)

    def _fetch_remote(self, hex_digest: str) -> bool:
        try:
            self.registry.pull_layer(Digest.from_hex(hex_digest))
        except Exception as e:  # noqa: BLE001 - remote miss/network
            log.debug("remote chunk %s unavailable: %s", hex_digest, e)
            return False
        # pull_layer verified the bytes against the digest before the
        # CAS link, so presence in the CAS is sufficient here.
        return self.cas.exists(hex_digest)

    def get(self, hex_digest: str) -> bytes:
        # Pin across the open+read: a concurrent eviction pass may cut
        # its victim list any time, and this read must win.
        with self.pins.pinned("chunks", hex_digest):
            return self.cas.read(hex_digest)

    def put(self, hex_digest: str, data: bytes) -> None:
        if hashlib.sha256(data).hexdigest() != hex_digest:
            raise ValueError(f"chunk content does not match {hex_digest}")
        self._count_files(self.cas.put(hex_digest, data))
        metrics.counter_add(metrics.CHUNK_INGEST, result="written")

    @staticmethod
    def _count_files(created: dict[str, int]) -> None:
        """Files the bulk ingest created, by kind: ``segment`` and
        ``index`` (a new pair where no segment of this process was
        free), ``loose`` (an entry no record can name). Files, not
        entries: an append to a segment that is there creates none."""
        for kind, n in created.items():
            metrics.counter_add(metrics.CHUNK_STORE_FILES_CREATED, n,
                                kind=kind)

    # index_layer's ingest window. Chunks leave the gunzip pass in
    # batches of up to INGEST_BATCH_BYTES (sixteen of the largest chunk
    # the chunker cuts, 64 KiB; ~128 average ones: a batch is one append
    # to a segment, two writes whatever it holds), at most
    # INGEST_WRITERS of them on the commit pool at once, so the bytes
    # staged outside the stream never pass (INGEST_WRITERS + 1) batches
    # and one chunk, whatever the layer's size (beside them, where a
    # recipe publisher observes the pass, the three packs its hand-off
    # may hold).
    INGEST_WRITERS = 8
    INGEST_BATCH_BYTES = 16 * 65536

    def _ingest_batch(self, batch: list[tuple[str, bytes, bool]]
                      ) -> tuple[list[str], dict[str, int]]:
        """One writer of index_layer's window, on the commit pool:
        probe the entries nobody has looked for and store the new ones
        (index_layer held each to its digest as it sliced it). Returns
        the digests it stored, in batch order, and the files the store
        created for them."""
        new = [(h, data) for h, data, probe in batch
               if not (probe and self.cas.exists(h))]
        return [h for h, _ in new], self.cas.write_many(new)

    def index_layer(self, layer_blob_path: str,
                    chunks: list[tuple[int, int, str]],
                    stats: dict | None = None,
                    observer=None) -> list[str]:
        """Slice a layer's uncompressed stream into its chunks and store
        any that are new locally (never fetching: the bytes are already
        in hand). Returns the hex digests newly added, each once, in
        offset order; ``stats`` (if given) receives ``ingest_window``,
        the peak number of writers in flight.

        Every slice is held to its digest here, once, as it is made:
        what a writer stores and what ``observer`` takes are the same
        verified bytes. ``observer(hex_digest, data)`` is told of every
        chunk of ``chunks``, in order: ``data`` is the slice just made,
        or None where the pass made none (found stored, or a repeat
        within the layer). The recipe publisher packs a cold layer's
        bytes from it, so nobody reads them back from the store.

        Decompression is streamed — the chunk list is offset-sorted,
        so one forward pass over the gzip stream suffices — a block at
        a time (``_Inflated``): the whole blob is inflated and its
        trailer verified, but only a chunk nobody has found stored is
        sliced out of its block; a run of chunks the streamed probe
        found costs neither bytes nor a file-system call. The stores
        run behind the pass through a bounded window of writers
        (``_ingest_batch``), so memory stays bounded by a block and the
        window (multi-GB layers never materialize whole). The first
        failure, of the stream or of any writer, is raised once the
        window has drained; a writer that fails leaves nothing under a
        final name."""
        from makisu_tpu.utils import concurrency
        added: list[str] = []
        # result -> chunks; flushed into the counters below. "raced" is
        # a digest this call already handed to a writer: judged here,
        # where a stat would race the write in flight.
        tally = collections.Counter()
        handed: set[str] = set()
        pool = concurrency.hash_pool()
        window: collections.deque = collections.deque()
        batch: list[tuple[str, bytes, bool]] = []
        batch_bytes = 0
        peak = 0
        failure: list[BaseException] = []
        created = collections.Counter()

        def reap() -> None:
            try:
                stored, files = window.popleft().result()
                added.extend(stored)
                created.update(files)
            except Exception as e:  # noqa: BLE001 - re-raised below
                failure.append(e)

        def flush() -> None:
            nonlocal batch, batch_bytes, peak
            while len(window) >= self.INGEST_WRITERS:
                reap()
            if not batch or failure:
                return
            # The batch just handed over is in flight by definition,
            # also where its writer finished before this line ran.
            peak = max(peak, 1 + sum(not f.done() for f in window))
            window.append(concurrency.submit_ctx(
                pool, self._ingest_batch, batch))
            batch, batch_bytes = [], 0

        try:
            with open(layer_blob_path, "rb") as raw:
                stream = _Inflated(raw)
                pos = 0
                for (offset, length, hex_digest), known in \
                        self._probed_each(chunks):
                    if failure:
                        break
                    if offset < pos:
                        raise ValueError(
                            f"chunk list not offset-sorted at "
                            f"{offset} < {pos}")
                    pos = offset + length
                    if known or hex_digest in handed:
                        # Stored already (its bytes stay in the block
                        # they were inflated into, unsliced) or handed
                        # to a writer earlier in this pass.
                        tally["hit" if known else "raced"] += 1
                        if observer is not None:
                            observer(hex_digest, None)
                        continue
                    handed.add(hex_digest)
                    tally["probe" if known is None else "miss"] += 1
                    data = stream.take(offset, length)
                    if hashlib.sha256(data).hexdigest() != hex_digest:
                        raise ValueError(
                            f"chunk content does not match {hex_digest}")
                    if observer is not None:
                        observer(hex_digest, data)
                    batch.append((hex_digest, data, known is None))
                    batch_bytes += length
                    if batch_bytes >= self.INGEST_BATCH_BYTES:
                        flush()
                flush()
                # To the blob's end, whoever wanted the bytes: zlib
                # validates the CRC32/ISIZE trailer there, so a corrupt
                # or short blob fails loudly here, not at reconstitute
                # (and a run of stored chunks past the stream's end
                # fails as a sliced one does).
                if not failure and stream.finish() < pos:
                    raise ValueError(
                        f"layer stream ended before {pos}, where its "
                        f"chunk list ends")
        finally:
            while window:
                reap()
        self._count_files(created)
        if failure:
            raise failure[0]
        if stats is not None:
            stats["ingest_window"] = peak
        for result in ("hit", "miss", "probe"):
            if tally[result]:
                metrics.counter_add("makisu_chunk_exists_prefetch_total",
                                    tally[result], result=result)
        for result, n in (
                ("written", len(added)),
                ("present", len(handed) - len(added) + tally["hit"]),
                ("raced", tally["raced"])):
            if n:
                metrics.counter_add(metrics.CHUNK_INGEST, n, result=result)
        return added

    def build_packs(self, chunks: list[tuple[int, int, str]],
                    added: list[str],
                    ) -> list[tuple[str, list[int]]]:
        """Group a layer's newly-added chunk bytes into pack blobs in
        the local CAS (push_packs uploads them; drop_local_packs cleans
        up). Returns ``[(pack_hex, [chunk_index, ...]), ...]`` — the
        mapping the cache entry records so consumers can locate any
        added chunk inside a pack (offset = sum of the lengths of the
        pack's preceding members, in index order).

        Member bytes come from the local CAS — index_layer stored every
        added chunk moments before — so assembling packs costs no
        second decompression pass over the layer blob. Peak memory is
        one ~pack_target_bytes() buffer."""
        added_set = set(added)
        target = pack_target_bytes()
        packs: list[tuple[str, list[int]]] = []
        buf = bytearray()
        members: list[int] = []
        packed: set[str] = set()

        def flush() -> None:
            nonlocal buf, members
            if not members:
                return
            pack_hex = hashlib.sha256(bytes(buf)).hexdigest()
            if not self.cas.exists(pack_hex):
                self.cas.write_bytes(pack_hex, bytes(buf))
            packs.append((pack_hex, members))
            buf = bytearray()
            members = []

        for i, (_, length, hex_digest) in enumerate(chunks):
            if hex_digest not in added_set or hex_digest in packed:
                continue
            data = self.get(hex_digest)
            if len(data) != length:
                raise ValueError(
                    f"chunk {hex_digest} CAS size {len(data)} != "
                    f"recorded length {length}")
            packed.add(hex_digest)
            buf += data
            members.append(i)
            if len(buf) >= target:
                flush()
        flush()
        return packs

    def push_packs(self, packs: list[tuple[str, list[int]]]) -> None:
        for pack_hex, _ in packs:
            self.registry.push_layer(Digest.from_hex(pack_hex))

    def pin_packs(self, layer_hex: str,
                  packs: list[tuple[str, list[int]]]) -> None:
        """Pin pack blobs against registry GC (same tag scheme as
        pin_remote, PACK media type). Only the packs THIS layer pushed
        are pinned: chunks reused from earlier layers live in the
        earlier layers' packs under the earlier layers' pins — retiring
        those pins degrades later consumers to the blob route, never to
        a broken build."""
        if self.registry is None or not packs:
            return
        # Distinct tag namespace from pin_remote's: a mixed fleet (one
        # builder with packs, one without) pinning the same layer must
        # not have the second pin's tag overwrite — and thereby unpin —
        # the first route's blobs.
        self._pin_shards(layer_hex,
                         [(self.cas.size(pack_hex), pack_hex)
                          for pack_hex, _ in packs],
                         PACK_MEDIA_TYPE, "makisu-packs")

    def drop_local_packs(self,
                         packs: list[tuple[str, list[int]]]) -> None:
        """Packs are a wire format; the local CAS keeps chunks
        individually. Called after push+pin (the BLOB_UNKNOWN retry in
        _push_pin_manifest re-uploads from the CAS, so packs must
        outlive the pin). A single-member pack's bytes ARE its chunk's
        bytes — same digest, same CAS entry — so deleting it would
        delete the chunk; those stay."""
        for pack_hex, members in packs:
            if len(members) == 1:
                continue
            try:
                self.cas.delete(pack_hex)
            except OSError:
                pass

    def ensure_available(self,
                         chunks: list[tuple[int, int, str]],
                         packs: list | None = None,
                         ledger_key: str | None = None) -> bool:
        """True when every chunk is local after this call. The local
        scan is one stat per chunk; the misses (the NOVEL fraction
        after an incremental edit — this is the wire transfer chunk
        dedup reduces to) fetch on a thread pool, since per-blob round
        trips, not bytes, dominate small-chunk transfer.

        ``ledger_key`` (the layer hex) opts the call into the decision
        ledger: one ``chunk_cas`` decision per consult carrying the
        requested/missing chunk counts and the byte split — exactly the
        per-key attribution cache-affinity routing needs as its
        signal."""
        # A digest repeated at several offsets (dedup within one layer)
        # must fetch once, not once per occurrence racing on the pool.
        lengths: dict[str, int] = {}
        for _, length, hex_digest in chunks:
            lengths.setdefault(hex_digest, length)
        # What is found here is read later without another look: the
        # store's index must have heard of deletes by other handles.
        self.cas.refresh()
        missing = sorted({h for _, _, h in chunks
                          if not self.cas.exists(h)})
        n_missing = len(missing)
        bytes_missing = sum(lengths[h] for h in missing)

        def outcome(available: bool) -> bool:
            if ledger_key is not None:
                verdict = ("hit" if not n_missing
                           else "partial" if available else "miss")
                ledger.record(
                    "chunk_cas", ledger_key, verdict,
                    reason=None if available else "chunks_incomplete",
                    requested=len(lengths), missing=n_missing,
                    bytes_total=sum(n for _, n, _ in chunks),
                    bytes_refetched=bytes_missing if available else 0)
            return available

        if not missing:
            return outcome(True)
        # Tier refetch first: a chunk the budget evictor demoted is
        # still on disk (or one object-tier read away) in its pack's
        # compressed twin — promoting it back is a local decompress,
        # cheaper than any wire route. No serve plane: free no-op.
        restored = contentstore.refetch_for_chunk_root(
            self.cas.root, missing, lengths)
        if restored:
            missing = [h for h in missing if h not in restored]
            if not missing:
                return outcome(True)
        # Peer exchange next: a fleet sibling that built this (or any
        # chunk-sharing) context holds the bytes one unix-socket round
        # trip away — the registry is a WAN away and the KV blob plane
        # may not even be attached. Budget-charged through the transfer
        # engine like every other wire path. No peers configured: free
        # no-op.
        from makisu_tpu.fleet import peers as fleet_peers
        if fleet_peers.available():
            # ledger_key IS the layer hex: it keys the peer's recipe,
            # so the exchange rides coalesced ranged pack reads with
            # the per-chunk GET kept as the old-worker fallback.
            from_peers = fleet_peers.fetch_chunks(
                self.put, missing, lengths, layer_hex=ledger_key)
            if from_peers:
                events.emit("chunk_fetch", route="peer",
                            fetched=len(from_peers),
                            requested=len(missing))
                log.info("fetched %d/%d missing chunks from fleet "
                         "peers", len(from_peers), len(missing))
                missing = [h for h in missing if h not in from_peers]
            if not missing:
                return outcome(True)
        if self.registry is None:
            return outcome(False)
        if packs:
            missing, mapped_failed = self._fetch_from_packs(
                chunks, packs, missing)
            if not missing and not mapped_failed:
                return outcome(True)
            if mapped_failed:
                # Pack-mapped chunks were never pushed as individual
                # blobs: a per-chunk fallback for them is a guaranteed
                # 404 per chunk (~100k futile round trips on a big
                # layer). Their pack is gone/corrupt — report
                # unavailable so the pull degrades to the blob route.
                return outcome(False)
        # The shared transfer engine bounds these alongside every other
        # wire path (they used to ride their own ThreadPoolExecutor(8),
        # unbounded against concurrent builds' transfers).
        ok = transfer.engine().map(self._fetch_remote, missing)
        metrics.counter_add("makisu_chunks_fetched_total", sum(ok),
                            route="blob")
        events.emit("chunk_fetch", route="blob", fetched=sum(ok),
                    requested=len(missing))
        return outcome(all(ok))

    # Coalesce needed spans within a pack when the gap between them is
    # under this: one ranged GET fetching a few spare KiB beats two
    # round trips.
    PACK_RUN_GAP = 128 * 1024
    # Above this needed-bytes fraction, ranged GETs stop paying: pull
    # the whole pack in one request.
    PACK_WHOLE_FETCH_FRACTION = 0.5

    def _fetch_from_packs(self, chunks, packs,
                          missing: list[str],
                          ) -> tuple[list[str], bool]:
        """Fetch missing chunks via their pack blobs, transferring only
        the spans that are actually missing: needed members coalesce
        into runs (gap <= PACK_RUN_GAP) served by HTTP Range requests,
        and a pack mostly-needed (> PACK_WHOLE_FETCH_FRACTION) or on a
        registry without Range support transfers whole. Either way the
        wire cost is ~the novel fraction in bytes and ~the novel-REGION
        count in round trips — never one request per ~8KiB chunk.
        Carved members are digest-verified before the CAS stores them.
        Returns (digests not mapped to any pack — still eligible for
        the per-chunk fallback, mapped_failed — True when a mapped
        chunk could not be produced because its pack is unavailable or
        corrupt; those never exist as individual blobs, so the caller
        must degrade, not retry them one by one)."""
        locate: dict[str, tuple[str, int, int]] = {}
        pack_sizes: dict[str, int] = {}
        pack_member_counts: dict[str, int] = {}
        for pack_hex, members in packs:
            off = 0
            for i in members:
                try:
                    _, length, hex_digest = chunks[i]
                except (IndexError, TypeError, ValueError):
                    # Malformed mapping: the entry came from a pack
                    # writer, so its chunks were never pushed as
                    # individual blobs — report mapped-failure (degrade
                    # to the blob route), don't unleash the per-chunk
                    # fallback's guaranteed 404s.
                    return [], True
                locate.setdefault(hex_digest, (pack_hex, off, length))
                off += length
            pack_sizes[pack_hex] = off
            pack_member_counts[pack_hex] = len(members)
        rows = [(h, locate[h][2], locate[h][0], locate[h][1])
                for h in dict.fromkeys(missing) if h in locate]
        got: set[str] = set()
        # Per-pack sorted missing spans, for carving full-pack bodies
        # and the degradation log.
        pack_spans: dict[str, list] = {}
        for h, length, pack_hex, off in rows:
            pack_spans.setdefault(pack_hex, []).append((off, length, h))
        for spans in pack_spans.values():
            spans.sort()

        def carve(pack_hex: str, data: bytes, base: int,
                  members) -> None:
            """Verify+store members whose bytes lie inside data (pack
            bytes [base, base+len(data))). set.add and CAS writes are
            thread-safe; corrupt members just stay missing."""
            for off, length, hex_digest in members:
                piece = data[off - base:off - base + length]
                if len(piece) != length:
                    continue
                try:
                    self.put(hex_digest, piece)
                    got.add(hex_digest)
                except ValueError as e:
                    log.warning("pack %s member %s corrupt: %s",
                                pack_hex, hex_digest, e)

        # Plan: ranged runs for sparsely-needed packs, whole fetches
        # for mostly-needed ones (shared planner — the serve/peer
        # plane rides the same math). Runs then execute on a pool —
        # after a 1% edit of a 100k-file context there are ~a thousand
        # novel regions, and round-trip LATENCY, not bytes, dominates
        # them (measured: 2/3 of a warm pull was sequential ranged
        # GETs). A registry without pull_blob_range support can't do
        # ranged runs at all: force every pack whole.
        run_jobs, whole_jobs = plan_pack_runs(
            rows, {r[0] for r in rows},
            gap=self.PACK_RUN_GAP,
            whole_fraction=(-1.0 if self.registry is None
                            else self.PACK_WHOLE_FETCH_FRACTION),
            pack_sizes=pack_sizes)

        requests_issued: list[int] = []  # list.append is GIL-atomic
        if run_jobs:
            range_failed: set[str] = set()
            budget = transfer.engine().budget

            def fetch_pack_runs(job) -> None:
                # One task per PACK; its runs issue sequentially so a
                # "full" response (server ignored Range) or a failure
                # stops further requests against that pack — the
                # parallelism is across packs, where after a scattered
                # 1% edit the misses actually live.
                pack_hex, runs = job
                for run in runs:
                    start = run[0][0]
                    end = run[-1][0] + run[-1][1]
                    # A run's bytes materialize in memory until carved
                    # into the CAS; charge them against the global
                    # transfer budget.
                    with budget.reserve(end - start):
                        got_range = self.registry.pull_blob_range(
                            Digest.from_hex(pack_hex), start, end)
                        requests_issued.append(1)
                        if got_range is None:
                            range_failed.add(pack_hex)  # whole-pack later
                            return
                        kind, data = got_range
                        if kind == "partial":
                            carve(pack_hex, data, start, run)
                    if kind == "full":
                        # The server ignored Range and the WHOLE pack
                        # is in hand. Re-reserve at its true size —
                        # outside the run reservation, or a self-held
                        # budget could never be satisfied — so
                        # concurrent pack jobs against a Range-less
                        # registry throttle at their real footprint,
                        # then finish the pack here.
                        with budget.reserve(len(data)):
                            carve(pack_hex, data, 0,
                                  pack_spans[pack_hex])
                        return

            transfer.engine().map(fetch_pack_runs, run_jobs)
            whole_jobs.extend(sorted(range_failed))
        n_requests = len(requests_issued)

        for pack_hex in whole_jobs:
            if not self._fetch_remote(pack_hex):
                log.debug("pack %s unavailable; degrading %d chunks",
                          pack_hex, len(pack_spans[pack_hex]))
                continue
            n_requests += 1
            single = pack_member_counts[pack_hex] == 1
            try:
                with self.cas.open(pack_hex) as f:
                    carve(pack_hex, f.read(), 0, pack_spans[pack_hex])
            finally:
                # A single-member pack IS its chunk (same digest):
                # deleting it would delete the chunk just carved.
                if not single:
                    try:
                        self.cas.delete(pack_hex)
                    except OSError:
                        pass
        # Count requests even when every fetch failed — undercounting
        # during failure episodes is exactly when the metric matters.
        if n_requests:
            metrics.counter_add("makisu_chunk_fetch_requests_total",
                                n_requests)
        if got:
            metrics.counter_add("makisu_chunks_fetched_total", len(got),
                                route="pack")
            events.emit("chunk_fetch", route="pack", fetched=len(got),
                        requested=len(missing), requests=n_requests)
            log.info("fetched %d/%d missing chunks from %d pack(s) in "
                     "%d request(s)", len(got), len(missing),
                     len(pack_spans), n_requests)
        unmapped = [h for h in missing
                    if h not in got and h not in locate]
        mapped_failed = any(h in locate and h not in got
                            and not self.cas.exists(h)
                            for h in missing)
        return unmapped, mapped_failed

    def coverage(self, chunks: list[tuple[int, int, str]]) -> float:
        """Fraction of the layer's bytes already present as LOCAL
        chunks. Deliberately never consults the remote plane: has()
        falls through to a synchronous registry pull per miss, so a
        remote-checking probe over a 100k-chunk layer would issue 100k
        sequential HTTP round trips just to report a number."""
        total = sum(length for _, length, _ in chunks)
        if total == 0:
            return 1.0
        have = sum(length for _, length, hex_digest in chunks
                   if self.cas.exists(hex_digest))
        return have / total

    def reconstitute_to_path(self, pair: DigestPair,
                             chunks: list[tuple[int, int, str]],
                             gz_backend: str | None = None) -> str | None:
        """Rebuild a layer blob from chunks into a temp file; verify
        both digests. Returns the temp path (caller owns/unlinks it) or
        None if any chunk is missing or a digest mismatches.

        Streaming discipline matches index_layer: chunk bytes flow
        chunk-by-chunk through the deterministic gzip writer with both
        digests updated incrementally, so peak memory is bounded by the
        largest chunk — a 10GB layer (BASELINE config 4) never
        materializes in RAM."""
        if gz_backend is not None and not tario.backend_id_usable(
                gz_backend):
            # Byte-identity is unachievable without the producing
            # compressor; report "cannot reconstitute" so the caller
            # falls back to the blob transfer route instead of dying
            # inside gzip_writer (pull_cache normally filters these
            # hits up front — this guards entries registered by the
            # base blob route).
            log.warning("cannot reconstitute %s: gzip backend %r not "
                        "usable here", pair.gzip_descriptor.digest,
                        gz_backend)
            return None
        tar_digest = hashlib.sha256()
        pos = 0
        # Temp file lives beside the chunk CAS (not $TMPDIR, commonly
        # tmpfs): a 10GB layer must hit disk once, and the destination
        # CAS's link_file can usually hardlink instead of copying.
        fd, tmp = self.cas.mkstemp("reconstitute-")
        try:
            with os.fdopen(fd, "wb") as raw:
                tee = tario.TeeDigest(raw)
                gz = tario.gzip_writer(tee, backend_id=gz_backend)
                failed = False
                try:
                    for offset, length, hex_digest in chunks:
                        if offset != pos or not self.has(hex_digest):
                            if offset != pos:
                                log.warning("chunk list has a gap at %d "
                                            "(expected %d)", offset, pos)
                            failed = True
                            break
                        with self.cas.open(hex_digest) as f:
                            remaining = length
                            while remaining > 0:
                                piece = f.read(min(remaining, 1 << 20))
                                if not piece:
                                    log.warning(
                                        "chunk %s shorter than its "
                                        "recorded length", hex_digest)
                                    failed = True
                                    break
                                tar_digest.update(piece)
                                gz.write(piece)
                                remaining -= len(piece)
                        if failed:
                            break
                        pos = offset + length
                    if (not failed
                            and tar_digest.hexdigest()
                            != pair.tar_digest.hex()):
                        log.warning("reconstituted stream digest mismatch "
                                    "for %s", pair.tar_digest)
                        failed = True
                finally:
                    # Always close (trailer into a file we may delete is
                    # harmless; an unclosed compressor would try writing
                    # at gc time after raw is gone).
                    gz.close()
            if failed:
                return None
            if tee.digest.hexdigest() != pair.gzip_descriptor.digest.hex():
                # Different compression level/implementation produced the
                # original blob; the bytes are right but the registry
                # identity isn't. Refuse rather than corrupt the CAS.
                log.warning("reconstituted gzip digest mismatch for %s "
                            "(compression settings differ?)",
                            pair.gzip_descriptor.digest)
                return None
            keep, tmp = tmp, None
            return keep
        finally:
            if tmp is not None:
                os.unlink(tmp)

    def open_stream(self, chunks: list[tuple[int, int, str]]):
        """Readable file-like over the layer's UNCOMPRESSED tar stream,
        served chunk by chunk (local CAS, remote fetch per miss when a
        registry is attached). This is what makes a lazily-pulled
        cached layer appliable with ZERO gzip work: chunks are raw
        tar-stream slices, so applying a layer whose chunks are ~99%
        local moves ~1% of its bytes and inflates nothing.

        Memory is bounded by one 1MiB read; a gap, short chunk, or
        unfetchable chunk raises (the caller falls back to blob
        materialization)."""
        store = self

        class _ChunkStream:
            def __init__(self) -> None:
                self._chunks = list(chunks)
                self._idx = 0
                self._fh = None
                self._remaining = 0
                self._pos = 0
                self._pinned: str | None = None

            def _pin(self, hex_digest: str | None) -> None:
                # One pin held at a time, on the chunk currently being
                # read: an eviction pass cutting its victim list while
                # this stream walks a layer must not delete the chunk
                # under the open fd's NAME (the bytes would survive the
                # unlink, but a later reader of the same stream plan
                # would miss; the pin keeps plan and disk coherent).
                if self._pinned is not None:
                    store.pins.unpin("chunks", self._pinned)
                self._pinned = hex_digest
                if hex_digest is not None:
                    store.pins.pin("chunks", hex_digest)

            def _advance(self) -> bool:
                while self._idx < len(self._chunks):
                    offset, length, hex_digest = self._chunks[self._idx]
                    self._idx += 1
                    if offset != self._pos:
                        raise ValueError(
                            f"chunk list has a gap at {offset} "
                            f"(expected {self._pos})")
                    if length == 0:
                        continue
                    self._pin(hex_digest)
                    # Open directly; a local miss falls back to the
                    # remote probe. An 800MB layer is ~100k chunks, so
                    # this path runs ~100k times — the happy path must
                    # cost ONE syscall, not stat+open.
                    try:
                        self._fh = store.cas.open(hex_digest)
                    except FileNotFoundError:
                        if not store.has(hex_digest):
                            self._pin(None)
                            raise FileNotFoundError(
                                f"chunk {hex_digest} unavailable"
                            ) from None
                        self._fh = store.cas.open(hex_digest)
                    self._remaining = length
                    return True
                self._pin(None)
                return False

            def read(self, n: int = -1) -> bytes:
                out = []
                want = n if n >= 0 else None
                while want is None or want > 0:
                    if self._remaining == 0:
                        if self._fh is not None:
                            self._fh.close()
                            self._fh = None
                        if not self._advance():
                            break
                    step = self._remaining if want is None else min(
                        want, self._remaining)
                    piece = self._fh.read(min(step, 1 << 20))
                    if not piece:
                        raise ValueError("chunk shorter than its "
                                         "recorded length")
                    out.append(piece)
                    self._remaining -= len(piece)
                    self._pos += len(piece)
                    if want is not None:
                        want -= len(piece)
                return b"".join(out)

            def close(self) -> None:
                if self._fh is not None:
                    self._fh.close()
                    self._fh = None
                self._pin(None)

            def __enter__(self):
                return self

            def __exit__(self, *exc) -> None:
                self.close()

        return _ChunkStream()

    def reconstitute(self, pair: DigestPair,
                     chunks: list[tuple[int, int, str]],
                     gz_backend: str | None = None) -> bytes | None:
        """Bytes-returning convenience over reconstitute_to_path (tests
        and small layers; the cache pull path links the file instead)."""
        path = self.reconstitute_to_path(pair, chunks, gz_backend)
        if path is None:
            return None
        try:
            with open(path, "rb") as f:
                return f.read()
        finally:
            os.unlink(path)


def _record_index(layer_hex: str, cache_id: str,
                  triples: list[tuple[int, int, str]],
                  added: list[str]) -> int:
    """Per-layer dedup accounting after index_layer: how many of the
    layer's bytes were NOVEL (the re-chunked fraction an edit cost)
    vs already held — the `makisu-tpu explain` blame for commit-side
    work, plus aggregate counters and a per-layer dedup-ratio gauge so
    chunking efficiency is visible without a ledger. Returns the
    novel bytes."""
    bytes_total = sum(n for _, n, _ in triples)
    lengths: dict[str, int] = {}
    for _, n, h in triples:
        lengths.setdefault(h, n)
    bytes_added = sum(lengths[h] for h in set(added))
    bytes_reused = bytes_total - bytes_added
    metrics.counter_add("makisu_chunk_bytes_total", bytes_added,
                        result="added")
    metrics.counter_add("makisu_chunk_bytes_total", bytes_reused,
                        result="reused")
    ratio = bytes_reused / bytes_total if bytes_total else 0.0
    # Per-layer series only in the BUILD registry (bounded by the
    # build's layer count); the process-global registry gets one
    # unlabeled last-layer series — a long-lived worker must not grow
    # a permanent /metrics series per layer it ever committed (the
    # per-layer detail lives in each build's ledger + report).
    bound = metrics.active_registry()
    if bound is not metrics.global_registry():
        bound.gauge_set("makisu_chunk_dedup_ratio", ratio,
                        layer=layer_hex[:12])
    metrics.global_registry().gauge_set("makisu_chunk_dedup_ratio",
                                        ratio)
    ledger.record("chunk_index", layer_hex, "indexed",
                  cache_id=cache_id, chunks=len(triples),
                  added=len(added), bytes_total=bytes_total,
                  bytes_added=bytes_added, bytes_reused=bytes_reused)
    return bytes_added


def attach_chunk_dedup(manager, chunk_root: str) -> ChunkStore:
    """Wire a ChunkStore into a CacheManager: index chunks on push,
    reconstitute layers on pull when the blob is missing locally. If the
    manager has a registry client, chunks also distribute through the
    registry blob plane."""
    chunk_store = ChunkStore(chunk_root)
    # Peer-exchange serving side: this store's chunks become fetchable
    # by fleet siblings through the worker's GET /chunks/<fp>.
    register_serving_store(chunk_store)
    if getattr(manager, "registry", None) is not None:
        chunk_store.set_remote(manager.registry)
    inner_push = manager.push_cache
    inner_pull = manager.pull_cache

    def push_cache(cache_id, pair, commit=None):
        inner_push(cache_id, pair, commit)
        if pair is not None and commit is not None and commit.chunks:
            try:
                layer_hex = pair.gzip_descriptor.digest.hex()
                path = manager.store.layers.path(layer_hex)
                triples = [(c.offset, c.length, c.hex_digest)
                           for c in commit.chunks]
                publication = _begin_recipe_publish(
                    pair, triples, commit, cache_id)
                try:
                    with metrics.span("chunk_index",
                                      chunks=len(triples)) as sp:
                        stats: dict = {}
                        added = chunk_store.index_layer(
                            path, triples, stats,
                            observer=publication and publication.feed)
                        metrics.counter_add("makisu_chunks_indexed_total",
                                            len(added))
                        sp.set(added=len(added),
                               bytes_added=_record_index(
                                   layer_hex, cache_id, triples, added),
                               ingest_window=stats["ingest_window"])
                except BaseException as e:
                    _end_recipe_publish(publication, failure=e)
                    raise
                log.info("indexed %d new chunks for %s", len(added),
                         cache_id)
                _end_recipe_publish(publication)
            except FileNotFoundError:
                return
            finally:
                # The streamed memo served exactly this commit→index
                # window; a True must not survive into the next
                # layer's window (CAS eviction in between would make
                # index_layer skip a chunk it no longer holds).
                chunk_store.reset_fingerprint_memo()
            if chunk_store.registry is not None:
                # Off the build thread, like layer pushes: upload the
                # chunks this layer introduced, then pin the layer's
                # full chunk set with a manifest (GC safety).
                layer_hex = pair.gzip_descriptor.digest.hex()

                def push_chunks(added=added, triples=triples,
                                layer_hex=layer_hex,
                                cache_id=cache_id):
                    if packs_enabled() and added:
                        if _push_as_packs(added, triples, layer_hex,
                                          cache_id):
                            return
                        log.warning("pack push for %s failed; falling "
                                    "back to per-chunk blobs", cache_id)
                    # Per-chunk route (packs disabled or failed): one
                    # blob per chunk, uploaded via the shared transfer
                    # engine since per-blob round trips, not bytes,
                    # dominate.
                    failed = []

                    def push_one(hex_digest):
                        try:
                            chunk_store.push_remote(hex_digest)
                        except Exception as e:  # noqa: BLE001
                            failed.append((hex_digest, e))

                    transfer.engine().map(push_one, added)
                    if failed:
                        log.warning("chunk push failed for %d/%d "
                                    "chunks (first: %s: %s)",
                                    len(failed), len(added),
                                    failed[0][0], failed[0][1])
                        return
                    try:
                        chunk_store.pin_remote(layer_hex, triples)
                    except Exception as e:  # noqa: BLE001
                        log.warning("chunk pin for %s failed: %s",
                                    layer_hex, e)

                def _push_as_packs(added, triples, layer_hex,
                                   cache_id) -> bool:
                    """Wire form: pack blobs (one PUT per ~8MB instead
                    of per ~8KiB chunk), pinned for GC, with the
                    chunk->pack mapping recorded back onto the cache
                    entry so consumers fetch packs, not chunks."""
                    packs = []
                    try:
                        packs = chunk_store.build_packs(triples, added)
                        chunk_store.push_packs(packs)
                        chunk_store.pin_packs(layer_hex, packs)
                        manager.set_entry_packs(
                            cache_id,
                            [[pack_hex, members]
                             for pack_hex, members in packs])
                        log.info("pushed %d chunks as %d pack blob(s) "
                                 "for %s", len(added), len(packs),
                                 cache_id)
                        return True
                    except Exception as e:  # noqa: BLE001
                        log.debug("pack push failed: %s", e)
                        return False
                    finally:
                        chunk_store.drop_local_packs(packs)
                import contextvars
                import threading
                # Carry the caller's context so worker-mode log sinks
                # attribute pin/push failures to the right build.
                t = threading.Thread(
                    target=contextvars.copy_context().run,
                    args=(push_chunks,), daemon=True,
                    name=f"chunkpush-{cache_id}")
                t.start()
                with manager._lock:
                    manager._pushes.append(t)

    def pull_cache(cache_id):
        """Chunk-aware pull: the chunk route is tried FIRST — after a
        1% edit, its transfer cost is the novel fraction of the layer,
        not the whole blob — then the base manager's blob route. Like
        the base route, materializability is settled here: missing
        chunks fetch now AND the recorded gzip identity must be
        replayable in this process, so an accepted hit can always be
        applied and (if an upload or export later demands it)
        reconstituted byte-identically. An entry whose compression
        backend we lack falls through to the blob route, whose HEAD
        check degrades an unmaterializable hit to a miss at pull time —
        never to a failed build after execution was already skipped."""
        from makisu_tpu.cache.manager import get_entry
        raw, pair, chunks, gz_backend, packs = get_entry(
            manager, cache_id)
        if pair is None:
            metrics.counter_add("makisu_cache_pull_total", result="empty")
            events.emit("cache", result="empty", cache_id=cache_id)
            ledger.record("kv", cache_id, "empty")
            return None
        hex_digest = pair.gzip_descriptor.digest.hex()
        if not manager.store.layers.exists(hex_digest) and chunks:
            if not tario.backend_id_usable(gz_backend):
                log.info("cache hit %s: gzip backend %r not replayable "
                         "here; trying the blob route", cache_id,
                         gz_backend)
                ledger.record("chunk_cas", hex_digest, "stale",
                              reason="gz_backend")
            elif chunk_store.ensure_available(
                    [tuple(c) for c in chunks], packs,
                    ledger_key=hex_digest):
                with manager._lock:
                    manager._lazy[hex_digest] = raw
                metrics.counter_add("makisu_cache_pull_total",
                                    result="hit")
                metrics.counter_add("makisu_cache_chunk_route_hits_total")
                events.emit("cache", result="hit", cache_id=cache_id,
                            layer=hex_digest, route="chunks",
                            chunks=len(chunks))
                ledger.record("kv", cache_id, "hit", layer=hex_digest,
                              route="chunks",
                              bytes_saved=pair.gzip_descriptor.size)
                log.info("cache hit %s -> %s (lazy: %d chunks "
                         "available)", cache_id, hex_digest, len(chunks))
                if not manager.lazy_enabled():
                    # Kill switch (MAKISU_TPU_LAZY_CACHE=0) applies to
                    # the chunk route too: reconstitute the blob now so
                    # disabling lazy pulls restores eager materialization
                    # everywhere, as manager.py documents.
                    manager.materialize(hex_digest)
                return pair
            else:
                log.info("cache hit %s: chunks incomplete; trying the "
                         "blob route", cache_id)
        # The blob route re-reads the entry; seed the build-local memory
        # tier so the fall-through costs no second KV round trip.
        with manager._lock:
            manager._mem.setdefault(cache_id, raw)
        return inner_pull(cache_id)

    # -- lazy materialization routes --------------------------------------

    def _lazy_entry(hex_digest):
        from makisu_tpu.cache.manager import decode_entry_full
        with manager._lock:
            raw = manager._lazy.get(hex_digest)
        if raw is None:
            return None, None, None, None
        return decode_entry_full(raw)

    inner_materialize = manager.materialize

    def materialize(hex_digest):
        """Chunk reconstitution first (bytes mostly local, gzip rebuilt
        deterministically), registry blob transfer second."""
        if manager.store.layers.exists(hex_digest):
            return manager.store.layers.path(hex_digest)
        pair, chunks, gz_backend, _packs = _lazy_entry(hex_digest)
        if pair is not None and chunks:
            path = chunk_store.reconstitute_to_path(
                pair, [tuple(c) for c in chunks], gz_backend=gz_backend)
            if path is not None:
                try:
                    manager.store.layers.link_file(hex_digest, path)
                finally:
                    os.unlink(path)
                with manager._lock:
                    manager._lazy.pop(hex_digest, None)
                log.info("reconstituted layer %s from %d cached chunks",
                         hex_digest, len(chunks))
                return manager.store.layers.path(hex_digest)
        return inner_materialize(hex_digest)

    inner_open_tar = manager.open_layer_tar

    def open_layer_tar(pair):
        """Serve the uncompressed tar straight from chunks when the
        blob is not local: zero gzip work, ~1% wire traffic after a 1%
        edit. Falls back to blob materialization + inflate.

        Availability is settled BEFORE the stream opens (missing chunks
        prefetch here): layer application mutates MemFS as it reads, so
        a mid-stream fetch failure would not be recoverable — the
        stream must be a sure thing by the time the caller sees it."""
        import contextlib

        hex_digest = pair.gzip_descriptor.digest.hex()
        if not manager.store.layers.exists(hex_digest):
            _, chunks, _, packs = _lazy_entry(hex_digest)
            if chunks:
                triples = [tuple(c) for c in chunks]
                if chunk_store.ensure_available(triples, packs,
                                                ledger_key=hex_digest):

                    @contextlib.contextmanager
                    def _chunk_tar():
                        log.info("applying layer %s from %d chunks "
                                 "(no blob, no gzip)", hex_digest,
                                 len(triples))
                        with chunk_store.open_stream(triples) as stream:
                            yield stream

                    return _chunk_tar()
                log.info("layer %s chunks incomplete locally/remotely; "
                         "falling back to blob materialization",
                         hex_digest)
        return inner_open_tar(pair)

    def _begin_recipe_publish(pair, triples, commit, cache_id):
        """Distribution-plane publish hook: when this process serves
        (worker / `makisu-tpu serve` / MAKISU_TPU_SERVE=1), every
        indexed layer also gets a signed recipe + pack member tables
        in ``<storage>/serve/`` — the metadata delta pulls and
        pack-granular peer exchange consume. The publication is opened
        before ``index_layer`` and fed from its pass: a cold layer's
        new chunks enter their packs as the pass slices them, and a
        filled pack is hashed, framed and written on the
        ``recipepub-*`` thread while the pass goes on. None where
        publishing is off. Never fails the build; an unpublished layer
        just stays blob-route-only."""
        from makisu_tpu.serve import server as serve_server
        if not serve_server.publish_enabled():
            return None
        try:
            return serve_server.register_store(manager.store.root).begin(
                pair, triples, commit.gzip_backend_id, chunk_store,
                thread_name=f"recipepub-{cache_id}")
        except Exception as e:  # noqa: BLE001 - publish is advisory
            log.warning("serve recipe publish failed for %s: %s",
                        pair.gzip_descriptor.digest.hex(), e)
            return None

    def _end_recipe_publish(publication, failure=None) -> None:
        """After the pass: hand the finish (last partial pack, tables,
        rows, seal, recipe file) to the publication's thread, or
        abandon it where the pass failed. The thread is joined by
        ``wait_for_push`` (build exit still implies published; a client
        asking earlier just takes the blob route)."""
        if publication is None:
            return
        if failure is None:
            publication.finish()
        else:
            publication.abandon(failure)
        if publication.thread is not None:
            with manager._lock:
                manager._pushes.append(publication.thread)

    manager.push_cache = push_cache
    manager.pull_cache = pull_cache
    manager.materialize = materialize
    manager.open_layer_tar = open_layer_tar
    manager.chunk_store = chunk_store
    from makisu_tpu.utils import concurrency
    if concurrency.hash_workers() > 1:
        # Stream dedup lookups: the commit pipeline reports each chunk
        # fingerprint as it is hashed (context-scoped — concurrent
        # worker builds observe only their own chunks), so the
        # per-chunk CAS stats index_layer needs have already run on
        # the pool by the time push_cache re-reads the layer.
        from makisu_tpu.chunker import cdc
        cdc.set_chunk_observer(chunk_store.note_fingerprint)
    return chunk_store
