"""Worker server: accept build requests over a unix socket.

Protocol (reference: lib/client/client.go):
- GET  /ready  → 200 when accepting builds
- POST /build  → body is a JSON argv list for the build command (or
  ``{"argv": [...], "tenant": "..."}``; the ``X-Makisu-Tenant`` header
  also names the tenant); the response streams newline-delimited JSON
  frames — log lines, build events (``{"event": {...}}``), and the
  terminal ``{"build_code": "<exit code>", ...}``
- GET  /metrics → Prometheus text of the process-global registry
- GET  /healthz → uptime + builds started/succeeded/failed/active +
  the admission queue's depth and wait/latency percentiles
- GET  /builds → in-flight + recently finished builds as JSON (trace
  id, tenant, phase, queue wait, progress age, cache economics)
- GET  /exit   → 200, then the server shuts down

Admission: ``--max-concurrent-builds N`` caps concurrently EXECUTING
builds; arrivals beyond the cap wait in an explicit FIFO queue in
front of build execution. The queue is instrumented (depth gauge,
wait/latency histograms with per-tenant labels) — the signals a fleet
scheduler needs before it can route by cache affinity or enforce
fairness (ROADMAP item 1).
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import io
import json
import os
import socket
import socketserver
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler

from makisu_tpu.utils import pathutils

# Prometheus text exposition content type (format 0.0.4).
_METRICS_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

# Histogram buckets for queue wait / build latency: builds span four
# orders of magnitude (sub-second scratch builds to multi-minute
# 100k-file trees), so the default millisecond ladder is too fine.
_LATENCY_BUCKETS = (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
                    120.0, 300.0, 600.0, 1800.0)

# Finished-build ring size for GET /builds "recent".
_RECENT_BUILDS_KEEP = 32

# Cap on distinct tenant label values in the latency rings and the
# process registry's histograms. The tenant string is CLIENT-supplied
# (X-Makisu-Tenant); without a cap, a buggy client stamping unique
# strings would grow per-tenant rings, /metrics series, and the
# /healthz payload without bound in a long-lived worker (the same
# cardinality discipline makisu_chunk_dedup_ratio applies). Tenants
# past the cap aggregate under "other".
_TENANT_LABELS_KEEP = 32
_TENANT_OVERFLOW = "other"

# Storage observability knobs. Census TTL bounds how often a /healthz
# poll may trigger a fresh walk; the scrub interval paces the
# background integrity cycle (0 disables it — tests drive scrubs
# directly). Scrub corruption findings kept for /healthz//storage.
_SCRUB_FINDINGS_KEEP = 64


def _census_ttl_seconds() -> float:
    try:
        return float(os.environ.get(
            "MAKISU_TPU_CENSUS_TTL_SECONDS", "60"))
    except ValueError:
        return 60.0


def _scrub_interval_seconds() -> float:
    try:
        return float(os.environ.get(
            "MAKISU_TPU_STORAGE_SCRUB_SECONDS", "300"))
    except ValueError:
        return 300.0


class _QuantileRing:
    """Bounded ring of raw observations with exact percentile export.
    The Prometheus histograms cover scrape-time quantiles; this ring is
    what ``/healthz`` and ``/builds`` serve — exact p50/p90/p99 over
    the last N builds, no bucket interpolation error."""

    def __init__(self, cap: int = 512) -> None:
        self._vals: collections.deque[float] = collections.deque(
            maxlen=cap)
        self._mu = threading.Lock()

    def add(self, value: float) -> None:
        with self._mu:
            self._vals.append(value)

    def stats(self) -> dict:
        from makisu_tpu.utils import metrics
        with self._mu:
            vals = list(self._vals)
        return metrics.percentile_stats(vals)


class _AdmissionQueue:
    """FIFO admission in front of build execution. ``limit <= 0``
    means unlimited (acquire never blocks). Slots transfer directly to
    the oldest waiter on release, so admission order is strictly
    arrival order — a fairness property a semaphore does not give."""

    def __init__(self, limit: int) -> None:
        self.limit = limit
        self._mu = threading.Lock()
        self._waiters: collections.deque[threading.Event] = \
            collections.deque()
        self._running = 0

    def _publish_depth(self) -> None:
        # Global registry explicitly: admission runs on handler threads
        # before/after any per-build registry is bound, and the gauge
        # is a process-level vital sign either way.
        from makisu_tpu.utils import metrics
        metrics.global_registry().gauge_set(
            "makisu_worker_queue_depth", len(self._waiters))

    def acquire(self) -> float:
        """Block until a slot frees (FIFO); returns seconds waited."""
        if self.limit <= 0:
            return 0.0
        t0 = time.monotonic()
        with self._mu:
            if self._running < self.limit and not self._waiters:
                self._running += 1
                return 0.0
            gate = threading.Event()
            self._waiters.append(gate)
            self._publish_depth()
        gate.wait()
        return time.monotonic() - t0

    def release(self) -> None:
        if self.limit <= 0:
            return
        with self._mu:
            if self._waiters:
                # The slot transfers: _running stays constant.
                self._waiters.popleft().set()
                self._publish_depth()
            else:
                self._running -= 1

    def depth(self) -> int:
        with self._mu:
            return len(self._waiters)

    def would_block(self) -> bool:
        """Whether an acquire right now would wait (the fleet front
        door's no-wait admission probe — advisory: the answer can go
        stale by the time the build actually acquires, in which case
        it simply queues like any other arrival)."""
        if self.limit <= 0:
            return False
        with self._mu:
            return self._running >= self.limit or bool(self._waiters)


class _BuildRecord:
    """One build's row in ``GET /builds``: identity, queue state, and
    a live telemetry digest fed by the build's own event stream (an
    extra event sink — trace id from ``build_start``, phase from
    ``span_start``, progress age from any event, cache economics
    accumulated from ``cache_decision`` events via the PR 6 ledger
    summary)."""

    def __init__(self, seq: int, tenant: str, argv: list[str]) -> None:
        from makisu_tpu.utils import ledger
        self.seq = seq
        self.tenant = tenant
        self.command = next(
            (a for a in argv if not a.startswith("-")), "")
        self.tag = self._tag_of(argv)
        self.state = "queued"
        self.trace_id = ""
        self.phase = ""
        self.exit_code: int | None = None
        self.queue_wait_seconds = 0.0
        self.enqueued_mono = time.monotonic()
        self.started_mono: float | None = None
        self.finished_mono: float | None = None
        # The request's own seconds (note_service): service is
        # admission to the end of run_build's finally; set-up is the
        # part of it before the command's root span opened, tear-down
        # the part after it closed. Tear-down runs after the admission
        # slot is handed on (_admission.release() precedes
        # _retire_build, the census and maybe_evict()): the client
        # waits for it, the slot does not.
        self._root_open_mono: float | None = None
        self._root_close_mono: float | None = None
        self.service: dict[str, float] = {}
        self._last_event_mono = self.enqueued_mono
        self._mu = threading.Lock()
        self._ledger = ledger.LedgerSummary()
        # Layer hexes this build's cache decisions named (chunk_cas /
        # chunk_index keys, kv hits' layer field): the join rows the
        # storage census's per-tenant attribution consumes.
        self._layer_hexes: set[str] = set()

    @staticmethod
    def _tag_of(argv: list[str]) -> str:
        for i, arg in enumerate(argv):
            if arg in ("-t", "--tag") and i + 1 < len(argv):
                return argv[i + 1]
            if arg.startswith("--tag="):
                return arg.split("=", 1)[1]
        return ""

    def note_event(self, event: dict) -> None:
        """Event-bus sink: cheap field updates under a record lock
        (the build's own threads emit concurrently)."""
        from makisu_tpu.utils import ledger as ledger_mod
        from makisu_tpu.utils import traceexport
        etype = event.get("type")
        with self._mu:
            self._last_event_mono = time.monotonic()
            if etype == "build_start":
                # cli.main emits it as the root span opens, and
                # build_end as it has closed.
                self.trace_id = event.get("trace_id", "")
                self._root_open_mono = self._last_event_mono
            elif etype == "build_end":
                self._root_close_mono = self._last_event_mono
            elif etype == "span_start":
                phase = traceexport.phase_of(event.get("name", ""))
                if phase != "other":
                    self.phase = phase
            elif etype == ledger_mod.EVENT_TYPE:
                self._ledger.add(event)
                for value in (event.get("key"), event.get("layer")):
                    value = str(value or "")
                    if len(value) == 64 and all(
                            c in "0123456789abcdef" for c in value):
                        self._layer_hexes.add(value)

    def layer_hexes(self) -> set[str]:
        with self._mu:
            return set(self._layer_hexes)

    def start_running(self, queue_wait: float) -> None:
        with self._mu:
            self.state = "running"
            self.queue_wait_seconds = queue_wait
            self.started_mono = time.monotonic()
            self._last_event_mono = self.started_mono

    def finish(self, exit_code: int) -> None:
        with self._mu:
            self.state = "finished"
            self.exit_code = exit_code
            self.finished_mono = time.monotonic()

    def note_service(self, admitted: float, done: float,
                     thread_cpu: float) -> None:
        """The split of ``done - admitted``; a request whose command
        never opened a root span was set-up all through.
        ``thread_cpu`` is the building thread's own CPU seconds over
        the same interval."""
        with self._mu:
            opened = self._root_open_mono or done
            closed = self._root_close_mono or done
            self.service = {
                "setup_seconds": round(opened - admitted, 6),
                "teardown_seconds": round(done - closed, 6),
                "service_seconds": round(done - admitted, 6),
                "thread_cpu_seconds": round(thread_cpu, 6)}

    def latency_seconds(self) -> float:
        """Queue wait + execution: arrival to completion."""
        end = self.finished_mono or time.monotonic()
        return end - self.enqueued_mono

    def to_dict(self) -> dict:
        now = time.monotonic()
        with self._mu:
            kv = self._ledger.by_source.get("kv", {})
            hits = kv.get("hit", 0)
            consults = sum(kv.values())
            out = {
                "id": self.seq,
                "tenant": self.tenant,
                "state": self.state,
                "command": self.command,
                "tag": self.tag,
                "trace_id": self.trace_id,
                "phase": self.phase,
                "queue_wait_seconds": round(
                    self.queue_wait_seconds
                    if self.started_mono is not None
                    else now - self.enqueued_mono, 3),
                "age_seconds": round(
                    (self.finished_mono or now) - self.enqueued_mono,
                    3),
                # Seconds since the build's own event stream last moved
                # — the per-build progress clock a fleet `top` watches
                # for wedged builds.
                "progress_age_seconds": round(
                    (self.finished_mono or now)
                    - self._last_event_mono, 3),
                "cache": {
                    "kv_hits": hits,
                    "kv_consults": consults,
                    "kv_hit_ratio": round(hits / consults, 4)
                    if consults else 0.0,
                    "bytes_added": self._ledger.bytes_added,
                    "bytes_reused": self._ledger.bytes_reused,
                    "dedup_ratio": round(
                        self._ledger.dedup_ratio(), 4),
                },
            }
            if self.exit_code is not None:
                out["exit_code"] = self.exit_code
            if self.finished_mono is not None \
                    and self.started_mono is not None:
                out["elapsed_seconds"] = round(
                    self.finished_mono - self.started_mono, 3)
            out.update(self.service)
            return out


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *args) -> None:  # quiet
        pass

    def do_GET(self) -> None:
        if self.path == "/ready":
            self._respond(200, b"ok")
        elif self.path == "/metrics":
            # Process-wide totals across every build this worker has
            # served — what a scraper wants. Per-build breakdowns come
            # from each build's own --metrics-out report.
            from makisu_tpu.utils import metrics
            self._respond(200, metrics.render_prometheus().encode(),
                          content_type=_METRICS_CONTENT_TYPE)
        elif self.path == "/healthz":
            # Liveness + vital signs as JSON: what a k8s probe or a
            # dashboard polls without parsing Prometheus text.
            self._respond(200,
                          json.dumps(self.server.health()).encode(),
                          content_type="application/json")
        elif self.path == "/builds":
            # The operator's (and `makisu-tpu top`'s) live view:
            # every in-flight build plus the recently finished ring.
            self._respond(200,
                          json.dumps(self.server.builds()).encode(),
                          content_type="application/json")
        elif self.path == "/sessions":
            # Resident build sessions: per-context warm state (builds
            # served, hits, resident bytes, dirty-tracker mode) plus
            # the manager's invalidation tallies. THIS server's manager
            # — the fleet scheduler polls it as the affinity signal, so
            # it must describe this worker's residency, not (in an
            # in-process fleet) a sibling's.
            self._respond(
                200,
                json.dumps(self.server.session_mgr.stats()).encode(),
                content_type="application/json")
        elif self.path.startswith("/sessions/snapshot"):
            # Session-snapshot recipe for one context: the chunk-plan
            # document the fleet prewarm path pulls from a source
            # worker and pushes at the routed-to target. Recipes live
            # on this worker's registered storage dirs; the chunks
            # they name are served by the /chunks endpoint above —
            # the snapshot plane rides the existing peer wire.
            from urllib.parse import parse_qs, urlsplit
            query = parse_qs(urlsplit(self.path).query)
            context = (query.get("context") or [""])[0]
            if not context:
                self._respond(400, b"context query param required")
                return
            recipe = self.server.find_session_snapshot(context)
            if recipe is None:
                self._respond(404, b"no snapshot for context")
                return
            self._respond(200, json.dumps(recipe).encode(),
                          content_type="application/json")
        elif self.path.startswith("/chunks/"):
            # Peer chunk exchange, serving side: read-only chunk bytes
            # out of the local chunk CAS(es). Strictly local — a miss
            # is a prompt 404, never a proxied fetch (see
            # cache/chunks.py open_served_chunk). Kept as the
            # compatibility fallback; pack-granular peers prefer
            # /recipes + /packs below.
            self._serve_chunk(self.path[len("/chunks/"):])
        elif self.path.startswith("/recipes/"):
            # Distribution plane, embedded: signed layer recipes for
            # the layers THIS worker's builds published (same
            # per-server honesty scoping as /chunks).
            from makisu_tpu.serve import server as serve_server
            serve_server.handle_recipe(
                self, self.path[len("/recipes/"):],
                roots=self.server.served_chunk_roots(),
                access=self.server.serve_access)
        elif self.path.startswith("/packs/"):
            # Ranged pack serving: spans synthesized from the chunk
            # CAS, streamed under the transfer memory budget.
            from makisu_tpu.serve import server as serve_server
            serve_server.handle_pack(
                self, self.path[len("/packs/"):],
                roots=self.server.served_chunk_roots(),
                access=self.server.serve_access)
        elif self.path.startswith("/zpacks/"):
            # Seekable twin: ranged COMPRESSED frames of the same
            # packs (404 routes frame-less packs to /packs).
            from makisu_tpu.serve import server as serve_server
            serve_server.handle_zpack(
                self, self.path[len("/zpacks/"):],
                roots=self.server.served_chunk_roots(),
                access=self.server.serve_access)
        elif self.path == "/storage" or self.path.startswith("/storage?"):
            # Storage observability plane: fresh census + reference
            # audit per storage dir (plus the latest scrub cycle), and
            # — when asked with ?eviction_budget=BYTES — the eviction
            # dry-run report real eviction will consume. /healthz
            # carries the cheap cached digest; this endpoint is the
            # full document `doctor --storage SOCKET` renders.
            from urllib.parse import parse_qs, urlsplit
            query = parse_qs(urlsplit(self.path).query)
            budget = None
            raw = (query.get("eviction_budget") or [None])[0]
            if raw is not None:
                try:
                    budget = int(raw)
                except ValueError:
                    self._respond(400, b"bad eviction_budget")
                    return
            self._respond(
                200,
                json.dumps(self.server.storage_report(
                    eviction_budget=budget), default=str).encode(),
                content_type="application/json")
        elif self.path == "/serve/access":
            # This worker's serve access ledger: every peer/delta
            # fetch it answered, stamped with the requesting build's
            # trace id — the server-side half of a stitched fleet
            # trace.
            self._respond(200, json.dumps({
                "entries": self.server.serve_access.snapshot(),
            }).encode(), content_type="application/json")
        elif self.path == "/peers":
            from makisu_tpu.fleet import peers as fleet_peers
            self._respond(200, json.dumps({
                "version": fleet_peers.map_version(),
                "peers": list(fleet_peers.peers()),
            }).encode(), content_type="application/json")
        elif self.path == "/alerts":
            # SLO plane: active + recently-resolved alerts from this
            # worker's rule evaluator (fleet/slo.py) — what doctor,
            # top, and `makisu-tpu alerts` render.
            self._respond(200,
                          json.dumps(self.server.alerts()).encode(),
                          content_type="application/json")
        elif self.path == "/profile" or self.path.startswith("/profile?"):
            # On-demand profile capture: sample for ?seconds=N and
            # answer with the makisu-tpu.profile.v1 window — what the
            # worker did DURING the window, not since boot. Blocks
            # this handler thread only; sampling (and every other
            # endpoint) continues underneath.
            from urllib.parse import parse_qs, urlsplit
            query = parse_qs(urlsplit(self.path).query)
            try:
                seconds = float((query.get("seconds") or ["5"])[0])
            except ValueError:
                self._respond(400, b"bad seconds")
                return
            self._respond(
                200,
                json.dumps(self.server.profile(seconds)).encode(),
                content_type="application/json")
        elif self.path == "/exit":
            # Shut down regardless of whether the response write lands
            # (clients may hang up as soon as the status line arrives).
            threading.Thread(target=self.server.shutdown,
                             daemon=True).start()
            self._respond(200, b"bye")
        else:
            self._respond(404, b"not found")

    def _serve_chunk(self, name: str) -> None:
        """``GET /chunks/<fingerprint>``: stream one chunk's bytes.
        The name is validated as a full lowercase-hex sha256 BEFORE it
        touches any path machinery — this endpoint fronts a CAS whose
        keys become file paths."""
        from makisu_tpu.cache import chunks as chunks_mod
        from makisu_tpu.serve import server as serve_server
        from makisu_tpu.utils import metrics
        if len(name) != 64 or any(c not in "0123456789abcdef"
                                  for c in name):
            self._respond(400, b"bad chunk fingerprint")
            return
        access = self.server.serve_access
        fh = chunks_mod.open_served_chunk(
            name, roots=self.server.served_chunk_roots())
        if fh is None:
            metrics.global_registry().counter_add(
                metrics.FLEET_CHUNK_SERVES, result="miss")
            access.record("chunk", name, 404, 0,
                          serve_server.inbound_trace_id(self))
            self._respond(404, b"chunk not held here")
            return
        try:
            with fh:
                data = fh.read()
            metrics.global_registry().counter_add(
                metrics.FLEET_CHUNK_SERVES, result="hit")
            metrics.global_registry().counter_add(
                metrics.FLEET_CHUNK_SERVE_BYTES, len(data))
            access.record("chunk", name, 200, len(data),
                          serve_server.inbound_trace_id(self))
            self._respond(200, data,
                          content_type="application/octet-stream")
        except OSError:
            # Evicted between open and read: a miss, not an error.
            self._respond(404, b"chunk not held here")

    def do_POST(self) -> None:
        if self.path == "/peers":
            # The fleet scheduler publishes the peer map here; builds
            # on this worker consult those sockets for missing chunks
            # before paying the registry (cache/chunks.py).
            from makisu_tpu.fleet import peers as fleet_peers
            length = int(self.headers.get("Content-Length", "0"))
            try:
                body = json.loads(self.rfile.read(length)) or {}
                peer_list = list(body.get("peers") or [])
                version = body.get("version")
                version = int(version) if version is not None else None
            except (ValueError, TypeError, AttributeError):
                self._respond(400, b"bad peers json")
                return
            applied = fleet_peers.set_peers(peer_list, version)
            self._respond(200, json.dumps(
                {"applied": applied,
                 "version": fleet_peers.map_version()}).encode(),
                content_type="application/json")
            return
        if self.path == "/sessions/invalidate":
            # Explicit session invalidation: body ``{"context": PATH}``
            # drops that context's session, ``{}`` (or no body) drops
            # every idle session. Busy sessions survive (their build
            # owns them); the response reports the dropped count.
            length = int(self.headers.get("Content-Length", "0"))
            context = ""
            if length:
                try:
                    body = json.loads(self.rfile.read(length))
                    context = str((body or {}).get("context", ""))
                except (ValueError, AttributeError):
                    self._respond(400, b"bad json body")
                    return
            dropped = self.server.session_mgr.invalidate(context)
            self._respond(200, json.dumps(
                {"invalidated": dropped}).encode(),
                content_type="application/json")
            return
        if self.path == "/sessions/snapshot":
            # Checkpoint resident sessions into the chunk-addressed
            # snapshot plane NOW: body ``{"context": PATH}`` snapshots
            # that context's session, ``{}`` every idle session. The
            # drain path calls this so a worker leaving the fleet
            # leaves its warmth behind in the CAS.
            length = int(self.headers.get("Content-Length", "0"))
            context = ""
            if length:
                try:
                    body = json.loads(self.rfile.read(length))
                    context = str((body or {}).get("context", ""))
                except (ValueError, AttributeError):
                    self._respond(400, b"bad json body")
                    return
            count = self.server.session_mgr.snapshot_all(context)
            self._respond(200, json.dumps(
                {"snapshotted": count}).encode(),
                content_type="application/json")
            return
        if self.path == "/sessions/restore":
            # Stage a session snapshot on THIS worker so the next
            # build on the context restores warm: ``{"recipe": {...}}``
            # (the prewarm push — chunks fetched over the peer wire
            # before the recipe lands, an optional ``"storage"`` names
            # the target storage dir) or ``{"context": PATH}`` (re-
            # validate a recipe already on this worker's storage).
            # Refusals are data (``{"ok": false, "reason"}``), not
            # HTTP errors: prewarm is best-effort by design.
            length = int(self.headers.get("Content-Length", "0"))
            try:
                body = json.loads(self.rfile.read(length)) or {}
                if not isinstance(body, dict):
                    raise ValueError("body must be an object")
            except (ValueError, AttributeError):
                self._respond(400, b"bad json body")
                return
            ok, reason = self.server.stage_session_snapshot(body)
            self._respond(200, json.dumps(
                {"ok": ok, "reason": reason}).encode(),
                content_type="application/json")
            return
        if self.path != "/build":
            self._respond(404, b"not found")
            return
        length = int(self.headers.get("Content-Length", "0"))
        try:
            body = json.loads(self.rfile.read(length))
        except ValueError:
            self._respond(400, b"bad argv json")
            return
        # Two body shapes: the legacy bare argv list, and the object
        # form ``{"argv": [...], "tenant": "..."}``. The header wins
        # when both name a tenant (proxies inject headers; bodies come
        # from the original submitter).
        tenant = ""
        traceparent = ""
        fleet_info = None
        if isinstance(body, dict):
            argv = body.get("argv") or []
            tenant = str(body.get("tenant") or "")
            traceparent = str(body.get("traceparent") or "")
            if isinstance(body.get("fleet"), dict):
                fleet_info = body["fleet"]
        else:
            argv = body
        tenant = self.headers.get("X-Makisu-Tenant") or tenant
        # Header wins over the body field (same precedence as the
        # tenant): proxies inject headers, bodies come from the
        # original submitter. Validation happens at adoption time —
        # a malformed value mints fresh ids, never a 400.
        traceparent = self.headers.get("traceparent") or traceparent
        if not isinstance(argv, list) or not all(
                isinstance(a, str) for a in argv):
            self._respond(400, b"bad argv json")
            return
        # Cooperative admission refusal: a fleet scheduler with other
        # candidate workers sends X-Makisu-No-Wait so a saturated
        # worker answers 503 NOW instead of silently queuing the build
        # behind its cap — the scheduler then fails over to the
        # next-best worker. Advisory (the real acquire happens in
        # run_build): a lost race just queues, exactly as if the
        # header had not been sent.
        if (self.headers.get("X-Makisu-No-Wait")
                and self.server._admission.would_block()):
            self._respond(503, json.dumps({
                "error": "admission_refused",
                "queue_depth": self.server._admission.depth(),
                "max_concurrent_builds":
                    self.server.max_concurrent_builds,
            }).encode(), content_type="application/json")
            return
        self.send_response(200)
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        # A build's stdout/stderr drain threads and async cache-push
        # threads all emit concurrently; chunk framing must be atomic or
        # interleaved writes corrupt the HTTP stream. `finished` guards
        # against stragglers (a cache/chunk push outliving the bounded
        # wait_for_push join still carries this build's log context):
        # once the terminal chunk is written, late frames are dropped
        # instead of corrupting the ended HTTP body.
        emit_lock = threading.Lock()
        finished = threading.Event()

        def emit(line: str) -> None:
            data = (line.rstrip("\n") + "\n").encode()
            frame = f"{len(data):x}\r\n".encode() + data + b"\r\n"
            with emit_lock:
                if finished.is_set():
                    return
                self.wfile.write(frame)

        start = time.monotonic()
        record = self.server.register_build(argv, tenant)
        code = self.server.run_build(argv, emit, record,
                                     traceparent=traceparent,
                                     fleet_info=fleet_info)
        # Terminal line carries the outcome as DATA — exit code,
        # elapsed seconds, and the admission split (queue wait vs
        # execution) — so clients never parse log text for it.
        # "build_code" (stringly) predates "exit_code"; kept for older
        # clients.
        emit(json.dumps({
            "build_code": str(code),
            "exit_code": code,
            "elapsed_seconds": round(time.monotonic() - start, 3),
            "queue_wait_seconds": round(record.queue_wait_seconds, 3),
            **record.service,
            "tenant": tenant,
        }))
        with emit_lock:
            finished.set()
            self.wfile.write(b"0\r\n\r\n")

    def _respond(self, status: int, body: bytes,
                 content_type: str | None = None) -> None:
        try:
            self.send_response(status)
            if content_type:
                self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        except (BrokenPipeError, ConnectionResetError):
            pass  # client hung up; not our problem


@dataclasses.dataclass(frozen=True, slots=True)
class _Resolved:
    """What one request resolved at its admission, once: its flags and
    the real paths of the directories it names. ``args`` is ``None``
    for a malformed ``argv``, which ``cli.main`` parses itself so that
    argparse's message and exit code are what a one-shot CLI gives."""

    args: argparse.Namespace | None = None
    log_level: str = "info"
    root: str | None = None
    storage: str | None = None
    # --root, --storage and the context, as given / absolute / real,
    # each to its real path (``pathutils.real_path`` while bound).
    dirs: dict[str, str] = dataclasses.field(default_factory=dict)


def _effective_flags(argv: list[str]) -> _Resolved:
    """Resolve the flags the worker cares about through the REAL CLI
    parser — hand-rolled argv scanning would miss argparse's equals
    form, abbreviations ('--roo'), and defaults, any of which would
    punch holes in path-lock serialization or per-build log levels.

    The one parse and the one resolution of a request: ``run_build``
    calls this right after admission, hands ``args`` to ``cli.main``
    (which then parses nothing) and the record to everything that
    asked ``argv`` before; --root, --storage and the context are walked
    through their symlinks here and nowhere else in the request
    (``pathutils.real_path`` answers from ``dirs`` while it is
    bound)."""
    from makisu_tpu import cli
    try:
        args = cli.parse_args(argv)
    except SystemExit:
        return _Resolved()  # malformed argv: cli.main will report it
    root = getattr(args, "root", None)
    storage = getattr(args, "storage", None)
    if storage is not None:
        # "" means the computed default storage dir; resolve it so an
        # explicit --storage of the same path shares the lock, and so
        # the command reads it from the namespace, computed.
        storage = args.storage = cli._storage_dir(storage)
    dirs = pathutils.resolve_request_dirs(
        d for d in (root, storage, getattr(args, "context", None))
        if d is not None)
    return _Resolved(args, getattr(args, "log_level", "info"),
                     root, storage, dirs)


def _peer_map_version() -> int:
    from makisu_tpu.fleet import peers as fleet_peers
    return fleet_peers.map_version()


def _warm_probe_wanted() -> bool:
    """Whether worker startup should begin JAX backend init eagerly.
    Explicit MAKISU_TPU_WORKER_WARM_PROBE=1/0 wins; otherwise probe
    exactly when JAX_PLATFORMS names a non-cpu platform or an
    attachment env var is present — the configurations where the probe
    buys wedge detection and the exclusive-device-acquisition side
    effect is intended. Known limitation: a host where plugin discovery
    finds an accelerator with ZERO env configuration gates off (there
    is no signal to distinguish it from a cpu-only host without paying
    the acquisition we're avoiding); such deployments set
    MAKISU_TPU_WORKER_WARM_PROBE=1 — the gated-off path logs a hint."""
    forced = os.environ.get("MAKISU_TPU_WORKER_WARM_PROBE")
    if forced is not None:
        return forced == "1"
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms:
        return platforms.lower() != "cpu"
    # JAX_PLATFORMS unset: default platform discovery may still find an
    # accelerator. The attachment env vars (the same signal the
    # device-session ledger fingerprints) say whether one is
    # configured; per-process ones say nothing about a device.
    from makisu_tpu.ops.backend import (
        ATTACHMENT_ENV_EXCLUDE,
        ATTACHMENT_ENV_PREFIXES,
    )
    from makisu_tpu.utils import logging as log
    if any(k.startswith(ATTACHMENT_ENV_PREFIXES)
           and k not in ATTACHMENT_ENV_EXCLUDE for k in os.environ):
        return True
    log.info("warm probe gated off (no device platform configured); "
             "set MAKISU_TPU_WORKER_WARM_PROBE=1 if this host has an "
             "accelerator via default discovery")
    return False


# Shared-path serialization across every WorkerServer in the process
# (see WorkerServer.__init__).
_PATH_LOCKS: dict[str, threading.Lock] = {}
_PATH_LOCKS_MU = threading.Lock()


class WorkerServer(socketserver.ThreadingMixIn, socketserver.UnixStreamServer):
    allow_reuse_address = True
    daemon_threads = True

    def __init__(self, socket_path: str,
                 stall_window: float | None = None,
                 diag_out: str = "",
                 max_concurrent_builds: int = 0,
                 slo_config: str = "",
                 alert_webhook: str = "",
                 slo_interval: float | None = None) -> None:
        if os.path.exists(socket_path):
            os.unlink(socket_path)
        super().__init__(socket_path, _Handler)
        self.socket_path = socket_path
        # /healthz vital signs. Monotonic for uptime (wall clock can
        # step); counters under one lock, cheap enough per build.
        self._started_mono = time.monotonic()
        self._health_mu = threading.Lock()
        self._builds_started = 0
        self._builds_succeeded = 0
        self._builds_failed = 0
        # Admission control: cap concurrently EXECUTING builds, FIFO
        # beyond the cap. 0/unset = unlimited (the pre-fleet default);
        # env MAKISU_TPU_MAX_CONCURRENT_BUILDS configures deployments
        # whose supervisor can't pass flags.
        if max_concurrent_builds <= 0:
            try:
                max_concurrent_builds = int(os.environ.get(
                    "MAKISU_TPU_MAX_CONCURRENT_BUILDS", "0") or 0)
            except ValueError:
                max_concurrent_builds = 0
        self.max_concurrent_builds = max_concurrent_builds
        self._admission = _AdmissionQueue(max_concurrent_builds)
        # GET /builds state: every accepted build gets a record that
        # lives in _inflight until it finishes, then rides the bounded
        # recent ring. Latency digests (exact, last-512) back the
        # /healthz queue section.
        self._builds_mu = threading.Lock()
        self._build_seq = 0
        self._inflight: dict[int, _BuildRecord] = {}
        self._recent: collections.deque[_BuildRecord] = \
            collections.deque(maxlen=_RECENT_BUILDS_KEEP)
        self._queue_wait_ring = _QuantileRing()
        self._latency_ring = _QuantileRing()
        self._tenant_latency: dict[str, _QuantileRing] = {}
        # Builds from all connections share one process — and therefore
        # one HashService, so chunk hashing from concurrent builds
        # batches onto full device programs (the build-farm scenario).
        # Step env lives in each BuildContext's exec_env, so builds run
        # genuinely concurrently with no cross-talk.
        os.environ["MAKISU_TPU_SHARED_HASH"] = "1"
        # Probe backend readiness ONCE at startup (non-blocking): by the
        # time the first build's ChunkSession consults backend_ready(),
        # a healthy backend has initialized and a wedged one charges the
        # build only the remaining probe budget — builds never pay a
        # fresh full bounded wait each (r3 verdict, weak #4). Gated:
        # jax backend init ACQUIRES the accelerator (a TPU attaches
        # exclusively to this process), which a worker serving only
        # cpu-hasher builds must not do. MAKISU_TPU_WORKER_WARM_PROBE=
        # 1/0 forces it; the default probes only when JAX_PLATFORMS
        # names a non-cpu platform (i.e. a device is configured for
        # this process at all). A gated-off worker still initializes
        # lazily on the first build that asks for the tpu hasher.
        if _warm_probe_wanted():
            from makisu_tpu.ops import backend as _backend
            _backend.warm_probe(source="worker")
        # The directory reader every build lists its context through:
        # built (a source checkout) and loaded here, not under the
        # first build's ``copy_checksum``.
        from makisu_tpu import native as _native
        _native.dir_reader()
        # Resident build sessions: each server owns ITS OWN manager
        # (bound per build via the session contextvar) so multiple
        # in-process workers — the fleet loadgen topology — model real
        # machines: a session minted on this worker is warm HERE and
        # nowhere else, and /sessions is a truthful affinity signal.
        from makisu_tpu.worker import session as session_mod
        self.session_mgr = session_mod.SessionManager()
        # Distribution plane: a worker is a serving process, so its
        # builds publish layer recipes at index time (MAKISU_TPU_SERVE=0
        # still wins) — that is what makes this worker's /recipes +
        # /packs answer for fleet peers and delta-pull clients.
        from makisu_tpu.serve import server as serve_server
        serve_server.enable_publishing()
        # This worker's serve access ledger (GET /serve/access): every
        # peer/delta fetch answered here, stamped with the requesting
        # build's trace id. Per server — an in-process sibling's
        # traffic must not appear in this worker's ledger.
        self.serve_access = serve_server.AccessLog()
        # Chunk CAS roots THIS server's builds have used: the /chunks
        # peer endpoint serves only these (the process-wide registry
        # would also hold in-process siblings' stores, and serving a
        # sibling's bytes would fake the cross-host exchange).
        self._served_chunk_roots: set[str] = set()
        # Storage observability plane (cache/census.py): the storage
        # DIRS behind those roots, a TTL census cache per dir (healthz
        # polls must not pay a walk each), and the background scrub
        # thread, armed lazily by the first storage registration.
        self._storage_mu = threading.Lock()
        self._storage_dirs: set[str] = set()
        self._storage_state: dict[str, dict] = {}
        self._scrub_thread: threading.Thread | None = None
        self._scrub_stop = threading.Event()
        # Builds sharing a --root or --storage directory would race on
        # the filesystem; those (and only those) serialize. The lock
        # table is PROCESS-wide (module global), not per server: two
        # in-process workers pointed at one storage dir race exactly
        # like two handler threads of one worker do.
        self._path_locks = _PATH_LOCKS
        self._path_locks_mu = _PATH_LOCKS_MU
        # Failure forensics: a process-level flight recorder sees every
        # build's events (global sink — per-build recorders inside each
        # cli.main still keep isolated rings), the resource sampler
        # feeds RSS/CPU gauges and span attribution, and an optional
        # stall watchdog (MAKISU_TPU_STALL_TIMEOUT seconds) dumps a
        # bundle when in-flight builds stop making progress.
        from makisu_tpu.utils import events, flightrecorder, resources
        resources.ensure_started()
        self.recorder = flightrecorder.FlightRecorder()
        self._recorder_sink = self.recorder.record_event
        events.add_global_sink(self._recorder_sink)
        self._watchdog = None
        if stall_window is None:
            stall_window = flightrecorder.stall_timeout_from_env()
        if stall_window > 0:
            from makisu_tpu.utils import metrics
            self._watchdog = flightrecorder.StallWatchdog(
                stall_window, self.recorder,
                flightrecorder.forced_bundle_path(diag_out, "stall"),
                # Explicitly the PROCESS registry: this thread's copied
                # context carries the worker invocation's per-build
                # registry (cli.main bound it before cmd_worker ran),
                # whose trace filter would drop every build's spans.
                registry=metrics.global_registry(),
                active_fn=lambda: self._active_builds() > 0).start()
        # SLO plane: a background rule evaluator over this worker's
        # existing vitals (quantile rings, health counters, census
        # digest, device probe, progress clock — no new sampling).
        # Firing/resolved alerts ride the event bus (into the flight
        # recorder's ring for free), GET /alerts serves the ring, and
        # /healthz carries a cheap active-count digest. Interval 0 (or
        # MAKISU_TPU_SLO_INTERVAL_SECONDS=0) disables evaluation;
        # the endpoint still answers with an empty payload.
        from makisu_tpu.fleet import slo as slo_mod
        rules = slo_mod.default_worker_rules()
        if slo_config:
            rules = slo_mod.load_rules(slo_config, rules)
        self.slo = slo_mod.SloEvaluator(
            self._slo_probe, rules, interval=slo_interval,
            webhook=alert_webhook, source="worker")
        self.slo.start()
        # Continuous profiling: one process-level wall-clock sampler
        # for the worker's lifetime (env MAKISU_TPU_PROFILE_HZ; 0 =
        # off). Ownership-gated: in an in-process fleet the FIRST
        # server to start arms it and the siblings share it — every
        # build's samples land in one process profile either way, and
        # only the owner stops it at close. Builds bind their handler
        # thread to their trace id (cli.main), so per-build phase
        # attribution survives concurrency.
        from makisu_tpu.utils import profiler as profiler_mod
        self._diag_out = diag_out
        self._profiler_owner = False
        self.profiler = profiler_mod.process_profiler()
        profile_hz = profiler_mod.resolve_hz()
        if self.profiler is None and profile_hz > 0:
            self.profiler = profiler_mod.SamplingProfiler(
                hz=profile_hz).start()
            profiler_mod.set_process_profiler(self.profiler)
            self._profiler_owner = True
        # A firing page-severity alert auto-attaches a profile tail
        # next to the diagnostic bundles: the page says "too slow",
        # the artifact says where the time was going when it fired.
        self.slo.manager.on_fire = self._profile_on_page

    # UnixStreamServer's client_address is a path; BaseHTTPRequestHandler
    # wants a (host, port) tuple for logging.
    def get_request(self):
        request, _ = super().get_request()
        return request, ("worker", 0)

    def handle_error(self, request, client_address) -> None:
        # A poller (fleet scheduler, top, loadgen sampler) dropping its
        # keep-alive connection mid-idle is normal churn, not an error
        # worth a traceback on the worker's stderr.
        import sys
        exc = sys.exc_info()[1]
        if isinstance(exc, (BrokenPipeError, ConnectionResetError)):
            return
        super().handle_error(request, client_address)

    def add_served_chunk_root(self, storage_dir: str) -> None:
        """Mark a storage's chunk CAS as servable by THIS worker's
        ``/chunks`` endpoint (run_build records every build's storage;
        embedders/tests may add roots directly — pass the storage dir
        containing ``chunks/``, or that ``chunks/`` dir itself). The
        storage's serve store (recipes + pack tables, at
        ``<storage>/serve``) registers alongside so /recipes and
        /packs answer for it too — the ``chunks/``-suffixed shape
        registers its PARENT storage, because registering the CAS dir
        itself would mint a store looking for recipes under
        ``<cas>/serve`` that the publisher never writes, silently
        degrading this worker's peer exchange to per-chunk GETs. A
        bare nonstandard CAS path has no recipe metadata to find and
        serves per-chunk only."""
        root = pathutils.real_path(storage_dir)
        chunk_root = pathutils.real_path(os.path.join(storage_dir,
                                                      "chunks"))
        from makisu_tpu.serve import server as serve_server
        if os.path.basename(root) == "chunks":
            # Ambiguous shape: a CAS dir handed directly (the common
            # embedder/test idiom), or a STORAGE dir that merely
            # happens to be named "chunks". The publisher writes serve
            # metadata at <storage>/serve, so probe for it before
            # assuming the parent — registering the wrong root would
            # 404 every /recipes lookup and silently degrade this
            # worker's peer exchange to per-chunk GETs.
            if os.path.isdir(os.path.join(root, "serve")):
                serve_server.register_store(storage_dir)
            else:
                serve_server.register_store(os.path.dirname(root))
        else:
            serve_server.register_store(storage_dir)
        with self._builds_mu:
            self._served_chunk_roots.update((root, chunk_root))
        # The storage DIR (the serve store's root, resolved with the
        # same chunks/-suffix disambiguation) joins the census set.
        if os.path.basename(root) == "chunks" \
                and not os.path.isdir(os.path.join(root, "serve")):
            self._add_storage_dir(os.path.dirname(root))
        else:
            self._add_storage_dir(root)

    def served_chunk_roots(self) -> set[str]:
        with self._builds_mu:
            return set(self._served_chunk_roots)

    # -- storage observability (census / audit / scrub) -------------------

    def _add_storage_dir(self, storage_dir: str) -> None:
        with self._storage_mu:
            self._storage_dirs.add(pathutils.real_path(storage_dir))
            if self._scrub_thread is None:
                interval = _scrub_interval_seconds()
                if interval > 0:
                    # Process-level maintenance thread: the scrub
                    # outlives any single build and must not pin one
                    # build's registry/log sink.
                    # check: allow(ctx-propagation)
                    self._scrub_thread = threading.Thread(
                        target=self._scrub_loop, args=(interval,),
                        daemon=True, name="storage-scrub")
                    self._scrub_thread.start()

    def storage_dirs(self) -> list[str]:
        with self._storage_mu:
            return sorted(self._storage_dirs)

    # -- session-snapshot plane (worker/snapshots.py) ----------------------

    def find_session_snapshot(self, context: str) -> dict | None:
        """The newest session-snapshot recipe for ``context`` across
        this worker's registered storage dirs (GET /sessions/snapshot
        — the fleet prewarm pull). Resident sessions name their own
        storage dir, so that one is probed first."""
        from makisu_tpu.worker import snapshots as snapshots_mod
        dirs: list[str] = []
        session_dir = self.session_mgr.storage_dir_for(context)
        if session_dir:
            dirs.append(session_dir)
        dirs.extend(d for d in self.storage_dirs() if d not in dirs)
        for storage_dir in dirs:
            try:
                recipe = snapshots_mod.SnapshotStore(
                    storage_dir).load_for_context(context)
            except OSError:
                continue
            if recipe is not None:
                return recipe
        return None

    def stage_session_snapshot(self, body: dict) -> tuple[bool, str]:
        """POST /sessions/restore: land a snapshot recipe (and its
        chunks, over the peer wire if needed) on this worker's storage
        so the next build's ``SessionManager.acquire`` restores warm.
        Failures count into the manager's snapshot ledger — that is
        what ``doctor --fleet``'s snapshot_restore_failed finding
        reads."""
        from makisu_tpu.worker import snapshots as snapshots_mod
        recipe = body.get("recipe")
        context = str(body.get("context", ""))
        storage = str(body.get("storage", ""))
        if recipe is None and context:
            # Re-validate a recipe already on local storage.
            recipe = self.find_session_snapshot(context)
            if recipe is None:
                return False, "no_snapshot"
        if not isinstance(recipe, dict):
            return False, "no_recipe"
        context = str(recipe.get("context", "")) or context
        if not storage:
            dirs = self.storage_dirs()
            if len(dirs) == 1:
                storage = dirs[0]
            elif not dirs:
                return False, "no_storage"
            else:
                # Ambiguous: prefer the storage a resident session (or
                # a prior snapshot of this context) already uses.
                storage = self.session_mgr.storage_dir_for(context) \
                    or dirs[0]
        try:
            ok, reason = snapshots_mod.SnapshotStore(storage).stage(
                recipe)
        except Exception as e:  # noqa: BLE001 - control plane answers
            ok, reason = False, f"error:{type(e).__name__}"
        if ok:
            # Staged chunks are servable onward (a prewarmed worker is
            # a peer source for the NEXT prewarm hop).
            self.add_served_chunk_root(storage)
        else:
            self.session_mgr.note_snapshot("restore_refused",
                                           context=context,
                                           reason=reason)
        return ok, reason

    def _census_for(self, storage_dir: str,
                    max_age: float | None = None) -> dict:
        """This dir's census, through the TTL cache — /healthz polls
        arrive every few seconds and must not each pay a walk."""
        from makisu_tpu.cache import census as census_mod
        if max_age is None:
            max_age = _census_ttl_seconds()
        now = time.monotonic()
        with self._storage_mu:
            state = self._storage_state.setdefault(storage_dir, {})
            doc = state.get("census")
            if doc is not None \
                    and now - state.get("census_mono", 0.0) < max_age:
                return doc
        doc = census_mod.StorageCensus(storage_dir).census()
        with self._storage_mu:
            state = self._storage_state.setdefault(storage_dir, {})
            state["census"] = doc
            state["census_mono"] = time.monotonic()
        return doc

    def storage_health(self) -> dict:
        """The /healthz ``storage`` digest: per-plane totals summed
        across this worker's storage dirs, the chunk CAS LRU seed
        state (worst dir wins — an eviction dry-run must know), and
        the latest audit/scrub finding counts."""
        from makisu_tpu.cache import census as census_mod
        dirs = self.storage_dirs()
        planes: dict[str, dict] = {}
        total_bytes = 0
        total_objects = 0
        seed = {"state": "seeded", "seeded_entries": 0}
        seed_rank = {"unseeded": 0, "seeding": 1, "seeded": 2}
        finding_kinds: dict[str, int] = {}
        for storage_dir in dirs:
            try:
                doc = self._census_for(storage_dir)
            except OSError:
                continue
            total_bytes += int(doc.get("total_bytes", 0) or 0)
            total_objects += int(doc.get("total_objects", 0) or 0)
            for plane, row in (doc.get("planes") or {}).items():
                agg = planes.setdefault(plane,
                                        {"objects": 0, "bytes": 0})
                agg["objects"] += int(row.get("objects", 0) or 0)
                agg["bytes"] += int(row.get("bytes", 0) or 0)
            state = census_mod.seed_states(storage_dir)
            if state:
                if seed_rank.get(state.get("state"), 0) \
                        < seed_rank.get(seed["state"], 2):
                    seed["state"] = state.get("state", "unseeded")
                seed["seeded_entries"] += int(
                    state.get("seeded_entries", 0) or 0)
            with self._storage_mu:
                cached = self._storage_state.get(storage_dir, {})
                for f in (cached.get("findings") or []):
                    kind = str(f.get("kind", "?"))
                    finding_kinds[kind] = \
                        finding_kinds.get(kind, 0) + 1
        # Budget digest (storage/contentstore.py): what the scheduler's
        # disk-pressure routing and fleet doctor read — budget, hot-tier
        # occupancy, and their ratio ("pressure"; 0.0 when unbudgeted).
        from makisu_tpu.storage import contentstore
        budget_total = 0
        hot_total = 0
        for storage_dir in dirs:
            try:
                store = contentstore.store_for(storage_dir)
                budget_total += store.budget_bytes
                hot_total += store.tier_bytes(publish=False)["hot"]
            except OSError:
                continue
        counters = contentstore.counters()
        return {
            "dirs": len(dirs),
            "planes": planes,
            "total_bytes": total_bytes,
            "total_objects": total_objects,
            "lru_seed": seed,
            "budget": {
                "budget_bytes": budget_total,
                "hot_bytes": hot_total,
                "pressure": (round(hot_total / budget_total, 4)
                             if budget_total > 0 else 0.0),
                "evictions_total": counters["evictions"],
                "evicted_bytes": counters["evicted_bytes"],
                "refetch_bytes": counters["refetch_bytes"],
            },
            "findings": {
                "total": sum(finding_kinds.values()),
                "kinds": dict(sorted(finding_kinds.items())),
            },
        }

    def storage_report(self,
                       eviction_budget: int | None = None) -> dict:
        """The ``GET /storage`` payload: fresh census + reference
        audit (+ eviction dry-run when a budget is asked for) per
        storage dir, plus the latest scrub cycle's findings. The dry
        run consults the LIVE chunk CAS's seed state and refuses on
        partial recency data."""
        from makisu_tpu.cache import census as census_mod
        reports = []
        for storage_dir in self.storage_dirs():
            engine = census_mod.StorageCensus(storage_dir)
            doc = engine.census()
            audit = engine.audit()
            entry: dict = {"storage_dir": storage_dir,
                           "census": doc, "audit": audit}
            seed = census_mod.seed_states(storage_dir)
            if seed is not None:
                entry["lru_seed"] = seed
            if eviction_budget is not None:
                entry["eviction_dry_run"] = engine.eviction_dry_run(
                    eviction_budget, seed_state=seed)
            from makisu_tpu.storage import contentstore
            try:
                entry["contentstore"] = contentstore.store_for(
                    storage_dir).describe()
            except OSError:
                pass
            with self._storage_mu:
                state = self._storage_state.setdefault(
                    storage_dir, {})
                state["census"] = doc
                state["census_mono"] = time.monotonic()
                state["findings"] = list(audit["findings"])
                entry["scrub"] = dict(state.get("scrub") or {})
            reports.append(entry)
        return {"storage": reports}

    def _scrub_loop(self, interval: float) -> None:
        """Background integrity scrub: every cycle re-hashes a few
        random chunks + one zpack frame per storage dir under the IO
        budget, refreshes the census gauges, and parks corruption
        findings where /healthz and /storage surface them. Corruption
        events ride the bus (the worker's global flight-recorder sink
        puts them in crash bundles for free)."""
        from makisu_tpu.cache import census as census_mod
        from makisu_tpu.utils import logging as log
        from makisu_tpu.storage import contentstore
        while not self._scrub_stop.wait(interval):
            for storage_dir in self.storage_dirs():
                try:
                    # Budget enforcement rides the same cadence as
                    # integrity: a worker idle between builds still
                    # converges to its byte budget (no-op unbudgeted).
                    contentstore.store_for(storage_dir).maybe_evict()
                    engine = census_mod.StorageCensus(storage_dir)
                    doc = engine.census()
                    result = engine.scrub()
                except Exception as exc:  # noqa: BLE001 - never kills
                    log.debug("storage scrub cycle failed for %s: %s",
                              storage_dir, exc)
                    continue
                with self._storage_mu:
                    state = self._storage_state.setdefault(
                        storage_dir, {})
                    state["census"] = doc
                    state["census_mono"] = time.monotonic()
                    state["scrub"] = {
                        "chunks_checked": result["chunks_checked"],
                        "packs_checked": result["packs_checked"],
                        "bytes_read": result["bytes_read"],
                        "corrupt": len(result["findings"]),
                    }
                    if result["findings"]:
                        state.setdefault("findings", [])
                        state["findings"].extend(result["findings"])
                        del state["findings"][:-_SCRUB_FINDINGS_KEEP]

    def register_build(self, argv: list[str],
                       tenant: str = "") -> _BuildRecord:
        """Create this build's ``/builds`` record (state=queued). The
        record exists BEFORE admission, so a build waiting in the FIFO
        is visible to ``top`` with a growing queue wait."""
        with self._builds_mu:
            self._build_seq += 1
            record = _BuildRecord(self._build_seq, tenant, argv)
            self._inflight[record.seq] = record
        return record

    def _retire_build(self, record: _BuildRecord, code: int) -> None:
        record.finish(code)
        latency = record.latency_seconds()
        self._queue_wait_ring.add(record.queue_wait_seconds)
        self._latency_ring.add(latency)
        with self._builds_mu:
            self._inflight.pop(record.seq, None)
            self._recent.append(record)
            tenant = record.tenant
            if (tenant not in self._tenant_latency
                    and len(self._tenant_latency)
                    >= _TENANT_LABELS_KEEP):
                tenant = _TENANT_OVERFLOW
            ring = self._tenant_latency.setdefault(
                tenant, _QuantileRing())
        ring.add(latency)
        # Prometheus histograms (scrape-side quantiles, per-tenant
        # fairness series); the rings above serve /healthz exactly.
        # Same capped tenant label: the process registry's series set
        # must stay bounded for a long-lived worker's /metrics.
        from makisu_tpu.utils import metrics
        g = metrics.global_registry()
        # `tenant` was capped to the _TENANT_OVERFLOW bucket a few
        # lines up — the ring-cap logic IS this file's cardinality
        # helper, and these two series predate the name registry.
        # check: allow(metric-registry)
        g.observe("makisu_build_queue_wait_seconds",
                  record.queue_wait_seconds,
                  buckets=_LATENCY_BUCKETS, tenant=tenant)
        # check: allow(metric-registry)
        g.observe("makisu_build_latency_seconds", latency,
                  buckets=_LATENCY_BUCKETS, tenant=tenant)

    def builds(self) -> dict:
        """The ``GET /builds`` payload."""
        with self._builds_mu:
            inflight = sorted(self._inflight.values(),
                              key=lambda r: r.seq)
            recent = list(self._recent)
        return {
            "queue_depth": self._admission.depth(),
            "max_concurrent_builds": self.max_concurrent_builds,
            "inflight": [r.to_dict() for r in inflight],
            "recent": [r.to_dict() for r in reversed(recent)],
        }

    def run_build(self, argv: list[str], emit,
                  record: _BuildRecord | None = None,
                  traceparent: str = "",
                  fleet_info: dict | None = None) -> int:
        """Run one build command in-process, forwarding log lines and
        build events.

        The log sink and event sink bind to this request's context (and
        the threads the build spawns), so concurrent builds' streams
        stay separate — client A never sees client B's log lines or
        events. Events ride the same chunked NDJSON stream as their own
        frame type, ``{"event": {...}}``, so a client watches the
        build's structure (spans, steps, cache outcomes) live.

        Admission happens here: past ``--max-concurrent-builds``
        executing builds, the request thread waits its FIFO turn. The
        wait lands on ``record`` (queue split in the terminal frame,
        queue-wait histograms, ``/builds``).

        ``thread_cpu_seconds`` on the record is this thread's own CPU
        (``time.thread_time()``) from admission to the end of the
        ``finally`` below, the interval of ``service_seconds``: the
        difference is what the thread waited (the interpreter lock
        among the other builds' threads, the file system, the device,
        the sink's ring). It leaves out the threads that work for the
        build beside this one: the native sink's compressor (stage
        ``compress``), the chunk store's probe and ingest pools
        (``chunk_index``), the cache-push threads and the shared hash
        service's dispatcher (the ``FeedClock`` stages)."""
        from makisu_tpu import cli
        from makisu_tpu.utils import events, metrics
        from makisu_tpu.utils import logging as log

        def sink(level: str, msg: str, fields: dict) -> None:
            try:
                emit(json.dumps({"level": level, "msg": msg}))
            except OSError:
                pass  # client went away; keep building

        def event_sink(event: dict) -> None:
            try:
                emit(json.dumps({"event": event}, default=str))
            except OSError:
                pass  # client went away; keep building

        if record is None:  # direct callers (tests) skip do_POST
            record = self.register_build(argv)
        queue_wait = self._admission.acquire()
        record.start_running(queue_wait)
        admitted = time.monotonic()
        admitted_cpu = time.thread_time()
        # Inbound trace context: bound for cli.main to adopt into the
        # build's registry (the build's spans, events, and outbound
        # traceparents all join the caller's trace). Parsed here too so
        # the queue-wait emission below can be stamped with the right
        # ids even though it precedes the registry's existence.
        trace_token = metrics.bind_inbound_traceparent(traceparent)
        parsed_tp = (metrics.parse_traceparent(traceparent)
                     if traceparent else None)
        # Fleet provenance: when the front door forwarded this build,
        # the routing outcome rides into the build's history record
        # (utils/history.py reads the contextvar at append time).
        from makisu_tpu.utils import history as history_mod
        fleet_token = None
        if fleet_info is not None:
            try:
                provenance = {
                    # The front door's scheduler-assigned id when it
                    # sent one (how every other fleet surface names
                    # workers); the socket path only as the fallback
                    # for non-fleet callers that pass a fleet dict.
                    "worker": str(fleet_info.get("worker", "")
                                  or self.socket_path),
                    "verdict": str(fleet_info.get("verdict", "")),
                    "attempts": int(fleet_info.get("attempts", 1) or 1),
                    "quota_wait_seconds": float(
                        fleet_info.get("quota_wait_seconds", 0.0)
                        or 0.0),
                }
            except (TypeError, ValueError):
                # A client-supplied junk "fleet" dict degrades to bare
                # via-a-front-door provenance, never a failed build.
                provenance = {"worker": self.socket_path}
            fleet_token = history_mod.bind_fleet_provenance(provenance)
        # The sink honors this build's own --log-level (the shared
        # console logger's level is process-global and can't).
        flags = _effective_flags(argv)
        dirs_token = pathutils.bind_request_dirs(flags.dirs)
        if flags.storage:
            # This build's chunk CAS becomes servable to fleet peers.
            self.add_served_chunk_root(flags.storage)
        token = log.set_build_sink(
            sink, flags.log_level.replace("warn", "warning"))
        events_token = events.add_sink(event_sink)
        record_token = events.add_sink(record.note_event)
        mode_token = cli.invocation_mode.set("worker")
        # This build's resident-session state lives in THIS server's
        # manager, and its peer chunk fetches must skip this server's
        # own socket — both context-scoped, so the threads the build
        # spawns inherit them.
        from makisu_tpu.fleet import peers as fleet_peers
        from makisu_tpu.worker import session as session_mod
        session_token = session_mod.bind_manager(self.session_mgr)
        peers_token = fleet_peers.bind_self_socket(self.socket_path)
        # The admission wait as a first-class trace event: it happened
        # BEFORE the build's registry existed, so it is emitted here —
        # now that the stream/record sinks are bound — stamped with the
        # inbound trace ids. The merged fleet trace synthesizes it into
        # a queue_wait span beside the front door's quota wait.
        events.emit("queue_wait", seconds=round(queue_wait, 6),
                    tenant=record.tenant or "",
                    **({"trace_id": parsed_tp[0],
                        "parent_id": parsed_tp[1]}
                       if parsed_tp else {}))
        # Count the build started BEFORE acquiring shared-path locks:
        # a build wedged waiting on another build's --root/--storage
        # must show as active in /healthz — that is the situation the
        # endpoint exists to expose. Gauge writes stay under
        # _health_mu: set outside the lock, two builds finishing
        # together could publish counts out of order and wedge the
        # gauge at a stale nonzero value.
        with self._health_mu:
            self._builds_started += 1
            metrics.global_registry().gauge_set(
                "makisu_worker_active_builds",
                self._builds_started - self._builds_succeeded
                - self._builds_failed)
        locks = self._shared_path_locks(flags)
        for lock in locks:
            lock.acquire()
        code = 1
        try:
            code = cli.main(argv, flags.args)
            return code
        except SystemExit as e:
            # argparse exits with an int; cmd_report exits with a
            # message string (exit status 1, message to the client).
            if e.code is None or isinstance(e.code, int):
                code = e.code or 0
            else:
                emit(json.dumps({"level": "error", "msg": str(e.code)}))
                code = 1
            return code
        except Exception as e:  # noqa: BLE001 - worker must survive
            emit(json.dumps({"level": "error", "msg": str(e)}))
            return 1
        finally:
            metrics.counter_add("makisu_worker_builds_total",
                                result="ok" if code == 0 else "error")
            with self._health_mu:
                if code == 0:
                    self._builds_succeeded += 1
                else:
                    self._builds_failed += 1
                metrics.global_registry().gauge_set(
                    "makisu_worker_active_builds",
                    self._builds_started - self._builds_succeeded
                    - self._builds_failed)
            for lock in reversed(locks):
                lock.release()
            self._admission.release()
            self._retire_build(record, code)
            # A storage its owner removed while the build ended (a
            # k8s job's scratch volume) is not made anew for a sidecar
            # or an eviction pass over nothing.
            storage_there = bool(flags.storage) \
                and os.path.isdir(flags.storage)
            if storage_there and record.tenant:
                # Ledger → census join: persist this build's layer
                # hexes under its tenant so the storage census can
                # attribute the bytes those layers put on disk.
                from makisu_tpu.cache import census as census_mod
                census_mod.record_attribution(
                    flags.storage, record.tenant,
                    record.layer_hexes())
            if storage_there:
                # Budget enforcement at the moment disk grows: build
                # end is when new chunks/blobs landed. Throttled and
                # a no-op when unbudgeted; never fails the build.
                from makisu_tpu.storage import contentstore
                contentstore.store_for(flags.storage).maybe_evict()
            fleet_peers.reset_self_socket(peers_token)
            session_mod.reset_manager(session_token)
            if fleet_token is not None:
                history_mod.reset_fleet_provenance(fleet_token)
            metrics.reset_inbound_traceparent(trace_token)
            cli.invocation_mode.reset(mode_token)
            events.reset_sink(record_token)
            events.reset_sink(events_token)
            log.reset_build_sink(token)
            pathutils.reset_request_dirs(dirs_token)
            thread_cpu = time.thread_time() - admitted_cpu
            record.note_service(admitted, time.monotonic(), thread_cpu)
            metrics.counter_add(metrics.WORKER_BUILD_THREAD_CPU_SECONDS,
                                thread_cpu)

    def _active_builds(self) -> int:
        with self._health_mu:
            return (self._builds_started - self._builds_succeeded
                    - self._builds_failed)

    def _slo_probe(self) -> dict:
        """The SLO evaluator's sample — every input is a surface this
        server already keeps (no new sampling): outcome counters for
        the burn-rate rules, and ring/probe/census levels for the
        threshold rules."""
        from makisu_tpu.utils import flightrecorder
        with self._health_mu:
            started = self._builds_started
            succeeded = self._builds_succeeded
            failed = self._builds_failed
        active = started - succeeded - failed
        latency = self._latency_ring.stats()
        wait = self._queue_wait_ring.stats()
        with self._builds_mu:
            tenant_rings = dict(self._tenant_latency)
        tenant_p99 = {t: float(ring.stats().get("p99", 0.0))
                      for t, ring in tenant_rings.items()}
        # Queue-wait share: how much of the typical build's wall clock
        # was admission queueing (p50-over-p50 — medians, so one
        # outlier can't claim the whole fleet is queue-bound).
        share = 0.0
        if latency.get("count") and latency.get("p50"):
            share = float(wait.get("p50", 0.0)) / float(latency["p50"])
        # Device probe verdict — consulted only when something already
        # imported the device stack (same gate as health()).
        device_bad = 0.0
        ops_backend = sys.modules.get("makisu_tpu.ops.backend")
        if ops_backend is not None:
            try:
                state = str(ops_backend.device_health()
                            .get("probe", {}).get("state", ""))
            except Exception as exc:  # noqa: BLE001
                # A probe that can't even answer IS the page signal.
                from makisu_tpu.utils import logging as log
                log.debug("device health probe failed: %s", exc)
                state = "error"
            device_bad = 1.0 if state in ("wedged", "failed",
                                          "error") else 0.0
        # Progress age counts only while builds are active: an idle
        # worker emitting nothing is healthy, not stalled.
        progress_age = (flightrecorder.last_progress_seconds()
                        if active > 0 else 0.0)
        storage_bytes = float(
            self.storage_health().get("total_bytes", 0) or 0)
        return {
            "counters": {
                "builds_started": float(started),
                "builds_failed": float(failed),
            },
            "levels": {
                "build_latency_p99": float(latency.get("p99", 0.0)),
                "tenant_latency_p99": tenant_p99,
                "queue_wait_share": round(share, 4),
                "queue_depth": float(self._admission.depth()),
                "progress_age": progress_age,
                "device_probe_bad": device_bad,
                "storage_total_bytes": storage_bytes,
            },
        }

    def alerts(self) -> dict:
        """The ``GET /alerts`` payload: the alert ring plus the rule
        names this worker evaluates."""
        out = self.slo.manager.snapshot()
        out["source"] = "worker"
        out["rules"] = [r.name for r in self.slo.rules]
        return out

    def health(self) -> dict:
        """The ``GET /healthz`` payload: uptime, build outcome counts
        (active = started - finished; a build blocked on a shared
        --root/--storage path lock counts as active), the progress
        clock, and the transfer engine's gauges — a wedged transfer
        plane is visible to a probe without scraping /metrics."""
        from makisu_tpu.utils import flightrecorder, metrics
        with self._health_mu:
            started = self._builds_started
            succeeded = self._builds_succeeded
            failed = self._builds_failed
        g = metrics.global_registry()
        # Process-wide cache economics: hit/miss totals, misses broken
        # down by reason, and the chunk plane's dedup split — the
        # per-worker signal a fleet scheduler's cache-affinity routing
        # reads without a Prometheus scrape (full per-key attribution
        # comes from each build's --explain-out ledger).
        chunk_added = g.counter_total("makisu_chunk_bytes_total",
                                      result="added")
        chunk_reused = g.counter_total("makisu_chunk_bytes_total",
                                       result="reused")
        cache = {
            "hits": int(g.counter_total("makisu_cache_pull_total",
                                        result="hit")),
            "misses": int(g.counter_total("makisu_cache_pull_total",
                                          result="miss")),
            "miss_reasons": {
                reason: int(n) for reason, n in sorted(
                    g.counter_by_label("makisu_cache_miss_total",
                                       "reason").items())},
            "chunk_bytes_added": int(chunk_added),
            "chunk_bytes_reused": int(chunk_reused),
            "chunk_dedup_ratio": round(
                chunk_reused / (chunk_added + chunk_reused), 4)
                if (chunk_added + chunk_reused) else 0.0,
        }
        # Admission-queue vitals: depth, the concurrency cap, and exact
        # wait/latency percentiles over recent builds (overall + per
        # tenant) — the fairness signal `loadgen` and a fleet scheduler
        # read. Rings are exact over the last 512 builds; the
        # Prometheus histograms carry the full-history series.
        with self._builds_mu:
            tenant_rings = dict(self._tenant_latency)
        queue = {
            "depth": self._admission.depth(),
            "max_concurrent_builds": self.max_concurrent_builds,
            "wait_seconds": self._queue_wait_ring.stats(),
            "latency_seconds": self._latency_ring.stats(),
            "tenant_latency_seconds": {
                tenant: ring.stats()
                for tenant, ring in sorted(tenant_rings.items())},
        }
        # Device-route vitals: probe state/phase/heartbeat (a wedged
        # backend init is visible to a probe BEFORE any build pays the
        # bounded wait) + per-bucket dispatch latency and byte
        # economics once a backend is serving programs. Consulted only
        # when something already imported the device stack (same gate
        # as flightrecorder/history): a cpu-only worker's first
        # /healthz must not block on a multi-second jax import.
        device = {"probe": {"state": "absent", "sample_count": 0},
                  "dispatch_seconds": {}, "h2d_bytes": 0,
                  "padding_waste_bytes": 0}
        ops_backend = sys.modules.get("makisu_tpu.ops.backend")
        if ops_backend is not None:
            try:
                device = ops_backend.device_health()
            except Exception:  # noqa: BLE001 - healthz always answers
                device = {"probe": {"state": "error"}}
        # Resident-session vitals: count, resident-byte accounting
        # against the budget, hit/invalidations tallies — the warm-path
        # state a fleet scheduler routes toward (cache affinity) and an
        # operator watches for memory pressure. The per-session rows
        # stay on GET /sessions; /healthz carries the digest — THIS
        # server's own manager, like /sessions.
        session_stats = self.session_mgr.stats()
        sessions = {k: session_stats[k] for k in
                    ("count", "resident_bytes", "hits",
                     "invalidations", "max_sessions",
                     "max_resident_bytes")}
        # Snapshot-plane digest rides along: write/restore tallies and
        # the last restore failure — what the fleet poll captures and
        # `doctor --fleet`'s snapshot_restore_failed finding reads.
        sessions["snapshot"] = session_stats.get("snapshot", {})
        # Distribution-plane vitals: what this worker can serve
        # (recipes/packs published by its builds) — the capacity
        # signal the fleet scheduler surfaces per worker. Scoped to
        # THIS server's stores only; the process-global request/byte
        # counters live on /metrics (in an in-process fleet they
        # aggregate every sibling and would misattribute traffic
        # here).
        from makisu_tpu.serve import server as serve_server
        serve = serve_server.serve_stats(
            roots=self.served_chunk_roots())
        return {
            "status": "ok",
            "uptime_seconds": round(
                time.monotonic() - self._started_mono, 3),
            "builds_started": started,
            "builds_succeeded": succeeded,
            "builds_failed": failed,
            "active_builds": started - succeeded - failed,
            "queue": queue,
            "cache": cache,
            "device": device,
            "sessions": sessions,
            "serve": serve,
            # Storage-plane vitals: per-plane object/byte totals over
            # this worker's storage dirs (TTL-cached census — polls
            # never pay a fresh walk), the chunk CAS LRU seed state
            # (satellite of the census work: the background seed
            # thread was invisible, and eviction dry-runs refuse to
            # run over its partial recency data), and audit/scrub
            # finding counts. Full findings live on GET /storage.
            "storage": self.storage_health(),
            # Seconds since the last observable progress (event bus,
            # log line, or transfer-engine work). A probe alerting on
            # active_builds > 0 && last_progress_seconds > window sees
            # a stalled worker without the watchdog being armed.
            "last_progress_seconds": round(
                flightrecorder.last_progress_seconds(), 3),
            "transfer_inflight_bytes": int(g.gauge_value(
                "makisu_transfer_inflight_bytes")),
            "transfer_queue_depth": int(g.gauge_value(
                "makisu_transfer_queue_depth")),
            # The peer map version this process holds: a worker that
            # restarted between two scheduler polls (never observed
            # dead) answers 0 here, telling the scheduler its map was
            # lost and must be republished.
            "peer_map_version": _peer_map_version(),
            # SLO-plane digest: active alert counts by severity — the
            # cheap signal the fleet poll captures for `top`'s ALERTS
            # column. Full rows live on GET /alerts.
            "alerts": self.slo.manager.digest(),
            # Continuous-profiling vitals: the sampler's own health
            # (rate, sample/drop totals, self-measured overhead
            # fraction against the 2% budget). Stacks live on
            # GET /profile.
            "profiler": self.profiler_health(),
        }

    def profiler_health(self) -> dict:
        if self.profiler is None:
            return {"enabled": False, "hz": 0.0, "samples_total": 0,
                    "dropped": 0, "throttled": 0, "distinct_stacks": 0,
                    "overhead_fraction": 0.0}
        return self.profiler.stats()

    def profile(self, seconds: float) -> dict:
        """The ``GET /profile?seconds=N`` body: a capture window from
        the resident sampler, or — when profiling is disabled process-
        wide — a temporary sampler spun up just for the window (the
        on-demand path must work precisely on the deployments that
        turned the always-on one off)."""
        from makisu_tpu.utils import profiler as profiler_mod
        seconds = min(max(float(seconds), 0.1), 30.0)
        if self.profiler is not None and self.profiler.enabled:
            return self.profiler.window(seconds, command="worker")
        temp = profiler_mod.SamplingProfiler().start()
        try:
            temp._stop.wait(seconds)
        finally:
            temp.stop()
        return temp.snapshot(command="worker")

    def _profile_on_page(self, payload: dict) -> None:
        """AlertManager ``on_fire`` hook: a page-severity alert writes
        the sampler's current snapshot beside the diagnostic bundles,
        named after the rule that fired."""
        from makisu_tpu.utils import flightrecorder
        from makisu_tpu.utils import profiler as profiler_mod
        sampler = self.profiler
        if sampler is None or not sampler.samples_total:
            return
        rule = str(payload.get("rule", "page")).replace("/", "_")
        profiler_mod.write_artifact(
            flightrecorder.forced_profile_path(
                self._diag_out, f"alert-{rule}"),
            sampler.snapshot(command=f"alert-{rule}"))

    def server_close(self) -> None:
        from makisu_tpu.utils import events
        from makisu_tpu.utils import profiler as profiler_mod
        if self._profiler_owner and self.profiler is not None:
            self.profiler.stop()
            if profiler_mod.process_profiler() is self.profiler:
                profiler_mod.set_process_profiler(None)
        self.slo.stop()
        self._scrub_stop.set()
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog = None
        events.remove_global_sink(self._recorder_sink)
        super().server_close()

    def _shared_path_locks(self, flags: _Resolved) -> list:
        """Locks for this build's --root/--storage dirs (created on
        demand, acquired in sorted order so overlapping sets can't
        deadlock). Builds with disjoint paths share no locks and run
        fully in parallel. ``flags`` is the request's one resolution
        (``_effective_flags``): both ``--flag PATH`` and ``--flag=PATH``
        spellings resolved by the real parser, and the paths
        canonicalized through symlinks at admission — missing either
        would let two builds race on one filesystem."""
        from makisu_tpu.utils import metrics
        metrics.counter_add(metrics.REQUEST_RESOLVE_TOTAL, kind="parse",
                            result="reused")
        paths = set()
        for name in ("root", "storage"):
            value = getattr(flags, name)
            key = flags.dirs[value] if value is not None else "<none>"
            paths.add(f"--{name}={key}")
        with self._path_locks_mu:
            return [self._path_locks.setdefault(p, threading.Lock())
                    for p in sorted(paths)]

    def serve_background(self) -> threading.Thread:
        t = threading.Thread(target=self.serve_forever, daemon=True)
        t.start()
        return t
