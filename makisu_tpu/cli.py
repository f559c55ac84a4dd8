"""makisu-tpu command line: build / pull / push / diff / version.

Reference surface: bin/makisu/cmd/ (root.go:73-87; build flags
build.go:97-135; helpers utils.go:41-224; pull.go, push.go, diff.go,
version.go). One addition over the reference: ``--hasher cpu|tpu``
selects the layer-commit hashing backend (the TPU path also records
chunk fingerprints into the distributed cache).
"""

from __future__ import annotations

import argparse
import contextvars
import cProfile
import os
import sys
import threading

import makisu_tpu
from makisu_tpu import tario
from makisu_tpu.utils import concurrency
from makisu_tpu.utils import events
from makisu_tpu.utils import logging as log
from makisu_tpu.utils import metrics
from makisu_tpu.utils import pathutils

# How this invocation was launched, for the build_info gauge. The
# worker sets "worker" around each in-process cli.main call —
# context-scoped, not process env, so a process that hosts a worker
# AND runs standalone builds labels each correctly.
invocation_mode: "contextvars.ContextVar[str]" = contextvars.ContextVar(
    "makisu_invocation_mode", default="standalone")


def make_parser() -> argparse.ArgumentParser:
    """A fresh tree, for whoever wants one of their own. ``main`` and
    the worker's request path parse through ``parse_args``, whose tree
    the process builds once."""
    return _parser_tree()[0]


def _parser_tree() -> tuple[argparse.ArgumentParser,
                            argparse.ArgumentParser]:
    """The parser and its ``build`` sub-parser (whose two environment
    defaults ``parse_args`` reads anew each parse)."""
    parser = argparse.ArgumentParser(
        prog="makisu-tpu",
        description="TPU-native daemonless container image builder.")
    parser.add_argument("--log-level", default="info",
                        choices=["debug", "info", "warn", "error"])
    parser.add_argument("--log-output", default="stdout")
    parser.add_argument("--log-fmt", default="json",
                        choices=["json", "console"])
    parser.add_argument("--cpu-profile", action="store_true",
                        help="write a cProfile dump to /tmp/makisu-tpu.prof")
    parser.add_argument("--transfer-concurrency", type=int, default=0,
                        metavar="N",
                        help="parallel registry transfers (pulls, pushes, "
                             "chunk fetches) across the whole process "
                             "(default 8)")
    parser.add_argument("--transfer-memory-budget", type=int, default=0,
                        metavar="MB",
                        help="cap on transfer bytes resident in memory at "
                             "once, across all parallel transfers "
                             "(default 256)")
    parser.add_argument("--hash-workers", type=int, default=0,
                        metavar="N",
                        help="layer-commit pipeline workers: file "
                             "read-ahead, parallel gear block scans, "
                             "and pooled chunk SHA-256 overlap on N "
                             "threads (default min(8, cpu) on >=4-core "
                             "hosts, serial below; 1 = the serial "
                             "pipeline; env MAKISU_TPU_HASH_WORKERS)")
    parser.add_argument("--compress-workers", type=int, default=0,
                        metavar="N",
                        help="block-parallel compress lanes for the "
                             "pgzip backend (and the native sink's C++ "
                             "block pool); bytes are identical at every "
                             "count (default min(8, cpu); env "
                             "MAKISU_TPU_COMPRESS_WORKERS)")
    parser.add_argument("--hash-linger-ms", type=float, default=-1.0,
                        metavar="MS",
                        help="shared hash-service batch linger in "
                             "milliseconds (worker-mode cross-build "
                             "device batching; default 2; env "
                             "MAKISU_TPU_HASH_LINGER_MS)")
    parser.add_argument("--metrics-out", default="", metavar="FILE",
                        help="write a JSON telemetry report (span tree + "
                             "counters) for this command to FILE")
    parser.add_argument("--events-out", default="", metavar="FILE",
                        help="write this command's build events (JSONL, "
                             "one event per line) to FILE as they happen")
    parser.add_argument("--explain-out", default="", metavar="FILE",
                        help="write this command's cache-decision ledger "
                             "(JSONL, schema makisu-tpu.ledger.v1: one "
                             "line per cache consult with verdict/reason/"
                             "blame, plus a summary line) to FILE — the "
                             "input `makisu-tpu explain` renders")
    parser.add_argument("--history-out", default="", metavar="FILE",
                        help="append one compact build-history record "
                             "(JSONL, schema makisu-tpu.history.v1: "
                             "duration, phase self-times, cache "
                             "economics, ISA route) to FILE after "
                             "build/pull/push commands; without it, "
                             "records land in $MAKISU_TPU_HISTORY_DIR/"
                             "history.jsonl when set — the input "
                             "`makisu-tpu history` renders")
    parser.add_argument("--diag-out", default="", metavar="FILE",
                        help="write a JSON diagnostic bundle (flight-"
                             "recorder ring, open spans, thread stacks, "
                             "resource trajectory) to FILE on failure, "
                             "stall, SIGTERM, or SIGUSR1; without it, "
                             "bundles land in $MAKISU_TPU_DIAG_DIR when "
                             "set (stall/signal dumps fall back to the "
                             "tempdir)")
    parser.add_argument("--stall-timeout", type=float, default=0.0,
                        metavar="SECONDS",
                        help="arm a stall watchdog: when the event bus "
                             "and transfer engine make no progress for "
                             "this long, emit a `stall` event and dump a "
                             "diagnostic bundle (default off; env "
                             "MAKISU_TPU_STALL_TIMEOUT)")
    parser.add_argument("--trace-out", default="", metavar="FILE",
                        help="write a Chrome/Perfetto trace-event JSON of "
                             "this command's span tree to FILE")
    parser.add_argument("--jax-profile", default="", metavar="DIR",
                        help="capture a JAX/XLA profiler trace (xprof) of "
                             "the accelerator hashing path into DIR")
    parser.add_argument("--profile-hz", type=float, default=None,
                        metavar="HZ",
                        help="wall-clock sampling profiler rate for this "
                             "command (default ~67 Hz, env "
                             "MAKISU_TPU_PROFILE_HZ; 0 disables). The "
                             "sampler self-measures its overhead and "
                             "throttles to stay under a 2%% budget")
    parser.add_argument("--profile-out", default="", metavar="FILE",
                        help="write the sampled profile (schema "
                             "makisu-tpu.profile.v1: phase-attributed "
                             "folded stacks + embedded speedscope JSON) "
                             "to FILE when the command finishes — the "
                             "input `makisu-tpu profile` renders")
    sub = parser.add_subparsers(dest="command")

    build = sub.add_parser("build", help="build a docker image")
    build.add_argument("context", help="build context directory")
    build.add_argument("-t", "--tag", required=True,
                       help="image tag (repo:tag)")
    build.add_argument("-f", "--file", default="",
                       help="Dockerfile path (default <context>/Dockerfile)")
    build.add_argument("--push", action="append", default=[],
                       metavar="REGISTRY",
                       help="push the built image to this registry")
    build.add_argument("--replica", action="append", default=[],
                       help="additional tags to save/push")
    build.add_argument("--registry-config", default="",
                       help="registry config file or inline JSON")
    build.add_argument("--dest", default="",
                       help="write a docker-save tar here")
    build.add_argument("--oci-dest", default="",
                       help="write an OCI image layout here (a directory,"
                            " or an oci-archive if the path ends in .tar)"
                            " — consumable by podman/skopeo/containerd")
    build.add_argument("--target", default="",
                       help="build up to this stage only")
    build.add_argument("--build-arg", action="append", default=[],
                       metavar="K=V")
    build.add_argument("--modifyfs", action="store_true",
                       help="allow modifying the local filesystem")
    build.add_argument("--commit", default="implicit",
                       choices=["implicit", "explicit"],
                       help="layer commit policy (#!COMMIT honored in "
                            "explicit mode)")
    build.add_argument("--blacklist", action="append", default=[],
                       help="extra paths to exclude from layers")
    # Reference default: 14 days (bin/makisu/cmd/build.go:113-117).
    build.add_argument("--local-cache-ttl", default="336h")
    build.add_argument("--redis-cache-addr", default="")
    build.add_argument("--redis-cache-password", default="")
    build.add_argument("--http-cache-addr", default="")
    build.add_argument("--http-cache-header", action="append", default=[])
    build.add_argument("--docker-host",
                       default=_docker_env_defaults()["docker_host"])
    build.add_argument("--docker-version",
                       default=_docker_env_defaults()["docker_version"])
    build.add_argument("--load", action="store_true",
                       help="load the image into the local docker daemon")
    build.add_argument("--storage", default="",
                       help="storage directory (default /makisu-storage or "
                            "$HOME fallback)")
    build.add_argument("--storage-budget", type=int, default=None,
                       metavar="MB",
                       help="hot-tier byte budget for the storage dir "
                            "(chunks + blobs); past it, cold objects "
                            "evict LRU after the build — chunks whose "
                            "pack has a compressed twin demote (bytes "
                            "recoverable locally), the rest refetch "
                            "via peers/registry "
                            "(MAKISU_TPU_STORAGE_BUDGET_MB; "
                            "0/unset = unbounded)")
    build.add_argument("--storage-remote", default=None,
                       metavar="DIR",
                       help="remote/object tier directory: cold packs "
                            "demote there and refetch on demand "
                            "(MAKISU_TPU_STORAGE_REMOTE)")
    build.add_argument("--compression", default="default",
                       choices=sorted(tario.COMPRESSION_LEVELS))
    build.add_argument("--gzip-backend", default="zlib",
                       choices=["zlib", "pgzip", "auto"],
                       help="layer compressor: stdlib zlib, the native "
                            "parallel block-deflate (native/libpgzip.so),"
                            " or auto (pgzip when the native library is "
                            "available, else zlib; the RESOLVED backend "
                            "is what enters cache identity)")
    build.add_argument("--preserve-root", action="store_true",
                       help="save and restore / around the build")
    build.add_argument("--root", default="/",
                       help="build filesystem root (testing)")
    build.add_argument("--hasher", default="cpu", choices=["cpu", "tpu"],
                       help="layer hashing backend; tpu adds CDC chunk "
                            "fingerprints for chunk-granular caching")
    build.add_argument("--watch", action="store_true",
                       help="stay resident after the build and rebuild "
                            "whenever context files change (inotify "
                            "when available, mtime-poll fallback); the "
                            "resident build session keeps the stat "
                            "cache, scan memos, and applied-layer "
                            "state warm so each rebuild re-scans and "
                            "re-chunks only dirtied files. Ctrl-C "
                            "exits")
    build.add_argument("--watch-interval", type=float, default=1.0,
                       metavar="SECONDS",
                       help="change-poll interval for --watch "
                            "(default 1.0; inotify hosts poll the "
                            "event queue at this cadence)")

    pull = sub.add_parser("pull", help="pull an image into the store")
    pull.add_argument("image")
    pull.add_argument("--extract", default="",
                      help="untar the pulled rootfs into this directory")
    pull.add_argument("--oci-dest", default="",
                      help="also export the pulled image as an OCI "
                           "layout (directory, or .tar oci-archive)")
    pull.add_argument("--storage", default="")
    pull.add_argument("--registry-config", default="")
    pull.add_argument("--delta", default="", metavar="SOCKET",
                      help="delta pull: layer bytes come from this "
                           "serve endpoint (a `makisu-tpu serve` or "
                           "worker unix socket) as coalesced ranged "
                           "pack fetches of only the chunks missing "
                           "from the local chunk CAS; manifest/config/"
                           "identity still come from the registry, "
                           "and any layer without a published recipe "
                           "falls back to the registry blob route")
    pull.add_argument("--report-out", default="", metavar="FILE",
                      help="write the delta-pull economics report "
                           "(bytes fetched vs full image, per-layer "
                           "routes) as JSON")

    push = sub.add_parser("push", help="push an image tar to registries")
    push.add_argument("tar_path")
    push.add_argument("-t", "--tag", required=True)
    push.add_argument("--push", action="append", default=[],
                      metavar="REGISTRY", dest="registries")
    push.add_argument("--storage", default="")
    push.add_argument("--registry-config", default="")

    diff = sub.add_parser("diff", help="compare two images")
    diff.add_argument("images", nargs=2)
    diff.add_argument("--ignore-modtime", action="store_true")
    diff.add_argument("--storage", default="")
    diff.add_argument("--registry-config", default="")

    worker = sub.add_parser("worker", help="run a long-lived build worker")
    worker.add_argument("--socket", default="/tmp/makisu-tpu-worker.sock",
                        help="unix socket to listen on")
    worker.add_argument("--max-concurrent-builds", type=int, default=0,
                        metavar="N",
                        help="cap concurrently executing builds; "
                             "arrivals beyond the cap wait in a FIFO "
                             "admission queue (instrumented: "
                             "makisu_worker_queue_depth, queue-wait/"
                             "latency histograms, GET /builds). "
                             "0 = unlimited (default; env "
                             "MAKISU_TPU_MAX_CONCURRENT_BUILDS)")
    worker.add_argument("--slo-config", default="", metavar="FILE",
                        help="SLO rule JSON (docs/SLO.md schema): "
                             "merged over the built-in worker rules "
                             "by name; evaluated on a background "
                             "thread, firing alerts at GET /alerts")
    worker.add_argument("--alert-webhook", default="", metavar="URL",
                        help="POST each alert fired/resolved "
                             "transition here as JSON (bounded "
                             "timeout; failures counted, never "
                             "blocking)")
    worker.add_argument("--storage-budget", type=int, default=None,
                        metavar="MB",
                        help="hot-tier byte budget per storage dir "
                             "this worker builds against; enforced "
                             "after each build and on the scrub "
                             "cadence (MAKISU_TPU_STORAGE_BUDGET_MB; "
                             "0/unset = unbounded)")
    worker.add_argument("--storage-remote", default=None,
                        metavar="DIR",
                        help="remote/object tier directory for cold "
                             "pack demotion "
                             "(MAKISU_TPU_STORAGE_REMOTE)")

    serve = sub.add_parser(
        "serve", help="run a chunk-native distribution endpoint over "
                      "a storage directory (signed layer recipes + "
                      "ranged pack serving for delta pulls)")
    serve.add_argument("--socket",
                       default="/tmp/makisu-tpu-serve.sock",
                       help="unix socket to listen on")
    serve.add_argument("--storage", default="",
                       help="storage directory to serve (a builder's "
                            "--storage; recipes/packs under serve/, "
                            "chunk bytes under chunks/)")

    fleet = sub.add_parser(
        "fleet", help="run the build-farm front door: route builds "
                      "across N workers by session affinity")
    fleet.add_argument("--socket",
                       default="/tmp/makisu-tpu-fleet.sock",
                       help="unix socket the front door listens on "
                            "(speaks the worker protocol — existing "
                            "clients/top/loadgen point here "
                            "unchanged)")
    fleet.add_argument("--worker", action="append", default=[],
                       metavar="SOCKET[=STORAGE]",
                       help="one fleet member's worker socket "
                            "(repeat per worker); an optional "
                            "=STORAGE overrides --storage on builds "
                            "forwarded to it (in-process fleets "
                            "modeling per-machine disks)")
    fleet.add_argument("--poll-interval", type=float, default=1.0,
                       metavar="SECONDS",
                       help="worker /healthz + /sessions poll cadence "
                            "(the affinity/liveness signal)")
    fleet.add_argument("--tenant-quota", type=int, default=0,
                       metavar="N",
                       help="per-tenant in-flight build quota at the "
                            "front door; excess builds wait (FIFO) "
                            "and the wait is recorded as a "
                            "quota_denied fleet decision "
                            "(0 = unlimited)")
    fleet.add_argument("--max-inflight-builds", type=int, default=0,
                       metavar="N",
                       help="fleet-wide in-flight cap across all "
                            "tenants (queue-depth backpressure on "
                            "top of the workers' own admission "
                            "queues; 0 = unlimited)")
    fleet.add_argument("--spillover-queue-depth", type=int, default=2,
                       metavar="N",
                       help="load score (queue depth + in-flight) at "
                            "which the consistent-hash owner of a "
                            "new context is passed over for the "
                            "least-loaded worker")
    fleet.add_argument("--slo-config", default="", metavar="FILE",
                       help="SLO rule JSON (docs/SLO.md schema): "
                            "merged over the built-in fleet rules by "
                            "name; evaluated over scheduler stats + "
                            "canary series, served at GET /alerts")
    fleet.add_argument("--alert-webhook", default="", metavar="URL",
                       help="POST each alert fired/resolved "
                            "transition here as JSON")
    fleet.add_argument("--canary-interval", type=float, default=60.0,
                       metavar="SECONDS",
                       help="synthetic canary build cadence: each "
                            "sweep builds one tiny generated context "
                            "end-to-end on every alive worker, "
                            "scoring per-worker health for "
                            "health-demoted routing (0 disables)")
    fleet.add_argument("--canary-slow-seconds", type=float,
                       default=10.0, metavar="SECONDS",
                       help="canary latency past this counts as bad "
                            "(feeds the build_latency_burn rule and "
                            "the health score)")

    alerts_p = sub.add_parser(
        "alerts", help="render a worker's or fleet front door's "
                       "active alerts (GET /alerts)")
    alerts_p.add_argument("socket",
                          help="worker or fleet unix socket to query")
    alerts_p.add_argument("--json", action="store_true",
                          dest="json_out",
                          help="print the raw /alerts JSON payload "
                               "instead of the human render")

    sessions_p = sub.add_parser(
        "sessions", help="inspect a worker's resident build sessions, "
                         "or checkpoint/restore them through the "
                         "chunk-addressed snapshot plane")
    sessions_p.add_argument("socket",
                            help="worker unix socket to query")
    sessions_p.add_argument("verb", nargs="?", default="list",
                            choices=("list", "snapshot", "restore"),
                            help="list sessions (default), snapshot "
                                 "resident sessions to the chunk CAS, "
                                 "or restore/stage a snapshot")
    sessions_p.add_argument("context", nargs="?", default="",
                            help="context dir (optional for snapshot: "
                                 "all sessions; required for restore)")
    sessions_p.add_argument("--from", dest="from_socket", default="",
                            help="restore: pull the recipe from this "
                                 "worker's socket and push it to "
                                 "SOCKET (the fleet prewarm hand-off, "
                                 "by hand)")
    sessions_p.add_argument("--json", action="store_true",
                            dest="json_out",
                            help="print raw JSON payloads")

    top = sub.add_parser(
        "top", help="live terminal view of a worker's (or fleet "
                    "front door's) builds")
    top.add_argument("--socket", default="/tmp/makisu-tpu-worker.sock",
                     help="worker unix socket to poll")
    top.add_argument("--interval", type=float, default=2.0,
                     metavar="SECONDS", help="refresh interval")
    top.add_argument("--once", action="store_true",
                     help="print a single frame and exit (no screen "
                          "clearing; for scripts)")
    top.add_argument("--count", type=int, default=0, metavar="N",
                     help="exit after N frames (0 = until interrupted)")

    loadgen = sub.add_parser(
        "loadgen", help="synthetic concurrent-build load harness "
                        "against a real worker")
    loadgen.add_argument("--socket", default="",
                         help="drive this live worker (default: spawn "
                              "an in-process worker for the run)")
    loadgen.add_argument("--concurrency", type=int, default=4,
                         metavar="N",
                         help="concurrent submission lanes")
    loadgen.add_argument("--builds", type=int, default=0, metavar="M",
                         help="total builds to run (default "
                              "2 x concurrency)")
    loadgen.add_argument("--contexts", type=int, default=0,
                         metavar="K",
                         help="distinct generated context templates "
                              "(default = concurrency, capped at it)")
    loadgen.add_argument("--files", type=int, default=16,
                         help="files per generated context")
    loadgen.add_argument("--file-kb", type=int, default=4,
                         help="KiB per generated file")
    loadgen.add_argument("--edit-churn", type=float, default=0.25,
                         metavar="FRACTION",
                         help="fraction of a lane's files append-"
                              "edited before each rebuild")
    loadgen.add_argument("--tenants", default="tenant-a,tenant-b",
                         help="comma-separated tenant mix, assigned "
                              "to lanes round-robin")
    loadgen.add_argument("--hasher", default="tpu",
                         choices=["cpu", "tpu"],
                         help="hashing backend for the synthetic "
                              "builds (tpu exercises chunk dedup + "
                              "the shared hash service)")
    loadgen.add_argument("--max-concurrent-builds", type=int,
                         default=0, metavar="N",
                         help="admission cap for the SPAWNED worker "
                              "(ignored with --socket)")
    loadgen.add_argument("--report", default="", metavar="FILE",
                         help="write the structured JSON report "
                              "(schema makisu-tpu.loadgen.v1) here")
    loadgen.add_argument("--work-dir", default="",
                         help="working directory for contexts/storage "
                              "(default: a tempdir, removed after)")
    loadgen.add_argument("--poll-interval", type=float, default=0.5,
                         metavar="SECONDS",
                         help="/healthz + /builds sampling interval")
    loadgen.add_argument("--ready-timeout", type=float, default=30.0,
                         metavar="SECONDS",
                         help="how long to wait for the worker's "
                              "/ready")
    loadgen.add_argument("--fleet", action="store_true",
                         help="fleet mode: spawn --workers in-process "
                              "workers behind the front-door "
                              "scheduler (plus a shared cache KV and "
                              "a single-worker baseline), drive "
                              "repeated same-context builds through "
                              "it, and report per-worker build "
                              "distribution, affinity hit-rate, "
                              "p99-vs-single-worker delta, drain-"
                              "driven peer chunk exchange, and a "
                              "mid-run worker kill's failover")
    loadgen.add_argument("--workers", type=int, default=3,
                         metavar="N",
                         help="fleet mode: in-process workers behind "
                              "the scheduler")
    loadgen.add_argument("--tenant-quota", type=int, default=1,
                         metavar="N",
                         help="fleet mode: per-tenant in-flight "
                              "quota at the front door (0 disables "
                              "the quota-enforcement phase)")
    loadgen.add_argument("--rounds", type=int, default=0,
                         metavar="R",
                         help="fleet mode: builds per context "
                              "(default 3; >= 3 so the warmup, "
                              "drain, and kill phases each get a "
                              "round)")
    loadgen.add_argument("--slo-smoke", action="store_true",
                         help="SLO fault-injection scenario: a "
                              "3-worker fleet with fast canary/"
                              "evaluation intervals, one worker "
                              "wedged via a held admission slot; "
                              "asserts the latency burn-rate alert "
                              "fires, routing shifts away "
                              "(health_demoted in the route ledger), "
                              "canary digests stay identical on "
                              "healthy workers, and the alert "
                              "resolves after the fault clears")
    loadgen.add_argument("--alert-events-out", default="",
                         metavar="FILE",
                         help="slo-smoke: write the alert transitions "
                              "(fired/resolved) as an alert-only "
                              "NDJSON file — the CI artifact")
    loadgen.add_argument("--evict-soak", action="store_true",
                         help="eviction soak scenario: the same "
                              "edited-rebuild stream against a "
                              "tiny-budget storage and an unbudgeted "
                              "oracle; asserts evictions fire, disk "
                              "high-water reaches steady state, "
                              "every round's digests match the "
                              "oracle byte for byte, and the "
                              "post-soak scrub finds zero corruption")
    loadgen.add_argument("--prewarm-smoke", action="store_true",
                         help="session-snapshot recovery scenario: a "
                              "worker is killed (no teardown) after a "
                              "resident warm build and a fresh worker "
                              "over the same storage must rebuild "
                              "warm_mode=restored, byte-identical, "
                              "within 2x of the resident floor; then "
                              "a 2-worker fleet drains a session "
                              "holder and the next build must land "
                              "on the prewarmed survivor")

    history = sub.add_parser(
        "history", help="render build-history trends, or `history "
                        "diff A B` to gate on regressions")
    history.add_argument("history_args", nargs="+",
                         metavar="PATH | diff A B",
                         help="history JSONL file(s) or directory "
                              "(rendered as a trend); or `diff A B` "
                              "to compare candidate B against "
                              "baseline A (exit 1 on a flagged "
                              "regression)")
    history.add_argument("--threshold", type=float, default=0.25,
                         metavar="FRACTION",
                         help="diff regression threshold: flag p50/"
                              "p99 latency growth or hit/dedup-ratio "
                              "drops beyond this fraction "
                              "(default 0.25)")
    history.add_argument("--limit", type=int, default=20,
                         help="records shown in the trend view")

    report = sub.add_parser(
        "report", help="critical-path analysis of a telemetry report")
    report.add_argument("metrics_file",
                        help="a --metrics-out JSON report OR a "
                             "diagnostic bundle (--diag-out) to "
                             "analyze; with --fleet, a merged events "
                             "JSONL (the fleet front door's "
                             "--events-out) instead")
    report.add_argument("--events", default="", metavar="FILE",
                        help="an --events-out JSONL log to include "
                             "(torn final lines of killed builds are "
                             "salvaged)")
    report.add_argument("--fleet", action="store_true",
                        help="cross-process fleet analysis: treat the "
                             "input as a merged event log (front-door "
                             "spans + teed worker events), assemble "
                             "one span tree per trace id across "
                             "processes, and render the cross-process "
                             "critical path (front-door quota wait vs "
                             "worker queue wait vs build phases, "
                             "failover attempts as sibling subtrees); "
                             "the top-level --trace-out writes the "
                             "merged Perfetto export")
    report.add_argument("--profile", default="", metavar="FILE",
                        help="with --fleet: a makisu-tpu.profile.v1 "
                             "artifact (e.g. `profile --fleet --out`) "
                             "to render beside the span analysis — "
                             "the sampled where-did-the-cycles-go "
                             "view next to the declared one")

    explain = sub.add_parser(
        "explain", help="chunk-level cache miss attribution from a "
                        "build's decision ledger")
    explain.add_argument("ledger",
                         help="an --explain-out JSONL ledger (an "
                              "--events-out log containing "
                              "cache_decision events also works)")
    explain.add_argument("--baseline", default="", metavar="LEDGER",
                         help="a previous build's ledger: render the "
                              "build-to-build diff (keys that flipped "
                              "hit→miss, file-level blame, re-chunked "
                              "byte delta) instead of single-build "
                              "attribution")
    explain.add_argument("--metrics", default="", metavar="FILE",
                         help="the matching --metrics-out report: adds "
                              "the warm-rebuild floor profile "
                              "(irreducible vs cache-avoidable wall "
                              "time per phase)")

    check = sub.add_parser(
        "check", help="repo-invariant static analysis: the six rules "
                      "distilled from shipped bugs (see "
                      "docs/ANALYSIS.md); exits 1 on any finding not "
                      "in the committed baseline")
    check.add_argument("paths", nargs="*", metavar="PATH",
                       help="files/directories to scan (default: the "
                            "makisu_tpu package)")
    check.add_argument("--json", action="store_true", dest="json_out",
                       help="machine-readable output: one JSON object "
                            "with findings/suppressed/baseline (the CI "
                            "gate's artifact)")
    check.add_argument("--update-baseline", action="store_true",
                       help="rewrite the baseline to the current "
                            "finding set (review the diff!) and exit 0")
    check.add_argument("--baseline", default="", metavar="FILE",
                       help="baseline file (default: the committed "
                            "makisu_tpu/analysis/baseline.json)")
    check.add_argument("--rule", action="append", default=[],
                       metavar="NAME",
                       help="run only this rule (repeatable)")

    doctor = sub.add_parser(
        "doctor", help="diagnose a failure-forensics bundle, or the "
                       "device route (--device)")
    doctor.add_argument("bundle", nargs="?", default="",
                        help="a diagnostic bundle JSON (written by "
                             "--diag-out, the stall watchdog, or the "
                             "SIGTERM/SIGUSR1 handlers); with "
                             "--device, the deviceprobe ledger file "
                             "or sessions directory instead")
    doctor.add_argument("--device", action="store_true",
                        help="cross-session device-route diagnosis "
                             "from the makisu-tpu.deviceprobe.v1 "
                             "ledger: dominant wedge phase/frame, "
                             "per-attachment verdict history, last "
                             "healthy window (default ledger: "
                             "$MAKISU_TPU_DEVICE_SESSIONS_DIR or "
                             "benchmarks/device_sessions)")
    doctor.add_argument("--fleet", action="store_true",
                        help="cross-worker fleet diagnosis: poll the "
                             "front door's /healthz at the given "
                             "SOCKET and name dead/draining workers, "
                             "stale peer-map acks, tenants pinned at "
                             "their quota, and placement-memo drift "
                             "vs actual session residency")
    doctor.add_argument("--storage", action="store_true",
                        help="storage-plane diagnosis: census + "
                             "reference audit + integrity scrub of "
                             "the four content planes (blob CAS, "
                             "chunk CAS, packs, recipes). TARGET is "
                             "a worker control socket (remote "
                             "report) or a storage dir (local walk; "
                             "default: the standard storage dir). "
                             "Exit 1 when findings exist")
    doctor.add_argument("--repair", action="store_true",
                        help="with --storage on a DIRECTORY target: "
                             "delete verified-orphaned zpack twins "
                             "(without this flag the repair is a "
                             "dry-run listing)")
    doctor.add_argument("--eviction-budget", type=int, default=None,
                        metavar="BYTES",
                        help="with --storage: publish an eviction "
                             "dry-run — what LRU eviction down to "
                             "this byte budget would remove and how "
                             "many bytes it would free (refused "
                             "while the chunk CAS LRU seed is "
                             "incomplete)")

    du = sub.add_parser(
        "du", help="storage census: per-plane object counts, byte "
                   "totals, age histogram, per-tenant attribution")
    du.add_argument("--storage", default="",
                    help="storage directory (default: the standard "
                         "storage dir)")
    du.add_argument("--json", action="store_true", dest="json_out",
                    help="machine-readable census document "
                         "(makisu-tpu.census.v1)")

    profile = sub.add_parser(
        "profile", help="render, capture, diff, and aggregate "
                        "wall-clock sampling profiles "
                        "(makisu-tpu.profile.v1)")
    profile.add_argument("target", nargs="*", default=[],
                         help="a profile artifact to render; "
                              "`diff BASELINE CANDIDATE` to attribute "
                              "a regression to the frames whose "
                              "self-time share grew; with --fleet, the "
                              "front door socket/address to capture "
                              "a merged cross-worker profile from")
    profile.add_argument("--top", type=int, default=10,
                         help="functions to list per table (default 10)")
    profile.add_argument("--threshold", type=float, default=0.1,
                         metavar="FRACTION",
                         help="diff: flag frames whose self-time share "
                              "grew by more than this fraction of "
                              "total samples (default 0.1 = ten "
                              "share points); exit 1 when any do")
    profile.add_argument("--flame", default="", metavar="FILE",
                         help="also write a self-contained flamegraph "
                              "HTML (phase-colored icicle) to FILE")
    profile.add_argument("--fleet", action="store_true",
                         help="TARGET is a fleet front door: ask every "
                              "alive worker for an on-demand "
                              "--seconds capture window and render "
                              "the merged profile")
    profile.add_argument("--seconds", type=float, default=5.0,
                         help="capture window for --fleet (default 5)")
    profile.add_argument("--out", default="", metavar="FILE",
                         help="also write the (merged) profile "
                              "artifact to FILE")

    sub.add_parser("version", help="print the build version")
    return parser, build


def _docker_env_defaults() -> dict[str, str]:
    return {"docker_host": os.environ.get("DOCKER_HOST",
                                          "unix:///var/run/docker.sock"),
            "docker_version": os.environ.get("DOCKER_VERSION", "1.21")}


# The process's one parser tree: ~150 ``add_argument``s and, through
# argparse's ``_()``, some sixty ``stat``s a tree (``gettext.find``),
# which a worker paid three times a request. argparse does not promise
# that one parser parses on two threads at once, and the environment's
# two defaults are set on the tree before each parse: both under the
# lock (a parse is ~0.2 ms).
_shared_tree: tuple[argparse.ArgumentParser,
                    argparse.ArgumentParser] | None = None
_parse_lock = threading.Lock()


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    """``argv`` parsed by the parser the process built once. Raises
    ``SystemExit`` with argparse's message on the standard error for a
    malformed ``argv``, as ``make_parser().parse_args`` does."""
    global _shared_tree
    with _parse_lock:
        if _shared_tree is None:
            _shared_tree = _parser_tree()
        parser, build = _shared_tree
        build.set_defaults(**_docker_env_defaults())
        args = parser.parse_args(argv)
    metrics.counter_add(metrics.REQUEST_RESOLVE_TOTAL, kind="parse",
                        result="done")
    return args


def _storage_dir(flag: str) -> str:
    if flag:
        return flag
    if os.path.isdir(os.path.dirname(pathutils.DEFAULT_STORAGE_DIR) or "/") \
            and os.access("/", os.W_OK):
        return pathutils.DEFAULT_STORAGE_DIR
    return os.path.join(os.path.expanduser("~"), ".makisu-tpu-storage")


def _parse_build_args(pairs: list[str]) -> dict[str, str]:
    out = {}
    for pair in pairs:
        key, sep, val = pair.partition("=")
        if not sep:
            val = os.environ.get(key, "")
        out[key] = val
    return out


def _new_cache_manager(args, store, registry_client=None):
    from makisu_tpu.cache import CacheManager, FSStore, HTTPStore, RedisStore
    from makisu_tpu.dockerfile import parse_duration
    ttl = parse_duration(args.local_cache_ttl) / 1e9
    if args.redis_cache_addr:
        kv = RedisStore(args.redis_cache_addr, ttl,
                        args.redis_cache_password)
    elif args.http_cache_addr:
        headers = dict(h.split(":", 1) for h in args.http_cache_header)
        kv = HTTPStore(args.http_cache_addr, headers)
    elif args.local_cache_ttl in ("0", "0s"):
        return None
    else:
        kv = FSStore(os.path.join(store.root,
                                  pathutils.CACHE_KV_FILE_NAME), ttl)
    return CacheManager(kv, store, registry_client=registry_client)


def cmd_build(args) -> int:
    from makisu_tpu.storage import contentstore
    storage_dir = _storage_dir(args.storage)
    if getattr(args, "storage_budget", None) is not None:
        # Per-dir override, not a process-global: a worker runs many
        # builds against many dirs, and one build's flag must not
        # rebudget its neighbors.
        contentstore.set_budget_for(storage_dir,
                                    max(0, args.storage_budget) << 20)
    if getattr(args, "storage_remote", None) is not None:
        contentstore.configure(remote=args.storage_remote)
    if getattr(args, "watch", False):
        if invocation_mode.get() == "worker":
            # A worker build runs on a handler thread; an endless
            # watch loop would pin it (and its session lease) forever.
            # The worker process is already resident — repeat
            # submissions get warm rebuilds without watching.
            log.warning("--watch is ignored in worker mode (the "
                        "worker itself is the resident process)")
        else:
            return _watch_loop(args)
    code = _build_once(args)
    # Enforce the byte budget at the moment disk grew (throttled;
    # no-op unbudgeted; never fails a finished build).
    contentstore.store_for(storage_dir).maybe_evict()
    return code


def _watch_loop(args) -> int:
    """``build --watch``: build, then stay resident and rebuild on
    every context change. Change detection rides the build session's
    dirty tracker (inotify when available); without a session (
    MAKISU_TPU_SESSION=0) a standalone mtime-walk snapshot polls. A
    failed rebuild keeps watching — the next edit gets its chance."""
    import importlib
    import time as time_mod

    from makisu_tpu.worker import session as session_mod
    walk_mod = importlib.import_module("makisu_tpu.snapshot.walk")

    interval = max(0.1, getattr(args, "watch_interval", 1.0))
    context_dir = os.path.abspath(args.context)
    # The standalone (session-less) poll must ignore the build's own
    # output dirs — a storage/root nested inside the context would
    # otherwise re-trigger a rebuild forever.
    poll_blacklist = [os.path.abspath(_storage_dir(args.storage)),
                      os.path.abspath(args.root)]

    def safe_build() -> int:
        """One rebuild that can never unwind the loop: a momentarily
        broken Dockerfile or a half-renamed COPY source is the normal
        rhythm of watch-mode editing — report, keep watching."""
        try:
            return _build_once(args)
        except KeyboardInterrupt:
            raise
        except SystemExit as e:
            log.error("watch: build exited: %s", e.code)
            return e.code if isinstance(e.code, int) else 1
        except Exception as e:  # noqa: BLE001 - watch must survive
            log.error("watch: build failed: %s", e)
            return 1

    code = safe_build()
    builds = 1
    snapshot = None
    log.info("watch: initial build exited %d; watching %s "
             "(interval %.1fs, Ctrl-C to exit)", code, context_dir,
             interval)
    try:
        while True:
            session = session_mod.manager().peek(context_dir)
            if session is not None:
                dirt = session.poll_changes()
            else:
                try:
                    if snapshot is None:
                        snapshot = walk_mod.snapshot_tree(
                            context_dir, poll_blacklist)
                        dirt = set()
                    else:
                        snapshot, delta = walk_mod.snapshot_delta(
                            snapshot, poll_blacklist)
                        dirt = delta.real_dirty
                except OSError:
                    # Context churned mid-walk (or vanished briefly):
                    # re-baseline next tick instead of dying.
                    snapshot = None
                    dirt = set()
            if dirt:
                sample = sorted(dirt)[:3]
                log.info("watch: %d paths changed (%s); rebuilding",
                         len(dirt), ", ".join(
                             os.path.relpath(p, context_dir)
                             for p in sample))
                code = safe_build()
                builds += 1
                log.info("watch: rebuild #%d exited %d", builds, code)
                snapshot = None  # re-baseline the standalone poll
            else:
                time_mod.sleep(interval)
    except KeyboardInterrupt:
        # A terminal Ctrl-C is delivered to the whole process group —
        # a second interrupt may land mid-log; exit quietly either way.
        try:
            log.info("watch: stopped after %d builds", builds)
        except KeyboardInterrupt:
            pass
        return code


def _build_once(args) -> int:
    # Named before the try whose finally releases them, so that the
    # release covers the set-up span's own close as well.
    store = ctx = preserver = build_session = None
    build_ok = False
    storage_dir = _storage_dir(args.storage)
    abs_context = os.path.abspath(args.context)
    try:
        try:
            # What a build does before its plan exists: the builder's
            # modules imported (a process's first build), flags, the
            # Dockerfile, the image store opened, the context, the cache
            # manager (and the chunk store), the session's lease.
            with metrics.span("build_setup"):
                from makisu_tpu.builder import BuildPlan
                from makisu_tpu.cache import NoopCacheManager
                from makisu_tpu.chunker import get_hasher
                from makisu_tpu.context import BuildContext
                from makisu_tpu.docker.image import ImageName
                from makisu_tpu.dockerfile import parse_file
                from makisu_tpu.registry import load_config_map, new_client
                from makisu_tpu.storage import ImageStore

                # Per-build registry config (never the process-global map:
                # builds in one worker may carry different
                # --registry-config flags).
                registry_config_map = (load_config_map(args.registry_config)
                                      if args.registry_config else None)
                # Validated per-build compression identity: threaded through
                # the BuildContext rather than tario's process globals, so
                # concurrent builds in one worker can use different flags.
                # `auto` resolves to a concrete backend HERE (logged once
                # per build) — only concrete backends enter cache identity.
                gzip_backend = tario.resolve_backend(args.gzip_backend)
                if args.gzip_backend == "auto":
                    log.info("gzip backend auto-selected: %s", gzip_backend)
                gzip_backend_id = tario.make_backend_id(gzip_backend,
                                                        args.compression)
                blacklist = list(pathutils.DEFAULT_BLACKLIST)
                for extra in args.blacklist:
                    if extra not in blacklist:
                        blacklist.append(extra)

                dockerfile_path = args.file or os.path.join(args.context,
                                                            "Dockerfile")
                with open(dockerfile_path) as f:
                    stages = parse_file(f.read(),
                                        _parse_build_args(args.build_arg))

                target = ImageName.parse(args.tag)
                replicas = [ImageName.parse(r) for r in args.replica]

                store = ImageStore(storage_dir)
                ctx = BuildContext(args.root, abs_context,
                                   store, hasher=get_hasher(args.hasher),
                                   blacklist=blacklist,
                                   gzip_backend_id=gzip_backend_id)
                # The first push registry doubles as the cache's blob/chunk
                # transfer plane (the reference's registryCacheManager pulls
                # cached layers through the push registry the same way,
                # lib/cache/cache_manager.go:116-182): a KV hit from another
                # builder is materializable from there — lazily, and at
                # chunk granularity when the TPU hasher indexed the layer.
                cache_registry = None
                if args.push:
                    cache_registry = new_client(
                        store, target.with_registry(args.push[0]),
                        config_map=registry_config_map)
                cache_mgr = (_new_cache_manager(args, store, cache_registry)
                             or NoopCacheManager())
                if args.hasher == "tpu" and not isinstance(cache_mgr,
                                                           NoopCacheManager):
                    from makisu_tpu.cache.chunks import attach_chunk_dedup
                    attach_chunk_dedup(cache_mgr,
                                       os.path.join(store.root, "chunks"))
                if args.preserve_root and args.modifyfs:
                    from makisu_tpu.storage.root_preserver import RootPreserver
                    preserver = RootPreserver(args.root, store.sandbox_dir,
                                              ctx.blacklist)
                # Resident build session: lease (or mint) the warm state for
                # this context + resolved-flag identity. A reused session
                # arms the context with the dirty set, the scan memo, and
                # the resident statcache/layer state; every outcome lands on
                # the decision ledger (source=session) and the warm_mode
                # history label. Leased LAST in the set-up, inside the
                # try whose finally releases it — a lease the finally
                # did not cover would leak the session busy forever.
                from makisu_tpu.utils import ledger as ledger_mod
                from makisu_tpu.worker import session as session_mod
                if session_mod.enabled():
                    # The restore spec (storage dir + PORTABLE flag
                    # identity) lets a cold acquire consult the
                    # chunk-addressed snapshot plane: same logical build,
                    # any worker — the fleet front door rewrites --storage
                    # per worker, which is exactly why the portable identity
                    # excludes it.
                    build_session, verdict = session_mod.manager().acquire(
                        abs_context, session_mod.identity_from_build_args(
                            args, storage_dir, gzip_backend_id),
                        restore_spec=(
                            storage_dir,
                            session_mod.portable_identity_from_build_args(
                                args, gzip_backend_id)))
                else:
                    verdict = "disabled"
            if build_session is not None:
                mode = build_session.begin_build(
                    ctx,
                    resident_process=(
                        invocation_mode.get() == "worker"
                        or bool(getattr(args, "watch", False))))
                session_mod.set_warm_mode(
                    mode if verdict in ("hit", "restored")
                    else "fresh")
                ledger_mod.record(
                    "session", abs_context, verdict,
                    reason=("reused" if verdict == "hit"
                            else "restored" if verdict == "restored"
                            else "created"),
                    mode=mode, dirty=len(ctx.dirty_paths),
                    resident_bytes=build_session.resident_bytes())
            else:
                session_mod.set_warm_mode("off")
                ledger_mod.record("session", abs_context, "miss",
                                  reason=verdict)
            plan = BuildPlan(ctx, target, replicas, cache_mgr, stages,
                             allow_modify_fs=args.modifyfs,
                             force_commit=(args.commit == "implicit"),
                             stage_target=args.target,
                             registry_client=_FromPuller(
                                 store, registry_config_map))
            manifest = plan.execute()
            build_ok = True
        finally:
            if preserver is not None:
                preserver.restore()
            if build_session is not None:
                # A failed build de-certifies the dirty set (the next
                # build re-scans); a successful one re-arms the
                # watcher/snapshot so the next rebuild is O(dirty).
                build_session.finish_build(ctx, build_ok)
                session_mod.manager().release(build_session)
        log.info("successfully built image %s", target)

        # Lazily-pulled cache hits hold no local blob; pushes
        # materialize per-blob only when the target registry can't
        # HEAD-skip (the materialize_blob hook), export paths need every
        # byte (materialize_pending below).
        materializer = getattr(cache_mgr, "materialize", None)
        push_jobs = [(image, registry)
                     for registry in args.push
                     for image in (target, *replicas)]

        def push_one(job):
            image, registry = job
            name = image.with_registry(registry)
            client = new_client(store, name,
                                config_map=registry_config_map)
            client.materialize_blob = materializer
            client.push(name if name.registry else image)
            log.info("successfully pushed %s to %s", name, registry)

        if len(push_jobs) == 1:
            push_one(push_jobs[0])
        elif push_jobs:
            # Image-level fan-out across registries/replicas runs on
            # its own small pool; the blob transfers inside each push
            # share the transfer engine's global concurrency and
            # memory budget (a dedicated outer pool keeps the engine's
            # blob tasks leaves — the tier rule in registry/transfer).
            from concurrent.futures import ThreadPoolExecutor
            with ThreadPoolExecutor(min(4, len(push_jobs))) as pool:
                concurrency.ctx_map(pool, push_one, push_jobs)
        if args.dest or args.oci_dest or args.load:
            cache_mgr.materialize_pending()
        if args.dest:
            from makisu_tpu.docker.save import write_save_tar
            write_save_tar(store, target, args.dest)
            log.info("saved image tar to %s", args.dest)
        if args.oci_dest:
            from makisu_tpu.docker.oci import write_oci_layout
            digest = write_oci_layout(store, target, args.oci_dest)
            log.info("saved OCI layout to %s (manifest %s)",
                     args.oci_dest, digest)
        if args.load:
            from makisu_tpu.docker.daemon import DockerClient
            from makisu_tpu.docker.save import write_save_tar
            tar_path = os.path.join(store.sandbox_dir, "load.tar")
            write_save_tar(store, target, tar_path)
            DockerClient(args.docker_host,
                         args.docker_version).image_tar_load(tar_path)
            log.info("loaded image into docker daemon")
    finally:
        if store is not None:
            with metrics.span("build_teardown"):
                store.cleanup_sandbox()
    log.info("finished building %s", target)
    return 0


class _FromPuller:
    """Registry access for FROM steps: resolves a client per image name
    and saves manifests under the image's own name."""

    def __init__(self, store, config_map=None) -> None:
        self.store = store
        self.config_map = config_map

    def pull(self, name):
        from makisu_tpu.registry import new_client
        return new_client(self.store, name,
                          config_map=self.config_map).pull(name)

    def start_pull(self, name):
        """Pipelined variant: FROM layer downloads run ahead on the
        transfer engine while extraction applies them in order."""
        from makisu_tpu.registry import new_client
        return new_client(self.store, name,
                          config_map=self.config_map).start_pull(name)


def cmd_pull(args) -> int:
    from makisu_tpu.docker.image import ImageName
    from makisu_tpu.registry import load_config_map, new_client
    from makisu_tpu.storage import ImageStore

    # Per-command map, not update_global_config: the worker serves
    # pull/push/diff concurrently with builds, and mutating the
    # process-global map would race other requests' config_for lookups.
    config_map = (load_config_map(args.registry_config)
                  if args.registry_config else None)
    name = ImageName.parse_for_pull(args.image)
    with ImageStore(_storage_dir(args.storage)) as store:
        if args.delta:
            from makisu_tpu.serve import pull_image_delta
            client = new_client(store, name, config_map=config_map)
            manifest, report = pull_image_delta(client, store, name,
                                                args.delta)
        else:
            client = new_client(store, name, config_map=config_map)
            # Snapshot which layers are already local BEFORE the pull:
            # pull_layer no-ops on present blobs, and the report must
            # say so (route "local", zero wire bytes) the same way the
            # delta report does for the same warm store. The snapshot
            # costs an extra manifest GET, so it only runs when a
            # report was actually asked for.
            local: set[str] = set()
            if args.report_out:
                pre = client.pull_manifest(name.tag)
                local = {d.digest.hex() for d in pre.layers
                         if store.layers.exists(d.digest.hex())}
            manifest = client.pull(name)
            if args.report_out:
                # Shared builder with the delta report, so a consumer
                # pointed at either file reads one shape. Repeated
                # digests dedup exactly like pull_image_delta's walk,
                # so the two reports agree on layer count and
                # denominator for the same image.
                from makisu_tpu.serve.client import build_pull_report
                uniq: dict[str, int] = {}
                for d in manifest.layers:
                    uniq.setdefault(d.digest.hex(), d.size)
                report = build_pull_report(name, "", [
                    {"layer": hx,
                     "route": "local" if hx in local else "blob",
                     "size": size,
                     "bytes_fetched": 0 if hx in local else size}
                    for hx, size in uniq.items()])
        if args.report_out:
            from makisu_tpu.utils import fileio
            fileio.write_json_atomic(args.report_out, report)
        log.info("pulled %s (%d layers)", name, len(manifest.layers))
        if args.oci_dest:
            from makisu_tpu.docker.oci import write_oci_layout
            digest = write_oci_layout(store, name, args.oci_dest)
            log.info("saved OCI layout to %s (manifest %s)",
                     args.oci_dest, digest)
        if args.extract:
            from makisu_tpu.snapshot import MemFS
            os.makedirs(args.extract, exist_ok=True)
            fs = MemFS(args.extract, blacklist=[])
            for desc in manifest.layers:
                fs.update_from_tar_path(store.layers.path(desc.digest.hex()),
                                        untar=True)
            log.info("extracted rootfs to %s", args.extract)
    return 0


def cmd_push(args) -> int:
    from makisu_tpu.docker.image import ImageName
    from makisu_tpu.docker.save import load_save_tar
    from makisu_tpu.registry import load_config_map, new_client
    from makisu_tpu.storage import ImageStore

    config_map = (load_config_map(args.registry_config)
                  if args.registry_config else None)
    name = ImageName.parse(args.tag)
    with ImageStore(_storage_dir(args.storage)) as store:
        load_save_tar(store, args.tar_path, name)
        registries = args.registries or [name.registry]
        if not all(registries):
            raise SystemExit("no registry to push to (use --push)")

        def push_to(registry):
            target = name.with_registry(registry)
            store.manifests.save(target, store.manifests.load(name))
            new_client(store, target, config_map=config_map).push(target)
            log.info("pushed %s", target)

        if len(registries) == 1:
            push_to(registries[0])
        else:
            from concurrent.futures import ThreadPoolExecutor
            with ThreadPoolExecutor(min(4, len(registries))) as pool:
                concurrency.ctx_map(pool, push_to, registries)
    return 0


def cmd_diff(args) -> int:
    import tempfile

    from makisu_tpu.docker.image import ImageName
    from makisu_tpu.registry import load_config_map, new_client
    from makisu_tpu.snapshot import MemFS
    from makisu_tpu.storage import ImageStore

    config_map = (load_config_map(args.registry_config)
                  if args.registry_config else None)
    with ImageStore(_storage_dir(args.storage)) as store:
        trees = []
        configs = []
        for image in args.images:
            name = ImageName.parse_for_pull(image)
            manifest = new_client(store, name,
                                  config_map=config_map).pull(name)
            with store.layers.open(manifest.config.digest.hex()) as f:
                import json as json_mod

                configs.append(json_mod.load(f))
            root = tempfile.mkdtemp(dir=store.sandbox_dir)
            fs = MemFS(root, blacklist=[])
            for desc in manifest.layers:
                fs.update_from_tar_path(
                    store.layers.path(desc.digest.hex()), untar=False)
            trees.append(fs)
        # Whole-config deep diff (reference: cmd/diff.go:117-120 go-cmp's
        # the entire config object, so architecture/os/rootfs differences
        # surface, not just config.* fields).
        c1, c2 = configs
        for line in _deep_diff(c1, c2):
            print(line)
        diff = trees[0].compare(trees[1],
                                ignore_mtime=args.ignore_modtime)
        for p in diff.missing_in_first:
            print(f"only in {args.images[1]}: {p}")
        for p in diff.missing_in_second:
            print(f"only in {args.images[0]}: {p}")
        for p, h1, h2 in diff.different:
            print(f"differs: {p} "
                  f"[{h1.mode:o} {h1.uid}:{h1.gid} {h1.size}] vs "
                  f"[{h2.mode:o} {h2.uid}:{h2.gid} {h2.size}]")
    return 0


def _deep_diff(a, b, path: str = "") -> list[str]:
    """Recursive structural diff of two JSON-ish values, one line per
    differing leaf (analog of the reference's go-cmp report)."""
    if isinstance(a, dict) and isinstance(b, dict):
        lines = []
        for key in sorted(set(a) | set(b)):
            sub = f"{path}.{key}" if path else key
            if key not in a:
                lines.append(f"{sub}: <absent> != {b[key]!r}")
            elif key not in b:
                lines.append(f"{sub}: {a[key]!r} != <absent>")
            else:
                lines.extend(_deep_diff(a[key], b[key], sub))
        return lines
    if a != b:
        return [f"{path or '<root>'}: {a!r} != {b!r}"]
    return []


def cmd_report(args) -> int:
    """Critical-path analysis of a build's telemetry: where the wall
    time went, what to attack first. Input is a ``--metrics-out`` JSON
    report (and optionally the matching ``--events-out`` log) — or a
    diagnostic bundle from a build that died mid-flight, whose embedded
    metrics snapshot is analyzed instead: completed spans get phase
    self-times, open spans are marked with their age at capture."""
    import json as json_mod

    from makisu_tpu.utils import events as events_mod
    from makisu_tpu.utils import flightrecorder, traceexport

    if args.fleet:
        # Cross-process mode: the input is a merged event log — the
        # fleet front door's --events-out (its own spans + the teed
        # worker build events). Torn logs salvage like everywhere.
        try:
            event_log = events_mod.read_jsonl(args.metrics_file)
        except ValueError as e:
            log.warning("%s; analyzing the valid lines only", e)
            event_log = events_mod.read_jsonl(args.metrics_file,
                                              skip_invalid=True)
        assembled = traceexport.assemble_fleet_trace(event_log)
        if not assembled["traces"]:
            raise SystemExit(
                f"{args.metrics_file}: no span events to assemble "
                f"(expected a fleet --events-out log with "
                f"span_start/span_end lines)")
        fleet_profile = None
        if getattr(args, "profile", ""):
            from makisu_tpu.utils import profiler as profiler_mod
            try:
                fleet_profile = profiler_mod.read_artifact(args.profile)
            except ValueError as e:
                log.error("%s", e)
                raise SystemExit(2)
        print(traceexport.render_fleet_report(assembled,
                                              profile=fleet_profile),
              end="")
        if args.trace_out:
            metrics.write_json_atomic(
                args.trace_out,
                traceexport.fleet_perfetto_trace(assembled))
            log.info("merged fleet trace written to %s",
                     args.trace_out)
            # cli.main's generic trace write would clobber the merged
            # export with this report invocation's (empty) span tree.
            args.trace_out = ""
        return 0
    with open(args.metrics_file, encoding="utf-8") as f:
        report = json_mod.load(f)
    capture_ts = None
    if report.get("schema") == flightrecorder.BUNDLE_SCHEMA:
        bundle, report = report, report.get("metrics")
        capture_ts = bundle.get("ts")
        if report is None:
            raise SystemExit(
                f"{args.metrics_file}: bundle carries no metrics "
                f"snapshot (the dying process held the registry lock); "
                f"try `makisu-tpu doctor` for the thread/span forensics")
    if report.get("schema") != "makisu-tpu.metrics.v1":
        raise SystemExit(
            f"{args.metrics_file}: not a makisu-tpu metrics report "
            f"(schema {report.get('schema')!r})")
    event_log = None
    if args.events:
        try:
            event_log = events_mod.read_jsonl(args.events)
        except ValueError as e:
            # A build killed mid-write leaves one torn final line —
            # exactly the case a post-mortem report is FOR. Analyze
            # the valid prefix instead of dying.
            log.warning("%s; analyzing the valid lines only", e)
            event_log = events_mod.read_jsonl(args.events,
                                              skip_invalid=True)
    print(traceexport.render_report(report, event_log,
                                    capture_ts=capture_ts), end="")
    return 0


def cmd_explain(args) -> int:
    """Render a cache-decision ledger: which node broke the cache
    chain and which files broke it (default), what flipped between two
    builds (``--baseline``), and where the warm-rebuild floor actually
    goes (``--metrics``). Torn ledgers (build killed mid-write) are
    salvaged line-by-line, same as ``report --events``."""
    import json as json_mod

    from makisu_tpu.utils import explain as explain_mod
    from makisu_tpu.utils import ledger as ledger_mod

    def load(path: str) -> dict:
        try:
            led = ledger_mod.read_ledger(path)
        except ValueError as e:
            log.warning("%s; analyzing the valid lines only", e)
            led = ledger_mod.read_ledger(path, skip_invalid=True)
        if not led["decisions"] and not led["header"]:
            # Both inputs get this check: a wrong --baseline file
            # would otherwise render a misleading "0 flips" diff.
            raise SystemExit(
                f"{path}: no ledger header or cache_decision lines "
                f"(expected an --explain-out file, schema "
                f"{ledger_mod.LEDGER_SCHEMA!r})")
        return led

    current = load(args.ledger)
    if args.baseline:
        print(explain_mod.render_diff(current, load(args.baseline)),
              end="")
        return 0
    report = None
    if args.metrics:
        with open(args.metrics, encoding="utf-8") as f:
            report = json_mod.load(f)
        if report.get("schema") != "makisu-tpu.metrics.v1":
            raise SystemExit(
                f"{args.metrics}: not a makisu-tpu metrics report "
                f"(schema {report.get('schema')!r})")
    print(explain_mod.render_explain(current, report), end="")
    return 0


def cmd_du(args) -> int:
    """Walk the four content planes (blob CAS, chunk CAS, packs,
    recipes) under the census IO budget and print per-plane object
    counts, byte totals, the age histogram, and per-tenant
    attribution. ``--json`` emits the makisu-tpu.census.v1 document
    (also cached at ``<storage>/census.json`` for cheap reuse by
    /healthz and history records)."""
    import json as json_mod

    from makisu_tpu.cache import census as census_mod

    storage_dir = _storage_dir(args.storage)
    if not os.path.isdir(storage_dir):
        raise SystemExit(f"{storage_dir}: not a directory")
    doc = census_mod.StorageCensus(storage_dir).census()
    if args.json_out:
        print(json_mod.dumps(doc, indent=2, default=str))
    else:
        print(census_mod.render_du(doc), end="")
    return 0


def _doctor_storage(args) -> int:
    """``doctor --storage TARGET``: census + reference audit +
    integrity scrub. A socket target asks the worker for its cached
    report (the worker's own IO budget and scrub cadence apply); a
    directory target walks locally and can ``--repair`` orphaned
    zpack twins. Exit 1 when any finding survives."""
    import stat as stat_mod

    from makisu_tpu.cache import census as census_mod

    target = args.bundle
    is_socket = False
    if target:
        try:
            is_socket = stat_mod.S_ISSOCK(os.stat(target).st_mode)
        except OSError:
            is_socket = False
    if is_socket:
        if args.repair:
            raise SystemExit(
                "doctor --storage --repair needs a storage "
                "DIRECTORY target (repair deletes files; run it "
                "where the files are, not through a worker socket)")
        from makisu_tpu.worker import WorkerClient
        try:
            report = WorkerClient(target).storage(
                eviction_budget=args.eviction_budget)
        except (OSError, RuntimeError, ValueError) as e:
            raise SystemExit(
                f"worker on {target} not reachable: {e}")
        entries = list(report.get("storage") or [])
    else:
        storage_dir = _storage_dir(target)
        if not os.path.isdir(storage_dir):
            raise SystemExit(
                f"{storage_dir}: neither a worker socket nor a "
                f"storage directory")
        census = census_mod.StorageCensus(storage_dir)
        entry = {"storage_dir": storage_dir,
                 "census": census.census(),
                 "audit": census.audit(),
                 "scrub": census.scrub()}
        from makisu_tpu.storage import contentstore
        entry["contentstore"] = \
            contentstore.store_for(storage_dir).describe()
        seed = census_mod.seed_states(storage_dir)
        if seed:
            entry["lru_seed"] = seed
        if args.eviction_budget is not None:
            entry["eviction_dry_run"] = census.eviction_dry_run(
                args.eviction_budget, seed_state=seed)
        repairable = [f for f in entry["audit"]["findings"]
                      if f.get("repairable")]
        if repairable:
            entry["repair"] = census.repair_orphaned_zpacks(
                repairable, apply=args.repair)
        entries = [entry]
    print(census_mod.render_storage_doctor(
        entries, target or "local storage"), end="")
    total = sum(
        len((e.get("audit") or {}).get("findings") or [])
        + len((e.get("scrub") or {}).get("findings") or [])
        for e in entries)
    return 1 if total else 0


def cmd_doctor(args) -> int:
    """Render a diagnostic bundle into a human diagnosis: the stuck
    span, wedged threads, transfer-engine backlog, and the resource
    trajectory leading up to the capture. ``--device`` switches to the
    cross-session device-route diagnosis: every recorded backend-probe
    attempt (the ``makisu-tpu.deviceprobe.v1`` ledger), its verdict,
    the dominant wedge phase and sampled frame, and when the route was
    last healthy."""
    import json as json_mod

    from makisu_tpu.utils import flightrecorder

    if getattr(args, "storage", False):
        return _doctor_storage(args)
    if getattr(args, "fleet", False):
        from makisu_tpu.fleet import doctor as fleet_doctor
        from makisu_tpu.worker import WorkerClient
        if not args.bundle:
            raise SystemExit(
                "doctor --fleet needs the front door's socket path: "
                "`makisu-tpu doctor --fleet SOCKET`")
        client = WorkerClient(args.bundle)
        try:
            health = client.healthz()
        except (OSError, RuntimeError, ValueError) as e:
            raise SystemExit(
                f"fleet front door on {args.bundle} not reachable: "
                f"{e}")
        if "fleet" not in health:
            raise SystemExit(
                f"{args.bundle} answers /healthz but carries no "
                f"fleet section — is it a worker socket? point "
                f"doctor --fleet at the `makisu-tpu fleet` socket")
        # Active alerts render as findings (severity-ordered with the
        # rest of the diagnosis). Best-effort: a front door predating
        # /alerts still gets the healthz-digest fallback.
        alerts_snap = None
        try:
            alerts_snap = client.alerts()
        except (OSError, RuntimeError, ValueError):
            pass
        print(fleet_doctor.render_fleet_doctor(health, args.bundle,
                                               alerts=alerts_snap),
              end="")
        return 0
    if args.device:
        from makisu_tpu.utils import deviceprobe
        records = deviceprobe.read_records(args.bundle or None)
        if not records:
            where = (args.bundle or deviceprobe.sessions_dir()
                     or "$MAKISU_TPU_DEVICE_SESSIONS_DIR (unset)")
            raise SystemExit(
                f"no {deviceprobe.SCHEMA} records found in {where}; "
                f"probe attempts record there when a device is "
                f"configured (or when MAKISU_TPU_DEVICE_SESSIONS_DIR "
                f"is set explicitly)")
        print(deviceprobe.render_device_doctor(records), end="")
        return 0
    if not args.bundle:
        raise SystemExit(
            "doctor needs a diagnostic-bundle path (or --device for "
            "the device-route ledger diagnosis)")
    import stat as stat_mod
    if os.path.exists(args.bundle) and stat_mod.S_ISSOCK(
            os.stat(args.bundle).st_mode):
        # A live control socket instead of a bundle file: render the
        # process's active alerts as a diagnosis (works against a
        # worker or a fleet front door — the payload names itself).
        from makisu_tpu.fleet import doctor as fleet_doctor
        from makisu_tpu.utils import alerts as alerts_mod
        from makisu_tpu.worker import WorkerClient
        try:
            snap = WorkerClient(args.bundle).alerts()
        except (OSError, RuntimeError, ValueError) as e:
            raise SystemExit(
                f"{args.bundle} is a socket but /alerts failed: {e}")
        print(alerts_mod.render_alerts(
            snap, heading=f"{snap.get('source') or '?'} alerts — "
                          f"{args.bundle}"))
        findings = fleet_doctor.alert_findings(snap)
        if findings:
            print(f"\ndiagnosis ({len(findings)} finding(s)):")
            for f in findings:
                print(f"  [{f['severity']:<7s}] {f['detail']}")
        return 0
    with open(args.bundle, encoding="utf-8") as f:
        bundle = json_mod.load(f)
    if bundle.get("schema") != flightrecorder.BUNDLE_SCHEMA:
        raise SystemExit(
            f"{args.bundle}: not a makisu-tpu diagnostic bundle "
            f"(schema {bundle.get('schema')!r}); bundles are written "
            f"by --diag-out, the stall watchdog, or SIGTERM/SIGUSR1")
    print(flightrecorder.render_doctor(bundle), end="")
    return 0


def cmd_check(args) -> int:
    """Run the static-analysis rule engine over the tree: six rules
    distilled from shipped bugs (ctx propagation, signal safety,
    metric-name registry, atomic durable writes, silent swallows,
    unbounded I/O). Pre-existing findings live in the committed
    baseline; anything new exits 1 naming the rule, file, and line."""
    import json as json_mod

    from makisu_tpu import analysis

    rules = analysis.default_rules()
    if args.rule:
        wanted = set(args.rule)
        known = {r.name for r in rules}
        unknown = wanted - known
        if unknown:
            raise SystemExit(
                f"unknown rule(s) {', '.join(sorted(unknown))}; "
                f"valid: {', '.join(sorted(known))}")
        rules = [r for r in rules if r.name in wanted]
    paths = args.paths or analysis.default_scan_paths()
    root = analysis.repo_root()
    baseline_path = args.baseline or analysis.default_baseline_path()
    if args.update_baseline and not args.baseline \
            and (args.rule or args.paths):
        # write_baseline REPLACES the file with the current finding
        # set; updating the committed repo baseline from a filtered
        # scan would silently discard every other rule's/path's
        # entries. An explicit --baseline names a file the caller
        # owns, so partial scopes are fine there.
        raise SystemExit(
            "--update-baseline with --rule/PATH filters would drop "
            "every unscanned finding from the committed baseline; "
            "run it unfiltered, or pass an explicit --baseline FILE")
    findings = analysis.run_check(paths, rules, root=root)
    if args.update_baseline:
        analysis.write_baseline(baseline_path, findings)
        log.info("baseline updated: %d finding(s) recorded in %s",
                 len(findings), baseline_path)
        return 0
    baseline = analysis.load_baseline(baseline_path)
    new, suppressed = analysis.apply_baseline(findings, baseline)
    if args.json_out:
        print(json_mod.dumps({
            "schema": "makisu-tpu.check.v1",
            "findings": [f.to_dict() for f in new],
            "suppressed": suppressed,
            "baseline": os.path.relpath(baseline_path, root)
            if baseline_path.startswith(root) else baseline_path,
            "rules": sorted(r.name for r in rules),
        }, indent=1))
    else:
        for f in new:
            print(f.render())
        print(f"makisu-tpu check: {len(new)} new finding(s), "
              f"{suppressed} baseline-suppressed")
    return 1 if new else 0


def cmd_worker(args) -> int:
    from makisu_tpu.utils import flightrecorder
    from makisu_tpu.utils import metrics as metrics_mod
    from makisu_tpu.worker import WorkerServer
    if args.storage_budget is not None or \
            args.storage_remote is not None:
        # Worker-wide defaults: every storage dir this worker builds
        # against inherits them (a build's own --storage-budget flag
        # still overrides per-dir).
        from makisu_tpu.storage import contentstore
        contentstore.configure(budget_mb=args.storage_budget,
                               remote=args.storage_remote)
    server = WorkerServer(args.socket,
                          stall_window=(args.stall_timeout or
                                        None),
                          diag_out=args.diag_out,
                          max_concurrent_builds=
                          args.max_concurrent_builds,
                          slo_config=args.slo_config,
                          alert_webhook=args.alert_webhook)
    # Process-level signal forensics: a worker killed by its
    # supervisor (SIGTERM) or poked for live inspection (SIGUSR1)
    # dumps a bundle covering EVERY in-flight build — the server's
    # process recorder sees all contexts' events via the global sink,
    # and the GLOBAL registry's trace id keeps every build's open
    # spans in the bundle. This replaces BOTH per-invocation handlers
    # cli.main installed, which would capture only the worker
    # invocation's own (empty) context.
    flightrecorder.install_signal_dumps(
        server.recorder, metrics_mod.global_registry(), args.diag_out)
    log.info("worker listening on %s", args.socket)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.server_close()
    return 0


def cmd_serve(args) -> int:
    """Run the standalone distribution endpoint: read-only recipes +
    ranged pack serving over one storage directory a builder (or
    worker) populates. The CDN-edge shape of the serve plane — workers
    embed the same handlers on their own sockets."""
    from makisu_tpu.serve import ServeServer
    server = ServeServer(args.socket, _storage_dir(args.storage))
    stats = server.store.stats()
    log.info("serve endpoint on %s over %s (%d recipe(s), %d pack(s), "
             "%d pack bytes)", args.socket, server.storage_dir,
             stats["recipes"], stats["packs"], stats["pack_bytes"])
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.server_close()
    return 0


def cmd_fleet(args) -> int:
    """Run the build-farm front door: a scheduler that fronts N
    workers, routing each build to the worker holding its resident
    session (affinity), placing new contexts by consistent hash with
    least-loaded spillover, enforcing per-tenant quotas, failing over
    past dead/refusing workers, and publishing the peer map workers
    use to fetch chunks from each other before the registry."""
    from makisu_tpu.fleet import FleetServer, WorkerSpec
    from makisu_tpu.utils import flightrecorder
    from makisu_tpu.utils import metrics as metrics_mod
    if not args.worker:
        raise SystemExit("fleet needs at least one "
                         "--worker SOCKET[=STORAGE]")
    specs = [WorkerSpec.parse(flag, i)
             for i, flag in enumerate(args.worker)]
    # The front door's own events — routing spans, decisions, teed
    # worker build events — happen on handler/poll threads that carry
    # NO bound context, so the --events-out/--explain-out sinks
    # cli.main bound in THIS context are promoted process-wide for the
    # server's lifetime. (Promotion replaces the old event_context
    # replay: one delivery path, no double-writes.)
    promoted = events.promote_context_sinks()
    server = FleetServer(
        args.socket, specs,
        poll_interval=args.poll_interval,
        tenant_quota=args.tenant_quota,
        max_inflight=args.max_inflight_builds,
        spillover_queue_depth=args.spillover_queue_depth,
        stall_window=(args.stall_timeout or None),
        diag_out=args.diag_out,
        slo_config=args.slo_config,
        alert_webhook=args.alert_webhook,
        canary_interval=args.canary_interval,
        canary_slow_seconds=args.canary_slow_seconds)
    # Process-level signal forensics, at parity with cmd_worker: a
    # SIGTERM'd front door dumps a bundle covering every in-flight
    # routed build (the server's recorder sees all contexts via the
    # global sink; the GLOBAL registry keeps every build's open route/
    # forward spans in it), and SIGUSR1 dumps one live WITHOUT
    # interrupting the in-flight builds.
    flightrecorder.install_signal_dumps(
        server.recorder, metrics_mod.global_registry(), args.diag_out)
    log.info("fleet front door listening on %s (%d workers: %s)",
             args.socket, len(specs),
             ", ".join(s.socket_path for s in specs))
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        # Pull every worker's serve access ledger BEFORE the sinks
        # demote: in a real multi-process fleet those rows (the
        # bytes-on-wire of peer/delta fetches, trace-id-stamped) live
        # only in the workers — delivering them here lands them in the
        # promoted --events-out file AND the merged-trace collector.
        # In-process fleets see them twice; the assembler dedupes.
        try:
            for access_event in server.collect_serve_access():
                events.deliver(access_event)
        except Exception as e:  # noqa: BLE001 - shutdown must proceed
            log.warning("serve-access collection failed: %s", e)
        trace_events = server.trace_events()
        server.server_close()
        events.demote_sinks(promoted)
        if args.trace_out:
            # The merged cross-process trace: the front door's own
            # spans plus every teed worker event, assembled per trace
            # id into one Perfetto export. Written here — and the flag
            # cleared — because cli.main's generic trace write only
            # sees the (empty) invocation registry, not the per-build
            # ones routing used.
            from makisu_tpu.utils import traceexport
            try:
                assembled = traceexport.assemble_fleet_trace(
                    trace_events)
                metrics.write_json_atomic(
                    args.trace_out,
                    traceexport.fleet_perfetto_trace(assembled))
                log.info("merged fleet trace written to %s "
                         "(%d trace(s), %d span(s))", args.trace_out,
                         len(assembled.get("traces", [])),
                         assembled.get("span_count", 0))
            except (OSError, ValueError) as e:
                log.error("failed to write merged fleet trace: %s", e)
            args.trace_out = ""
    return 0


def cmd_alerts(args) -> int:
    """Fetch and render ``GET /alerts`` from a worker or fleet front
    door: active alerts severity-first, the recently-resolved ring,
    and — on a fleet socket — each worker's own section."""
    import json as json_mod

    from makisu_tpu.utils import alerts as alerts_mod
    from makisu_tpu.worker import WorkerClient
    client = WorkerClient(args.socket)
    try:
        snap = client.alerts()
    except (OSError, RuntimeError, ValueError) as e:
        raise SystemExit(
            f"cannot fetch /alerts from {args.socket}: {e}")
    if args.json_out:
        print(json_mod.dumps(snap, indent=1))
        return 0
    source = snap.get("source") or "?"
    print(alerts_mod.render_alerts(
        snap, heading=f"{source} alerts — {args.socket}"))
    for wid, payload in sorted((snap.get("workers") or {}).items()):
        print()
        if payload.get("error"):
            print(f"worker {wid}: {payload['error']}")
        else:
            print(alerts_mod.render_alerts(
                payload, heading=f"worker {wid}"))
    canary = snap.get("canary") or {}
    if canary.get("workers"):
        print(f"\ncanary: {canary.get('sweeps', 0)} sweep(s), digest "
              f"mismatch={str(bool(canary.get('digest_mismatch'))).lower()}")
        for wid, row in sorted(canary["workers"].items()):
            print(f"  {wid}: score {row.get('score', 1.0):g} "
                  f"({row.get('bad', 0)}/{row.get('total', 0)} bad, "
                  f"last {row.get('latency_seconds', 0):g}s"
                  + (f", error: {row['error']}" if row.get("error")
                     else "") + ")")
    return 0


def cmd_sessions(args) -> int:
    """Resident-session surface of one worker: ``sessions SOCKET``
    lists the resident sessions plus the snapshot counters;
    ``sessions SOCKET snapshot [CONTEXT]`` checkpoints resident
    session state into the chunk-addressed snapshot plane;
    ``sessions SOCKET restore CONTEXT [--from SRC]`` stages a
    snapshot onto SOCKET (pulling the recipe from SRC when given —
    the fleet prewarm hand-off, driven by hand)."""
    import json as json_mod

    from makisu_tpu.worker import WorkerClient
    client = WorkerClient(args.socket)
    try:
        if args.verb == "snapshot":
            payload = client.snapshot_sessions(args.context)
            if args.json_out:
                print(json_mod.dumps(payload, indent=1))
            else:
                print(f"checkpointed {payload.get('snapshotted', 0)} "
                      f"session(s)")
            return 0
        if args.verb == "restore":
            if not args.context:
                raise SystemExit(
                    "sessions restore requires a context dir")
            if args.from_socket:
                recipe = WorkerClient(
                    args.from_socket).session_snapshot(args.context)
                payload = client.restore_session({"recipe": recipe})
            else:
                payload = client.restore_session(
                    {"context": args.context})
            if args.json_out:
                print(json_mod.dumps(payload, indent=1))
            elif payload.get("ok"):
                print("snapshot staged; the next build on this "
                      "context restores warm")
            else:
                print("restore refused: "
                      f"{payload.get('reason') or 'unknown'}")
            return 0 if payload.get("ok") else 1
        snap = client.sessions()
    except (OSError, RuntimeError, ValueError) as e:
        raise SystemExit(
            f"sessions {args.verb} via {args.socket} failed: {e}")
    if args.json_out:
        print(json_mod.dumps(snap, indent=1))
        return 0
    sessions = snap.get("sessions") or []
    print(f"{len(sessions)} resident session(s) — {args.socket}")
    for row in sessions:
        print(f"  {row.get('context', '?')}: builds={row.get('builds', 0)} "
              f"bytes={row.get('resident_bytes', 0)} "
              f"exact={str(bool(row.get('exact'))).lower()} "
              f"busy={str(bool(row.get('busy'))).lower()}")
    counters = snap.get("snapshot") or {}
    if counters:
        print("snapshot: " + " ".join(
            f"{k}={counters[k]}" for k in
            ("write", "write_error", "restore", "restore_refused",
             "restore_error") if k in counters))
        failure = counters.get("last_restore_failure") or {}
        if failure.get("reason"):
            print(f"  last restore failure: {failure.get('context', '?')} "
                  f"({failure['reason']})")
    return 0


def cmd_top(args) -> int:
    """Live terminal view of a worker: in-flight builds (tenant,
    phase, progress age, queue wait, cache hit rate), the admission
    queue, and the transfer plane — polled from ``/builds`` +
    ``/healthz``."""
    from makisu_tpu.tools import top
    return top.run(args)


def cmd_loadgen(args) -> int:
    """Synthetic concurrent-build load harness: N lanes of generated-
    context builds against a real worker, reporting p50/p99 latency,
    the queue-wait split, per-tenant fairness, hash-batch occupancy,
    and the cache hit-rate trajectory."""
    from makisu_tpu.tools import loadgen
    return loadgen.run(args)


def cmd_history(args) -> int:
    """Render build-history trends, or gate on a regression:
    ``history PATH...`` renders the trend view; ``history diff A B``
    compares candidate B against baseline A. Exit codes are gate-
    script friendly: 0 = ok, 1 = a latency/cache regression beyond
    ``--threshold`` was flagged, 2 = unreadable input (a missing
    baseline must not look like a regression)."""
    from makisu_tpu.utils import history as history_mod
    tokens = args.history_args

    def read(path: str) -> list[dict]:
        try:
            return history_mod.read_history(path)
        except OSError as e:
            log.error("cannot read history %s: %s", path, e)
            raise SystemExit(2)

    if tokens[0] == "diff":
        if len(tokens) != 3:
            raise SystemExit(
                "history diff takes exactly two history paths: "
                "`makisu-tpu history diff BASELINE CANDIDATE`")
        result = history_mod.diff(read(tokens[1]), read(tokens[2]),
                                  threshold=args.threshold)
        print(history_mod.render_diff(result), end="")
        return 0 if result["ok"] else 1
    records: list[dict] = []
    for path in tokens:
        records.extend(read(path))
    records.sort(key=lambda r: r.get("ts", 0.0))
    print(history_mod.render_trends(records, limit=args.limit),
          end="")
    return 0


def cmd_profile(args) -> int:
    """Work with wall-clock sampling profiles: ``profile ARTIFACT``
    renders the phase-attributed breakdown (``--flame`` adds a
    self-contained flamegraph HTML); ``profile diff BASELINE
    CANDIDATE`` attributes a regression to the frames whose self-time
    share grew; ``profile --fleet SOCKET`` captures and merges an
    on-demand window from every alive worker. Exit codes follow the
    ``history diff`` gate contract: 0 = ok, 1 = a frame regressed
    beyond ``--threshold``, 2 = unreadable input."""
    from makisu_tpu.utils import profiler as profiler_mod
    tokens = args.target

    def read(path: str) -> dict:
        try:
            return profiler_mod.read_artifact(path)
        except ValueError as e:
            log.error("%s", e)
            raise SystemExit(2)

    if args.fleet:
        from makisu_tpu.worker import WorkerClient
        if not tokens:
            raise SystemExit(
                "profile --fleet needs the front door's socket path: "
                "`makisu-tpu profile --fleet SOCKET`")
        client = WorkerClient(tokens[0],
                              control_timeout=args.seconds + 30.0)
        try:
            doc = client.profile(seconds=args.seconds)
        except (OSError, RuntimeError, ValueError) as e:
            raise SystemExit(
                f"fleet profile capture from {tokens[0]} failed: {e}")
    elif tokens and tokens[0] == "diff":
        if len(tokens) != 3:
            raise SystemExit(
                "profile diff takes exactly two artifacts: "
                "`makisu-tpu profile diff BASELINE CANDIDATE`")
        result = profiler_mod.diff(read(tokens[1]), read(tokens[2]),
                                   threshold=args.threshold)
        print(profiler_mod.render_diff(result), end="")
        return 0 if result["ok"] else 1
    elif len(tokens) == 1:
        doc = read(tokens[0])
    else:
        raise SystemExit(
            "profile takes one artifact path, `diff BASELINE "
            "CANDIDATE`, or `--fleet SOCKET`")
    print(profiler_mod.render_profile(doc, top=args.top), end="")
    if args.flame:
        try:
            with open(args.flame, "w", encoding="utf-8") as f:
                f.write(profiler_mod.flamegraph_html(doc))
            log.info("flamegraph written to %s", args.flame)
        except OSError as e:
            log.error("failed to write flamegraph: %s", e)
            return 1
    if args.out:
        try:
            profiler_mod.write_artifact(args.out, doc)
            log.info("profile artifact written to %s", args.out)
        except OSError as e:
            log.error("failed to write profile artifact: %s", e)
            return 1
    return 0


def _device_identity() -> dict | None:
    """Platform, device_kind and device count as the backend probe
    found them; None when this process brought no JAX backend up.
    Never imports the device stack itself."""
    ops_backend = sys.modules.get("makisu_tpu.ops.backend")
    return ops_backend.device_identity() if ops_backend else None


def main(argv: list[str] | None = None,
         args: argparse.Namespace | None = None) -> int:
    """Run one command. ``args`` is ``argv`` already parsed by
    ``parse_args`` (the worker parses a request once, at admission, and
    hands the namespace on); without it ``argv`` is parsed here."""
    if args is None:
        args = parse_args(argv)
    else:
        metrics.counter_add(metrics.REQUEST_RESOLVE_TOTAL, kind="parse",
                            result="reused")
    log.configure(args.log_level.replace("warn", "warning"), args.log_fmt,
                  args.log_output)
    if args.transfer_concurrency or args.transfer_memory_budget:
        from makisu_tpu.registry import transfer
        transfer.configure(args.transfer_concurrency,
                           args.transfer_memory_budget)
    hash_workers_token = None
    if args.hash_workers > 0:
        # Context-scoped (like the metrics registry): concurrent
        # worker builds can carry different worker counts.
        hash_workers_token = concurrency.set_hash_workers(
            args.hash_workers)
    compress_workers_token = None
    if args.compress_workers > 0:
        compress_workers_token = concurrency.set_compress_workers(
            args.compress_workers)
    if args.hash_linger_ms >= 0:
        # Process-wide by design: the hash service batches ACROSS
        # builds, so there is one linger per process.
        concurrency.set_hash_linger_ms(args.hash_linger_ms)
    if args.command == "version":
        print(makisu_tpu.BUILD_HASH)
        return 0
    handlers = {"build": cmd_build, "pull": cmd_pull, "push": cmd_push,
                "diff": cmd_diff, "worker": cmd_worker,
                "serve": cmd_serve,
                "fleet": cmd_fleet, "report": cmd_report,
                "doctor": cmd_doctor, "explain": cmd_explain,
                "check": cmd_check, "top": cmd_top,
                "alerts": cmd_alerts, "sessions": cmd_sessions,
                "loadgen": cmd_loadgen, "history": cmd_history,
                "du": cmd_du, "profile": cmd_profile}
    handler = handlers.get(args.command)
    if handler is None:
        make_parser().print_help()
        return 1
    profiler = None
    if args.cpu_profile:
        profiler = cProfile.Profile()
        profiler.enable()
    jax_trace = False
    if getattr(args, "jax_profile", ""):
        # Importing ops FIRST places the compile cache before anything
        # compiles.
        from makisu_tpu import ops  # noqa: F401
        import jax
        jax.profiler.start_trace(args.jax_profile)
        # Spans on the trace's host timeline even when no build of
        # this invocation brings the device backend up (--hasher cpu).
        metrics.set_annotation_factory(jax.profiler.TraceAnnotation)
        jax_trace = True
    # Every invocation gets its own telemetry registry, bound to this
    # context exactly like the worker's per-build log sink: concurrent
    # builds in one worker never mix span trees or counters, while the
    # process-global registry (the worker's /metrics) still aggregates.
    registry = metrics.MetricsRegistry()
    # Trace adoption: when an upstream caller handed this invocation a
    # trace context (the worker binds the /build request's traceparent;
    # the fleet forwarder sends its forward span's), the fresh registry
    # JOINS that trace — same trace id, root span id = the caller's
    # span — so front door → worker → peer fetch all tell one causal
    # story. A malformed value mints fresh ids (counted, never fatal).
    metrics.adopt_inbound(registry, metrics.inbound_traceparent())
    metrics_token = metrics.set_build_registry(registry)
    # Alerts fired during this invocation's window: the SLO evaluator
    # (worker/fleet background thread) bumps the process-GLOBAL fired
    # counter, so the delta across this build is what the history
    # record carries — `history diff` attributes latency regressions
    # that coincide with alert storms.
    alerts_fired_base = metrics.global_registry().counter_total(
        metrics.ALERTS_FIRED)
    # Failure forensics: every invocation arms a flight recorder (a
    # lock-free ring of recent events/log records) and the process
    # resource sampler. Cost when nothing goes wrong: one deque append
    # per event. When something does — failure, stall, SIGTERM — the
    # recorder dumps a diagnostic bundle `makisu-tpu doctor` can read.
    from makisu_tpu.utils import flightrecorder, resources
    resources.ensure_started()
    # This invocation's own progress clock: every thread the build
    # spawns inherits the cell, so a per-build stall watchdog in a
    # busy worker watches THIS build, not its neighbors.
    progress_token = events.bind_progress_cell()
    recorder = flightrecorder.FlightRecorder()
    recorder_tokens = flightrecorder.install(recorder)
    # SIGTERM (the CI-timeout kill) dumps then unwinds; SIGUSR1 dumps
    # and keeps building. Worker mode replaces these with
    # process-level handlers (cmd_worker); in-worker builds run on
    # handler threads, where install_signal_dumps is a no-op.
    old_signal_handlers = flightrecorder.install_signal_dumps(
        recorder, registry, args.diag_out, tag=registry.trace_id[:8])
    # Continuous profiling: real-work commands run under the wall-clock
    # sampler. This invocation's thread is bound to its trace id so the
    # sampler attributes its stacks to THIS build even inside a busy
    # worker; a process-level sampler (the worker's, or loadgen's) is
    # reused rather than double-sampled — ownership decides who stops
    # it and clears the registry slot.
    from makisu_tpu.utils import profiler as profiler_mod
    sampler = None
    sampler_thread_token = None
    if args.command in ("build", "pull", "push", "diff", "loadgen"):
        sampler_thread_token = profiler_mod.bind_thread(
            registry.trace_id)
        if profiler_mod.process_profiler() is None:
            sample_hz = profiler_mod.resolve_hz(args.profile_hz)
            if sample_hz > 0:
                sampler = profiler_mod.SamplingProfiler(
                    hz=sample_hz).start()
                profiler_mod.set_process_profiler(sampler)
    events_writer = None
    events_token = None
    if args.events_out:
        try:
            events_writer = events.JsonlWriter(args.events_out)
            events_token = events.add_sink(events_writer)
        except OSError as e:
            log.error("failed to open events log %s: %s",
                      args.events_out, e)
    # The cache-decision ledger rides the same event bus: the writer is
    # just a sink filtering cache_decision events into the compact
    # --explain-out artifact (header + one line per consult + summary).
    ledger_writer = None
    ledger_token = None
    if args.explain_out:
        from makisu_tpu.utils import ledger as ledger_mod
        try:
            ledger_writer = ledger_mod.LedgerWriter(
                args.explain_out, trace_id=registry.trace_id,
                command=args.command or "")
            ledger_token = events.add_sink(ledger_writer)
        except OSError as e:
            log.error("failed to open cache ledger %s: %s",
                      args.explain_out, e)
    # The watchdog starts AFTER every event sink is bound: it runs
    # under a copy of this context, so its `stall` event reaches the
    # recorder, the --events-out log, and (in a worker) the client's
    # live stream. The `worker` command is exempt: a per-invocation
    # watchdog has no active_fn gate and would flag a healthy IDLE
    # worker as stalled — cmd_worker's server arms its own, gated on
    # in-flight builds. The `fleet` front door is exempt for the same
    # reason (long-lived, legitimately idle between submissions).
    watchdog = None
    stall_timeout = (args.stall_timeout or
                     flightrecorder.stall_timeout_from_env())
    if stall_timeout > 0 and args.command not in ("worker", "fleet",
                                                  "serve"):
        watchdog = flightrecorder.StallWatchdog(
            stall_timeout, recorder,
            flightrecorder.forced_bundle_path(
                args.diag_out, "stall", tag=registry.trace_id[:8]),
            registry, cell=events.progress_cell()).start()
    # argv deliberately stays out of the event record: it can carry
    # credentials (--redis-cache-password, registry configs).
    events.emit("build_start", trace_id=registry.trace_id,
                command=args.command or "",
                version=makisu_tpu.__version__)
    code = 1
    try:
        with metrics.span(args.command or "cli", structural=True):
            code = handler(args)
        return code
    except SystemExit as e:
        # A signal handler's SystemExit(143) or a subcommand's
        # SystemExit(msg) unwinds through here: record the true exit
        # code so build_end (and the failure-dump gate) see 143/1,
        # not the untouched sentinel.
        code = (e.code if isinstance(e.code, int)
                else 0 if e.code is None else 1)
        raise
    except Exception as e:  # noqa: BLE001 - top-level CLI boundary
        log.error("failed to execute command: %s", e)
        if args.log_level == "debug":
            raise
        return 1
    finally:
        events.emit("build_end", trace_id=registry.trace_id,
                    exit_code=code)
        if watchdog is not None:
            watchdog.stop()
        flightrecorder.restore_signal_handlers(old_signal_handlers)
        if (code != 0
                and args.command in ("build", "pull", "push", "diff")
                and not recorder.captured_terminal_moment()):
            # A stall/SIGTERM dump already froze the interesting
            # moment (a SIGUSR1 inspection poke doesn't count);
            # otherwise a plain failure dumps here (opt-in via
            # --diag-out / $MAKISU_TPU_DIAG_DIR — red CI runs upload
            # the bundle as an artifact). Only real-work commands
            # dump: a failed `report`/`doctor` analysis has no build
            # to do forensics ON, and the `worker` command's
            # forensics are the PROCESS-level handlers in cmd_worker
            # — this invocation-scoped recorder, blind to the builds,
            # would clobber the SIGTERM bundle they just wrote at the
            # same --diag-out path.
            diag_path = flightrecorder.resolve_bundle_path(
                args.diag_out, "failure", tag=registry.trace_id[:8])
            if diag_path:
                try:
                    recorder.dump(diag_path, "failure", registry,
                                  exit_code=code)
                    log.info("diagnostic bundle written to %s",
                             diag_path)
                except OSError as e:
                    log.error("failed to write diagnostic bundle: %s", e)
        elif recorder.last_dump_path:
            log.info("diagnostic bundle written to %s",
                     recorder.last_dump_path)
        if events_token is not None:
            events.reset_sink(events_token)
        if events_writer is not None:
            events_writer.close()
            log.info("event log written to %s", args.events_out)
        if ledger_token is not None:
            events.reset_sink(ledger_token)
        if ledger_writer is not None:
            # Closing AFTER the build_end emit above: the summary line
            # carries the exit code the writer captured from it.
            ledger_writer.close()
            log.info("cache ledger written to %s", args.explain_out)
        flightrecorder.uninstall(recorder_tokens)
        events.reset_progress_cell(progress_token)
        # Deploy-identity info gauge: constant 1, identity in the
        # labels (the node_exporter "build_info" idiom). Scrapers join
        # it against rate() series to slice by
        # version/hasher/platform/mode. Published as the invocation
        # ends, because ``platform`` is what the backend probe FOUND
        # (ops/backend.py), not what the environment asked for:
        # "none" when this process never brought a JAX backend up.
        # native_isa: the runtime-dispatched SIMD route of the
        # layer-commit hot path (native.py), e.g. "gear=avx2,sha=shani"
        # — resolved once per process and NEVER part of cache identity
        # (every route emits identical bytes). Whatever is already
        # resolved is labelled — an accelerator build must not pay a
        # synchronous `make -C native` for a telemetry label.
        device = _device_identity()
        from makisu_tpu import native as _native
        metrics.gauge_set(
            metrics.BUILD_INFO, 1,
            version=makisu_tpu.__version__,
            command=args.command or "",
            hasher=getattr(args, "hasher", "") or "",
            platform=device["platform"] if device else "none",
            mode=invocation_mode.get(),
            hash_workers=concurrency.hash_workers(),
            compress_workers=concurrency.compress_workers(),
            hash_linger_ms=concurrency.hash_linger_ms(),
            native_isa=_native.isa_route_if_resolved() or "unresolved")
        metrics.reset_build_registry(metrics_token)
        if hash_workers_token is not None:
            concurrency.reset_hash_workers(hash_workers_token)
        if compress_workers_token is not None:
            concurrency.reset_compress_workers(compress_workers_token)
        if jax_trace:
            import jax
            jax.profiler.stop_trace()
            log.info("jax profiler trace written to %s", args.jax_profile)
        if profiler is not None:
            profiler.disable()
            profiler.dump_stats("/tmp/makisu-tpu.prof")
            log.info("cpu profile written to /tmp/makisu-tpu.prof")
        if sampler_thread_token is not None:
            profiler_mod.unbind_thread(sampler_thread_token)
        if sampler is not None:
            # Stop BEFORE snapshotting so the artifact's duration is
            # the command's, not the teardown's.
            sampler.stop()
        active_sampler = sampler or profiler_mod.process_profiler()
        if args.profile_out:
            if active_sampler is not None:
                try:
                    profiler_mod.write_artifact(
                        args.profile_out, active_sampler.snapshot(
                            command=args.command or ""))
                    log.info("profile written to %s", args.profile_out)
                except OSError as e:
                    log.error("failed to write profile: %s", e)
            else:
                log.info("profile requested but the sampler is "
                         "disabled (--profile-hz 0 / "
                         "MAKISU_TPU_PROFILE_HZ=0)")
        if sampler is not None:
            profiler_mod.set_process_profiler(None)
        if args.command == "build":
            # One greppable line with the build's vital signs; the full
            # breakdown lives in --metrics-out / the worker's /metrics.
            log.info("build telemetry", exit_code=code,
                     **metrics.summary(registry))
        # Build-history record: one compact JSONL line per real-work
        # invocation, appended to --history-out (or
        # $MAKISU_TPU_HISTORY_DIR/history.jsonl) — the durable perf
        # trajectory `makisu-tpu history` renders and `history diff`
        # gates on. Only real-work commands record: a `report` or
        # `history` invocation has no build trajectory to extend.
        history_path = ""
        if args.command in ("build", "pull", "push"):
            from makisu_tpu.utils import history as history_mod
            history_path = history_mod.resolve_out(args.history_out)
        if args.metrics_out or args.trace_out or history_path:
            # One registry.report() feeds every output — the span tree
            # and counter tables are not walked twice per build.
            report = registry.report()
            report["command"] = args.command or ""
            report["exit_code"] = code
            if device:
                # The device this invocation's numbers belong to.
                report["device"] = device
            if history_path:
                # Storage-plane snapshot beside the perf gates: the
                # CACHED census totals only (census.json written by
                # the last walk) — a history append must never pay a
                # multi-GB store walk.
                storage_bytes = None
                try:
                    from makisu_tpu.cache import census as census_mod
                    storage_bytes = census_mod.cached_totals(
                        _storage_dir(getattr(args, "storage", "")))
                except Exception as exc:  # noqa: BLE001 - telemetry
                    log.debug("history storage snapshot skipped: %s",
                              exc)
                    storage_bytes = None
                extra = ({"storage_bytes": storage_bytes}
                         if storage_bytes else {})
                extra["alerts_fired"] = int(
                    metrics.global_registry().counter_total(
                        metrics.ALERTS_FIRED) - alerts_fired_base)
                try:
                    history_mod.append_record(
                        history_path,
                        history_mod.record_from_report(
                            report, command=args.command or "",
                            exit_code=code, **extra))
                    log.info("history record appended to %s",
                             history_path)
                except OSError as e:
                    log.error("failed to append history record: %s",
                              e)
            if args.metrics_out:
                try:
                    metrics.write_json_atomic(args.metrics_out, report)
                    log.info("telemetry report written to %s",
                             args.metrics_out)
                except OSError as e:
                    log.error("failed to write telemetry report: %s", e)
            if args.trace_out:
                try:
                    from makisu_tpu.utils import traceexport
                    metrics.write_json_atomic(
                        args.trace_out,
                        traceexport.perfetto_trace(report))
                    log.info("perfetto trace written to %s",
                             args.trace_out)
                except OSError as e:
                    log.error("failed to write perfetto trace: %s", e)


if __name__ == "__main__":
    sys.exit(main())
