#!/usr/bin/env python3
"""chip_smoke.py: does makisu-tpu still start, and hash right, on the chip?

    python3 chip_smoke.py [--seed N]

The quickest proof the repo keeps: it drives the system's main path once
through the entry points a user calls (the ``makisu-tpu`` CLI and the
worker socket behind ``loadgen``), at the size a real build has (a seeded
context of 1 GiB in 2,000 files), and checks what comes out by the repo's
own means: every chunk digest against ``hashlib.sha256`` of its slice of
the layer tar, and layer digests and chunk lists against a
``JAX_PLATFORMS=cpu`` build of the same tree (a TPU builder and a CPU
builder share cache identity). It measures nothing: the seconds it prints
are smoke observations, not metrics.

ONE PROCESS FOR EACH CHIP: this parent never imports JAX (asserted before
it exits). Every stage that needs the chip runs as a child process, one
after another, and each child has exited, releasing the chip, before the
next starts. A parent that touched JAX would hold the chip, and the
children would fail or hang.

It sets no route-selecting option (MAKISU_TPU_PALLAS*, _CHUNK_NATIVE,
_SHA_*, _SHARED_HASH, _CHUNK_STRICT) and removes any it inherits, so the
routes it reports are the ones a user's build takes. It fails, with a
code other than 0 and no result line, at the first stage that fails: in
particular when JAX finds no TPU, when the device_kind is one it does not
know, or outside a checkout. ``--dry-run-cpu`` walks the same stages at
toy size on the CPU to debug this script; it proves nothing about the
device and says so.

Last line of standard output on success: one JSON object with exactly
these keys, ``{"ok": true, "device": {"platform": "tpu", "kind": "...",
"count": N}}``, the device as JAX reports it. What the run observed
(versions, routes, sizes, per-stage wall seconds, any cut) is the line
before it, ``[chip_smoke] observations {...}``. The dry run prints its
observations and no result line.
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import zlib

REPO = os.path.dirname(os.path.abspath(__file__))

# The devices this program's kernels and constants have met
# (ops/sha256.py unrolls, ops/gear_pallas.py tiles). Another
# device_kind is an error here, not a default: sweep it first.
KNOWN_DEVICE_KINDS = ("TPU v5 lite",)

# Options that select a route. The smoke never sets them and drops any
# it inherits.
ROUTE_OPTIONS = ("MAKISU_TPU_PALLAS", "MAKISU_TPU_CHUNK_NATIVE",
                 "MAKISU_TPU_SHARED_HASH", "MAKISU_TPU_CHUNK_STRICT")

DEVICE_BACKENDS = ("pallas", "xla")
MAX_CHUNK = 64 * 1024   # gear.DEFAULT_MAX_SIZE, restated: no JAX here

# Whole-script budget: the contract allows 1200s, compilation included.
DEADLINE_SECONDS = 1150.0
_T0 = time.monotonic()


class Failed(Exception):
    """A stage failed; the message says which check."""


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def child_env(**extra: str) -> dict:
    env = {k: v for k, v in os.environ.items() if k not in ROUTE_OPTIONS}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra)
    return env


class Child:
    """One child process in its own process group, output to a log
    file. The chip belongs to one process at a time, so at most one
    live Child is a chip child; a CPU-pinned one (the oracle builds)
    may run beside it."""

    live: list["Child"] = []

    def __init__(self, stage: str, argv: list[str], log_path: str,
                 env: dict, limit: float = 900.0) -> None:
        if DEADLINE_SECONDS - (time.monotonic() - _T0) <= 0:
            raise Failed(f"{stage}: out of time before it started")
        self.stage, self.log_path = stage, log_path
        self.t0 = time.monotonic()
        self.limit = limit
        with open(log_path, "wb") as log:
            self.proc = subprocess.Popen(
                argv, cwd=REPO, env=env, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True)
        Child.live.append(self)

    def kill(self) -> None:
        """Nothing the child started outlives it."""
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()
        if self in Child.live:
            Child.live.remove(self)

    def finish(self) -> str:
        """Wait for the child; its output, or Failed on a non-zero
        exit or when it outlasts its limit or the script's deadline."""
        left = min(self.t0 + self.limit,
                   _T0 + DEADLINE_SECONDS) - time.monotonic()
        try:
            code = self.proc.wait(timeout=max(left, 0))
        except subprocess.TimeoutExpired:
            code = 124
        finally:
            self.kill()
        self.wall = time.monotonic() - self.t0
        with open(self.log_path, encoding="utf-8", errors="replace") as f:
            out = f.read()
        if code != 0:
            raise Failed(f"{self.stage}: exit {code}\n{tail(out)}")
        return out


def tail(text: str, lines: int = 40) -> str:
    return "\n".join(text.splitlines()[-lines:])


def must_run(stage: str, argv: list[str], log_path: str, env: dict,
             limit: float = 900.0) -> str:
    return Child(stage, argv, log_path, env, limit).finish()


def marked_json(stage: str, out: str, marker: str) -> dict:
    """The JSON a child's snippet printed after ``marker``."""
    lines = [ln for ln in out.splitlines() if ln.startswith(marker)]
    if not lines:
        raise Failed(f"{stage}: the child printed no {marker.strip()} "
                     f"line\n{tail(out)}")
    return json.loads(lines[-1][len(marker):])


# -- identity ---------------------------------------------------------------

_IDENTITY_SNIPPET = """
import importlib.metadata as md, json
import jax, jaxlib
import makisu_tpu.ops  # the program's compile-cache site
dev = jax.devices()
def version(name):
    try:
        return md.version(name)
    except md.PackageNotFoundError:
        return None
print("IDENTITY " + json.dumps({
    "jax": jax.__version__, "jaxlib": jaxlib.__version__,
    "libtpu": version("libtpu"),
    "default_backend": jax.default_backend(),
    "platform": dev[0].platform, "kind": dev[0].device_kind,
    "count": len(dev),
    "compile_cache_dir": jax.config.jax_compilation_cache_dir}))
"""


def stage_identity(work: str, env: dict, dry_run: bool) -> dict:
    out = must_run("identity", [sys.executable, "-c", _IDENTITY_SNIPPET],
                   os.path.join(work, "identity.log"), env, limit=300)
    ident = marked_json("identity", out, "IDENTITY ")
    for key in ("jax", "jaxlib", "libtpu", "default_backend", "platform",
                "kind", "count", "compile_cache_dir"):
        say(f"identity: {key} = {ident[key]}")
    if dry_run:
        say("identity: DRY RUN on the CPU: this run proves nothing about "
            "the device")
        return ident
    if ident["platform"] != "tpu" or ident["default_backend"] != "tpu":
        raise Failed(
            f"identity: JAX found no TPU (platform {ident['platform']!r}, "
            f"default backend {ident['default_backend']!r}); this is a "
            "failure, not a fallback")
    if ident["kind"] not in KNOWN_DEVICE_KINDS:
        raise Failed(
            f"identity: unknown device_kind {ident['kind']!r} (known: "
            f"{', '.join(KNOWN_DEVICE_KINDS)}); the kernels' tile sizes "
            "and unrolls are one generation's, sweep this one first")
    return ident


def result_line(ident: dict) -> str:
    """The last line of a run that passed: these keys and no others (the
    chip check reads it; the observations line carries the rest)."""
    return json.dumps({
        "ok": True,
        "device": {"platform": str(ident["platform"]),
                   "kind": str(ident["kind"]),
                   "count": int(ident["count"])}})


# -- native libraries -------------------------------------------------------

_NATIVE_SNIPPET = """
import json
from makisu_tpu import native
print("NATIVE " + json.dumps({
    "libpgzip.so": bool(native.pgzip_available()),
    "liblayersink.so": bool(native.layersink_available()),
    "libgear.so": bool(native.gear_scan_available()),
    "libdirscan.so": native.dir_reader() is not None,
    "isa": native.isa_route()}))
"""


def stage_native(work: str, env: dict) -> dict:
    """Build the libraries from the committed sources. The copy
    may carry another machine's git-ignored .so/.o files, and the
    loader's own make is mtime-driven and swallows a failure, after
    which the whole commit path would switch to Python without a word
    (chunker/hasher.py _use_native)."""
    must_run("native: make", ["make", "-C", os.path.join(REPO, "native"),
                              "clean", "all"],
             os.path.join(work, "make.log"), env, limit=600)
    out = must_run("native: load", [sys.executable, "-c", _NATIVE_SNIPPET],
                   os.path.join(work, "native.log"), env, limit=120)
    libs = marked_json("native", out, "NATIVE ")
    say(f"native: {libs}")
    missing = [k for k, v in libs.items() if k.endswith(".so") and not v]
    if missing:
        raise Failed(f"native: did not load: {', '.join(missing)}")
    return libs


# -- the context ------------------------------------------------------------


def make_context(ctx: str, seed: int, total: int, files: int,
                 big: int) -> dict:
    """Two #!COMMIT layers (a/, b/) of ``files`` files and ``total``
    bytes: one incompressible and one repetitive file of ``big`` bytes,
    the rest alternating incompressible / repetitive, a tenth of them
    smaller than the minimum chunk. All of it from ``seed``."""
    import numpy as np
    rng = np.random.default_rng(seed)
    small = files // 10
    rest = files - 2 - small
    small_sizes = rng.integers(1, 2048, size=small)
    budget = total - 2 * big - int(small_sizes.sum())
    weights = rng.uniform(0.25, 1.75, size=rest)
    rest_sizes = np.maximum(
        (weights / weights.sum() * budget).astype(np.int64), 4096)
    sizes = [big, big] + [int(n) for n in rest_sizes] \
        + [int(n) for n in small_sizes]
    sizes[2] += max(total - sum(sizes), 0)   # the flooring's shortfall
    written = 0
    for sub in ("a", "b"):
        os.makedirs(os.path.join(ctx, sub), exist_ok=True)
    for i, n in enumerate(sizes):
        sub = "ab"[i % 2] if i >= 2 else "ab"[i]
        path = os.path.join(ctx, sub, f"d{i % 37:02d}", f"f{i:05d}.bin")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        if (i // 2) % 2 == 0 and i != 1:
            data = rng.bytes(n)                       # incompressible
        else:
            # Repetitive: a short seeded line repeated. A period this
            # small gives the gear window a handful of values, so most
            # such files hold no candidate at all (forced maximum-size
            # cuts) and a few hold one per period (minimum-size skips).
            line = (b"%05d " % i) + rng.bytes(int(rng.integers(3, 40))) \
                + b"\n"
            data = (line * (n // len(line) + 1))[:n]
        with open(path, "wb") as f:
            f.write(data)
        written += n
    with open(os.path.join(ctx, "Dockerfile"), "w") as f:
        f.write("FROM scratch\n"
                "COPY a /a/ #!COMMIT\n"
                "COPY b /b/ #!COMMIT\n")
    return {"context_bytes": written, "context_files": len(sizes),
            "largest_file_bytes": big}


def edit_one_file(ctx: str, seed: int):
    """Insert bytes in the middle of one incompressible file of layer
    b: every later byte of the layer shifts, the case content-defined
    chunking exists for. Layer a is untouched and stays a cache hit.
    Returns (path, undo): ``undo()`` puts the file back, bytes and
    times, so that the tree is again the one the first build saw."""
    import numpy as np
    candidates = []
    for root, _, names in os.walk(os.path.join(ctx, "b")):
        for name in names:
            path = os.path.join(root, name)
            index = int(name[1:6])
            if os.path.getsize(path) >= 65536 and index != 1 \
                    and (index // 2) % 2 == 0:
                candidates.append(path)
    path = sorted(candidates)[len(candidates) // 2]
    stat = os.stat(path)
    with open(path, "rb") as f:
        data = f.read()
    insert = np.random.default_rng(seed + 1).bytes(1000)
    with open(path, "wb") as f:
        f.write(data[:len(data) // 2] + insert + data[len(data) // 2:])

    def undo() -> None:
        with open(path, "wb") as f:
            f.write(data)
        os.utime(path, ns=(stat.st_atime_ns, stat.st_mtime_ns))

    return path, undo


# -- builds and what they left behind ---------------------------------------

_COMPILE_RE = re.compile(r"Finished XLA compilation of .* in ([0-9.]+) sec")


def start_build(stage: str, work: str, ctx: str, storage: str,
                env: dict) -> Child:
    """``makisu-tpu build --hasher tpu --commit explicit`` in a new
    process."""
    root = os.path.join(work, f"root-{stage}")
    os.makedirs(root, exist_ok=True)
    report_path = os.path.join(work, f"{stage}.report.json")
    child = Child(stage, [
        sys.executable, "-m", "makisu_tpu.cli", "--log-fmt", "console",
        "--metrics-out", report_path,
        "build", ctx, "-t", f"smoke/{stage}:1", "--hasher", "tpu",
        "--commit", "explicit", "--storage", storage, "--root", root],
        os.path.join(work, f"{stage}.log"), env)
    child.root, child.report_path = root, report_path
    return child


def finish_build(child: Child) -> dict:
    """The build's report, log and wall seconds, once it exited 0
    without a word about disabled fingerprinting or kernels."""
    out = child.finish()
    stage = child.stage
    shutil.rmtree(child.root, ignore_errors=True)
    for needle in ("chunk fingerprinting disabled", "kernel disabled"):
        if needle in out:
            raise Failed(f"{stage}: the log says {needle!r}\n{tail(out)}")
    with open(child.report_path, encoding="utf-8") as f:
        report = json.load(f)
    if report.get("exit_code") != 0:
        raise Failed(f"{stage}: report exit_code {report.get('exit_code')}")
    say(f"{stage}: build collected {child.wall:.1f}s after it started "
        "(smoke observation)")
    return {"report": report, "log": out, "wall": child.wall,
            # JAX_LOG_COMPILES lines: backend compiles and cache loads.
            "compile_seconds": sum(float(m) for m in
                                   _COMPILE_RE.findall(out)),
            "cache_hits": out.count("Persistent compilation cache hit")}


def build(stage: str, work: str, ctx: str, storage: str,
          env: dict) -> dict:
    return finish_build(start_build(stage, work, ctx, storage, env))


def counter(report: dict, name: str, **labels: str) -> dict:
    """{backend label: value} over the series of ``name`` matching
    ``labels``."""
    out: dict = {}
    for series in report.get("counters", {}).get(name, []):
        got = series.get("labels", {})
        if all(got.get(k) == v for k, v in labels.items()):
            key = got.get("backend", "")
            out[key] = out.get(key, 0) + int(series["value"])
    return out


def layers_of(storage: str) -> dict:
    """{cache id: {"tar", "gzip", "size", "gz", "chunks"}} for every
    layer the builds into ``storage`` committed."""
    with open(os.path.join(storage, "cache_key_value.json"),
              encoding="utf-8") as f:
        kv = json.load(f)
    out = {}
    for key, (value, _stamp) in kv.items():
        if value.startswith("{"):
            entry = json.loads(value)
            if "tar" in entry:
                out[key] = entry
    return out


def verify_layer(storage: str, entry: dict) -> dict:
    """The layer's chunk list tiles its tar exactly and every digest is
    hashlib's. Returns the tar size and what kinds of cut occurred."""
    gz_hex = entry["gzip"].split(":", 1)[1]
    inflater = zlib.decompressobj(wbits=31)
    parts = []
    with open(os.path.join(storage, "layers", gz_hex[:2], gz_hex),
              "rb") as f:
        while True:
            block = f.read(8 << 20)
            if not block:
                break
            parts.append(inflater.decompress(block))
    parts.append(inflater.flush())
    tar = b"".join(parts)
    del parts
    if "sha256:" + hashlib.sha256(tar).hexdigest() != entry["tar"]:
        raise Failed(f"layer {entry['tar']}: stored blob does not inflate "
                     "to the recorded tar digest")
    chunks = entry.get("chunks") or []
    if not chunks:
        raise Failed(f"layer {entry['tar']}: empty chunk list")
    view = memoryview(tar)
    offset = 0
    for off, length, hexdigest in chunks:
        if off != offset or length <= 0:
            raise Failed(f"layer {entry['tar']}: chunk at {off} does not "
                         f"continue the tiling at {offset}")
        if hashlib.sha256(view[off:off + length]).hexdigest() != hexdigest:
            raise Failed(f"layer {entry['tar']}: chunk [{off}, "
                         f"{off + length}) digest differs from hashlib")
        offset += length
    if offset != len(tar):
        raise Failed(f"layer {entry['tar']}: chunks cover {offset} of "
                     f"{len(tar)} tar bytes")
    lengths = [c[1] for c in chunks]
    return {"tar_bytes": len(tar), "chunks": len(chunks),
            "forced_max_cuts": sum(1 for n in lengths if n == MAX_CHUNK),
            "content_cuts": sum(1 for n in lengths if n != MAX_CHUNK)}


def check_device_hashed(stage: str, report: dict, tar_bytes: int,
                        ident: dict, dry_run: bool) -> dict:
    """All gear-scan and chunk-hash bytes of the build went through
    device backends, and the report names the device."""
    gear = counter(report, "makisu_gear_scan_bytes_total")
    sha = counter(report, "makisu_bytes_hashed_total", path="cdc")
    say(f"{stage}: gear scan bytes by backend {gear}, chunk hash bytes "
        f"by backend {sha}, layer tar bytes {tar_bytes}")
    for name, got in (("makisu_gear_scan_bytes_total", gear),
                      ("makisu_bytes_hashed_total{path=cdc}", sha)):
        if sum(got.values()) != tar_bytes:
            raise Failed(f"{stage}: {name} is {sum(got.values())}, the "
                         f"layers' tar bytes are {tar_bytes}")
        off_device = {k: v for k, v in got.items()
                      if k not in DEVICE_BACKENDS and v}
        if off_device and not dry_run:
            raise Failed(f"{stage}: {name} counts bytes off the device: "
                         f"{off_device}")
    device = report.get("device") or {}
    want = {"platform": ident["platform"], "device_kind": ident["kind"],
            "device_count": ident["count"]}
    if device != want:
        raise Failed(f"{stage}: the report names device {device}, the "
                     f"identity stage saw {want}")
    [info] = report["gauges"]["makisu_build_info"]
    if info["labels"].get("platform") != ident["platform"]:
        raise Failed(f"{stage}: makisu_build_info platform is "
                     f"{info['labels'].get('platform')!r}")
    if len(gear) != 1 or len(sha) != 1:
        raise Failed(f"{stage}: more than one route ran: {gear} {sha}")
    return {"gear": next(iter(gear)), "sha": next(iter(sha))}


def count_files(path: str | None) -> int:
    if not path or not os.path.isdir(path):
        return 0
    return sum(len(names) for _, _, names in os.walk(path))


def probe_seconds(sessions: str) -> dict:
    """Backend-init phase seconds of the newest device-session ledger
    record (utils/deviceprobe.py)."""
    path = os.path.join(sessions, "device_probes.jsonl")
    if not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as f:
        records = [json.loads(ln) for ln in f if ln.strip()]
    if not records:
        return {}
    return {p["phase"]: p["seconds"] for p in records[-1]["phases"]}


# -- the stages -------------------------------------------------------------


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=21)
    parser.add_argument("--dry-run-cpu", action="store_true",
                        help="toy sizes on the CPU, to debug this script; "
                             "proves nothing about the device")
    parser.add_argument("--keep-work", action="store_true",
                        help="leave the work directory behind")
    args = parser.parse_args()
    if not os.path.isfile(os.path.join(REPO, "makisu_tpu", "cli.py")):
        print("chip_smoke: FAILED: not in a makisu-tpu checkout "
              f"({REPO} holds no makisu_tpu/cli.py)", file=sys.stderr)
        return 2

    dry = args.dry_run_cpu
    sizes = ({"total": 24 << 20, "files": 60, "big": 5 << 20}
             if dry else {"total": 1 << 30, "files": 2000, "big": 64 << 20})
    farm = ({"concurrency": 2, "builds": 4, "files": 4, "file_kb": 256}
            if dry else
            {"concurrency": 8, "builds": 16, "files": 64, "file_kb": 1024})
    cuts: list[str] = []
    if dry:
        cuts.append("dry run on the CPU at toy sizes: proves nothing "
                    "about the device")
    stages: dict[str, float] = {}
    work = tempfile.mkdtemp(prefix="makisu-chip-smoke-")
    sessions = os.path.join(work, "device_sessions")
    # Deployment settings, not routes: where the probe ledger lands (so
    # the checkout stays clean) and JAX's own compile log (read back for
    # compile seconds and cache hits).
    base = {"MAKISU_TPU_DEVICE_SESSIONS_DIR": sessions,
            "JAX_LOG_COMPILES": "1"}
    chip_env = child_env(**base, **({"JAX_PLATFORMS": "cpu"} if dry else {}))
    cpu_env = child_env(**base, JAX_PLATFORMS="cpu")

    @contextlib.contextmanager
    def timed(name: str):
        say(f"== {name}")
        t0 = time.monotonic()
        yield
        stages[name] = round(time.monotonic() - t0, 2)
        say(f"== {name}: ok, {stages[name]}s wall (smoke observation)")

    try:
        with timed("identity"):
            ident = stage_identity(work, chip_env, dry)
        cache_dir = ident["compile_cache_dir"]
        cache_files = {"at_start": count_files(cache_dir)}

        with timed("native"):
            libs = stage_native(work, chip_env)

        with timed("kernels"):
            # Every device program at its production shape against its
            # plain reference (not an entry point: the one stage that
            # calls kernels directly, so an opt-in kernel that breaks
            # is seen before a user opts in).
            if dry:
                say("kernels: not run (no TPU)")
            else:
                out = must_run(
                    "kernels", [sys.executable, os.path.join(
                        REPO, "benchmarks", "kernel_check.py")],
                    os.path.join(work, "kernels.log"), chip_env, limit=600)
                for line in out.splitlines():
                    if line.startswith("kernel_check:"):
                        say(line)

        with timed("context"):
            ctx = os.path.join(work, "ctx")
            made = make_context(ctx, args.seed, **sizes)
            say(f"context: {made}")

        with timed("cold_build"):
            # The CPU oracle needs no chip (JAX_PLATFORMS=cpu), so it
            # builds beside the chip child instead of after it.
            s_cpu = os.path.join(work, "storage-cpu")
            oracle = start_build("oracle", work, ctx, s_cpu, cpu_env)
            s_tpu = os.path.join(work, "storage-tpu")
            cache_files["before_first_pass"] = count_files(cache_dir)
            cold = build("cold", work, ctx, s_tpu, chip_env)
            cache_files["after_first_pass"] = count_files(cache_dir)
            for line in cold["log"].splitlines():
                if "chunk route:" in line or "committed layer" in line:
                    say("cold: " + line.strip())
            layers_tpu = layers_of(s_tpu)
            if len(layers_tpu) != 2:
                raise Failed(f"cold: {len(layers_tpu)} layers committed, "
                             "the Dockerfile has two #!COMMIT layers")
            seen = [verify_layer(s_tpu, e) for e in layers_tpu.values()]
            tar_bytes = sum(s["tar_bytes"] for s in seen)
            say(f"cold: every chunk digest equals hashlib; layers {seen}")
            if not (sum(s["forced_max_cuts"] for s in seen)
                    and sum(s["content_cuts"] for s in seen)):
                raise Failed("cold: the context did not exercise both "
                             f"content cuts and forced cuts: {seen}")
            routes = check_device_hashed("cold", cold["report"], tar_bytes,
                                         ident, dry)
            init = probe_seconds(sessions)
            say(f"cold: backend init phases (seconds) {init}")
            say(f"cold: compile seconds {cold['compile_seconds']:.2f}, "
                f"persistent-cache hits {cold['cache_hits']}")

        with timed("cpu_oracle"):
            finish_build(oracle)
            layers_cpu = layers_of(s_cpu)
            if layers_cpu != layers_tpu:
                diff = [k for k in set(layers_cpu) | set(layers_tpu)
                        if layers_cpu.get(k) != layers_tpu.get(k)]
                raise Failed("cpu_oracle: layer digests or chunk lists "
                             f"differ from the CPU build's at {diff}")
            say(f"cpu_oracle: {len(layers_cpu)} layers, "
                f"{sum(len(e['chunks']) for e in layers_cpu.values())} "
                "chunks: digests and chunk lists identical")
            shutil.rmtree(s_cpu, ignore_errors=True)

        with timed("warm_build"):
            warm = build("warm", work, ctx, s_tpu, chip_env)
            moved = {
                name: sum(counter(warm["report"], name).values())
                for name in ("makisu_gear_scan_bytes_total",
                             "makisu_device_h2d_bytes_total",
                             "makisu_layer_commits_total")}
            if any(moved.values()) or layers_of(s_tpu) != layers_tpu:
                raise Failed(f"warm: expected a pure cache hit, got {moved}")
            say(f"warm: cache hit, bytes to the device {moved}")

        with timed("edit_build"):
            edited, undo_edit = edit_one_file(ctx, args.seed)
            say("edit: inserted 1000 bytes into "
                + os.path.relpath(edited, ctx))
            # A cold build of the edited tree by the CPU builder, again
            # beside the chip's work; read back after the farm stage.
            s_cold_edit = os.path.join(work, "storage-cpu-edit")
            oracle_edit = start_build("oracle-edit", work, ctx, s_cold_edit,
                                      cpu_env)
            edit = build("edit", work, ctx, s_tpu, chip_env)
            reused = sum(counter(edit["report"], "makisu_chunk_bytes_total",
                                 result="reused").values())
            rescanned = sum(counter(
                edit["report"], "makisu_gear_scan_bytes_total").values())
            layers_edit = {k: v for k, v in layers_of(s_tpu).items()
                           if k not in layers_tpu}
            if len(layers_edit) != 1:
                raise Failed(f"edit: {len(layers_edit)} new layers, "
                             "expected only layer b to rebuild")
            for entry in layers_edit.values():
                verify_layer(s_tpu, entry)
            if reused <= 0:
                raise Failed("edit: no chunk-dedup hit after a one-file edit")
            say(f"edit: rescanned {rescanned} bytes, chunk-dedup reused "
                f"{reused} bytes")
            shutil.rmtree(s_tpu, ignore_errors=True)

        with timed("farm"):
            farm_report = os.path.join(work, "farm.report.json")
            out = must_run("farm", [
                sys.executable, "-m", "makisu_tpu.cli", "--log-fmt",
                "console", "loadgen", "--hasher", "tpu",
                "--concurrency", str(farm["concurrency"]),
                "--builds", str(farm["builds"]),
                "--files", str(farm["files"]),
                "--file-kb", str(farm["file_kb"]),
                "--work-dir", os.path.join(work, "farm"),
                "--report", farm_report],
                os.path.join(work, "farm.log"), chip_env)
            with open(farm_report, encoding="utf-8") as f:
                loadgen = json.load(f)
            device = (loadgen.get("worker_health") or {}).get("device", {})
            say(f"farm: builds {loadgen['builds']}, failures "
                f"{loadgen['failures']}, device {json.dumps(device)}")
            if loadgen["failures"] or loadgen["builds"] != farm["builds"]:
                raise Failed("farm: not every build exited 0")
            probe = device.get("probe", {})
            if probe.get("state") != "ok" \
                    or probe.get("platform") != ident["platform"] \
                    or probe.get("device_kind") != ident["kind"]:
                raise Failed(f"farm: /healthz device probe is {probe}")
            if not dry:
                if device.get("hash_batch_failures") != 0:
                    raise Failed("farm: makisu_hash_batch_failures_total "
                                 f"is {device.get('hash_batch_failures')}")
                if device.get("hash_cross_build_batches", 0) < 1:
                    raise Failed("farm: no batch mixed two builds' chunks "
                                 "(makisu_hash_cross_build_batches_total)")
                buckets = sorted(
                    b for b, d in device.get("dispatch_seconds", {}).items()
                    if d.get("count"))
                if len(buckets) < 2:
                    raise Failed("farm: dispatch digests for buckets "
                                 f"{buckets}, expected both")
            shutil.rmtree(os.path.join(work, "farm"), ignore_errors=True)

        with timed("edit_oracle"):
            finish_build(oracle_edit)
            cold_edit = layers_of(s_cold_edit)
            for key, entry in layers_edit.items():
                if cold_edit.get(key) != entry:
                    raise Failed("edit_oracle: the rebuilt layer differs "
                                 "from a cold build of the edited tree")
            say("edit_oracle: rebuilt layer identical to a cold CPU build "
                "of the edited tree")
            undo_edit()
            shutil.rmtree(s_cold_edit, ignore_errors=True)

        with timed("second_pass"):
            # The first cold build again, in a new process, on the
            # unedited tree: everything it compiles is in the cache.
            cache_files["before_second_pass"] = count_files(cache_dir)
            s_second = os.path.join(work, "storage-second")
            second = build("second", work, ctx, s_second, chip_env)
            cache_files["after_second_pass"] = count_files(cache_dir)
            if layers_of(s_second) != layers_tpu:
                raise Failed("second_pass: digests differ from the first")
            say(f"second_pass: compile seconds first "
                f"{cold['compile_seconds']:.2f} (cache hits "
                f"{cold['cache_hits']}), second "
                f"{second['compile_seconds']:.2f} (cache hits "
                f"{second['cache_hits']}); cache files {cache_files} "
                f"in {cache_dir}")
            added = (cache_files["after_second_pass"]
                     - cache_files["before_second_pass"])
            if added:
                raise Failed(f"second_pass: added {added} files to the "
                             "compile cache")
            if not dry and cold["cache_hits"] == 0 and not (
                    second["compile_seconds"]
                    < 0.5 * cold["compile_seconds"]):
                raise Failed("second_pass: the cache did not cut the "
                             "compile seconds")
            shutil.rmtree(s_second, ignore_errors=True)

        with timed("multichip"):
            if ident["count"] >= 4 and not dry:
                out = must_run("multichip", [
                    sys.executable, "-c",
                    "import __graft_entry__ as g; g.dryrun_multichip(4)"],
                    os.path.join(work, "multichip.log"), chip_env)
                multichip = [ln for ln in out.splitlines()
                             if ln.startswith("dryrun_multichip OK")]
                for line in multichip:
                    say("multichip: " + line)
                if not multichip:
                    raise Failed("multichip: no OK line\n" + tail(out))
            else:
                multichip = f"not run ({ident['count']} device)"
                say(f"multichip: {multichip}")
    except Failed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        for child in list(Child.live):
            child.kill()
        if args.keep_work:
            say(f"work directory kept: {work}")
        else:
            shutil.rmtree(work, ignore_errors=True)
        assert "jax" not in sys.modules, "the parent must stay off JAX"

    say("observations " + json.dumps({
        "versions": {k: ident[k] for k in ("jax", "jaxlib", "libtpu")},
        "routes": routes,
        "native": libs,
        "compile_cache": {"dir": cache_dir, "files": cache_files,
                          "first_pass_compile_seconds":
                              round(cold["compile_seconds"], 2),
                          "second_pass_compile_seconds":
                              round(second["compile_seconds"], 2)},
        "backend_init_phase_seconds": init,
        "stage_wall_seconds_smoke_observations": stages,
        "sizes": {**made, "layer_tar_bytes": tar_bytes, "farm": farm},
        "multichip": multichip,
        "cuts": cuts,
    }))
    if not dry:
        print(result_line(ident), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
