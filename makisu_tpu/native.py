"""ctypes bindings for the native runtime pieces (native/).

``pgzip_compress``: parallel block-deflate gzip (native/pgzip.cpp) — the
capability the reference gets from pgzip (lib/tario/gzip.go:46). Falls
back cleanly when the shared library hasn't been built; callers check
``pgzip_available()``.

``LayerSinkHandle``: the native layer-commit pipeline
(native/layersink.cpp) — tar content framing, dual SHA-256, and
deterministic gzip in one C++ pass, replacing Python-side byte shuffling
on the hot path (reference: lib/builder/step/common.go:35-64).

ISA dispatch: libgear.so resolves its gear-scan route (avx2 / striped /
scalar) and SHA-256 batch route (shani / evp / scalar) once per
process from CPUID — one binary serves every host. The
``MAKISU_TPU_NATIVE_ISA`` env knob (read here at load) caps the
ladder; ``set_native_isa`` forces it in-process (tests/bench). Every
route emits byte-identical positions and digests: ISA is a throughput
knob and never enters cache identity.

Build: ``make -C native`` (g++ + zlib; no extra dependencies — SIMD
flags are probed per translation unit, see native/Makefile).
"""

from __future__ import annotations

import ctypes
import itertools
import os
import struct
import subprocess
import threading

# Containerized installs (Dockerfile) bake the prebuilt .so files at
# /makisu-internal/native and point this env var there; source checkouts
# use the sibling native/ directory.
_NATIVE_DIR = os.environ.get("MAKISU_TPU_NATIVE_DIR") or os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libpgzip.so")
_LSK_PATH = os.path.join(_NATIVE_DIR, "liblayersink.so")
_GEAR_PATH = os.path.join(_NATIVE_DIR, "libgear.so")
_TSK_PATH = os.path.join(_NATIVE_DIR, "libthreadstate.so")
_DSC_PATH = os.path.join(_NATIVE_DIR, "libdirscan.so")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_load_failed = False
_lsk_lib: ctypes.CDLL | None = None
_lsk_failed = False

DEFAULT_BLOCK = 128 * 1024

# Tap callback: (data_ptr, nbytes, user) — the uncompressed tar stream,
# called synchronously from the native pipeline on the writer's thread.
_TAP_FN = ctypes.CFUNCTYPE(None, ctypes.POINTER(ctypes.c_uint8),
                           ctypes.c_size_t, ctypes.c_void_p)


# What each library is built from (mirrors the Makefile rules): the
# staleness gate below must only compare a library against ITS inputs,
# or every rebuild of one library would smear false STALE errors over
# the others.
_LIB_SOURCES = {
    "libpgzip.so": ("pgzip.cpp", "deflate_common.h"),
    "liblayersink.so": ("layersink.cpp", "deflate_common.h",
                        "sha256_common.h"),
    "libgear.so": ("gear.cpp", "gear_simd.cpp", "sha_ni.cpp",
                   "gear_isa.h", "sha256_common.h"),
    "libthreadstate.so": ("threadstate.cpp",),
    "libdirscan.so": ("dirscan.cpp",),
}


def _ensure_built(lib_path: str) -> bool:
    """Run make (mtime-based, so stale .so files rebuild — their output
    bytes are cache identity) and report whether the library exists."""
    made = False
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                       capture_output=True, timeout=120)
        made = True
    except (OSError, subprocess.SubprocessError):
        pass  # no toolchain: a prebuilt library is still usable
    if not os.path.isfile(lib_path):
        return False
    if not made:
        # make could not run (or failed): the prebuilt library may
        # predate the sources — shout rather than silently serve old
        # routes. When make DID run, mtime-driven rebuilds are its job.
        _warn_if_stale(lib_path)
    return True


def _warn_if_stale(lib_path: str) -> None:
    """Loud staleness gate (CI has hit silent-stale .so confusion): if
    any of THIS library's sources is newer than the built library and
    make could not fix it (no toolchain, or a swallowed build failure),
    say so in the log instead of silently serving old routes.
    Correctness is unaffected — every route emits identical bytes — so
    this warns rather than refuses; an ABI mismatch (checked at load)
    refuses."""
    sources = _LIB_SOURCES.get(os.path.basename(lib_path), ())
    try:
        lib_mtime = os.path.getmtime(lib_path)
        stale = [
            name for name in sources
            if os.path.isfile(os.path.join(_NATIVE_DIR, name))
            and os.path.getmtime(os.path.join(_NATIVE_DIR, name))
            > lib_mtime]
    except OSError:
        return
    if stale:
        from makisu_tpu.utils import logging as log
        log.error(
            "%s is STALE vs %s and `make -C native` did not rebuild it "
            "— run `make -C native clean all` (or `make -C native "
            "check` to verify)", os.path.basename(lib_path),
            ", ".join(sorted(stale)))


def _open(path: str, abi_fn: str, abi: int,
          symbols: dict[str, tuple]) -> ctypes.CDLL | None:
    """The library at ``path`` with every symbol this module calls
    bound (``name: (restype, argtypes)``), or ``None``. A library is
    the one this tree builds or it is absent: one that cannot be
    loaded, lacks a symbol (ctypes raises ``AttributeError``, not
    ``OSError``, on a dlsym miss) or answers another ABI number is
    refused whole, and the log says so. With ``_lock`` held."""
    if not _ensure_built(path):
        return None
    try:
        lib = ctypes.CDLL(path)
        for name, (restype, argtypes) in symbols.items():
            fn = getattr(lib, name)
            fn.restype, fn.argtypes = restype, argtypes
        got = getattr(lib, abi_fn)()
        if got != abi:
            raise OSError(f"{abi_fn}() is {got}, this tree's is {abi}")
    except (OSError, AttributeError) as e:
        from makisu_tpu.utils import logging as log
        log.error("%s is not this tree's (%s) and stays unused — run "
                  "`make -C native clean all` to rebuild",
                  os.path.basename(path), e)
        return None
    return lib


_U8_P = ctypes.POINTER(ctypes.c_uint8)
_U32_P = ctypes.POINTER(ctypes.c_uint32)
_U64_P = ctypes.POINTER(ctypes.c_uint64)
_SIZE_P = ctypes.POINTER(ctypes.c_size_t)

_PGZ_SYMBOLS = {
    "pgz_compress": (_U8_P, [ctypes.c_char_p, ctypes.c_size_t,
                             ctypes.c_int, ctypes.c_size_t, ctypes.c_int,
                             _SIZE_P]),
    "pgz_block": (_U8_P, [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int,
                          ctypes.c_int, _SIZE_P]),
    "pgz_blocks": (_U8_P, [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int,
                           ctypes.c_size_t, ctypes.c_int, _SIZE_P]),
    "pgz_free": (None, [_U8_P]),
}


def _load() -> ctypes.CDLL | None:
    global _lib, _load_failed
    with _lock:
        if _lib is None and not _load_failed:
            _lib = _open(_LIB_PATH, "pgz_abi_version", 1, _PGZ_SYMBOLS)
            _load_failed = _lib is None
        return _lib


def pgzip_available() -> bool:
    return _load() is not None


_LSK_SYMBOLS = {
    "lsk_new": (ctypes.c_void_p, [ctypes.c_int, ctypes.c_int, ctypes.c_int,
                                  ctypes.c_size_t, ctypes.c_int]),
    "lsk_set_tap": (None, [ctypes.c_void_p, _TAP_FN, ctypes.c_void_p]),
    "lsk_write": (ctypes.c_int, [ctypes.c_void_p, ctypes.c_char_p,
                                 ctypes.c_size_t]),
    "lsk_write_entries": (ctypes.c_int, [
        ctypes.c_void_p, ctypes.c_size_t, ctypes.c_char_p, _U64_P,
        ctypes.POINTER(ctypes.c_char_p), _U64_P,
        ctypes.POINTER(ctypes.c_int64)]),
    "lsk_finish": (ctypes.c_int, [ctypes.c_void_p, _U8_P, _U8_P, _U64_P,
                                  _U64_P]),
    "lsk_compress_seconds": (ctypes.c_double, [ctypes.c_void_p]),
    "lsk_wall_seconds": (ctypes.c_double, [ctypes.c_void_p]),
    "lsk_wait_seconds": (ctypes.c_double, [ctypes.c_void_p]),
    "lsk_blob_write_seconds": (ctypes.c_double, [ctypes.c_void_p]),
    "lsk_prefetch_stats": (None, [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.c_double), _U64_P]),
    "lsk_free": (None, [ctypes.c_void_p]),
}


def _load_lsk() -> ctypes.CDLL | None:
    global _lsk_lib, _lsk_failed
    with _lock:
        if _lsk_lib is None and not _lsk_failed:
            _lsk_lib = _open(_LSK_PATH, "lsk_abi_version", 3, _LSK_SYMBOLS)
            _lsk_failed = _lsk_lib is None
        return _lsk_lib


def layersink_available() -> bool:
    return _load_lsk() is not None


_gear_lib: ctypes.CDLL | None = None
_gear_failed = False
_isa_route: tuple[str, str] | None = None  # resolved (gear, sha) names

# Combined ISA ladder the MAKISU_TPU_NATIVE_ISA knob selects from. Each
# level caps BOTH halves of the hot path; "auto" (the default) resolves
# to the best the CPU/build supports. ISA is a throughput knob only:
# cut positions and digests are byte-identical at every level, so it
# must NEVER enter cache identity.
ISA_LEVELS = ("auto", "scalar", "striped", "simd")
_ISA_MAP = {
    # level: (gear route preference order, sha route preference order)
    "scalar": (("scalar",), ("scalar",)),
    "striped": (("striped",), ("evp", "scalar")),
    "simd": (("avx2", "striped"), ("shani", "evp", "scalar")),
    "auto": (("auto",), ("auto",)),
}


def _apply_isa(lib: ctypes.CDLL, level: str) -> tuple[str, str]:
    """Set both route halves for ``level`` (first supported preference
    wins) and return the resolved (gear, sha) route names."""
    gear_prefs, sha_prefs = _ISA_MAP[level]
    for name in gear_prefs:
        if lib.gear_set_gear_isa(name.encode()) == 0:
            break
    for name in sha_prefs:
        if lib.gear_set_sha_isa(name.encode()) == 0:
            break
    return (lib.gear_gear_isa().decode(), lib.gear_sha_isa().decode())


_GEAR_SYMBOLS = {
    "gear_scan": (None, [_U8_P, ctypes.c_size_t, _U32_P, ctypes.c_uint32,
                         _U8_P]),
    "gear_scan_pos2": (ctypes.c_int, [
        _U8_P, ctypes.c_size_t, _U32_P, ctypes.c_uint32, _U32_P,
        ctypes.c_size_t, _U32_P, ctypes.c_size_t]),
    "gear_sha256_batch": (ctypes.c_int, [_U8_P, _U64_P, _U64_P,
                                         ctypes.c_size_t, _U8_P]),
    "gear_set_gear_isa": (ctypes.c_int, [ctypes.c_char_p]),
    "gear_set_sha_isa": (ctypes.c_int, [ctypes.c_char_p]),
    "gear_isa_supported": (ctypes.c_int, [ctypes.c_char_p]),
    "gear_gear_isa": (ctypes.c_char_p, []),
    "gear_sha_isa": (ctypes.c_char_p, []),
}


def _load_gear() -> ctypes.CDLL | None:
    global _gear_lib, _gear_failed, _isa_route
    with _lock:
        if _gear_lib is not None or _gear_failed:
            return _gear_lib
        lib = _open(_GEAR_PATH, "gear_abi_version", 2, _GEAR_SYMBOLS)
        if lib is None:
            _gear_failed = True
            return None
        level = os.environ.get("MAKISU_TPU_NATIVE_ISA", "auto")
        if level not in _ISA_MAP:
            from makisu_tpu.utils import logging as log
            log.warning(
                "unknown MAKISU_TPU_NATIVE_ISA=%r (valid: %s); "
                "using auto", level, "/".join(ISA_LEVELS))
            level = "auto"
        _isa_route = _apply_isa(lib, level)
        _note_isa_route(level)
        _gear_lib = lib
        return _gear_lib


def _note_isa_route(level: str) -> None:
    """Log the resolved route once per process and publish the
    per-route ``makisu_native_isa`` info gauge (process-global, so a
    worker's /metrics carries it; the per-build ``makisu_build_info``
    gauge carries the same string as a label)."""
    from makisu_tpu.utils import logging as log
    from makisu_tpu.utils import metrics
    gear_r, sha_r = _isa_route  # built inline: _lock is held here
    log.info("native ISA route resolved: gear=%s sha=%s (knob=%s)",
             gear_r, sha_r, level)
    try:
        metrics.global_registry().gauge_set(
            "makisu_native_isa", 1, route=f"gear={gear_r},sha={sha_r}")
    except Exception:  # noqa: BLE001 - telemetry must not fail loads
        pass


def gear_scan_available() -> bool:
    return _load_gear() is not None


def sha_batch_available() -> bool:
    return gear_scan_available()


def isa_route() -> str | None:
    """The resolved ISA route string, e.g. ``"gear=avx2,sha=shani"`` —
    what the build_info label and the bench record carry. None when the
    native library is unavailable."""
    if _load_gear() is None:
        return None
    return f"gear={_isa_route[0]},sha={_isa_route[1]}"


def isa_route_if_resolved() -> str | None:
    """Like :func:`isa_route` but never forces the library load —
    for telemetry on commands that may not touch the hash path."""
    if _isa_route is None:
        return None
    return f"gear={_isa_route[0]},sha={_isa_route[1]}"


def set_native_isa(level: str) -> str | None:
    """Force an ISA level in-process (tests / bench sweeps). ``level``
    is one of ISA_LEVELS; returns the resolved route string. The
    MAKISU_TPU_NATIVE_ISA env knob applies the same mapping once at
    library load."""
    global _isa_route
    if level not in _ISA_MAP:
        raise ValueError(f"unknown ISA level {level!r}; "
                         f"valid: {'/'.join(ISA_LEVELS)}")
    lib = _load_gear()
    if lib is None:
        return None
    old = isa_route()
    _isa_route = _apply_isa(lib, level)
    new = isa_route()
    if new != old:
        # Keep the per-route info gauge tracking the LIVE route: the
        # old series drops to 0 so a scraper never sees two routes at 1.
        try:
            from makisu_tpu.utils import metrics
            reg = metrics.global_registry()
            if old is not None:
                reg.gauge_set("makisu_native_isa", 0, route=old)
            reg.gauge_set("makisu_native_isa", 1, route=new)
        except Exception:  # noqa: BLE001 - telemetry plane
            pass
    return new


def isa_supported(name: str) -> bool:
    """Whether this host/build can run a specific route half
    ("avx2", "shani", "evp", "striped", "scalar")."""
    lib = _load_gear()
    return bool(lib is not None
                and lib.gear_isa_supported(name.encode()))


def sha256_batch(buf, lengths):
    """SHA-256 each slice of ``buf`` (slice i covers
    ``[sum(lengths[:i]), sum(lengths[:i+1]))``); returns an
    ``np.uint8[count, 32]`` digest array. ONE ctypes call for the whole
    batch — the GIL is released end to end, which is what lets pooled
    chunk hashing scale past the per-call GIL ping-pong that per-chunk
    hashlib suffers at ~8KiB sizes. Digests are byte-identical to
    hashlib on every dispatched route (SHA-NI multi-buffer / OpenSSL
    EVP / audited scalar fallback)."""
    import numpy as np

    lib = _load_gear()
    if lib is None:
        raise OSError("libgear.so unavailable")
    lengths64 = np.ascontiguousarray(lengths, dtype=np.uint64)
    offsets = np.zeros(len(lengths64), dtype=np.uint64)
    np.cumsum(lengths64[:-1], out=offsets[1:])
    out = np.empty((len(lengths64), 32), dtype=np.uint8)
    # frombuffer: zero-copy for bytes AND bytearray (the pooled commit
    # route hands its batch bytearray straight through).
    buf_arr = np.frombuffer(buf, dtype=np.uint8)
    rc = lib.gear_sha256_batch(
        buf_arr.ctypes.data_as(_U8_P), offsets.ctypes.data_as(_U64_P),
        lengths64.ctypes.data_as(_U64_P), len(lengths64),
        out.ctypes.data_as(_U8_P))
    if rc != 0:
        raise RuntimeError("gear_sha256_batch failed")
    return out


def gear_scan_bits(buf, table, mask: int):
    """Boundary-candidate bits for ``buf`` (np.uint8 array) — the CPU
    recurrence form of ops.gear's windowed scan, bit-identical. ``table``
    is gear.gear_table() (np.uint32[256]); returns np.uint8[len(buf)]
    with 1 where (h & mask) == 0."""
    import numpy as np

    lib = _load_gear()
    if lib is None:
        raise OSError("libgear.so unavailable")
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    table = np.ascontiguousarray(table, dtype=np.uint32)
    out = np.empty(len(buf), dtype=np.uint8)
    lib.gear_scan(
        buf.ctypes.data_as(_U8_P), len(buf), table.ctypes.data_as(_U32_P),
        ctypes.c_uint32(mask), out.ctypes.data_as(_U8_P))
    return out


def gear_scan_positions(buf, table, mask: int):
    """Boundary-candidate POSITIONS for ``buf`` — same predicate as
    gear_scan_bits with no bit-array materialization or host rescan.
    Returns a sorted np.uint32 array. Slot capacity is ~several-x the
    expected hit rate; the (adversarial-data) overflow case falls back
    to the bit scan, so the result is always complete.

    Routes through the library's runtime ISA dispatch: 8 output slots,
    so the AVX2 kernel's 8 lanes map 1:1."""
    import numpy as np

    lib = _load_gear()
    if lib is None:
        raise OSError("libgear.so unavailable")
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    table = np.ascontiguousarray(table, dtype=np.uint32)
    n = len(buf)
    expected = n // max(mask, 1) + 1
    nslots = 8
    slot_cap = max(64, expected)  # per-slot ~nslots-x margin overall
    out = np.empty(nslots * slot_cap, dtype=np.uint32)
    counts = np.zeros(nslots, dtype=np.uint32)
    rc = lib.gear_scan_pos2(
        buf.ctypes.data_as(_U8_P), n, table.ctypes.data_as(_U32_P),
        ctypes.c_uint32(mask), out.ctypes.data_as(_U32_P), slot_cap,
        counts.ctypes.data_as(_U32_P), nslots)
    if rc != 0:
        bits = gear_scan_bits(buf, table, mask)
        return np.nonzero(bits)[0].astype(np.uint32)
    return np.concatenate([
        out[s * slot_cap:s * slot_cap + int(counts[s])]
        for s in range(nslots)])


_DOUBLE_P = ctypes.POINTER(ctypes.c_double)
_TSK_SYMBOLS = {
    "tsk_table": (_DOUBLE_P, []),
    "tsk_vitals": (_DOUBLE_P, []),
    "tsk_slots": (ctypes.c_int, []),
    "tsk_probe": (None, []),
    "tsk_calibrate": (ctypes.c_int, [ctypes.c_int, ctypes.c_int,
                                     ctypes.c_int]),
    "tsk_watch": (ctypes.c_int, [ctypes.c_int]),
    "tsk_unwatch": (None, [ctypes.c_int, ctypes.c_int]),
    "tsk_test_refuse": (None, [ctypes.c_int]),
}

# A row of the reader's table (native/threadstate.cpp: enum Col) and
# its vitals row (enum Vital), in order.
TSK_COLS = ("tid", "run", "runqueue", "system", "running",
            "interpreter_lock", "wait", "fs", "socket", "other")
TSK_VITALS = ("source", "schedstat", "lock_ref", "sightings", "beats",
              "reads", "busy_seconds")


class ThreadStateReader:
    """libthreadstate.so: the kernel's word on where each watched
    thread is (native/threadstate.cpp). ``table`` and ``vitals`` are
    the library's own memory, read without a call; the reads of
    ``/proc/self/task`` happen on the library's thread, never on the
    caller's."""

    def __init__(self, lib: ctypes.CDLL) -> None:
        self.lib = lib
        # Watching and unwatching take a mutex for microseconds: called
        # with the interpreter lock kept, so the sampler's beat hands
        # the lock to nobody (a hand-over costs it a turn of the queue
        # it is there to measure).
        held = ctypes.PyDLL(lib._name)
        self.watch, self.unwatch = held.tsk_watch, held.tsk_unwatch
        for fn in (self.watch, self.unwatch):
            fn.restype, fn.argtypes = _TSK_SYMBOLS[fn.__name__]
        self.table = ctypes.cast(lib.tsk_table(), ctypes.POINTER(
            ctypes.c_double * (lib.tsk_slots() * len(TSK_COLS)))).contents
        self.vitals = ctypes.cast(lib.tsk_vitals(), ctypes.POINTER(
            ctypes.c_double * len(TSK_VITALS))).contents
        lib.tsk_probe()

    def row(self, slot: int) -> list[float]:
        base = slot * len(TSK_COLS)
        return self.table[base:base + len(TSK_COLS)]

    def vital(self, name: str) -> float:
        return self.vitals[TSK_VITALS.index(name)]


_tsk: ThreadStateReader | None = None
_tsk_failed = False


def thread_state_reader() -> ThreadStateReader | None:
    """The process's reader, or ``None`` where the library cannot be
    built or loaded."""
    global _tsk, _tsk_failed
    with _lock:
        if _tsk is None and not _tsk_failed:
            lib = _open(_TSK_PATH, "tsk_abi_version", 1, _TSK_SYMBOLS)
            _tsk = ThreadStateReader(lib) if lib is not None else None
            _tsk_failed = _tsk is None
        return _tsk


_DSC_SYMBOLS = {
    "dsc_read": (ctypes.c_int, [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_void_p, ctypes.c_size_t,
        ctypes.c_void_p, ctypes.c_size_t, _U64_P]),
    "dsc_test_before_lstat": (None, [ctypes.c_void_p]),
}

# A child's record where its lstat was asked for (native/dirscan.cpp):
# the 19 fields of ``os.stat_result`` in its own order.
_DSC_RECORD = struct.Struct("=qQQ4q3q3d3q2qQ")
_DSC_SMALL, _DSC_RANGE = -1, -2
# What the first call of a directory brings: room for 512 children and
# 32 bytes of name each. A larger directory is read again, once, into
# buffers of the size the first call reported.
_DSC_FIRST_CHILDREN, _DSC_FIRST_NAMES = 512, 16384
_DT_DIR = 4


class DirReader:
    """libdirscan.so: a directory's names and types, and where asked
    every child's ``lstat``, by one foreign call with the interpreter
    lock free (native/dirscan.cpp). Stateless: any thread may call."""

    def __init__(self, lib: ctypes.CDLL) -> None:
        self.lib = lib
        self._read = lib.dsc_read

    def read(self, path: str, want_stat: bool) -> list[tuple] | None:
        """``path``'s children in the directory's own order, unsorted:
        ``(name, os.stat_result)`` with ``want_stat``, the result equal
        to ``os.lstat``'s field for field and a child gone before its
        ``lstat`` left out; else ``(name, is a directory itself)`` from
        the type bits. Names are decoded as ``os.scandir`` decodes them. Raises the ``OSError`` the
        directory's ``open`` or read gave; ``None`` where a child's time
        does not fit the record (the caller asks ``os`` itself)."""
        raw = os.fsencode(path)
        record = _DSC_RECORD.size if want_stat else 1
        children, names_cap = _DSC_FIRST_CHILDREN, _DSC_FIRST_NAMES
        out = (ctypes.c_uint64 * 2)()
        while True:
            records = bytearray(children * record)
            names = bytearray(names_cap)
            rc = self._read(
                raw, int(want_stat),
                (ctypes.c_char * len(records)).from_buffer(records),
                len(records),
                (ctypes.c_char * names_cap).from_buffer(names), names_cap,
                out)
            if rc != _DSC_SMALL:
                break
            # Grown past what the call saw, for a directory that is
            # growing meanwhile.
            children, names_cap = out[0] + 64, out[1] + 4096
            from makisu_tpu.utils import metrics
            metrics.counter_add(metrics.DIR_READ_REPEATS_TOTAL)
        if rc == _DSC_RANGE:
            return None
        if rc:
            raise OSError(rc, os.strerror(rc), path)
        count = out[0]
        if not count:
            return []
        listed = os.fsdecode(bytes(names[:out[1] - 1])).split("\0")
        if not want_stat:
            return [(name, d_type == _DT_DIR)
                    for name, d_type in zip(listed, records)]
        rows = _DSC_RECORD.iter_unpack(
            memoryview(records)[:count * record])
        return [(name, os.stat_result(row))
                for name, row in zip(listed, rows)]


_dsc: DirReader | None = None
_dsc_failed = False


def dir_reader() -> DirReader | None:
    """The process's directory reader, or ``None`` where the library
    was not built or is not this tree's."""
    global _dsc, _dsc_failed
    if _dsc is not None or _dsc_failed:
        return _dsc
    with _lock:
        if _dsc is None and not _dsc_failed:
            lib = _open(_DSC_PATH, "dsc_abi_version", 1, _DSC_SYMBOLS)
            _dsc = DirReader(lib) if lib is not None else None
            _dsc_failed = _dsc is None
        return _dsc


class LayerSinkHandle:
    """One native layer-commit pipeline bound to an output fd."""

    def __init__(self, out_fd: int, backend: str, level: int,
                 block_size: int = DEFAULT_BLOCK,
                 nthreads: int | None = None) -> None:
        lib = _load_lsk()
        if lib is None:
            raise RuntimeError("native layersink library unavailable; "
                               "run `make -C native`")
        self._lib = lib
        if nthreads is None:
            nthreads = os.cpu_count() or 1
        self._handle = lib.lsk_new(out_fd, 1 if backend == "pgzip" else 0,
                                   level, block_size, nthreads)
        if not self._handle:
            raise RuntimeError("lsk_new failed")

    def _live(self):
        if not self._handle:
            raise RuntimeError("native layer sink already closed")
        return self._handle

    def set_tap(self, fn) -> None:
        """Stream every uncompressed tar byte to ``fn(bytes)`` as well
        (the TPU chunker's intake). The CFUNCTYPE wrapper is pinned on
        self so the callback outlives the ctypes call.

        ctypes callbacks cannot propagate exceptions into C; a failure
        is recorded and re-raised by the NEXT write/finish call, so a
        dying chunker fails the build instead of silently producing
        wrong (cache-identity-bearing) fingerprints.

        The sink hands ``fn`` the stream through a staging buffer, on
        the writing thread: once a filled 256 KiB and once at the end
        of every call, so each call returns with ``fn`` having seen all
        it wrote."""
        self._tap_error: list = []

        def trampoline(ptr, n, _user):
            if self._tap_error:
                return  # already failed; drain quietly until re-raise
            try:
                fn(ctypes.string_at(ptr, n))
            except BaseException as e:  # noqa: BLE001
                self._tap_error.append(e)
        self._tap_ref = _TAP_FN(trampoline)  # keep alive
        self._lib.lsk_set_tap(self._live(), self._tap_ref, None)

    def _check_tap(self) -> None:
        err = getattr(self, "_tap_error", None)
        if err:
            raise RuntimeError("layer chunk tap failed") from err[0]

    def write(self, data: bytes) -> None:
        if self._lib.lsk_write(self._live(), data, len(data)) != 0:
            raise RuntimeError("native layer sink write failed")
        self._check_tap()

    def write_entries(self, headers: list[bytes],
                      paths: list[str | None], sizes: list[int]) -> None:
        """A batch of tar entries in stream order, in one call with the
        interpreter lock let go: each entry's rendered header, then,
        where its path is not ``None`` and its size not 0, the file's
        first ``size`` bytes and the padding. The sink reads the
        batch's files of up to 8 MiB ahead on threads of its own. A
        file that cannot be read, or ends before its size, raises
        ``OSError`` naming it; nothing of a later entry has reached the
        stream then, and the sink stays failed."""
        n = len(headers)
        offsets = (ctypes.c_uint64 * (n + 1))(
            0, *itertools.accumulate(map(len, headers)))
        at_fault = ctypes.c_int64(-1)
        rc = self._lib.lsk_write_entries(
            self._live(), n, b"".join(headers), offsets,
            (ctypes.c_char_p * n)(
                *[None if p is None else os.fsencode(p) for p in paths]),
            (ctypes.c_uint64 * n)(*sizes), ctypes.byref(at_fault))
        if rc in (-2, -3):
            i = at_fault.value
            raise OSError(
                f"native layer sink could not read {paths[i]}" if rc == -2
                else f"{paths[i]} shrank below its header size {sizes[i]}")
        if rc != 0:
            raise RuntimeError("native layer sink write failed")
        # After the rc checks: a tap failure must not mask the
        # root-cause file error above.
        self._check_tap()

    def finish(self) -> tuple[str, str, int, int]:
        """Returns (tar_sha_hex, gzip_sha_hex, gzip_size, tar_size)."""
        tar_sha = (ctypes.c_uint8 * 32)()
        gz_sha = (ctypes.c_uint8 * 32)()
        gz_size = ctypes.c_uint64(0)
        tar_size = ctypes.c_uint64(0)
        rc = self._lib.lsk_finish(self._live(), tar_sha, gz_sha,
                                  ctypes.byref(gz_size),
                                  ctypes.byref(tar_size))
        if rc != 0:
            raise RuntimeError("native layer sink finish failed")
        self._check_tap()
        return (bytes(tar_sha).hex(), bytes(gz_sha).hex(),
                gz_size.value, tar_size.value)

    def compress_seconds(self) -> float:
        """Seconds the gzip stream kept a thread busy (summed over the
        pgzip lanes)."""
        return self._lib.lsk_compress_seconds(self._live())

    def wall_seconds(self) -> float:
        """Seconds the gzip stream had a block queued or deflating:
        the wall time of the pgzip pool's work (``compress_seconds`` is
        its CPU time); under zlib the compressor thread's busy
        seconds."""
        return self._lib.lsk_wall_seconds(self._live())

    def wait_seconds(self) -> float:
        """Seconds the writer was blocked on the gzip stream: the zlib
        backend's ring full or draining in ``finish``; the pgzip pool's
        oldest block not yet deflated, with more than 2 x lanes + 2 in
        flight or in ``finish``."""
        return self._lib.lsk_wait_seconds(self._live())

    def blob_write_seconds(self) -> float:
        """Seconds the writer itself digested and wrote compressed
        blocks (pgzip; 0 under zlib, whose compressor thread does
        both)."""
        return self._lib.lsk_blob_write_seconds(self._live())

    def prefetch_stats(self) -> tuple[float, int, int, int]:
        """(seconds the writer was blocked on one of the sink's reader
        threads, files a reader had ready, files the writer waited for,
        files it streamed itself)."""
        read_wait = ctypes.c_double(0)
        counts = (ctypes.c_uint64 * 3)()
        self._lib.lsk_prefetch_stats(self._live(), ctypes.byref(read_wait),
                                     counts)
        return (read_wait.value, *counts)

    def close(self) -> None:
        """Frees the sink; one that was never finished (its build died
        between two entries) first stops and joins its compressor and
        its readers."""
        if self._handle:
            self._lib.lsk_free(self._handle)
            self._handle = None

    def __del__(self) -> None:
        self.close()


def pgzip_compress(data: bytes, level: int = 6,
                   block_size: int = DEFAULT_BLOCK,
                   nthreads: int | None = None) -> bytes:
    """Compress to a single deterministic gzip member using parallel
    block deflate. Output depends only on (data, level, block_size)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native pgzip library unavailable; run "
                           "`make -C native`")
    if nthreads is None:
        nthreads = os.cpu_count() or 1
    out_n = ctypes.c_size_t(0)
    buf = lib.pgz_compress(data, len(data), level, block_size, nthreads,
                           ctypes.byref(out_n))
    if not buf:
        raise RuntimeError("pgz_compress failed")
    try:
        return ctypes.string_at(buf, out_n.value)
    finally:
        lib.pgz_free(buf)


_GZIP_HEADER = bytes([0x1f, 0x8b, 0x08, 0, 0, 0, 0, 0, 0, 0xff])


def _block_compress(data: bytes, level: int, last: bool) -> bytes:
    lib = _load()
    assert lib is not None
    out_n = ctypes.c_size_t(0)
    buf = lib.pgz_block(data, len(data), level, 1 if last else 0,
                        ctypes.byref(out_n))
    if not buf:
        raise RuntimeError("pgz_block failed")
    try:
        return ctypes.string_at(buf, out_n.value)
    finally:
        lib.pgz_free(buf)


def deflate_blocks(data: bytes, level: int, block_size: int,
                   last: bool) -> bytes:
    """Compress ``data`` as consecutive ``block_size`` raw-deflate
    slices (sync-flush terminated; the final slice Z_FINISH when
    ``last``) in ONE GIL-released native call — the block-compress
    stage's per-lane unit (tario.BlockGzipWriter). Byte-identical to
    compressing the slices one ``pgz_block`` call at a time."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native pgzip library unavailable; run "
                           "`make -C native`")
    out_n = ctypes.c_size_t(0)
    buf = lib.pgz_blocks(data, len(data), level, block_size,
                         1 if last else 0, ctypes.byref(out_n))
    if not buf:
        raise RuntimeError("pgz_blocks failed")
    try:
        return ctypes.string_at(buf, out_n.value)
    finally:
        lib.pgz_free(buf)


class PgzipWriter:
    """Streaming parallel gzip writer (file-like: write/flush/close).

    Buffers ``block_size`` bytes at a time, compresses blocks on a thread
    pool (ctypes releases the GIL during the native call), and writes
    segments in order — bounded memory, identical output bytes to
    ``pgzip_compress`` for the same (level, block_size).
    """

    def __init__(self, fileobj, level: int = 6,
                 block_size: int = DEFAULT_BLOCK,
                 workers: int | None = None) -> None:
        if not pgzip_available():
            raise RuntimeError("native pgzip library unavailable")
        from concurrent.futures import ThreadPoolExecutor
        import zlib
        self._out = fileobj
        self._level = level
        self._block = block_size
        self._buf = bytearray()
        self._crc = zlib.crc32(b"")
        self._size = 0
        self._pool = ThreadPoolExecutor(workers or (os.cpu_count() or 1))
        self._pending = []  # ordered futures
        self._out.write(_GZIP_HEADER)
        self._closed = False

    def write(self, data: bytes) -> int:
        import zlib
        self._crc = zlib.crc32(data, self._crc)
        self._size += len(data)
        self._buf.extend(data)
        while len(self._buf) >= self._block:
            chunk = bytes(self._buf[:self._block])
            del self._buf[:self._block]
            self._pending.append(self._pool.submit(
                _block_compress, chunk, self._level, False))
            self._drain(max_pending=2 * (os.cpu_count() or 1))
        return len(data)

    def _drain(self, max_pending: int = 0) -> None:
        """Write completed segments in order; block only when the queue
        exceeds ``max_pending`` (bounds memory)."""
        while self._pending:
            if len(self._pending) > max_pending or self._pending[0].done():
                self._out.write(self._pending.pop(0).result())
            else:
                break

    def flush(self) -> None:
        self._out.flush()

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._pending.append(self._pool.submit(
            _block_compress, bytes(self._buf), self._level, True))
        self._buf.clear()
        for fut in self._pending:
            self._out.write(fut.result())
        self._pending = []
        self._pool.shutdown()
        trailer = (self._crc & 0xFFFFFFFF).to_bytes(4, "little") + \
            (self._size & 0xFFFFFFFF).to_bytes(4, "little")
        self._out.write(trailer)

    def __enter__(self) -> "PgzipWriter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
