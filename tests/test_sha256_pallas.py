"""SHA-256 Pallas kernel: route selection, the parity probe, and
(opt-in, slow on CPU) interpret-mode correctness.

The kernel's round math is sha256._schedule_rounds16 / _round — the
exact functions the heavily-tested XLA path runs — so CPU CI focuses on
the dispatch logic; bit-level kernel validation runs on the chip
(benchmarks/kernel_check.py compares every digest with hashlib at both
production shapes) and via the per-process parity probe in production.
"""

import itertools
import os

import numpy as np
import pytest

from makisu_tpu.chunker import route
from makisu_tpu.ops import sha256_pallas


def _hashlib_digests(data, lengths):
    import hashlib

    return [hashlib.sha256(data[i, : lengths[i]].tobytes()).digest()
            for i in range(len(lengths))]


def _hashlib_kernel(calls):
    """A stand-in kernel that is digest-correct by construction
    (hashlib, not the slow-on-CPU lane path — the probe runs the
    production shape itself)."""
    def kernel(data, lengths, interpret=False):
        data, lengths = np.asarray(data), np.asarray(lengths)
        calls.append(data.shape)
        out = np.zeros((len(lengths), 8), np.uint32)
        for i, digest in enumerate(_hashlib_digests(data, lengths)):
            out[i] = np.frombuffer(digest, dtype=">u4")
        return out
    return kernel


@pytest.fixture(autouse=True)
def _fresh_parity(monkeypatch):
    monkeypatch.setattr(sha256_pallas, "_parity_ok", {})


def _tpu_route(sha="pallas"):
    return route.ChunkRoute("pallas", sha, "tpu", "TPU v5 lite", 1)


def test_select_is_a_pure_function_of_backend_and_options():
    """The whole decision table: no breaker, no history, nothing read
    but the arguments."""
    sel = route.select
    # A TPU backend rides both kernels.
    assert sel("tpu", False, False, {}) == ("pallas", "pallas")
    assert sel("tpu", True, False, {}) == ("pallas", "pallas")
    assert sel("tpu", False, False,
               {"MAKISU_TPU_PALLAS": "0"}) == ("xla", "xla")
    # The native route never engages on an accelerator, even with the
    # library present.
    assert sel("tpu", False, True, {}) == ("pallas", "pallas")
    # A CPU backend chunks natively, except through the shared service,
    # without the library, or when told not to.
    assert sel("cpu", False, True, {}) == ("native", "native")
    assert sel("cpu", True, True, {}) == ("xla", "xla")
    assert sel("cpu", False, False, {}) == ("xla", "xla")
    assert sel("cpu", False, True,
               {"MAKISU_TPU_CHUNK_NATIVE": "0"}) == ("xla", "xla")
    # Forced kernels on the CPU: gear in interpret mode, SHA stays on
    # XLA (the kernel's unrolled body explodes XLA:CPU compile time).
    assert sel("cpu", True, True,
               {"MAKISU_TPU_PALLAS": "1"}) == ("pallas", "xla")


def test_select_answers_three_names_over_its_whole_domain():
    """Platform × shared × library × ``MAKISU_TPU_PALLAS``: ``native``,
    ``xla`` and ``pallas`` come back and no other name, and the retired
    option for a second gear kernel changes nothing."""
    sel = route.select
    retired = "MAKISU_TPU_PALLAS" + "_V2"
    for platform, shared, native_ok, forced in itertools.product(
            ("cpu", "tpu"), (False, True), (False, True), (None, "0", "1")):
        environ = {} if forced is None else {"MAKISU_TPU_PALLAS": forced}
        gear, sha = sel(platform, shared, native_ok, environ)
        assert (gear, sha) in {("native", "native"), ("xla", "xla"),
                               ("pallas", "pallas"), ("pallas", "xla")}
        assert (gear == "native") == (
            platform == "cpu" and not shared and native_ok)
        assert sel(platform, shared, native_ok,
                   {**environ, retired: "1"}) == (gear, sha)


def test_route_is_logged_once_and_labels_the_counters(monkeypatch):
    """One `chunk route:` line per process and decision, and the bytes
    counters carry the decision's names — not whichever route ran
    last."""
    from makisu_tpu.chunker.cdc import ChunkSession
    from makisu_tpu.utils import logging as log
    from makisu_tpu.utils import metrics

    lines = []
    monkeypatch.setattr(
        log, "info",
        lambda msg, *a, **k: lines.append(msg % a if a else msg))
    route._announce.cache_clear()
    monkeypatch.setenv("MAKISU_TPU_CHUNK_NATIVE", "0")
    payload = np.random.default_rng(5).integers(
        0, 256, size=300_000, dtype=np.uint8).tobytes()
    registry = metrics.MetricsRegistry()
    token = metrics.set_build_registry(registry)
    try:
        for _ in range(2):
            s = ChunkSession(block=128 * 1024)
            s.update(payload)
            assert s.finish()
    finally:
        metrics.reset_build_registry(token)
    logged = [ln for ln in lines if ln.startswith("chunk route:")]
    assert len(logged) == 1
    assert logged[0].startswith("chunk route: device cpu ")
    assert logged[0].endswith("gear=xla sha=xla")
    assert registry.counter_total("makisu_gear_scan_bytes_total",
                                  backend="xla") == 2 * len(payload)
    assert registry.counter_total("makisu_bytes_hashed_total",
                                  backend="xla", path="cdc") \
        == 2 * len(payload)
    assert registry.counter_total("makisu_bytes_hashed_total") \
        == 2 * len(payload)

    # The native route says so, once.
    monkeypatch.delenv("MAKISU_TPU_CHUNK_NATIVE")
    s = ChunkSession()
    if s._native:
        assert lines[-1] == "chunk route: native (backend cpu)"


def test_xla_route_never_touches_kernel(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("kernel dispatched on the xla route")

    monkeypatch.setattr(sha256_pallas, "sha256_lanes_pallas", boom)
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=(16, 256), dtype=np.uint8)
    lengths = rng.integers(0, 247, size=16).astype(np.int32)
    got = np.asarray(route.hash_lanes(_tpu_route("xla"), data, lengths))
    assert [g.astype(">u4").tobytes() for g in got] == _hashlib_digests(
        data, lengths)


def test_parity_probe_mismatch_raises(monkeypatch):
    """A kernel that compiles but produces wrong digests raises before
    any production digest is computed, and goes on raising: nothing
    pins the process to the XLA path."""
    def wrong(data, lengths, interpret=False):
        return np.zeros((data.shape[0], 8), dtype=np.uint32)

    def no_xla(*a, **k):
        raise AssertionError("fell back to the XLA path")

    monkeypatch.setattr(sha256_pallas, "sha256_lanes_pallas", wrong)
    monkeypatch.setattr(sha256_pallas.sha256, "sha256_lanes", no_xla)
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, size=(8, 256), dtype=np.uint8)
    lengths = rng.integers(0, 247, size=8).astype(np.int32)
    for _ in range(2):
        with pytest.raises(RuntimeError, match="digest mismatch vs hashlib"):
            route.hash_lanes(_tpu_route(), data, lengths)
    assert sha256_pallas._parity_ok[(8, 256)] is False


def test_parity_probe_exception_propagates(monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("synthetic Mosaic rejection")

    monkeypatch.setattr(sha256_pallas, "sha256_lanes_pallas", boom)
    data = np.zeros((4, 64), dtype=np.uint8)
    lengths = np.array([0, 1, 2, 3], dtype=np.int32)
    with pytest.raises(RuntimeError, match="synthetic Mosaic rejection"):
        route.hash_lanes(_tpu_route(), data, lengths)
    # No verdict was reached, so none is cached: the next call probes
    # (and fails) again instead of trusting or condemning the kernel.
    assert (4, 64) not in sha256_pallas._parity_ok


def test_parity_probe_pass_routes_to_kernel(monkeypatch):
    """When the probe passes, production dispatch uses the kernel."""
    calls = []
    monkeypatch.setattr(sha256_pallas, "sha256_lanes_pallas",
                        _hashlib_kernel(calls))
    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, size=(8, 256), dtype=np.uint8)
    lengths = rng.integers(0, 247, size=8).astype(np.int32)
    for _ in range(2):
        got = np.asarray(route.hash_lanes(_tpu_route(), data, lengths))
        assert [g.astype(">u4").tobytes() for g in got] \
            == _hashlib_digests(data, lengths)
    assert sha256_pallas._parity_ok[(8, 256)] is True
    assert len(calls) == 3           # one probe + two production calls


def test_parity_probe_runs_per_bucket_shape(monkeypatch):
    """Each distinct (lanes, cap) compiles a different kernel program,
    so each must be parity-probed before its digests become cache
    identity: a kernel correct at the first bucket shape but wrong at
    the second must be caught when the second shape first flushes —
    never trusted on the strength of the first probe."""
    probed_shapes = []
    good = _hashlib_kernel(probed_shapes)

    def shape_dependent_kernel(data, lengths, interpret=False):
        out = good(data, lengths)
        if data.shape[1] >= 512:  # "miscompiles" at the bigger bucket
            out[:] = 0
        return out

    monkeypatch.setattr(sha256_pallas, "sha256_lanes_pallas",
                        shape_dependent_kernel)
    rng = np.random.default_rng(5)

    small = rng.integers(0, 256, size=(8, 256), dtype=np.uint8)
    small_len = rng.integers(0, 247, size=8).astype(np.int32)
    got = np.asarray(route.hash_lanes(_tpu_route(), small, small_len))
    assert [g.astype(">u4").tobytes() for g in got] == _hashlib_digests(
        small, small_len)
    assert sha256_pallas._parity_ok[(8, 256)] is True

    big = rng.integers(0, 256, size=(4, 512), dtype=np.uint8)
    big_len = rng.integers(0, 503, size=4).astype(np.int32)
    with pytest.raises(RuntimeError, match="4x512: digest mismatch"):
        route.hash_lanes(_tpu_route(), big, big_len)
    assert sha256_pallas._parity_ok[(4, 512)] is False
    assert (8, 256) in probed_shapes
    assert (4, 512) in probed_shapes


@pytest.mark.skipif(
    os.environ.get("MAKISU_TPU_SLOW_TESTS") != "1",
    reason="interpret-mode kernel compile takes minutes on XLA:CPU "
           "(set MAKISU_TPU_SLOW_TESTS=1; device validation runs in "
           "benchmarks/kernel_check.py and the production parity probe)")
def test_kernel_interpret_matches_hashlib():
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, size=(8, 128), dtype=np.uint8)
    lengths = np.array([0, 1, 55, 56, 63, 64, 100, 119], dtype=np.int32)
    got = np.asarray(sha256_pallas.sha256_lanes_pallas(
        data, lengths, interpret=True))
    assert [g.astype(">u4").tobytes() for g in got] == _hashlib_digests(
        data, lengths)


def test_the_commit_paths_sources_name_only_the_options_that_stay():
    """``makisu_tpu/ops/``, ``makisu_tpu/chunker/`` and ``native.py``
    read these ``MAKISU_TPU_*`` options and name no other, in code or
    in a comment: a tuning with one value in use is a constant, and a
    kernel nobody measured has no switch."""
    import glob
    import re

    import makisu_tpu
    pkg = os.path.dirname(makisu_tpu.__file__)
    found = set()
    for path in (glob.glob(os.path.join(pkg, "ops", "*.py"))
                 + glob.glob(os.path.join(pkg, "chunker", "*.py"))
                 + [os.path.join(pkg, "native.py")]):
        with open(path, encoding="utf-8") as f:
            found.update(re.findall(r"MAKISU_TPU_[A-Z0-9_]+", f.read()))
    assert found == {"MAKISU_TPU_" + name for name in (
        "BACKEND_INIT_TIMEOUT", "CHUNK_NATIVE", "CHUNK_STRICT",
        "DEVICE_SESSIONS_DIR", "HASH_LINGER_MS", "NATIVE_DIR",
        "NATIVE_ISA", "NATIVE_SINK", "PALLAS", "PROBE_SAMPLE_INTERVAL",
        "PROBE_TIMEOUT", "SHARED_HASH", "SYNC_TIMEOUT")}
