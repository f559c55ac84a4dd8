"""Pallas gear kernel: interpret-mode equivalence with the XLA path."""

import numpy as np
import pytest

from makisu_tpu.ops import gear, gear_pallas


def candidates_xla(data: bytes) -> np.ndarray:
    """Reference: candidate positions from the XLA path, restricted to
    the window-complete region (>= WINDOW) to match the kernel's
    zero-pad-at-head semantics; below-min-size positions are irrelevant
    to chunking either way."""
    import jax.numpy as jnp
    arr = np.frombuffer(data, dtype=np.uint8)
    pad = (-len(arr)) % 32
    h = np.asarray(gear.gear_hash(jnp.asarray(
        np.concatenate([arr, np.zeros(pad, np.uint8)]))))[:len(arr)]
    mask = (h & ((1 << gear.DEFAULT_AVG_BITS) - 1)) == 0
    return np.nonzero(mask)[0]


@pytest.mark.parametrize("n", [1000, gear_pallas.ROW,
                               3 * gear_pallas.ROW + 777,
                               40 * gear_pallas.ROW])
def test_pallas_candidates_match_xla(n):
    buf = np.random.default_rng(n).integers(0, 256, size=n, dtype=np.uint8)
    got = set(gear_pallas.gear_candidates(buf, 0, n, interpret=True))
    want = set(candidates_xla(buf.tobytes()))
    # Positions below WINDOW may differ (zero-pad vs zero-history); both
    # sit far under the minimum chunk size and never become cuts.
    got = {p for p in got if p >= gear.WINDOW}
    want = {p for p in want if p >= gear.WINDOW}
    assert got == want


def test_pallas_with_offset_window():
    buf = np.random.default_rng(9).integers(
        0, 256, size=30_000, dtype=np.uint8)
    start, n = 5_000, 20_000
    got = set(gear_pallas.gear_candidates(buf, start, n, interpret=True))
    # Reference over the same window WITH its true 128-byte history.
    import jax.numpy as jnp
    h = np.asarray(gear.gear_hash(jnp.asarray(
        buf[start - 128:start + n])))[128:]
    want = set(np.nonzero(
        (h & ((1 << gear.DEFAULT_AVG_BITS) - 1)) == 0)[0])
    assert got == want


def test_stage_rows_shapes():
    buf = np.arange(20_000, dtype=np.uint32).astype(np.uint8)
    rows, nrows = gear_pallas.stage_rows(buf, 0, len(buf))
    cols = (gear_pallas.HALO + gear_pallas.ROW) // 32
    assert rows.shape[1:] == (32, cols)
    assert rows.shape[0] % gear_pallas.ROW_TILE == 0
    assert nrows == (len(buf) + gear_pallas.ROW - 1) // gear_pallas.ROW
    # Sublane-major: byte j of a row sits at [j % 32, j // 32]. Row 1's
    # halo (its first HALO byte positions) equals the last HALO bytes
    # before its live region.
    flat1 = rows[1].T.reshape(-1)
    np.testing.assert_array_equal(
        flat1[:gear_pallas.HALO],
        buf[gear_pallas.ROW - gear_pallas.HALO:gear_pallas.ROW])


@pytest.mark.parametrize(
    "start,live",
    [(0, 1000), (0, 8192), (128, 3 * 8192 + 777), (50, 9000)]
    + [(start, live) for live in (1, 100, 33_000, 200_000)
       for start in (0, 128)])
def test_gear_bitmap_flat_matches_staged_rows(start, live):
    """The fused on-device restage must cut exactly where the numpy
    stage_rows path does (production vs test-oracle staging), and
    where the XLA reference (``gear.gear_bitmap``) does: with a whole
    window of true history (a 128-byte halo prefix) at every position,
    without one from ``WINDOW`` on (the kernel's halo is zero bytes,
    the reference's zero G-values; both sit far below the minimum chunk
    size and never become cuts)."""
    rng = np.random.default_rng(start + live)
    buf = rng.integers(0, 256, size=start + live, dtype=np.uint8)
    words = np.asarray(gear_pallas.gear_bitmap_flat(
        gear_pallas.quantize_flat(buf, start, live), start,
        interpret=True))
    nrows = gear_pallas.nrows_for(live)
    got = gear.unpack_bits_np(
        words[:nrows], nrows * gear_pallas.ROW).reshape(-1)[:live]
    rows, nr = gear_pallas.stage_rows(buf, start, live)
    w2 = np.asarray(gear_pallas.gear_bitmap_rows(rows, interpret=True))
    want = gear.unpack_bits_np(
        w2[:nr], nr * gear_pallas.ROW).reshape(-1)[:live]
    np.testing.assert_array_equal(got, want)
    padded = np.concatenate(
        [buf, np.zeros(-len(buf) % 32, dtype=np.uint8)])
    xla = gear.unpack_bits_np(
        np.asarray(gear.gear_bitmap(padded)), len(buf))[start:]
    skip = 0 if start >= gear.WINDOW else gear.WINDOW
    np.testing.assert_array_equal(got[skip:], xla[skip:])


def test_kernel_failure_fails_the_session(monkeypatch):
    """A kernel the compiler refuses propagates: the session raises
    with the kernel's reason. No breaker hands the scan to the XLA
    route — that would change what a build measures without a word —
    and only MAKISU_TPU_CHUNK_STRICT=0 degrades the layer."""
    # Kernel-route test: pin off the native CPU route (it never
    # touches Pallas, so the simulated failure would not fire).
    monkeypatch.setenv("MAKISU_TPU_CHUNK_NATIVE", "0")
    monkeypatch.setenv("MAKISU_TPU_PALLAS", "1")
    from makisu_tpu.chunker.cdc import ChunkSession

    payload = np.random.default_rng(11).integers(
        0, 256, size=400_000, dtype=np.uint8).tobytes()
    other_routes = []

    def boom(*a, **k):
        raise RuntimeError("synthetic Mosaic rejection")

    def other(*a, **k):
        other_routes.append(1)
        raise AssertionError("the scan moved to another route")

    monkeypatch.setattr(gear_pallas, "gear_bitmap_flat", boom)
    monkeypatch.setattr(gear, "gear_bitmap", other)

    monkeypatch.delenv("MAKISU_TPU_CHUNK_STRICT", raising=False)
    s = ChunkSession(block=128 * 1024)
    with pytest.raises(RuntimeError, match="synthetic Mosaic rejection"):
        s.update(payload)
    assert not other_routes

    monkeypatch.setenv("MAKISU_TPU_CHUNK_STRICT", "0")
    s = ChunkSession(block=128 * 1024)
    s.update(payload)
    assert s.finish() == []
    assert "synthetic Mosaic rejection" in s._degraded
    assert not other_routes


def test_gear_bitmap_batch_matches_xla_above_window():
    """The SnapshotHasher kernel route must select the same candidate
    positions as the XLA route for every stream in the batch (positions
    below WINDOW excluded per the zero-halo caveat)."""
    rng = np.random.default_rng(21)
    B, n = 3, 2 * gear_pallas.ROW_TILE * gear_pallas.ROW
    blocks = rng.integers(0, 256, size=(B, n), dtype=np.uint8)
    got_words = np.asarray(gear_pallas.gear_bitmap_batch(
        blocks, interpret=True))
    want_words = np.asarray(gear.gear_bitmap(blocks))
    for b in range(B):
        got = np.nonzero(gear.unpack_bits_np(got_words[b], n))[0]
        want = np.nonzero(gear.unpack_bits_np(want_words[b], n))[0]
        np.testing.assert_array_equal(got[got >= gear.WINDOW],
                                      want[want >= gear.WINDOW])


def test_chunk_session_pallas_path_matches(monkeypatch):
    """MAKISU_TPU_PALLAS=1 must produce identical chunks end to end."""
    from makisu_tpu.chunker.cdc import ChunkSession

    payload = np.random.default_rng(42).integers(
        0, 256, size=500_000, dtype=np.uint8).tobytes()

    def run():
        s = ChunkSession(block=128 * 1024)
        for i in range(0, len(payload), 50_000):
            s.update(payload[i:i + 50_000])
        return [(c.offset, c.length, c.digest) for c in s.finish()]

    baseline = run()
    monkeypatch.setenv("MAKISU_TPU_PALLAS", "1")
    assert run() == baseline
