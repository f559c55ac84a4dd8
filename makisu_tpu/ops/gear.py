"""Gear content-defined chunking as a data-parallel TPU program.

Gear CDC walks a byte stream with the recurrence

    h_i = (h_{i-1} << 1) + G[b_i]   (mod 2^32)

and cuts a chunk boundary after byte i when ``h_i & mask == 0``. The
recurrence looks inherently sequential, but mod 2^32 the contribution of a
byte k positions back is ``G[b_{i-k}] << k``, which vanishes for k >= 32.
So the sequential hash *equals* a 32-byte windowed correlation:

    h_i = sum_{k=0}^{31} G[b_{i-k}] << k   (mod 2^32)

which this module computes for every position at once in 5 log-doubling
steps (window 1 -> 2 -> 4 -> 8 -> 16 -> 32):

    H_1[i]    = G[b_i]
    H_2m[i]   = H_m[i] + (H_m[i-m] << m)

Each step is one shifted slice, one constant bit-shift, one add over the
whole buffer — pure VPU elementwise work, ~15 int ops/byte, fully
parallel over positions and over a batch axis, and shardable along the
sequence axis with a 31-byte halo (see parallel/pipeline.py).

This is the project's "ring-attention equivalent" (SURVEY.md §5): it makes
the long-stream dimension parallelizable so per-chunk SHA-256 lanes
(ops/sha256.py) can do the heavy hashing in parallel. The reference has no
counterpart — it hashes layers as single sequential streams
(lib/builder/step/common.go:35-67) and caches whole layers only.

Boundary decisions come back to the host as a bit-packed bitmap (32 bytes of
input per output uint32 word = 3% readback); min/max chunk-size policy is a
cheap greedy pass over candidate positions on the host (makisu_tpu/chunker).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

WINDOW = 32  # bytes of history that survive mod 2^32

# Default chunking geometry: 8 KiB average (mask of 13 bits), 2 KiB min,
# 64 KiB max. Matches common CDC deployments (FastCDC, restic are 512B-8MB
# range; container layers skew to many small text files).
DEFAULT_AVG_BITS = 13
DEFAULT_MIN_SIZE = 2 * 1024
DEFAULT_MAX_SIZE = 64 * 1024


def _splitmix32(x: int) -> int:
    x = (x + 0x9E3779B9) & 0xFFFFFFFF
    z = x
    z = ((z ^ (z >> 16)) * 0x21F0AAAD) & 0xFFFFFFFF
    z = ((z ^ (z >> 15)) * 0x735A2D97) & 0xFFFFFFFF
    return (z ^ (z >> 15)) & 0xFFFFFFFF


@functools.lru_cache(maxsize=1)
def gear_table() -> np.ndarray:
    """Deterministic 256-entry uint32 gear table (stable across versions —
    cache keys derived from it must never change)."""
    state = 0x6D616B69  # "maki"
    vals = []
    for _ in range(256):
        vals.append(_splitmix32(state))
        state = (state + 0x9E3779B9) & 0xFFFFFFFF
    return np.array(vals, dtype=np.uint32)


def _shift_seq(h: jax.Array, m: int) -> jax.Array:
    """h[..., i-m] with zero fill at the left edge (static shift)."""
    pad = [(0, 0)] * (h.ndim - 1) + [(m, 0)]
    return jnp.pad(h, pad)[..., :-m]


def _gear_value(data: jax.Array) -> jax.Array:
    """G[b] computed arithmetically — bit-identical to ``gear_table()[b]``
    but with no gather: table index i holds splitmix32 of
    ``seed + i*GOLDEN``, so the lookup is an 8-op elementwise mix chain,
    which maps onto the VPU far better than a 256-entry gather."""
    x = data.astype(jnp.uint32) * jnp.uint32(0x9E3779B9) + jnp.uint32(
        0x6D616B69)
    z = x + jnp.uint32(0x9E3779B9)
    z = (z ^ (z >> jnp.uint32(16))) * jnp.uint32(0x21F0AAAD)
    z = (z ^ (z >> jnp.uint32(15))) * jnp.uint32(0x735A2D97)
    return z ^ (z >> jnp.uint32(15))


def _windowed_sum(g: jax.Array, shift=_shift_seq) -> jax.Array:
    """The log-doubling window accumulation over per-byte G-values —
    THE cache-identity-bearing Gear recurrence. Single definition on
    purpose: every bitmap path (flat, blocked, and the Pallas kernel,
    which passes its layout's ``shift``) must cut identical boundaries
    forever. ``shift(h, m)`` must return h displaced by m sequence
    positions with zero fill at the stream head."""
    h = g
    m = 1
    while m < WINDOW:
        h = h + (shift(h, m) << jnp.uint32(m))
        m *= 2
    return h


def gear_hash(data: jax.Array) -> jax.Array:
    """Per-position Gear hashes for uint8 data [..., N].

    Position i's hash covers bytes max(0, i-31)..i, i.e. the stream is
    treated as starting at index 0 (zero history). For segmented streams
    pass 31 bytes of left halo and drop the first 31 outputs.
    """
    return _windowed_sum(_gear_value(data))


def boundary_mask(h: jax.Array, avg_bits: int = DEFAULT_AVG_BITS) -> jax.Array:
    """Candidate-boundary bool mask from per-position hashes."""
    mask = jnp.uint32((1 << avg_bits) - 1)
    return (h & mask) == 0


def pack_bits(bits: jax.Array) -> jax.Array:
    """bool [..., N] -> uint32 [..., N//32] little-bit-order bitmap."""
    n = bits.shape[-1]
    if n % 32:
        raise ValueError(f"bit count {n} not a multiple of 32")
    b = bits.reshape(*bits.shape[:-1], n // 32, 32).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(b * weights, axis=-1, dtype=jnp.uint32)


def unpack_bits_np(words: np.ndarray, n: int) -> np.ndarray:
    """uint32 [..., W] bitmap -> bool [..., n] (host side, numpy)."""
    le_bytes = np.asarray(words, dtype="<u4").view(np.uint8)
    bits = np.unpackbits(le_bytes.reshape(*words.shape[:-1], -1),
                         axis=-1, bitorder="little")
    return bits[..., :n].astype(bool)


# Scan-block size for the bandwidth-lean bitmap path. 64KiB of input
# makes each in-flight intermediate a 256KiB uint32 tile — comfortably
# VMEM-resident on every TPU generation, large enough to amortize the
# scan-step overhead. An earlier round's builders chose it (no record
# of a sweep survives; no ledger line ranks another size). NOT cache
# identity — outputs are bit-identical at any multiple of 32
# (pack_bits works in 32-bit words).
SCAN_BLOCK = 64 * 1024


def _gear_bitmap_blocked(data: jax.Array, avg_bits: int, block: int,
                         halo_g: jax.Array | None = None) -> jax.Array:
    """Same output as pack_bits(boundary_mask(gear_hash(data))) with a
    fraction of the HBM traffic: the flat path materializes ~6
    full-stream uint32 arrays (G-values + one per log-doubling step =
    ~40 bytes of memory traffic per input byte); here a lax.scan walks
    64KiB blocks carrying the previous block's last 31 G-values as
    halo, so every intermediate is block-sized and lives in VMEM — the
    stream itself is only sliced per block (read ~once) and only the 3%
    bitmap is written. Bit-identical by construction: position i's
    windowed sum needs only the 31 preceding G-values, which the halo
    supplies (zeros at stream start = the zero-history convention)."""
    *batch, n = data.shape
    rem = n % block
    mask = jnp.uint32((1 << avg_bits) - 1)

    if halo_g is None:
        halo_g = jnp.zeros((*batch, WINDOW - 1), dtype=jnp.uint32)
    # Leading remainder (the chunker's intake buffer is halo+blocks,
    # e.g. 128B + 4MiB): computed flat — it is tiny — and its last 31
    # G-values seed the scan's halo so the stream stays contiguous.
    if rem:
        g_prefix = _gear_value(data[..., :rem])
        hp = _windowed_sum(
            jnp.concatenate([halo_g, g_prefix], axis=-1))[..., WINDOW - 1:]
        prefix_words = pack_bits((hp & mask) == 0)
        halo0 = g_prefix[..., -(WINDOW - 1):]
        data = data[..., rem:]
    else:
        halo0 = halo_g
    nb = (n - rem) // block

    def step(halo, i):
        # dynamic_slice instead of a transposed xs array: scanning a
        # moveaxis'd copy would materialize a second full read+write of
        # the input for batched callers.
        blk = jax.lax.dynamic_slice_in_dim(data, i * block, block,
                                           axis=data.ndim - 1)
        g = _gear_value(blk)
        h = _windowed_sum(jnp.concatenate([halo, g], axis=-1))
        bits = (h[..., WINDOW - 1:] & mask) == 0
        return g[..., -(WINDOW - 1):], pack_bits(bits)

    _, words = jax.lax.scan(step, halo0, jnp.arange(nb))
    words = jnp.moveaxis(words, 0, -2).reshape(*batch, (n - rem) // 32)
    if rem:
        words = jnp.concatenate([prefix_words, words], axis=-1)
    return words


@functools.partial(jax.jit, static_argnums=(1,),
                   static_argnames=("avg_bits",))
@jax.named_scope("gear_scan")
def gear_bitmap(data: jax.Array, avg_bits: int = DEFAULT_AVG_BITS) -> jax.Array:
    """Fused: uint8 [..., N] -> packed candidate bitmap uint32 [..., N//32].

    Streams spanning >= 2 SCAN_BLOCKs (every production buffer: the
    chunker ships 128B halo + 4MiB blocks) take the blocked
    low-bandwidth path, with any leading remainder computed flat as a
    prefix; short streams take the flat path. Both are bit-identical,
    so the choice is shape-local and identity-free."""
    zero_halo = jnp.zeros((*data.shape[:-1], WINDOW - 1), jnp.uint32)
    return gear_bitmap_with_halo(data, zero_halo, avg_bits)


def gear_bitmap_with_halo(data: jax.Array, halo_g: jax.Array,
                          avg_bits: int = DEFAULT_AVG_BITS) -> jax.Array:
    """gear_bitmap for a stream SEGMENT: ``halo_g`` is the G-values of
    the 31 bytes preceding the segment (zeros = stream start; the
    zero-halo concat is bit-identical to the flat zero-history
    computation because _windowed_sum zero-fills its left edge). The
    seq-sharded mesh path computes each shard's bitmap with exactly one
    evaluation this way — the neighbor's bytes arrive by ppermute, their
    G-values are masked to zero on shard 0, and the result is
    bit-identical to the unsharded stream's bitmap. This is the ONE
    routing gate between the flat and blocked formulations."""
    n = data.shape[-1]
    rem = n % SCAN_BLOCK
    # rem % 32 == 0 (pack_bits needs word-aligned segments) also
    # guarantees rem is 0 or >= 32 > WINDOW-1, so the prefix always has
    # enough G-values to seed the scan halo.
    if n // SCAN_BLOCK >= 2 and rem % 32 == 0:
        return _gear_bitmap_blocked(data, avg_bits, SCAN_BLOCK,
                                    halo_g=halo_g)
    h = _windowed_sum(
        jnp.concatenate([halo_g, _gear_value(data)],
                        axis=-1))[..., WINDOW - 1:]
    return pack_bits(boundary_mask(h, avg_bits))


def select_boundaries_np(
    candidates: np.ndarray,
    n: int,
    min_size: int = DEFAULT_MIN_SIZE,
    max_size: int = DEFAULT_MAX_SIZE,
) -> np.ndarray:
    """TEST ORACLE for the min/max chunk policy — not a production path.

    The one production implementation of this policy is
    ``chunker.cdc.ChunkSession`` (``_cut_to``/``_force_cut``), which
    applies it streaming. This whole-stream restatement exists so tests
    can assert the streaming cuts equal the policy applied to the full
    candidate list (``tests/test_chunker.py::test_session_cuts_match_
    oracle``); policy changes must land in ChunkSession first and only
    mirror here. The policy is cache-identity-bearing: changing it
    invalidates every chunk fingerprint ever cached.

    candidates: sorted int array of positions p meaning "cut after byte p"
    n:          stream length
    Returns cut *end offsets* (exclusive), always ending with n.

    Deterministic for identical byte content, which is all the chunk-dedup
    cache needs. Oversize gaps are split at fixed strides from the previous
    (content-defined) cut, so splits are content-anchored too.
    """
    cuts = []
    prev = 0
    for p in np.asarray(candidates, dtype=np.int64):
        end = int(p) + 1
        if end - prev < min_size:
            continue
        while end - prev > max_size:
            prev += max_size
            cuts.append(prev)
        if end - prev >= min_size:
            cuts.append(end)
            prev = end
    while n - prev > max_size:
        prev += max_size
        cuts.append(prev)
    if prev < n or n == 0:
        cuts.append(n)
    return np.array(cuts, dtype=np.int64)


def gear_hash_ref(data: bytes) -> np.ndarray:
    """Pure-Python sequential reference (for tests): h_i for every i."""
    table = gear_table()
    out = np.empty(len(data), dtype=np.uint32)
    h = 0
    for i, byte in enumerate(data):
        h = ((h << 1) + int(table[byte])) & 0xFFFFFFFF
        out[i] = h
    return out
