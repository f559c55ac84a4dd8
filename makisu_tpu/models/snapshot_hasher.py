"""SnapshotHasher: the accelerator program at the heart of the framework.

This is the "flagship model" in ML-framework terms: a fixed-shape,
jittable computation that consumes a batch of layer-stream blocks and a
batch of chunk lanes and produces (candidate-boundary bitmaps, chunk
digests). Single-chip it runs as plain jit; multi-chip it shards over a
(data, seq) mesh with a Gear-window halo exchange (parallel/pipeline.py).

Reference counterpart being replaced: the sequential CPU hash loop at
lib/builder/step/common.go:35-67.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from makisu_tpu.ops import gear, sha256


@dataclasses.dataclass(frozen=True)
class SnapshotHasher:
    """Configuration: chunking geometry + batch shapes."""

    avg_bits: int = gear.DEFAULT_AVG_BITS
    block_bytes: int = 1 << 20      # per-stream block shipped to the chip
    batch: int = 8                  # streams scanned per step
    lanes: int = 1024               # chunk lanes hashed per step
    lane_cap: int = 16 * 1024       # bytes per lane buffer
    # Gear route: None = auto (the fused Pallas kernel on TPU backends,
    # matching the production chunker's default; XLA elsewhere), which
    # is what the driver's compile gate (__graft_entry__.entry)
    # compiles. SHA stays on the XLA SSA path inside this jitted model:
    # a TPU build hashes chunks with the sha256_pallas kernel
    # (chunker/route.py), but behind a per-process parity probe against
    # hashlib that a jitted forward cannot run — chunk digests are
    # cache identity.
    use_pallas: bool | None = None

    def example_inputs(self) -> tuple[jax.Array, jax.Array, jax.Array]:
        blocks = jnp.zeros((self.batch, self.block_bytes), jnp.uint8)
        lanes = jnp.zeros((self.lanes, self.lane_cap), jnp.uint8)
        lengths = jnp.full((self.lanes,), 64, jnp.int32)
        return blocks, lanes, lengths

    def forward(self, blocks: jax.Array, lanes: jax.Array,
                lengths: jax.Array) -> tuple[jax.Array, jax.Array]:
        """One hash step: gear candidate bitmaps + per-lane digests.

        The gear scan rides the fused Pallas kernel on TPU (see
        use_pallas); the XLA gear_bitmap routes these block sizes
        (1-4MiB = SCAN_BLOCK multiples, no remainder) through the
        bandwidth-lean scan path — intermediates stay VMEM-sized
        instead of materializing ~40 bytes of HBM traffic per input
        byte (bit-identical either way)."""
        from makisu_tpu.ops import gear_pallas

        use_pallas = self.use_pallas
        if use_pallas is None:
            use_pallas = (gear_pallas.env_enabled()
                          and jax.default_backend() != "cpu"
                          and self.block_bytes
                          % (gear_pallas.ROW_TILE * gear_pallas.ROW)
                          == 0)
        if use_pallas:
            bitmap = gear_pallas.gear_bitmap_batch(blocks, self.avg_bits)
        else:
            bitmap = gear.gear_bitmap(blocks, self.avg_bits)
        digests = sha256.sha256_lanes(lanes, lengths)
        return bitmap, digests

    def jit_forward(self):
        return jax.jit(self.forward)

    def sharded_step(self, mesh):
        """The multi-chip step over a (data, seq) mesh."""
        from makisu_tpu.parallel.pipeline import snapshot_hash_step
        return snapshot_hash_step(mesh, self.avg_bits)
