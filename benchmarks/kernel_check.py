"""Every device program at its production shape, checked bit for bit.

    python benchmarks/kernel_check.py          # on the chip

Runs the XLA and Pallas gear scans (the kernel at both halo offsets) on one
``chunker.cdc.BLOCK`` of seeded bytes and the XLA and Pallas lane
SHA-256 at both ``chunker.cdc._BUCKETS`` shapes, and compares each with
its plain reference: ``gear.gear_hash_ref`` (the sequential recurrence)
and ``hashlib``. Nothing here is a measurement: the seconds printed are
compile-plus-first-run observations, and the comparisons run outside
them. Exits non-zero on the first mismatch, on a kernel the compiler
refuses, or when JAX's backend is not a TPU.

``programs()`` is also the table ``tests/test_tpu_aot_compile.py``
compiles against the compile-only v5e topology, so the shapes checked
on the chip and the shapes compiled in tier-1 cannot drift apart.
"""

from __future__ import annotations

import hashlib
import os
import sys
import time
import typing

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


class Program(typing.NamedTuple):
    name: str
    fn: typing.Callable          # jitted
    shapes: tuple                # ((shape, dtype), ...) of the array args
    static: dict                 # static keyword arguments
    check: typing.Callable       # (inputs, output) -> None, raises


def _gear_inputs(n: int, seed: int) -> np.ndarray:
    """Half incompressible, half repetitive: candidates, long
    candidate-free runs and dense-candidate runs all occur."""
    rng = np.random.default_rng(seed)
    buf = rng.integers(0, 256, size=n, dtype=np.uint8)
    buf[n // 2:] = np.resize(
        np.frombuffer(b"makisu-tpu chip check\n", dtype=np.uint8),
        n - n // 2)
    return buf


def _sha_inputs(lanes: int, cap: int, seed: int):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, size=(lanes, cap), dtype=np.uint8)
    lengths = rng.integers(0, cap - 9, size=lanes).astype(np.int32)
    edge = (0, 1, 55, 56, 63, 64, 100, cap - 64, cap - 9)
    lengths[:len(edge)] = edge
    return data, lengths


def _want_bits(buf: np.ndarray, avg_bits: int) -> np.ndarray:
    from makisu_tpu.ops import gear
    h = gear.gear_hash_ref(buf.tobytes())
    return (h & np.uint32((1 << avg_bits) - 1)) == 0


def _same_bits(name: str, got: np.ndarray, want: np.ndarray) -> None:
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = np.nonzero(got != want)[0] if got.shape == want.shape else []
        raise AssertionError(
            f"{name}: candidate bitmap differs from gear_hash_ref at "
            f"{len(bad)} positions (first {list(bad[:5])})")


def programs() -> list[Program]:
    from makisu_tpu.chunker import cdc
    from makisu_tpu.ops import gear, gear_pallas, sha256, sha256_pallas

    bits = gear.DEFAULT_AVG_BITS
    halo, block = gear_pallas.HALO, cdc.BLOCK
    u8 = np.uint8

    def check_xla_gear(inputs, words):
        (buf,) = inputs
        _same_bits("gear xla", gear.unpack_bits_np(words, len(buf)),
                   _want_bits(buf, bits))

    def check_pallas_gear(start):
        def check(inputs, words):
            (buf,) = inputs
            nrows = gear_pallas.nrows_for(block)
            got = gear.unpack_bits_np(
                words[:nrows], nrows * gear_pallas.ROW).reshape(-1)[:block]
            want = _want_bits(buf, bits)[start:start + block]
            # Without true history (start 0) the kernel's zero-byte
            # halo makes the first WINDOW-1 positions differ; they sit
            # below the minimum chunk size and never become cuts.
            skip = 0 if start else gear.WINDOW
            _same_bits(f"gear pallas start={start}",
                       got[skip:], want[skip:])
        return check

    def check_sha(name):
        def check(inputs, words):
            data, lengths = inputs
            for i, n in enumerate(lengths):
                want = hashlib.sha256(data[i, :n].tobytes()).digest()
                if words[i].astype(">u4").tobytes() != want:
                    raise AssertionError(
                        f"{name}: lane {i} (length {n}) differs from "
                        "hashlib.sha256")
        return check

    out = [
        Program("gear_xla", gear.gear_bitmap,
                (((halo + block,), u8),), {"avg_bits": bits},
                check_xla_gear),
        Program("gear_pallas_start0", gear_pallas.gear_bitmap_flat,
                (((block,), u8),), {"start": 0, "avg_bits": bits},
                check_pallas_gear(0)),
        Program("gear_pallas_start128", gear_pallas.gear_bitmap_flat,
                (((halo + block,), u8),),
                {"start": halo, "avg_bits": bits}, check_pallas_gear(halo)),
    ]
    for cap, lanes in cdc._BUCKETS:
        shapes = (((lanes, cap), u8), ((lanes,), np.int32))
        out.append(Program(f"sha_xla_{lanes}x{cap}", sha256.sha256_lanes,
                           shapes, {}, check_sha(f"sha xla {lanes}x{cap}")))
        out.append(Program(f"sha_pallas_{lanes}x{cap}",
                           sha256_pallas.sha256_lanes_pallas, shapes, {},
                           check_sha(f"sha pallas {lanes}x{cap}")))
    return out


def inputs_for(program: Program, seed: int) -> tuple:
    if program.name.startswith("gear"):
        ((shape, _),) = program.shapes
        return (_gear_inputs(shape[0], seed),)
    (shape, _), _ = program.shapes
    return _sha_inputs(shape[0], shape[1], seed)


def main() -> int:
    import jax

    dev = jax.devices()[0]
    print(f"kernel_check: backend {jax.default_backend()} "
          f"device_kind {dev.device_kind!r} x{len(jax.devices())}",
          flush=True)
    if dev.platform != "tpu":
        print("kernel_check: FAILED: no TPU; this check proves nothing "
              "about the device on any other backend", flush=True)
        return 1
    for seed, program in enumerate(programs()):
        inputs = inputs_for(program, seed)
        t0 = time.monotonic()
        out = np.asarray(program.fn(*inputs, **program.static))
        first = time.monotonic() - t0
        program.check(inputs, out)
        print(f"kernel_check: {program.name}: ok "
              f"(compile + first run {first:.2f}s, smoke observation)",
              flush=True)
    import __graft_entry__
    fn, args = __graft_entry__.entry()
    jax.block_until_ready(fn(*args))
    print("kernel_check: graft entry: ok", flush=True)
    print("kernel_check: all programs bit-identical to their references",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
