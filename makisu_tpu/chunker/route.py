"""Which code scans and hashes a layer's chunks: decided once.

The choice is a pure function of the backend JAX reports and of the
existing options (:func:`select`), so every session of a process gets
the same answer; it is logged once and carried by every counter's
``backend`` label. Nothing downstream
second-guesses it: a kernel the compiler refuses, or whose digests
differ from hashlib's, fails the build with its reason
(chunker/cdc.py "failure discipline") instead of giving way to another
route that would quietly change what a build measures.

Routes:

- ``native``: a host whose JAX backend IS the CPU runs the C++ gear
  scan and batch SHA-256 (makisu_tpu/native.py) and never touches JAX.
  Never on an accelerator; never under the shared HashService, whose
  point is cross-build device batches. ``MAKISU_TPU_CHUNK_NATIVE=0``
  forces the XLA route (tests of the device formulation on the CPU).
- gear ``pallas`` / sha ``pallas``: the default on a TPU backend, as
  run and compared bit for bit on a v5e (``chip_smoke.py``,
  ``benchmarks/kernel_check.py``). ``MAKISU_TPU_PALLAS=0/1`` forces
  both kernels off/on; on the CPU the gear kernel then runs in
  interpret mode (tests) and SHA stays on XLA, because XLA:CPU takes
  minutes to compile the kernel's 64 inlined rounds.
- ``xla``: everything else.
"""

from __future__ import annotations

import functools
import os
import typing

from makisu_tpu.utils import logging as log


class ChunkRoute(typing.NamedTuple):
    gear: str            # native | xla | pallas
    sha: str             # native | xla | pallas
    platform: str        # jax.devices()[0].platform
    device_kind: str
    device_count: int

    @property
    def native(self) -> bool:
        return self.gear == "native"

    @property
    def interpret(self) -> bool:
        """Pallas kernels run in interpret mode off the TPU."""
        return self.platform != "tpu"


def select(platform: str, shared: bool, native_ok: bool,
           environ: typing.Mapping[str, str]) -> tuple[str, str]:
    """(gear, sha) for a session on ``platform``. ``shared``: the
    session hashes through the HashService; ``native_ok``: libgear.so
    loaded."""
    if (platform == "cpu" and not shared and native_ok
            and environ.get("MAKISU_TPU_CHUNK_NATIVE", "1") == "1"):
        return "native", "native"
    from makisu_tpu.ops import gear_pallas
    if not gear_pallas.env_enabled(platform, environ):
        return "xla", "xla"
    return "pallas", ("pallas" if platform != "cpu" else "xla")


@functools.lru_cache(maxsize=None)
def _announce(route: ChunkRoute) -> ChunkRoute:
    """Log a decision the first time it is made: once per process
    (tests that flip an option get a second line)."""
    if route.native:
        log.info("chunk route: native (backend %s)", route.platform)
    else:
        log.info('chunk route: device %s "%s" ×%d gear=%s sha=%s',
                 route.platform, route.device_kind, route.device_count,
                 route.gear, route.sha)
    return route


def chunk_route(shared: bool = False) -> ChunkRoute:
    """This process's route, from what the backend probe found. Waits
    (bounded, once per process) for the backend; raises the probe's
    reason when it cannot come up."""
    from makisu_tpu import native
    from makisu_tpu.ops import backend
    err = backend.backend_ready()
    if err is not None:
        raise RuntimeError(err)
    ident = backend.device_identity()
    platform = ident["platform"]
    gear, sha = select(
        platform, shared,
        # Only a CPU backend ever loads (or builds) the native library.
        platform == "cpu" and native.gear_scan_available(), os.environ)
    return _announce(ChunkRoute(gear, sha, platform, ident["device_kind"],
                                ident["device_count"]))


def hash_lanes(route: ChunkRoute, data, lengths):
    """Ragged uint8 lanes [L, CAP] + lengths [L] → [L, 8] digests on
    the route's SHA program (async dispatch)."""
    from makisu_tpu.ops import sha256, sha256_pallas
    if route.sha == "pallas":
        return sha256_pallas.sha256_lanes_checked(data, lengths)
    return sha256.sha256_lanes(data, lengths)
