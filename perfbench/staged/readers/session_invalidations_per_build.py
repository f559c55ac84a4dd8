"""Resident sessions the worker's table dropped, per counted build
(``makisu_session_invalidations_total``, every ``reason`` together:
``flag_identity`` where a build names another ``--root`` than the
session's, ``lru`` and ``lru_restore`` where the table is smaller than
its tenants, ``ttl``, ``isa_change``, ``explicit``). A worker that
never dropped one exports no such series: where sessions began builds
(``makisu_session_dirty_paths_total`` is there) that is 0.0, and
``None`` only from a run without counters."""
from pbharness import hostspans


def read(run):
    dropped = hostspans.counter_per_build(
        run, "makisu_session_invalidations_total")
    if dropped is None and hostspans.counter_per_build(
            run, "makisu_session_dirty_paths_total") is not None:
        return 0.0
    return dropped
