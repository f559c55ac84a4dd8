"""Of the bytes the SHA lane buffers carried to the device over the
window, the share that was chunk payload: growth of
``makisu_bytes_hashed_total`` for the lane feeders (``path="cdc"``, a
build's own batcher; ``path="service"``, the worker's shared hash
service, which every build of the harness goes through) over growth of
``makisu_device_transfer_bytes_total{direction="h2d",stage="sha"}``.
The rest is lane padding: a chunk fills its 16 KiB or 65,600-byte lane
to its own length only, and a batch is sent with its unused lanes."""
from pbharness import hostspans

_FEEDERS = ("cdc", "service")


def read(run):
    sent = hostspans.counter_per_build(
        run, "makisu_device_transfer_bytes_total",
        direction="h2d", stage="sha")
    payload = [hostspans.counter_per_build(
        run, "makisu_bytes_hashed_total", path=path) for path in _FEEDERS]
    if not sent or all(p is None for p in payload):
        return None
    return 100.0 * sum(p or 0.0 for p in payload) / sent
