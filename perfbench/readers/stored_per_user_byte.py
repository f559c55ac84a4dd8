"""Bytes the lane's storage directory grew by during the counted
builds over the context bytes of those builds."""


def read(run):
    done = [b for b in run.counted if b.ok and b.storage_growth is not None]
    if not done:
        return None
    return sum(b.storage_growth for b in done) \
        / sum(b.context_bytes for b in done)
