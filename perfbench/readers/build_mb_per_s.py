"""Context bytes of the builds that completed in the window over the
window's seconds (many lanes; a one-lane cell reports no rate)."""


def read(run):
    done = [b for b in run.counted if b.ok]
    if not done or run.window_s <= 0:
        return None
    return sum(b.context_bytes for b in done) / 1e6 / run.window_s
