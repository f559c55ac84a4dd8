"""Of the seconds the reader sampled under the spans that list and
stat a context (``copy_checksum``, ``session_begin``,
``session_finish``: growth of ``makisu_thread_state_seconds_total``,
every state), the share in ``state="interpreter_lock"``: whether a
build that reads slow under these spans queues for the lock or for the
mount. ``None`` unless ``makisu_thread_state_source`` reads 2, and
where nothing was sampled under them."""
from pbharness import threadstates

SPANS = ("copy_checksum", "session_begin", "session_finish")


def read(run):
    rows = threadstates.by_span(run)
    if threadstates.source(run) != 2 or rows is None:
        return None
    listing = [rows.get(span, {}) for span in SPANS]
    sampled = sum(row.get(state, 0.0) for row in listing
                  for state in threadstates.STATES)
    locked = sum(row.get("interpreter_lock", 0.0) for row in listing)
    return 100.0 * locked / sampled if sampled > 0 else None
