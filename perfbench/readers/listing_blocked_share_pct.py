"""Of the seconds the thread-state reader sampled under the spans that
list and stat a context (``copy_checksum``, ``session_begin``,
``session_finish``: growth of ``makisu_thread_state_seconds_total``,
every state), the share in any state but ``running``: the thread was
blocked, by the kernel's word. No wait the program wrote is open under
these spans, so what blocks there is the queue for the interpreter lock
or a file-system call that sleeps; ``listing_lock_wait_share_pct`` says
which where the reader has ``syscall`` to read, and from the ``stat``
letters (source 1) this is what can be said. ``None`` where there is no
reader or nothing was sampled under them."""
from pbharness import threadstates

SPANS = ("copy_checksum", "session_begin", "session_finish")


def read(run):
    rows = threadstates.by_span(run)
    if rows is None:
        return None
    listing = [rows.get(span, {}) for span in SPANS]
    sampled = sum(row.get(state, 0.0) for row in listing
                  for state in threadstates.STATES)
    running = sum(row.get("running", 0.0) for row in listing)
    return 100.0 * (1.0 - running / sampled) if sampled > 0 else None
