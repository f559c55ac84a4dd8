"""Seconds a counted build spent walking the whole root and diffing it
against the in-memory tree (span ``layer_scan{kind="scan"}``:
``MemFS.add_layer_by_scan``, an ``lstat``, a tar header and a header
compare an entry of the root, whiteouts for what is gone).

A build's record keeps each closed span's name and seconds and not its
attributes, so ``kind`` is read from the order of the spans, which says
the same: a layer is made by a scan where a ``RUN`` has executed since
the last layer was committed (``RunStep.execute`` sets ``must_scan``,
``commit_layer`` clears it), so a ``layer_scan`` that closes after a
``run_exec`` and before the next ``commit_layer`` closes is a scan.
Nothing from a program without ``run_exec``."""


def read(run):
    done = [b for b in run.counted if b.ok]
    seconds = []
    for b in done:
        ran = False
        for name, duration in b.spans:
            if name == "run_exec":
                ran = True
            elif name == "commit_layer":
                ran = False
            elif name == "layer_scan" and ran:
                seconds.append(float(duration or 0.0))
    if not done or not seconds:
        return None
    return sum(seconds) / len(done)
