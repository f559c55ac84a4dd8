"""``commit_layer`` span seconds over build wall seconds, from the span
reports of the counted builds."""


def read(run):
    done = [b for b in run.counted if b.ok]
    wall = sum(b.seconds for b in done)
    if not done or wall <= 0:
        return None
    commit = sum(float(d or 0.0) for b in done for name, d in b.spans
                 if name == "commit_layer")
    return 100.0 * commit / wall
