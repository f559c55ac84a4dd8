"""Process start to window open."""


def read(run):
    return run.setup_s
