"""Submissions the harness had to repeat after a refused or failed
connect, per counted build (``WorkerClient.build`` does not retry a
``POST /build``)."""


def read(run):
    if not run.counted:
        return None
    return sum(b.retries for b in run.counted) / len(run.counted)
