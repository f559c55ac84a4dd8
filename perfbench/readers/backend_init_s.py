"""Seconds of the backend probe's phases (plugin discovery, client
init, enumeration, first compile, first dispatch)."""


def read(run):
    phases = run.probe.get("phases") or []
    if not phases:
        return None
    return float(sum(p["seconds"] for p in phases))
