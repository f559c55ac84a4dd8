"""Percentile and counter arithmetic (the yardstick's own copy)."""

from __future__ import annotations

import re
import statistics

_SERIES_RE = re.compile(r"^([A-Za-z_:][\w:]*)(\{[^}]*\})?\s+(\S+)$")
_LABEL_RE = re.compile(r'(\w+)="([^"]*)"')


def percentile(values, p: float) -> float:
    """Percentile by linear interpolation between the closest ranks
    (rank p/100 * (n - 1)); the median of an even count is the mean of
    the middle two. Raises on an empty sequence."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of nothing")
    if p == 50:
        return float(statistics.median(ordered))
    rank = p / 100.0 * (len(ordered) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(ordered) - 1)
    return float(ordered[lo] + (ordered[hi] - ordered[lo]) * (rank - lo))


def parse_prometheus(text: str) -> dict:
    """{(name, ((label, value), ...)): float} of a text exposition."""
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _SERIES_RE.match(line)
        if m is None:
            continue
        try:
            value = float(m.group(3))
        except ValueError:
            continue
        labels = tuple(sorted(_LABEL_RE.findall(m.group(2) or "")))
        out[(m.group(1), labels)] = value
    return out


def counter_delta(before: dict, after: dict, name: str, **labels) -> float:
    """Growth of every series of ``name`` whose labels include
    ``labels``, between two parsed expositions."""
    want = set(labels.items())
    total = 0.0
    for (series, have), value in after.items():
        if series == name and want <= set(have):
            total += value - before.get((series, have), 0.0)
    return total
