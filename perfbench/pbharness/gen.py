"""Build contexts from a configuration's ``context`` object, and the
edits a traffic mix asks for. One generator for every configuration:
sizes, and the repeated-line files' lines, come from the configuration's
``size_seed`` alone (so every run, seed and slice has the same sizes,
tar lengths, device shapes and nearly the same chunk counts); all other
content and the edit positions come from the run's ``--seed``."""

from __future__ import annotations

import os

import numpy as np

_VOCAB_WORDS = 4096
_WORD = 8   # bytes per token of generated text


def file_plan(context: dict) -> list[dict]:
    """[{path, size, kind, layer}] for one context: the same list for
    every seed. Sizes are log-uniform between ``lo`` and ``hi``, a
    ``small_share`` of the files drawn under ``small_below`` instead,
    the rest scaled so that each layer holds exactly its ``bytes``."""
    rng = np.random.default_rng(int(context["size_seed"]))
    dist = context["sizes"]
    kinds = context["content"]
    plan = []
    index = 0
    for layer in context["layers"]:
        n = int(layer["files"])
        small = int(n * float(dist.get("small_share", 0.0)))
        small_sizes = rng.integers(1, int(dist.get("small_below", 2)),
                                   size=small)
        budget = int(layer["bytes"]) - int(small_sizes.sum())
        weights = np.exp(rng.uniform(np.log(dist["lo"]), np.log(dist["hi"]),
                                     size=n - small))
        sizes = np.maximum((weights / weights.sum() * budget)
                           .astype(np.int64), 1)
        sizes[int(np.argmax(sizes))] += budget - int(sizes.sum())
        for size in list(sizes) + list(small_sizes):
            sub = f"d{index % int(context.get('fanout', 37)):02d}"
            plan.append({
                "path": os.path.join(layer["dir"], sub,
                                     f"f{index:05d}{layer.get('ext', '.bin')}"),
                "size": int(size), "layer": layer["dir"],
                "kind": kinds[index % len(kinds)]})
            index += 1
    return plan


def _text(rng: np.random.Generator, n: int) -> bytes:
    """``n`` bytes of compressible pseudo-source: tokens of eight bytes
    drawn from a vocabulary of 4096, a newline every eighth token."""
    letters = rng.integers(97, 123, size=(_VOCAB_WORDS, _WORD),
                           dtype=np.uint8)
    letters[:, -1] = 32
    tokens = letters[rng.integers(0, _VOCAB_WORDS, size=n // _WORD + 1)]
    tokens[7::8, -1] = 10
    return tokens.tobytes()[:n]


def _content(kind: str, rng: np.random.Generator, fixed_seed: int,
             index: int, n: int) -> bytes:
    if kind == "random":
        return rng.bytes(n)
    if kind == "repeat_line":
        # A short line repeated: few gear-window values, so such files
        # cut at the maximum size, or at the minimum where the line holds
        # a candidate, and their chunks repeat where the period allows.
        # How many chunks and duplicates that makes depends on the line,
        # and a build's time on them (slices of one seed differed by
        # 2.4 s of 14), so the line comes from ``size_seed``: these
        # files are the same for every seed, like the sizes.
        fixed = np.random.default_rng([fixed_seed, index])
        line = (b"%05d " % index) \
            + fixed.bytes(int(fixed.integers(3, 40))) + b"\n"
        return (line * (n // len(line) + 1))[:n]
    if kind == "text":
        return _text(rng, n)
    raise ValueError(f"unknown content kind {kind!r}")


def make_tree(context: dict, root: str, content_seed: int) -> int:
    """Write one context under ``root``; returns its bytes."""
    rng = np.random.default_rng(content_seed)
    total = 0
    made = set()
    for index, entry in enumerate(file_plan(context)):
        path = os.path.join(root, entry["path"])
        parent = os.path.dirname(path)
        if parent not in made:
            os.makedirs(parent, exist_ok=True)
            made.add(parent)
        with open(path, "wb") as f:
            f.write(_content(entry["kind"], rng, int(context["size_seed"]),
                             index, entry["size"]))
        total += entry["size"]
    with open(os.path.join(root, "Dockerfile"), "w") as f:
        f.write(context["dockerfile"])
    return total


def tree_bytes(root: str) -> int:
    """Bytes of every regular file under ``root``."""
    total = 0
    for parent, _, names in os.walk(root):
        for name in names:
            total += os.lstat(os.path.join(parent, name)).st_size
    return total


def layer_files(context: dict, root: str, layer: str) -> list[str]:
    """Sorted paths of the files of one layer directory (``"last"``
    names the Dockerfile's top layer)."""
    if layer == "last":
        layer = context["layers"][-1]["dir"]
    out = []
    for parent, _, names in os.walk(os.path.join(root, layer)):
        out.extend(os.path.join(parent, name) for name in names)
    return sorted(out)


def apply_edit(edit: dict, context: dict, root: str,
               rng: np.random.Generator, stamp: str) -> int:
    """One developer edit between two builds; returns files touched.

    ``insert``: ``bytes`` random bytes in the middle of one file drawn
    among the layer's files of at least ``min_file_bytes`` (every later
    byte of that file shifts). ``append``: a stamped comment line on the
    first ``share`` of the layer's files, as loadgen's edit does."""
    files = layer_files(context, root, edit.get("layer", "last"))
    if edit["kind"] == "insert":
        big = [p for p in files
               if os.path.getsize(p) >= int(edit.get("min_file_bytes", 0))]
        path = big[int(rng.integers(0, len(big)))]
        with open(path, "rb") as f:
            data = f.read()
        cut = len(data) // 2
        with open(path, "wb") as f:
            f.write(data[:cut] + rng.bytes(int(edit["bytes"])) + data[cut:])
        return 1
    if edit["kind"] == "append":
        n_edit = max(1, int(len(files) * float(edit["share"])))
        line = edit.get("text", "# edited {stamp}\n").format(
            stamp=stamp).encode()
        for path in files[:n_edit]:
            with open(path, "ab") as f:
                f.write(line)
        return n_edit
    raise ValueError(f"unknown edit kind {edit['kind']!r}")
