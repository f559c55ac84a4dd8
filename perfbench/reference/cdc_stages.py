"""The plain reference of ``cdc.py`` for a deployment with build stages:
the same cut points, digests and tar reading (the sibling ``cdc.py``'s,
loaded by its path), and a plain interpreter of the Dockerfile subset
such a deployment uses, so that check (d) holds a build to what the
``Dockerfile`` means and not to a hand-written list of directories.

What is interpreted, line by line:

- ``FROM scratch [AS alias]`` opens a stage with an empty file system,
  known to later stages by its alias, or by its number where it has none;
- ``COPY [--from=alias] src dst`` copies into the stage, from the context
  or from an earlier stage's file system as that stage left it. A source
  directory gives its contents (``COPY deps /lib/`` puts ``deps/a/b`` at
  ``/lib/a/b``); a source file lands in ``dst`` where ``dst`` ends in
  ``/`` and at ``dst`` otherwise. A copied file keeps its size, mode,
  mtime and bytes, through any number of stages;
- a trailing ``#!COMMIT`` (``commit="explicit"``) closes a layer of the
  stage: the files written since the stage's previous layer, but those
  that replaced an identical file. The image's last step closes one too.
  With ``commit="implicit"`` every ``COPY`` closes a layer.

The image is the **last stage's layers alone**, in order; an earlier
stage gives it nothing but what a ``COPY --from`` names.

What is **not** interpreted, and raises ``ValueError`` where met: a base
image other than ``scratch``, ``RUN``, ``ADD``, ``WORKDIR`` and every other
directive, ``--chown`` and every flag but ``--from``, globs, several
sources on one line, a relative destination, ``--from=<image>``, line
continuations and heredocs. Not represented at all: directories (the
check compares regular files), symlinks and special files in the context,
owners, ``.dockerignore``, whiteouts (nothing in the subset deletes).

Imports nothing of makisu_tpu."""

from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "perfbench_cdc", os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "cdc.py"))
_cdc = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cdc)

AVG_BITS, MIN_SIZE, MAX_SIZE = _cdc.AVG_BITS, _cdc.MIN_SIZE, _cdc.MAX_SIZE
REGTYPE = _cdc.REGTYPE
gear_table = _cdc.gear_table
candidates = _cdc.candidates
cut_points = _cdc.cut_points
inflate = _cdc.inflate
sha256_hex = _cdc.sha256_hex
file_sha256_hex = _cdc.file_sha256_hex
tar_members = _cdc.tar_members

_COMMIT = "#!commit"


def _parse(text: str) -> list[dict]:
    """[{alias, steps: [(from_alias or None, src, dst, commit)]}]."""
    stages: list[dict] = []
    for number, raw in enumerate(text.splitlines(), start=1):
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        commit = False
        if "#" in line:
            line, _, comment = line.partition("#")
            commit = ("#" + comment).replace(" ", "").lower() == _COMMIT
            if not commit:
                raise ValueError(f"line {number}: a comment after a "
                                 f"directive that is not #!COMMIT")
        words = line.split()
        directive, args = words[0].upper(), words[1:]
        if directive == "FROM":
            if args[:1] != ["scratch"] or len(args) not in (1, 3) \
                    or (len(args) == 3 and args[1].upper() != "AS"):
                raise ValueError(f"line {number}: only FROM scratch "
                                 f"[AS alias] is interpreted")
            alias = args[2] if len(args) == 3 else str(len(stages))
            if alias in {s["alias"] for s in stages}:
                raise ValueError(f"line {number}: stage {alias} twice")
            stages.append({"alias": alias, "steps": []})
        elif directive == "COPY":
            if not stages:
                raise ValueError(f"line {number}: COPY before FROM")
            source_stage = None
            while args and args[0].startswith("--"):
                flag = args.pop(0)
                if not flag.startswith("--from="):
                    raise ValueError(f"line {number}: {flag} is not "
                                     f"interpreted")
                source_stage = flag.split("=", 1)[1]
            if len(args) != 2:
                raise ValueError(f"line {number}: COPY takes one source "
                                 f"and one destination here")
            src, dst = args
            if any(c in src for c in "*?[") or not dst.startswith("/"):
                raise ValueError(f"line {number}: globs and relative "
                                 f"destinations are not interpreted")
            stages[-1]["steps"].append((source_stage, src, dst, commit))
        else:
            raise ValueError(f"line {number}: {directive} is not "
                             f"interpreted")
    if not stages:
        raise ValueError("no stage")
    return stages


def _copied(files: dict, src: str, dst: str) -> dict:
    """Where ``COPY src dst`` puts the files of ``files``, a mapping of
    clean paths without a leading slash to anything: ``src`` names a
    directory of it (its contents go under ``dst``) or one file."""
    src = os.path.normpath(src).strip("/")
    src = "" if src == "." else src
    dst_dir = dst.endswith("/")
    dst = os.path.normpath(dst).strip("/")
    if src in files:
        name = os.path.join(dst, os.path.basename(src)) if dst_dir else dst
        return {name: files[src]}
    under = src + "/" if src else ""
    out = {os.path.normpath(os.path.join(dst, path[len(under):])): what
           for path, what in files.items() if path.startswith(under)}
    if not out:
        raise ValueError(f"COPY source {src!r} names nothing")
    return out


def _context_files(root: str) -> dict:
    """{path under the context: (absolute path, None)} of its regular
    files; the second place is filled with the COPY source a file
    entered a stage by."""
    out = {}
    for parent, _, names in os.walk(root):
        for name in names:
            path = os.path.join(parent, name)
            if os.path.isfile(path) and not os.path.islink(path):
                out[os.path.relpath(path, root)] = (path, None)
    return out


def _interpret(root: str, commit: str) -> list[dict]:
    """The final stage's layers: [{"files": {name: absolute context
    path}, "copies": [(destination, origin)]}], where ``origin`` is the
    set of context sources the layer's files entered their first stage
    by."""
    if commit not in ("explicit", "implicit"):
        raise ValueError(f"commit is explicit or implicit, not {commit!r}")
    with open(os.path.join(root, "Dockerfile"), encoding="utf-8") as f:
        stages = _parse(f.read())
    context = _context_files(root)
    done: dict[str, dict] = {}
    for k, stage in enumerate(stages):
        fs: dict = {}        # name -> (absolute context path, origin)
        layers = []
        pending: dict = {}
        copies: list = []
        for i, (source_stage, src, dst, marked) in enumerate(stage["steps"]):
            if source_stage is None:
                origin = os.path.normpath(src).strip("/")
                placed = {name: (path, origin) for name, (path, _)
                          in _copied(context, src, dst).items()}
            elif source_stage in done:
                placed = _copied(done[source_stage], src, dst)
            else:
                raise ValueError(f"COPY --from={source_stage}: no earlier "
                                 f"stage of that name")
            for name, (path, origin) in placed.items():
                if name in fs and _member(fs[name][0]) == _member(path):
                    continue
                pending[name] = path
            fs.update(placed)
            copies.append((os.path.normpath(dst).strip("/"),
                           {origin for _, origin in placed.values()}))
            last = k == len(stages) - 1 and i == len(stage["steps"]) - 1
            if marked or commit == "implicit" or last:
                if pending:
                    layers.append({"files": pending, "copies": copies})
                pending, copies = {}, []
        done[stage["alias"]] = fs
    return layers


def _member(path: str) -> tuple:
    st = os.lstat(path)
    return (REGTYPE, st.st_size, st.st_mode & 0o7777, int(st.st_mtime),
            file_sha256_hex(path))


def image_layers(root: str, commit: str = "explicit") -> list[dict]:
    """The committed layers of the image that the ``Dockerfile`` under
    ``root`` builds from the tree under ``root``, in order, each in
    :func:`tar_members`' shape for its regular files."""
    return [{name: _member(path) for name, path in layer["files"].items()}
            for layer in _interpret(root, commit)]


def tree_members(root: str, sub: str, dest: str) -> dict:
    """What the image's layer with the destination ``dest`` has to hold,
    by the interpretation of ``root``'s ``Dockerfile`` under explicit
    commit (what a configuration with stages builds with). Where no layer,
    or more than one, has a ``COPY`` to ``dest``, or where its files did
    not enter the build from the context directory ``sub``, the answer
    is a member no tar has, so the comparison cannot come out equal."""
    want = os.path.normpath(dest).strip("/")
    found = [layer for layer in _interpret(root, "explicit")
             if any(dst == want for dst, _ in layer["copies"])]
    if len(found) != 1:
        return {f"<{len(found)} layers of the image copy to {dest}>": ()}
    [layer] = found
    origins = set().union(*(origin for _, origin in layer["copies"]))
    if origins != {os.path.normpath(sub).strip("/")}:
        return {f"<the layer at {dest} comes from {sorted(origins)}, "
                f"not from {sub}>": ()}
    return {name: _member(path) for name, path in layer["files"].items()}
