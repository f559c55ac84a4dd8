"""The plain reference of ``cdc.py`` for a deployment whose Dockerfile has
``RUN`` steps: the same cut points, digests and tar reading (the sibling
``cdc.py``'s, loaded by its path), and a plain interpreter of the
Dockerfile subset such a deployment uses, commands included, on a file
system held in memory, so that check (d) holds a build to what the
``Dockerfile`` means: a ``RUN``'s layer holds what the command changed
under the root, a whiteout for what it removed, and nothing it left
alone.

What is interpreted, line by line (one stage):

- ``FROM scratch`` opens the stage with an empty file system;
- ``ENV name=value`` sets a variable of every later ``RUN``;
- ``WORKDIR /dir`` makes the directory and is where a later ``RUN`` runs;
- ``COPY src dst`` copies a context directory's contents, or one file,
  to an absolute destination, as ``cdc_stages.py`` does: a copied file
  keeps its size, mode, mtime and bytes;
- ``RUN c1 && c2 && ...`` where each command is one of a closed list,
  every path relative to the ``WORKDIR``, without ``..``:

  - ``umask 022`` (must come first in a ``RUN`` that makes anything);
  - ``mkdir -p D`` makes ``D`` and its missing parents;
  - ``cp -Rp A/. B/`` copies the tree under directory ``A`` into the
    directory ``B``, which is there; every file keeps size, mode, mtime
    and bytes;
  - ``rm -rf P`` removes the file or the tree ``P``, or nothing;
  - ``cat GLOB > F`` writes to ``F`` the bytes of the files ``GLOB``
    names, in order. ``*`` matches within one path component and no
    leading dot; the order is the byte order of the names, which a shell
    gives only under ``ENV LC_ALL=C``, so that line has to come first.
    ``F`` has the mode 0666 less the umask, and **no stated time**: a
    command's output is stamped when it runs;

- a trailing ``#!COMMIT`` closes a layer, and so does the last step: the
  regular files written since the previous layer that differ from what
  that layer's file system held at their path (a file ``cat`` wrote
  always counts), and one whiteout, an empty member ``.wh.<name>``
  beside it, for each path the previous layers held that is gone and
  whose parent is still there.

**Which members have no time** (:func:`tar_members` leaves it out for the
same paths, so the comparison is of equals): a regular file in a
directory named ``dist``. Only ``cat ... > F`` may write there (a
``COPY`` or a ``cp`` into a ``dist`` raises), and it may write nowhere
else, so the rule names exactly the files whose time the Dockerfile does
not determine. Their name, size, mode and bytes are compared. A
**whiteout** is compared by name, type and emptiness: the image format
gives it no mode or time to state.

What is **not** interpreted, and raises ``ValueError`` where met: a base
image, stages, ``ADD``, ``USER`` and every other directive, every ``COPY``
flag, a relative ``COPY`` destination, any command, option, operator
(``;``, ``|``, ``||``, ``>>``), quoting, variable or absolute path outside
the list above, line continuations and heredocs. Not represented:
directories as members (the check compares regular files), symlinks and
special files, owners, ``.dockerignore``.

Imports nothing of makisu_tpu."""

from __future__ import annotations

import collections
import fnmatch
import hashlib
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

_COMMIT = "#!commit"
WHITEOUT_PREFIX = ".wh."
# The one directory name under which a command's outputs live.
OUTPUT_DIR = "dist"
_EMPTY = hashlib.sha256(b"").hexdigest()
_SHELL_SPECIALS = set(";|&<>$`\\\"'(){}!~#")


def _is_output(name: str) -> bool:
    return OUTPUT_DIR in name.split("/")[:-1]


def tar_members(tar: bytes) -> dict:
    """``cdc.tar_members``, less what the Dockerfile does not determine:
    the time of a file in a ``dist`` directory, the mode and time of a
    whiteout (the module's docstring has the rule)."""
    out = {}
    for name, (kind, size, mode, mtime, digest) in \
            _cdc.tar_members(tar).items():
        if os.path.basename(name).startswith(WHITEOUT_PREFIX):
            mode = mtime = None
        elif _is_output(name):
            mtime = None
        out[name] = (kind, size, mode, mtime, digest)
    return out


# A regular file of the file system in memory: its bytes are those of
# ``parts``, files of the context, one after another.
_File = collections.namedtuple("_File", "parts mode mtime")


def _clean(path: str) -> str:
    path = os.path.normpath(path).strip("/")
    return "" if path == "." else path


class _Stage:
    def __init__(self, context_root: str) -> None:
        self.context_root = context_root
        self.files: dict[str, _File] = {}
        self.dirs: set[str] = {""}
        self.env: dict[str, str] = {}
        self.workdir = ""
        self.written: set[str] = set()
        self.copies: list = []
        # What the closed layers hold: {name: member}, and every path.
        self.committed: dict[str, tuple] = {}
        self.committed_paths: set[str] = {""}
        self.layers: list[dict] = []
        # {context file: (size, sha256)}, read once an interpretation.
        self._digests: dict[str, tuple] = {}

    # -- the file system --------------------------------------------------

    def _make_dirs(self, path: str) -> None:
        while path and path not in self.dirs:
            if path in self.files:
                raise ValueError(f"{path} is a file, not a directory")
            self.dirs.add(path)
            path = os.path.dirname(path)

    def _put(self, name: str, f: _File, by_command_output: bool) -> None:
        if _is_output(name) != by_command_output:
            raise ValueError(
                f"{name}: only `cat ... > F` writes under a {OUTPUT_DIR}/, "
                f"and it writes nowhere else")
        if name in self.dirs:
            raise ValueError(f"{name} is a directory")
        self._make_dirs(os.path.dirname(name))
        self.files[name] = f
        self.written.add(name)

    def _remove(self, path: str) -> None:
        under = path + "/"
        for name in [n for n in self.files
                     if n == path or n.startswith(under)]:
            del self.files[name]
            self.written.discard(name)
        self.dirs -= {d for d in self.dirs
                      if d == path or d.startswith(under)}

    def _context_file(self, path: str) -> _File:
        st = os.lstat(path)
        return _File((path,), st.st_mode & 0o7777, int(st.st_mtime))

    def _member(self, f: _File) -> tuple:
        if len(f.parts) == 1:
            [path] = f.parts
            if path not in self._digests:
                self._digests[path] = (os.lstat(path).st_size,
                                       file_sha256_hex(path))
            size, hexdigest = self._digests[path]
        else:
            size = 0
            digest = hashlib.sha256()
            for part in f.parts:
                with open(part, "rb") as src:
                    for block in iter(lambda: src.read(8 << 20), b""):
                        size += len(block)
                        digest.update(block)
            hexdigest = digest.hexdigest()
        return (REGTYPE, size, f.mode, f.mtime, hexdigest)

    # -- directives -------------------------------------------------------

    def copy(self, src: str, dst: str) -> None:
        if any(c in src for c in "*?[") or not dst.startswith("/"):
            raise ValueError("globs and relative destinations are not "
                             "interpreted")
        source = os.path.join(self.context_root, _clean(src))
        dst_dir = dst.endswith("/")
        dst = _clean(dst)
        placed = {}
        if os.path.isfile(source) and not os.path.islink(source):
            name = os.path.join(dst, os.path.basename(source)) \
                if dst_dir else dst
            placed[name] = source
        else:
            for parent, _, names in os.walk(source):
                for leaf in names:
                    path = os.path.join(parent, leaf)
                    if os.path.isfile(path) and not os.path.islink(path):
                        placed[_clean(os.path.join(
                            dst, os.path.relpath(path, source)))] = path
        if not placed:
            raise ValueError(f"COPY source {src!r} names nothing")
        for name, path in placed.items():
            self._put(name, self._context_file(path), False)
        self.copies.append((_clean(src), dst))

    def _path(self, word: str) -> str:
        if not word or word.startswith("/") or ".." in word.split("/") \
                or (_SHELL_SPECIALS | set("*?[]")) & set(word):
            raise ValueError(f"path {word!r}: relative, plain paths only")
        return _clean(os.path.join(self.workdir, word))

    def _glob(self, pattern: str) -> list[str]:
        if self.env.get("LC_ALL") != "C":
            raise ValueError("a glob's order is the byte order only under "
                             "ENV LC_ALL=C")
        if _SHELL_SPECIALS & set(pattern):
            raise ValueError(f"glob {pattern!r} is not interpreted")
        found = [self.workdir]
        for component in pattern.split("/"):
            if not component or component in (".", ".."):
                raise ValueError(f"glob {pattern!r} is not interpreted")
            step = []
            for base in found:
                if base not in self.dirs:
                    continue
                prefix = base + "/" if base else ""
                children = {n[len(prefix):].split("/", 1)[0]
                            for n in list(self.files) + list(self.dirs)
                            if n.startswith(prefix) and n != base}
                step.extend(prefix + c for c in children
                            if not c.startswith(".")
                            and fnmatch.fnmatchcase(c, component))
            found = step
        names = sorted((n for n in found if n in self.files),
                       key=lambda n: n.encode())
        if not names:
            raise ValueError(f"glob {pattern!r} names no file")
        return names

    def run(self, command_line: str) -> None:
        umask = None
        for command in command_line.split("&&"):
            words = command.split()
            if not words:
                raise ValueError("an empty command")
            if words == ["umask", "022"]:
                umask = 0o022
            elif words[:2] == ["mkdir", "-p"] and len(words) == 3:
                self._require(umask)
                self._make_dirs(self._path(words[2]))
            elif words[:2] == ["cp", "-Rp"] and len(words) == 4 \
                    and words[2].endswith("/.") and words[3].endswith("/"):
                self._cp(self._path(words[2][:-2]), self._path(words[3]))
            elif words[:2] == ["rm", "-rf"] and len(words) == 3:
                self._remove(self._path(words[2]))
            elif words[0] == "cat" and len(words) == 4 and words[2] == ">":
                self._require(umask)
                parts = tuple(part for name in self._glob(words[1])
                              for part in self.files[name].parts)
                target = self._path(words[3])
                if os.path.dirname(target) not in self.dirs:
                    raise ValueError(f"{words[3]}: no such directory")
                self._put(target, _File(parts, 0o666 & ~umask, None), True)
            else:
                raise ValueError(f"command {command.strip()!r} is not "
                                 f"interpreted")

    @staticmethod
    def _require(umask) -> None:
        if umask is None:
            raise ValueError("a RUN that makes a file or a directory "
                             "starts with `umask 022`")

    def _cp(self, src: str, dst: str) -> None:
        if src not in self.dirs or dst not in self.dirs:
            raise ValueError(f"cp: {src} and {dst} must be directories "
                             f"that are there")
        under = src + "/"
        for name in [n for n in self.files if n.startswith(under)]:
            self._put(os.path.join(dst, name[len(under):]),
                      self.files[name], False)
        for d in [d for d in self.dirs if d.startswith(under)]:
            self._make_dirs(os.path.join(dst, d[len(under):]))

    def close_layer(self) -> None:
        now = {name: self._member(self.files[name]) for name in self.written}
        members = {name: member for name, member in now.items()
                   if member[3] is None or self.committed.get(name) != member}
        paths = set(self.files) | self.dirs
        for gone in self.committed_paths - paths:
            parent = os.path.dirname(gone)
            if parent in paths:
                members[os.path.join(
                    parent, WHITEOUT_PREFIX + os.path.basename(gone))] = (
                        REGTYPE, 0, None, None, _EMPTY)
        if members:
            self.layers.append({"members": members, "copies": self.copies})
        for name in list(self.committed):
            if name not in self.files:
                del self.committed[name]
        self.committed.update(now)
        self.committed_paths = paths
        self.written = set()
        self.copies = []


def _steps(text: str) -> list[tuple]:
    """[(directive, rest of the line, commit)]."""
    out = []
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
        if line.rstrip().endswith("\\"):
            raise ValueError(f"line {number}: line continuations are not "
                             f"interpreted")
        directive, _, rest = line.strip().partition(" ")
        out.append((directive.upper(), rest.strip(), commit))
    return out


def _interpret(root: str) -> list[dict]:
    """The image's layers under explicit commit: [{"members": {name:
    member}, "copies": [(context source, destination)]}]."""
    with open(os.path.join(root, "Dockerfile"), encoding="utf-8") as f:
        steps = _steps(f.read())
    if not steps or steps[0][:2] != ("FROM", "scratch"):
        raise ValueError("only one stage, FROM scratch, is interpreted")
    stage = _Stage(root)
    for i, (directive, rest, commit) in enumerate(steps[1:], start=1):
        if directive == "ENV":
            name, eq, value = rest.partition("=")
            if not eq or not name or len(rest.split()) != 1 \
                    or _SHELL_SPECIALS & set(rest):
                raise ValueError(f"ENV {rest!r}: one plain name=value")
            stage.env[name] = value
        elif directive == "WORKDIR":
            if not rest.startswith("/") or len(rest.split()) != 1:
                raise ValueError("WORKDIR takes one absolute path")
            stage.workdir = _clean(rest)
            stage._make_dirs(stage.workdir)
        elif directive == "COPY":
            args = rest.split()
            if len(args) != 2 or args[0].startswith("--"):
                raise ValueError("COPY takes one source and one "
                                 "destination here, and no flag")
            stage.copy(*args)
        elif directive == "RUN":
            stage.run(rest)
        else:
            raise ValueError(f"{directive} is not interpreted")
        if commit or i == len(steps) - 1:
            stage.close_layer()
    return stage.layers


def image_layers(root: str) -> list[dict]:
    """The committed layers of the image that the ``Dockerfile`` under
    ``root`` builds from the tree under ``root``, in order, each in
    :func:`tar_members`' shape for its regular files and whiteouts."""
    return [layer["members"] for layer in _interpret(root)]


def tree_members(root: str, sub: str, dest: str) -> dict:
    """What the layer closed after the ``COPY`` of the context directory
    ``sub`` has to hold, by the interpretation of ``root``'s
    ``Dockerfile``: the files its ``COPY``s and commands wrote or
    changed, and a whiteout for each path they removed. Where no layer,
    or more than one, copies from ``sub``, or where that ``COPY`` does
    not go to ``dest``, the answer is a member no tar has, so the
    comparison cannot come out equal."""
    want = _clean(sub)
    found = [layer for layer in _interpret(root)
             if any(src == want for src, _ in layer["copies"])]
    if len(found) != 1:
        return {f"<{len(found)} layers of the image copy from {sub}>": ()}
    [layer] = found
    to = [dst for src, dst in layer["copies"] if src == want]
    if to != [_clean(dest)]:
        return {f"<COPY {sub} goes to {to}, not to {dest}>": ()}
    return dict(layer["members"])
