"""Path constants and helpers (reference: lib/pathutils/).

The blacklist is the set of host paths never scanned, copied, or committed
into layers — kernel pseudo-filesystems plus files the container runtime
bind-mounts read-only.
"""

from __future__ import annotations

import contextvars
import functools
import os

from makisu_tpu.utils import metrics

DEFAULT_STORAGE_DIR = "/makisu-storage"
DEFAULT_INTERNAL_DIR = "/makisu-internal"
CACHE_KV_FILE_NAME = "cache_key_value.json"

DEFAULT_BLACKLIST = [
    DEFAULT_INTERNAL_DIR,
    "/.dockerinit",
    "/dev",
    "/.dockerenv",
    "/dev/console",
    "/dev/pts",
    "/dev/shm",
    "/etc/hosts",
    "/etc/hostname",
    "/etc/mtab",
    "/etc/resolv.conf",
    "/proc",
    "/sys",
]


@functools.lru_cache(maxsize=65536)
def abs_path(p: str) -> str:
    """Normalize to an absolute path with a leading '/'. Does not resolve
    symlinks (layer paths are logical, not host-resolved).

    Memoized: scans normalize the same paths many times over (each
    blacklist entry per visited file, ancestors per descendant); the
    cache turns the string work into a dict hit on the hot loop."""
    p = os.path.normpath("/" + p)
    if p.startswith("//"):  # POSIX normpath preserves a double leading slash
        p = "/" + p.lstrip("/")
    return p


def rel_path(p: str) -> str:
    """Path relative to '/', with no leading slash."""
    return abs_path(p).lstrip("/")


def trim_root(p: str, root: str) -> str:
    """Strip a root prefix, returning an absolute logical path."""
    root = os.path.normpath(root)
    p = os.path.normpath(p)
    if root in ("/", ""):
        return abs_path(p)
    if p == root:
        return "/"
    if p.startswith(root + os.sep):
        return abs_path(p[len(root):])
    raise ValueError(f"{p!r} is not under root {root!r}")


def join_root(root: str, p: str) -> str:
    """Map a logical absolute path into a physical root directory."""
    return os.path.normpath(os.path.join(root, rel_path(p)))


def split_path(p: str) -> list[str]:
    """Path components, no empties: '/a/b/c' -> ['a','b','c']."""
    return [c for c in abs_path(p).split("/") if c]


def is_descendant_of_any(p: str, ancestors: list[str]) -> bool:
    """True if p equals or sits beneath any listed path."""
    p = abs_path(p)
    for a in ancestors:
        a = abs_path(a)
        if p == a or p.startswith(a.rstrip("/") + "/"):
            return True
    return False


def ancestors(p: str) -> list[str]:
    """All proper ancestor directories of p, outermost first ('/a', '/a/b')."""
    parts = split_path(p)
    return ["/" + "/".join(parts[:i]) for i in range(1, len(parts))]


# -- a request's directories, resolved once ----------------------------------

# The real paths of the directories a worker's request names (--root,
# --storage, the context), as given, absolute and resolved, each to its
# real path: made at the request's admission, bound to its context like
# its log sink, and gone with it. Nothing here outlives a request: the
# next build's storage may be a directory made anew, or a symlink
# retargeted since.
_request_dirs: "contextvars.ContextVar[dict[str, str] | None]" = \
    contextvars.ContextVar("makisu_request_dirs", default=None)


def _asked(result: str) -> None:
    metrics.counter_add(metrics.REQUEST_RESOLVE_TOTAL, kind="realpath",
                        result=result)


def resolve_request_dirs(dirs) -> dict[str, str]:
    """Walk each directory of ``dirs`` through its symlinks, once: the
    map ``bind_request_dirs`` takes."""
    known: dict[str, str] = {}
    for given in dirs:
        if given in known:
            continue
        real = os.path.realpath(given)
        _asked("done")
        for form in (given, os.path.abspath(given), real):
            known.setdefault(form, real)
    return known


def bind_request_dirs(known: dict[str, str]):
    """Returns a token for ``reset_request_dirs``."""
    return _request_dirs.set(known)


def reset_request_dirs(token) -> None:
    _request_dirs.reset(token)


def real_path(path: str) -> str:
    """``os.path.realpath(path)``, answered by the request where it
    has resolved ``path`` already. A path one component below a
    directory the request knows (``<storage>/chunks``) costs that
    component's ``lstat`` the first time and is known from then on.
    Any other path, and every path outside a request, is walked."""
    known = _request_dirs.get()
    if known is None:
        _asked("done")
        return os.path.realpath(path)
    real = known.get(path)
    if real is not None:
        _asked("reused")
        return real
    _asked("done")
    parent, name = os.path.split(path)
    real_parent = known.get(parent)
    if real_parent is None or name in ("", ".", ".."):
        return os.path.realpath(path)
    real = os.path.join(real_parent, name)
    if os.path.islink(real):
        real = os.path.realpath(real)
    known[path] = known[real] = real
    return real
