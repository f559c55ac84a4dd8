"""The wait for tar's whole-second mtimes (``MemFS._wait_out_mtime``):
a commit returns only once a later write cannot be stamped in a second
its scan visited, and sleeps for that only while the newest mtime it
visited is still in the clock's current second. Both commits, the scan
after a RUN and the COPY diff, hold it."""

import io
import os
import tarfile
import time

import pytest

from makisu_tpu.snapshot import CopyOperation, MemFS, memfs
from makisu_tpu.utils import metrics

SPAN = "memfs_sync.mtime_wait"
OLD = 10            # seconds: an mtime the clock has long passed


class Tree:
    """A directory of files and the commit that diffs it: the build
    root itself (``scan``) or a context directory a COPY reads
    (``copy``)."""

    def __init__(self, tmp_path, how: str, sync_wait: float) -> None:
        root = tmp_path / "root"
        root.mkdir()
        self.how = how
        self.dir = root if how == "scan" else tmp_path / "ctx" / "src"
        self.dir.mkdir(parents=True, exist_ok=True)
        self.fs = MemFS(str(root), blacklist=[], sync_wait=sync_wait)
        self.ops = [CopyOperation(["src"], str(tmp_path / "ctx"), "/",
                                  "/app/")]

    def write(self, name: str, data: bytes, age: float = 0.0) -> int:
        """Write a file; returns its whole-second mtime. ``age`` dates
        it, and every directory, that many seconds back."""
        path = self.dir / name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_bytes(data)
        if age:
            self.date(path, age)
        self.date_directories()
        return int(os.lstat(path).st_mtime)

    def date(self, path, age: float) -> None:
        then = time.time() - age
        os.utime(path, (then, then))

    def date_directories(self) -> None:
        """Creating a file stamps its directory: put every directory
        back in the past, so that only files decide the wait."""
        for parent, _dirs, _files in os.walk(self.dir):
            self.date(parent, OLD)

    def commit(self) -> dict[str, bytes | None]:
        """Commit one layer; returns its members' contents by name."""
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w|") as tw:
            if self.how == "scan":
                self.fs.add_layer_by_scan(tw)
            else:
                self.fs.add_layer_by_copy_ops(self.ops, tw)
        buf.seek(0)
        with tarfile.open(fileobj=buf, mode="r|") as tr:
            return {m.name: tr.extractfile(m).read() if m.isreg() else None
                    for m in tr}

    def member(self, name: str) -> str:
        return name if self.how == "scan" else f"app/{name}"


@pytest.fixture(params=["scan", "copy"])
def how(request):
    return request.param


@pytest.fixture
def registry():
    reg = metrics.MetricsRegistry()
    token = metrics.set_build_registry(reg)
    yield reg
    metrics.reset_build_registry(token)


@pytest.fixture(autouse=True)
def no_flush(monkeypatch):
    """``os.sync()`` is not under test, and on a busy machine takes
    what it takes: these tests time the commit."""
    monkeypatch.setattr(memfs.os, "sync", lambda: None)


def _waits(registry) -> list:
    return [s for s in registry.root.children if s.name == SPAN]


def _count(registry, result: str) -> float:
    return registry.counter_total(metrics.MTIME_WAIT_TOTAL, result=result)


def _early_in_a_second() -> None:
    """Return between .05 and .3 of a second: what is written next and
    rewritten within half a second shares one second, and is not
    within the clock margin of the second's start."""
    while not 0.05 <= time.time() % 1 <= 0.3:
        time.sleep(0.01)


def test_old_tree_commits_without_sleeping(tmp_path, how, registry):
    tree = Tree(tmp_path, how, sync_wait=1.0)
    tree.write("a.txt", b"a", age=OLD)
    tree.write("sub/b.txt", b"b", age=2)
    t0 = time.monotonic()
    members = tree.commit()
    assert time.monotonic() - t0 < 0.5
    assert tree.member("sub/b.txt") in members
    [span] = _waits(registry)
    assert span.duration < 0.05
    assert span.attrs == {"wait_s": "0.000"}
    assert _count(registry, "clear") == 1
    assert _count(registry, "slept") == 0


@pytest.mark.parametrize("waiting", [True, False],
                         ids=["waits", "wait_stubbed_out"])
def test_rewrite_after_commit_is_seen_by_the_next_scan(
        tmp_path, how, registry, monkeypatch, waiting):
    """What the wait exists for: a file written an instant before a
    commit and rewritten to the same size an instant after it. With the
    sleep stubbed out both writes share a second and the next diff
    misses the edit."""
    tree = Tree(tmp_path, how, sync_wait=1.0)
    tree.write("old.txt", b"old", age=OLD)
    _early_in_a_second()
    if not waiting:
        monkeypatch.setattr(memfs.time, "sleep", lambda _s: None)
    second = tree.write("f.txt", b"first")
    assert tree.commit()[tree.member("f.txt")] == b"first"
    returned = time.time()
    assert _count(registry, "slept") == 1
    [span] = _waits(registry)
    assert 0.5 < float(span.attrs["wait_s"]) <= 1.0

    tree.write("f.txt", b"again")       # same size, at once
    tree.fs.sync_wait = 0.0
    edited = tree.commit()
    if waiting:
        assert returned >= second + 1 + memfs._CLOCK_MARGIN
        assert span.duration >= float(span.attrs["wait_s"]) - 0.001
        assert edited[tree.member("f.txt")] == b"again"
    else:
        assert returned < second + 1
        assert tree.member("f.txt") not in edited


def test_newest_mtime_on_an_unchanged_entry_still_waits(
        tmp_path, how, registry):
    tree = Tree(tmp_path, how, sync_wait=0.0)
    _early_in_a_second()
    second = tree.write("fresh.txt", b"fresh")
    tree.commit()                       # the tree now holds fresh.txt
    tree.fs.sync_wait = 1.0
    tree.write("added.txt", b"added", age=OLD)
    members = tree.commit()
    returned = time.time()
    assert tree.member("added.txt") in members
    assert tree.member("fresh.txt") not in members
    assert returned >= second + 1 + memfs._CLOCK_MARGIN
    assert _count(registry, "slept") == 1
    assert _count(registry, "clear") == 1       # the commit at 0.0


def test_mtime_in_the_future_waits_sync_wait_and_no_longer(
        tmp_path, how, registry):
    tree = Tree(tmp_path, how, sync_wait=0.25)
    tree.write("future.txt", b"f", age=-3600)
    t0 = time.monotonic()
    tree.commit()
    assert 0.25 <= time.monotonic() - t0 < 0.75
    [span] = _waits(registry)
    assert span.attrs == {"wait_s": "0.250"}
    assert _count(registry, "slept") == 1


def test_sync_wait_zero_never_sleeps(tmp_path, how, registry, monkeypatch):
    slept = []
    monkeypatch.setattr(memfs.time, "sleep", slept.append)
    tree = Tree(tmp_path, how, sync_wait=0.0)
    tree.write("now.txt", b"now")
    tree.write("future.txt", b"f", age=-3600)
    tree.commit()
    assert not slept
    [span] = _waits(registry)
    assert span.attrs == {"wait_s": "0.000"}
    assert _count(registry, "clear") == 1
    assert _count(registry, "slept") == 0


def test_empty_copy_layer_has_nothing_to_wait_for(tmp_path, registry):
    """A COPY whose walk visits no entry (an empty directory's
    contents) still opens the span and counts the layer."""
    tree = Tree(tmp_path, "copy", sync_wait=1.0)
    t0 = time.monotonic()
    tree.commit()
    assert time.monotonic() - t0 < 0.5
    assert len(_waits(registry)) == 1
    assert _count(registry, "clear") == 1
