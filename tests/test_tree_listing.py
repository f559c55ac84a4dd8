"""A build's one listing of its context tree (``snapshot/walk.py``
``TreeListing``): ``walk`` through it visits what a live walk visits,
a second pass makes no file-system call, it serves the context and
nothing else, a ``RUN`` ends it, and no build sees another's. And the
two routes a directory is read from disk by (``_read_dir``: the native
reader's one foreign call, the ``scandir`` body): the same children,
the same ``lstat`` field for field, the same cache ids."""

import contextlib
import ctypes
import importlib
import io
import json
import os
import tarfile

import pytest

from makisu_tpu import cli, native, shell
from makisu_tpu.builder import BuildPlan
from makisu_tpu.cache import NoopCacheManager
from makisu_tpu.context import BuildContext
from makisu_tpu.docker.image import ImageName
from makisu_tpu.dockerfile import parse_file
from makisu_tpu.snapshot import CopyOperation, MemFS, TreeListing
from makisu_tpu.steps.run_step import RunStep
from makisu_tpu.storage import ImageStore
from makisu_tpu.utils import metrics, statcache
from makisu_tpu.worker import session as session_mod

walk_mod = importlib.import_module("makisu_tpu.snapshot.walk")

DIRS = "makisu_tree_listing_dirs_total"
READS = "makisu_dir_reads_total"
REPEATS = "makisu_dir_read_repeats_total"


def _reader_or_skip():
    reader = native.dir_reader()
    if reader is None:
        pytest.skip("libdirscan.so cannot be built or loaded here")
    return reader


@pytest.fixture
def tree(tmp_path):
    """A context with nested directories, a symlink to a directory, a
    special file, a subtree the caller blacklists and one a
    .dockerignore would exclude (exclusions ride the blacklist)."""
    ctx = tmp_path / "ctx"
    (ctx / "app" / "lib" / "deep").mkdir(parents=True)
    (ctx / "app" / "a.txt").write_text("a")
    (ctx / "app" / "lib" / "b.txt").write_text("bb")
    (ctx / "app" / "lib" / "deep" / "c.txt").write_text("ccc")
    os.symlink("lib", ctx / "app" / "link-to-dir")
    os.symlink("a.txt", ctx / "app" / "link-to-file")
    os.mkfifo(ctx / "app" / "fifo")
    (ctx / "app" / "secret").mkdir()
    (ctx / "app" / "secret" / "key").write_text("k")
    (ctx / "app" / "ignored").mkdir()
    (ctx / "app" / "ignored" / "junk").write_text("j")
    (ctx / "z-last.txt").write_text("z")
    return ctx


@pytest.fixture
def registry():
    reg = metrics.MetricsRegistry()
    token = metrics.set_build_registry(reg)
    yield reg
    metrics.reset_build_registry(token)


def _dirs(registry, result):
    return registry.counter_total(DIRS, result=result)


def _blacklist(ctx):
    return [str(ctx / "app" / "secret"), str(ctx / "app" / "ignored")]


def _visits(root, blacklist, listing=None):
    seen = []
    walk_mod.walk(str(root), blacklist,
                  lambda path, st: seen.append((path, tuple(st))), listing)
    return seen


class _Calls:
    """Counts the directories read (``os.scandir`` or the native
    reader's call, whichever route is taken: ``scandir``) and the
    ``os.lstat`` calls made from now on, those on paths under ``under``
    where one is given (``walk.py`` reaches both through the ``os``
    module, as everyone does)."""

    def __init__(self, monkeypatch, under=""):
        self.scandir = self.lstat = 0
        real_scandir, real_lstat = os.scandir, os.lstat
        reader = native.dir_reader()
        if reader is not None:
            real_read = reader.read

            def read(path, want_stat):
                self.scandir += counted(path)
                return real_read(path, want_stat)

            monkeypatch.setattr(reader, "read", read)

        def counted(path):     # shutil hands both a descriptor
            return not isinstance(path, int) \
                and os.fsdecode(path).startswith(str(under))

        def scandir(path):
            self.scandir += counted(path)
            return real_scandir(path)

        def lstat(path, **kw):
            self.lstat += counted(path)
            return real_lstat(path, **kw)

        monkeypatch.setattr(walk_mod.os, "scandir", scandir)
        monkeypatch.setattr(walk_mod.os, "lstat", lstat)

    @property
    def total(self):
        return self.scandir + self.lstat


# -- walk(): the same visits, with and without ------------------------------


@pytest.mark.parametrize("start", ["root", "subtree", "file", "symlink"])
@pytest.mark.parametrize("state", ["empty", "filled"])
def test_walk_with_a_listing_visits_what_a_live_walk_visits(
        tree, start, state):
    src = {"root": tree, "subtree": tree / "app" / "lib",
           "file": tree / "z-last.txt",
           "symlink": tree / "app" / "link-to-dir"}[start]
    live = _visits(src, _blacklist(tree))
    listing = TreeListing(str(tree))
    if state == "filled":
        _visits(tree, None, listing)
    assert _visits(src, _blacklist(tree), listing) == live
    paths = [p for p, _ in live]
    if start == "root":
        # Sorted, a directory before its later siblings; the symlink
        # to a directory visited and not entered; the special file,
        # the blacklisted and the excluded subtree never.
        app = str(tree / "app")
        assert paths == [str(tree), app, f"{app}/a.txt", f"{app}/lib",
                         f"{app}/lib/b.txt", f"{app}/lib/deep",
                         f"{app}/lib/deep/c.txt", f"{app}/link-to-dir",
                         f"{app}/link-to-file", str(tree / "z-last.txt")]
    if start == "symlink":
        assert paths == [str(src)]


@pytest.mark.parametrize("form", ["plain", "trailing-slash", "dot"])
def test_one_directory_one_key_whatever_the_path_form(tree, form,
                                                      registry):
    """``COPY . /app/`` resolves to ``<context>/.`` and ``COPY app/``
    to a trailing slash: the same directories, replayed all the same,
    and the paths handed back keep the caller's form."""
    listing = TreeListing(str(tree))
    _visits(tree / "app", None, listing)
    src = {"plain": f"{tree}/app", "trailing-slash": f"{tree}/app/",
           "dot": f"{tree}/./app/."}[form]
    seen = _visits(src, None, listing)
    # app, ignored, lib, lib/deep, secret: listed once, replayed once.
    assert (_dirs(registry, "listed"), _dirs(registry, "replayed")) == (5, 5)
    assert all(p.startswith(src) for p, _ in seen)
    assert [tuple(st) for _, st in seen] \
        == [tuple(st) for _, st in _visits(tree / "app", None)]
    assert not listing.serves(f"{tree}/app/../app")


def test_a_second_walk_makes_no_file_system_call(tree, monkeypatch, dir_route):
    listing = TreeListing(str(tree))
    first = _visits(tree, _blacklist(tree), listing)
    calls = _Calls(monkeypatch)
    assert _visits(tree, _blacklist(tree), listing) == first
    assert calls.total == 0
    # A walk with another blacklist replays what it can and lists only
    # what nobody entered before.
    assert len(_visits(tree, None, listing)) == len(first) + 4
    assert calls.scandir == 2 and calls.lstat == 0


def test_a_live_walk_stats_each_entry_once(tree, monkeypatch, dir_route):
    calls = _Calls(monkeypatch)
    _visits(tree, None)
    # One scandir a directory (the root, app, ignored, lib, deep,
    # secret); the root's own lstat and no other (a child's stat comes
    # from its directory entry).
    assert (calls.scandir, calls.lstat) == (6, 1)


class _Vanishing:
    """``os.scandir`` whose entry for ``gone`` is handed over with the
    file just removed: the ``lstat`` that follows finds nothing."""

    def __init__(self, path, gone, real_scandir):
        self.it, self.gone = real_scandir(path), gone

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.it.close()

    def __iter__(self):
        for entry in self.it:
            if entry.path == self.gone:
                os.unlink(self.gone)
            yield entry


@pytest.fixture
def vanishing(monkeypatch, dir_route):
    """``vanishing(path)``: from now on ``path`` is removed after its
    directory gave its name and before its ``lstat``, on the route the
    test runs (the native reader calls the tests' hook before each
    child's ``lstat``, on the reading thread)."""
    hooks = []
    reader = native.dir_reader()

    def arm(gone):
        if dir_route == "python":
            real_scandir = os.scandir
            monkeypatch.setattr(
                walk_mod.os, "scandir",
                lambda path: _Vanishing(path, gone, real_scandir))
            return

        @ctypes.CFUNCTYPE(None, ctypes.c_char_p)
        def before_lstat(name):
            if name == os.fsencode(os.path.basename(gone)) \
                    and os.path.lexists(gone):
                os.unlink(gone)

        hooks.append(before_lstat)
        reader.lib.dsc_test_before_lstat(
            ctypes.cast(before_lstat, ctypes.c_void_p))

    yield arm
    if hooks:
        reader.lib.dsc_test_before_lstat(None)


def test_a_child_gone_between_listing_and_stat_is_left_out(
        tree, vanishing):
    gone = str(tree / "app" / "a.txt")
    vanishing(gone)
    paths = [p for p, _ in _visits(tree, None, TreeListing(str(tree)))]
    assert gone not in paths and str(tree / "app" / "lib") in paths
    assert not os.path.lexists(gone)


def test_tarinfo_from_stat_uses_the_stat_it_is_handed(tree, monkeypatch):
    path = str(tree / "app" / "lib" / "b.txt")
    link = str(tree / "app" / "link-to-dir")
    st, link_st = os.lstat(path), os.lstat(link)
    want = walk_mod.tarinfo_from_stat(path, "b.txt", str(tree))
    want_link = walk_mod.tarinfo_from_stat(link, "l", str(tree))
    calls = _Calls(monkeypatch)
    got = walk_mod.tarinfo_from_stat(path, "b.txt", str(tree), st)
    got_link = walk_mod.tarinfo_from_stat(link, "l", str(tree), link_st)
    assert calls.lstat == 0
    assert got.get_info() == want.get_info()
    assert got_link.get_info() == want_link.get_info()
    assert got_link.linkname == "lib"


# -- what it serves ---------------------------------------------------------


def test_a_root_outside_the_context_is_listed_live(tree, tmp_path,
                                                   monkeypatch, registry,
                                                   dir_route):
    other = tmp_path / "root"
    (other / "etc").mkdir(parents=True)
    (other / "etc" / "passwd").write_text("root")
    listing = TreeListing(str(tree))
    assert not listing.serves(str(other))
    assert not listing.serves(str(tree) + "-sibling")
    first = _visits(other, None, listing)
    calls = _Calls(monkeypatch)
    assert _visits(other, None, listing) == first
    assert calls.scandir == 2 and calls.lstat == 1
    assert _dirs(registry, "listed") == _dirs(registry, "replayed") == 0
    assert listing.started_ns is None


def _copy_layer(fs, op, listing):
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w|") as tw:
        fs.add_layer_by_copy_ops([op], tw, listing)
    buf.seek(0)
    with tarfile.open(fileobj=buf, mode="r|") as tr:
        return [m.name for m in tr]


@pytest.mark.parametrize("internal", [False, True])
def test_an_internal_copy_is_always_listed_live(tree, tmp_path, internal,
                                                monkeypatch, dir_route):
    """A ``COPY --from`` source was written by the build: even one that
    lies under the context (a sandbox inside it) never goes through
    the listing. An external op does."""
    monkeypatch.setattr(os, "sync", lambda: None)
    root = tmp_path / "root"
    root.mkdir()
    fs = MemFS(str(root), blacklist=[], sync_wait=0.0)
    listing = TreeListing(str(tree))
    op = CopyOperation(["app/lib"], str(tree), "/", "/lib/",
                       blacklist=[], internal=internal)
    names = _copy_layer(fs, op, listing)
    assert names == ["lib", "lib/b.txt", "lib/deep", "lib/deep/c.txt"]
    assert (listing.started_ns is None) == internal
    assert listing.serves(str(tree / "app" / "lib"))
    calls = _Calls(monkeypatch)
    fs2 = MemFS(str(root), blacklist=[], sync_wait=0.0)
    assert _copy_layer(fs2, op, listing) == names
    assert (calls.scandir == 0) == (not internal)


def _build_ctx(tmp_path, ctx_dir):
    root = tmp_path / "root"
    root.mkdir(exist_ok=True)
    store = ImageStore(str(tmp_path / "store"))
    return BuildContext(str(root), str(ctx_dir), store, sync_wait=0.0)


def test_a_run_step_ends_the_listing_for_the_build(tree, tmp_path,
                                                   monkeypatch, registry,
                                                   dir_route):
    ctx = _build_ctx(tmp_path, tree)
    stage_ctx = ctx.new_stage_context()
    assert stage_ctx.listing is ctx.listing
    _visits(tree, None, ctx.listing)
    assert ctx.listing.serves(str(tree))
    ran = []
    monkeypatch.setattr(shell, "exec_command",
                        lambda *a, **k: ran.append(
                            ctx.listing.serves(str(tree))))
    step = RunStep("touch x", "touch x", False)
    step.working_dir = str(tmp_path)
    step.execute(stage_ctx, modify_fs=True)
    # Closed before the command ran, and for good: every later pass
    # lists live, as without a listing.
    assert ran == [False]
    assert not ctx.listing.serves(str(tree))
    assert ctx.listing.started_ns is None
    calls = _Calls(monkeypatch)
    assert _visits(tree, None, ctx.listing) == _visits(tree, None)
    assert calls.scandir == 2 * 6
    assert (_dirs(registry, "listed"), _dirs(registry, "replayed")) == (6, 0)


# -- through a build --------------------------------------------------------


@pytest.fixture
def fresh_sessions(monkeypatch):
    monkeypatch.setenv("MAKISU_TPU_STAT_CACHE_WINDOW_NS", "0")
    monkeypatch.setenv("MAKISU_TPU_SESSION_SNAPSHOT", "1")
    session_mod.manager().reset()
    yield
    session_mod.manager().reset()


def _cli_build(tmp_path, ctx, tag):
    storage = tmp_path / "storage"
    (tmp_path / "root").mkdir(exist_ok=True)
    assert cli.main([
        "--log-level", "error", "build", str(ctx), "-t", tag,
        "--hasher", "cpu", "--storage", str(storage),
        "--root", str(tmp_path / "root")]) == 0
    with ImageStore(str(storage)) as store:
        manifest = store.manifests.load(ImageName.parse(tag))
        [layer] = manifest.layers
        with store.layers.open(layer.digest.hex()) as f:
            with tarfile.open(fileobj=f, mode="r:gz") as tar:
                return {m.name: (tar.extractfile(m).read()
                                 if m.isreg() else None)
                        for m in tar.getmembers()}


def test_a_file_replaced_between_two_builds_is_seen_by_the_second(
        tree, tmp_path, fresh_sessions):
    """A listing is a build's, never a process's or a session's."""
    os.unlink(tree / "app" / "fifo")
    (tree / "Dockerfile").write_text("FROM scratch\nCOPY app /app/\n")
    first = _cli_build(tmp_path, tree, "listing/t:1")
    assert first["app/lib/b.txt"] == b"bb"
    replacement = tree / "app" / "lib" / "b.txt.new"
    replacement.write_text("replaced, and longer")
    os.replace(replacement, tree / "app" / "lib" / "b.txt")
    (tree / "app" / "lib" / "added.txt").write_text("new")
    second = _cli_build(tmp_path, tree, "listing/t:2")
    assert second["app/lib/b.txt"] == b"replaced, and longer"
    assert second["app/lib/added.txt"] == b"new"
    assert session_mod.manager().peek(str(tree)).build_listing is None


def test_a_build_lists_each_directory_once_and_replays_it_twice(
        tree, tmp_path, fresh_sessions, monkeypatch):
    """The checksum pass lists, the layer scan and the session's
    checkpoint replay: one add a pass to the counter, and ``walk.py``
    reads each directory of the context once (the watcher's own walk
    to place its watches is not ``walk.py``'s)."""
    os.unlink(tree / "app" / "fifo")
    (tree / "Dockerfile").write_text("FROM scratch\nCOPY app /app/\n")
    adds = []
    real_add = metrics.counter_add

    def counter_add(name, value=1.0, **labels):
        if name == DIRS:
            adds.append((labels["result"], value))
        real_add(name, value, **labels)

    monkeypatch.setattr(metrics, "counter_add", counter_add)
    read = []
    real_list_dir = walk_mod._list_dir
    monkeypatch.setattr(
        walk_mod, "_list_dir",
        lambda path: read.append(path) or real_list_dir(path))
    _cli_build(tmp_path, tree, "listing/count:1")
    # app, lib, deep, secret, ignored: listed by the checksum pass,
    # replayed by the scan and by the checkpoint, which alone lists
    # the context's root.
    assert adds == [("listed", 5), ("replayed", 5),
                    ("listed", 1), ("replayed", 5)]
    in_context = [p for p in read if p.startswith(str(tree))]
    assert len(in_context) == len(set(in_context)) == 6


def test_stages_of_one_build_share_the_listing(tree, tmp_path,
                                               monkeypatch, dir_route):
    os.unlink(tree / "app" / "fifo")
    ctx = _build_ctx(tmp_path, tree)
    calls = _Calls(monkeypatch, under=tree)
    plan = BuildPlan(
        ctx, ImageName("", "listing/stages", "t"), [], NoopCacheManager(),
        parse_file("FROM scratch AS one\nCOPY app /a/\n"
                   "FROM scratch\nCOPY app/lib /b/\n"),
        allow_modify_fs=False, force_commit=True)
    assert calls.scandir == 5       # the second stage's pass: replayed
    plan.execute()
    assert calls.scandir == 5


# -- the two routes a directory is read by -----------------------------------


@contextlib.contextmanager
def _python_route(monkeypatch):
    """A context in which ``_read_dir`` finds no library."""
    with monkeypatch.context() as m:
        m.setattr(native, "dir_reader", lambda: None)
        yield


def _outcome(path, want_stat):
    """What ``_read_dir`` gives for ``path``, sorted by name, in a form
    that compares whole: every field ``os.lstat`` has (``__reduce__``:
    the ten of the tuple and the named ones, ``_ns`` and floats among
    them), or the error's type and number."""
    try:
        listed = sorted(walk_mod._read_dir(str(path), want_stat))
    except OSError as e:
        return ("raised", type(e), e.errno, e.filename)
    if want_stat:
        return [(name, st.__reduce__()[1]) for name, st in listed]
    return listed


def _both_routes(monkeypatch, path, want_stat):
    _reader_or_skip()
    by_native = _outcome(path, want_stat)
    with _python_route(monkeypatch):
        by_python = _outcome(path, want_stat)
    return by_native, by_python


def _case_dir(tmp_path, case):
    d = tmp_path / "dir"
    d.mkdir()
    if case == "files-and-directories":
        for k in range(12):
            (d / f"f{k}.txt").write_bytes(b"x" * (k * 1000))
        (d / "sub").mkdir()
        (d / "sub" / "inner").write_text("i")
        (d / ".hidden").write_text("h")
        os.chmod(d / "f1.txt", 0o4755)
        os.utime(d / "f2.txt", ns=(1, 1_000_000_007))
        os.link(d / "f3.txt", d / "f3-again.txt")
    elif case == "empty-directory":
        pass
    elif case == "symlink":
        (d / "target").write_text("t")
        os.symlink("target", d / "link")
        os.symlink(".", d / "link-to-dir")
    elif case == "dangling-symlink":
        os.symlink("nowhere", d / "dangling")
    elif case == "fifo":
        os.mkfifo(d / "fifo")
        (d / "beside").write_text("b")
    elif case == "name-not-utf8":
        raw = os.fsencode(str(d))
        for name in (b"caf\xe9", b"\xff\xfe", b"half-\xe2\x82", b"ok"):
            with open(raw + b"/" + name, "wb") as f:
                f.write(name)
    elif case == "far-times":
        # Past what 64 bits of nanoseconds hold: the native reader
        # declines the directory and the scandir body reads it.
        (d / "late").write_text("l")
        os.utime(d / "late", (10 ** 10, 10 ** 10))
        if os.lstat(d / "late").st_mtime != 10 ** 10:
            (d / "late").unlink()        # this file system clamps
    elif case == "missing":
        d.rmdir()
    elif case == "not-a-directory":
        d.rmdir()
        d.write_text("a file")
    elif case == "unreadable":
        (d / "f").write_text("f")
        os.chmod(d, 0)              # root reads it all the same: equal
    return d


@pytest.mark.parametrize("want_stat", [True, False])
@pytest.mark.parametrize("case", [
    "files-and-directories", "empty-directory", "symlink",
    "dangling-symlink", "fifo", "name-not-utf8", "far-times", "missing",
    "not-a-directory", "unreadable"])
def test_both_routes_read_the_same_directory(tmp_path, monkeypatch, case,
                                             want_stat, registry):
    d = _case_dir(tmp_path, case)
    try:
        by_native, by_python = _both_routes(monkeypatch, d, want_stat)
    finally:
        if case == "unreadable":
            os.chmod(d, 0o755)
    assert by_native == by_python
    if case in ("missing", "not-a-directory"):
        assert by_native[0] == "raised" and by_native[3] == str(d)
        return
    if case == "unreadable" and by_native[0] == "raised":
        return
    # One add a directory read, under the route that read it.
    declined = case == "far-times" and want_stat and bool(by_native)
    assert (native.dir_reader().read(str(d), want_stat) is None) == declined
    stat = "1" if want_stat else "0"
    assert (registry.counter_total(READS, route="native", stat=stat),
            registry.counter_total(READS, route="python", stat=stat)) \
        == ((0, 2) if declined else (1, 1))
    if want_stat:
        # The fields are the file system's own, and what the stat cache
        # keys a content id on is equal with them.
        for name, (ten, named) in by_native:
            st = os.lstat(os.path.join(str(d), name))
            assert st.__reduce__()[1] == (ten, named)
        listed = dict(walk_mod._list_dir(str(d)))
        assert list(listed) == sorted(listed)
        with _python_route(monkeypatch):
            assert [statcache.ContentIDCache._key(st)
                    for _, st in walk_mod._list_dir(str(d))] \
                == [statcache.ContentIDCache._key(st)
                    for st in listed.values()]
    else:
        assert walk_mod.child_dirs(str(d)) == [
            name for name, is_dir in walk_mod._read_dir(str(d), False)
            if is_dir]


@pytest.mark.parametrize("want_stat", [True, False])
def test_a_directory_past_the_first_buffer_is_read_again_once(
        tmp_path, monkeypatch, registry, want_stat):
    """5,000 children are more than the first call's room for 512: the
    call is made again with buffers of the size the first reported, and
    the repeat is counted."""
    d = tmp_path / "wide"
    d.mkdir()
    for k in range(5000):
        (d / f"entry-with-a-long-enough-name-{k:05d}").write_bytes(b"")
    (d / "sub").mkdir()
    by_native, by_python = _both_routes(monkeypatch, d, want_stat)
    assert by_native == by_python and len(by_native) == 5001
    assert registry.counter_total(REPEATS) == 1
    assert registry.counter_total(READS, route="native") == 1
    assert registry.counter_total(READS, route="python") == 1


def test_a_child_gone_before_its_lstat_is_left_out_by_both_routes(
        tmp_path, monkeypatch, vanishing, dir_route):
    d = _case_dir(tmp_path, "files-and-directories")
    before = dict(walk_mod._list_dir(str(d)))
    vanishing(str(d / "f5.txt"))
    after = dict(walk_mod._list_dir(str(d)))
    assert set(before) - set(after) == {"f5.txt"}
    assert all(after[name].__reduce__() == before[name].__reduce__()
               for name in after if name != "f3.txt")


def _two_copy_context(tmp_path):
    ctx = tmp_path / "ctx"
    (ctx / "src" / "pkg").mkdir(parents=True)
    (ctx / "Dockerfile").write_text(
        "FROM scratch\nCOPY src/ /src/\nCOPY . /all/\n")
    for k in range(6):
        (ctx / "src" / f"m{k}.py").write_text(f"# {k}\n" + "x=1\n" * 40)
    (ctx / "src" / "pkg" / "deep.py").write_text("deep")
    (ctx / "top.txt").write_text("top")
    return ctx


def _explained_build(tmp_path, ctx, tag):
    """Builds ``ctx`` into the one storage; returns the cache ids with
    what each names, and the stat cache's verdict a ``COPY``."""
    storage = tmp_path / "storage"
    (tmp_path / "root").mkdir(exist_ok=True)
    ledger = tmp_path / f"{tag}.jsonl"
    assert cli.main([
        "--log-level", "error", "--explain-out", str(ledger), "build",
        str(ctx), "-t", f"routes/t:{tag}", "--hasher", "cpu", "--storage",
        str(storage), "--root", str(tmp_path / "root")]) == 0
    with open(storage / "cache_key_value.json", encoding="utf-8") as f:
        ids = {key: value for key, (value, _) in json.load(f).items()}
    with open(ledger, encoding="utf-8") as f:
        rows = [json.loads(line) for line in f]
    return ids, [(row["key"], row["hits"], row["misses"]) for row in rows
                 if row.get("source") == "statcache"]


def test_cache_ids_are_the_same_with_the_library_absent_and_present(
        tmp_path, monkeypatch, fresh_sessions):
    """A storage filled by the scandir body is warm for the native
    reader, and back: every file's content id is vouched for by the stat
    cache (its key is ``lstat``'s fields), so no cache id moves and no
    file is read again."""
    _reader_or_skip()
    ctx = _two_copy_context(tmp_path)
    with _python_route(monkeypatch):
        cold_ids, cold = _explained_build(tmp_path, ctx, "python")
    session_mod.manager().reset()
    warm_ids, warm = _explained_build(tmp_path, ctx, "native")
    session_mod.manager().reset()
    with _python_route(monkeypatch):
        back_ids, back = _explained_build(tmp_path, ctx, "python-again")
    assert warm_ids == cold_ids == back_ids and len(cold_ids) >= 2
    assert [key for key, _, _ in cold] == [key for key, _, _ in warm]
    assert all(misses > 0 for _, _, misses in cold)
    assert all(misses == 0 and hits > 0 for _, hits, misses in warm + back)


def test_a_build_crosses_once_a_directory(tmp_path, monkeypatch,
                                          fresh_sessions):
    """With the library present a build reads its three context
    directories by three native calls with an ``lstat`` a child (the
    checksum pass), the layer scan and the checkpoint replay them, the
    session's watcher descends the same three by type bits alone, and
    nothing under the context is asked of ``os.scandir`` or ``os.lstat``
    (but the top of the source, which no listed directory holds)."""
    _reader_or_skip()
    ctx = tmp_path / "ctx"
    (ctx / "lib" / "deep").mkdir(parents=True)
    (ctx / "Dockerfile").write_text("FROM scratch\nCOPY . /app/\n")
    (ctx / "lib" / "a.py").write_text("a")
    (ctx / "lib" / "deep" / "b.py").write_text("b")
    real_scandir, real_lstat = os.scandir, os.lstat

    def scandir(path):
        if not isinstance(path, int) \
                and os.fsdecode(path).startswith(str(ctx)):
            raise AssertionError(f"os.scandir({path!r})")
        return real_scandir(path)

    def lstat(path, **kw):
        if not isinstance(path, int) and os.path.dirname(
                os.path.normpath(os.fsdecode(path))).startswith(str(ctx)):
            raise AssertionError(f"os.lstat({path!r})")
        return real_lstat(path, **kw)

    storage = tmp_path / "storage"
    (tmp_path / "root").mkdir()
    report = tmp_path / "report.json"
    monkeypatch.setattr(walk_mod.os, "scandir", scandir)
    monkeypatch.setattr(walk_mod.os, "lstat", lstat)
    assert cli.main([
        "--log-level", "error", "--metrics-out", str(report), "build",
        str(ctx), "-t", "routes/crossings:1", "--hasher", "cpu",
        "--storage", str(storage), "--root", str(tmp_path / "root")]) == 0
    monkeypatch.undo()
    with open(report, encoding="utf-8") as f:
        counters = json.load(f)["counters"]

    def total(name, **labels):
        return sum(row["value"] for row in counters.get(name, [])
                   if labels.items() <= row["labels"].items())

    assert total(READS, route="native", stat="1") == 3
    assert total(READS, route="python") == 0
    watched = session_mod.manager().peek(str(ctx)).watcher.healthy
    assert total(READS, route="native", stat="0") == (3 if watched else 0)
    assert (total(DIRS, result="listed"), total(DIRS, result="replayed")) \
        == (3, 6)


# -- the benchmark's reader, on a run record made by hand -------------------


@pytest.mark.parametrize("open_, close, want", [
    ({"listed": 10.0, "replayed": 4.0},
     {"listed": 1010.0, "replayed": 2004.0}, 100.0 * 2000 / 3000),
    ({}, {"listed": 500.0, "replayed": 500.0}, 50.0),
    ({"listed": 7.0}, {"listed": 7.0}, None),       # nothing asked
    ({}, {}, None),                                 # an older program
    (None, None, None),                             # an untraced run
])
def test_reader_gives_the_replayed_share_or_nothing(tmp_path, open_,
                                                    close, want):
    import json
    import sys
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    perfbench = os.path.join(checkout, "perfbench")
    if perfbench not in sys.path:
        sys.path.insert(0, perfbench)
    from pbharness import cells, driver

    def series(counts):
        if counts is None:
            return None
        rows = {(DIRS, (("result", r),)): v for r, v in counts.items()}
        rows[("makisu_layer_entries_total", (("kind", "file"),))] = 3.0
        return rows

    run = driver.Run(cell=None, seed=1, seconds=45.0, trace=True,
                     work_dir=str(tmp_path))
    run.counters_open, run.counters_close = series(open_), series(close)
    read = cells._load_module(os.path.join(
        perfbench, "readers", "tree_listing_replay_pct.py")).read
    got = read(run)
    assert got == (pytest.approx(want) if want is not None else None)
    with open(os.path.join(checkout, "BENCHMARK.json"),
              encoding="utf-8") as f:
        [entry] = [m for m in json.load(f)["per_layer"]
                   if m["name"] == "tree_listing_replay_pct"]
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "build_p50_s" and entry["better"] == "higher"


@pytest.mark.parametrize("open_, close, want", [
    ({("native", "1"): 5.0}, {("native", "1"): 65.0, ("native", "0"): 40.0},
     100.0),
    ({}, {("python", "1"): 30.0, ("python", "0"): 30.0}, 0.0),
    ({}, {("native", "1"): 30.0, ("python", "1"): 10.0}, 75.0),
    ({("native", "1"): 7.0}, {("native", "1"): 7.0}, None),  # nothing read
    ({}, {}, None),                                 # an older program
    (None, None, None),                             # an untraced run
])
def test_reader_gives_the_native_share_or_nothing(tmp_path, open_, close,
                                                  want):
    import sys
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    perfbench = os.path.join(checkout, "perfbench")
    if perfbench not in sys.path:
        sys.path.insert(0, perfbench)
    from pbharness import cells, driver

    def series(counts):
        if counts is None:
            return None
        rows = {(READS, (("route", route), ("stat", stat))): v
                for (route, stat), v in counts.items()}
        rows[(DIRS, (("result", "listed"),))] = 3.0
        return rows

    run = driver.Run(cell=None, seed=1, seconds=45.0, trace=True,
                     work_dir=str(tmp_path))
    run.counters_open, run.counters_close = series(open_), series(close)
    read = cells._load_module(os.path.join(
        perfbench, "readers", "dir_reads_native_pct.py")).read
    got = read(run)
    assert got == (pytest.approx(want) if want is not None else None)
    with open(os.path.join(checkout, "BENCHMARK.json"),
              encoding="utf-8") as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    entry = per_layer["dir_reads_native_pct"]
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "build_p50_s" and entry["better"] == "higher"
    assert entry["workloads"] \
        == per_layer["listing_blocked_share_pct"]["workloads"]
