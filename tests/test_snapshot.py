"""Snapshot engine tests: scan diffs, whiteouts, tar merge/untar, copy ops.

Modeled on the reference's heaviest suite (lib/snapshot/mem_fs_test.go,
1279 lines): real temp trees, crafted tars, asserted headers/whiteouts.
"""

import io
import os
import shutil
import tarfile

import pytest

from makisu_tpu.snapshot import CopyOperation, MemFS, eval_symlinks


def new_fs(root) -> MemFS:
    return MemFS(str(root), blacklist=[], sync_wait=0.0)


def scan_layer(fs: MemFS):
    """Run add_layer_by_scan into an in-memory tar; return (names, layer)."""
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w|") as tw:
        layer = fs.add_layer_by_scan(tw)
    buf.seek(0)
    with tarfile.open(fileobj=buf, mode="r|") as tr:
        names = [m.name for m in tr]
    return names, layer


def tar_bytes(entries) -> bytes:
    """entries: list of (name, type, content/linkname, extra-attrs dict)."""
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w|") as tw:
        for name, typ, payload, attrs in entries:
            ti = tarfile.TarInfo(name)
            ti.type = typ
            ti.mode = attrs.get("mode", 0o755)
            ti.uid = attrs.get("uid", 0)
            ti.gid = attrs.get("gid", 0)
            ti.mtime = attrs.get("mtime", 1000)
            if typ in (tarfile.SYMTYPE, tarfile.LNKTYPE):
                ti.linkname = payload
                tw.addfile(ti)
            elif typ == tarfile.REGTYPE:
                data = payload.encode() if isinstance(payload, str) else payload
                ti.size = len(data)
                tw.addfile(ti, io.BytesIO(data))
            else:
                tw.addfile(ti)
    return buf.getvalue()


def make_tar(entries) -> tarfile.TarFile:
    return tarfile.open(fileobj=io.BytesIO(tar_bytes(entries)), mode="r|")


# ---------------------------------------------------------------------------
# Scan-based layers
# ---------------------------------------------------------------------------

def test_scan_initial_tree(tmp_path):
    (tmp_path / "dir").mkdir()
    (tmp_path / "dir" / "f.txt").write_text("hello")
    (tmp_path / "top.txt").write_text("top")
    fs = new_fs(tmp_path)
    names, layer = scan_layer(fs)
    assert "dir" in names
    assert "dir/f.txt" in names
    assert "top.txt" in names


def test_rescan_without_changes_is_empty(tmp_path):
    (tmp_path / "a").mkdir()
    (tmp_path / "a" / "f").write_text("x")
    fs = new_fs(tmp_path)
    scan_layer(fs)
    names, layer = scan_layer(fs)
    assert names == []
    assert len(layer) == 0


def test_modified_file_appears_with_ancestors(tmp_path):
    d = tmp_path / "a" / "b"
    d.mkdir(parents=True)
    f = d / "f"
    f.write_text("one")
    fs = new_fs(tmp_path)
    scan_layer(fs)
    f.write_text("two!")  # size change → always detected
    names, _ = scan_layer(fs)
    assert "a/b/f" in names
    assert "a" in names and "a/b" in names  # ancestors re-emitted


def test_deleted_file_produces_whiteout(tmp_path):
    (tmp_path / "a").mkdir()
    (tmp_path / "a" / "gone").write_text("x")
    fs = new_fs(tmp_path)
    scan_layer(fs)
    os.unlink(tmp_path / "a" / "gone")
    names, _ = scan_layer(fs)
    assert "a/.wh.gone" in names


def test_deleted_subtree_single_whiteout(tmp_path):
    d = tmp_path / "a" / "sub"
    d.mkdir(parents=True)
    (d / "f1").write_text("1")
    (d / "f2").write_text("2")
    fs = new_fs(tmp_path)
    scan_layer(fs)
    import shutil
    shutil.rmtree(d)
    names, _ = scan_layer(fs)
    assert "a/.wh.sub" in names
    assert not any(n.startswith("a/sub/") for n in names)


def test_symlink_scanned_with_target(tmp_path):
    (tmp_path / "real").write_text("content")
    os.symlink("real", tmp_path / "rel_link")
    os.symlink(str(tmp_path / "real"), tmp_path / "abs_link")
    fs = new_fs(tmp_path)
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w|") as tw:
        fs.add_layer_by_scan(tw)
    buf.seek(0)
    with tarfile.open(fileobj=buf, mode="r|") as tr:
        links = {m.name: m.linkname for m in tr if m.issym()}
    assert links["rel_link"] == "real"
    assert links["abs_link"] == "/real"  # absolute target trimmed to root


def test_replace_file_with_dir(tmp_path):
    p = tmp_path / "thing"
    p.write_text("file")
    fs = new_fs(tmp_path)
    scan_layer(fs)
    p.unlink()
    p.mkdir()
    (p / "inner").write_text("x")
    names, _ = scan_layer(fs)
    assert "thing" in names and "thing/inner" in names


# ---------------------------------------------------------------------------
# Tar merge / untar
# ---------------------------------------------------------------------------

def test_update_from_tar_untars_to_disk(tmp_path):
    tf = make_tar([
        ("app/", tarfile.DIRTYPE, None, {"mode": 0o755, "mtime": 1234}),
        ("app/bin", tarfile.REGTYPE, "#!/bin/sh\n", {"mode": 0o755}),
        ("app/link", tarfile.SYMTYPE, "bin", {}),
    ])
    fs = new_fs(tmp_path)
    fs.update_from_tar(tf, untar=True)
    assert (tmp_path / "app" / "bin").read_text() == "#!/bin/sh\n"
    assert os.readlink(tmp_path / "app" / "link") == "bin"
    # Tree now mirrors the tar: immediate rescan yields nothing new.
    names, _ = scan_layer(fs)
    assert names == []


def test_update_from_tar_whiteout_deletes(tmp_path):
    (tmp_path / "a").mkdir()
    (tmp_path / "a" / "victim").write_text("x")
    fs = new_fs(tmp_path)
    scan_layer(fs)
    tf = make_tar([
        ("a/", tarfile.DIRTYPE, None, {}),
        ("a/.wh.victim", tarfile.REGTYPE, "", {}),
    ])
    fs.update_from_tar(tf, untar=True)
    assert not (tmp_path / "a" / "victim").exists()
    # The tree forgot it too: putting a new file there is a plain add.
    names, _ = scan_layer(fs)
    assert "a/.wh.victim" not in names


def test_update_from_tar_hardlink_second_pass(tmp_path):
    # Hard link appears BEFORE its target in the tar; the second pass
    # makes it work anyway.
    tf = make_tar([
        ("ln", tarfile.LNKTYPE, "orig", {}),
        ("orig", tarfile.REGTYPE, "data", {"mode": 0o644}),
    ])
    fs = new_fs(tmp_path)
    fs.update_from_tar(tf, untar=True)
    st1, st2 = os.stat(tmp_path / "ln"), os.stat(tmp_path / "orig")
    assert st1.st_ino == st2.st_ino


def test_update_restores_parent_mtime(tmp_path):
    d = tmp_path / "d"
    d.mkdir()
    os.utime(d, (5000, 5000))
    tf = make_tar([
        ("d/", tarfile.DIRTYPE, None, {"mtime": 5000}),
        ("d/new", tarfile.REGTYPE, "x", {}),
    ])
    fs = new_fs(tmp_path)
    fs.update_from_tar(tf, untar=True)
    assert int(os.lstat(d).st_mtime) == 5000


def test_update_existing_dir_not_deleted(tmp_path):
    d = tmp_path / "etc"
    d.mkdir()
    keep = d / "keep.conf"
    keep.write_text("keep me")
    tf = make_tar([("etc/", tarfile.DIRTYPE, None, {"mode": 0o700})])
    fs = new_fs(tmp_path)
    fs.update_from_tar(tf, untar=True)
    assert keep.read_text() == "keep me"
    assert (os.lstat(d).st_mode & 0o7777) == 0o700


def test_update_without_untar_only_builds_tree(tmp_path):
    tf = make_tar([
        ("x/", tarfile.DIRTYPE, None, {}),
        ("x/f", tarfile.REGTYPE, "abc", {}),
    ])
    fs = new_fs(tmp_path)
    fs.update_from_tar(tf, untar=False)
    assert not (tmp_path / "x").exists()
    assert fs._lookup("/x/f") is not None


# -- untar=True against a plain reference: stdlib extraction plus the
# whiteout rule, on a twin root seeded the same way ------------------------

_ME = {"uid": os.getuid(), "gid": os.getgid()}


def _f(name, content, mode=0o644, mtime=1000):
    return (name, tarfile.REGTYPE, content,
            dict(_ME, mode=mode, mtime=mtime))


def _d(name, mode=0o755, mtime=2000):
    return (name, tarfile.DIRTYPE, None, dict(_ME, mode=mode, mtime=mtime))


def _l(name, target):
    return (name, tarfile.SYMTYPE, target, dict(_ME))


def _remove(path):
    if os.path.isdir(path) and not os.path.islink(path):
        shutil.rmtree(path)
    elif os.path.lexists(path):
        os.remove(path)


def _plain_untar(root, blob):
    """The reference: members in the tar's order; a ``.wh.`` member
    removes its victim; a member replaces what is in its place unless
    both are directories; directory times are set last, as the tar
    states them."""
    stated = {}
    with tarfile.open(fileobj=io.BytesIO(blob)) as tf:
        for m in tf.getmembers():
            dest = os.path.join(root, m.name)
            head, base = os.path.split(dest)
            if base.startswith(".wh."):
                _remove(os.path.join(head, base[len(".wh."):]))
                continue
            on_disk_dir = os.path.isdir(dest) and not os.path.islink(dest)
            if not (m.isdir() and on_disk_dir):
                _remove(dest)
            tf.extract(m, root, filter="fully_trusted")
            if m.isdir():
                stated[dest] = m.mtime
    for dest, mtime in stated.items():
        os.utime(dest, (mtime, mtime))


def _disk(root, unstated=()):
    """path → (kind, mode, whole seconds of mtime, content or target);
    a symlink has no mode or time of its own to compare, a directory no
    tar member stated no time."""
    out = {}
    for cur, dirs, files in os.walk(root):
        for name in dirs + files:
            path = os.path.join(cur, name)
            rel = os.path.relpath(path, root)
            st = os.lstat(path)
            mode, mtime = st.st_mode & 0o7777, int(st.st_mtime)
            if os.path.islink(path):
                out[rel] = ("link", None, None, os.readlink(path))
            elif os.path.isdir(path):
                out[rel] = ("dir", mode,
                            None if rel in unstated else mtime, None)
            else:
                with open(path, "rb") as f:
                    out[rel] = ("file", mode, mtime, f.read())
    return out


def _entries(layer):
    """dst → what the layer holds for it."""
    out = {}
    for dst, e in layer.entries.items():
        if hasattr(e, "deleted"):
            out[dst] = ("whiteout",)
        else:
            h = e.hdr
            out[dst] = (h.type, h.mode, int(h.mtime), h.size, h.linkname,
                        h.uid, h.gid)
    return out


def _members(blob):
    """What the tar itself says of each path, in the shape of
    ``_entries``."""
    out = {}
    with tarfile.open(fileobj=io.BytesIO(blob)) as tf:
        for m in tf.getmembers():
            head, base = os.path.split("/" + m.name.rstrip("/"))
            if base.startswith(".wh."):
                out[os.path.join(head, base[len(".wh."):])] = ("whiteout",)
            else:
                out["/" + m.name.rstrip("/")] = (
                    m.type, m.mode, int(m.mtime), m.size, m.linkname,
                    m.uid, m.gid)
    return out


def _seed_nothing(root):
    pass


def _seed_etc_with_a_stranger(root):
    os.mkdir(os.path.join(root, "etc"))
    with open(os.path.join(root, "etc", "keep.conf"), "w") as f:
        f.write("keep me")
    os.utime(os.path.join(root, "etc", "keep.conf"), (500, 500))
    os.utime(os.path.join(root, "etc"), (600, 600))


def _seed(kind):
    def seed(root):
        os.mkdir(os.path.join(root, "app"))
        path = os.path.join(root, "app", "x")
        if kind == "dir":
            os.mkdir(path)
            with open(os.path.join(path, "inner"), "w") as f:
                f.write("inner")
        elif kind == "file":
            with open(path, "w") as f:
                f.write("old")
        elif kind == "dangling":
            os.symlink("nowhere", path)
        os.utime(os.path.join(root, "app"), (2000, 2000))
    return seed


_APP = _d("app/")
_TREE = [_d("app/"), _f("app/bin", "#!/bin/sh\n", mode=0o755),
         _d("app/lib/", mode=0o750, mtime=3000),
         _f("app/lib/a.js", "a" * 300), _f("app/lib/empty", ""),
         _l("app/link", "bin"), _d("var/")]
_LOWER = [_d("a/"), _f("a/victim", "v"), _f("a/keep", "k"), _d("b/"),
          _d("b/gone/"), _f("b/gone/f", "f")]
_UPPER = [_d("a/"), _f("a/.wh.victim", ""), _f("a/new", "new"),
          _d("b/", mtime=4000), _f("b/.wh.gone", "")]

# name: (seed, layers applied before, the layer, created, probed, bytes
# written, directories no member states)
_UNTAR_CASES = {
    "empty_root": (_seed_nothing, [], _TREE, 7, 0, 310, ()),
    # Applied twice: the second time every member finds its like.
    "every_member_exists_similar": (
        _seed_nothing, [_TREE], _TREE, 0, 7, 0, ()),
    "file_over_directory": (
        _seed("dir"), [], [_APP, _f("app/x", "now a file")], 0, 2, 10, ()),
    "directory_over_file": (
        _seed("file"), [], [_APP, _d("app/x/"), _f("app/x/y", "y")],
        1, 2, 1, ()),
    "file_over_dangling_symlink": (
        _seed("dangling"), [], [_APP, _f("app/x", "real")], 0, 2, 4, ()),
    "symlink_over_file": (
        _seed("file"), [], [_APP, _f("app/bin", "b"), _l("app/x", "bin")],
        1, 2, 1, ()),
    "directory_over_directory_that_holds_a_stranger": (
        _seed_etc_with_a_stranger, [],
        [_d("etc/", mode=0o700, mtime=7000), _f("etc/new.conf", "n")],
        1, 1, 1, ()),
    "no_directory_member_for_a_parent": (
        _seed_nothing, [],
        [_f("opt/pkg/bin/tool", "tool", mode=0o755), _l("opt/alt/t", "x"),
         _d("srv/www/", mtime=5000)],
        3, 0, 4, ("opt", "opt/pkg", "opt/pkg/bin", "opt/alt", "srv")),
    "second_layer_with_whiteouts": (
        _seed_nothing, [_LOWER], _UPPER, 1, 4, 3, ()),
}


@pytest.mark.parametrize("case", sorted(_UNTAR_CASES))
def test_untar_gives_what_plain_extraction_gives(tmp_path, monkeypatch, case):
    from makisu_tpu.utils import metrics
    seed, before, entries, created, probed, written, unstated = \
        _UNTAR_CASES[case]
    ours, plain = str(tmp_path / "ours"), str(tmp_path / "plain")
    for root in (ours, plain):
        os.mkdir(root)
        seed(root)
    os.mkdir(tmp_path / "stays_empty")
    fs, folded = new_fs(ours), new_fs(tmp_path / "stays_empty")
    for lower in before:
        fs.update_from_tar(make_tar(lower), untar=True)
        folded.update_from_tar(make_tar(lower), untar=False)
        _plain_untar(plain, tar_bytes(lower))
    kept = {rel: os.lstat(os.path.join(ours, rel)).st_ino
            for rel in _disk(ours)}
    adds = []
    monkeypatch.setattr(metrics, "counter_add",
                        lambda name, value=1.0, **labels:
                        adds.append((name, value, labels)))
    blob = tar_bytes(entries)
    layer = fs.update_from_tar(make_tar(entries), untar=True)
    monkeypatch.undo()
    _plain_untar(plain, blob)

    assert _disk(ours, unstated) == _disk(plain, unstated)
    assert [a for a in adds if a[0] != metrics.CACHED_LAYERS_APPLIED_TOTAL] \
        == [(metrics.ON_DISK_BYTES_TOTAL, written, {"op": "untar"}),
            (metrics.UNTAR_MEMBERS_TOTAL, created, {"result": "created"}),
            (metrics.UNTAR_MEMBERS_TOTAL, probed, {"result": "probed"})]
    if case == "every_member_exists_similar":
        # Nothing was written again: each path is the inode it was.
        assert {rel: os.lstat(os.path.join(ours, rel)).st_ino
                for rel in _disk(ours)} == kept
    # The layer: what the in-memory fold of the same tars holds, entry
    # for entry; each member as the tar states it; the rest ancestors.
    mine, stated = _entries(layer), _members(blob)
    assert mine == _entries(
        folded.update_from_tar(make_tar(entries), untar=False))
    for dst, fields in mine.items():
        if dst in stated:
            assert fields == stated[dst], dst
        else:
            assert fields[0] == tarfile.DIRTYPE
            assert any(m.startswith(dst + "/") for m in stated), dst
    assert before or set(stated) <= set(mine)
    assert os.listdir(tmp_path / "stays_empty") == []


def _counting(monkeypatch, module, names):
    """Count the calls of ``module.<name>`` from here on."""
    counts = dict.fromkeys(names, 0)

    def wrap(name, real):
        def counted(*args, **kwargs):
            counts[name] += 1
            return real(*args, **kwargs)
        return counted
    for name in names:
        monkeypatch.setattr(module, name, wrap(name, getattr(module, name)))
    return counts


def test_unpack_and_wipe_ask_nothing_the_loop_already_knows(
        tmp_path, monkeypatch):
    """Under an empty root a member is its writes: the whole unpack
    makes no ``lexists``, ``exists``, ``isdir`` or ``makedirs``; the
    wipe of that tree takes one ``lstat`` an entry and no ``isdir`` or
    ``islink``."""
    from makisu_tpu.snapshot.walk import remove_all_children
    files, dirs = 24, 6
    # The last file takes more than one read of its tar to write.
    big = 2 * (1 << 20) + 5
    entries = [_d(f"d{k}/") for k in range(dirs)] + [
        _f(f"d{k % dirs}/f{k}", "x" * (k + 1 if k < files - 1 else big))
        for k in range(files)]
    fs = new_fs(tmp_path)
    with monkeypatch.context() as m:
        probes = _counting(m, os.path, ["lexists", "exists", "isdir",
                                        "islink"])
        made = _counting(m, os, ["makedirs", "fdopen"])
        fs.update_from_tar(make_tar(entries), untar=True)
    on_disk = _disk(str(tmp_path))
    assert len(on_disk) == files + dirs
    assert on_disk[f"d{(files - 1) % dirs}/f{files - 1}"][3] == b"x" * big
    assert probes == {"lexists": 0, "exists": 0, "isdir": 0, "islink": 0}
    assert made == {"makedirs": 0, "fdopen": 0}
    with monkeypatch.context() as m:
        probes = _counting(m, os.path, ["isdir", "islink", "lexists",
                                        "exists"])
        stats = _counting(m, os, ["lstat", "stat"])
        remove_all_children(str(tmp_path), [])
    assert os.listdir(tmp_path) == []
    assert probes == {"isdir": 0, "islink": 0, "lexists": 0, "exists": 0}
    assert stats == {"lstat": files + dirs, "stat": 0}


def _wipe_tree(root):
    for rel in ("a/b/c", "keep/deep", "other"):
        os.makedirs(os.path.join(root, rel))
    for rel in ("a/f", "a/b/c/g", "keep/deep/precious", "keep/h", "other/i"):
        with open(os.path.join(root, rel), "w") as f:
            f.write(rel)


def _wipe_blacklisted(root, monkeypatch):
    return [os.path.join(root, "keep", "deep")], \
        {"keep", "keep/deep", "keep/deep/precious"}, None


def _wipe_symlink_to_directory(root, monkeypatch):
    outside = root + "-outside"
    os.mkdir(outside)
    with open(os.path.join(outside, "theirs"), "w") as f:
        f.write("theirs")
    os.symlink(outside, os.path.join(root, "a", "out"))
    os.symlink("missing", os.path.join(root, "a", "dangling"))
    return [], set(), lambda: os.listdir(outside) == ["theirs"]


def _wipe_raced(root, monkeypatch):
    """A file goes between its ``lstat`` and its delete, a directory
    between its ``lstat`` and its listing."""
    real = os.lstat
    going = {os.path.join(root, "a", "f"): os.remove,
             os.path.join(root, "other"): shutil.rmtree}

    def lstat(path, *args, **kwargs):
        st = real(path, *args, **kwargs)
        going.pop(path, lambda gone: None)(path)
        return st
    monkeypatch.setattr(os, "lstat", lstat)
    return [], set(), None


@pytest.mark.parametrize("arrange", [
    _wipe_blacklisted, _wipe_symlink_to_directory, _wipe_raced],
    ids=lambda f: f.__name__[len("_wipe_"):])
def test_remove_all_children_keeps_and_tolerates_what_it_did(
        tmp_path, monkeypatch, arrange):
    from makisu_tpu.snapshot.walk import remove_all_children
    root = str(tmp_path / "root")
    _wipe_tree(root)
    blacklist, survivors, also = arrange(root, monkeypatch)
    remove_all_children(root, blacklist)
    monkeypatch.undo()
    assert set(_disk(root)) == survivors
    assert also is None or also()


def test_remove_all_children_fails_on_a_directory_it_cannot_list(
        tmp_path, monkeypatch):
    """Keeping an unreadable directory's contents in silence would leak
    one stage's files into the next stage's layers."""
    from makisu_tpu.snapshot.walk import remove_all_children
    root = str(tmp_path / "root")
    _wipe_tree(root)
    real = os.listdir

    def listdir(path):
        if path == os.path.join(root, "a", "b"):
            raise PermissionError(13, "Permission denied", path)
        return real(path)
    monkeypatch.setattr(os, "listdir", listdir)
    with pytest.raises(PermissionError):
        remove_all_children(root, [])


# ---------------------------------------------------------------------------
# Copy-op layers
# ---------------------------------------------------------------------------

def _ctx(tmp_path):
    ctx = tmp_path / "ctx"
    ctx.mkdir()
    (ctx / "f1").write_text("one")
    (ctx / "sub").mkdir()
    (ctx / "sub" / "f2").write_text("two")
    return ctx


def copyop_layer(fs, ops):
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w|") as tw:
        layer = fs.add_layer_by_copy_ops(ops, tw)
    buf.seek(0)
    with tarfile.open(fileobj=buf, mode="r|") as tr:
        return {m.name: m for m in tr}, layer


def test_copyop_file_to_file(tmp_path):
    root = tmp_path / "root"
    root.mkdir()
    ctx = _ctx(tmp_path)
    fs = new_fs(root)
    op = CopyOperation(["f1"], str(ctx), "/", "/dest.txt")
    members, _ = copyop_layer(fs, [op])
    assert "dest.txt" in members
    assert members["dest.txt"].uid == 0


def test_copyop_file_to_dir_creates_ancestors(tmp_path):
    root = tmp_path / "root"
    root.mkdir()
    ctx = _ctx(tmp_path)
    fs = new_fs(root)
    op = CopyOperation(["f1"], str(ctx), "/", "/a/b/", chown="7:9")
    members, _ = copyop_layer(fs, [op])
    # Single-file copy: ancestors synthesize root-owned (reference
    # behavior — only explicit dst-dir creation takes the chown owner);
    # the file itself is chowned.
    assert members["a"].uid == 0 and members["a/b"].uid == 0
    assert members["a/b/f1"].uid == 7 and members["a/b/f1"].gid == 9


def test_copyop_dir_srcs_dst_dir_chowned(tmp_path):
    root = tmp_path / "root"
    root.mkdir()
    ctx = _ctx(tmp_path)
    fs = new_fs(root)
    op = CopyOperation(["f1", "sub"], str(ctx), "/", "/pkg/", chown="7:9")
    members, _ = copyop_layer(fs, [op])
    assert members["pkg"].uid == 7 and members["pkg"].gid == 9
    assert members["pkg/f1"].uid == 7
    # Directory sources copy their *contents* into dst (docker semantics).
    assert members["pkg/f2"].uid == 7
    assert "pkg/sub" not in members


def test_copyop_dir_contents_to_dst(tmp_path):
    root = tmp_path / "root"
    root.mkdir()
    ctx = _ctx(tmp_path)
    fs = new_fs(root)
    op = CopyOperation(["."], str(ctx), "/", "/app/")
    members, _ = copyop_layer(fs, [op])
    assert "app/f1" in members
    assert "app/sub" in members and members["app/sub"].isdir()
    assert "app/sub/f2" in members
    assert "app/ctx" not in members  # contents, not the dir itself


def test_copyop_multiple_srcs_require_dir_dst(tmp_path):
    ctx = _ctx(tmp_path)
    with pytest.raises(ValueError):
        CopyOperation(["f1", "sub"], str(ctx), "/", "/notadir")


def test_copyop_workdir_resolution(tmp_path):
    op = CopyOperation(["f"], str(tmp_path), "/srv", "rel/path")
    assert op.dst == "/srv/rel/path"


def test_copyop_execute_on_disk(tmp_path):
    root = tmp_path / "root"
    root.mkdir()
    ctx = _ctx(tmp_path)
    op = CopyOperation(["sub"], str(ctx), "/", "/app/")
    op.execute(eval_symlinks, str(root))
    assert (root / "app" / "f2").read_text() == "two"


# ---------------------------------------------------------------------------
# Symlink resolution, checkpoint, compare
# ---------------------------------------------------------------------------

def test_eval_symlinks_within_root(tmp_path):
    (tmp_path / "real").mkdir()
    (tmp_path / "real" / "f").write_text("x")
    os.symlink("real", tmp_path / "alias")
    assert eval_symlinks("alias/f", str(tmp_path)) == "/real/f"


def test_eval_symlinks_absolute_target(tmp_path):
    (tmp_path / "data").mkdir()
    os.symlink(str(tmp_path / "data"), tmp_path / "abs")
    assert eval_symlinks("abs", str(tmp_path)) == "/data"


def test_eval_symlinks_loop_detected(tmp_path):
    os.symlink("b", tmp_path / "a")
    os.symlink("a", tmp_path / "b")
    with pytest.raises(OSError):
        eval_symlinks("a/x", str(tmp_path))


def test_checkpoint_copies_sources(tmp_path):
    root = tmp_path / "root"
    (root / "out").mkdir(parents=True)
    (root / "out" / "bin").write_text("binary")
    fs = new_fs(root)
    newroot = tmp_path / "ckpt"
    newroot.mkdir()
    fs.checkpoint(str(newroot), ["out"])
    assert (newroot / "out" / "bin").read_text() == "binary"


def test_compare_trees(tmp_path):
    r1, r2 = tmp_path / "r1", tmp_path / "r2"
    for r in (r1, r2):
        r.mkdir()
        (r / "same").write_text("same")
    (r1 / "only1").write_text("1")
    (r2 / "only2").write_text("22")
    fs1, fs2 = new_fs(r1), new_fs(r2)
    scan_layer(fs1)
    scan_layer(fs2)
    diff = fs1.compare(fs2)
    assert "/only1" in diff.missing_in_second
    assert "/only2" in diff.missing_in_first
    assert not any(p == "/same" for p, _, _ in diff.different)


def test_hardlinked_files_scan_as_regular(tmp_path):
    """Scan layers record hardlinks as independent regular files (the
    reference does the same: createHeader's hardlink TODO); content must
    be intact for both names."""
    (tmp_path / "orig").write_bytes(b"shared-bytes")
    os.link(tmp_path / "orig", tmp_path / "alias")
    fs = new_fs(tmp_path)
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w|") as tw:
        fs.add_layer_by_scan(tw)
    buf.seek(0)
    with tarfile.open(fileobj=buf, mode="r|") as tr:
        members = {m.name: (m, tr.extractfile(m).read() if m.isreg()
                            else None) for m in tr}
    assert members["orig"][0].isreg() and members["alias"][0].isreg()
    assert members["orig"][1] == members["alias"][1] == b"shared-bytes"


def test_long_paths_roundtrip(tmp_path):
    """>100-char paths need PAX/GNU extensions; scan + merge must agree."""
    deep = tmp_path
    for i in range(12):
        deep = deep / f"directory-level-{i:02d}-with-a-long-name"
    deep.mkdir(parents=True)
    f = deep / ("f" * 60 + ".txt")
    f.write_text("deep")
    fs = new_fs(tmp_path)
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w|") as tw:
        fs.add_layer_by_scan(tw)
    buf.seek(0)
    dest = tmp_path.parent / (tmp_path.name + "-restored")
    dest.mkdir()
    fs2 = new_fs(dest)
    with tarfile.open(fileobj=buf, mode="r|") as tf:
        fs2.update_from_tar(tf, untar=True)
    restored = str(f).replace(str(tmp_path), str(dest))
    assert open(restored).read() == "deep"


def test_walk_survives_very_deep_trees(tmp_path):
    """Trees deeper than Python's recursion limit must scan and clean
    without RecursionError (walk and remove_all_children are iterative)."""
    import importlib
    walk_mod = importlib.import_module("makisu_tpu.snapshot.walk")

    depth = 1200  # > default recursion limit; path stays under PATH_MAX
    deep = str(tmp_path)
    for _ in range(depth):
        deep = deep + "/d"
        os.mkdir(deep)  # (pathlib's parents=True recurses — avoid it)
    with open(deep + "/leaf.txt", "w") as f:
        f.write("bottom")

    seen = []
    walk_mod.walk(str(tmp_path), [], lambda p, st: seen.append(p))
    assert any(p.endswith("leaf.txt") for p in seen)
    assert len(seen) == depth + 2  # root + dirs + leaf

    # Order parity with the recursive form: parents before children.
    for parent, child in zip(seen[1:], seen[2:]):
        assert child.startswith(parent)

    walk_mod.remove_all_children(str(tmp_path), [])
    assert os.listdir(tmp_path) == []


# ---------------------------------------------------------------------------
# Deferred application: cached layers wait until the tree is read
# ---------------------------------------------------------------------------

def _defer_two_layers(fs: MemFS, order: list) -> None:
    """Queue a layer that makes a/one, then one that deletes it and
    adds a/two: only in that order does the tree end with a/two alone."""
    first = [("a/", tarfile.DIRTYPE, None, {}),
             ("a/one", tarfile.REGTYPE, "1", {})]
    second = [("a/", tarfile.DIRTYPE, None, {}),
              ("a/.wh.one", tarfile.REGTYPE, "", {}),
              ("a/two", tarfile.REGTYPE, "2", {})]
    for name, entries in (("first", first), ("second", second)):
        def apply(name=name, entries=entries):
            order.append(name)
            fs.update_from_tar(make_tar(entries), untar=False,
                               chain_key=name)
        fs.defer(name, apply)


def _read_by_scan(fs, tmp_path):
    # The disk has no a/: the scan saw it in the tree and whites it out.
    assert scan_layer(fs)[0] == [".wh.a"]
    return "scanned away"


def _read_by_copy_ops(fs, tmp_path):
    op = CopyOperation(["f1"], str(_ctx(tmp_path)), "/", "/dest.txt")
    copyop_layer(fs, [op])


def _read_by_tar(fs, tmp_path):
    fs.update_from_tar(make_tar([("b/", tarfile.DIRTYPE, None, {})]),
                       untar=False)


def _read_by_tar_path(fs, tmp_path):
    import gzip
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w|") as tw:
        ti = tarfile.TarInfo("b/")
        ti.type = tarfile.DIRTYPE
        tw.addfile(ti)
    path = tmp_path / "layer.tar.gz"
    path.write_bytes(gzip.compress(buf.getvalue()))
    fs.update_from_tar_path(str(path), untar=False)


def _read_by_replay(fs, tmp_path):
    fs.replay_layer([], chain_key="third")


def _read_by_checkpoint(fs, tmp_path):
    (tmp_path / "sandbox").mkdir()
    fs.checkpoint(str(tmp_path / "sandbox"), [])


def _read_by_compare(fs, tmp_path):
    (tmp_path / "other").mkdir()
    assert fs.compare(new_fs(tmp_path / "other")).missing_in_second \
        == ["/a"]


def _read_by_being_compared(fs, tmp_path):
    (tmp_path / "other").mkdir()
    assert new_fs(tmp_path / "other").compare(fs).missing_in_first \
        == ["/a"]


@pytest.mark.parametrize("read", [
    _read_by_scan, _read_by_copy_ops, _read_by_tar, _read_by_tar_path,
    _read_by_replay, _read_by_checkpoint, _read_by_compare,
    _read_by_being_compared], ids=lambda f: f.__name__[len("_read_by_"):])
def test_every_reader_of_the_tree_applies_pending_layers_first(
        tmp_path, read):
    """Each method that reads or writes ``tree`` flushes the queue at
    its top: once each, oldest first, though the applications come
    back in through ``update_from_tar``, which flushes too."""
    root = tmp_path / "root"
    root.mkdir()
    fs = new_fs(root)
    order = []
    _defer_two_layers(fs, order)
    assert order == [] and fs.tree.children == {}
    chain_before = fs.applied_chain
    scanned_away = read(fs, tmp_path)
    assert order == ["first", "second"]
    if not scanned_away:
        assert sorted(fs.tree.children["a"].children) == ["two"]
    assert fs.applied_chain != chain_before
    assert fs.drop_pending() == []


def test_dropped_applications_never_run(tmp_path):
    """A stage that ends unread: the queue empties without one
    application having run, and names what it dropped, in order."""
    fs = new_fs(tmp_path)
    order = []
    _defer_two_layers(fs, order)
    assert fs.drop_pending() == ["first", "second"]
    fs.flush()
    scan_layer(fs)
    assert order == [] and fs.applied_chain == ""
    assert "a" not in fs.tree.children


def test_deferred_chain_identity_equals_eager(tmp_path):
    """Flushes keep the order, so ``applied_chain``, the replay memo's
    key, comes out as it does when each layer is applied at once."""
    eager_fs, deferred_fs = new_fs(tmp_path), new_fs(tmp_path)
    _defer_two_layers(eager_fs, [])
    eager_fs.flush()
    after_first = []

    def probe():
        after_first.append(deferred_fs.applied_chain)
    _defer_two_layers(deferred_fs, [])
    deferred_fs._pending.insert(1, ("probe", probe))
    deferred_fs.flush()
    assert deferred_fs.applied_chain == eager_fs.applied_chain
    assert after_first and after_first[0] not in ("",
                                                  eager_fs.applied_chain)
    assert not deferred_fs.chain_tainted


def test_scan_over_a_deferred_layer_equals_scan_over_an_applied_one(
        tmp_path):
    """A cached layer, then a disk that has since overwritten one of
    its files, deleted one (a whiteout) and added one under a directory
    the layer made: the scan's tar is byte for byte the same whether
    the layer was folded in at once or waited for the scan."""
    root = tmp_path / "root"
    (root / "d").mkdir(parents=True)
    for name in ("keep", "gone", "over"):
        (root / "d" / name).write_text(name)
    cached = io.BytesIO()
    with tarfile.open(fileobj=cached, mode="w|") as tw:
        new_fs(root).add_layer_by_scan(tw)
    (root / "d" / "gone").unlink()
    (root / "d" / "over").write_text("written over")
    (root / "d" / "new").write_text("new")
    for path in (root / "d" / "over", root / "d" / "new", root / "d"):
        os.utime(path, (2_000_000_000, 2_000_000_000))

    def scan(fs):
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w|") as tw:
            fs.add_layer_by_scan(tw)
        return buf.getvalue()

    def apply(fs):
        cached.seek(0)
        with tarfile.open(fileobj=cached, mode="r|") as tf:
            fs.update_from_tar(tf, untar=False, chain_key="cached")

    at_once, deferred = new_fs(root), new_fs(root)
    apply(at_once)
    deferred.defer("cached", lambda: apply(deferred))
    assert deferred.tree.children == {}
    tar_at_once, tar_deferred = scan(at_once), scan(deferred)
    assert tar_deferred == tar_at_once
    with tarfile.open(fileobj=io.BytesIO(tar_deferred), mode="r|") as tr:
        names = [m.name for m in tr]
    assert names == ["d", "d/.wh.gone", "d/new", "d/over"]
