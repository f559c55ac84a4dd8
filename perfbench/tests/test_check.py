"""What decides ``correct`` goes by the lane's record and the store's
owner (PR 44): ``check.sample`` on made-up runs, ``Checker`` on a
storage written here by hand, the ``started`` lane under a clock of the
test's own.

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q

No chip, no worker and no build: a few seconds."""

import gzip
import hashlib
import io
import json
import os
import re
import sys
import tarfile
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
sys.path.insert(0, PERFBENCH)
sys.path.insert(1, os.path.dirname(PERFBENCH))

from pbharness import cells, check, driver  # noqa: E402

REF = cells._load_module(os.path.join(PERFBENCH, "reference", "cdc.py"))
CONTEXT = {"layers": [{"dir": "a", "dest": "/a/"}]}


def _build(storage, index, lane=0, **kw):
    kw.setdefault("exit_code", 0)
    kw.setdefault("terminal", {"ok": True})
    kw.setdefault("t_done", float(index))
    return driver.Build(lane=lane, index=index, kind="cold",
                        tag=f"perfbench/lane{lane}:b{index}", context="",
                        storage=str(storage), context_bytes=1, **kw)


def _run(builds, fresh=True):
    cell = types.SimpleNamespace(traffic={"fresh_storage": fresh})
    counted = [b for b in builds if b.counted]
    return types.SimpleNamespace(cell=cell, builds=builds, counted=counted)


def _sha(data) -> str:
    return hashlib.sha256(data).hexdigest()


def write_outputs(build, seed=5, manifest=True, chunks_on_disk=True):
    """What one build of one layer leaves in its storage, written the
    way the program lays it out today; the chunk list and the bytes."""
    rng = np.random.default_rng(seed)
    raw = io.BytesIO()
    with tarfile.open(fileobj=raw, mode="w") as tar:
        for k in range(3):
            data = rng.bytes(40000)
            info = tarfile.TarInfo(f"a/f{k}")
            info.size = len(data)
            tar.addfile(info, io.BytesIO(data))
    tar_bytes = raw.getvalue()
    blob = gzip.compress(tar_bytes, mtime=0)
    cuts = REF.cut_points(tar_bytes)
    chunks = [[s, e - s, _sha(tar_bytes[s:e])]
              for s, e in zip([0] + cuts[:-1], cuts)]
    storage = build.storage

    def put(kind, hexd, data):
        os.makedirs(os.path.join(storage, kind, hexd[:2]), exist_ok=True)
        with open(os.path.join(storage, kind, hexd[:2], hexd), "wb") as f:
            f.write(data)
    put("layers", _sha(blob), blob)
    config = json.dumps({"rootfs": {
        "diff_ids": ["sha256:" + _sha(tar_bytes)]}}).encode()
    put("layers", _sha(config), config)
    if manifest:
        path = check.manifest_path(build)
        os.makedirs(os.path.dirname(path))
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"config": {"digest": "sha256:" + _sha(config)},
                       "layers": [{"digest": "sha256:" + _sha(blob),
                                   "size": len(blob)}]}, f)
    entry = {"gzip": "sha256:" + _sha(blob), "size": len(blob),
             "tar": "sha256:" + _sha(tar_bytes), "chunks": chunks}
    with open(os.path.join(storage, "cache_key_value.json"), "w",
              encoding="utf-8") as f:
        json.dump({"k": [json.dumps(entry), 0]}, f)
    if chunks_on_disk:
        store = check.chunk_store(build)
        for s, n, hexd in chunks:
            store.put(hexd, tar_bytes[s:s + n])
    return chunks, tar_bytes


# -- the sample ---------------------------------------------------------


def _standing(storage, what):
    os.makedirs(storage)
    if what == "one_file":
        with open(os.path.join(storage, "content_id_cache.json"), "w") as f:
            f.write("{}")
    elif what == "chunks_tree":
        os.makedirs(os.path.join(storage, "chunks", "ab"))
        with open(os.path.join(storage, "chunks", "ab", "ab" * 32), "w"):
            pass


@pytest.mark.parametrize("what", ["empty", "one_file", "chunks_tree"])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_sample_never_draws_a_build_the_lane_did_not_keep(tmp_path, what,
                                                          seed):
    builds = [_build(tmp_path / f"s{i}", i, counted=True, kept=i in (2, 5))
              for i in range(1, 6)]
    for b in builds:
        if not b.kept:
            _standing(b.storage, what)
    picked = check.sample(_run(builds), np.random.default_rng([seed, 13]), 4)
    assert sorted(b.index for b, _ in picked) == [2, 5]
    assert all(current for _, current in picked)


@pytest.mark.parametrize("seed", range(20))
def test_sample_always_has_the_last_counted_build(tmp_path, seed):
    keep = np.random.default_rng([seed, 11])
    builds = [_build(tmp_path / f"s{i}", i, counted=True,
                     kept=bool(keep.random() < 0.5) or i == 6)
              for i in range(1, 7)]
    picked = check.sample(_run(builds), np.random.default_rng([seed, 13]), 2)
    assert 6 in [b.index for b, _ in picked]
    assert all(b.kept for b, _ in picked)
    assert len(picked) == min(2, sum(b.kept for b in builds))


def test_sample_asks_the_file_system_nothing(tmp_path, monkeypatch):
    """No directory is made: every kept build is drawn all the same."""
    def refuse(*_a, **_kw):
        raise AssertionError("the sample looked at the disk")
    for name in ("isdir", "exists", "lexists"):
        monkeypatch.setattr(os.path, name, refuse)
    builds = [_build(tmp_path / f"s{i}", i, counted=True, kept=True)
              for i in range(1, 4)]
    for fresh in (True, False):
        picked = check.sample(_run(builds, fresh),
                              np.random.default_rng(3), 3)
        assert {b.index for b, _ in picked} == {1, 2, 3}


def test_sample_of_a_shared_storage_adds_the_lanes_last_build(tmp_path):
    builds = [_build(tmp_path / "s", i, counted=i < 4, kept=True)
              for i in range(1, 5)]
    picked = check.sample(_run(builds, fresh=False),
                          np.random.default_rng(1), 1)
    assert [(b.index, current) for b, current in picked] == [(3, False),
                                                             (4, True)]


# -- the checker --------------------------------------------------------


def test_a_sound_build_counts_nothing(tmp_path):
    b = _build(tmp_path / "s", 1, counted=True, kept=True)
    chunks, _ = write_outputs(b)
    checker = check.Checker(REF, CONTEXT)
    checker.check_build(b, False)
    assert checker.verdict() and checker.found == dict.fromkeys(
        check.LIMITS, 0)
    assert checker.checked == {"builds": 1, "layers": 1,
                               "chunks": len(chunks), "members": 0}
    numbers = checker.numbers()
    assert [k for k in numbers] == list(check.LIMITS) + ["checked",
                                                         "sampled"]
    assert all(numbers[k] == {"value": 0, "limit": 0}
               for k in check.LIMITS)
    assert numbers["sampled"] == [b.tag]
    assert checker.lines()[-6:] == [f"check: {k} 0 (limit 0)"
                                    for k in check.LIMITS]


def test_a_kept_build_without_a_manifest_counts_missing_outputs(tmp_path):
    kept = _build(tmp_path / "s2", 2, counted=True, kept=True)
    write_outputs(kept, manifest=False)
    gone = _build(tmp_path / "s3", 3, counted=True, kept=True)
    picked = check.sample(_run([kept, gone]), np.random.default_rng(0), 2)
    checker = check.Checker(REF, CONTEXT)
    for b, current in picked:
        checker.check_build(b, current)
    assert checker.found["missing_outputs"] == 2      # one each
    assert not checker.verdict()
    assert "no readable manifest" in " ".join(checker.lines())


class OwnNames:
    """A chunk store that lays its chunks out under names of its own:
    one packed file and an index, nothing under ``<aa>/<hex>``."""

    def __init__(self, root, index):
        self.pack = os.path.join(root, "pack.bin")
        self.index = index

    @classmethod
    def written(cls, root, chunks, tar_bytes):
        os.makedirs(root)
        index, at = {}, 0
        with open(os.path.join(root, "pack.bin"), "wb") as f:
            for s, n, hexd in chunks:
                f.write(tar_bytes[s:s + n])
                index[hexd] = (at, n)
                at += n
        return cls(root, index)

    def read(self, name):
        at, n = self.index[name]          # KeyError where it has none
        with open(self.pack, "rb") as f:
            f.seek(at)
            return f.read(n)


@pytest.mark.parametrize("fault, differing", [
    ("none", 0), ("missing", 1), ("altered", 1), ("truncated", 1)])
def test_stored_chunks_are_read_through_the_layouts_owner(
        tmp_path, monkeypatch, fault, differing):
    b = _build(tmp_path / "s", 1, counted=True, kept=True)
    chunks, tar_bytes = write_outputs(b, chunks_on_disk=False)
    assert not os.path.exists(os.path.join(b.storage, "chunks"))
    store = OwnNames.written(os.path.join(b.storage, "chunks"), chunks,
                             tar_bytes)
    name = chunks[2][2]
    if fault == "missing":
        del store.index[name]
    elif fault == "altered":
        with open(store.pack, "r+b") as f:
            f.seek(store.index[name][0])
            byte = f.read(1)
            f.seek(store.index[name][0])
            f.write(bytes([byte[0] ^ 1]))
    elif fault == "truncated":
        store.index[name] = (store.index[name][0], store.index[name][1] - 1)
    monkeypatch.setattr(check, "chunk_store", lambda build: store)
    checker = check.Checker(REF, CONTEXT)
    checker.check_build(b, False)
    assert checker.found["stored_chunks_differing"] == differing
    assert checker.found["chunk_digests_differing"] == 0
    assert checker.verdict() is (differing == 0)
    assert os.listdir(os.path.join(b.storage, "chunks")) == ["pack.bin"]


def test_the_programs_own_store_answers_and_a_deleted_entry_counts(tmp_path):
    b = _build(tmp_path / "s", 1, counted=True, kept=True)
    chunks, _ = write_outputs(b)
    check.chunk_store(b).delete(chunks[0][2])
    checker = check.Checker(REF, CONTEXT)
    checker.check_build(b, False)
    assert checker.found["stored_chunks_differing"] == 1
    assert checker.numbers()["stored_chunks_differing"] == {"value": 1,
                                                            "limit": 0}


def test_no_file_of_the_yardstick_spells_a_chunks_place():
    """The chunk store's layout is ``storage/cas.py``'s alone: nothing
    under ``perfbench/`` joins a digest's first characters into a path
    under ``chunks`` (a blob under ``layers`` is one file by design)."""
    sliced = re.compile(r"\[\s*:\s*\d+\s*\]")
    found = []
    for dirpath, _, files in os.walk(PERFBENCH):
        for fn in files:
            path = os.path.join(dirpath, fn)
            if not fn.endswith(".py") or path == os.path.abspath(__file__):
                continue
            with open(path, encoding="utf-8") as f:
                lines = f.read().splitlines()
            for i, line in enumerate(lines):
                near = " ".join(lines[max(0, i - 2):i + 3])
                if sliced.search(line) and "chunks" in near \
                        and "join(" in near:
                    found.append(f"{os.path.relpath(path, PERFBENCH)}:"
                                 f"{i + 1}: {line.strip()}")
    assert found == []
    with open(os.path.join(PERFBENCH, "pbharness", "check.py"),
              encoding="utf-8") as f:
        assert "CASDir" in f.read()


# -- the lane -----------------------------------------------------------


class Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


class FakeLane:
    """Builds that take ``build_s`` on the test's clock, with
    ``between_s`` of untimed work after each."""

    def __init__(self, clock, build_s, between_s):
        self.clock, self.build_s, self.between_s = clock, build_s, between_s
        self.builds = []

    def build(self, kind):
        self.clock.now += self.build_s
        b = _build("", len(self.builds) + 1)
        self.builds.append(b)
        return b

    def after_build(self, b, keep):
        b.kept = keep
        self.clock.now += self.between_s


class Draws:
    """``keep_rng`` with the draws the test names, then 0.9 (remove)."""

    def __init__(self, *values):
        self.values = list(values)
        self.drawn = 0

    def random(self):
        self.drawn += 1
        return self.values.pop(0) if self.values else 0.9


def parent_loop(lane, kind, deadline, keep_rng, clock):
    """``lane_main``'s ``started`` loop as PR 42 had it: two readings."""
    while clock() < deadline:
        b = lane.build(kind)
        b.counted = True
        last = clock() >= deadline
        lane.after_build(b, keep=last or keep_rng.random() < 0.5)


@pytest.mark.parametrize("draw", [0.1, 0.9])
def test_a_build_that_ends_just_inside_the_window_is_not_lost(draw):
    """The build ends 10 ms before the deadline and the untimed work
    after it crosses the deadline. The parent then ended the loop with
    that build unflagged, and removed it on one draw of two; now the
    same reading that did not flag it sends the lane round once more,
    and the build that ends past the deadline is kept whatever the
    draws say."""
    for loop, n_builds, last_kept in ((parent_loop, 1, draw < 0.5),
                                      (driver.drive_started, 2, True)):
        clock = Clock()
        lane = FakeLane(clock, build_s=0.99, between_s=0.5)
        loop(lane, "cold", clock.now + 1.0, Draws(draw, draw), clock)
        assert len(lane.builds) == n_builds
        assert all(b.counted for b in lane.builds)
        assert lane.builds[-1].kept is last_kept
    assert lane.builds[0].kept is (draw < 0.5)      # the seed's draw stands


@pytest.mark.parametrize("draw", [0.1, 0.9])
def test_the_last_build_is_kept_without_a_draw(draw):
    clock = Clock()
    lane = FakeLane(clock, build_s=0.4, between_s=0.05)
    rng = Draws(draw, draw, draw, draw)
    driver.drive_started(lane, "cold", clock.now + 1.0, rng, clock)
    assert [b.kept for b in lane.builds] == [draw < 0.5] * 2 + [True]
    assert rng.drawn == 2


def test_no_build_where_the_window_is_already_shut():
    clock = Clock()
    lane = FakeLane(clock, 1.0, 0.0)
    driver.drive_started(lane, "cold", clock.now, Draws(), clock)
    assert lane.builds == []


@pytest.mark.parametrize("seed", [0, 1, 7, 43, 2**31 + 5])
@pytest.mark.parametrize("between_s", [0.0, 0.31])
def test_a_seed_keeps_the_builds_the_parent_kept(seed, between_s):
    """One draw a build in the builds' order: over the builds both
    loops make, the same are kept, but that the last is kept always."""
    lanes = []
    for loop in (parent_loop, driver.drive_started):
        clock = Clock()
        lane = FakeLane(clock, build_s=0.83, between_s=between_s)
        loop(lane, "cold", clock.now + 9.0,
             np.random.default_rng([seed, 11]), clock)
        lanes.append(lane)
    parent, change = ([b.kept for b in lane.builds] for lane in lanes)
    assert len(change) - len(parent) in (0, 1)
    shared = len(parent) - 1 if len(change) == len(parent) else len(parent)
    assert change[:shared] == parent[:shared]
    assert change[-1] is True


def _lane(tmp_path, fresh):
    lane = driver._Lane.__new__(driver._Lane)
    lane.traffic = {"fresh_storage": fresh}
    lane.fresh = fresh
    return lane


@pytest.mark.parametrize("fresh, keep, kept", [
    (True, True, True), (True, False, False),
    (False, True, True), (False, False, True)])
def test_after_build_records_what_it_kept(tmp_path, fresh, keep, kept):
    b = _build(tmp_path / "s", 1)
    _standing(b.storage, "chunks_tree")
    _lane(tmp_path, fresh).after_build(b, keep)
    assert b.kept is kept
    assert os.path.isdir(b.storage) is kept


@pytest.mark.parametrize("remade, stands", [(1, False), (2, False),
                                            (5, True)])
def test_after_build_removes_again_what_was_made_under_the_walk(
        tmp_path, monkeypatch, remade, stands):
    """A worker's thread that creates a file while ``rmtree`` walks
    leaves the directory standing: up to three passes, then it is
    left (the check does not go by it)."""
    b = _build(tmp_path / "s", 1)
    _standing(b.storage, "one_file")
    real, passes = driver.shutil.rmtree, []

    def rmtree(path, ignore_errors=False):
        real(path, ignore_errors=ignore_errors)
        passes.append(path)
        if len(passes) <= remade:
            _standing(path, "one_file")
    monkeypatch.setattr(driver.shutil, "rmtree", rmtree)
    _lane(tmp_path, True).after_build(b, keep=False)
    assert b.kept is False
    assert os.path.isdir(b.storage) is stands
    assert len(passes) == min(remade + 1, 3)
