"""Decide ``correct``: after the window, untimed, a sample of the
counted builds the lane kept, drawn from the seed (the last one always
in it), is held against the configuration's reference. What is in the
sample follows from the lane's own record (``Build.kept``), never from
what the file system holds: a kept build whose outputs are gone is
drawn and counts ``missing_outputs``. For each layer of a sampled
build:

(a) the chunk list's cut points equal the reference's sequential gear
    scan over the stored tar stream;
(b) every chunk fingerprint the build recorded equals hashlib's SHA-256
    of those bytes, and the chunk store, asked through the owner of its
    layout (``makisu_tpu.storage.cas.CASDir``), holds those bytes under
    it;
(c) the blob's and the tar's digests, as manifest, image config and
    cache entry state them, equal hashlib's over the stored blob;
(d) for the last build of a lane (the tree on disk is then the tree it
    built): the layer's tar members equal the tree's files, names,
    sizes, modes, times and contents.

Every number compared is a count of differences with the limit 0."""

from __future__ import annotations

import json
import os

import numpy as np

LIMITS = {"missing_outputs": 0, "cut_points_differing": 0,
          "chunk_digests_differing": 0, "stored_chunks_differing": 0,
          "blob_digests_differing": 0, "tar_members_differing": 0}


_TAGS_KEPT = 12
_MEMO_BYTES = 160 << 20


def manifest_path(build) -> str:
    repo, tag = build.tag.rsplit(":", 1)
    return os.path.join(build.storage, "manifests", repo, tag + ".json")


def chunk_store(build):
    """The build's chunk store as a bare directory: no store is opened,
    nothing is created or listed. Where a chunk lies in it is the
    program's to say."""
    from makisu_tpu.storage.cas import CASDir
    return CASDir(os.path.join(build.storage, "chunks"))


class Checker:
    def __init__(self, reference, context: dict) -> None:
        self.ref = reference
        self.context = context
        self.found = dict.fromkeys(LIMITS, 0)
        self.checked = {"builds": 0, "layers": 0, "chunks": 0, "members": 0}
        self.sampled: list[str] = []
        self._layer_memo: dict = {}
        self.notes: list[str] = []

    def _manifest(self, build):
        """The manifest a build's tag names, the image config it names,
        and the storage's cache entries by blob digest."""
        with open(manifest_path(build), encoding="utf-8") as f:
            manifest = json.load(f)
        hexd = manifest["config"]["digest"].split(":", 1)[1]
        with open(os.path.join(build.storage, "layers", hexd[:2], hexd),
                  encoding="utf-8") as f:
            config = json.load(f)
        with open(os.path.join(build.storage, "cache_key_value.json"),
                  encoding="utf-8") as f:
            kv = json.load(f)
        entries = {}
        for value, _stamp in kv.values():
            if isinstance(value, str) and value.startswith("{"):
                entry = json.loads(value)
                if "gzip" in entry:
                    entries[entry["gzip"]] = entry
        return manifest, config, entries

    def check_build(self, build, tree_is_current: bool) -> None:
        self.checked["builds"] += 1
        self.sampled.append(build.tag)
        try:
            manifest, config, entries = self._manifest(build)
        except (OSError, ValueError, KeyError) as e:
            self.found["missing_outputs"] += 1
            self.notes.append(f"{build.tag}: no readable manifest: {e!r}")
            return
        layers = manifest["layers"]
        diff_ids = config["rootfs"]["diff_ids"]
        if len(layers) != len(self.context["layers"]) \
                or len(diff_ids) != len(layers):
            self.found["missing_outputs"] += 1
            return
        for spec, layer, diff_id in zip(self.context["layers"], layers,
                                        diff_ids):
            entry = entries.get(layer["digest"])
            if entry is None:
                self.found["missing_outputs"] += 1
                self.notes.append(f"{build.tag}: no cache entry names "
                                  f"layer {layer['digest']}")
                continue
            self._check_layer(build, spec, layer, diff_id, entry,
                              tree_is_current)

    def _check_layer(self, build, spec, layer, diff_id, entry,
                     tree_is_current: bool) -> None:
        ref = self.ref
        key = (build.storage, layer["digest"])
        hexd = layer["digest"].split(":", 1)[1]
        blob = os.path.join(build.storage, "layers", hexd[:2], hexd)
        if key in self._layer_memo:
            tar = self._layer_memo[key]
        else:
            self.checked["layers"] += 1
            try:
                tar = ref.inflate(blob)
            except (OSError, ValueError):
                self.found["missing_outputs"] += 1
                return
            # (c)
            self.found["blob_digests_differing"] += sum((
                "sha256:" + ref.file_sha256_hex(blob) != layer["digest"],
                os.path.getsize(blob) != layer["size"],
                "sha256:" + ref.sha256_hex(tar) != diff_id,
                entry["tar"] != diff_id,
                entry["size"] != layer["size"]))
            chunks = entry.get("chunks") or []
            # (a)
            ends = [off + n for off, n, _ in chunks]
            starts = [off for off, _, _ in chunks]
            want = ref.cut_points(tar)
            if ends != want or starts != [0] + want[:-1]:
                self.found["cut_points_differing"] += max(
                    len(set(ends) ^ set(want)), 1)
            # (b)
            view = memoryview(tar)
            store = chunk_store(build)
            for off, n, hexdigest in chunks:
                self.checked["chunks"] += 1
                piece = view[off:off + n]
                if ref.sha256_hex(piece) != hexdigest:
                    self.found["chunk_digests_differing"] += 1
                try:
                    stored = store.read(hexdigest)
                except Exception as e:  # noqa: BLE001 - whatever the
                    # owner raises for an entry it does not hold
                    stored = None
                    self.notes.append(f"{build.tag}: chunk {hexdigest}: "
                                      f"{e!r}")
                if stored != piece:
                    self.found["stored_chunks_differing"] += 1
            # A layer that several sampled builds share (the edit cell's
            # lower layer, an unchanged rebuild's both) is looked at once.
            self._layer_memo[key] = tar
            while sum(map(len, self._layer_memo.values())) > _MEMO_BYTES:
                self._layer_memo.pop(next(iter(self._layer_memo)))
        if tree_is_current:
            # (d)
            got = {k: v for k, v in ref.tar_members(tar).items()
                   if v[0] == ref.REGTYPE}
            want = ref.tree_members(build.context, spec["dir"], spec["dest"])
            self.checked["members"] += len(want)
            self.found["tar_members_differing"] += len(
                set(got.items()) ^ set(want.items()))

    def verdict(self) -> bool:
        return all(self.found[k] <= LIMITS[k] for k in LIMITS)

    def numbers(self) -> dict:
        """Each number compared beside its limit, and how much was
        looked at: the result line's ``check``."""
        out = {k: {"value": self.found[k], "limit": limit}
               for k, limit in LIMITS.items()}
        out["checked"] = dict(self.checked)
        out["sampled"] = list(self.sampled)
        return out

    def lines(self) -> list[str]:
        """What was looked at, what was found wanting, and last each
        number compared beside its limit."""
        out = ["check: " + ", ".join(f"{v} {k}"
                                     for k, v in self.checked.items())
               + ": " + " ".join(self.sampled)]
        out += [f"check: {note}" for note in self.notes[:8]]
        return out + [f"check: {k} {self.found[k]} (limit {limit})"
                      for k, limit in LIMITS.items()]


def sample(run, rng: np.random.Generator, wanted: int) -> list:
    """(build, tree_is_current) pairs: ``wanted`` counted builds drawn
    from the seed among those the lane kept, the last counted build
    always among them, and the last build of each lane drawn (its tree
    on disk is the tree it built)."""
    newest = {}
    for b in run.builds:
        newest[b.lane] = max(newest.get(b.lane, 0), b.index)
    # The program's manifest store keeps a storage's 16 newest tags, so
    # only a lane's last dozen builds can still be looked up.
    have = [b for b in run.counted if b.ok and b.kept
            and newest[b.lane] - b.index < _TAGS_KEPT]
    if not have:
        return []
    picked = {id(have[-1]): have[-1]}
    order = rng.permutation(len(have))
    for i in order:
        if len(picked) >= wanted:
            break
        picked[id(have[i])] = have[i]
    last_of_lane = {}
    for b in sorted(run.builds, key=lambda b: b.index):
        if b.ok:
            last_of_lane[b.lane] = b
    out = []
    for b in picked.values():
        last = last_of_lane[b.lane]
        # A cold mix builds each context once, so every kept build's
        # tree is current; otherwise only the lane's last build's is.
        fresh = run.cell.traffic.get("fresh_storage", False)
        out.append((b, fresh or b is last))
        if not fresh and b is not last and last.kept:
            out.append((last, True))
    return out
