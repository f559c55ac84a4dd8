"""Snapshot engine: in-memory merged FS, layer diffing, whiteouts.

Reference capability: lib/snapshot/ (MemFS mem_fs.go:59-88, CopyOperation
copy_op.go:29-80, walk/evalSymlinks utils.go).
"""

from makisu_tpu.snapshot.copy_op import CopyOperation
from makisu_tpu.snapshot.layer import ContentEntry, Layer, WhiteoutEntry
from makisu_tpu.snapshot.memfs import FSDiff, MemFS, Node
from makisu_tpu.snapshot.walk import (
    WHITEOUT_META_PREFIX,
    WHITEOUT_PREFIX,
    TreeListing,
    create_tar_from_directory,
    eval_symlinks,
    tarinfo_from_stat,
    walk,
)

__all__ = [
    "CopyOperation", "ContentEntry", "FSDiff", "Layer", "MemFS", "Node",
    "TreeListing", "WhiteoutEntry", "WHITEOUT_META_PREFIX",
    "WHITEOUT_PREFIX",
    "create_tar_from_directory", "eval_symlinks", "tarinfo_from_stat",
    "walk",
]
