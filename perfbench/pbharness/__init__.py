"""The benchmark's harness: everything a cell's run needs that is not
data (configs/, traffic/), a reader (readers/) or a reference
(reference/)."""
