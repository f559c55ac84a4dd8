"""Files the chunk store's bulk ingest created a counted build: growth
of ``makisu_chunk_store_files_created_total`` over the window, its
three kinds summed (``segment`` and ``index``: a new segment pair where
none of the process's was free; ``loose``: an entry no index record can
name, staged and renamed as a file of its own), ÷ counted builds. Files,
not entries: a batch appended to a segment that is there creates none.
The program adds it once an ``index_layer`` pass and once a ``put``
(``cache/chunks.py``, from what ``storage/cas.py:write_many`` returns).
``None`` from a program without the series: it makes a file a chunk and
counts none of them."""
from pbharness import hostspans


def read(run):
    return hostspans.counter_per_build(
        run, "makisu_chunk_store_files_created_total")
