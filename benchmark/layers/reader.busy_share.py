"""Share of the traced stretch the prefetch producer thread spent in
``edl.reader_batch``: read + decode + feed (+ prepare) of one batch.  At
100% the reader sets the pace; the rest of its time it waits on the full
queue, ahead of the trainer.  Batches made in under 100 us are not in the
source (benchmark/lib/spans.py)."""

from benchmark.lib import spans


def read(run):
    return spans.share(run, "edl.reader_batch")
