"""Share of the traced stretch the training thread spent in
``edl.data_wait``, waiting on the prefetch queue for its next batch (or
for the stream's end).  Waits under 100 us, a batch that was ready, are
not in the source (benchmark/lib/spans.py): 0 means "none that long"."""

from benchmark.lib import spans


def read(run):
    return spans.share(run, "edl.data_wait")
