"""Milliseconds of the window's stalls that the training thread's own
phases explain: the sum of ``host_excess_ms`` over the ``worker stall:``
lines stamped inside the window (``loop.stalls_in_window``), i.e. how far
each stalled interval's time outside its fence lay over the quiet
fences'.  The rest of ``loop.stall_excess_ms`` waited on the device with
its work queued.  0.0 in a quiet run; nothing where the program does not
watch its fences."""

from benchmark.lib import manifest

window = manifest.load_named("layers", "loop.stalls_in_window")


def read(run):
    found = window.stalls(run)
    if found is None:
        return None
    return float(sum(stall.get("host_excess_ms", 0.0) for stall in found))
