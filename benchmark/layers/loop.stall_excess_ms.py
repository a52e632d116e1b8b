"""Milliseconds the window's stalled fences took beyond their steps at
the quiet median: the sum of ``excess_ms`` over the ``worker stall:`` lines
stamped inside the window (``loop.stalls_in_window``).  Over the window's
length it is ``loop.stall_share`` by the program's own word.  0.0 in a
quiet run; nothing where the program does not watch its fences."""

from benchmark.lib import manifest

window = manifest.load_named("layers", "loop.stalls_in_window")


def read(run):
    found = window.stalls(run)
    if found is None:
        return None
    return float(sum(stall.get("excess_ms", 0.0) for stall in found))
