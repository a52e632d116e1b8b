"""What a windowed attention layer costs beside a full one in the same
step: the time of a windowed layer's flash calls (the mean call of each
kind) over a full layer's, over whatever kinds BOTH have in the traced
window: forward and the fused backward today, forward, dq and dk-dv on a
path that still splits (``kernels/flash_attention.py``).  The query-key
pairs say 58,722,304 / 134,225,920 = 0.44 at T = 16,384 and a window of
4,096; the kernels walk tiles of 1,024, of which 70 of a windowed
layer's and 136 of a full layer's are live, 0.51.  Nothing unless both
kinds of layer have the same, non-empty set of kinds of call."""

from benchmark.lib import manifest

roofline = manifest.load_named("layers", "kernel.banded_attention_roofline")


def read(run):
    seen = {}        # windowed -> kind -> [seconds, calls]
    for (kind, _work, window), seconds, count in roofline.calls(run):
        both = seen.setdefault(bool(window), {}).setdefault(
            kind, [0.0, 0.0])
        both[0] += seconds
        both[1] += count
    full, windowed = seen.get(False, {}), seen.get(True, {})
    if not full or set(full) != set(windowed):
        return None
    layer = lambda kinds: sum(s / n for s, n in kinds.values())
    return layer(windowed) / layer(full)
