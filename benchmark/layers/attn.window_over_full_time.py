"""What a windowed attention layer costs beside a full one in the same
step: the time of a windowed layer's three flash calls (forward, dq,
dk-dv: the mean call of each) over a full layer's, from the traced
window.  The query-key pairs say 58,722,304 / 134,225,920 = 0.44 at T =
16,384 and a window of 4,096; the kernels walk tiles of 1,024, of which
70 of a windowed layer's and 136 of a full layer's are live, 0.51.
Nothing unless the trace holds both kinds of call."""

from benchmark.lib import manifest

roofline = manifest.load_named("layers", "kernel.banded_attention_roofline")


def read(run):
    seen = {}        # (windowed, kind) -> [seconds, calls]
    for kind, window, _work, seconds, count in roofline.calls(run):
        both = seen.setdefault((bool(window), kind), [0.0, 0.0])
        both[0] += seconds
        both[1] += count
    layer = lambda windowed: [s / n for (w, _), (s, n) in seen.items()
                              if w == windowed and n]
    full, windowed = layer(False), layer(True)
    if len(full) != 3 or len(windowed) != 3:
        return None
    return sum(windowed) / sum(full)
