"""The latent-attention kernels against their roofline: the least time
their calls in the traced window could take (a causal head at T (T + 1)
/ 2 query-key pairs, scores over ``qk_nope_head_dim + qk_rope_head_dim``
and values of ``v_head_dim``, the RoPE key read once a sequence;
operations over peak FLOP/s or bytes over peak bytes/s, whichever is
larger, per call) over the time they took in the trace; forward, dq and
dk-dv apart on stderr.  Calls are told by the names they carry
(``kernels/latent_attention.py``).  Nothing in a configuration that does
not list the kernel, or where the program names no such call (a parent,
or a run that fell back to the reference)."""

import sys

from benchmark.lib import kernels, manifest, peaks

KERNEL = "latent_attention"


def calls(run):
    """[(kind, (operations, bytes), seconds, calls)] of the kernel's
    calls in the trace."""
    t = run.trace
    if not t or KERNEL not in run.config.get("kernels", ()):
        return []
    module = manifest.load_named("kernels", KERNEL)
    heads = run.config.get("num_attention_heads")
    d_rope = run.config.get("qk_rope_head_dim", 0)
    out = []
    for hlo, (seconds, count) in t["custom_calls"].items():
        parsed = kernels.parse_call(hlo)
        call = module.classify(*parsed, hlo=hlo, heads=heads,
                               d_rope=d_rope) if parsed else None
        if call is not None:
            out.append((call[0], call[1], seconds, count))
    return out


def read(run):
    least = taken = 0.0
    groups = {}
    for kind, (flops, nbytes), seconds, count in calls(run):
        floor, bound = peaks.roofline_seconds(flops, nbytes,
                                              run.device["kind"])
        least += count * floor
        taken += seconds
        seen = groups.setdefault((kind, bound), [0.0, 0.0, 0.0])
        seen[0] += count * floor
        seen[1] += seconds
        seen[2] += count
    if not taken:
        return None
    for (kind, bound), (floor, seconds, count) in sorted(groups.items()):
        print("[benchmark] %s %s: %s-bound, least %.6f s of %.6f s taken "
              "(%.1f%%) in %.1f calls" % (
                  KERNEL, kind, bound, floor, seconds,
                  100 * floor / seconds, count),
              file=sys.stderr, flush=True)
    return 100.0 * least / taken
