"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 16 GB
of HBM at 819 GB/s per chip.  JAX reports that chip as "TPU v5 lite"
(chip run, PR 21).  A device that is not here is an error, not a default.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_of(device_kind):
    # The worker's log writes the kind with "_" for " ".
    kind = device_kind.replace("_", " ")
    if kind not in PEAKS:
        raise KeyError("no published peaks for device_kind %r; known: %s"
                       % (kind, sorted(PEAKS)))
    return PEAKS[kind]


def roofline_seconds(flops, hbm_bytes, device_kind):
    """(least seconds, which bound) for a call of that many operations
    and bytes."""
    peak = peaks_of(device_kind)
    by_flops = flops / peak["bf16_flops"]
    by_bytes = hbm_bytes / peak["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes else (
        by_bytes, "memory")
