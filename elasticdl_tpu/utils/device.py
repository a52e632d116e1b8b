"""What every compiling process entry does with the device it was given.

Two things, each in one place so entries cannot drift apart:

 - ``place_compile_cache`` puts JAX's persistent compilation cache where
   the operator said (``JAX_COMPILATION_CACHE_DIR``) or, unset, at a fixed
   path inside the checkout.  The path is part of the cache key's
   environment: a directory that moves never hits, so no tempfile, pid or
   timestamp path — a relaunched worker must find what its predecessor
   compiled.
 - ``device_report`` says which backend the process actually got, so a
   JAX-free parent (the master's log reader, ``chip_smoke.py``) can
   check it instead of trusting the environment.
"""

import os

import jax

CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def place_compile_cache():
    """Returns the directory the persistent compile cache lives in.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already read it and
    nothing is written to the config; otherwise ``<checkout>/.jax_cache``.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def device_report():
    """Initializes the backend and returns what it is, as plain values.
    ``worker/main.py`` adds what its line says of the kernels."""
    devices = jax.devices()
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "local_devices": jax.local_device_count(),
        "global_devices": len(devices),
        "device_ids": [d.id for d in jax.local_devices()],
        # The chips the launcher restricted this process to
        # (master/worker_manager.chip_env_for_slot); every process on a
        # host numbers its own devices from 0, so this is what tells two
        # workers' chips apart.
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS", "all"),
        # Per local device; 0 where the backend keeps no statistics.
        # On the TPU a program's temporaries (activations, logits) are
        # not in ``peak_bytes_in_use`` but in ``peak_bytes_reserved``
        # (chip run, PR 23): the peak is the sum of the two.
        "peak_bytes_in_use": [
            s.get("peak_bytes_in_use", 0) for s in stats],
        "peak_bytes_reserved": [
            s.get("peak_bytes_reserved", 0) for s in stats],
    }


def format_device_report(report):
    """One ``key=value`` line; the format ``chip_smoke.py`` parses."""
    return " ".join(
        "%s=%s" % (
            key,
            ",".join(str(v) for v in value)
            if isinstance(value, list) else
            str(value).replace(" ", "_"),
        )
        for key, value in report.items()
    )


# Published bf16 peaks, keyed by ``device_kind`` as JAX reports it.  A
# device that is not here is an error for any bench that divides by a
# peak — never a default.
PEAK_BF16_FLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip;
    # JAX reports that chip as "TPU v5 lite" (my chip run, PR 21).
    "TPU v5 lite": 197e12,
}


def require_tpu():
    """For measurement paths: the device report, or SystemExit when the
    backend is not a TPU whose peak is known.  A CPU timing is never
    written under a per-chip unit."""
    report = device_report()
    if report["platform"] != "tpu":
        raise SystemExit(
            "no TPU: jax.devices()[0].platform is %r; this measures the "
            "chip and does not fall back" % report["platform"]
        )
    if report["device_kind"] not in PEAK_BF16_FLOPS:
        raise SystemExit(
            "device_kind %r has no entry in PEAK_BF16_FLOPS "
            "(elasticdl_tpu/utils/device.py); add it with its source"
            % report["device_kind"]
        )
    return report
