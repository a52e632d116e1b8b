"""Headline benchmark: ResNet-50 training throughput (images/sec/chip).

Reference baseline: 145 images/s on 1x NVIDIA P100 for ResNet-50/ImageNet
(docs/benchmark/ftlib_benchmark.md:121; see BASELINE.md).  This measures
the same model shape (ResNet-50, 224x224x3, 1000 classes) running the
framework's jitted train step in bfloat16 on one TPU chip, with the batch
resident on device (synthetic data; the data plane is benchmarked
separately).

One in-process measurement.  It measures the chip: with no ``tpu``
platform, or a ``device_kind`` whose peak is not in
``elasticdl_tpu.utils.device.PEAK_BF16_FLOPS``, it exits non-zero and
prints no number — a CPU timing is never written under a per-chip unit.
The persistent compile cache sits where ``JAX_COMPILATION_CACHE_DIR``
says, else ``<checkout>/.jax_cache`` (``place_compile_cache``).

Prints exactly one JSON line:
  {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N}

``--compare-fused`` is a different measurement (fused-step driver vs the
per-step loop, a ratio of steps/s at small per-step compute) that is
meaningful on any backend and names the platform it ran on.
"""

import json
import os
import sys
import time

BASELINE_IMAGES_PER_SEC = 145.0  # ftlib_benchmark.md:121 (1x P100)

# Fwd+bwd FLOPs per image for ResNet-50 @224 (~3x the 4.1 GFLOP forward).
# An estimate — MFU is reported as context, not a measured counter.
FLOPS_PER_IMAGE = 12.3e9


def _mark(phase):
    """Progress marker on stderr: a caller that times this process out
    (bench_kernels.py) can say where it stopped."""
    print("BENCHMARK-MARK %s" % phase, file=sys.stderr, flush=True)


def run_bench(batch_size=128, warmup=3, iters=20, fused_steps=0):
    _mark("imports_start")
    import jax
    import numpy as np

    from elasticdl_tpu.models import resnet
    from elasticdl_tpu.utils.device import (
        PEAK_BF16_FLOPS,
        place_compile_cache,
        require_tpu,
    )
    from elasticdl_tpu.worker.collective_trainer import CollectiveTrainer

    place_compile_cache()
    _mark("imports_done")
    device = require_tpu()  # exits non-zero on any other backend
    platform = device["platform"]
    _mark("devices_ok:%s" % device["device_kind"])

    variant = (
        "resnet50_s2d"
        if os.environ.get("ELASTICDL_RESNET_S2D") == "1"
        else "resnet50"
    )
    spec = resnet.model_spec(variant=variant, num_classes=1000,
                             image_size=224, learning_rate=0.1)
    trainer = CollectiveTrainer(
        spec, batch_size=batch_size, use_bf16_compute=True
    )
    rng = np.random.RandomState(0)
    xs = jax.device_put(
        rng.rand(batch_size, 224, 224, 3).astype(np.float32)
    )
    ys = jax.device_put(
        rng.randint(0, 1000, size=batch_size).astype(np.int32)
    )
    ws = jax.device_put(np.ones((batch_size,), np.float32))

    params, opt_state = trainer._params, trainer._opt_state
    if fused_steps > 1:
        # Steps-per-loop: K optimizer steps in ONE XLA program, so host
        # dispatch amortizes over K.
        step = trainer.build_fused_steps(fused_steps)
        iters = max(2, iters // fused_steps)
    else:
        step = trainer._train_step
    _mark("compile_start")
    compile_start = time.perf_counter()
    params, opt_state, loss = step(params, opt_state, xs, ys, ws)
    float(loss)  # fence
    compile_secs = time.perf_counter() - compile_start
    _mark("compile_done:%.1fs" % compile_secs)
    # A cache hit makes the first call cheap; skip further warmup then.
    remaining_warmup = 1 if compile_secs < 5.0 else warmup - 1
    for _ in range(remaining_warmup):
        params, opt_state, loss = step(params, opt_state, xs, ys, ws)
    float(loss)  # fence
    _mark("warmup_done")

    # Audit-grade samples: time BLOCKS of iterations, each closed by a
    # value fetch.  (On the TPU backend block_until_ready fences just as
    # well — my chip run, PR 21 — the fetch is kept because the loss is
    # reported.)  A fence per iteration would serialize dispatch with
    # execution; per-block ones cost one fetch per `block` steps.
    block = 5
    blocks = []  # [iters_in_block, ms] — a trailing partial block
    # records its true iteration count, not the nominal block size
    start = time.perf_counter()
    t_block = start
    done_at_fence = 0
    for k in range(iters):
        params, opt_state, loss = step(params, opt_state, xs, ys, ws)
        if (k + 1) % block == 0 or k == iters - 1:
            float(loss)  # fence: close the block with a value fetch
            now = time.perf_counter()
            blocks.append([k + 1 - done_at_fence,
                           round((now - t_block) * 1000.0, 2)])
            t_block, done_at_fence = now, k + 1
            _mark("iter:%d/%d" % (k + 1, iters))
    last_loss = float(loss)
    elapsed = time.perf_counter() - start
    _mark("measured")

    steps_done = iters * max(1, fused_steps)
    images_per_sec = batch_size * steps_done / elapsed
    ms_per_step = 1000.0 * elapsed / steps_done
    peak = PEAK_BF16_FLOPS[device["device_kind"]]
    mfu = round(images_per_sec * FLOPS_PER_IMAGE / peak, 4)
    return {
        "metric": "resnet50_train_throughput",
        "value": round(images_per_sec, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(images_per_sec / BASELINE_IMAGES_PER_SEC, 3),
        "detail": {
            "platform": platform,
            "variant": variant,
            "batch_size": batch_size,
            "iters": iters,
            "fused_steps": fused_steps,
            "ms_per_step": round(ms_per_step, 2),
            "mfu_estimate": mfu,
            "compile_secs": round(compile_secs, 1),
            "last_loss": last_loss,
            "baseline": "145 img/s ResNet-50/ImageNet 1xP100 "
                        "(ftlib_benchmark.md:121)",
            # Provenance: raw per-block timings, device fingerprint,
            # and env snapshot so a capture is auditable.
            "samples": {"blocks": blocks,
                        "format": "[iters, ms] per block"},
            "device": _device_fingerprint(jax),
            "env": _env_snapshot(),
        },
    }


def run_fused_compare(fused_steps=8, blocks=5, steps_per_block=40,
                      batch_size=64):
    """Fused-step driver vs the per-step hot loop at SMALL per-step
    compute (MNIST MLP) — the regime where host dispatch and the
    per-step loss sync dominate, i.e. what the worker's fused driver
    (--fused_steps, worker/fused_driver.py) exists to amortize.

    Methodology (as in bench_ps_wire): INTERLEAVED timed blocks —
    per-step then fused, alternating — so machine-load drift lands on
    both legs equally; each leg's block closes with a value fetch.  The
    per-step leg reproduces the seed loop exactly: one dispatch + one
    ``float(loss)`` sync per step.  The fused leg runs K steps per
    dispatch with losses fetched ONCE per block (the report cadence).

    Honest annotation: on CPU the jitted step and the host loop share
    the same cores, so the measured speedup UNDERSTATES what the TPU
    path gains (there, dispatch+sync is idle device time the fused
    window reclaims).  The JSON carries the platform.

    Prints one JSON line; also reports a same-seed loss-equivalence
    check (fresh trainer pair, identical batch sequence).
    """
    _mark("imports_start")
    import jax
    import numpy as np

    from elasticdl_tpu.models import mnist
    from elasticdl_tpu.utils.device import place_compile_cache
    from elasticdl_tpu.worker.collective_trainer import CollectiveTrainer

    place_compile_cache()
    platform = jax.devices()[0].platform
    _mark("devices_ok:%s" % platform)
    assert steps_per_block % fused_steps == 0, "block must fill windows"

    spec = mnist.model_spec(learning_rate=1e-3)
    xs, ys = mnist.synthetic_data(n=batch_size * 8, seed=0)
    data = [
        (xs[i * batch_size:(i + 1) * batch_size],
         ys[i * batch_size:(i + 1) * batch_size])
        for i in range(8)
    ]

    # Same-seed equivalence gate: identical batch sequence through both
    # paths from identical init — the acceptance criterion's
    # bit-tolerance check, measured, not assumed.
    seq = CollectiveTrainer(spec, batch_size=batch_size, rng_seed=0)
    win = CollectiveTrainer(spec, batch_size=batch_size, rng_seed=0)
    seq_losses = [float(seq.train_minibatch(*data[i % 8])[0])
                  for i in range(8)]
    prepared = [win.prepare_batch(*data[i % 8]) for i in range(8)]
    win_losses = np.asarray(
        win.train_window(win.stage_window(prepared))[0]
    )
    loss_max_abs_diff = float(
        np.max(np.abs(np.asarray(seq_losses) - win_losses))
    )
    _mark("equivalence_done")

    per_step = CollectiveTrainer(spec, batch_size=batch_size, rng_seed=1)
    fused = CollectiveTrainer(spec, batch_size=batch_size, rng_seed=1)
    # warm both programs (compile outside the timed region)
    float(per_step.train_minibatch(*data[0])[0])
    warm = [fused.prepare_batch(*data[i % 8]) for i in range(fused_steps)]
    np.asarray(fused.train_window(fused.stage_window(warm))[0])
    _mark("warmup_done")

    def per_step_block(k0):
        t0 = time.perf_counter()
        for k in range(steps_per_block):
            loss, _ = per_step.train_minibatch(*data[(k0 + k) % 8])
            float(loss)          # the seed loop's per-step sync
        return time.perf_counter() - t0

    def fused_block(k0):
        t0 = time.perf_counter()
        losses = None
        for w in range(steps_per_block // fused_steps):
            prepared = [
                fused.prepare_batch(
                    *data[(k0 + w * fused_steps + i) % 8]
                )
                for i in range(fused_steps)
            ]
            losses, _ = fused.train_window(fused.stage_window(prepared))
        np.asarray(losses)       # ONE fetch per block (report cadence)
        return time.perf_counter() - t0

    pairs = []  # [per_step_ms, fused_ms] per interleaved block
    for b in range(blocks):
        k0 = b * steps_per_block
        pairs.append([
            round(per_step_block(k0) * 1000.0, 2),
            round(fused_block(k0) * 1000.0, 2),
        ])
    _mark("measured")
    per_step_sps = (
        blocks * steps_per_block / (sum(p[0] for p in pairs) / 1000.0)
    )
    fused_sps = (
        blocks * steps_per_block / (sum(p[1] for p in pairs) / 1000.0)
    )
    return {
        "metric": "fused_step_driver_speedup",
        "value": round(fused_sps / per_step_sps, 3),
        "unit": "x steps/sec (K=%d fused dispatch + async loss vs "
                "per-step loop)" % fused_steps,
        "vs_baseline": None,
        "detail": {
            "platform": platform,
            "per_step_steps_per_sec": round(per_step_sps, 1),
            "fused_steps_per_sec": round(fused_sps, 1),
            "fused_steps": fused_steps,
            "batch_size": batch_size,
            "loss_max_abs_diff_same_seed": loss_max_abs_diff,
            "samples": {"pairs": pairs,
                        "format": "[per_step_ms, fused_ms] per "
                                  "interleaved block of %d steps"
                                  % steps_per_block},
            "note": "CPU legs share cores between the jitted step and "
                    "the host loop, understating the gain; on TPU the "
                    "amortized dispatch+sync is reclaimed idle device "
                    "time" if platform == "cpu" else
                    "TPU capture: dispatch+sync amortized over K "
                    "device steps",
            "device": _device_fingerprint(jax),
            "env": _env_snapshot(),
        },
    }


def _device_fingerprint(jax_mod):
    dev = jax_mod.devices()[0]
    return {
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", None),
        "num_devices": len(jax_mod.devices()),
        "jax_version": jax_mod.__version__,
    }


def _env_snapshot():
    """The env knobs that can change what this benchmark measures."""
    return {
        k: v for k, v in sorted(os.environ.items())
        if k.startswith(("ELASTICDL_", "JAX_", "XLA_"))
    }


def _flag(name, default):
    return (int(sys.argv[sys.argv.index(name) + 1])
            if name in sys.argv else default)


if __name__ == "__main__":
    if "--compare-fused" in sys.argv:
        print(json.dumps(
            run_fused_compare(fused_steps=_flag("--fused", 8))))
    else:
        print(json.dumps(run_bench(batch_size=_flag("--batch", 128),
                                   fused_steps=_flag("--fused", 0))))
