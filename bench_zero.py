"""ZeRO-1 weight-update sharding benchmark (--zero1, worker/zero.py).

Measures the three claims of the sharded weight update on an N-device
data-parallel mesh:

  1. **memory** — per-device optimizer-state bytes, sharded vs
     replicated, from the live state's actual shard placement (the
     ~(N-1)/N reduction that is the point of ZeRO-1);
  2. **throughput** — steps/s, zero1 vs replicated, INTERLEAVED timed
     blocks (per-step K=1 and fused windows K=8) so machine-load drift
     lands on both legs equally; each block closes with a value fetch;
  3. **exactness** — same-seed losses bit-identical with zero1 on vs
     off at K=1 and K=8, and an in-process elastic churn drill: Adam
     moments bit-exact through a live N -> N/2 device-to-device
     re-partition, and a same-size world re-form mid-run continuing
     the no-churn trajectory bit-for-bit at equal step count.

Honest annotation: on CPU the collectives are loopback memcpy and the
jitted step shares cores with the host loop, so the throughput ratio
is a parity check (the acceptance gate is +/-5%), not the TPU story —
there, reduce-scatter + 1/N update + all-gather reclaims both memory
and update-compute time.  The JSON carries the platform.

Prints exactly one JSON line.
"""

import json
import os
import sys
import time

# CPU fallback gets a virtual 8-device mesh; inert for real TPU
# backends (the flag only affects the host platform).  Must be set
# before jax imports.
_FLAGS = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _FLAGS:
    os.environ["XLA_FLAGS"] = (
        _FLAGS + " --xla_force_host_platform_device_count=8"
    ).strip()


def _trainer(spec, mesh, batch_size, zero1, seed, accum=1):
    from elasticdl_tpu.worker.collective_trainer import CollectiveTrainer

    # batch_size here is rows per device; the trainer wants the rows
    # this process feeds a step.
    return CollectiveTrainer(
        spec, batch_size=batch_size * mesh.devices.size, mesh=mesh,
        rng_seed=seed, zero1=zero1, accum_steps=accum,
    )


def run_bench(blocks=5, steps_per_block=40, fused_steps=8,
              batch_size=8, bit_steps=40):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from elasticdl_tpu.models import mnist

    platform = jax.devices()[0].platform
    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(np.array(devices), ("data",))
    spec = mnist.model_spec(learning_rate=1e-3)
    xs, ys = mnist.synthetic_data(n=batch_size * n * 8, seed=0)
    per = batch_size * n
    data = [(xs[i * per:(i + 1) * per], ys[i * per:(i + 1) * per])
            for i in range(8)]

    # ---- 1. memory: live per-device optimizer-state bytes ----------------
    rep_t = _trainer(spec, mesh, batch_size, False, 0)
    z1_t = _trainer(spec, mesh, batch_size, True, 0)
    mem_rep = rep_t.zero1_report()
    mem_z1 = z1_t.zero1_report()
    reduction = 1.0 - (
        mem_z1["per_device_bytes"] / mem_rep["per_device_bytes"]
    )
    # The gate: >= (N-1)/N up to the irreducible replicated remainder
    # (Adam's scalar step count + pad tail; < 0.01% of the state here).
    target = (n - 1) / n
    memory_ok = mem_z1["per_device_bytes"] <= (
        mem_rep["per_device_bytes"] / n * 1.01
    )

    # ---- 3a. exactness: same-seed bit-identity, K=1 and K=8 --------------
    losses_rep = [float(rep_t.train_minibatch(*data[i % 8])[0])
                  for i in range(bit_steps)]
    losses_z1 = [float(z1_t.train_minibatch(*data[i % 8])[0])
                 for i in range(bit_steps)]
    bitwise_k1 = losses_rep == losses_z1
    max_diff_k1 = float(np.max(np.abs(
        np.asarray(losses_rep) - np.asarray(losses_z1)
    )))

    rep_w = _trainer(spec, mesh, batch_size, False, 1)
    z1_w = _trainer(spec, mesh, batch_size, True, 1)
    wl_rep, wl_z1 = [], []
    for w in range(3):
        pb = [rep_w.prepare_batch(*data[(w * fused_steps + i) % 8])
              for i in range(fused_steps)]
        pz = [z1_w.prepare_batch(*data[(w * fused_steps + i) % 8])
              for i in range(fused_steps)]
        wl_rep.append(np.asarray(
            rep_w.train_window(rep_w.stage_window(pb))[0]))
        wl_z1.append(np.asarray(
            z1_w.train_window(z1_w.stage_window(pz))[0]))
    bitwise_k8 = all(
        np.array_equal(a, b) for a, b in zip(wl_rep, wl_z1)
    )
    max_diff_k8 = float(max(
        np.max(np.abs(a - b)) for a, b in zip(wl_rep, wl_z1)
    ))

    # ---- 2. throughput: interleaved blocks -------------------------------
    def per_step_block(trainer, k0):
        t0 = time.perf_counter()
        for k in range(steps_per_block):
            loss, _ = trainer.train_minibatch(*data[(k0 + k) % 8])
        float(loss)  # fence: close the block with a value fetch
        return time.perf_counter() - t0

    def window_block(trainer, k0):
        t0 = time.perf_counter()
        losses = None
        for w in range(steps_per_block // fused_steps):
            prepared = [
                trainer.prepare_batch(
                    *data[(k0 + w * fused_steps + i) % 8]
                )
                for i in range(fused_steps)
            ]
            losses, _ = trainer.train_window(
                trainer.stage_window(prepared)
            )
        np.asarray(losses)  # fence
        return time.perf_counter() - t0

    # One untimed warm block per leg first (the box takes ~a minute to
    # reach steady state — page cache, thread pools, frequency), then
    # interleaved timed blocks with the LEG ORDER alternating per block
    # so any residual monotonic drift cancels instead of crediting
    # whichever leg runs second.
    per_step_block(rep_t, 0), per_step_block(z1_t, 0)
    window_block(rep_w, 0), window_block(z1_w, 0)
    pairs_k1, pairs_k8 = [], []
    for b in range(blocks):
        k0 = b * steps_per_block
        legs_k1 = [(rep_t, 0), (z1_t, 1)]
        legs_k8 = [(rep_w, 0), (z1_w, 1)]
        if b % 2:
            legs_k1.reverse()
            legs_k8.reverse()
        row = [None, None]
        for trainer, idx in legs_k1:
            row[idx] = round(per_step_block(trainer, k0) * 1000.0, 2)
        pairs_k1.append(row)
        row = [None, None]
        for trainer, idx in legs_k8:
            row[idx] = round(window_block(trainer, k0) * 1000.0, 2)
        pairs_k8.append(row)
    total_steps = blocks * steps_per_block

    def sps(pairs, idx):
        return total_steps / (sum(p[idx] for p in pairs) / 1000.0)

    def median_ratio(pairs):
        # Per-block replicated/zero1 time ratio, median over blocks:
        # robust to the load spikes a shared CI box injects into
        # individual blocks (each pair ran back-to-back, so a spike
        # hits both legs of ITS block roughly equally; the median
        # discards blocks where it didn't).
        ratios = sorted(p[0] / p[1] for p in pairs)
        mid = len(ratios) // 2
        if len(ratios) % 2:
            return ratios[mid]
        return (ratios[mid - 1] + ratios[mid]) / 2.0

    ratio_k1 = median_ratio(pairs_k1)
    ratio_k8 = median_ratio(pairs_k8)

    # ---- 3b. elastic churn: repartition + same-size re-form --------------
    churn = _trainer(spec, mesh, batch_size, True, 2)
    nochurn = _trainer(spec, mesh, batch_size, True, 2)
    ref_losses = [float(nochurn.train_minibatch(*data[i % 8])[0])
                  for i in range(10)]
    churn_losses = [float(churn.train_minibatch(*data[i % 8])[0])
                    for i in range(5)]
    t0 = time.perf_counter()
    churn.rebuild(mesh)  # same-size world re-form (peer replaced)
    reform_ms = (time.perf_counter() - t0) * 1000.0
    churn_losses += [float(churn.train_minibatch(*data[i % 8])[0])
                     for i in range(5, 10)]
    reform_bitwise = churn_losses == ref_losses

    moments_ok = None
    resize_ms = None
    if n >= 2:
        half = Mesh(np.array(devices[: n // 2]), ("data",))
        before = churn._opt_state_on_host()
        t0 = time.perf_counter()
        churn.rebuild(half)  # N -> N/2, live device-to-device
        resize_ms = (time.perf_counter() - t0) * 1000.0
        after = churn._opt_state_on_host()
        moments_ok = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree_util.tree_leaves(before),
                            jax.tree_util.tree_leaves(after))
        )
    counters = churn.timing.counters()

    return {
        "metric": "zero1_update_sharding",
        "value": round(mem_rep["per_device_bytes"]
                       / mem_z1["per_device_bytes"], 3),
        "unit": "x per-device optimizer-state bytes vs replicated "
                "(%d devices)" % n,
        "vs_baseline": None,
        "detail": {
            "platform": platform,
            "num_devices": n,
            "memory": {
                "replicated_bytes_per_device":
                    mem_rep["per_device_bytes"],
                "zero1_bytes_per_device": mem_z1["per_device_bytes"],
                "reduction": round(reduction, 6),
                "target_reduction": round(target, 6),
                "meets_target_within_1pct": memory_ok,
                "padding_bytes": mem_z1["padding_bytes"],
                "scalar_leaves_replicated":
                    mem_z1["scalar_leaves_replicated"],
            },
            "throughput": {
                "per_step_ratio_zero1_vs_replicated":
                    round(ratio_k1, 4),
                "fused_k%d_ratio_zero1_vs_replicated" % fused_steps:
                    round(ratio_k8, 4),
                "ratio_is": "median over per-block steps/s ratios "
                            "(load-spike robust)",
                "aggregate_per_step_ratio": round(
                    sps(pairs_k1, 1) / sps(pairs_k1, 0), 4),
                "aggregate_fused_ratio": round(
                    sps(pairs_k8, 1) / sps(pairs_k8, 0), 4),
                # One-sided gate: zero1 must not cost steps/s (>= 0.95
                # of replicated).  Being FASTER is expected — the
                # replicated path redundantly applies the full update
                # on all N devices, the sharded path does 1/N each.
                "within_5pct": ratio_k1 >= 0.95 and ratio_k8 >= 0.95,
                "samples": {
                    "per_step_pairs": pairs_k1,
                    "fused_pairs": pairs_k8,
                    "format": "[replicated_ms, zero1_ms] per "
                              "interleaved block of %d steps"
                              % steps_per_block,
                },
            },
            "exactness": {
                "bitwise_k1": bitwise_k1,
                "bitwise_k%d" % fused_steps: bitwise_k8,
                "loss_max_abs_diff_k1": max_diff_k1,
                "loss_max_abs_diff_k%d" % fused_steps: max_diff_k8,
                "bit_steps": bit_steps,
            },
            "elastic": {
                "same_size_reform_trajectory_bitwise": reform_bitwise,
                "reform_ms": round(reform_ms, 1),
                "resize_to_half_moments_bitwise": moments_ok,
                "resize_ms": round(resize_ms, 1)
                if resize_ms is not None else None,
                "zero1_reshard_bytes":
                    counters.get("zero1_reshard_bytes", 0),
                "host_fallbacks":
                    counters.get("reshard_host_fallbacks", 0),
            },
            "timing_zero1": z1_t.timing.summary().get("zero1", {}),
            "note": (
                "CPU capture: collectives are loopback memcpy, so the "
                "throughput ratio is a parity check; the TPU regime "
                "(reduce-scatter + 1/N update + all-gather over ICI) "
                "is where the update-compute win lands"
                if platform == "cpu" else
                "TPU capture: sharded update over ICI"
            ),
            "device": {
                "platform": platform,
                "device_kind": jax.devices()[0].device_kind,
                "num_devices": len(jax.devices()),
                "jax_version": jax.__version__,
            },
            "env": {k: v for k, v in sorted(os.environ.items())
                    if k.startswith(("ELASTICDL_", "JAX_", "XLA_"))},
        },
    }


def main():
    t0 = time.monotonic()
    result = run_bench()
    result["detail"]["bench_wall_secs"] = round(
        time.monotonic() - t0, 1
    )
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
