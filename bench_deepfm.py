"""DeepFM steps/sec over the full parameter-server path.

The sparse-CTR benchmark named in BASELINE.json (the reference's async-PS
benchmark role, docs/benchmark/report_cn.md): a worker trains DeepFM
through 2 real PS shard subprocesses — gRPC push/pull, tensor codec,
id-mod-N sharding, native C++ optimizer kernels — end to end.  Each
"step" is one minibatch: pull dense params, pull unique embedding rows,
jitted fwd/bwd, push dense+sparse gradients.

The reference publishes no absolute DeepFM steps/sec (report_cn is a
scaling study), so ``vs_baseline`` is null; the absolute number and its
breakdown are the artifact.

Default mode prints exactly one JSON line (single worker).

``--scale`` runs the multi-worker concurrency study (VERDICT r3 #3):
N async worker processes hammer the same PS shards; reports aggregate
examples/s per worker count plus per-phase worker timings.  NOTE this
image pins the whole job — every worker, every PS shard — to ONE cpu
core (nproc=1), so aggregate throughput CANNOT rise with workers here;
what the study shows is (a) correctness and stability under concurrent
pushes, (b) no serialization collapse (aggregate stays ~flat while per-
worker RPC latency absorbs the queueing), and (c) the measured PS
service cost per step, which is what determines workers/shard capacity
on real multi-core hosts (reference analog: the Go PS's 64-stream
server, go/pkg/ps/server.go:233-253).
"""

import json
import os
import subprocess
import sys
import time

# A host-side CPU bench: the PS path is numpy + C++ kernels + gRPC and
# the worker's jitted step is tiny.  Pinned to the CPU so that it takes
# no chip on a TPU host; its numbers are host numbers, not chip numbers.
os.environ["JAX_PLATFORMS"] = "cpu"


def run_bench(num_ps=2, batch_size=512, vocab_size=100_000,
              num_fields=10, embedding_dim=8, warmup=5, iters=50):
    import jax
    import numpy as np

    from elasticdl_tpu.models import deepfm
    from elasticdl_tpu.utils import grpc_utils
    from elasticdl_tpu.worker.ps_client import PSClient
    from elasticdl_tpu.worker.ps_trainer import ParameterServerTrainer

    ports, procs = _start_ps(num_ps)
    try:
        channels = []
        for port in ports:
            ch = grpc_utils.build_channel("localhost:%d" % port)
            grpc_utils.wait_for_channel_ready(ch, timeout=30)
            channels.append(ch)
        client = PSClient(channels)

        spec = deepfm.model_spec(
            num_fields=num_fields, vocab_size=vocab_size,
            embedding_dim=embedding_dim,
        )
        trainer = ParameterServerTrainer(
            spec, client, batch_size=batch_size, get_model_steps=1
        )
        dense, ids, labels = deepfm.synthetic_data(
            n=batch_size * 8, num_fields=num_fields,
            vocab_size=vocab_size, seed=0,
        )
        batches = []
        for s in range(0, len(labels), batch_size):
            records = [
                (dense[j], ids[j], labels[j])
                for j in range(s, s + batch_size)
            ]
            batches.append(spec.feed(records))

        for k in range(warmup):
            trainer.train_minibatch(*batches[k % len(batches)])
        start = time.perf_counter()
        for k in range(iters):
            loss, version = trainer.train_minibatch(
                *batches[k % len(batches)]
            )
        elapsed = time.perf_counter() - start

        steps_per_sec = iters / elapsed
        platform = jax.devices()[0].platform
        return {
            "metric": "deepfm_ps_steps_per_sec",
            "value": round(steps_per_sec, 2),
            "unit": "steps/sec",
            "vs_baseline": None,
            "detail": {
                "platform": platform,
                "num_ps": num_ps,
                "batch_size": batch_size,
                "vocab_size": vocab_size,
                "num_fields": num_fields,
                "embedding_dim": embedding_dim,
                "examples_per_sec": round(steps_per_sec * batch_size, 1),
                "ms_per_step": round(1000.0 * elapsed / iters, 2),
                "last_loss": float(loss),
                "ps_version": int(version),
                "baseline": "reference publishes no absolute DeepFM "
                            "steps/sec (report_cn.md is a scaling "
                            "study)",
            },
        }
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()


def _start_ps(num_ps):
    """Spawn num_ps PS shard subprocesses; returns (ports, procs)."""
    from elasticdl_tpu.utils import grpc_utils

    ports = [grpc_utils.find_free_port() for _ in range(num_ps)]
    procs = []
    for i, port in enumerate(ports):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # PS is host-side numpy/C++
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "elasticdl_tpu.ps.server",
             "--port", str(port), "--ps_id", str(i),
             "--num_ps", str(num_ps),
             "--opt_type", "adam", "--opt_args", "learning_rate=0.001"],
            env=env,
        ))
    return ports, procs


def run_worker(ports, batch_size=512, vocab_size=100_000, num_fields=10,
               embedding_dim=8, warmup=3, iters=30, seed=0,
               barrier=None):
    """One concurrent worker: train against EXISTING PS shards, print a
    JSON line with steps, wall-clock window, and per-phase timings."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    from elasticdl_tpu.models import deepfm
    from elasticdl_tpu.utils import grpc_utils
    from elasticdl_tpu.worker.ps_client import PSClient
    from elasticdl_tpu.worker.ps_trainer import ParameterServerTrainer

    channels = []
    for port in ports:
        ch = grpc_utils.build_channel("localhost:%d" % port)
        grpc_utils.wait_for_channel_ready(ch, timeout=30)
        channels.append(ch)
    spec = deepfm.model_spec(
        num_fields=num_fields, vocab_size=vocab_size,
        embedding_dim=embedding_dim,
    )
    trainer = ParameterServerTrainer(
        spec, PSClient(channels), batch_size=batch_size,
        get_model_steps=1,
    )
    dense, ids, labels = deepfm.synthetic_data(
        n=batch_size * 4, num_fields=num_fields,
        vocab_size=vocab_size, seed=seed,
    )
    batches = []
    for s in range(0, len(labels), batch_size):
        records = [(dense[j], ids[j], labels[j])
                   for j in range(s, s + batch_size)]
        batches.append(spec.feed(records))
    for k in range(warmup):
        trainer.train_minibatch(*batches[k % len(batches)])
    if barrier:
        # All workers finish warmup (incl. jit compile) BEFORE any
        # measures, so one worker's compile can't pollute another's
        # measured window on this single-core box.
        with open("%s.ready.%d" % (barrier, seed), "w"):
            pass
        # Longer than the coordinator's 600 s ready-deadline, so a fast
        # worker never aborts a run the coordinator still considers live.
        deadline = time.time() + 900
        while not os.path.exists(barrier + ".go"):
            if time.time() > deadline:
                raise RuntimeError("barrier timeout")
            time.sleep(0.05)
    trainer.timing.reset()
    start = time.time()
    loss = version = 0.0
    for k in range(iters):
        loss, version = trainer.train_minibatch(
            *batches[k % len(batches)]
        )
    end = time.time()
    print(json.dumps({
        "steps": iters, "start": start, "end": end,
        "last_loss": float(loss), "ps_version": int(version),
        "timing": {
            name: round(s["total_s"], 3)
            for name, s in trainer.timing.summary().items()
            if "total_s" in s  # skip counter sections (e.g. zero1)
        },
    }))


def run_scale(worker_counts=(1, 2, 4), num_ps=2, batch_size=512,
              iters=60):
    """Aggregate async-PS throughput at 1..N concurrent workers."""
    results = []
    import tempfile

    for n in worker_counts:
        ports, procs = _start_ps(num_ps)
        barrier = os.path.join(
            tempfile.mkdtemp(prefix="edl_scale_"), "barrier")
        workers = []
        try:
            workers = [
                subprocess.Popen(
                    [sys.executable, __file__, "--worker",
                     "--ports", ",".join(map(str, ports)),
                     "--iters", str(iters), "--seed", str(100 + w),
                     "--batch", str(batch_size),
                     "--barrier", barrier],
                    stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                    text=True,
                )
                for w in range(n)
            ]
            deadline = time.time() + 600
            while sum(
                os.path.exists("%s.ready.%d" % (barrier, 100 + w))
                for w in range(n)
            ) < n:
                if time.time() > deadline:
                    raise RuntimeError("workers never reached barrier")
                if any(w.poll() not in (None, 0) for w in workers):
                    raise RuntimeError("a worker died before barrier")
                time.sleep(0.1)
            with open(barrier + ".go", "w"):
                pass
            from elasticdl_tpu.utils.jsonline import last_json_line

            reports = []
            for w in workers:
                out, _ = w.communicate(timeout=1200)
                report = last_json_line(out)
                if report is not None:
                    reports.append(report)
            if len(reports) < n:
                raise RuntimeError(
                    "only %d/%d workers reported" % (len(reports), n))
            window = (max(r["end"] for r in reports)
                      - min(r["start"] for r in reports))
            total_steps = sum(r["steps"] for r in reports)
            timing = {}
            for r in reports:
                for name, secs in r["timing"].items():
                    timing[name] = timing.get(name, 0.0) + secs
            results.append({
                "workers": n,
                "examples_per_sec": round(
                    total_steps * batch_size / window, 1),
                "steps_per_sec": round(total_steps / window, 2),
                "wall_secs": round(window, 1),
                "mean_step_ms": round(
                    1000.0 * window * n / total_steps, 1),
                "phase_secs_total": {
                    k: round(v, 2) for k, v in sorted(timing.items())
                },
                "last_losses": [
                    round(r["last_loss"], 3) for r in reports
                ],
                "ps_version": max(r["ps_version"] for r in reports),
            })
            print("scale %d workers: %s" % (n, results[-1]),
                  file=sys.stderr, flush=True)
        finally:
            # Workers first (they busy-poll the barrier file), then PS.
            for p in workers + procs:
                if p.poll() is None:
                    p.terminate()
            for p in workers + procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
    base = results[0]["examples_per_sec"]
    out = {
        "metric": "deepfm_ps_scaleout",
        "value": results[-1]["examples_per_sec"],
        "unit": "examples/sec aggregate",
        "vs_baseline": None,
        "detail": {
            "nproc": os.cpu_count(),
            "num_ps": num_ps,
            "batch_size": batch_size,
            "scaling": results,
            "relative": [
                round(r["examples_per_sec"] / base, 3) for r in results
            ],
            "note": "single-core image: flat aggregate == no "
                    "serialization collapse; see BENCHMARKS.md for the "
                    "workers/shard capacity model",
        },
    }
    print(json.dumps(out))
    return out


def run_service_cost(batch_size=512, vocab_size=100_000, num_fields=10,
                     embedding_dim=8, pushes=300):
    """Measure the PS shard's SERIALIZED section directly: decode+apply
    of one worker push, called in-process on the servicer (no gRPC).

    This is the quantity that caps multi-worker scaling per shard on a
    real multi-core host — everything else (worker compute, client
    codec, transport) runs concurrently across cores, but gradient
    apply serializes behind the shard lock.  workers/shard capacity ~=
    worker_step_time / serialized_time_per_push.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from elasticdl_tpu.models import deepfm
    from elasticdl_tpu.proto import elastic_pb2 as pb
    from elasticdl_tpu.ps.optimizer import create_optimizer
    from elasticdl_tpu.ps.parameters import Parameters
    from elasticdl_tpu.ps.servicer import PserverServicer
    from elasticdl_tpu.utils import tensor_codec
    from elasticdl_tpu.utils.pytree import flatten_with_names, to_numpy

    spec = deepfm.model_spec(
        num_fields=num_fields, vocab_size=vocab_size,
        embedding_dim=embedding_dim,
    )
    named, _ = flatten_with_names(
        to_numpy(spec.init_fn(jax.random.PRNGKey(0))))
    servicer = PserverServicer(
        Parameters(), create_optimizer("adam", "learning_rate=0.001"),
        ps_id=0, num_ps=1,
    )
    servicer.push_model(tensor_codec.model_to_pb(
        dense=named, infos=spec.ps_embedding_infos))

    rng = np.random.RandomState(0)
    dense_bytes = sum(a.nbytes for a in named.values())
    # One full worker minibatch worth of gradients (num_ps=1 -> this
    # shard owns everything): dense grads + unique embedding rows.
    uniq = np.unique(rng.randint(
        0, vocab_size, size=batch_size * num_fields))
    requests = []
    for _ in range(8):  # vary payloads so caches don't flatter the loop
        grads = {n: rng.randn(*a.shape).astype(np.float32)
                 for n, a in named.items()}
        emb = {
            info["name"]: (
                rng.randn(len(uniq), info["dim"]).astype(np.float32),
                uniq,
            )
            for info in spec.ps_embedding_infos
        }
        requests.append(pb.PushGradientsRequest(
            gradients=tensor_codec.model_to_pb(
                dense=grads, embeddings=emb, version=0),
        ))
    for req in requests:  # warm (lazy row init, allocator)
        servicer.push_gradients(req)
    t0 = time.perf_counter()
    for k in range(pushes):
        servicer.push_gradients(requests[k % len(requests)])
    push_ms = 1000.0 * (time.perf_counter() - t0) / pushes

    pull_req = pb.PullEmbeddingVectorsRequest(
        name=spec.ps_embedding_infos[0]["name"], ids=uniq.tolist())
    t0 = time.perf_counter()
    for _ in range(pushes):
        servicer.pull_embedding_vectors(pull_req)
    pull_ms = 1000.0 * (time.perf_counter() - t0) / pushes

    out = {
        "metric": "ps_serialized_service_cost",
        "value": round(push_ms, 3),
        "unit": "ms per push (decode+apply, in-process)",
        "vs_baseline": None,
        "detail": {
            "pull_embedding_ms": round(pull_ms, 3),
            "unique_rows": int(len(uniq)),
            "embedding_dim": embedding_dim,
            "dense_bytes": int(dense_bytes),
            "batch_size": batch_size,
            "pushes": pushes,
            "note": "pull_embedding runs OUTSIDE the shard lock "
                    "(per-row native rw-lock), so only the push cost "
                    "serializes",
        },
    }
    print(json.dumps(out))
    return out


def _run_with_watchdog(timeout_secs=None):
    if timeout_secs is None:
        timeout_secs = int(
            os.environ.get("ELASTICDL_BENCH_TIMEOUT", "600")
        )
    stderr_tail = ""
    try:
        from elasticdl_tpu.utils.jsonline import last_json_line

        proc = subprocess.run(
            [sys.executable, __file__, "--inner"],
            capture_output=True, text=True, timeout=timeout_secs,
        )
        stderr_tail = (proc.stderr or "")[-300:]
        result = last_json_line(proc.stdout)
        if result is not None:
            return result
        reason = "no JSON output from measurement subprocess"
    except subprocess.TimeoutExpired:
        reason = "measurement timed out after %ds" % timeout_secs
    except (OSError, json.JSONDecodeError) as e:
        reason = "%s: %s" % (type(e).__name__, e)
    return {
        "metric": "deepfm_ps_steps_per_sec",
        "value": None,
        "unit": "steps/sec",
        "vs_baseline": None,
        "detail": {"error": reason, "stderr_tail": stderr_tail},
    }


def _argv_int(flag, default):
    if flag in sys.argv:
        return int(sys.argv[sys.argv.index(flag) + 1])
    return default


if __name__ == "__main__":
    if "--worker" in sys.argv:
        ports = [
            int(p) for p in
            sys.argv[sys.argv.index("--ports") + 1].split(",")
        ]
        barrier = None
        if "--barrier" in sys.argv:
            barrier = sys.argv[sys.argv.index("--barrier") + 1]
        run_worker(
            ports,
            batch_size=_argv_int("--batch", 512),
            iters=_argv_int("--iters", 30),
            seed=_argv_int("--seed", 0),
            barrier=barrier,
        )
    elif "--service-cost" in sys.argv:
        run_service_cost(pushes=_argv_int("--pushes", 300))
    elif "--scale" in sys.argv:
        counts = tuple(
            int(c) for c in os.environ.get(
                "ELASTICDL_SCALE_WORKERS", "1,2,4,8").split(",")
        )
        run_scale(worker_counts=counts,
                  iters=_argv_int("--iters", 60))
    elif "--inner" in sys.argv:
        print(json.dumps(run_bench()))
    else:
        print(json.dumps(_run_with_watchdog()))
    sys.exit(0)
