"""Closed-loop online-learning drill: trainer -> aggregation tier ->
fleet, all elastic at once (ROADMAP item 4, docs/serving.md "The
online loop").

One process hosts the control plane, real subprocesses do the serving:

 - a REAL CollectiveTrainer (mnist spec) trains continuously and its
   ``--export_steps`` hook lands versioned servables at the SOURCE
   base (atomic publish, program traced once and reused);
 - the ModelAggregator ingests them, EMA-aggregates over a window, and
   publishes complete servables at the FLEET base on the freshness
   SLO; each publish is driven through the router — a plain barrier
   rollout, except one mid-run publish that goes CANARY-first: p% of
   the key ring on canary replicas, soak, promote barrier-clean;
 - serving replicas are SUBPROCESSES spawned/drained by the
   FleetAutoscaler off the router's own telemetry: a zipf workload
   phase pushes queue wait over the breach threshold (>= 1 grow), a
   light phase lets it idle (>= 1 shrink down the SIGTERM
   graceful-drain path);
 - closed-loop zipf clients hammer ``:predict`` through the router the
   whole time and record every response's ``model_version`` stamp.

Everything is asserted FROM OUTSIDE — response stamps and /metrics:

 - 0 dropped/errored requests and 0 mixed-version keys (per-key
   ``model_version`` monotone) across >= 3 aggregator-driven publishes
   riding live traffic;
 - >= 1 autoscaler grow and >= 1 shrink (router.scale_up/scale_down
   counters), with every admitted request completing;
 - the canary cohort serves ~p% of keyed traffic during its soak
   (cohort counters diffed around the soak) and is promoted
   barrier-clean;
 - measured publish freshness meets the configured SLO
   (elasticdl_agg_freshness_seconds on the router's /metrics, and the
   aggregator's slo_misses counter stays 0).

Run: python bench_online.py [--load_secs 50 --light_secs 40]
Exit code 0 = all gates passed; the result JSON is printed either way.
"""

import http.client
import json
import os
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

FEATURES = 128             # model wide enough that device execute —
HIDDEN = 768               # not the HTTP shell — saturates the
CLASSES = 8                # executor under the load phase
ROWS_PER_REQUEST = 4
EXPORT_STEPS = 40          # trainer steps per servable export
STEP_SLEEP = 0.06          # paces exports to one every ~4s
AGG_WINDOW = 3
PUBLISH_INTERVAL = 8.0     # publish throttle (each publish = rollout)
FRESHNESS_SLO = 25.0       # = throttle + scan cadence + margin
EXPORT_KEEP = 4
CANARY_FRACTION = 0.3
CANARY_SOAK = 8.0
ZIPF_KEYS = 400
ZIPF_EXPONENT = 1.05
LOAD_CONCURRENCY = 8
LIGHT_CONCURRENCY = 1
LIGHT_THINK_SECS = 0.15
SCALE_UP_QUEUE_MS = 10.0
SCALE_DOWN_QUEUE_MS = 3.0
BREACH_SECS = 2.0
IDLE_SECS = 6.0
COOLDOWN_SECS = 10.0
MAX_REPLICAS = 3


def _zipf_weights(n, a):
    ranks = np.arange(1, n + 1, dtype=np.float64)
    weights = ranks ** -a
    return weights / weights.sum()


class _Recorder:
    """Per-key model_version sequences + drop accounting, shared by
    every client thread."""

    def __init__(self):
        self.lock = threading.Lock()
        self.versions = {}
        self.errors = []
        self.total = 0

    def note(self, key, version):
        with self.lock:
            self.versions.setdefault(key, []).append(version)
            self.total += 1

    def note_error(self, detail):
        with self.lock:
            self.errors.append(detail)

    def mixed_keys(self):
        with self.lock:
            return [key for key, seen in self.versions.items()
                    if seen != sorted(seen)]

    def distinct_versions(self):
        with self.lock:
            return sorted({v for seen in self.versions.values()
                           for v in seen})


def _workload_phase(port, recorder, keys, weights, concurrency,
                    duration, think_secs=0.0, seed=0):
    """Closed-loop keyed clients for ``duration`` seconds."""
    stop_at = time.monotonic() + duration

    # Request rows serialized ONCE — per-request JSON cost stays on
    # the wire, not in this process's hot loop.
    rows = [[round((r * FEATURES + c) % 17 / 17.0, 3)
             for c in range(FEATURES)] for r in range(ROWS_PER_REQUEST)]
    instances_json = json.dumps(rows)

    def client(idx):
        rng = np.random.RandomState(seed * 1000 + idx)
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=60)
        try:
            while time.monotonic() < stop_at:
                key = keys[rng.choice(len(keys), p=weights)]
                body = ('{"instances": %s, "routing_key": "%s"}'
                        % (instances_json, key))
                try:
                    conn.request("POST", "/v1/models/mlp:predict",
                                 body=body)
                    resp = conn.getresponse()
                    payload = resp.read()
                except OSError as e:
                    recorder.note_error("transport: %r" % (e,))
                    conn.close()
                    conn = http.client.HTTPConnection(
                        "127.0.0.1", port, timeout=60)
                    continue
                if resp.status != 200:
                    recorder.note_error(
                        (resp.status,
                         payload[:160].decode("utf-8", "replace")))
                else:
                    recorder.note(
                        key, json.loads(payload)["model_version"])
                if think_secs:
                    time.sleep(think_secs)
        finally:
            conn.close()

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def _trainer_loop(trainer, xs, ys, stop):
    while not stop.is_set():
        trainer.train_minibatch(xs, ys)
        stop.wait(STEP_SLEEP)
    trainer.flush_checkpoints()


def _metrics(port):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        conn.request("GET", "/metrics")
        return conn.getresponse().read().decode()
    finally:
        conn.close()


def _metric_value(text, name):
    for line in text.splitlines():
        if line.startswith(name) and (line[len(name)] in " {"):
            return float(line.rsplit(" ", 1)[1])
    return None


def _wait(predicate, timeout, interval=0.2):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


def _drill_spec():
    """A CTR-ranking-shaped MLP, wide enough that one batch's device
    execute dominates its HTTP shell on this rig — the regime where
    queue wait is a real load signal."""
    import jax
    import optax

    from elasticdl_tpu.models.mlp import mlp_apply, mlp_init
    from elasticdl_tpu.models.spec import ModelSpec

    sizes = [FEATURES, HIDDEN, HIDDEN, CLASSES]

    def loss_fn(outputs, labels):
        return optax.softmax_cross_entropy(
            outputs, jax.nn.one_hot(labels, CLASSES))

    return ModelSpec(
        name="mlp",
        init_fn=lambda rng: mlp_init(rng, sizes),
        apply_fn=lambda params, x, train=False: mlp_apply(params, x),
        loss_fn=loss_fn,
        optimizer=optax.adam(1e-3),
        feed=lambda records: records,
    )


def run_drill(load_secs, light_secs):
    from elasticdl_tpu.aggregation import ModelAggregator
    from elasticdl_tpu.serving.export import ContinuousExporter
    from elasticdl_tpu.serving.fleet import (
        FleetAutoscaler,
        ProcessReplicaSpawner,
        canary_slice,
    )
    from elasticdl_tpu.serving.router import (
        Router,
        build_router_server,
    )
    from elasticdl_tpu.worker.collective_trainer import (
        CollectiveTrainer,
    )

    tmp = tempfile.mkdtemp(prefix="bench_online_")
    src = os.path.join(tmp, "trainer_exports")
    pub = os.path.join(tmp, "fleet_exports")

    # -- trainer tier --------------------------------------------------
    spec = _drill_spec()
    exporter = ContinuousExporter(src, model_name="mlp",
                                  platforms=("cpu",))
    trainer = CollectiveTrainer(spec, batch_size=16,
                                exporter=exporter,
                                export_steps=EXPORT_STEPS)
    rng = np.random.RandomState(0)
    xs = rng.rand(16, FEATURES).astype(np.float32)
    ys = rng.randint(0, CLASSES, 16)
    stop = threading.Event()
    trainer_thread = threading.Thread(
        target=_trainer_loop, args=(trainer, xs, ys, stop),
        daemon=True)
    trainer_thread.start()

    # -- aggregation tier ----------------------------------------------
    agg = ModelAggregator(
        src, pub, window=AGG_WINDOW, mode="ema", ema_decay=0.5,
        freshness_slo_secs=FRESHNESS_SLO,
        min_publish_interval_secs=PUBLISH_INTERVAL,
        export_keep=EXPORT_KEEP, model_name="mlp")
    assert _wait(lambda: agg.ingest_once() or
                 agg.stats()["last_ingested_version"], 60), (
        "trainer never exported")
    first_version, _ = agg.publish()

    # -- serving fleet -------------------------------------------------
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "OMP_NUM_THREADS": "1",
                "OPENBLAS_NUM_THREADS": "1"})
    # An unfillable batch size + a real window: under CONCURRENT load
    # every request waits ~the window for companions (the batcher's
    # pressure-aware flush), a lone client pays zero — so the windowed
    # queue-wait signal tracks concurrency pressure even on a rig
    # where the model itself can't saturate a core.
    spawner = ProcessReplicaSpawner(
        pub, extra_args=["--max_batch_size", "64",
                         "--batch_timeout_ms", "30"], env=env)
    first_addr = spawner.spawn(boot_version=first_version)
    # probe_timeout rides 1-core compile storms (a replica warming a
    # fresh version can stall its /statz answer for seconds here).
    router = Router([first_addr], export_dir=pub,
                    probe_interval=0.25, probe_timeout=5.0,
                    poll_interval=0.5, auto_rollout=False)
    server = build_router_server(router, port=0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    router.start(coordinate=True)
    autoscaler = FleetAutoscaler(
        router, spawner, min_replicas=1, max_replicas=MAX_REPLICAS,
        scale_up_queue_ms=SCALE_UP_QUEUE_MS,
        scale_down_queue_ms=SCALE_DOWN_QUEUE_MS,
        breach_secs=BREACH_SECS,
        idle_secs=IDLE_SECS, cooldown_secs=COOLDOWN_SECS,
        cadence_secs=0.5)
    assert _wait(lambda: router.coordinator.committed_version
                 == first_version
                 and len(router.state.routable(first_version)) >= 1,
                 90), router.fleet_status()
    autoscaler.start()

    # -- aggregation control loop (publish -> rollout/canary -> GC) ----
    canary_report = {}

    def agg_loop():
        while not stop.is_set():
            agg.ingest_once()
            if agg.publish_due():
                version, freshness = agg.publish()
                committed = router.coordinator.committed_version
                routable = len(router.state.routable(committed))
                if not canary_report and routable >= 2:
                    before = router.cohort_stats()
                    started = router.start_canary(
                        version, CANARY_FRACTION,
                        freshness_seconds=freshness)
                    if started.get("started"):
                        stop.wait(CANARY_SOAK)
                        after = router.cohort_stats()
                        promoted = router.promote_canary()
                        keyed = {
                            c: (after[c]["keyed_requests"]
                                - before[c]["keyed_requests"])
                            for c in ("canary", "baseline")}
                        total = sum(keyed.values())
                        canary_report.update({
                            "version": version,
                            "fraction": CANARY_FRACTION,
                            "soak_keyed_requests": keyed,
                            "measured_traffic_share":
                                round(keyed["canary"] / total, 4)
                                if total else None,
                            "promoted":
                                bool(promoted.get("promoted")),
                        })
                    else:
                        router.external_rollout(
                            version, freshness_seconds=freshness)
                else:
                    router.external_rollout(
                        version, freshness_seconds=freshness)
                agg.gc_published(
                    router.coordinator.committed_version)
            stop.wait(0.5)

    agg_thread = threading.Thread(target=agg_loop, daemon=True)
    agg_thread.start()

    # -- workload ------------------------------------------------------
    recorder = _Recorder()
    keys = ["user-%d" % i for i in range(ZIPF_KEYS)]
    weights = _zipf_weights(ZIPF_KEYS, ZIPF_EXPONENT)
    t0 = time.monotonic()
    _workload_phase(port, recorder, keys, weights,
                    LOAD_CONCURRENCY, load_secs, seed=1)
    _workload_phase(port, recorder, keys, weights,
                    LIGHT_CONCURRENCY, light_secs,
                    think_secs=LIGHT_THINK_SECS, seed=2)
    # Tail: give a pending shrink time to drain, keep a trickle going.
    _workload_phase(port, recorder, keys, weights, 1, 8.0,
                    think_secs=0.2, seed=3)
    elapsed = time.monotonic() - t0

    metrics_text = _metrics(port)
    stop.set()
    agg_thread.join(timeout=30)
    trainer_thread.join(timeout=30)
    autoscaler.stop()
    agg_stats = agg.stats()
    status = router.fleet_status()
    router.stop()
    server.shutdown()
    server.server_close()
    spawner.close()

    # -- gates (all from response stamps + /metrics) -------------------
    expected_share = float(sum(
        w for key, w in zip(keys, weights)
        if canary_slice(key) < CANARY_FRACTION))
    scale_up = _metric_value(
        metrics_text,
        'elasticdl_fleet_router_counter{name="router.scale_up"}') or 0
    scale_down = _metric_value(
        metrics_text,
        'elasticdl_fleet_router_counter{name="router.scale_down"}'
    ) or 0
    freshness_metric = _metric_value(
        metrics_text, "elasticdl_agg_freshness_seconds")
    mixed = recorder.mixed_keys()
    versions_seen = recorder.distinct_versions()
    share = canary_report.get("measured_traffic_share")
    gates = {
        "zero_drops": len(recorder.errors) == 0,
        "zero_mixed_version_keys": len(mixed) == 0,
        "rode_3_publishes": len(versions_seen) >= 3,
        "autoscaler_grew": scale_up >= 1,
        "autoscaler_shrank": scale_down >= 1,
        "canary_promoted": bool(canary_report.get("promoted")),
        "canary_share_near_p": (
            share is not None
            and abs(share - expected_share) <= 0.15),
        "freshness_met_slo": (
            freshness_metric is not None
            and freshness_metric <= FRESHNESS_SLO
            and agg_stats["counters"].get("slo_misses", 0) == 0),
    }
    result = {
        "metric": "online_loop_drill",
        "value": int(all(gates.values())),
        "unit": "all gates passed (1/0)",
        "vs_baseline": None,
        "detail": {
            "gates": gates,
            "elapsed_secs": round(elapsed, 1),
            "requests": recorder.total,
            "dropped_or_errored": recorder.errors[:5],
            "distinct_versions_served": versions_seen,
            "mixed_version_keys": mixed[:5],
            "publishes": agg_stats["counters"].get("published", 0),
            "ingested_exports": agg_stats["counters"].get(
                "ingested", 0),
            "freshness_seconds": freshness_metric,
            "freshness_slo_secs": FRESHNESS_SLO,
            "slo_misses": agg_stats["counters"].get("slo_misses", 0),
            "scale_up_events": scale_up,
            "scale_down_events": scale_down,
            "canary": dict(canary_report,
                           expected_traffic_share=round(
                               expected_share, 4)),
            "final_committed_version":
                status["committed_version"],
            "final_replicas": sorted(status["replicas"]),
            "n_cpus": len(os.sched_getaffinity(0)),
        },
    }
    return result


def main(argv=None):
    import argparse

    parser = argparse.ArgumentParser("bench_online")
    parser.add_argument("--load_secs", type=float, default=50.0,
                        help="heavy zipf phase (drives the scale-up)")
    parser.add_argument("--light_secs", type=float, default=40.0,
                        help="light phase (drives the scale-down)")
    args = parser.parse_args(argv)
    result = run_drill(args.load_secs, args.light_secs)
    print(json.dumps(result, indent=2))
    return 0 if result["value"] else 1


if __name__ == "__main__":
    sys.exit(main())
