"""Serving microbenchmark: serialized-lock baseline vs dynamic batcher,
plus (``--fleet``) the multi-replica fleet leg.

The fleet leg (PR 9) measures the serving TIER, not one server: real
replica subprocesses (each its own interpreter + XLA runtime — no
shared GIL) behind the in-process router (serving/router.py):

 - aggregate closed-loop ``:predict`` throughput, 1 replica vs 3
   replicas behind the router, as interleaved timed blocks;
 - a fleet hot-swap fired MID-STORM: a new export version rolls out
   through the coordinator's barrier while keyed clients hammer —
   reported: dropped requests (must be 0) and mixed-version pairs
   (a version regression for one key; must be 0);
 - PS-backed ``:lookup``: a table served straight from a live PS shard
   (never exported to disk), verified bit-identical to the
   exported-table path, with the hot-row-cache hit ratio scraped off
   the replica's /metrics.

Each replica is pinned to ONE core via taskset (the cpuset a
per-container CPU limit would impose) in BOTH legs, so the 1-vs-3
ratio measures fleet fan-out, not XLA intra-op threading — and the
result JSON carries the rig's physical-core scaling ceiling, because a
2-core box cannot express 3-replica scaling no matter how good the
router is (the headline regime needs >= 4 cores or one host per
replica).

The original single-server comparison (default mode):

Closed-loop concurrent clients (next request only after the previous
response) hammer ``:predict`` on two endpoints over the SAME export:

 - ``serialized``: batching disabled — every request takes the
   per-model execution lock and dispatches its own ``exported.call``
   (the pre-batcher server behavior);
 - ``batched``: the dynamic micro-batcher (serving/batcher.py)
   coalesces concurrent requests into bucketed padded device batches.

Two measurement layers, both reported:

 - ``endpoint``: clients call ``ModelEndpoint.predict`` directly — the
   serving hot path this PR changes (marshalling, admission queue,
   device execution), without the HTTP shell.  The headline ratio.
 - ``http``: end-to-end over real keep-alive HTTP connections.  On
   this single-core rig the client+server JSON/HTTP CPU — identical in
   both modes and GIL-serialized with everything else — dominates, so
   the end-to-end ratio understates the device-path win; reported
   honestly alongside.

Each pair runs as INTERLEAVED timed blocks (A,B,A,B,... best block
kept per mode, the BENCHMARKS.md convention): this container is
shared, so wall-clock noise between back-to-back runs exceeds the
effect under test, and pairing decorrelates it.  Before timing, one
canonical request is sent through both modes and compared — the
batcher must be numerically identical, not just faster.

The model is CTR-ranking shaped (small dense feature vector, small
MLP): per-request device work is tiny, so the serialized path is
dispatch-bound — exactly the regime request batching exists for.
"""

import http.client
import json
import os
import tempfile
import threading
import time

# A host-side CPU bench (HTTP, batching and codec costs around a tiny
# model): pinned to the CPU so that it takes no chip on a TPU host.
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

from elasticdl_tpu.client import frame_client as fc  # noqa: E402
from elasticdl_tpu.utils import hist as hist_mod  # noqa: E402
from elasticdl_tpu.utils import tensor_codec as tc  # noqa: E402

FEATURES = 64
HIDDEN = 128
CLASSES = 8
# max_batch_size matches the benched concurrency: a complete wave of
# in-flight requests size-flushes the instant it is assembled instead
# of burning the residual batch window (docs/serving.md tuning notes —
# cap at the live concurrency you provision for).
MAX_BATCH = 8
TIMEOUT_MS = 20.0
REQUESTS_PER_CLIENT = 60
BLOCKS = 4
CONCURRENCY = (1, 8, 16)
HEADLINE_CONCURRENCY = 8  # the acceptance level; 16 reported too


def _export_mlp(export_dir):
    from elasticdl_tpu.serving.export import export_servable

    rng = np.random.RandomState(0)
    params = {
        "w1": rng.randn(FEATURES, HIDDEN).astype(np.float32) * 0.05,
        "b1": np.zeros(HIDDEN, np.float32),
        "w2": rng.randn(HIDDEN, HIDDEN).astype(np.float32) * 0.05,
        "b2": np.zeros(HIDDEN, np.float32),
        "w3": rng.randn(HIDDEN, CLASSES).astype(np.float32) * 0.05,
        "b3": np.zeros(CLASSES, np.float32),
    }

    def apply_fn(p, x):
        import jax.numpy as jnp

        h = jnp.maximum(x @ p["w1"] + p["b1"], 0.0)
        h = jnp.maximum(h @ p["w2"] + p["b2"], 0.0)
        return h @ p["w3"] + p["b3"]

    export_servable(
        export_dir, apply_fn, params,
        np.zeros((1, FEATURES), np.float32),
        model_name="mlp", platforms=("cpu",),
    )


def _payload(idx, rows=1):
    return {"instances": [[float((idx * 37 + r + j) % 23) / 23.0
                           for j in range(FEATURES)]
                          for r in range(rows)]}


class _Rig:
    """One endpoint (+ HTTP server) per mode; collects best-block
    wall times and latency distributions per (layer, concurrency)."""

    def __init__(self, export_dir, batching, payload_rows=1):
        from elasticdl_tpu.serving.server import (
            ModelEndpoint,
            build_server,
        )

        self.label = "batched" if batching is not None else "serialized"
        self.payload_rows = payload_rows
        self.endpoint = ModelEndpoint(export_dir, batching=batching)
        self.server = build_server(self.endpoint, port=0)
        self.port = self.server.server_address[1]
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()
        self.best = {}       # (layer, conc) -> best wall seconds
        self.latencies = {}  # (layer, conc) -> best block's latencies
        self.counters = {}   # (layer, conc) -> /statz counters snapshot

    def close(self):
        self.server.shutdown()
        self.server.server_close()
        self.endpoint.close()

    def predict_http_once(self, payload):
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=60)
        try:
            conn.request("POST", "/v1/models/mlp:predict",
                         body=json.dumps(payload))
            resp = conn.getresponse()
            assert resp.status == 200, resp.read()[:500]
            return json.loads(resp.read())["predictions"]
        finally:
            conn.close()

    def timed_block(self, layer, concurrency, requests_per_client):
        self.endpoint.timing.reset()  # per-block counters
        barrier = threading.Barrier(concurrency + 1)
        latencies = [[] for _ in range(concurrency)]
        errors = []

        def endpoint_client(idx):
            body = _payload(idx, self.payload_rows)
            try:
                self.endpoint.predict(body)  # unmeasured warm request
                barrier.wait()
                for _ in range(requests_per_client):
                    t0 = time.perf_counter()
                    self.endpoint.predict(body)
                    latencies[idx].append(time.perf_counter() - t0)
            except Exception as e:  # noqa: BLE001 — fail loudly, not
                # by hanging the barrier.
                errors.append(repr(e))
                barrier.abort()

        def http_client(idx):
            body = json.dumps(_payload(idx, self.payload_rows))
            conn = http.client.HTTPConnection(
                "127.0.0.1", self.port, timeout=120)
            try:
                conn.request("POST", "/v1/models/mlp:predict",
                             body=body)
                conn.getresponse().read()  # warm: connection + state
                barrier.wait()
                for _ in range(requests_per_client):
                    t0 = time.perf_counter()
                    conn.request("POST", "/v1/models/mlp:predict",
                                 body=body)
                    resp = conn.getresponse()
                    raw = resp.read()
                    if resp.status != 200:
                        errors.append(raw[:200])
                        return
                    json.loads(raw)
                    latencies[idx].append(time.perf_counter() - t0)
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))
                barrier.abort()
            finally:
                conn.close()

        def http_bin_client(idx):
            # The binary wire path through the frame client SDK
            # (client/frame_client.py) — the same keep-alive
            # connection discipline as the JSON client, one pooled
            # connection per thread.  Work parity with the JSON leg:
            # encode once outside the loop (predict_frame replays the
            # blob), decode every response into typed arrays.
            x = np.asarray(_payload(idx, self.payload_rows)
                           ["instances"], np.float32)
            body = fc.encode_predict(x)
            client = fc.FrameClient("127.0.0.1:%d" % self.port,
                                    timeout=120, pool_size=1)
            try:
                client.predict_frame("mlp", body)  # warm
                barrier.wait()
                for _ in range(requests_per_client):
                    t0 = time.perf_counter()
                    frame = client.predict_frame("mlp", body)
                    fc.decode_predictions(frame)
                    latencies[idx].append(time.perf_counter() - t0)
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))
                barrier.abort()
            finally:
                client.close()

        target = {"endpoint": endpoint_client,
                  "http": http_client,
                  "http_bin": http_bin_client}[layer]
        threads = [threading.Thread(target=target, args=(i,),
                                    daemon=True)
                   for i in range(concurrency)]
        for t in threads:
            t.start()
        try:
            barrier.wait()
        except threading.BrokenBarrierError:
            pass  # a client aborted pre-barrier; errors raise below
        start = time.perf_counter()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - start
        if errors:
            raise RuntimeError("client errors: %s" % errors[:3])
        key = (layer, concurrency)
        if key not in self.best or elapsed < self.best[key]:
            self.best[key] = elapsed
            self.latencies[key] = [
                x for per_client in latencies for x in per_client]
            self.counters[key] = self.endpoint.stats()
        return elapsed

    def result(self, layer, concurrency, requests_per_client):
        key = (layer, concurrency)
        lats = np.asarray(sorted(self.latencies[key]))
        total = concurrency * requests_per_client
        stats = self.counters[key]
        counters = stats["counters"]
        return {
            "mode": self.label,
            "layer": layer,
            "concurrency": concurrency,
            "requests": total,
            "requests_per_sec": round(total / self.best[key], 1),
            "p50_ms": round(1e3 * float(np.percentile(lats, 50)), 2),
            "p99_ms": round(1e3 * float(np.percentile(lats, 99)), 2),
            "mean_batch_occupancy": stats["mean_batch_occupancy"],
            "padded_rows": counters.get("batcher.padded_rows", 0),
            "size_flushes": counters.get("batcher.size_flushes", 0),
            "timeout_flushes": counters.get(
                "batcher.timeout_flushes", 0),
            "empty_flushes": counters.get("batcher.empty_flushes", 0),
        }


# -- fleet leg (PR 9) --------------------------------------------------

FLEET_FEATURES = 64
FLEET_HIDDEN = 1024
FLEET_ROWS_PER_REQUEST = 64
FLEET_CONCURRENCY = 6
FLEET_REQUESTS_PER_CLIENT = 20
FLEET_BLOCKS = 3


def _free_port():
    import socket

    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    return port


def _export_fleet_version(base, version, bias=0.0):
    """A compute-heavier MLP than the batching leg's: per-request
    device time must dominate the HTTP/JSON shell so the fleet ratio
    measures replicated EXECUTION, not the bench process's client
    CPU."""
    from elasticdl_tpu.serving.export import export_servable

    rng = np.random.RandomState(7)
    params = {
        "w1": rng.randn(FLEET_FEATURES, FLEET_HIDDEN)
        .astype(np.float32) * 0.03,
        "w2": rng.randn(FLEET_HIDDEN, FLEET_HIDDEN)
        .astype(np.float32) * 0.03,
        "w3": rng.randn(FLEET_HIDDEN, CLASSES).astype(np.float32)
        * 0.03,
    }

    def apply_fn(p, x):
        import jax.numpy as jnp

        h = jnp.maximum(x @ p["w1"], 0.0)
        h = jnp.maximum(h @ p["w2"], 0.0)
        return h @ p["w3"] + bias

    export_servable(
        os.path.join(base, str(version)), apply_fn, params,
        np.zeros((1, FLEET_FEATURES), np.float32),
        model_name="mlp", version=version, platforms=("cpu",),
    )


def _spawn_replica(base, port, ps_addrs="", cpu=None):
    import shutil
    import subprocess
    import sys

    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "OMP_NUM_THREADS": "1",
        "OPENBLAS_NUM_THREADS": "1",
    })
    cmd = [
        sys.executable, "-m", "elasticdl_tpu.serving.server",
        "--export_dir", base, "--host", "127.0.0.1",
        "--port", str(port), "--fleet_managed", "true",
        "--max_batch_size", str(MAX_BATCH),
        "--batch_timeout_ms", "5",
    ]
    if cpu is not None and shutil.which("taskset"):
        # One core per replica (the cpuset a per-container CPU limit
        # would impose): XLA's intra-op pool otherwise grabs every
        # visible core for ONE replica's matmuls, so the 1-vs-3 ratio
        # would measure intra-op threading, not fleet fan-out.
        cmd = ["taskset", "-c", str(cpu)] + cmd
    if ps_addrs:
        cmd += ["--ps_addrs", ps_addrs]
    return subprocess.Popen(cmd, env=env)


def _wait_http_ok(port, path="/healthz", timeout=90):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=2)
            conn.request("GET", path)
            ok = conn.getresponse().status == 200
            conn.close()
            if ok:
                return True
        except OSError:
            time.sleep(0.2)
    return False


class _Fleet:
    """N replica subprocesses behind an in-process router."""

    def __init__(self, base, n, ps_addrs=""):
        from elasticdl_tpu.serving.router import (
            Router,
            build_router_server,
        )

        n_cpus = len(os.sched_getaffinity(0))
        self.procs = []
        addrs = []
        for i in range(n):
            port = _free_port()
            self.procs.append(_spawn_replica(
                base, port, ps_addrs=ps_addrs, cpu=i % n_cpus))
            addrs.append("127.0.0.1:%d" % port)
        for addr in addrs:
            assert _wait_http_ok(int(addr.rpartition(":")[2])), (
                "replica %s did not come up" % addr)
        self.router = Router(addrs, export_dir=base,
                             probe_interval=0.25, poll_interval=1.0,
                             barrier_timeout=120.0)
        self.server = build_router_server(self.router, port=0)
        self.port = self.server.server_address[1]
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()
        self.router.start(coordinate=True)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            status = self.router.fleet_status()
            healthy = sum(1 for r in status["replicas"].values()
                          if r["healthy"])
            if healthy == n and status["committed_version"] >= 1:
                break
            time.sleep(0.1)
        else:
            raise RuntimeError("fleet did not become healthy: %s"
                               % self.router.fleet_status())

    def replica_metrics(self):
        out = []
        for addr in list(self.router.state.snapshot()[0]):
            port = int(addr.rpartition(":")[2])
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=5)
            conn.request("GET", "/metrics")
            out.append(conn.getresponse().read().decode())
            conn.close()
        return out

    def close(self):
        import signal as _signal

        self.router.stop()
        self.server.shutdown()
        self.server.server_close()
        for proc in self.procs:
            if proc.poll() is None:
                proc.send_signal(_signal.SIGTERM)  # graceful drain
        deadline = time.monotonic() + 15
        for proc in self.procs:
            while proc.poll() is None and time.monotonic() < deadline:
                time.sleep(0.1)
            if proc.poll() is None:
                proc.kill()
                proc.wait()


def _fleet_storm(port, concurrency, requests_per_client, keyed=False,
                 payload_rows=FLEET_ROWS_PER_REQUEST):
    """Closed-loop keep-alive clients against the router.  Returns
    (elapsed_secs, ok_count, error_list, per_key_versions)."""
    barrier = threading.Barrier(concurrency + 1)
    errors = []
    versions = {}

    def client(idx):
        body = {"instances": [[float((idx * 31 + j) % 17) / 17.0
                               for j in range(FLEET_FEATURES)]
                              for _ in range(payload_rows)]}
        if keyed:
            body["routing_key"] = "storm-%d" % idx
        raw = json.dumps(body)
        seen = versions.setdefault(idx, [])
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=120)
        try:
            conn.request("POST", "/v1/models/mlp:predict", body=raw)
            resp = conn.getresponse()
            resp.read()  # warm: connection + replica state
            if resp.status != 200:
                errors.append("warm: %d" % resp.status)
                barrier.abort()
                return
            barrier.wait()
            for _ in range(requests_per_client):
                conn.request("POST", "/v1/models/mlp:predict",
                             body=raw)
                resp = conn.getresponse()
                payload = resp.read()
                if resp.status != 200:
                    errors.append((resp.status, payload[:200]))
                    return
                if keyed:
                    seen.append(json.loads(payload)["model_version"])
                else:
                    # Throughput blocks: don't burn bench-process GIL
                    # decoding payloads — status checked, bytes read.
                    seen.append(0)
        except threading.BrokenBarrierError:
            pass
        except Exception as e:  # noqa: BLE001 — a dropped request IS
            # the failure the fleet drill counts
            errors.append(repr(e))
        finally:
            conn.close()

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(concurrency)]
    for t in threads:
        t.start()
    try:
        barrier.wait()
    except threading.BrokenBarrierError:
        pass
    start = time.perf_counter()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - start
    ok = sum(len(v) for v in versions.values())
    return elapsed, ok, errors, versions


def _run_fleet_throughput(base, requests_per_client):
    """Interleaved 1-replica vs 3-replica blocks.  The headline ratio
    is the MEDIAN of per-block ratios (the bench_zero idiom): each
    block pairs the two fleets back-to-back, so the shared container's
    CPU-steal noise — which far exceeds the effect at this core count —
    cancels within a pair instead of corrupting a best-of comparison
    across instants."""
    rates = {1: [], 3: []}
    fleets = {1: _Fleet(base, 1), 3: _Fleet(base, 3)}
    try:
        for block in range(FLEET_BLOCKS):
            # Alternate leg order per block to cancel warmup drift.
            order = [1, 3] if block % 2 == 0 else [3, 1]
            for n in order:
                elapsed, ok, errors, _ = _fleet_storm(
                    fleets[n].port, FLEET_CONCURRENCY,
                    requests_per_client)
                if errors:
                    raise RuntimeError("fleet-%d errors: %s"
                                       % (n, errors[:3]))
                rates[n].append(ok / elapsed)
        # Hot-swap drill on the 3-replica fleet, mid-storm.
        drill = _run_hotswap_drill(base, fleets[3])
    finally:
        for fleet in fleets.values():
            fleet.close()
    ratios = sorted(r3 / r1 for r1, r3 in zip(rates[1], rates[3]))
    median_ratio = ratios[len(ratios) // 2]
    return ({n: round(max(r), 1) for n, r in rates.items()},
            round(median_ratio, 2), drill)


def _run_hotswap_drill(base, fleet):
    """Fire a new export version mid-storm; count drops and
    mixed-version (per-key regression) pairs."""
    swap_result = {}

    def swap():
        time.sleep(1.0)  # let the storm establish
        _export_fleet_version(base, 2, bias=1.0)
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            if fleet.router.coordinator.committed_version == 2:
                swap_result["committed"] = True
                return
            time.sleep(0.1)
        swap_result["committed"] = False

    swapper = threading.Thread(target=swap, daemon=True)
    swapper.start()
    elapsed, ok, errors, versions = _fleet_storm(
        fleet.port, FLEET_CONCURRENCY, FLEET_REQUESTS_PER_CLIENT * 3,
        keyed=True)
    swapper.join(timeout=120)
    mixed = 0
    straddled = 0
    for _key, seen in versions.items():
        if seen != sorted(seen):
            mixed += 1
        if seen and seen[0] == 1 and seen[-1] == 2:
            straddled += 1
    return {
        "committed": swap_result.get("committed", False),
        "requests": ok,
        "dropped_or_errored": len(errors),
        "mixed_version_keys": mixed,
        "keys_straddling_flip": straddled,
        "storm_secs": round(elapsed, 1),
    }


def _run_ps_lookup_leg(tmp):
    """A table served straight from a live PS shard — never exported —
    bit-identical to the exported-table path, hit ratio on /metrics."""
    from elasticdl_tpu.proto import rpc
    from elasticdl_tpu.ps.optimizer import create_optimizer
    from elasticdl_tpu.ps.parameters import Parameters
    from elasticdl_tpu.ps.servicer import PserverServicer
    from elasticdl_tpu.serving.export import export_servable
    from elasticdl_tpu.utils import grpc_utils
    from elasticdl_tpu.worker.ps_client import PSClient

    servicer = PserverServicer(
        Parameters(), create_optimizer("sgd", "learning_rate=0.1"),
        generation=1)
    ps_server = grpc_utils.build_server(max_workers=8)
    rpc.add_pserver_servicer(servicer, ps_server)
    ps_port = ps_server.add_insecure_port("[::]:0")
    ps_server.start()
    channel = grpc_utils.build_channel("localhost:%d" % ps_port)
    grpc_utils.wait_for_channel_ready(channel)
    seed_client = PSClient([channel])
    n_rows, dim = 4096, 16
    seed_client.push_model({}, embedding_infos=[
        {"name": "users", "dim": dim, "initializer": "uniform"}])
    trained = seed_client.pull_embedding_vectors(
        "users", np.arange(n_rows))

    base = os.path.join(tmp, "lookup_exports")
    # The export embeds a COPY of the table under another name; "users"
    # itself is never exported — it serves from the PS.
    export_servable(
        os.path.join(base, "1"),
        lambda p, x: x @ p["w"],
        {"w": np.zeros((2, 2), np.float32)},
        np.zeros((1, 2), np.float32), model_name="mlp", version=1,
        embeddings={"users_copy": (np.arange(n_rows), trained)},
        platforms=("cpu",),
    )
    port = _free_port()
    proc = _spawn_replica(base, port,
                          ps_addrs="localhost:%d" % ps_port)
    try:
        assert _wait_http_ok(port)
        rng = np.random.RandomState(11)
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=60)
        identical = True
        lookups = 0
        t0 = time.perf_counter()
        for _ in range(200):
            # Zipf-ish id mix: a hot head + a long tail, the access
            # pattern the hot-row LRU exists for.
            ids = np.concatenate([
                rng.randint(0, 64, 48),
                rng.randint(0, n_rows, 16),
            ]).tolist()
            out = {}
            for table in ("users", "users_copy"):
                conn.request("POST", "/v1/models/mlp:lookup",
                             body=json.dumps({"table": table,
                                              "ids": ids}))
                resp = conn.getresponse()
                payload = json.loads(resp.read())
                assert resp.status == 200, payload
                out[table] = (payload["source"],
                              np.asarray(payload["vectors"],
                                         np.float32))
            assert out["users"][0] == "ps"
            assert out["users_copy"][0] == "export"
            identical = identical and bool(np.array_equal(
                out["users"][1], out["users_copy"][1]))
            lookups += 1
        lookup_secs = time.perf_counter() - t0
        conn.request("GET", "/metrics")
        metrics = conn.getresponse().read().decode()
        conn.close()
        hit_ratio = None
        for line in metrics.splitlines():
            if line.startswith(
                    "elasticdl_serving_emb_cache_hit_ratio"):
                hit_ratio = float(line.rsplit(" ", 1)[1])
        return {
            "bit_identical_to_export_path": identical,
            "lookups": lookups,
            "lookups_per_sec": round(lookups / lookup_secs, 1),
            "emb_cache_hit_ratio": hit_ratio,
            "table_rows_served_from_ps": n_rows,
        }
    finally:
        if proc.poll() is None:
            proc.terminate()
            proc.wait()
        ps_server.stop(grace=None)


def run_fleet_bench(requests_per_client=FLEET_REQUESTS_PER_CLIENT):
    n_cpus = len(os.sched_getaffinity(0))
    with tempfile.TemporaryDirectory() as tmp:
        base = os.path.join(tmp, "fleet_exports")
        _export_fleet_version(base, 1)
        throughput, ratio, drill = _run_fleet_throughput(
            base, requests_per_client)
        lookup = _run_ps_lookup_leg(tmp)
    # With R replicas pinned one-core-each, aggregate scaling is
    # hard-capped by the physical core count — and the router + the
    # closed-loop clients (one shared process here) compete for the
    # SAME cores, so a 2-core rig cannot reach even 2x at any replica
    # count.  Reported so the number can't be read as a fleet defect.
    ceiling = round(min(3.0, float(n_cpus)), 2)
    print(json.dumps({
        "metric": "serving_fleet_throughput",
        "value": ratio,
        "unit": "x aggregate predict throughput (3 replicas vs 1 "
                "behind the router, %d closed-loop clients, %d-row "
                "requests, median of per-block ratios)"
                % (FLEET_CONCURRENCY, FLEET_ROWS_PER_REQUEST),
        "vs_baseline": None,
        "detail": {
            "best_requests_per_sec_by_replicas": {
                str(n): rps for n, rps in sorted(throughput.items())},
            "hotswap_drill": drill,
            "ps_lookup_leg": lookup,
            "replicas_are_subprocesses": True,
            "cpuset": "one core per replica via taskset (a "
                      "per-container CPU limit); router + clients "
                      "share the same %d cores" % n_cpus,
            "n_cpus": n_cpus,
            "aggregate_scaling_ceiling_x": ceiling,
            "baseline": "self-relative: 1 replica behind the same "
                        "router IS the baseline; the 3-vs-1 regime "
                        "this tier targets (each replica + the router "
                        "on its own host/core) needs >= 4 cores",
        },
    }))
    return ratio, drill, lookup


# -- binary wire leg (the zero-copy data plane) -------------------------

WIRE_CONCURRENCY = 16       # the acceptance level (ROADMAP item 5)
WIRE_APPROACH_FLOOR = 0.75  # e2e ratio must be >= 75% of endpoint's
WIRE_P99_SLACK = 1.10       # binary p99 may not exceed json p99 by >10%
# Requests carry a realistic ranking-candidate slate (the fleet leg's
# 64-row shape), not one row — marshal cost scales with rows (the
# whole point of the binary plane) while the per-request stdlib-HTTP
# overhead (identical in both modes, the irreducible transport floor)
# amortizes.  The batch cap fits 8 such requests per executed batch.
WIRE_ROWS = 64
WIRE_MAX_BATCH = 512


def _hist_p99_ms(stats):
    snap = (stats.get("hists") or {}).get("serving.request")
    if not snap or not snap.get("count"):
        return None
    return round(1e3 * hist_mod.quantile(snap, 0.99), 3)


def _run_router_passthrough(rig):
    """One keyed binary request direct vs through the router: the
    forwarded RESPONSE must be byte-identical (zero re-encode on the
    proxied body; the request side's byte-identity is pinned with a
    capturing replica in tests/test_serving_binary.py)."""
    from elasticdl_tpu.serving.router import (
        Router,
        build_router_server,
    )

    x = np.asarray(_payload(5)["instances"], np.float32)
    blob = fc.encode_predict(x, routing_key="bench-key")

    def post(port):
        # roundtrip (not predict_frame): the check compares RAW reply
        # bytes, which the typed surface would decode away.
        with fc.FrameClient("127.0.0.1:%d" % port,
                           timeout=60) as client:
            status, _ctype, raw = client.roundtrip(
                "/v1/models/mlp:predict", blob)
            return status, raw

    router = Router(["127.0.0.1:%d" % rig.port], probe_interval=0.2)
    router.start()
    server = build_router_server(router, port=0)
    threading.Thread(target=server.serve_forever,
                     daemon=True).start()
    try:
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            if router.state.routable(None):
                break
            time.sleep(0.05)
        direct_status, direct = post(rig.port)
        routed_status, routed = post(server.server_address[1])
        return {
            "direct_status": direct_status,
            "routed_status": routed_status,
            "byte_identical_response": bool(direct == routed),
        }
    finally:
        router.stop()
        server.shutdown()
        server.server_close()


def _run_frame_transfer_leg(blocks=5):
    """The streaming export/ingest sub-leg: ONE model payload through
    the npz archive path (what every publish used to round-trip) vs
    the binary model frame (encode -> decode as zero-copy views),
    interleaved, best-of per mode."""
    from elasticdl_tpu.serving.export import _npz_bytes, decode_payload

    rng = np.random.RandomState(0)
    payload = {"layer%02d/w" % i: rng.randn(256, 256)
               .astype(np.float32) for i in range(16)}
    payload["emb_ids/users"] = np.arange(20000, dtype=np.int64)
    payload["emb_vals/users"] = rng.randn(20000, 32)\
        .astype(np.float32)
    nbytes = sum(a.nbytes for a in payload.values())

    import io as _io

    def npz_pass():
        blob = _npz_bytes(payload)
        with np.load(_io.BytesIO(blob)) as z:
            dense, emb = decode_payload(
                {key: z[key] for key in z.files})
        return dense, emb

    def frame_pass():
        blob = tc.encode_frame(payload, kind="servable")
        frame = tc.decode_frame(blob)
        return decode_payload(dict(frame.tensors))

    best = {"npz": float("inf"), "frame": float("inf")}
    for block in range(blocks):
        order = (("npz", npz_pass), ("frame", frame_pass)) \
            if block % 2 == 0 else (("frame", frame_pass),
                                    ("npz", npz_pass))
        for name, fn in order:
            t0 = time.perf_counter()
            dense, emb = fn()
            best[name] = min(best[name], time.perf_counter() - t0)
    assert set(dense) and "users" in emb  # both paths decoded fully
    return {
        "payload_mb": round(nbytes / 1e6, 1),
        "npz_roundtrip_ms": round(1e3 * best["npz"], 1),
        "frame_roundtrip_ms": round(1e3 * best["frame"], 1),
        "frame_speedup_x": round(best["npz"] / best["frame"], 2),
    }


def run_wire_bench(requests_per_client, max_batch_size,
                   batch_timeout_ms, blocks=BLOCKS):
    """The binary-plane acceptance leg: batched-vs-serialized ratios
    at THREE layers (endpoint, http+JSON, http+binary) as interleaved
    blocks, then the gates the ISSUE/ROADMAP name:

      1. the binary e2e ratio at c=16 must be within 25% of the
         endpoint-layer ratio (the JSON e2e ratio historically halved
         it — that dilution is what this data plane removes);
      2. binary server-side request p99 (the PR-13
         ``serving.request`` histogram) must not exceed the JSON
         path's by more than 10%;
      3. JSON and binary responses bit-identical on the same model;
      4. the router forwards binary bodies byte-identically.
    """
    conc = WIRE_CONCURRENCY
    with tempfile.TemporaryDirectory() as tmp:
        export_dir = os.path.join(tmp, "export")
        _export_mlp(export_dir)
        from elasticdl_tpu.serving.batcher import BatchConfig

        serialized = _Rig(export_dir, None,
                          payload_rows=WIRE_ROWS)
        batched = _Rig(export_dir, BatchConfig(
            max_batch_size=max_batch_size or WIRE_MAX_BATCH,
            batch_timeout_ms=batch_timeout_ms),
            payload_rows=WIRE_ROWS)
        try:
            # Bit-identity gate before any timing: JSON vs binary on
            # the SAME batched server.
            probe = _payload(3, WIRE_ROWS)
            probe["instances"] = probe["instances"] * 3
            want = np.asarray(batched.predict_http_once(probe),
                              np.float32)
            with fc.FrameClient("127.0.0.1:%d" % batched.port,
                                timeout=60) as probe_client:
                got = probe_client.predict(
                    "mlp", np.asarray(probe["instances"],
                                      np.float32))
            identical = bool(np.array_equal(want, got))
            if not identical:
                raise SystemExit("binary predictions differ from JSON")

            # Interleaved blocks with leg-order alternation; the
            # gate ratios come from each leg's BEST block (the PR-3
            # idiom): container steal/scheduling noise is strictly
            # one-sided (it only ever slows a leg), so best-of-N is
            # the consistent estimator of each leg's capability —
            # medians of the 16-threads-on-2-cores endpoint legs
            # measured +/-30% run to run and made the cross-layer
            # fraction a coin flip.  Per-block medians still ride in
            # the detail for honesty.
            results = []
            layers = ("endpoint", "http", "http_bin")
            block_ratios = {layer: [] for layer in layers}
            for block in range(blocks):
                legs = ((serialized, batched) if block % 2 == 0
                        else (batched, serialized))
                for layer in layers:
                    wall = {}
                    for rig in legs:
                        wall[rig.label] = rig.timed_block(
                            layer, conc, requests_per_client)
                    block_ratios[layer].append(
                        wall["serialized"] / wall["batched"])
            medians = {}
            for layer in layers:
                ordered = sorted(block_ratios[layer])
                medians[layer] = ordered[len(ordered) // 2]
                results.append(serialized.result(
                    layer, conc, requests_per_client))
                results.append(batched.result(
                    layer, conc, requests_per_client))
            for r in results:
                print(json.dumps(r))

            def _best_ratio(layer):
                return (serialized.best[(layer, conc)]
                        / batched.best[(layer, conc)])

            endpoint_ratio = _best_ratio("endpoint")
            json_ratio = _best_ratio("http")
            bin_ratio = _best_ratio("http_bin")
            bin_fraction = bin_ratio / max(1e-9, endpoint_ratio)
            json_fraction = json_ratio / max(1e-9, endpoint_ratio)
            p99_json = _hist_p99_ms(
                batched.counters[("http", conc)])
            p99_bin = _hist_p99_ms(
                batched.counters[("http_bin", conc)])
            router_leg = _run_router_passthrough(batched)
            transfer = _run_frame_transfer_leg()
        finally:
            serialized.close()
            batched.close()

    gates = {
        "e2e_approaches_endpoint": bool(
            bin_fraction >= WIRE_APPROACH_FLOOR),
        "p99_within_slack": bool(
            p99_json is not None and p99_bin is not None
            and p99_bin <= p99_json * WIRE_P99_SLACK),
        "bit_identical_responses": identical,
        "router_byte_identical": bool(
            router_leg["routed_status"] == 200
            and router_leg["byte_identical_response"]),
    }
    print(json.dumps({
        "metric": "serving_binary_plane",
        "value": round(bin_fraction, 3),
        "unit": "binary e2e ratio at c=%d as a fraction of the "
                "endpoint-layer ratio (best-of-block legs; 1.0 = "
                "zero transport dilution; gate >= %.2f)"
                % (conc, WIRE_APPROACH_FLOOR),
        "vs_baseline": round(json_fraction, 3),
        "detail": {
            "all_green": all(gates.values()),
            "gates": gates,
            "endpoint_ratio": round(endpoint_ratio, 2),
            "json_e2e_ratio": round(json_ratio, 2),
            "binary_e2e_ratio": round(bin_ratio, 2),
            "median_block_ratios": {
                layer: round(value, 2)
                for layer, value in sorted(medians.items())},
            "p99_ms_json_server_side": p99_json,
            "p99_ms_binary_server_side": p99_bin,
            "router_passthrough": router_leg,
            "frame_transfer": transfer,
            "concurrency": conc,
            "baseline": "self-relative: the JSON http layer on the "
                        "same rig IS the dilution baseline; "
                        "endpoint-layer ratio is the transport-free "
                        "ceiling (PR 3 measured JSON e2e at ~51% of "
                        "it on this class of rig)",
        },
    }))
    return gates


def main(argv=None):
    import argparse

    parser = argparse.ArgumentParser("bench_serving")
    parser.add_argument("--requests_per_client", type=int,
                        default=REQUESTS_PER_CLIENT)
    parser.add_argument("--max_batch_size", type=int, default=None,
                    help="batch cap; defaults to %d (default mode) or %d\n(--wire mode, sized for its 64-row slates)"
                         % (MAX_BATCH, WIRE_MAX_BATCH))
    parser.add_argument("--batch_timeout_ms", type=float,
                        default=TIMEOUT_MS)
    parser.add_argument("--fleet", action="store_true",
                        help="run the multi-replica fleet leg (replica "
                             "subprocesses behind the router, hot-swap "
                             "mid-storm, PS-backed lookup) instead of "
                             "the single-server batching comparison")
    parser.add_argument("--wire", action="store_true",
                        help="run the binary data-plane leg (JSON vs "
                             "binary frames at c=16, p99 gate off the "
                             "serving.request histogram, router "
                             "pass-through byte-identity, npz-vs-"
                             "frame transfer) instead of the batching "
                             "comparison")
    parser.add_argument("--blocks", type=int, default=BLOCKS)
    args = parser.parse_args(argv)

    if args.fleet:
        run_fleet_bench()
        return

    if args.wire:
        gates = run_wire_bench(args.requests_per_client,
                               args.max_batch_size,
                               args.batch_timeout_ms,
                               blocks=args.blocks)
        if not all(gates.values()):
            raise SystemExit("wire gates failed: %s" % gates)
        return

    from elasticdl_tpu.serving.batcher import BatchConfig

    with tempfile.TemporaryDirectory() as tmp:
        export_dir = os.path.join(tmp, "export")
        _export_mlp(export_dir)
        serialized = _Rig(export_dir, None)
        batched = _Rig(export_dir, BatchConfig(
            max_batch_size=args.max_batch_size or MAX_BATCH,
            batch_timeout_ms=args.batch_timeout_ms))
        try:
            # Numerical identity gate before any timing.
            probe = _payload(3)
            probe["instances"] = probe["instances"] * 3
            want = serialized.predict_http_once(probe)
            got = batched.predict_http_once(probe)
            identical = bool(np.array_equal(
                np.asarray(want), np.asarray(got)))
            if not identical:
                raise SystemExit(
                    "batched predictions differ from serialized")

            results = []
            for layer in ("endpoint", "http"):
                for concurrency in CONCURRENCY:
                    for _ in range(BLOCKS):  # interleaved pairs
                        serialized.timed_block(
                            layer, concurrency,
                            args.requests_per_client)
                        batched.timed_block(
                            layer, concurrency,
                            args.requests_per_client)
                    results.append(serialized.result(
                        layer, concurrency, args.requests_per_client))
                    results.append(batched.result(
                        layer, concurrency, args.requests_per_client))
            for r in results:
                print(json.dumps(r))

            by = {(r["mode"], r["layer"], r["concurrency"]): r
                  for r in results}

            def ratio(layer, conc):
                return round(
                    by[("batched", layer, conc)]["requests_per_sec"]
                    / max(1e-9, by[("serialized", layer, conc)]
                          ["requests_per_sec"]), 2)

            top = HEADLINE_CONCURRENCY
            ser = by[("serialized", "endpoint", top)]
            bat = by[("batched", "endpoint", top)]
            print(json.dumps({
                "metric": "serving_batching_throughput",
                "value": ratio("endpoint", top),
                "unit": "x predict throughput (batched vs serialized "
                        "lock, %d closed-loop clients, endpoint "
                        "layer)" % top,
                "vs_baseline": None,
                "detail": {
                    "identical_responses": identical,
                    "endpoint_speedup_by_concurrency": {
                        str(c): ratio("endpoint", c)
                        for c in CONCURRENCY},
                    "http_speedup_by_concurrency": {
                        str(c): ratio("http", c) for c in CONCURRENCY},
                    "p99_ms_serialized_endpoint": ser["p99_ms"],
                    "p99_ms_batched_endpoint": bat["p99_ms"],
                    "mean_batch_occupancy": bat[
                        "mean_batch_occupancy"],
                    "max_batch_size": args.max_batch_size
                    or MAX_BATCH,
                    "batch_timeout_ms": args.batch_timeout_ms,
                    "baseline": "self-relative: the serialized "
                                "execution-lock server IS the "
                                "baseline; reference delegates this "
                                "role to TF Serving's batcher",
                },
            }))
        finally:
            serialized.close()
            batched.close()


if __name__ == "__main__":
    main()
