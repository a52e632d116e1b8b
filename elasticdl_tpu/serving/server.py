"""Minimal model server over a servable export — the TF-Serving role.

The reference's deployment story is "export a SavedModel, point TF
Serving (or EAS) at it" (model_handler.py:242-269, docs SQLFlow
integration).  The TPU-native equivalent: ``serving/export.py`` writes
StableHLO + npz, and THIS module serves it over the same REST surface
TF Serving exposes, so clients migrating from the reference keep their
request shape:

  GET  /v1/models/<name>            -> model metadata (manifest)
  GET  /statz                       -> batching/queue/latency counters
  GET  /metrics                     -> the serving counters in
                                       Prometheus text format (the
                                       master status-server convention)
  GET  /fleet/state                 -> per-model serving/prepared
                                       versions (fleet barrier protocol)
  POST /fleet/prepare {"version"}   -> background-load + warm a version
  POST /fleet/commit  {"version"}   -> atomically publish a prepared one
  POST /v1/models/<name>:predict    -> {"predictions": [...],
       body {"instances": [...]}        "model_version": v}
       body {"inputs": {name: [...]}}     dict-input models
  POST /v1/models/<name>:lookup     -> {"vectors": [...],
       body {"table": t, "ids": [...]}    "model_version": v}

Predict/lookup responses carry the ``model_version`` that actually
served them — the fleet router and its drills verify version purity
across a hot-swap from exactly this stamp.

``:lookup`` resolves from the export's embedded tables, or — when the
server is armed with ``--ps_addrs`` — from the TRAINING parameter
servers through the PS-backed shared embedding service
(serving/embedding_service.py): tables larger than one server's RAM
serve from where they live, fronted by a byte-budgeted hot-row LRU.

On SIGTERM the server DRAINS instead of dropping connections: new
requests get 503 + ``Connection: close`` (so the router's health probe
ejects the replica), in-flight batches finish, then the process exits.

Stdlib-only HTTP (ThreadingHTTPServer, HTTP/1.1 keep-alive); jax is
needed only to execute the StableHLO — the loader stays framework-free.

Under load the hot path is the dynamic micro-batcher
(``serving/batcher.py``): request threads marshal and enqueue, one
executor thread per model coalesces concurrent requests into bucketed
padded batches and runs a single ``predict`` — see that module and
docs/serving.md.  ``--max_batch_size 1`` (or ``--enable_batching
false``) restores the serialized per-request execution-lock path.

Run: python -m elasticdl_tpu.serving.server --export_dir D [--port P]
"""

import json
import os
import signal
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from elasticdl_tpu.serving.batcher import (
    BatchConfig,
    ModelBatcher,
    batch_plan,
    is_leaf_signature,
)
from elasticdl_tpu.serving.loader import (
    load_servable,
    resolve_export_dir,
)
from elasticdl_tpu.utils import slo as slo_mod
from elasticdl_tpu.utils import tensor_codec
from elasticdl_tpu.utils import tracing
from elasticdl_tpu.utils.args import build_serving_parser
from elasticdl_tpu.utils.logging import get_logger
from elasticdl_tpu.utils.prom import serving_to_prometheus
from elasticdl_tpu.utils.timing import Timing

logger = get_logger(__name__)


def _leaf_dtypes(signature):
    """Manifest input_signature -> {key_or_None: dtype}.

    The REST surface supports a single array ({"instances": ...}) or a
    FLAT dict of arrays ({"inputs": {name: ...}}); deeper pytree inputs
    need the Python loader directly.
    """
    if is_leaf_signature(signature):
        return {None: signature["dtype"]}
    if isinstance(signature, dict):
        return {
            key: (sub.get("dtype", "float32")
                  if isinstance(sub, dict) else "float32")
            for key, sub in signature.items()
        }
    return {None: "float32"}


def _jsonable(outputs):
    """Model output pytree (array | tuple | list | dict) -> JSON.

    ndarray leaves (the batcher hands back numpy views) marshal via ONE
    direct ``.tolist()`` — no ``np.asarray`` re-wrap — and already-
    plain scalars/strings pass through untouched instead of being
    re-copied leaf-by-leaf through numpy; only a genuinely foreign
    leaf (a live jax array on the serialized path) pays the one
    ``np.asarray`` materialization."""
    if isinstance(outputs, np.ndarray):
        return outputs.tolist()
    if isinstance(outputs, np.generic):
        return outputs.item()
    if isinstance(outputs, dict):
        return {k: _jsonable(v) for k, v in outputs.items()}
    if isinstance(outputs, (list, tuple)):
        return [_jsonable(v) for v in outputs]
    if outputs is None or isinstance(outputs, (bool, int, float, str)):
        return outputs
    return np.asarray(outputs).tolist()


class ModelEndpoint:
    """One loaded servable + request/response marshalling.

    When ``export_dir`` is a versioned base (``<base>/<N>/`` numeric
    subdirs, the TF-Serving layout the reference's deployment story
    assumes — model_handler.py:242-269), the endpoint serves the latest
    complete version and hot-swaps when a newer one appears: each
    request re-scans at most once per ``poll_interval`` seconds (a
    single listdir), loads the new servable OUTSIDE the execution lock,
    and swaps it in under the lock, so in-flight predicts finish on the
    old model and later ones see the new one.
    """

    def __init__(self, export_dir, name=None, poll_interval=2.0,
                 batching=None, fleet_managed=False,
                 embedding_service=None, boot_version=None):
        self.export_dir = export_dir
        self.poll_interval = poll_interval
        # Fleet-managed replicas NEVER self-swap from a local disk scan:
        # version changes arrive only through the coordinator's
        # prepare/commit barrier (serving/fleet.py), so a replica
        # rejoining mid-rollout cannot regress — or race ahead of — the
        # fleet's committed version just because of what its local
        # export dir happens to hold.
        self.fleet_managed = bool(fleet_managed)
        # PS-backed embedding lookups (embedding_service.py); one
        # service per endpoint — its cache is keyed by THIS model's
        # version, re-keyed on every publish.
        self._embedding_service = embedding_service
        # boot_version pins the INITIAL load (the autoscaler spawns
        # replicas pinned to the fleet's committed version so a fresh
        # spawn mid-canary can't boot ahead of the fleet off its own
        # disk scan); later versions arrive via reload/barrier as ever.
        self.model = load_servable(
            export_dir if boot_version is None
            else resolve_export_dir(export_dir, version=boot_version))
        # Versioned mode iff the base itself is not a direct export —
        # then the loader resolved a numeric subdir we can re-scan.
        self._versioned = not os.path.isfile(
            os.path.join(export_dir, "manifest.json"))
        self._loaded_dir = self.model.export_dir
        self._last_scan = time.monotonic()
        self.name = name or self.model.manifest.get("model_name") or (
            "model"
        )
        self._dtypes = _leaf_dtypes(
            self.model.manifest.get("input_signature", {})
        )
        # Batching config (serving/batcher.BatchConfig) — None, or a
        # disabled config (max_batch_size 1), keeps the original
        # serialized per-request execution-lock path EXACTLY.
        self._batching = batching if (
            batching is not None and batching.enabled) else None
        self.timing = Timing()
        plan = (batch_plan(self.model.manifest)
                if self._batching is not None else None)
        # (model, dtypes, plan) as ONE tuple: a single attribute
        # assignment is atomic, so a request never marshals with one
        # version's dtypes and executes another version's model.
        self._active = (self.model, self._dtypes, plan)
        self._lock = threading.Lock()  # jax.export call is not
        # documented thread-safe; serialize execution, marshal outside
        self._reload_lock = threading.Lock()  # scan/load/swap critical
        # section — never held during predict execution
        self._batcher = None
        self._reload_thread = None
        # Fleet barrier slots, all guarded by _reload_lock: the version
        # being background-prepared, the warm prepared servable waiting
        # for its commit, and the last prepare failure.
        self._preparing = None
        self._prepared = None          # (version, model, dtypes, plan)
        self._prepare_error = None
        self._prepare_thread = None
        if self._embedding_service is not None:
            self._embedding_service.set_version(self.serving_version())
        if self._batching is not None:
            self._warm_buckets(self.model, plan)
            self._batcher = ModelBatcher(
                self._batching, reload_fn=self.maybe_reload,
                execute_lock=self._lock, timing=self.timing,
                name=self.name)

    def _snapshot(self):
        """THE unlocked read of the atomic ``(model, dtypes, plan)``
        triple — every consumer (predict, lookup, metadata, stats)
        routes through here, so a hot-swap can never interleave one
        version's manifest/dtypes with another version's weights."""
        return self._active

    def _warm_buckets(self, model, plan):
        """Pre-run ``predict`` at every pad bucket so the export's
        per-shape XLA compiles happen NOW — at load / hot-swap time,
        before the model takes traffic — and no live request pays a
        cold compile.  Called on the fresh model BEFORE it is swapped
        in, so the warm old version keeps serving meanwhile."""
        if plan is None or not self._batching.warm:
            return
        for bucket in self._batching.pad_buckets:
            try:
                # Per-bucket lock acquisition: warmup may run on a
                # request thread (a metadata() reload) while the
                # executor serves the OLD model — exported.call is not
                # documented thread-safe, so even different-model
                # predicts serialize; live traffic interleaves between
                # bucket warms rather than stalling for all of them.
                with self.timing.timeit("batcher.warmup"), self._lock:
                    model.predict(model.dummy_inputs(bucket))
            except Exception as e:  # noqa: BLE001 — a model whose
                # zero-input crashes still serves real traffic; it just
                # pays its compiles lazily.
                logger.warning("bucket-%d warmup failed for %r: %s",
                               bucket, self.name, e)
                return
        self.timing.bump("batcher.warmed_models")

    def close(self):
        """Stop the batcher executor thread (pending requests fail
        fast); the endpoint itself holds no other resources."""
        if self._batcher is not None:
            self._batcher.close()

    def maybe_reload(self):
        """Hot-swap to a newer complete version, if one has appeared.

        The steady-state cost is ONE listdir per poll_interval
        (resolve_export_dir); the full servable load happens only when
        the resolved dir actually changed.  On the serialized path the
        scan/load/swap runs synchronously on the calling request
        thread, as it always has.  With batching enabled the caller is
        the batcher executor (or a metadata request) — neither may
        stall the admission queue behind a servable load plus bucket
        warmup — so the heavy work runs on a short-lived background
        thread and the new version publishes (atomically, warm) when
        ready; in-flight and in-queue requests finish on the model
        they were admitted under either way."""
        if self.fleet_managed:
            return  # version changes only via prepare/commit barrier
        if not self._versioned:
            return
        if time.monotonic() - self._last_scan < self.poll_interval:
            return
        if self._batcher is None:
            self._scan_and_swap()
            return
        thread = self._reload_thread
        if thread is not None and thread.is_alive():
            return
        # Benign race: two threads may both spawn; _scan_and_swap
        # itself is serialized by _reload_lock and re-checks the scan
        # clock, so the loser is a no-op.
        thread = threading.Thread(target=self._scan_and_swap,
                                  daemon=True,
                                  name="reload-%s" % self.name)
        self._reload_thread = thread
        thread.start()

    def _scan_and_swap(self):
        """One version scan; on change: load + warm the fresh model,
        then publish it.  Runs under the dedicated reload lock so
        concurrent callers can neither duplicate the load nor swap
        versions out of order (the execution lock stays free for
        predicts on the old model while a new one loads)."""
        with self._reload_lock:
            now = time.monotonic()
            if now - self._last_scan < self.poll_interval:
                return  # another thread just scanned
            self._last_scan = now
            try:
                resolved = resolve_export_dir(self.export_dir)
                if resolved == self._loaded_dir:
                    return
                fresh = load_servable(resolved)
            except (OSError, ValueError) as e:
                logger.warning("version rescan failed: %s", e)
                return
            dtypes = _leaf_dtypes(
                fresh.manifest.get("input_signature", {}))
            plan = (batch_plan(fresh.manifest)
                    if self._batching is not None else None)
            # Warm the fresh model's pad buckets BEFORE publishing it:
            # traffic keeps hitting the warm old version while the new
            # one compiles its bucket shapes.
            self._warm_buckets(fresh, plan)
            with self._lock:
                self.model = fresh
                self._dtypes = dtypes
                self._active = (fresh, dtypes, plan)
                self._loaded_dir = fresh.export_dir
        if self._embedding_service is not None:
            self._embedding_service.set_version(
                fresh.manifest.get("version", 0))
        logger.info("reloaded model %r from %s (version %s)",
                    self.name, fresh.export_dir,
                    fresh.manifest.get("version"))

    # -- fleet hot-swap barrier (serving/fleet.py drives these) ---------

    def serving_version(self):
        """Version of the model CURRENTLY serving traffic."""
        return int(self._snapshot()[0].manifest.get("version", 0) or 0)

    def prepare_version(self, version, rollback=False):
        """Background-load + warm export version ``version`` without
        publishing it (phase 1 of the fleet barrier): traffic keeps
        hitting the warm serving model while the incoming version
        compiles its pad buckets.  Idempotent; returns the fleet-state
        dict so the coordinator can poll readiness off the reply.

        ``rollback`` (the canary-rollback push): preparing a version
        BELOW the serving one is normally short-circuited as "already
        there" — the flag makes it actually load, so the matching
        ``commit_version(..., rollback=True)`` has a warm model to
        swap down to."""
        version = int(version)
        start = False
        with self._reload_lock:
            serving_ok = (self.serving_version() == version
                          if rollback
                          else self.serving_version() >= version)
            already = (
                serving_ok
                or (self._prepared is not None
                    and self._prepared[0] == version)
                or (self._preparing == version
                    and self._prepare_thread is not None
                    and self._prepare_thread.is_alive())
            )
            if not already:
                self._preparing = version
                self._prepare_error = None
                thread = threading.Thread(
                    target=self._prepare_worker, args=(version,),
                    daemon=True, name="prepare-%s" % self.name)
                self._prepare_thread = thread
                start = True
        if start:
            thread.start()
        return self.fleet_state()

    def _prepare_worker(self, version):
        """Load + warm one pinned version; park it in the prepared
        slot.  Runs OUTSIDE the reload lock — only the slot update
        takes it — so a fleet prepare never stalls /fleet/state polls
        or (non-fleet) scan-and-swap behind an XLA warmup."""
        try:
            resolved = resolve_export_dir(self.export_dir,
                                          version=version)
            fresh = load_servable(resolved)
            dtypes = _leaf_dtypes(
                fresh.manifest.get("input_signature", {}))
            plan = (batch_plan(fresh.manifest)
                    if self._batching is not None else None)
            self._warm_buckets(fresh, plan)
        except Exception as e:  # noqa: BLE001 — a bad/missing export
            # must surface on /fleet/state, not kill the thread silently
            logger.warning("prepare of version %d failed: %s",
                           version, e)
            with self._reload_lock:
                if self._preparing == version:
                    self._prepare_error = "%s: %s" % (
                        type(e).__name__, e)
                    self._preparing = None
            return
        with self._reload_lock:
            if self._preparing == version:
                self._prepared = (version, fresh, dtypes, plan)
                self._preparing = None

    def commit_version(self, version, rollback=False):
        """Phase 2 of the fleet barrier: atomically publish a PREPARED
        version.  Refuses a version below the one already serving — a
        coordinator healing a rejoined replica can therefore never
        regress it — and refuses an un-prepared version (the
        coordinator re-prepares and retries).  In-queue requests
        admitted before the flip finish on the model they were
        marshalled against (the batcher's version purity): stale-version
        traffic drains, it never mixes.

        ``rollback`` waives the regression refusal for exactly ONE
        caller: the coordinator's canary rollback, a deliberate
        operator-path downgrade of a canary replica back to the
        fleet's committed version (docs/serving.md "The online loop").
        The plain barrier/heal path never sets it, so a confused
        coordinator still cannot regress a replica by accident."""
        version = int(version)
        with self._reload_lock:
            serving = self.serving_version()
            if serving == version:
                return {"committed": True, "serving": serving}
            if version < serving and not rollback:
                return {"committed": False, "serving": serving,
                        "error": "version %d would regress serving "
                                 "version %d" % (version, serving)}
            if self._prepared is None or self._prepared[0] != version:
                return {"committed": False, "serving": serving,
                        "error": "version %d not prepared" % version}
            if version < serving:
                logger.warning(
                    "ROLLBACK commit: model %r serving %d -> %d",
                    self.name, serving, version)
            _, fresh, dtypes, plan = self._prepared
            self._prepared = None
            with self._lock:
                self.model = fresh
                self._dtypes = dtypes
                self._active = (fresh, dtypes, plan)
                self._loaded_dir = fresh.export_dir
        if self._embedding_service is not None:
            # Version-keyed cache invalidation: PS-backed rows never
            # survive a version flip (docs/serving.md fleet section).
            self._embedding_service.set_version(version)
        # In the coordinator's trace (the commit arrives as an HTTP
        # POST, so no gRPC propagation — the replica-local instant is
        # still the serving half of the barrier timeline).
        tracing.event("serving.version_commit", model=self.name,
                      version=version, rollback=bool(rollback))
        logger.info("fleet commit: model %r now serving version %d",
                    self.name, version)
        return {"committed": True, "serving": version}

    def fleet_state(self):
        """Barrier-protocol view: what this replica serves, what it has
        warm and ready, what it is still preparing."""
        with self._reload_lock:
            prepared = (self._prepared[0] if self._prepared is not None
                        else None)
            preparing = self._preparing
            error = self._prepare_error
        return {
            "serving": self.serving_version(),
            "prepared": prepared,
            "preparing": preparing,
            "error": error,
        }

    def metadata(self):
        self.maybe_reload()
        model = self._snapshot()[0]
        return {
            "model_version_status": [{
                "version": str(model.manifest.get("version", 0)),
                "state": "AVAILABLE",
            }],
            "metadata": model.manifest,
        }

    # Window for the replica-reported recent queue wait (see stats():
    # one probe interval's worth of "how loaded am I right now").
    RECENT_WINDOW_SECS = 2.0

    def stats(self):
        """/statz payload: live version, batching config, Timing
        counters (batch occupancy, queue wait, execution time), the
        queue-wait/execute HISTOGRAMS (native Prometheus rendering +
        p99 for anyone reading /statz raw), and the windowed
        ``queue_wait_recent_ms`` — the replica's OWN recent-load
        signal, so the router/autoscaler's probe-differencing becomes
        a cross-check instead of the only recent series."""
        model = self._snapshot()[0]
        counters = self.timing.counters()
        batches = counters.get("batcher.batches", 0)
        out = {
            "model": self.name,
            "version": model.manifest.get("version", 0),
            "batching": (self._batching.describe()
                         if self._batching is not None else None),
            "counters": counters,
            "timing": self.timing.summary(),
            "mean_batch_occupancy": (
                counters.get("batcher.rows", 0) / batches
                if batches else None),
            "hists": self.timing.histograms(
                names=("batcher.queue_wait", "batcher.execute",
                       "serving.request")),
        }
        recent = self.timing.recent("batcher.queue_wait",
                                    self.RECENT_WINDOW_SECS)
        if recent is not None and recent["count"] > 0:
            out["queue_wait_recent_ms"] = (
                1e3 * recent["sum"] / recent["count"])
        elif recent is not None:
            out["queue_wait_recent_ms"] = 0.0
        if self._embedding_service is not None:
            out["emb_cache"] = self._embedding_service.stats()
        return out

    def predict(self, body):
        if self._batcher is None:
            # Serialized path: reload checks stay on request threads
            # (the batcher executor does them between batches instead).
            self.maybe_reload()
        model, dtypes, plan = self._snapshot()
        if "instances" in body:
            dtype = dtypes.get(None, "float32")
            inputs = np.asarray(body["instances"], dtype=dtype)
        elif "inputs" in body:
            inputs = {
                key: np.asarray(
                    value, dtype=dtypes.get(key, "float32")
                )
                for key, value in body["inputs"].items()
            }
        else:
            raise ValueError("body needs 'instances' or 'inputs'")
        outputs = self._execute_predict(model, plan, inputs)
        # The version stamp is read from the SAME snapshot the request
        # executed against (batches never mix models), so the fleet
        # router's drills can assert version purity from responses.
        return {"predictions": _jsonable(outputs),
                "model_version": int(model.manifest.get("version", 0)
                                     or 0)}

    def _execute_predict(self, model, plan, inputs):
        """ONE execution point for both content types: the batcher's
        admission queue when batching is on, the serialized
        execution-lock path (the documented off-switch behavior)
        otherwise."""
        if self._batcher is not None:
            return self._batcher.predict(model, plan, inputs)
        with self._lock:
            return model.predict(inputs)

    def lookup(self, body):
        if self._batcher is None:
            self.maybe_reload()
        model = self._snapshot()[0]
        table = body["table"]
        ids = np.asarray(body["ids"], np.int64)
        version = int(model.manifest.get("version", 0) or 0)
        if self._embedding_service is not None and (
                body.get("source") == "ps"
                or table not in model.embeddings):
            # PS-backed shared embedding service: the table serves from
            # the training PS shards (it may never have been exported at
            # all), fronted by the per-model hot-row cache.  Network-
            # bound, touches no model state — so it runs on the request
            # thread, concurrent, never convoying device batches behind
            # a PS round trip on the executor.
            vectors = self._embedding_service.lookup(table, ids)
            return {"vectors": vectors.tolist(),
                    "model_version": version, "source": "ps"}
        if self._batcher is not None:
            # Same admission queue as predicts: a lookup executes on
            # ONE model snapshot, never racing a hot-swap mid-read.
            vectors = self._batcher.lookup(model, table, ids)
        else:
            vectors = model.lookup_embedding(table, ids)
        return {"vectors": vectors.tolist(), "model_version": version,
                "source": "export"}

    # -- binary frame surface (docs/serving.md "Wire protocol") --------

    @staticmethod
    def _response_wire(frame):
        """Per-request bf16 opt-in: ``meta.response_wire`` asks for the
        RESPONSE payload in a reduced-precision wire dtype (the request
        payload declares its own encoding per tensor)."""
        wire = frame.meta.get("response_wire")
        if wire is None:
            return None
        if wire not in tensor_codec.WIRE_DTYPES:
            raise ValueError(
                "response_wire %r not supported (one of %s)"
                % (wire, list(tensor_codec.WIRE_DTYPES)))
        return wire

    @staticmethod
    def _cast(arr, dtype_name_):
        """The frame view is already a typed ndarray: pass it straight
        through when the dtype matches (zero-copy into the batcher),
        cast once when the manifest disagrees — never via Python
        lists."""
        want = np.dtype(dtype_name_)
        return arr if arr.dtype == want else arr.astype(want)

    def predict_frame(self, frame):
        """Binary ``:predict``: inputs come in as zero-copy frame
        views ({"instances": x} for array-input models, one named
        tensor per leaf for dict-input models) and go into the SAME
        batcher admission queue as JSON requests — coalescing, version
        purity, and hot-swap discipline are content-type-blind.
        Returns the encoded response frame (kind "predictions", the
        output pytree flattened with its tree spec in meta)."""
        if self._batcher is None:
            self.maybe_reload()
        model, dtypes, plan = self._snapshot()
        tensors = frame.tensors
        if not tensors:
            raise ValueError("predict frame carries no tensors")
        if None in dtypes:
            # Array-input model (leaf signature): exactly one tensor,
            # named "instances" (the JSON body's key).  The MODEL's
            # signature decides the marshal shape — a dict-input model
            # may legitimately have an input leaf named "instances".
            if set(tensors) != {"instances"}:
                raise ValueError(
                    "array-input model expects exactly one "
                    "'instances' tensor, got %s" % sorted(tensors))
            inputs = self._cast(tensors["instances"],
                                dtypes.get(None, "float32"))
        else:
            inputs = {
                key: self._cast(arr, dtypes.get(key, "float32"))
                for key, arr in tensors.items()
            }
        outputs = self._execute_predict(model, plan, inputs)
        out_tensors, spec = tensor_codec.flatten_tree(outputs,
                                                      prefix="p")
        return tensor_codec.encode_frame(
            out_tensors, kind="predictions",
            model_version=int(model.manifest.get("version", 0) or 0),
            wire_dtype=self._response_wire(frame),
            meta={"tree": spec})

    def lookup_frame(self, frame):
        """Binary ``:lookup``: ids ride as one int64 tensor, the table
        name in meta; vectors come back as one tensor — no row lists
        in either direction.  PS-backed tables resolve exactly as on
        the JSON path."""
        if self._batcher is None:
            self.maybe_reload()
        model = self._snapshot()[0]
        table = frame.meta.get("table")
        if not table:
            raise ValueError("lookup frame needs meta.table")
        ids_view = frame.tensors.get("ids")
        if ids_view is None:
            raise ValueError("lookup frame needs an 'ids' tensor")
        ids = self._cast(ids_view, "int64")
        version = int(model.manifest.get("version", 0) or 0)
        wire = self._response_wire(frame)
        if self._embedding_service is not None and (
                frame.meta.get("source") == "ps"
                or table not in model.embeddings):
            vectors = self._embedding_service.lookup(table, ids)
            source = "ps"
        elif self._batcher is not None:
            vectors = self._batcher.lookup(model, table, ids)
            source = "export"
        else:
            vectors = model.lookup_embedding(table, ids)
            source = "export"
        return tensor_codec.encode_frame(
            {"vectors": vectors}, kind="vectors",
            model_version=version, wire_dtype=wire,
            meta={"source": source})


class DrainController:
    """Graceful-drain state for one serving process.

    On SIGTERM (``begin``) the replica stops ADMITTING: new POSTs get
    503 + ``Connection: close`` so the router's health probe ejects it
    and keep-alive clients reconnect elsewhere, while every already-
    admitted request — including whole in-queue batches — runs to
    completion (``wait_idle``).  The HTTP server only shuts down once
    the in-flight count hits zero (or the grace budget runs out), so a
    SIGTERM never drops a request mid-batch the way a bare process exit
    did."""

    def __init__(self):
        self._lock = threading.Lock()
        self._inflight = 0
        self._draining = threading.Event()

    @property
    def draining(self):
        return self._draining.is_set()

    def begin(self):
        self._draining.set()

    def admit(self):
        """True = request admitted (caller MUST pair with done());
        False = draining, reply 503."""
        with self._lock:
            if self._draining.is_set():
                return False
            self._inflight += 1
            return True

    def done(self):
        with self._lock:
            self._inflight -= 1

    def inflight(self):
        with self._lock:
            return self._inflight

    def wait_idle(self, timeout):
        """Poll until every admitted request finished; True on idle,
        False when the grace budget ran out first."""
        deadline = time.monotonic() + timeout
        while True:
            if self.inflight() <= 0:
                return True
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.02)


def install_drain_handler(server, endpoints, drain, grace_secs=10.0):
    """Arm SIGTERM for graceful drain (main-thread only — the signal
    module's constraint): stop admitting, let in-flight batches finish,
    then stop the HTTP server and the batcher executors."""

    def drain_and_stop():
        logger.info("SIGTERM: draining (%d in flight, grace %.1fs)",
                    drain.inflight(), grace_secs)
        idle = drain.wait_idle(grace_secs)
        if not idle:
            logger.warning("drain grace expired with %d in flight",
                           drain.inflight())
        server.shutdown()
        # Close the LISTENING socket immediately: a late client must
        # get connection-refused (clean, instantly retryable
        # elsewhere), not a connection the dead serve loop will never
        # answer.  serve_forever's own server_close is a no-op after
        # this.
        server.server_close()
        for endpoint in endpoints:
            endpoint.close()

    def on_sigterm(_signum, _frame):
        drain.begin()
        # The actual wait runs off the signal frame: a handler must
        # not block (it may have interrupted arbitrary code).
        threading.Thread(target=drain_and_stop, daemon=True,
                         name="drain").start()

    signal.signal(signal.SIGTERM, on_sigterm)


def build_server(endpoints, port=0, host="127.0.0.1", drain=None):
    """``endpoints``: one ModelEndpoint or a list — the TF-Serving
    model-config role: one server process hosts several models, each
    under its own /v1/models/<name> tree.  ``drain``: a
    :class:`DrainController`; one is built when omitted and exposed as
    ``server.drain``."""
    if isinstance(endpoints, ModelEndpoint):
        endpoints = [endpoints]
    by_name = {e.name: e for e in endpoints}
    if len(by_name) != len(endpoints):
        raise ValueError(
            "duplicate model names: %s"
            % sorted(e.name for e in endpoints))
    drain = drain if drain is not None else DrainController()

    # Routing tables built ONCE: O(1) dispatch per request.  POST
    # routes carry (endpoint, json handler, frame handler): the same
    # path serves both content types, negotiated per request.
    get_paths = {}
    post_routes = {}
    for name, endpoint in by_name.items():
        base = "/v1/models/%s" % name
        # TF Serving clients also GET <base>/metadata; serve the
        # alias so their request shape carries over.
        get_paths[base] = endpoint.metadata
        get_paths[base + "/metadata"] = endpoint.metadata
        post_routes[base + ":predict"] = (
            endpoint, endpoint.predict, endpoint.predict_frame)
        post_routes[base + ":lookup"] = (
            endpoint, endpoint.lookup, endpoint.lookup_frame)

    class Handler(BaseHTTPRequestHandler):
        # HTTP/1.1 => persistent connections: without this every
        # request pays a fresh TCP handshake (BaseHTTPRequestHandler
        # defaults to HTTP/1.0 + Connection: close), which throttles
        # real clients and pollutes benchmarks.  Safe here because
        # _reply ALWAYS sets Content-Length, including error replies.
        protocol_version = "HTTP/1.1"
        # Kill the Nagle/delayed-ACK interaction on the response path:
        # the stdlib handler writes the header block and the body as
        # SEPARATE sends, and on keep-alive connections the second
        # small segment sits behind the peer's delayed ACK — measured
        # 44 ms per request on this kernel, i.e. the entire serving
        # latency budget.  TCP_NODELAY plus a buffered wfile (one
        # segment per response, flushed by handle_one_request) makes a
        # small predict ~0.8 ms end-to-end.
        disable_nagle_algorithm = True
        wbufsize = -1

        def log_message(self, fmt, *args):  # route through our logger
            logger.debug("http: " + fmt, *args)

        def _reply(self, code, payload, close=False):
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            if close:
                # Advertise the close so keep-alive clients (and the
                # router's connection pool) re-connect elsewhere
                # instead of finding a dead socket mid-request later.
                self.send_header("Connection", "close")
                self.close_connection = True
            self.end_headers()
            self.wfile.write(body)

        def _reply_text(self, code, text, content_type):
            body = text.encode()
            self.send_response(code)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _statz(self):
            out = {
                "draining": drain.draining,
                "models": {name: endpoint.stats()
                           for name, endpoint in by_name.items()},
            }
            slo = slo_mod.slo_section()
            if slo is not None:
                out["slo"] = slo
            return out

        def do_GET(self):
            if self.path == "/healthz":
                # liveness/readiness probe target (matches the
                # master's and PS's observability surface); a draining
                # replica fails the probe so orchestrators and the
                # router stop sending traffic before the socket dies.
                if drain.draining:
                    return self._reply(503, {"status": "draining"},
                                       close=True)
                return self._reply(200, {"status": "ok"})
            if self.path == "/statz":
                # Batching observability: per-model batch occupancy,
                # queue wait, execution time, flush reasons — plus the
                # drain flag the router's health probe keys on.
                return self._reply(200, self._statz())
            if self.path == "/metrics":
                # The same numbers in Prometheus exposition format
                # (shared utils/prom.py renderer), so the router and
                # the fleet drills scrape one format everywhere.
                return self._reply_text(
                    200, serving_to_prometheus(self._statz()),
                    "text/plain; version=0.0.4")
            if tracing.is_tracez_path(self.path):
                # Live flight recorder (utils/tracing.py): hot-swap
                # barrier spans and lookup incidents, same query API
                # as every other tier's /tracez.
                return self._reply_text(
                    200, tracing.tracez_body(self.path),
                    "application/json")
            if slo_mod.is_alertz_path(self.path):
                # The SLO watchdog surface (utils/slo.py), same API
                # as every other tier's /alertz.
                return self._reply_text(
                    200, slo_mod.alertz_body(), "application/json")
            if tracing.is_profilez_path(self.path):
                # On-demand jax.profiler capture; blocks this request
                # thread only (the executor keeps serving).
                return self._reply_text(
                    200, tracing.profilez_body(self.path),
                    "application/json")
            if self.path == "/fleet/state":
                return self._reply(200, {
                    "draining": drain.draining,
                    "models": {name: endpoint.fleet_state()
                               for name, endpoint in by_name.items()},
                })
            handler = get_paths.get(self.path)
            if handler is not None:
                return self._reply(200, handler())
            self._reply(404, {"error": "unknown path %r (models: %s)"
                              % (self.path, sorted(by_name))})

        def _reply_bytes(self, code, blob, content_type):
            self.send_response(code)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(blob)))
            self.end_headers()
            self.wfile.write(blob)

        def do_POST(self):
            if self.headers.get("Transfer-Encoding") or (
                    "Content-Length" not in self.headers):
                # Keep-alive framing depends on Content-Length: a
                # chunked body we don't parse would desync the
                # persistent connection (its bytes would be read as
                # the next request line).  411 + close instead.
                self.close_connection = True
                return self._reply(
                    411, {"error": "Content-Length required "
                                   "(chunked bodies unsupported)"})
            length = int(self.headers.get("Content-Length", 0))
            raw = self.rfile.read(length)
            # Content-type negotiation (docs/serving.md "Wire
            # protocol"): the binary frame content type takes the
            # zero-copy path; anything else is the JSON compatibility
            # fallback.  Errors are ALWAYS JSON, whatever came in.
            binary = tensor_codec.is_frame_content_type(
                self.headers.get("Content-Type"))
            frame = body = None
            if binary:
                try:
                    frame = tensor_codec.decode_frame(raw)
                except tensor_codec.FrameError as e:
                    return self._reply(400, {"error": "bad frame: %s"
                                             % e})
            else:
                try:
                    # ValueError covers JSONDecodeError AND the
                    # UnicodeDecodeError a non-UTF-8 body raises.
                    body = json.loads(raw or b"{}")
                except ValueError as e:
                    return self._reply(400,
                                       {"error": "bad JSON: %s" % e})
            if not drain.admit():
                # Draining: refuse + close so the client's next request
                # opens against a healthy replica (the router also
                # ejects us off this signal / the failed probe).
                return self._reply(503, {"error": "draining"},
                                   close=True)
            try:
                if self.path == "/fleet/prepare":
                    return self._reply(200, {
                        name: endpoint.prepare_version(
                            body["version"],
                            rollback=bool(body.get("rollback")))
                        for name, endpoint in by_name.items()})
                if self.path == "/fleet/commit":
                    return self._reply(200, {
                        name: endpoint.commit_version(
                            body["version"],
                            rollback=bool(body.get("rollback")))
                        for name, endpoint in by_name.items()})
                route = post_routes.get(self.path)
                if route is None:
                    return self._reply(
                        404, {"error": "unknown path %r (models: %s)"
                              % (self.path, sorted(by_name))})
                endpoint, json_fn, frame_fn = route
                # Server-side request latency (marshal + queue +
                # execute + RESPONSE ENCODE — json.dumps runs inside
                # the window on the JSON path so both content types
                # measure the same span) as a PR-13 histogram — the
                # p99 the bench gate and /metrics read.  Local start:
                # handler threads run concurrently.
                t0 = time.monotonic()
                if binary:
                    blob = frame_fn(frame)
                    content_type = tensor_codec.FRAME_CONTENT_TYPE
                else:
                    blob = json.dumps(json_fn(body)).encode()
                    content_type = "application/json"
                endpoint.timing.observe("serving.request",
                                        time.monotonic() - t0)
                return self._reply_bytes(200, blob, content_type)
            except (KeyError, ValueError, TypeError) as e:
                self._reply(400, {"error": str(e)})
            except Exception as e:  # noqa: BLE001 — runtime failures
                # (e.g. an XLA error) must return 500, not crash the
                # handler thread and drop the connection.
                logger.warning("request failed: %s", e)
                self._reply(500, {"error": "%s: %s"
                                  % (type(e).__name__, e)})
            finally:
                drain.done()

    server = ThreadingHTTPServer((host, port), Handler)
    server.drain = drain
    return server


def batch_config_from_args(args):
    """CLI knobs -> BatchConfig (or None when batching is off:
    ``--enable_batching false`` or ``--max_batch_size 1`` both restore
    the serialized per-request path exactly)."""
    if not args.enable_batching or args.max_batch_size <= 1:
        return None
    buckets = [int(piece) for piece in
               str(args.pad_buckets or "").split(",") if piece.strip()]
    return BatchConfig(
        max_batch_size=args.max_batch_size,
        batch_timeout_ms=args.batch_timeout_ms,
        pad_buckets=buckets or None,
        warm=args.warm_buckets,
    )


def main(argv=None):
    args = build_serving_parser().parse_args(argv)
    tracing.configure_identity("serving", rank=args.port)
    # A restarted replica must find what its predecessor compiled.
    from elasticdl_tpu.utils.device import place_compile_cache

    place_compile_cache()
    # Multi-model form: EVERY comma-piece must be name=dir (a single
    # path that merely CONTAINS '=' is not a spec list).
    pieces = [p.strip() for p in args.export_dir.split(",")
              if p.strip()]
    is_multi = len(pieces) > 0 and all(
        "=" in p and p.partition("=")[0].strip()
        and p.partition("=")[2].strip() for p in pieces
    ) and ("=" in args.export_dir)
    batching = batch_config_from_args(args)
    multi = is_multi and (len(pieces) > 1 or os.path.sep not in
                          pieces[0].partition("=")[0])
    n_models = len(pieces) if multi else 1

    # PS-backed embedding lookups: ONE retry-armed PSClient per
    # process (channels are shared), but one service PER MODEL — the
    # hot-row cache is keyed by the model's OWN version counter, so
    # model a's hot-swap can neither wipe nor permanently out-key
    # model b's cache (version counters are independent per model).
    # The byte budget splits evenly across models.
    ps_client = None
    if args.ps_addrs:
        from elasticdl_tpu.utils.retry import ps_rpc_policy
        from elasticdl_tpu.worker.ps_client import build_ps_client

        ps_client = build_ps_client(args.ps_addrs,
                                    retry=ps_rpc_policy())

    def kwargs():
        service = None
        if ps_client is not None:
            from elasticdl_tpu.serving.embedding_service import (
                PSEmbeddingService,
            )

            service = PSEmbeddingService(
                ps_client,
                cache_bytes=int(args.emb_cache_mb * (1 << 20))
                // n_models,
            )
        return dict(
            poll_interval=args.poll_interval, batching=batching,
            fleet_managed=args.fleet_managed,
            embedding_service=service,
            boot_version=(args.boot_version
                          if args.boot_version >= 0 else None),
        )

    if multi:
        if args.model_name:
            logger.warning(
                "--model_name %r ignored: the name=dir form names "
                "each model explicitly", args.model_name)
        endpoints = [
            ModelEndpoint(p.partition("=")[2].strip(),
                          name=p.partition("=")[0].strip(), **kwargs())
            for p in pieces
        ]
    else:
        endpoints = [ModelEndpoint(args.export_dir,
                                   name=args.model_name, **kwargs())]
    server = build_server(endpoints, port=args.port, host=args.host)
    # SLO rules from the environment (ELASTICDL_SLO_SPEC, e.g.
    # "p99(batcher.queue_wait) < 0.05"): pXX()/mean() phases resolve
    # against the first endpoint's Timing (the single-model common
    # case; multi-model processes name sources explicitly in code).
    slo_mod.default_watchdog().bind_timing(endpoints[0].timing)
    slo_mod.default_watchdog().arm_from_env()
    install_drain_handler(server, endpoints, server.drain,
                          grace_secs=args.drain_grace_secs)
    # AFTER the drain hook: SIGTERM dumps the flight recorder, then
    # the drain chain runs ($ELASTICDL_TRACE_DIR gates the dump).
    tracing.arm_crash_dump()
    logger.info(
        "serving model(s) %s on %s:%d (predict: POST "
        "/v1/models/<name>:predict; batching: %s; fleet_managed: %s; "
        "ps_addrs: %s)",
        sorted(e.name for e in endpoints), args.host,
        server.server_address[1],
        batching.describe() if batching else "off",
        args.fleet_managed, args.ps_addrs or "-",
    )
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.server_close()
        for endpoint in endpoints:
            endpoint.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
