"""Fused-step training driver — the worker hot loop's windowed path.

The per-step loop pays three host costs on every minibatch: a device
sync (``float(loss)``), padding/reshape host work on the critical path,
and one ``report_batch_done`` RPC.  This driver amortizes all three
over a window of K steps while preserving elastic semantics *by
construction*:

 - **Multi-step dispatch**: K prefetched minibatches are stacked on the
   leading axis and run as ONE XLA program
   (``trainer.build_fused_window`` — a ``lax.scan`` of the raw step),
   so host dispatch latency amortizes over K optimizer steps.
 - **Device double-buffer**: batch padding/reshape/globalize runs in
   the ``prefetch_batches`` producer stage (``trainer.prepare_batch``),
   and the NEXT window is stacked and ``device_put`` while the current
   window's program is still executing — host feed and host→device
   transfer overlap the running step.
 - **Async loss cadence**: losses stay device-resident in a
   ``LossRing``; the only host syncs are one fetch per log cadence,
   one task-final fence (so a task is reported complete only after its
   last window verifiably finished), and one fence on preemption.

Composes with ``--zero1`` (ZeRO-1 weight-update sharding): the fused
window's opt-state carry is then the flat sharded form — each chained
step hands 1/N of the optimizer state to the next instead of a full
replicated copy — and window dispatches count their reduce-scatter /
all-gather payloads into ``Timing.summary()['zero1']``
(docs/training_pipeline.md has the carry-size math).

Elasticity is preserved because the window is **clamped** to the
distance to the next report/version/checkpoint/log/elastic-check
boundary (``_window_limit``) and to the task's remaining batches (the
stream simply ends), so every cadence event lands on exactly the same
step numbers as the per-step loop.  Preemption is observed between
windows: the in-flight window is fenced and its record counts flushed
(one coalesced ``report_batch_done`` RPC per window, mandatory flush
before the requeue), and batches collected but never dispatched are the
*unconsumed remainder* — they were never counted, so the master's shard
accounting is unchanged when the task is handed back.

Trainers opt in by implementing the window API
(``prepare_batch`` / ``stage_window`` / ``train_window`` /
``max_window`` / ``steps_to_boundary``); ``ParameterServerTrainer``
keeps ``max_window = 1`` (its overlap lives in the async push pipeline,
see docs/ps_pipeline.md), which routes it through the classic per-step
loop unchanged.

Failure semantics: a fused window has no per-minibatch retry — a
dispatch error fails the whole task and the master's task-retry
machinery takes over (the per-step loop keeps its retry budget; the
worker selects it for ``--fused_steps 1`` and for every trainer whose
``max_window`` is 1).
"""

import time
from collections import namedtuple

import numpy as np

from elasticdl_tpu.utils import tracing
from elasticdl_tpu.utils.logging import get_logger
from elasticdl_tpu.utils.timing import SETUP, FenceWatch

logger = get_logger(__name__)

# One host-prepared minibatch: padded/reshaped (and, multi-controller,
# globalized) leaves plus the pre-pad record count the shard protocol
# reports.  ``weights`` is the loss mask (None for trainers that mask
# internally, e.g. the PS path pads inside train_minibatch).
PreparedBatch = namedtuple(
    "PreparedBatch", ["features", "labels", "weights", "count"]
)

# A window of ``size`` prepared batches stacked on the leading axis
# (size 1 keeps the unstacked leaves), possibly already device-resident.
StagedWindow = namedtuple(
    "StagedWindow", ["size", "features", "labels", "weights"]
)


class LossRing:
    """Holder for the newest window's device-resident losses.

    ``push`` never touches the device; ``fetch_last`` performs the ONE
    host sync (a value fetch: the host needs the losses anyway, and on
    the TPU it fences exactly as ``block_until_ready`` does) and clears
    the slot.  Because windows chain through the params
    pytree, fetching the newest window's losses proves every earlier
    step completed too — which is why only the newest entry is kept:
    older device arrays would be pinned for nothing.
    """

    def __init__(self):
        self._latest = None

    def __len__(self):
        return 0 if self._latest is None else 1

    def push(self, step, losses):
        """``losses``: device scalar (window of 1) or [K] device array;
        ``step`` is the global step number of the window's LAST step."""
        self._latest = (step, losses)

    def fetch_last(self):
        """Fetch the newest window's losses (one device sync), clear
        the slot, and return ``(step, last_loss_float)`` — or None when
        nothing is pending."""
        if self._latest is None:
            return None
        step, losses = self._latest
        values = np.asarray(losses).reshape(-1)  # the device sync
        self._latest = None
        return step, float(values[-1])


class FusedStepDriver:
    """Windowed training loop over one task's prepared-batch stream."""

    def __init__(
        self,
        trainer,
        shard_service,
        timing,
        fences=None,
        fused_steps=1,
        device_prefetch=2,
        log_loss_steps=100,
        elastic=None,
        stop_check=None,
        callbacks=(),
        prepare=None,
        step_throttle_secs=0.0,
    ):
        """``prepare``: optional item -> PreparedBatch hook applied
        INSIDE the loop, after each window's elastic epoch check — the
        elastic path uses it so a world re-form (which can change batch
        geometry via an accum resize) never sees batches prepared under
        the old world.  None means the stream already yields
        PreparedBatch (the prefetch producer prepared them).
        ``fences``: the worker's ``FenceWatch`` over ``timing``."""
        self._trainer = trainer
        self._shard = shard_service
        self._timing = timing
        self._fences = fences if fences is not None else FenceWatch(timing)
        self._prepare = prepare
        self._fused_steps = max(1, int(fused_steps))
        # > 0: stage (stack + device_put) the next window while the
        # current one executes — the device double-buffer.  0: stage at
        # dispatch time (transfer lands on the critical path).  Staging
        # ahead requires already-prepared items (``prepare is None`` =
        # the producer prepared them); with a driver-side prepare hook
        # — the elastic case — the stage is ALWAYS deferred past the
        # window's epoch check: a world re-form clears XLA backends,
        # which would invalidate anything staged ahead of it.
        self._stage_ahead = device_prefetch > 0 and prepare is None
        self._log_loss_steps = log_loss_steps
        self._elastic = elastic
        self._stop_check = stop_check
        self._callbacks = callbacks
        # Drill knob (worker.step_throttle_secs): deliberate per-step
        # slowdown so churn drills can stage a straggler on the FUSED
        # path too — without this the env-armed throttle would be a
        # silent no-op for any fused-config worker.
        self._step_throttle = float(step_throttle_secs or 0.0)
        self.loss_ring = LossRing()

    @property
    def effective_window(self):
        """Configured window clamped to the trainer's structural cap
        (1 for the PS path; 1 for multi-controller collectives, whose
        batches are already committed global arrays)."""
        cap = getattr(self._trainer, "max_window", None)
        if cap:
            return min(self._fused_steps, cap)
        return self._fused_steps

    @staticmethod
    def _dist(steps_done, cadence):
        """Steps until ``steps_done`` next lands on a cadence multiple."""
        return cadence - (steps_done % cadence)

    def _window_limit(self, steps_done):
        """Clamp the next window so every cadence event (loss log,
        version report, checkpoint, elastic epoch check) fires at the
        same step number the per-step loop would fire it at."""
        w = self.effective_window
        if self._log_loss_steps:
            w = min(w, self._dist(steps_done, self._log_loss_steps))
        boundary_fn = getattr(self._trainer, "steps_to_boundary", None)
        boundary = boundary_fn() if boundary_fn is not None else None
        if boundary:
            w = min(w, boundary)
        if self._elastic is not None:
            # Epoch checks run at window granularity (one step_check
            # per window, counted as the window's steps) — clamping
            # here bounds how far past the per-step cadence a check can
            # drift to less than one window; the check may fire up to
            # window-1 steps EARLIER than the per-step loop's, which is
            # safe for a poll (init_world_if_needed only re-forms when
            # the epoch actually changed).  Exact step-number parity is
            # only guaranteed for the report/checkpoint/log boundaries
            # above.
            check_fn = getattr(self._elastic, "steps_to_check", None)
            check = check_fn() if check_fn is not None else None
            if check:
                w = min(w, check)
        return max(1, w)

    @staticmethod
    def _collect(batch_iter, k):
        """Pull up to k prepared batches; fewer means the task's stream
        ended (the window clamps to the task's remaining batches)."""
        out = []
        for _ in range(k):
            item = next(batch_iter, None)
            if item is None:
                break
            out.append(item)
        return out

    def _fence(self):
        """One blocking loss fetch — the sync half of the
        dispatch-vs-sync timing split (see Timing.sync_fraction) —
        and the loop's unit of measurement: the fence watch observes
        ``step_time`` over the steps it proves done."""
        with self._timing.timeit("loss_sync"):
            fetched = self.loss_ring.fetch_last()
        if fetched is not None:
            self._fences.fence(fetched[0])
        return fetched

    def _stage(self, batches):
        """Stage ahead (the device double-buffer) when enabled; None
        defers staging to dispatch time — after the window's elastic
        epoch check, so a world re-form never strands staged device
        arrays on a cleared backend."""
        if not batches or not self._stage_ahead:
            return None
        with self._timing.timeit("host_prep"):
            return self._trainer.stage_window(batches, to_device=True)

    def _dispatch(self, cur, staged):
        """Dispatch one window; ``staged`` is the ahead-staged form (or
        None when staging was deferred past the epoch check)."""
        trainer = self._trainer
        if staged is not None:
            return trainer.train_window(staged)
        cap = getattr(trainer, "max_window", None)
        if cap and len(cur) > cap:
            # An epoch re-form between collect and dispatch shrank the
            # structural window cap (e.g. the world grew to
            # multi-controller): dispatch the already-collected batches
            # singly — correctness over overlap for this one window.
            losses = []
            version = None
            for batch in cur:
                staged_one = trainer.stage_window([batch], to_device=True)
                loss, version = trainer.train_window(staged_one)
                losses.append(loss)
            return losses, version
        return trainer.train_window(
            trainer.stage_window(cur, to_device=True)
        )

    def run_task(self, batch_iter, steps_done=0):
        """Drive one task's stream through fused windows.

        ``batch_iter`` yields PreparedBatch (prep already ran in the
        prefetch producer).  Returns ``(steps_run, preempted)``; the
        caller raises its preemption exception and requeues the task.
        Dispatch errors propagate to the caller's task-failure path.
        """
        trainer, timing = self._trainer, self._timing
        start = steps_done
        with timing.timeit("data_wait"):
            cur = self._collect(batch_iter,
                                self._window_limit(steps_done))
        staged = self._stage(cur)
        # Step-time anatomy (docs/observability.md): each loop pass
        # below is decomposed into data_wait (producer starvation) /
        # host_prep (stack + device_put) / window_dispatch (XLA
        # enqueue) / loss_sync (device fence) / progress_rpc (master
        # report), each feeding a per-phase histogram via Timing.  The
        # per-step step time is the fence's to observe (``_fence``):
        # between fences a pass's wall time is its dispatch.
        while cur:
            # One ``step`` phase per window pass (``steps`` of them in
            # one program), holding the phases above: the per-step
            # loop's vocabulary (worker.py), so one reader serves both.
            with timing.timeit("step", step=steps_done + len(cur),
                               steps=len(cur)):
                if self._elastic is not None:
                    # One epoch check per window, counted as len(cur) steps
                    # so the check cadence matches the per-step loop's.
                    self._elastic.step_check(len(cur))
                for callback in self._callbacks:
                    if hasattr(callback, "on_train_batch_begin"):
                        for _ in cur:  # once per step, as the old loop did
                            callback.on_train_batch_begin(trainer)
                if self._prepare is not None:
                    # Post-epoch-check prep (elastic path): the batches are
                    # prepared against the CURRENT world's geometry.
                    cur = [self._prepare(item) for item in cur]
                with timing.timeit("window_dispatch"):
                    losses, version = self._dispatch(cur, staged)
                if self._step_throttle:
                    time.sleep(self._step_throttle * len(cur))
                steps_done += len(cur)
                timing.bump("fused_windows")
                timing.bump("fused_steps_run", len(cur))
                # Collect + stage the NEXT window while the current one is
                # still executing on device: host feed and host→device
                # transfer overlap the running step.
                with timing.timeit("data_wait"):
                    nxt = self._collect(batch_iter,
                                        self._window_limit(steps_done))
                staged = self._stage(nxt)
                self.loss_ring.push(steps_done, losses)
                fetched = None
                if not nxt:
                    # Task-final fence BEFORE the final report: the last
                    # window must verifiably complete before the shard
                    # protocol can auto-complete the task (same strictness
                    # the per-step loop had via its per-step sync).
                    fetched = self._fence()
                    SETUP.mark("first_report")
                # Coalesced progress accounting: one report_batch_done RPC
                # per fused window (counts buffered per batch, flushed at
                # the window boundary — and, structurally, at task
                # boundaries inside DataShardService).
                with timing.timeit("progress_rpc"):
                    for batch in cur:
                        self._shard.report_batch_done(batch.count,
                                                      defer=True)
                    self._shard.flush_batch_done()
                if (
                    self._log_loss_steps
                    and steps_done % self._log_loss_steps == 0
                ):
                    if fetched is None:
                        fetched = self._fence()
                    if fetched is not None:
                        logger.info(
                            "step %d loss %.6f (version %d)",
                            fetched[0], fetched[1], version,
                        )
                if self._stop_check is not None and self._stop_check():
                    # Graceful preemption between windows: fence the
                    # in-flight window, flush the (already reported) window
                    # counts, and hand back.  ``nxt`` was collected but
                    # never dispatched — the unconsumed remainder, never
                    # counted, requeued with the task.
                    self._fence()
                    self._shard.flush_batch_done()
                    tracing.event("worker.preempt_flush",
                                  steps_run=steps_done - start,
                                  undispatched=len(nxt))
                    return steps_done - start, True
            cur = nxt
        return steps_done - start, False
