"""Fused-step training driver (worker/fused_driver.py): multi-step
dispatch equivalence, cadence alignment, coalesced progress RPCs, and
the preemption drill (zero lost records, zero double counts)."""

from types import SimpleNamespace

import numpy as np
import pytest

from elasticdl_tpu.data.reader import ArrayDataReader
from elasticdl_tpu.models import mnist
from elasticdl_tpu.proto import elastic_pb2 as pb
from elasticdl_tpu.utils import hist
from elasticdl_tpu.utils.args import parse_master_args, parse_worker_args
from elasticdl_tpu.utils.timing import Timing
from elasticdl_tpu.worker.collective_trainer import CollectiveTrainer
from elasticdl_tpu.worker.data_shard_service import DataShardService
from elasticdl_tpu.worker.fused_driver import LossRing
from elasticdl_tpu.worker.worker import Worker


@pytest.fixture(scope="module")
def spec():
    return mnist.model_spec(learning_rate=1e-3)


@pytest.fixture(scope="module")
def dataset():
    return mnist.synthetic_data(n=192, seed=1)


class FakeMasterClient:
    """Task queue + RPC recorder: counts every report_batch_done call
    (the coalescing assertion) and the record totals (the accounting
    assertion)."""

    def __init__(self, sizes, worker_id=0):
        self.worker_id = worker_id
        self._tasks = [
            SimpleNamespace(
                id=i + 1, type=pb.TRAINING,
                shard=SimpleNamespace(name="s", start=sum(sizes[:i]),
                                      end=sum(sizes[:i]) + size,
                                      record_indices=[]),
                model_version=-1,
            )
            for i, size in enumerate(sizes)
        ]
        self.batch_done_calls = []   # record_count per RPC
        self.telemetry = []          # the piggyback of each RPC that had one
        self.task_results = []       # (task_id, err_message, requeue)
        self.versions = []           # report_version stream

    def get_task(self, task_type=None):
        if self._tasks:
            return self._tasks.pop(0)
        # id < 0 and type != WAIT: "job finished" (fetch_task -> None)
        return SimpleNamespace(id=-1, type=-1, shard=None,
                               model_version=-1)

    def report_batch_done(self, count, telemetry=None):
        self.batch_done_calls.append(count)
        if telemetry:
            self.telemetry.append(telemetry)

    def report_task_result(self, task_id, err_message="",
                           exec_counters=None, requeue=False):
        self.task_results.append((task_id, err_message, requeue))

    def report_version(self, version):
        self.versions.append(version)


def run_worker(dataset, spec, fused_steps, device_prefetch=2,
               accum_steps=1, batch_size=32, records_per_shard=64,
               trainer_kwargs=None, mc=None, worker_hook=None):
    xs, ys = dataset
    reader = ArrayDataReader((xs, ys), records_per_shard=records_per_shard)
    if mc is None:
        sizes = [records_per_shard] * (len(xs) // records_per_shard)
        mc = FakeMasterClient(sizes)
    trainer = CollectiveTrainer(
        spec, batch_size=batch_size // max(1, accum_steps),
        accum_steps=accum_steps, rng_seed=0, master_client=mc,
        **(trainer_kwargs or {}),
    )
    worker = Worker(
        mc, reader, spec, trainer, batch_size=batch_size,
        fused_steps=fused_steps, device_prefetch=device_prefetch,
    )
    if worker_hook is not None:
        worker_hook(worker, trainer)
    worker.run()
    return mc, trainer, worker


# -- equivalence ------------------------------------------------------------


@pytest.mark.parametrize("fused_steps", [2, 4])
def test_fused_matches_per_step_loop(dataset, spec, fused_steps):
    """K steps per dispatch == K per-step dispatches, same seed: loss
    trajectory and final params bit-tolerant, cadence/version counts
    identical."""
    mc_ref, ref, _ = run_worker(dataset, spec, fused_steps=1)
    mc_f, fused, _ = run_worker(dataset, spec, fused_steps=fused_steps)
    assert fused.version == ref.version
    p_ref, p_fused = ref.export_parameters(), fused.export_parameters()
    for k in p_ref:
        np.testing.assert_allclose(p_ref[k], p_fused[k], rtol=2e-4,
                                   atol=1e-6)
    # identical record accounting, fewer RPCs
    assert sum(mc_f.batch_done_calls) == sum(mc_ref.batch_done_calls)
    assert len(mc_f.batch_done_calls) < len(mc_ref.batch_done_calls)


def test_fused_steps_one_is_exact_old_path(dataset, spec):
    """--fused_steps 1 routes through the classic per-step loop: params
    BIT-identical to a default worker, one RPC per batch."""
    mc_a, a, worker_a = run_worker(dataset, spec, fused_steps=1)
    mc_b, b, _ = run_worker(dataset, spec, fused_steps=1)
    assert worker_a._windowed_driver() is None
    for k, v in a.export_parameters().items():
        np.testing.assert_array_equal(v, b.export_parameters()[k])
    assert mc_a.batch_done_calls == mc_b.batch_done_calls
    assert len(mc_a.batch_done_calls) == 192 // 32


def test_fused_with_gradient_accumulation(dataset, spec):
    """Windows compose with accum_steps > 1 (stacked [K, accum, micro]
    batches)."""
    _, ref, _ = run_worker(dataset, spec, fused_steps=1, accum_steps=2)
    _, fused, _ = run_worker(dataset, spec, fused_steps=2, accum_steps=2)
    assert fused.version == ref.version
    p_ref = ref.export_parameters()
    p_fused = fused.export_parameters()
    for k in p_ref:
        np.testing.assert_allclose(p_ref[k], p_fused[k], rtol=2e-4,
                                   atol=1e-6)


def test_device_prefetch_zero_matches(dataset, spec):
    """--device_prefetch 0 (prep on the dispatch path, no staged
    transfer) is numerically identical to the double-buffered path."""
    _, staged, _ = run_worker(dataset, spec, fused_steps=4,
                              device_prefetch=2)
    _, inline, _ = run_worker(dataset, spec, fused_steps=4,
                              device_prefetch=0)
    for k, v in staged.export_parameters().items():
        np.testing.assert_array_equal(v, inline.export_parameters()[k])


# -- cadence alignment ------------------------------------------------------


def test_report_and_checkpoint_land_on_per_step_numbers(
    dataset, spec, tmp_path
):
    """Windows clamp to the next report/checkpoint boundary: version
    reports and checkpoints fire at exactly the step numbers the
    per-step loop fires them at."""
    from elasticdl_tpu.utils.checkpoint import CheckpointSaver

    def run(fused_steps, subdir):
        saver = CheckpointSaver(str(tmp_path / subdir))
        mc, trainer, _ = run_worker(
            dataset, spec, fused_steps=fused_steps,
            trainer_kwargs=dict(
                report_version_steps=2,
                checkpoint_saver=saver, checkpoint_steps=3,
            ),
        )
        trainer.flush_checkpoints()
        return mc.versions, saver

    versions_ref, saver_ref = run(1, "ref")
    versions_fused, saver = run(4, "fused")
    assert versions_fused == versions_ref == [2, 4, 6]
    # 6 steps, cadence 3 -> checkpoints at versions 3 and 6, both paths
    assert saver.latest_version() == saver_ref.latest_version() == 6


def test_steps_to_boundary(spec):
    trainer = CollectiveTrainer(
        spec, batch_size=16, master_client=FakeMasterClient([]),
        report_version_steps=5,
    )
    assert trainer.steps_to_boundary() == 5
    xs, ys = mnist.synthetic_data(n=16, seed=2)
    trainer.train_minibatch(xs, ys)
    assert trainer.steps_to_boundary() == 4
    bare = CollectiveTrainer(spec, batch_size=16)
    assert bare.steps_to_boundary() is None


# -- coalesced progress RPCs ------------------------------------------------


def test_one_report_batch_done_rpc_per_window(dataset, spec):
    """192 records / batch 32 = 6 batches; K=2 -> 3 RPCs per... the
    windows span tasks of 2 batches each, so: one RPC per window, sum
    of counts exact."""
    mc, _, _ = run_worker(dataset, spec, fused_steps=2)
    assert sum(mc.batch_done_calls) == 192
    # 3 tasks x (one 2-batch window each) = 3 RPCs
    assert len(mc.batch_done_calls) == 3
    assert all(c == 64 for c in mc.batch_done_calls)


def test_deferred_counts_flush_on_task_boundaries():
    """DataShardService: deferred counts auto-flush when a shard drains
    (task boundary) and on report_task_failed/done — never lost, never
    doubled."""
    mc = FakeMasterClient([])
    svc = DataShardService(mc, batch_size=5)
    task = SimpleNamespace(
        id=7, type=pb.TRAINING,
        shard=SimpleNamespace(name="s", start=0, end=10,
                              record_indices=[]),
        model_version=-1,
    )
    mc._tasks = [task]
    t = svc.fetch_task()
    svc.report_batch_done(5, defer=True)
    assert mc.batch_done_calls == []          # buffered
    svc.flush_batch_done()
    assert mc.batch_done_calls == [5]         # one coalesced RPC
    svc.flush_batch_done()
    assert mc.batch_done_calls == [5]         # idempotent when empty
    svc.report_batch_done(5, defer=True)      # drains the shard ->
    assert mc.batch_done_calls == [5, 5]      # mandatory flush
    assert (t.id, "", False) in mc.task_results
    # failure path flushes too
    task2 = SimpleNamespace(
        id=8, type=pb.TRAINING,
        shard=SimpleNamespace(name="s", start=10, end=20,
                              record_indices=[]),
        model_version=-1,
    )
    mc._tasks = [task2]
    t2 = svc.fetch_task()
    svc.report_batch_done(5, defer=True)
    svc.report_task_failed(t2, "preempted", requeue=True)
    assert mc.batch_done_calls == [5, 5, 5]
    assert (t2.id, "preempted", True) in mc.task_results


# -- preemption drill -------------------------------------------------------


def test_preemption_mid_window_loses_and_double_counts_nothing(
    dataset, spec
):
    """The elastic drill: preempt during a fused task.  The in-flight
    window is flushed (counted exactly once), collected-but-undispatched
    batches are the unconsumed remainder (never counted), the task is
    requeued without consuming a retry, and a second worker finishes
    every record."""
    xs, ys = dataset
    reader = ArrayDataReader((xs, ys), records_per_shard=192)
    mc = FakeMasterClient([192])
    trainer = CollectiveTrainer(spec, batch_size=32, rng_seed=0,
                                master_client=mc)
    worker = Worker(mc, reader, spec, trainer, batch_size=32,
                    fused_steps=2)

    real_train_window = trainer.train_window
    windows = []

    def spy_train_window(staged):
        windows.append(staged.size)
        if len(windows) == 2:  # preempt DURING the second window
            worker.request_stop()
        return real_train_window(staged)

    trainer.train_window = spy_train_window
    worker.run()
    assert worker.preempted
    # exactly the two dispatched windows were counted, once each
    assert windows == [2, 2]
    assert sum(mc.batch_done_calls) == 4 * 32
    # the task went back with requeue=True (no retry consumed)
    assert mc.task_results == [(1, "worker preempted (graceful)", True)]

    # a replacement worker picks the task back up and finishes it
    mc2 = FakeMasterClient([])
    mc2._tasks = [SimpleNamespace(
        id=1, type=pb.TRAINING,
        shard=SimpleNamespace(name="s", start=0, end=192,
                              record_indices=[]),
        model_version=-1,
    )]
    worker2 = Worker(mc2, reader, spec, trainer, batch_size=32,
                     fused_steps=2)
    worker2.run()
    assert sum(mc2.batch_done_calls) == 192     # zero lost records
    assert mc2.task_results == [(1, "", False)]


def test_preemption_between_tasks_old_loop_unchanged(dataset, spec):
    """fused_steps=1 keeps the seed preemption semantics."""
    def hook(worker, trainer):
        orig = trainer.train_minibatch

        def stop_after_one(f, l):
            loss, v = orig(f, l)
            if v == 1:  # mid-task: one of the task's two batches done
                worker.request_stop()
            return loss, v

        trainer.train_minibatch = stop_after_one

    mc, _, worker = run_worker(dataset, spec, fused_steps=1,
                               worker_hook=hook)
    assert worker.preempted
    assert sum(mc.batch_done_calls) == 32
    assert mc.task_results == [(1, "worker preempted (graceful)", True)]


# -- lazy loss + loss ring --------------------------------------------------


def test_train_minibatch_returns_lazy_device_loss(spec):
    trainer = CollectiveTrainer(spec, batch_size=16)
    xs, ys = mnist.synthetic_data(n=16, seed=5)
    loss, version = trainer.train_minibatch(xs, ys)
    assert not isinstance(loss, float)       # lazy device scalar
    assert hasattr(loss, "dtype")
    assert np.isfinite(float(loss))          # explicit fetch works
    assert version == 1


def test_loss_ring_single_sync_and_clear(spec):
    trainer = CollectiveTrainer(spec, batch_size=16)
    xs, ys = mnist.synthetic_data(n=32, seed=6)
    ring = LossRing()
    assert ring.fetch_last() is None
    prepared = [trainer.prepare_batch(xs[:16], ys[:16]),
                trainer.prepare_batch(xs[16:], ys[16:])]
    losses, version = trainer.train_window(trainer.stage_window(prepared))
    ring.push(2, losses)
    step, value = ring.fetch_last()
    assert step == 2 and np.isfinite(value)
    assert len(ring) == 0 and ring.fetch_last() is None


# -- pad-plan cache ---------------------------------------------------------


def test_pad_plan_cached_per_shape(spec):
    trainer = CollectiveTrainer(spec, batch_size=16)
    xs, ys = mnist.synthetic_data(n=40, seed=7)
    trainer.prepare_batch(xs[:16], ys[:16])
    trainer.prepare_batch(xs[16:32], ys[16:32])
    assert len(trainer._pad_plans) == 1          # full batch: one plan
    partial = trainer.prepare_batch(xs[32:40], ys[32:40])
    assert len(trainer._pad_plans) == 2          # tail batch adds one
    # padded to the static batch with a correct loss mask
    leaves = np.asarray(partial.features)
    assert leaves.shape[0] == 16
    assert partial.weights.sum() == 8.0
    assert partial.count == 8


def test_pad_plan_cache_invalidated_on_rebuild(spec):
    trainer = CollectiveTrainer(spec, batch_size=16)
    xs, ys = mnist.synthetic_data(n=16, seed=8)
    trainer.prepare_batch(xs, ys)
    trainer.stage_window(
        [trainer.prepare_batch(xs, ys), trainer.prepare_batch(xs, ys)]
    )
    fn = trainer.build_fused_window(2)
    trainer._fused_window_cache[2] = fn
    trainer.rebuild(None)
    assert trainer._pad_plans == {}
    assert trainer._fused_window_cache == {}


def test_prepare_batch_accum_reshape(spec):
    trainer = CollectiveTrainer(spec, batch_size=8, accum_steps=2)
    xs, ys = mnist.synthetic_data(n=16, seed=9)
    prepared = trainer.prepare_batch(xs, ys)
    assert np.asarray(prepared.features).shape[:2] == (2, 8)
    assert prepared.weights.shape == (2, 8)


# -- timing + args ----------------------------------------------------------


def test_timing_sync_fraction():
    t = Timing()
    assert t.sync_fraction("window_dispatch", "loss_sync") is None
    t.observe("window_dispatch", 3.0)
    t.observe("loss_sync", 1.0)
    assert t.sync_fraction("window_dispatch", "loss_sync") == 0.25


def test_step_anatomy_phases_and_step_time_hist(dataset, spec):
    """The fused loop decomposes into data_wait / host_prep /
    window_dispatch / loss_sync / progress_rpc phases (each
    histogram-backed via Timing), and observes one step_time sample
    per step — the distribution the telemetry piggyback ships to the
    master (docs/observability.md)."""
    mc, _trainer, worker = run_worker(dataset, spec, fused_steps=4)
    timing = worker.timing
    step_snap = timing.hist_snapshot("step_time")
    assert step_snap is not None
    assert step_snap["count"] == worker._steps  # one sample per step
    for phase in ("data_wait", "window_dispatch", "progress_rpc"):
        snap = timing.hist_snapshot(phase)
        assert snap is not None and snap["count"] > 0, phase
    # host_prep only when staging ahead ran (device_prefetch > 0)
    assert timing.hist_snapshot("host_prep") is not None
    # and the progress report behind each fence carried the encoded
    # delta: every sample has reached the master by the run's end
    shipped = [hist.decode_deltas(t["hist_delta"])["step_time"]["count"]
               for t in mc.telemetry if "hist_delta" in t]
    assert sum(shipped) == worker._steps
    assert "hist_delta" not in worker._telemetry_snapshot()


def test_fused_flags_roundtrip_master_to_worker():
    args = parse_master_args([
        "--fused_steps", "8", "--device_prefetch", "4",
    ])
    from elasticdl_tpu.master.main import _MASTER_ONLY_ARGS
    from elasticdl_tpu.utils.args import build_arguments_from_parsed_result

    flags = build_arguments_from_parsed_result(
        args, filter_args=_MASTER_ONLY_ARGS
    )
    worker_args = parse_worker_args(flags)
    assert worker_args.fused_steps == 8
    assert worker_args.device_prefetch == 4
    defaults = parse_worker_args([])
    assert defaults.fused_steps == 1       # the exact old path
    assert defaults.device_prefetch == 2


# -- PS trainer passthrough -------------------------------------------------


def test_ps_trainer_window_api_is_passthrough():
    """The PS trainer exposes the same driver API but max_window=1
    keeps it on the per-step loop; a Worker with fused_steps>1 must
    therefore NOT select the windowed driver for it."""
    from elasticdl_tpu.worker.ps_trainer import ParameterServerTrainer

    assert ParameterServerTrainer.max_window.fget(None) == 1
    trainer = ParameterServerTrainer.__new__(ParameterServerTrainer)
    assert trainer.steps_to_boundary() is None
    features = {"x": np.zeros((4, 3), np.float32)}
    labels = np.zeros((4,), np.int32)
    prepared = trainer.prepare_batch(features, labels)
    assert prepared.count == 4 and prepared.weights is None
    staged = trainer.stage_window([prepared])
    assert staged.size == 1
    assert staged.features[0] is features  # raw dict, IDS_KEY intact


class _CappedTrainer(CollectiveTrainer):
    """PS-style structural cap: window 1 regardless of --fused_steps."""

    @property
    def max_window(self):
        return 1


def test_dispatch_splits_window_when_cap_shrinks(dataset, spec):
    """An elastic epoch re-form can shrink max_window between collect
    and dispatch (world grows to multi-controller): the driver then
    dispatches the already-collected window per-step — bit-identical
    to the per-step loop, no task failure."""
    from elasticdl_tpu.worker.fused_driver import FusedStepDriver

    xs, ys = dataset
    trainer = _CappedTrainer(spec, batch_size=32, rng_seed=0)
    driver = FusedStepDriver(trainer, None, Timing(), fused_steps=2)
    cur = [trainer.prepare_batch(xs[:32], ys[:32]),
           trainer.prepare_batch(xs[32:64], ys[32:64])]
    losses, version = driver._dispatch(cur, None)
    assert version == 2 and len(losses) == 2
    ref = CollectiveTrainer(spec, batch_size=32, rng_seed=0)
    ref.train_minibatch(xs[:32], ys[:32])
    ref.train_minibatch(xs[32:64], ys[32:64])
    p = trainer.export_parameters()
    for k, v in ref.export_parameters().items():
        np.testing.assert_array_equal(v, p[k])


def test_worker_routes_ps_style_trainer_to_per_step_loop(dataset, spec):
    """A trainer whose max_window is 1 (the PS path) never enters the
    windowed driver even with --fused_steps 4."""
    xs, ys = dataset
    reader = ArrayDataReader((xs, ys), records_per_shard=64)
    mc = FakeMasterClient([64, 64, 64])
    trainer = _CappedTrainer(spec, batch_size=32, rng_seed=0,
                             master_client=mc)
    worker = Worker(mc, reader, spec, trainer, batch_size=32,
                    fused_steps=4)
    assert worker._windowed_driver() is None
    worker.run()
    assert len(mc.batch_done_calls) == 6   # per-batch RPCs (old loop)
