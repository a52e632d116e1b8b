"""Worker entrypoint (parity: elasticdl/python/worker/main.py:26-62).

Identity and topology arrive via env (``MASTER_ADDR``, ``WORKER_ID``) with
flag overrides; the model comes from the zoo contract by module name.
"""

import contextlib
import os
import threading
import time

from elasticdl_tpu.data.factory import create_data_reader
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.ops.mode import kernel_mode
from elasticdl_tpu.utils import grpc_utils, tracing
from elasticdl_tpu.utils.args import parse_worker_args
from elasticdl_tpu.utils.checkpoint import CheckpointSaver
from elasticdl_tpu.utils.device import (
    device_report,
    format_device_report,
    place_compile_cache,
)
from elasticdl_tpu.utils.logging import get_logger
from elasticdl_tpu.utils.timing import SETUP, WORKER_SETUP, XLA_PROGRAMS
from elasticdl_tpu.worker.collective_trainer import CollectiveTrainer
from elasticdl_tpu.worker.master_client import MasterClient
from elasticdl_tpu.worker.worker import Worker

logger = get_logger(__name__)


def resolve_worker_id(args):
    """Flag wins, env fallback — the ONE resolution both the identity
    label and the MasterClient registration use (they must never name
    different workers)."""
    return (
        args.worker_id if args.worker_id >= 0
        else int(os.environ.get("WORKER_ID", 0))
    )


def _build_collective_trainer(args, mc, spec, worker_id,
                              batch_size=None, checkpoint_dir=None,
                              checkpoint_steps=None, seed=None,
                              mesh=None):
    """The ONE CollectiveTrainer construction path — shared by the
    eager launch build and the multi-tenant job-switch factory, so a
    rebuilt worker can never silently train with different settings
    (checkpoint rules, bf16, zero1, version-report cadence) than a
    freshly launched one.  The keyword overrides are the job-config
    fields; everything unset falls back to the launch args."""
    batch_size = (
        args.batch_size if batch_size is None else int(batch_size)
    )
    checkpoint_dir = (
        args.checkpoint_dir if checkpoint_dir is None
        else checkpoint_dir
    )
    checkpoint_steps = (
        args.checkpoint_steps if checkpoint_steps is None
        else int(checkpoint_steps)
    )
    seed = args.seed if seed is None else int(seed)
    saver = None
    if checkpoint_dir:
        saver = CheckpointSaver(
            checkpoint_dir, keep_max=args.keep_checkpoint_max
        )
        if worker_id != 0:
            # Every worker may restore, but only worker 0 writes (the
            # collective path replicates params, so any single copy is
            # the model).
            checkpoint_steps = 0
    exporter = None
    export_steps = getattr(args, "export_steps", 0)
    if getattr(args, "export_base", "") and export_steps:
        if worker_id != 0:
            # Same single-writer guard as checkpointing: params are
            # replicated, so worker 0's exports ARE the model.
            export_steps = 0
        else:
            from elasticdl_tpu.serving.export import ContinuousExporter

            exporter = ContinuousExporter(
                args.export_base, model_name=args.job_name,
                wire_format=getattr(args, "export_wire", "npz"),
            )
    trainer = CollectiveTrainer(
        spec,
        batch_size=batch_size,
        mesh=mesh,
        master_client=mc,
        report_version_steps=max(1, args.evaluation_steps // 4)
        if args.evaluation_steps else 0,
        checkpoint_saver=saver,
        checkpoint_steps=checkpoint_steps,
        use_bf16_compute=args.use_bf16,
        rng_seed=seed,
        zero1=args.zero1,
        exporter=exporter,
        export_steps=export_steps,
    )
    if saver is not None:
        trainer.init_from_checkpoint()
    return trainer


def _job_context_factory(args, mc):
    """Multi-tenant pools (docs/scheduler.md): build the callable the
    Worker invokes when the scheduler re-assigns it to a different job
    — rebuilds data reader, model spec and trainer from the handshake
    config, in place, without a process restart.  Wired for
    local-strategy pool workers; collective workers keep their elastic
    controller bound to one trainer, and PS workers keep their PS
    client topology, so both adopt re-assignments as an id only."""
    if args.distribution_strategy != "local":
        return None

    worker_id = resolve_worker_id(args)

    def build(cfg):
        model_zoo = cfg.get("model_zoo", args.model_zoo)
        model_params = cfg.get("model_params", args.model_params)
        batch_size = int(cfg.get("batch_size", args.batch_size))
        records_per_task = batch_size * int(
            cfg.get("num_minibatches_per_task",
                    args.num_minibatches_per_task)
        )
        spec = load_model_spec(model_zoo, model_params=model_params)
        reader = create_data_reader(
            cfg.get("data_origin", args.data_origin),
            records_per_shard=records_per_task,
        )
        # Job state lives with the job: a worker joining a
        # checkpointed job resumes that job's trajectory, a worker
        # joining an uncheckpointed one starts from the job's seeded
        # init (tenant isolation — nothing rides over from the
        # previous job's params).
        trainer = _build_collective_trainer(
            args, mc, spec, worker_id,
            batch_size=batch_size,
            checkpoint_dir=cfg.get("checkpoint_dir"),
            checkpoint_steps=cfg.get("checkpoint_steps"),
            seed=cfg.get("seed"),
        )
        return reader, spec, trainer

    return build


def _initial_job_config(args):
    """The pool-template config this worker's eagerly-built pipeline
    corresponds to — lets the first handshake skip the rebuild when
    the assigned job matches the launch args.  Derived from the ONE
    field list the fast-path comparison uses, so the two can't
    drift."""
    return {
        field: getattr(args, field)
        for field in Worker._JOB_KEY_FIELDS
    }


def build_worker(args):
    master_addr = args.master_addr or os.environ.get("MASTER_ADDR", "")
    worker_id = resolve_worker_id(args)
    channel = grpc_utils.build_channel(master_addr)
    grpc_utils.connect_to_master(channel, master_addr)
    mc = MasterClient(channel, worker_id=worker_id, addr=master_addr)

    spec = load_model_spec(args.model_zoo,
                           model_params=args.model_params)
    records_per_task = args.batch_size * args.num_minibatches_per_task
    reader = create_data_reader(
        args.data_origin, records_per_shard=records_per_task
    )
    if args.job_type == "predict" and spec.prediction_outputs_processor \
            is None:
        from elasticdl_tpu.worker.prediction_outputs_processor import (
            NpzPredictionWriter,
        )

        spec.prediction_outputs_processor = NpzPredictionWriter(
            args.prediction_outputs
        )
    if args.distribution_strategy == "ps":
        from elasticdl_tpu.utils.retry import ps_rpc_policy
        from elasticdl_tpu.worker.ps_client import build_ps_client
        from elasticdl_tpu.worker.ps_trainer import ParameterServerTrainer

        ps_client = build_ps_client(
            args.ps_addrs, wire_dtype=args.ps_wire_dtype,
            # The pipelined trainer pushes from a background thread;
            # give that traffic its own connections so it never convoys
            # the foreground pulls.
            dedicated_push_channels=(
                args.use_async and args.async_push_window > 0
            ),
            # Outage riding (docs/ps_recovery.md): a shard SIGKILLed
            # and relaunched by PSManager on the same port is ridden
            # through per-shard retries with channel rebuild instead of
            # killing this worker.
            retry=ps_rpc_policy(),
        )
        trainer = ParameterServerTrainer(
            spec, ps_client,
            batch_size=args.batch_size,
            master_client=mc,
            rng_seed=args.seed,
            atomic_sync=not args.use_async,
            async_push_window=args.async_push_window,
            # Every dense pull drains the push pipeline; a cadence > 1
            # is what gives the async push room to overlap compute.
            get_model_steps=args.get_model_steps,
        )
        return Worker(
            mc, reader, spec, trainer,
            batch_size=args.batch_size,
            log_loss_steps=args.log_loss_steps,
            # Same driver API as the collective path; the PS trainer's
            # max_window=1 keeps it on the per-step loop (its overlap
            # lives in the async push pipeline + embedding prefetch).
            fused_steps=args.fused_steps,
            device_prefetch=args.device_prefetch,
        )
    mesh = None
    if args.distribution_strategy == "collective":
        # Shard the batch over every device this process sees (a TPU
        # worker VM sees its slice's local chips); XLA inserts the
        # gradient all-reduce over ICI.  Multi-host worlds additionally
        # join the master rendezvous (join_rendezvous below) and
        # re-initialize on membership epochs via the elastic controller.
        import jax
        import numpy as np
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()), ("data",))
    trainer = _build_collective_trainer(args, mc, spec, worker_id,
                                        mesh=mesh)
    mem = trainer.zero1_report()
    if mem is not None:
        # Startup accounting for the operator: what one device holds in
        # optimizer state under the chosen placement, and what the
        # other mode would cost (rebuild() logs the same line again on
        # every elastic re-form).
        logger.info(
            "optimizer state per device: %d bytes (%s, %d devices; "
            "replicated equivalent %d bytes, %.1fx)",
            mem["per_device_bytes"], mem["mode"], mem["num_shards"],
            mem["replicated_equiv_bytes"], mem["reduction_factor"],
        )
    elastic = None
    if args.distribution_strategy == "collective":
        # Managed elastic AllReduce: the controller consumes the
        # master's rendezvous epochs from inside the task loop — the
        # worker joins the (possibly multi-process) collective world,
        # re-forms it on membership changes, and a finished/dead peer
        # is just another epoch (docs/designs/elastic_collectives.md).
        from elasticdl_tpu.api.controller import (
            ElasticCollectiveController,
        )
        from elasticdl_tpu.parallel.distributed import (
            initialize_from_rendezvous,
        )

        def mesh_builder(rank, world_size, coordinator_addr):
            import jax
            import numpy as np
            from jax.sharding import Mesh

            initialize_from_rendezvous(
                rank, world_size, coordinator_addr)
            return Mesh(np.array(jax.devices()), ("data",))

        elastic = ElasticCollectiveController(
            mc, trainer,
            check_steps=max(1, args.num_minibatches_per_task),
            mesh_builder=mesh_builder,
        )
    worker = Worker(
        mc, reader, spec, trainer,
        batch_size=args.batch_size,
        log_loss_steps=args.log_loss_steps,
        join_rendezvous=args.distribution_strategy == "collective",
        elastic_controller=elastic,
        fused_steps=args.fused_steps,
        device_prefetch=args.device_prefetch,
        # Multi-tenant pools: rebuild the pipeline in place when the
        # scheduler re-assigns this worker to a different job.
        job_context_factory=_job_context_factory(args, mc),
        initial_job_config=_initial_job_config(args),
    )
    return worker


_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}


@contextlib.contextmanager
def xla_compiles_logged(steps_done, timing=lambda: None):
    """Make every XLA program this process builds visible from inside,
    for as long as the block lasts: JAX reports one
    ``backend_compile_duration`` per fresh jit (a program loaded from the
    persistent cache included: the duration is then the load), and each
    becomes one stamped log line, ``xla compile: secs=<s> step=<n>
    fun=<name> trace_s=<s> lower_s=<s> cache=hit|miss|off``.  A compile
    after the first steps is the per-shape recompile that stalls a step;
    counting new files in the cache directory misses every one shorter
    than the persistent cache's minimum compile time.  ``steps_done()``
    is the number of steps trained so far, ``timing()`` the ``Timing``
    whose ``xla_programs`` counter counts the lines (None: nobody's yet):
    a stalled fence's ``compiles=`` (utils/timing.FenceWatch).

    ``secs`` is the backend's part alone.  ``trace_s`` and ``lower_s``
    are what JAX reported on the same thread since that thread's
    previous program: the Python trace to a jaxpr (a jit traced inside
    another's trace reports first and lies within the outer one's
    seconds, so it is not counted again) and the lowering to MLIR.
    ``cache`` is the persistent cache's word: ``hit`` (``secs`` is the
    load), ``miss`` (compiled, and written for the next process) or
    ``off`` (the cache keeps no entry of it: no directory, or a compile
    under its minimum time).  While the set-up timeline is open the same
    numbers are added up by its phase (utils/timing.SETUP)."""
    import jax

    class Building(threading.local):
        """What a thread has heard of the program it is building."""

        def __init__(self):
            self.traces, self.lower_s, self.cache = [], 0.0, "off"

    mine = Building()

    def on_event(event, **_):
        if event in _CACHE_EVENTS:
            mine.cache = _CACHE_EVENTS[event]

    def on_duration(event, secs, fun_name="", **_):
        if event == _TRACE_EVENT:
            began = time.time() - secs
            mine.traces = [t for t in mine.traces if t[0] < began]
            mine.traces.append((began, secs))
        elif event == _LOWER_EVENT:
            mine.lower_s += secs
        elif event == _COMPILE_EVENT:
            trace_s = sum(s for _, s in mine.traces)
            logger.info(
                "xla compile: secs=%.3f step=%d fun=%s trace_s=%.3f "
                "lower_s=%.3f cache=%s", secs, steps_done(), fun_name,
                trace_s, mine.lower_s, mine.cache)
            counted = timing()
            if counted is not None:
                counted.bump(XLA_PROGRAMS)
            SETUP.add(programs=1, trace_s=trace_s, lower_s=mine.lower_s,
                      compile_or_load_s=secs,
                      cache_hits=mine.cache == "hit",
                      cache_misses=mine.cache == "miss")
            mine.__init__()

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def device_line():
    """``device_report`` as the one ``key=value`` line a JAX-free parent
    parses (``chip_smoke.py``, the benchmark), with what the kernels'
    one mode is where the line has always said it, under both names it
    has had: ``flash=`` and ``fused_gn=``, before the peaks."""
    report = device_report()
    peaks = {key: report.pop(key)
             for key in ("peak_bytes_in_use", "peak_bytes_reserved")}
    mode = kernel_mode()
    return format_device_report(
        {**report, "flash": mode, "fused_gn": mode, **peaks})


def main(argv=None):
    # The interpreter's start and this module's import chain end here.
    SETUP.begin("worker", WORKER_SETUP, logger)
    import signal

    from elasticdl_tpu.worker.worker import PREEMPTED_EXIT_CODE

    args = parse_worker_args(argv)
    # Structured process identity: every log line (and every flight-
    # recorder event) of an interleaved drill names its process.
    worker_id = resolve_worker_id(args)
    tracing.configure_identity("worker", rank=worker_id)
    logger.info("worker starting: %s", vars(args))
    # A relaunched worker must find what its predecessor compiled.
    cache_dir = place_compile_cache()
    # Which backend this process actually got, stated for a JAX-free
    # parent to check (the master only sees the log and the exit code).
    logger.info("worker device: %s compile_cache=%s", device_line(),
                cache_dir)
    SETUP.mark("build")
    worker = None
    with xla_compiles_logged(
            lambda: worker.steps_done if worker is not None else 0,
            lambda: worker.timing if worker is not None else None):
        worker = build_worker(args)

        def _graceful_preempt(_sig, _frame):
            # Preemptible hosts deliver SIGTERM with a grace window: finish
            # the in-flight minibatch, checkpoint, exit 143 (the manager
            # relaunches a replacement).
            logger.warning("SIGTERM received: graceful preemption")
            worker.request_stop()

        try:
            signal.signal(signal.SIGTERM, _graceful_preempt)
        except ValueError:
            pass  # not the main thread (embedded use)
        # AFTER the preemption hook so the SIGTERM chain is
        # dump-ring-then-graceful-preempt ($ELASTICDL_TRACE_DIR gates it).
        tracing.arm_crash_dump()
        if args.profile_dir:
            from elasticdl_tpu.utils.timing import device_trace

            with device_trace(args.profile_dir):
                worker.run()
        else:
            worker.run()
        logger.info("worker end-of-run: steps=%d %s", worker.steps_done,
                    device_line())
        if worker.preempted:
            logger.info("worker preempted (checkpointed)")
            return PREEMPTED_EXIT_CODE
        logger.info("worker done")
        return 0


if __name__ == "__main__":
    raise SystemExit(main())
