"""Master entrypoint (parity: elasticdl/python/master/main.py:20-24).

Builds the control plane from flags, optionally launches/manages workers
(local-process backend), runs the job to completion.
"""

import os

from elasticdl_tpu.data.factory import create_data_reader
from elasticdl_tpu.master.evaluation_service import EvaluationService
from elasticdl_tpu.master.master import Master
from elasticdl_tpu.master.rendezvous import RendezvousServer
from elasticdl_tpu.master.task_manager import TaskManager
from elasticdl_tpu.master.worker_manager import (
    ProcessWorkerBackend,
    WorkerManager,
)
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.utils import tracing
from elasticdl_tpu.utils.args import (
    build_arguments_from_parsed_result,
    parse_master_args,
)
from elasticdl_tpu.utils.logging import get_logger
from elasticdl_tpu.utils.timing import MASTER_SETUP, SETUP

logger = get_logger(__name__)

_MASTER_ONLY_ARGS = (
    "port", "num_workers", "num_ps", "shuffle", "shuffle_shards",
    "max_task_retries", "task_timeout_secs", "relaunch_on_worker_failure",
    "grads_to_wait", "sync_version_tolerance",
    "worker_backend", "image", "namespace", "worker_resource_request",
    "tpu_topology", "worker_pod_priority", "cluster_spec", "volume",
    "status_port", "journal_dir", "rpc_fault_spec",
    "ps_rpc_fault_spec",
    "jobs_spec", "sched_cadence_secs", "sched_moves_per_tick",
    "sched_worker_stale_secs",
)

# Job-config fields that must match between the journal and a
# restarted master's flags: replaying a journal into a DIFFERENT job
# (other dataset, other task split) would rebuild nonsense queues.
_JOURNAL_META_FIELDS = (
    "job_name", "job_type", "data_origin", "records_per_task",
    "num_epochs", "seed", "shuffle", "shuffle_shards",
)


def _journal_meta(args, records_per_task):
    meta = {
        field: getattr(args, field) for field in _JOURNAL_META_FIELDS
        if field != "records_per_task"
    }
    meta["records_per_task"] = records_per_task
    return meta


def _check_journal_meta(state, meta):
    if state.meta is None:
        logger.warning("journal has no meta record; replaying anyway")
        return
    mismatched = {
        k: (state.meta.get(k), meta[k])
        for k in meta if state.meta.get(k) != meta[k]
    }
    if mismatched:
        raise RuntimeError(
            "journal replay refused: the journaled job does not match "
            "this master's flags (journaled vs current): %r — point "
            "--journal_dir at a fresh directory for a new job"
            % mismatched
        )


def _build_worker_backend(args, worker_args):
    if args.worker_backend == "k8s":
        from elasticdl_tpu.client.k8s_renderer import parse_resource_string
        from elasticdl_tpu.master.k8s_backend import (
            K8sWorkerBackend,
            owner_ref_from_env,
        )

        return K8sWorkerBackend(
            job_name=args.job_name,
            image=args.image,
            namespace=args.namespace,
            worker_args=worker_args,
            resources=parse_resource_string(args.worker_resource_request),
            tpu_topology=args.tpu_topology or None,
            num_workers=args.num_workers,
            high_priority_fraction=args.worker_pod_priority,
            cluster_spec=args.cluster_spec,
            owner_ref=owner_ref_from_env(),
            volume=args.volume,
        )
    return ProcessWorkerBackend(worker_args=worker_args,
                                num_workers=args.num_workers)


def build_master(args):
    records_per_task = args.batch_size * args.num_minibatches_per_task
    journal_state = None
    if args.journal_dir:
        from elasticdl_tpu.master.journal import replay_journal

        # The recovery trace: journal replay is this incarnation's
        # root recovery span; every later event this master records
        # carries link_trace back to it, so a worker's outage-riding
        # trace and the replay stitch into ONE incident component
        # (docs/observability.md, cpu_master_kill drill gate).
        with tracing.span("master.journal_replay") as replay_span:
            journal_state = replay_journal(args.journal_dir)
            if journal_state is not None:
                tracing.event(
                    "journal.replayed",
                    restarts=journal_state.restarts,
                    rendezvous_id=journal_state.rendezvous_id,
                )
        if journal_state is not None:
            restart = journal_state.restarts + 1
            tracing.configure_identity(
                "master", generation=restart, restart=restart,
                # replay_span is None when tracing is disabled
                link_trace=getattr(replay_span, "trace", None),
            )
    reader = create_data_reader(
        args.data_origin, records_per_shard=records_per_task
    )
    eval_reader = None
    if args.validation_data_origin:
        eval_reader = create_data_reader(
            args.validation_data_origin, records_per_shard=records_per_task
        )
    common = dict(
        records_per_task=records_per_task,
        num_epochs=args.num_epochs,
        shuffle=args.shuffle,
        shuffle_shards=args.shuffle_shards,
        max_task_retries=args.max_task_retries,
        task_timeout_secs=args.task_timeout_secs,
        seed=args.seed,
    )
    if args.job_type == "predict":
        task_manager = TaskManager(
            prediction_shards=reader.create_shards(), **common
        )
    elif args.job_type == "evaluate":
        task_manager = TaskManager(
            evaluation_shards=reader.create_shards(), **common
        )
    else:
        task_manager = TaskManager(
            training_shards=reader.create_shards(),
            evaluation_shards=(
                eval_reader.create_shards() if eval_reader else None
            ),
            **common,
        )
    journal = None
    if args.journal_dir:
        from elasticdl_tpu.master.journal import JournalWriter

        journal = JournalWriter(args.journal_dir)
    if journal_state is not None:
        # Master crash-restart: the journal is the exact task/progress
        # state — replaying it supersedes the checkpoint-version
        # skip_records approximation below.
        _check_journal_meta(
            journal_state, _journal_meta(args, records_per_task)
        )
        task_manager.restore_from_journal(journal_state)
        journal.append({"ev": "restart"})
        journal.flush()
        task_manager.attach_journal(journal, bootstrap=False)
    else:
        if journal is not None:
            journal.append(
                {"ev": "meta",
                 "job": _journal_meta(args, records_per_task)}
            )
            # Attach BEFORE any checkpoint skip below, so the skip's
            # done/trim events land in the journal too.
            task_manager.attach_journal(journal, bootstrap=True)
        if args.job_type == "train" and args.checkpoint_dir:
            # Resume: the checkpoint version counts optimizer steps;
            # skip the records those steps consumed so epoch 1
            # continues where the previous run stopped.
            from elasticdl_tpu.utils.checkpoint import CheckpointSaver

            latest = CheckpointSaver(
                args.checkpoint_dir
            ).latest_resumable_version(max(args.num_ps, 1))
            if latest:
                task_manager.skip_records(latest * args.batch_size)
    spec = load_model_spec(args.model_zoo,
                           model_params=args.model_params)
    evaluation_service = None
    if args.job_type == "evaluate":
        if spec.eval_metrics_fn is None:
            raise ValueError(
                "evaluate job requires eval_metrics_fn in the model spec"
            )
        evaluation_service = EvaluationService(
            task_manager, spec.eval_metrics_fn, evaluation_steps=1
        )
        evaluation_service.add_evaluation_task_if_needed(0)
    elif (
        args.evaluation_steps
        and eval_reader is not None
        and spec.eval_metrics_fn is not None
    ):
        evaluation_service = EvaluationService(
            task_manager,
            spec.eval_metrics_fn,
            evaluation_steps=args.evaluation_steps,
        )
    if spec.callbacks:
        # One worker runs on_train_end (model export) after the last
        # training task (reference: deferred train-end task,
        # task_manager.py:35-68 + callbacks.py:23-66).
        task_manager.set_train_end_callback_task()
    rendezvous = None
    if args.distribution_strategy == "collective":
        from elasticdl_tpu.parallel.distributed import (
            MasterCoordinationService,
            derive_reap_secs,
        )

        # The master hosts the per-epoch JAX coordination service so
        # worker churn can never strand the survivors (see
        # docs/designs/elastic_collectives.md).  Per-epoch services
        # bind fresh ports the master's k8s Service does NOT map, so
        # workers must dial the master POD itself: POD_IP (downward
        # API, injected by the submission manifest) on k8s, localhost
        # for process workers.  Fail fast when it's missing — a
        # Service-DNS fallback would only produce opaque worker-side
        # connect timeouts.
        if args.worker_backend == "k8s":
            coord_host = os.environ.get("POD_IP")
            if not coord_host:
                raise RuntimeError(
                    "collective strategy on k8s requires the POD_IP "
                    "downward-API env (the per-epoch coordination "
                    "ports are not mapped by the master Service); "
                    "resubmit with a current client — "
                    "client/k8s_submit.py injects it"
                )
        else:
            coord_host = "localhost"
        rendezvous = RendezvousServer(
            coordinator_factory=MasterCoordinationService(
                host=coord_host,
                # Old-epoch services must outlive the workers'
                # worst-case epoch discovery: workers poll every
                # num_minibatches_per_task steps (worker/main.py
                # passes the same value as check_steps).
                reap_secs=derive_reap_secs(
                    check_steps=max(1, args.num_minibatches_per_task)
                ),
            ).start_epoch,
            journal=journal,
            # Restart re-arms STRICTLY past every epoch a worker can
            # hold (journaled id, +1 for an un-journaled commit racing
            # the crash) so reconnecting workers re-form at a fresh id.
            initial_epoch=(
                journal_state.rendezvous_id + 1 if journal_state else 0
            ),
        )
    ps_manager = None
    if args.distribution_strategy == "ps" and args.num_ps > 0:
        from elasticdl_tpu.master.ps_manager import PSManager

        opt_type, opt_args = spec.ps_optimizer
        ps_manager = PSManager(
            args.num_ps, opt_type, opt_args,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_steps=args.checkpoint_steps,
            evaluation_steps=args.evaluation_steps,
            use_async=args.use_async,
            grads_to_wait=args.grads_to_wait,
            sync_version_tolerance=args.sync_version_tolerance,
            # Worker->PS drills: each shard arms this as its own
            # --rpc_fault_spec (docs/ps_recovery.md).
            ps_fault_spec=args.ps_rpc_fault_spec,
        )
    worker_manager = None
    if args.num_workers > 0:
        worker_args = build_arguments_from_parsed_result(
            args, filter_args=_MASTER_ONLY_ARGS
        )
        if ps_manager is not None:
            worker_args += ["--ps_addrs", ps_manager.addrs]
        worker_manager = WorkerManager(
            _build_worker_backend(args, worker_args),
            num_workers=args.num_workers,
            max_relaunch_count=args.relaunch_on_worker_failure,
        )
    port = args.port
    if args.worker_backend == "k8s" and not port:
        # Pods dial the master through its Service, whose targetPort is
        # fixed (client/k8s_submit.py MASTER_PORT) — a free-port bind
        # would be unreachable.
        from elasticdl_tpu.client.k8s_submit import MASTER_PORT

        port = MASTER_PORT
    interceptors = None
    if args.rpc_fault_spec:
        from elasticdl_tpu.utils.grpc_utils import (
            FaultInjectionInterceptor,
        )

        logger.warning(
            "RPC fault injection armed: %s", args.rpc_fault_spec
        )
        interceptors = [FaultInjectionInterceptor(args.rpc_fault_spec)]
    master = Master(
        task_manager,
        rendezvous_server=rendezvous,
        evaluation_service=evaluation_service,
        worker_manager=worker_manager,
        port=port,
        journal=journal,
        interceptors=interceptors,
    )
    if journal_state is not None:
        master.servicer.restore_from_journal(journal_state)
    if args.worker_backend == "k8s":
        # Workers in other pods reach the master by its service DNS
        # name, not localhost (the service the submit path created).
        master.advertise_addr = "%s-master.%s.svc:%%d" % (
            args.job_name, args.namespace
        )
    master.ps_manager = ps_manager
    return master


def _load_jobs_spec(text):
    """--jobs_spec accepts inline JSON or a path to a JSON file; the
    value is a list of job-spec dicts (docs/scheduler.md)."""
    import json

    if os.path.exists(text):
        with open(text) as fh:
            text = fh.read()
    spec = json.loads(text)
    if not isinstance(spec, list) or not spec:
        raise ValueError(
            "--jobs_spec must be a non-empty JSON list of job specs"
        )
    return spec


def build_multitenant_master(args):
    """The multi-tenant control plane (master/scheduler.py): J jobs,
    each with its own task queue, rendezvous epoch space, journal
    namespace and telemetry aggregate, over ONE shared worker pool
    driven by the resize controller.  Train-type local/collective jobs
    only — a PS-mode job keeps its own single-job master."""
    from elasticdl_tpu.master.journal import (
        JournalWriter,
        replay_journal,
    )
    from elasticdl_tpu.master.scheduler import (
        JobRegistry,
        JobSpec,
        ManagedJob,
        MultiTenantMaster,
        ResizeController,
    )
    from elasticdl_tpu.master.servicer import MasterServicer

    specs = [
        JobSpec.from_dict(entry, defaults=args)
        for entry in _load_jobs_spec(args.jobs_spec)
    ]
    names = [s.name for s in specs]
    if len(set(names)) != len(names):
        raise ValueError("duplicate job names in --jobs_spec: %s"
                         % names)
    if args.num_workers > 0:
        # A managed pool never grows past --num_workers, so a job
        # whose floor exceeds it could NEVER be admitted — the run
        # would hang forever in the admission queue.  Fail fast.
        impossible = [
            s.name for s in specs if s.min_workers > args.num_workers
        ]
        if impossible:
            raise ValueError(
                "jobs %s require min_workers > --num_workers (%d) "
                "and could never be admitted" % (impossible,
                                                 args.num_workers)
            )
    sched_journal = None
    sched_state = None
    if args.journal_dir:
        sched_dir = os.path.join(args.journal_dir, "sched")
        # The recovery trace (same contract as the single-job path):
        # replaying the scheduler journal is this incarnation's root
        # recovery span; post-replay events link back to it so worker
        # outage rides and the restarted schedule stitch into one
        # incident component (the cpu_multitenant drill gate).
        with tracing.span("master.journal_replay") as replay_span:
            sched_state = replay_journal(sched_dir)
        if sched_state is not None:
            restart = sched_state.restarts + 1
            tracing.configure_identity(
                "master", generation=restart, restart=restart,
                link_trace=getattr(replay_span, "trace", None),
            )
        sched_journal = JournalWriter(sched_dir)
        sched_meta = {"jobs": names, "multitenant": True}
        if sched_state is not None:
            _check_journal_meta(sched_state, sched_meta)
            sched_journal.append({"ev": "restart"})
            sched_journal.flush()
        else:
            sched_journal.append({"ev": "meta", "job": sched_meta})
    registry = JobRegistry(
        journal=sched_journal, pool_size=args.num_workers
    )
    for index, spec in enumerate(specs):
        job_id = index + 1   # deterministic: spec order, 1-based (0 =
        #                      "unscoped" on the wire)
        records_per_task = spec.records_per_task
        reader = create_data_reader(
            spec.data_origin, records_per_shard=records_per_task
        )
        task_manager = TaskManager(
            training_shards=reader.create_shards(),
            records_per_task=records_per_task,
            num_epochs=spec.num_epochs,
            shuffle=spec.shuffle,
            shuffle_shards=spec.shuffle_shards,
            max_task_retries=args.max_task_retries,
            task_timeout_secs=args.task_timeout_secs,
            seed=spec.seed,
        )
        job_journal = None
        job_state = None
        if args.journal_dir:
            job_dir = os.path.join(args.journal_dir,
                                   "job-%02d" % job_id)
            job_state = replay_journal(job_dir)
            job_journal = JournalWriter(job_dir)
            if job_state is not None:
                _check_journal_meta(job_state, spec.journal_meta())
                task_manager.restore_from_journal(job_state)
                job_journal.append({"ev": "restart"})
                job_journal.flush()
                task_manager.attach_journal(job_journal,
                                            bootstrap=False)
            else:
                job_journal.append(
                    {"ev": "meta", "job": spec.journal_meta()}
                )
                task_manager.attach_journal(job_journal,
                                            bootstrap=True)
        rendezvous = None
        if spec.distribution_strategy == "collective":
            # Per-job epoch space.  No coordinator factory: pool
            # workers keep process-local device meshes (the same
            # regime the elastic drills run); every join/leave still
            # commits a real journaled epoch for this job only.
            rendezvous = RendezvousServer(
                journal=job_journal,
                initial_epoch=(
                    job_state.rendezvous_id + 1 if job_state else 0
                ),
                name=spec.name,
            )
        servicer = MasterServicer(
            task_manager, rendezvous_server=rendezvous,
            journal=job_journal, job_id=job_id,
        )
        if job_state is not None:
            servicer.restore_from_journal(job_state)
        job = ManagedJob(
            job_id, spec, task_manager, servicer,
            rendezvous=rendezvous, journal=job_journal,
        )
        registry.submit(job, journal=sched_state is None)
    if sched_state is not None:
        registry.restore_from_journal(sched_state)
    worker_manager = None
    if args.num_workers > 0:
        worker_args = build_arguments_from_parsed_result(
            args, filter_args=_MASTER_ONLY_ARGS
        )
        worker_manager = WorkerManager(
            _build_worker_backend(args, worker_args),
            num_workers=args.num_workers,
            max_relaunch_count=args.relaunch_on_worker_failure,
        )
    controller = ResizeController(
        registry, worker_manager=worker_manager,
        cadence_secs=args.sched_cadence_secs,
        moves_per_tick=args.sched_moves_per_tick,
        worker_stale_secs=args.sched_worker_stale_secs,
    )
    interceptors = None
    if args.rpc_fault_spec:
        from elasticdl_tpu.utils.grpc_utils import (
            FaultInjectionInterceptor,
        )

        logger.warning(
            "RPC fault injection armed: %s", args.rpc_fault_spec
        )
        interceptors = [FaultInjectionInterceptor(args.rpc_fault_spec)]
    return MultiTenantMaster(
        registry, controller, worker_manager=worker_manager,
        port=args.port, sched_journal=sched_journal,
        interceptors=interceptors,
    )


def _arm_master_slo(servicers):
    """Default master SLO: zero sustained stragglers (the acceptance
    objective the straggler detector feeds — a flagged worker IS a
    breach on /alertz and an ``slo.breach`` flight-recorder event),
    plus any operator rules from $ELASTICDL_SLO_SPEC."""
    from elasticdl_tpu.utils import slo as slo_mod

    wd = slo_mod.default_watchdog()
    wd.add_source(
        "straggler_workers",
        lambda: float(sum(len(s.stragglers()) for s in servicers())))
    wd.add_rule("value(straggler_workers) < 1", name="stragglers",
                description="no worker sustained-flagged as a "
                            "straggler (cross-worker step-time skew)")
    wd.arm_from_env()


def _run_multitenant(args):
    master = build_multitenant_master(args)
    master.prepare()
    SETUP.close()   # a master that launches no worker says so here
    _arm_master_slo(
        lambda: [job.servicer for job in master.registry.jobs()])
    status_server = None
    if args.status_port >= 0:
        from elasticdl_tpu.master.status_server import (
            MultiTenantStatusServer,
        )

        status_server = MultiTenantStatusServer(
            master.registry, worker_manager=master.worker_manager,
            port=args.status_port,
        )
        status_server.start()
    try:
        return master.run()
    finally:
        if status_server is not None:
            status_server.stop()
        for job in master.registry.jobs():
            if job.journal is not None:
                job.journal.close()
        if master.sched_journal is not None:
            master.sched_journal.close()


def main(argv=None):
    # The interpreter's start and this module's import chain end here.
    SETUP.begin("master", MASTER_SETUP, logger)
    args = parse_master_args(argv)
    tracing.configure_identity("master")
    tracing.arm_crash_dump()
    logger.info("master starting: %s", vars(args))
    if args.jobs_spec:
        return _run_multitenant(args)
    master = build_master(args)
    master.prepare()
    SETUP.close()   # a master that launches no worker says so here
    _arm_master_slo(lambda: [master.servicer])
    status_server = None
    if args.status_port >= 0:
        from elasticdl_tpu.master.status_server import StatusServer

        status_server = StatusServer(
            master.task_manager,
            worker_manager=master.worker_manager,
            rendezvous_server=master.rendezvous_server,
            servicer=master.servicer,
            port=args.status_port,
        )
        status_server.start()
    if getattr(master, "ps_manager", None) is not None:
        master.ps_manager._master_addr = "localhost:%d" % master.port
        master.ps_manager.start()
    try:
        return master.run()
    finally:
        if getattr(master, "ps_manager", None) is not None:
            master.ps_manager.stop()
        if status_server is not None:
            status_server.stop()
        if master.journal is not None:
            master.journal.close()


if __name__ == "__main__":
    raise SystemExit(main())
