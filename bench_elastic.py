"""Elastic recovery benchmark: time from worker preemption to restored
training progress (the BASELINE.json "elastic recovery time after
preempt" metric).

Runs a managed job with the process backend, SIGKILLs a worker mid-run,
and measures:
  - relaunch_secs: preemption -> replacement worker process launched
  - recovery_secs: preemption -> first task completed after the
    preemption (training is demonstrably making progress again)

Control-plane metric: runs on CPU workers; the recovery path is identical
for TPU-VM workers (same state flows).  Prints one JSON line.
"""

import json
import os
import sys
import threading
import time

# A host-side CPU bench: the drills measure the control plane.  Pinned to
# the CPU so that this process and its CPU-leg workers take no chip on a
# TPU host; only the tpu_cold / tpu_warm legs aim their workers at one.
os.environ["JAX_PLATFORMS"] = "cpu"


def run_drill(num_workers=2, records=4096, worker_env=None,
              deadline_secs=180, extra_worker_args=None,
              with_rendezvous=False, wait_complete=False):
    """One preemption drill.  ``worker_env`` overrides the worker
    process env — the TPU legs use it to aim workers at the real chip
    and at a persistent compilation cache (see ``main``).
    ``extra_worker_args``: appended worker flags — the fused leg passes
    ``--fused_steps`` to drill preemption against the windowed hot
    loop (worker/fused_driver.py).

    ``with_rendezvous``: attach a RendezvousServer so collective-mode
    workers get membership epochs (no coordinator factory — each
    worker keeps a process-local device mesh, which is what this
    container's jax supports, but every join/leave commits a real
    epoch, so the preemption exercises snapshot -> rebuild ->
    re-partition on the survivors).  ``wait_complete``: after recovery
    is measured, wait for the JOB to finish and account every record —
    the zero-lost/zero-double-count gate of the zero1 churn leg."""
    import jax

    jax.config.update("jax_platforms", "cpu")  # master stays on CPU

    from elasticdl_tpu.data.factory import create_data_reader
    from elasticdl_tpu.master.master import Master
    from elasticdl_tpu.master.rendezvous import RendezvousServer
    from elasticdl_tpu.master.task_manager import TaskManager
    from elasticdl_tpu.master.worker_manager import (
        ProcessWorkerBackend,
        WorkerManager,
    )
    from elasticdl_tpu.proto import elastic_pb2 as pb

    records_per_task = 128
    num_epochs = 2
    reader = create_data_reader("synthetic_mnist:%d" % records,
                                records_per_shard=records_per_task)
    task_manager = TaskManager(
        training_shards=reader.create_shards(),
        records_per_task=records_per_task,
        num_epochs=num_epochs,
    )
    worker_args = [
        "--model_zoo", "mnist", "--data_origin",
        "synthetic_mnist:%d" % records, "--batch_size", "32",
        "--num_minibatches_per_task", "4", "--num_epochs",
        str(num_epochs),
    ] + list(extra_worker_args or [])
    worker_manager = WorkerManager(
        ProcessWorkerBackend(worker_args=worker_args,
                             env=worker_env or {}),
        num_workers=num_workers,
    )
    rendezvous = (
        RendezvousServer(grace_secs=1.0) if with_rendezvous else None
    )
    master = Master(task_manager, worker_manager=worker_manager,
                    rendezvous_server=rendezvous)

    events = {}
    launch_times = []
    worker_manager.add_start_callback(
        lambda wid: launch_times.append((wid, time.perf_counter()))
    )

    master.prepare()
    runner = threading.Thread(target=master.run, daemon=True)
    runner.start()

    # wait until training is underway (a few tasks done)
    deadline = time.time() + deadline_secs
    while time.time() < deadline:
        if task_manager.counts()["completed"][pb.TRAINING] >= 2:
            break
        time.sleep(0.2)

    victim = worker_manager.live_worker_ids()[0]
    completed_before = task_manager.counts()["completed"][pb.TRAINING]
    t_kill = time.perf_counter()
    worker_manager.preempt_worker(victim, force=True)

    # relaunch time: first launch event after the kill
    relaunch_secs = None
    recovery_secs = None
    deadline = time.time() + deadline_secs
    while time.time() < deadline:
        if relaunch_secs is None:
            later = [t for wid, t in launch_times if t > t_kill]
            if later:
                relaunch_secs = later[0] - t_kill
        counts = task_manager.counts()
        if counts["completed"][pb.TRAINING] > completed_before:
            recovery_secs = time.perf_counter() - t_kill
            break
        time.sleep(0.05)

    expected_tasks = -(-records // records_per_task) * num_epochs
    records_ok = None
    if wait_complete:
        # Run the job to the end and account every record: the
        # preempted worker's in-flight task must be requeued (never
        # lost) and its completed batches never double-reported, so
        # exactly the expected task count completes — no more (a
        # double count would finish a task twice), no less.
        deadline = time.time() + deadline_secs
        while time.time() < deadline:
            counts = task_manager.counts()
            done = (counts["completed"][pb.TRAINING]
                    + counts["failed"][pb.TRAINING])
            if counts["todo"] == 0 and counts["doing"] == 0 and (
                done >= expected_tasks
            ):
                break
            time.sleep(0.2)
        counts = task_manager.counts()
        records_ok = (
            counts["completed"][pb.TRAINING] == expected_tasks
            and counts["failed"][pb.TRAINING] == 0
        )

    master.stop()
    runner.join(timeout=30)
    counts = task_manager.counts()
    out = {
        "recovery_secs": round(recovery_secs, 3) if recovery_secs
        else None,
        "relaunch_secs": round(relaunch_secs, 3) if relaunch_secs
        else None,
        "tasks_failed_permanently": counts["failed"][pb.TRAINING],
        "tasks_completed": counts["completed"][pb.TRAINING],
    }
    if wait_complete:
        out["tasks_expected"] = expected_tasks
        out["all_records_accounted"] = records_ok
    return out


def _reap_orphan_workers(marker):
    """Workers of a SIGKILLed master are re-parented to init; find any
    stragglers by the drill's distinctive data-origin arg in
    /proc/*/cmdline and kill them (best effort, drill hygiene)."""
    import signal

    reaped = 0
    for pid in os.listdir("/proc"):
        if not pid.isdigit() or int(pid) == os.getpid():
            continue
        try:
            with open("/proc/%s/cmdline" % pid, "rb") as fh:
                cmd = fh.read().decode("utf-8", "replace")
        except OSError:
            continue
        if marker in cmd and "elasticdl_tpu.worker.main" in cmd:
            try:
                os.kill(int(pid), signal.SIGKILL)
                reaped += 1
            except OSError:
                pass
    return reaped


def run_master_kill_drill(records=4160, deadline_secs=300):
    """SIGKILL the MASTER mid-training, restart it from the job-state
    journal, and prove the job completes with exact task accounting.

    Phase 1 master launches 2 process workers and journals to a temp
    dir.  The kill orphans the workers; their outage-riding clients
    (utils/retry.py) keep retrying against the fixed port.  Phase 2
    relaunches the master with --num_workers 0 on the SAME port: it
    replays the journal, requeues the in-flight tasks, and the
    surviving workers reconnect WITHOUT a process restart.  Measures
    recovery_secs (kill -> first task completion after restart,
    observed by replaying the live journal) and asserts completed ==
    expected with zero permanent failures — a double-counted record
    would overshoot, a lost one would hang/undershoot.

    Tracing gate (docs/observability.md): every process runs with
    $ELASTICDL_TRACE_DIR armed; the surviving workers' and the
    restarted master's flight-recorder dumps must stitch into ONE
    connected trace covering kill (worker-side rpc_retry events in the
    outage window) -> recovery (master #2's journal replay, linked via
    link_trace) -> the first post-recovery task completion
    (restart-stamped task.completed) — ``trace_connected`` below."""
    import shutil
    import signal
    import subprocess
    import tempfile

    from elasticdl_tpu.master.journal import replay_journal
    from elasticdl_tpu.proto import elastic_pb2 as pb
    from elasticdl_tpu.utils import tracing
    from elasticdl_tpu.utils.grpc_utils import find_free_port

    records_per_task = 32 * 4
    num_epochs = 2
    expected_tasks = -(-records // records_per_task) * num_epochs
    data_origin = "synthetic_mnist:%d" % records
    jdir = tempfile.mkdtemp(prefix="edl_journal_")
    tdir = os.path.join(jdir, "traces")
    port = find_free_port()
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        # Orphaned workers must die promptly if the job wedges; 45 s
        # comfortably covers the master restart gap.
        ELASTICDL_RPC_DEADLINE_SECS="45",
        # Flight-recorder dumps on exit: workers + master #2 land here
        # (master #1 is SIGKILLed — by definition it leaves no dump;
        # the survivors' rings reconstruct the incident).
        ELASTICDL_TRACE_DIR=tdir,
    )
    base_cmd = [
        sys.executable, "-m", "elasticdl_tpu.master.main",
        "--model_zoo", "mnist", "--data_origin", data_origin,
        "--batch_size", "32", "--num_minibatches_per_task", "4",
        "--num_epochs", str(num_epochs),
        "--journal_dir", jdir, "--port", str(port),
    ]

    def completed_training():
        state = replay_journal(jdir)
        if state is None:
            return 0
        return state.completed_counts.get(int(pb.TRAINING), 0)

    out = {"tasks_expected": expected_tasks}
    master2 = None
    master1 = subprocess.Popen(base_cmd + ["--num_workers", "2"],
                               env=env)
    try:
        deadline = time.time() + deadline_secs
        while time.time() < deadline and completed_training() < 3:
            time.sleep(0.25)
        t_kill = time.perf_counter()
        master1.send_signal(signal.SIGKILL)
        master1.wait(timeout=30)
        # Baseline AFTER the master is verifiably dead: the journal is
        # final, so any later increase can only come from master #2.
        # (Reading it before the SIGKILL lands races a concurrent done
        # flush and fakes a near-zero recovery time.)
        done_at_kill = completed_training()
        out["tasks_done_at_kill"] = done_at_kill

        # Restart from the journal; the orphaned workers reconnect.
        master2 = subprocess.Popen(base_cmd + ["--num_workers", "0"],
                                   env=env)
        recovery_secs = None
        deadline = time.time() + deadline_secs
        while time.time() < deadline:
            if recovery_secs is None and (
                completed_training() > done_at_kill
            ):
                recovery_secs = time.perf_counter() - t_kill
            if master2.poll() is not None:
                break
            time.sleep(0.25)
        if master2.poll() is None:
            master2.kill()
            master2.wait(timeout=10)
            out["error"] = "restarted master did not finish in time"
        out["recovery_secs"] = (
            round(recovery_secs, 3) if recovery_secs else None
        )
        out["master2_exit_code"] = master2.poll()
        state = replay_journal(jdir)
        completed = state.completed_counts.get(int(pb.TRAINING), 0)
        failed = sum(state.failed_counts.values())
        out["tasks_completed"] = completed
        out["tasks_failed_permanently"] = failed
        out["restarts_journaled"] = state.restarts
        # Exact accounting: every task completes exactly once across
        # the crash (the journal's done-set can't double-count).
        out["all_records_accounted"] = (
            completed == expected_tasks and failed == 0
            and master2.poll() == 0
        )
        out["journal_bytes"] = os.path.getsize(
            os.path.join(jdir, "job.journal")
        )
        # Trace gate: the orphaned workers exit (and dump) shortly
        # after master #2 reports the job done — wait briefly for
        # master #2 + both workers' rings (an idle worker that rides
        # its WAIT poll into the reaper leaves no dump; the gate only
        # needs ONE worker ring plus the master's).
        deadline = time.time() + 10
        while time.time() < deadline:
            dumps = (
                [] if not os.path.isdir(tdir) else
                [f for f in os.listdir(tdir)
                 if f.endswith(".trace.json")]
            )
            if len(dumps) >= 2:
                break
            time.sleep(0.25)
        events = tracing.load_dumps(tdir)
        components = tracing.trace_components(events)

        def _connected(component):
            names = {e["name"] for e in component}
            return (
                {"rpc_retry", "journal.replayed",
                 "task.completed"} <= names
                and any(e.get("restart") for e in component
                        if e["name"] == "task.completed")
            )

        out["trace_dumps"] = len(dumps)
        out["trace_events"] = len(events)
        out["trace_connected"] = any(
            _connected(c) for c in components
        )
    finally:
        for proc in (master1, master2):
            if proc is not None and proc.poll() is None:
                proc.kill()
                try:
                    proc.wait(timeout=10)
                except Exception:  # noqa: BLE001 — best-effort reap
                    pass
        reaped = _reap_orphan_workers(data_origin)
        if reaped:
            out["orphan_workers_reaped"] = reaped
        shutil.rmtree(jdir, ignore_errors=True)
    return out


def _scan_procs(marker, module):
    """Pids whose cmdline holds both ``marker`` and ``module`` —
    (pid, cmdline) pairs, the drill's view of a managed job's
    subprocess tree."""
    found = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit() or int(pid) == os.getpid():
            continue
        try:
            with open("/proc/%s/cmdline" % pid, "rb") as fh:
                cmd = fh.read().replace(b"\x00", b" ").decode(
                    "utf-8", "replace"
                )
        except OSError:
            continue
        if marker in cmd and module in cmd:
            found.append((int(pid), cmd))
    return found


def run_ps_kill_drill(records=1024, deadline_secs=300):
    """SIGKILL one PS SHARD mid-training (the worker->PS direction of
    the recovery drills, docs/ps_recovery.md): PSManager relaunches it
    with a bumped restart generation and restore from the newest
    COMMITTED cross-shard checkpoint; the workers ride the outage on
    the same port through the PSClient retry policy — WITHOUT a worker
    restart — detect the generation change, drop fenced in-flight
    pushes, and reconcile.  Gates:

      - shard relaunched with --generation 2 + restore (cmdline-proved)
      - restored version was a COMMITTED label (consistent across all
        shards — CheckpointSaver.is_valid_version)
      - zero worker relaunches (outage ridden, not died through)
      - exact record accounting: completed == expected, 0 failed
      - every push stamped by the dead incarnation that reached the new
        one was generation-fenced (rejected, never applied) — counted
        from the servicer's fencing log lines

    Additionally arms --ps_rpc_fault_spec so the run ALSO rides
    deterministic injected worker->PS faults (every 31st dense pull
    answers UNAVAILABLE) through the same retry plumbing.  A fault
    spec that fails to parse kills every shard at startup, so the
    drill doubles as a grammar conformance check."""
    import re
    import shutil
    import signal
    import subprocess
    import tempfile

    from elasticdl_tpu.master.journal import replay_journal
    from elasticdl_tpu.proto import elastic_pb2 as pb
    from elasticdl_tpu.utils.checkpoint import CheckpointSaver
    from elasticdl_tpu.utils.grpc_utils import find_free_port

    records_per_task = 32 * 4
    num_epochs = 2
    expected_tasks = -(-records // records_per_task) * num_epochs
    data_origin = "synthetic_ctr:%d" % records
    jdir = tempfile.mkdtemp(prefix="edl_psjournal_")
    ckpt = tempfile.mkdtemp(prefix="edl_psckpt_")
    port = find_free_port()
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        # The outage window is PSManager's relaunch (~seconds); 45 s
        # of riding covers it with margin while bounding a wedged run.
        ELASTICDL_RPC_DEADLINE_SECS="45",
    )
    cmd = [
        sys.executable, "-m", "elasticdl_tpu.master.main",
        "--model_zoo", "deepfm", "--data_origin", data_origin,
        "--batch_size", "32", "--num_minibatches_per_task", "4",
        "--num_epochs", str(num_epochs),
        "--distribution_strategy", "ps", "--num_ps", "2",
        "--num_workers", "2",
        "--checkpoint_dir", ckpt, "--checkpoint_steps", "8",
        "--journal_dir", jdir, "--port", str(port),
        # Pipelined pushes + embedding prefetch ON so the kill lands
        # against in-flight state the reconcile must drop.
        "--async_push_window", "2", "--get_model_steps", "2",
        # Worker->PS deterministic fault injection riding alongside
        # the kill (docs/master_recovery.md grammar).
        "--ps_rpc_fault_spec",
        "pull_dense_parameters:every=31,code=UNAVAILABLE",
    ]

    def completed_training():
        state = replay_journal(jdir)
        if state is None:
            return 0
        return state.completed_counts.get(int(pb.TRAINING), 0)

    out = {"tasks_expected": expected_tasks}
    log_path = os.path.join(jdir, "drill.log")
    log_fh = open(log_path, "w")
    master = subprocess.Popen(cmd, env=env, stdout=log_fh,
                              stderr=subprocess.STDOUT, text=True)
    try:
        saver = CheckpointSaver(ckpt)
        # Labels observed committed at ANY point during the run: the
        # restored-label gate must judge against commit state around
        # restore time, not after end-of-job GC pruned old labels.
        seen_committed = set()
        deadline = time.time() + deadline_secs
        # Kill only after a checkpoint label COMMITTED across both
        # shards (else the relaunch legitimately restores nothing) and
        # training demonstrably progresses.
        while time.time() < deadline:
            seen_committed.update(saver.versions())
            if completed_training() >= 3 and seen_committed:
                break
            time.sleep(0.25)
        shards = _scan_procs(ckpt, "elasticdl_tpu.ps.server")
        victim = next((pid for pid, cmd_ in shards
                       if "--ps_id 0" in cmd_), None)
        workers_before = sorted(
            pid for pid, _ in _scan_procs(data_origin,
                                          "elasticdl_tpu.worker.main")
        )
        out["error"] = None
        if victim is None:
            out["error"] = "PS shard 0 process not found"
            return out
        done_baseline = completed_training()
        t_kill = time.perf_counter()
        os.kill(victim, signal.SIGKILL)

        relaunch_secs = None
        recovery_secs = None
        deadline = time.time() + deadline_secs
        while time.time() < deadline:
            if relaunch_secs is None:
                for pid, cmd_ in _scan_procs(
                    ckpt, "elasticdl_tpu.ps.server"
                ):
                    if pid != victim and "--ps_id 0" in cmd_:
                        relaunch_secs = time.perf_counter() - t_kill
                        out["relaunch_cmdline_ok"] = (
                            "--generation 2" in cmd_
                            and "--checkpoint_dir_for_init" in cmd_
                        )
            if recovery_secs is None and (
                completed_training() > done_baseline
            ):
                recovery_secs = time.perf_counter() - t_kill
            seen_committed.update(saver.versions())
            if master.poll() is not None:
                break
            time.sleep(0.25)
        if master.poll() is None:
            master.kill()
            master.wait(timeout=10)
            out["error"] = "job did not finish in time"
        out["relaunch_secs"] = (
            round(relaunch_secs, 3) if relaunch_secs else None
        )
        out["recovery_secs"] = (
            round(recovery_secs, 3) if recovery_secs else None
        )
        state = replay_journal(jdir)
        completed = state.completed_counts.get(int(pb.TRAINING), 0)
        failed = sum(state.failed_counts.values())
        out["tasks_completed"] = completed
        out["tasks_failed_permanently"] = failed
        log_fh.flush()
        with open(log_path) as fh:
            log = fh.read()
        # Outage ridden, not died through: no worker was ever
        # relaunched (the manager logs every relaunch decision).
        out["worker_relaunches"] = log.count("relaunch=True")
        out["workers_at_kill"] = len(workers_before)
        # Restore consistency: the relaunched shard logged the version
        # it restored; that label must be a COMMITTED (all-shard) one.
        restored = re.findall(r"restored PS shard 0 from version (\d+)",
                              log)
        out["restored_version"] = (
            int(restored[-1]) if restored else None
        )
        out["restored_version_committed"] = bool(
            restored and int(restored[-1]) in seen_committed
        )
        # Fencing: every dead-incarnation push that reached the new
        # shard was rejected (servicer logs each), and the workers
        # reconciled (dropped pipelined pushes + re-pulled).
        out["fenced_pushes"] = log.count(
            "rejecting gradients stamped by generation"
        )
        out["worker_reconciles"] = log.count("reconciled PS restart")
        out["injected_faults_ridden"] = log.count(
            "fault injection: aborting"
        )
        out["all_records_accounted"] = (
            completed == expected_tasks and failed == 0
            and master.poll() == 0
            and out["worker_relaunches"] == 0
            and out["restored_version_committed"]
            and out.get("relaunch_cmdline_ok") is True
            and out["error"] is None
        )
        if out["error"] is None:
            del out["error"]
    finally:
        if master.poll() is None:
            master.kill()
            try:
                master.wait(timeout=10)
            except Exception:  # noqa: BLE001 — best-effort reap
                pass
        log_fh.close()
        _reap_orphan_workers(data_origin)
        for pid, _ in _scan_procs(ckpt, "elasticdl_tpu.ps.server"):
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        shutil.rmtree(jdir, ignore_errors=True)
        shutil.rmtree(ckpt, ignore_errors=True)
    return out


def run_multitenant_drill(records_a=1024, records_b=4096,
                          deadline_secs=300):
    """The multi-tenant scheduler drill (docs/scheduler.md): TWO jobs
    over ONE shared 4-worker pool, with a controller-driven resize and
    a master SIGKILL landing MID-RESIZE.

    Topology: jobA (small) and jobB (larger) are admitted together and
    the pool splits 2/2.  jobA finishes first; the resize controller
    reclaims its workers one per tick (each move a journaled, traced
    decision).  The drill SIGKILLs the master the moment the FIRST
    move's ``sched`` record lands in the scheduler journal — the
    decision is durable, the drained worker's re-register is not — and
    restarts it with ``--num_workers 0`` on the same port.  The replay
    must recover the assignment map exactly; the worker still parked
    on finished jobA then gets a LIVE post-restart resize decision,
    whose trace must stitch to the worker's re-register + in-place
    pipeline rebuild.  Gates:

      - both jobs complete with exact per-job record accounting
        (per-job journal namespaces, ``all_records_accounted`` each)
      - ZERO worker process restarts: the 4 pool pids at kill time are
        the only worker pids the drill ever observes
      - >= 1 controller-driven resize (``sched`` assign with prev != 0)
      - trace connectivity: one component holds the resize decision
        (``sched.resize``), the drained worker's re-register
        (``sched.worker_reassigned``, link_trace) and the worker's
        in-place rebuild (``worker.job_switch``)
      - STRAGGLER gate (ISSUE 14): worker 1 is DELIBERATELY throttled
        (ELASTICDL_STEP_THROTTLE_SPEC) — the restarted master's
        straggler sweep must flag it (observed on /status within the
        drill window, or post-hoc via the journal-independent trace
        dump), and the default ``value(straggler_workers) < 1`` SLO
        rule must land an ``slo.breach`` event in the master's flight
        recorder + show on /alertz."""
    import shutil
    import signal
    import subprocess
    import tempfile

    from elasticdl_tpu.master.journal import (
        journal_path,
        replay_journal,
        scan_frames,
    )
    from elasticdl_tpu.proto import elastic_pb2 as pb
    from elasticdl_tpu.utils import tracing
    from elasticdl_tpu.utils.grpc_utils import find_free_port

    records_per_task = 32 * 4
    expected = {
        "jobA": -(-records_a // records_per_task),
        "jobB": -(-records_b // records_per_task),
    }
    # Template data origin: distinctive marker for /proc scans; differs
    # from both jobs so every worker exercises the handshake rebuild.
    template_origin = "synthetic_mnist:1408"
    jdir = tempfile.mkdtemp(prefix="edl_mtjournal_")
    tdir = os.path.join(jdir, "traces")
    jobs_path = os.path.join(jdir, "jobs.json")
    with open(jobs_path, "w") as fh:
        json.dump([
            {"name": "jobA", "data_origin":
             "synthetic_mnist:%d" % records_a,
             "min_workers": 1, "max_workers": 3, "weight": 1.0},
            {"name": "jobB", "data_origin":
             "synthetic_mnist:%d" % records_b,
             "min_workers": 1, "max_workers": 4, "weight": 1.0},
        ], fh)
    port = find_free_port()
    status_port = find_free_port()
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        ELASTICDL_RPC_DEADLINE_SECS="45",
        ELASTICDL_TRACE_DIR=tdir,
        # Straggler staging: worker 1 (one member of the shared pool,
        # targeted by id through the inherited env) sleeps 500 ms per
        # step — ~4x this rig's ~170 ms per-step-loop CPU mnist step,
        # a GROSS straggler that clears the 2.0x ratio bar by a full
        # log bucket (the p50 estimate quantizes at ~2.15x per
        # bucket) while still stepping fast enough to fill two
        # 4-sample sweep windows before its job drains.
        ELASTICDL_STEP_THROTTLE_SPEC="1:500",
    )
    base_cmd = [
        sys.executable, "-m", "elasticdl_tpu.master.main",
        "--jobs_spec", jobs_path,
        "--model_zoo", "mnist", "--data_origin", template_origin,
        "--batch_size", "32", "--num_minibatches_per_task", "4",
        "--num_epochs", "1",
        "--journal_dir", jdir, "--port", str(port),
        "--sched_cadence_secs", "0.5",
        "--status_port", str(status_port),
    ]

    def _http_json(path, timeout=2.0):
        import urllib.request

        try:
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d%s" % (status_port, path),
                    timeout=timeout) as resp:
                return json.loads(resp.read())
        except Exception:  # noqa: BLE001 — master between lives
            return None
    sched_dir = os.path.join(jdir, "sched")

    def sched_moves():
        """Resize decisions journaled so far: assign records whose
        ``prev`` names a real job — a cross-job MOVE, not a pool
        registration."""
        path = journal_path(sched_dir)
        if not os.path.exists(path):
            return 0
        with open(path, "rb") as fh:
            data = fh.read()
        return sum(
            1 for rec, _ in scan_frames(data)
            if rec.get("ev") == "sched" and rec.get("op") == "assign"
            and rec.get("prev")
        )

    def job_completed(job_dir):
        state = replay_journal(os.path.join(jdir, job_dir))
        if state is None:
            return 0, 0
        return (state.completed_counts.get(int(pb.TRAINING), 0),
                sum(state.failed_counts.values()))

    out = {"tasks_expected": dict(expected)}
    log_path = os.path.join(jdir, "drill.log")
    log_fh = open(log_path, "w")
    master2 = None
    master1 = subprocess.Popen(base_cmd + ["--num_workers", "4"],
                               env=env, stdout=log_fh,
                               stderr=subprocess.STDOUT, text=True)
    worker_pids = set()

    def scan_workers():
        pids = {
            pid for pid, _ in _scan_procs(
                template_origin, "elasticdl_tpu.worker.main")
        }
        worker_pids.update(pids)
        return pids

    try:
        # Wait for the mid-resize moment: jobA drains, the controller's
        # FIRST reclaim decision lands in the scheduler journal.
        deadline = time.time() + deadline_secs
        while time.time() < deadline and sched_moves() < 1:
            scan_workers()
            time.sleep(0.1)
        pids_at_kill = scan_workers()
        out["workers_at_kill"] = len(pids_at_kill)
        out["moves_at_kill"] = sched_moves()
        t_kill = time.perf_counter()
        master1.send_signal(signal.SIGKILL)
        master1.wait(timeout=30)
        if out["moves_at_kill"] < 1:
            out["error"] = "no resize decision before deadline"
            return out

        master2 = subprocess.Popen(base_cmd + ["--num_workers", "0"],
                                   env=env, stdout=log_fh,
                                   stderr=subprocess.STDOUT, text=True)
        recovery_secs = None
        straggler_on_status = False
        breach_on_alertz = False
        deadline = time.time() + deadline_secs
        while time.time() < deadline:
            scan_workers()
            done_b, _ = job_completed("job-02")
            if recovery_secs is None and done_b >= expected["jobB"]:
                recovery_secs = time.perf_counter() - t_kill
            if not straggler_on_status:
                # The throttled worker on the live /status surface:
                # the restarted master's sweeps re-flag it from fresh
                # state; once sustained it STAYS flagged (un-flagging
                # takes a healthy judged window), so this poll is not
                # racing a transient.
                status = _http_json("/status")
                for job in (status or {}).get("jobs", {}).values():
                    workers = (job.get("telemetry") or {}).get(
                        "workers", {})
                    if any(t.get("straggler")
                           for t in workers.values()):
                        straggler_on_status = True
            if not breach_on_alertz:
                alertz = _http_json("/alertz")
                if alertz and "stragglers" in alertz.get(
                        "breaching", []):
                    breach_on_alertz = True
            if master2.poll() is not None:
                break
            time.sleep(0.25)
        if master2.poll() is None:
            master2.kill()
            master2.wait(timeout=10)
            out["error"] = "restarted master did not finish in time"
        out["master2_exit_code"] = master2.poll()
        out["recovery_secs"] = (
            round(recovery_secs, 3) if recovery_secs else None
        )

        # Per-job exact accounting from each job's journal namespace.
        accounted = {}
        for job_dir, name in (("job-01", "jobA"), ("job-02", "jobB")):
            completed, failed = job_completed(job_dir)
            accounted[name] = (
                completed == expected[name] and failed == 0
            )
            out["tasks_completed_%s" % name] = completed
            out["tasks_failed_%s" % name] = failed
        out["resize_moves_total"] = sched_moves()
        sched_state = replay_journal(sched_dir)
        out["restarts_journaled"] = (
            sched_state.restarts if sched_state else 0
        )

        # Zero worker process restarts: the pool pids at kill time are
        # the only worker pids ever observed, and the master log holds
        # no relaunch decision.
        log_fh.flush()
        with open(log_path) as fh:
            log = fh.read()
        out["worker_relaunches"] = log.count("relaunch=True")
        out["worker_pids_observed"] = len(worker_pids)
        zero_restarts = (
            out["worker_relaunches"] == 0
            and worker_pids == pids_at_kill
            and len(pids_at_kill) == 4
        )
        out["zero_worker_restarts"] = zero_restarts

        # Trace gate: master #2's live resize decision + the drained
        # worker's re-register + its in-place pipeline rebuild in ONE
        # connected component (master #1's ring died with it — the
        # post-restart decision is the one that must stitch).
        deadline = time.time() + 10
        while time.time() < deadline:
            dumps = (
                [] if not os.path.isdir(tdir) else
                [f for f in os.listdir(tdir)
                 if f.endswith(".trace.json")]
            )
            if len(dumps) >= 2:
                break
            time.sleep(0.25)
        events = tracing.load_dumps(tdir)
        components = tracing.trace_components(events)
        required = {"sched.resize", "sched.worker_reassigned",
                    "worker.job_switch"}
        out["trace_dumps"] = len(dumps)
        out["trace_events"] = len(events)
        out["trace_connected"] = any(
            required <= {e["name"] for e in c} for c in components
        )

        # Straggler gate (ISSUE 14): flagged live on /status +
        # breaching on /alertz, AND the slo.breach / worker.straggler
        # events in the master's dumped flight recorder.
        names = {e.get("name") for e in events}
        out["straggler_on_status"] = straggler_on_status
        out["slo_breach_on_alertz"] = breach_on_alertz
        out["slo_breach_in_recorder"] = "slo.breach" in names
        out["straggler_event_in_recorder"] = (
            "worker.straggler" in names)
        straggler_gate = (
            straggler_on_status and breach_on_alertz
            and out["slo_breach_in_recorder"]
            and out["straggler_event_in_recorder"]
        )
        out["straggler_detected"] = straggler_gate

        out["all_records_accounted"] = (
            all(accounted.values())
            and master2.poll() == 0
            and zero_restarts
            and out["resize_moves_total"] >= 1
            and out["trace_connected"]
            and straggler_gate
        )
        out["per_job_accounted"] = accounted
    finally:
        for proc in (master1, master2):
            if proc is not None and proc.poll() is None:
                proc.kill()
                try:
                    proc.wait(timeout=10)
                except Exception:  # noqa: BLE001 — best-effort reap
                    pass
        log_fh.close()
        reaped = _reap_orphan_workers(template_origin)
        if reaped:
            out["orphan_workers_reaped"] = reaped
        shutil.rmtree(jdir, ignore_errors=True)
    return out


def main():
    """Three legs (VERDICT r4 #3 — BASELINE.json metric #3 and SURVEY
    §7's named hard part, re-init -> re-shard -> re-compile):

    cpu        control-plane drill, 2 CPU workers (state flows only)
    tpu_cold   1 TPU worker, EMPTY compilation cache: preemption ->
               replacement boots, re-inits the TPU backend,
               RE-COMPILES the train step, completes a task
    tpu_warm   same with the persistent cache already populated (by
               tpu_cold) — the production recovery path

    The TPU legs run only on a host with a TPU chip (counted from the
    device nodes, without JAX) and each in its own subprocess with a
    timeout.  Their workers place the compile cache by the one rule
    (utils/device.place_compile_cache): ``JAX_COMPILATION_CACHE_DIR`` if
    it is set, else ``<checkout>/.jax_cache``.  The cold leg empties
    only the repo's own default; a directory given from outside is never
    deleted (the leg then says its cache was not emptied).  Headline
    value = tpu_warm recovery when measured (else cpu, a host
    control-plane number), with every leg in the detail.  The TPU legs
    are not measured on the current code.
    """
    import shutil
    import subprocess

    budget = int(os.environ.get("ELASTICDL_ELASTIC_BENCH_BUDGET",
                                "900"))
    t0 = time.monotonic()

    def remaining():
        return budget - (time.monotonic() - t0) - 10

    detail = {"platform_legs": {}}
    legs = detail["platform_legs"]
    legs["cpu"] = run_drill()
    legs["cpu"]["note"] = "2 CPU process workers; control-plane cost"
    # Same drill against the fused-step hot loop: preemption must land
    # between windows, flush the in-flight window's progress, and
    # requeue the remainder — recovery and zero-task-loss must match
    # the per-step leg (worker/fused_driver.py semantics).
    legs["cpu_fused"] = run_drill(
        extra_worker_args=["--fused_steps", "4"]
    )
    legs["cpu_fused"]["note"] = (
        "2 CPU process workers, --fused_steps 4: preemption against "
        "the windowed hot loop"
    )
    # ZeRO-1 churn leg: collective workers (each on a process-local
    # 4-device virtual mesh — the drill's rendezvous has no coordinator
    # factory, so epochs re-form per-process worlds) with sharded
    # optimizer state and fused windows.  The kill lands a
    # real rendezvous epoch on the survivor: snapshot gathers its live
    # zero1 shards, rebuild re-shards them, and the job then runs to
    # completion with every record accounted exactly once.  (The
    # trajectory-bitwise-through-resize assertion lives in
    # bench_zero.py's in-process churn, where both runs share one
    # param state.)
    legs["cpu_zero1"] = run_drill(
        extra_worker_args=[
            "--distribution_strategy", "collective",
            "--zero1", "true", "--fused_steps", "4",
        ],
        worker_env={
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        },
        with_rendezvous=True,
        wait_complete=True,
    )
    legs["cpu_zero1"]["note"] = (
        "2 CPU collective workers, --zero1 --fused_steps 4, "
        "4-device process-local meshes: preemption re-forms the "
        "world with live sharded optimizer state; job runs to "
        "completion with exact record accounting"
    )
    # Master-kill leg: the one component that used to be the SPOF.
    # SIGKILL the MASTER mid-run, restart it from the job-state
    # journal on the same port, orphaned workers ride the outage and
    # reconnect without a process restart (docs/master_recovery.md).
    legs["cpu_master_kill"] = run_master_kill_drill()
    legs["cpu_master_kill"]["note"] = (
        "master SIGKILLed mid-run and restarted from --journal_dir; "
        "2 orphaned CPU workers reconnect via the outage-riding RPC "
        "retry policy; exact task accounting asserted from the "
        "journal (wait_complete-equivalent gate)"
    )
    # PS-shard-kill leg: the worker->PS direction (docs/ps_recovery.md).
    # SIGKILL one PS shard of a pipelined 2-shard PS-mode job; PSManager
    # relaunches it with a bumped restart generation + restore from the
    # committed cross-shard checkpoint; both workers ride the outage on
    # the same port, fence/reconcile, and the job completes with exact
    # accounting — with deterministic worker->PS faults injected on top.
    legs["cpu_ps_kill"] = run_ps_kill_drill()
    legs["cpu_ps_kill"]["note"] = (
        "PS shard 0 SIGKILLed mid-run (2 shards, 2 CPU workers, "
        "--async_push_window 2): relaunch+restore at a committed "
        "checkpoint label, generation fencing rejects dead-incarnation "
        "pushes, zero worker relaunches, exact task accounting"
    )
    # Multi-tenant leg (docs/scheduler.md): 2 jobs over one shared
    # 4-worker pool; the resize controller reclaims the finished job's
    # workers one journaled+traced decision at a time; the master is
    # SIGKILLed MID-RESIZE and restarted from the sched journal — both
    # jobs complete with exact per-job accounting, zero worker process
    # restarts, and the post-restart resize decision stitches to the
    # drained worker's re-register + in-place rebuild in one trace.
    legs["cpu_multitenant"] = run_multitenant_drill()
    legs["cpu_multitenant"]["note"] = (
        "2 jobs / shared 4-worker pool: controller-driven resize, "
        "master SIGKILLed mid-resize and restarted from the scheduler "
        "journal; per-job all_records_accounted, zero worker process "
        "restarts, decision->re-register trace connectivity"
    )

    from elasticdl_tpu.master.worker_manager import count_host_tpu_chips
    from elasticdl_tpu.utils.device import DEFAULT_CACHE_DIR

    # undo this module's CPU pin for the TPU legs' worker processes only
    tpu_env = {"JAX_PLATFORMS": "tpu"}
    cache_given = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    if not count_host_tpu_chips():
        legs["tpu"] = {"skipped": "no TPU chip on this host"}
    else:
        if not cache_given:
            shutil.rmtree(DEFAULT_CACHE_DIR, ignore_errors=True)
        for leg, note in (
            ("tpu_cold", "1 TPU worker, empty compile cache: full "
                         "re-init + re-compile on recovery"),
            ("tpu_warm", "1 TPU worker, warm persistent compile "
                         "cache: the production recovery path"),
        ):
            if remaining() < 180:
                legs[leg] = {"error": "skipped, %ds left"
                             % int(remaining())}
                continue
            code = (
                "import json, bench_elastic as b; "
                "print('LEG ' + json.dumps(b.run_drill("
                "num_workers=1, worker_env=%r, deadline_secs=300)))"
                % (tpu_env,)
            )
            try:
                proc = subprocess.run(
                    [sys.executable, "-c", code],
                    capture_output=True, text=True,
                    timeout=max(60, int(remaining())),
                    cwd=os.path.dirname(os.path.abspath(__file__)),
                )
                row = next(
                    (json.loads(ln[4:]) for ln in
                     proc.stdout.splitlines() if ln.startswith("LEG ")),
                    None,
                )
                legs[leg] = row or {
                    "error": "no LEG line (exit %d): %s"
                    % (proc.returncode, (proc.stderr or "")[-200:])}
            except subprocess.TimeoutExpired:
                legs[leg] = {"error": "timed out"}
            if isinstance(legs[leg], dict) and "recovery_secs" in (
                legs[leg]
            ):
                legs[leg]["note"] = note
                if leg == "tpu_cold" and cache_given:
                    legs[leg]["note"] += (
                        "; cache placed from outside, NOT emptied")

    warm = legs.get("tpu_warm", {}).get("recovery_secs")
    value = warm if warm is not None else legs["cpu"]["recovery_secs"]
    print(json.dumps({
        "metric": "elastic_recovery_time",
        "value": value,
        "unit": "seconds",
        "vs_baseline": None,
        "detail": dict(
            detail,
            headline_leg="tpu_warm" if warm is not None else "cpu",
            env={k: v for k, v in sorted(os.environ.items())
                 if k.startswith(("ELASTICDL_", "JAX_", "XLA_"))},
            bench_wall_secs=round(time.monotonic() - t0, 1),
        ),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
