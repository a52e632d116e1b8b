"""Elastic worker lifecycle management.

The TPU-native analog of the reference's pod manager
(elasticdl/python/master/pod_manager.py:207-674): launch workers, watch
their lifecycle events, relaunch failures/preemptions with *fresh* worker
ids, and notify observers (task re-queue, rendezvous refresh).  Backends
plug in under one interface:

 - ProcessWorkerBackend: workers are local subprocesses (tests and
   single-host multi-process jobs).  Preemption drills kill processes.
 - TPU-VM/k8s backends slot in here later with the same event surface.
"""

import glob
import os
import signal
import subprocess
import sys
import threading
import time

from elasticdl_tpu.master import worker_state as ws
from elasticdl_tpu.utils.logging import get_logger
from elasticdl_tpu.utils.timing import SETUP

logger = get_logger(__name__)


class WorkerHandle:
    def __init__(self, worker_id, backend_ref, slot=None):
        self.worker_id = worker_id
        self.backend_ref = backend_ref   # backend-specific (process, pod name)
        # The stable "slot" a worker occupies across relaunches: worker 0
        # dies, worker 4 replaces it, but both fill slot 0 — services and
        # priority classes follow the slot, not the ever-increasing id.
        self.slot = worker_id if slot is None else slot
        self.status = ws.INIT
        self.launched_at = time.time()
        self.relaunch_count = 0
        self.relaunch_pending = False


# libtpu's TPU_CHIPS_PER_PROCESS_BOUNDS for a process that owns this many
# consecutive chips of one host.  Two chips are "2,1,1": chips 0,1 and
# 2,3 of a v5e 2x2 host are neighbours along x, and libtpu 0.0.34 exits 1
# without a word on "1,2,1" (my chip run, PR 21).
_CHIP_BOUNDS = {1: "1,1,1", 2: "2,1,1", 4: "2,2,1", 8: "2,4,1"}


def count_host_tpu_chips():
    """TPU chips this host lets a process open: the device nodes libtpu
    itself enumerates, ``/dev/accel<N>`` (through v4) or ``/dev/vfio/<N>``
    (v5e and later).  Listing them opens nothing, so the master never
    holds a chip.  (PCI sysfs is not the answer: a one-chip slot of a
    four-chip host lists four functions and one vfio group — my chip
    run, PR 21.)  0 on a host with none."""
    return len(glob.glob("/dev/accel[0-9]*")) or len(
        glob.glob("/dev/vfio/[0-9]*"))


def chip_env_for_slot(slot, num_workers, num_chips):
    """The libtpu environment that gives worker ``slot`` its own share
    of the host's chips, disjoint from every other slot's.

    A chip belongs to one process at a time, so N workers that all see
    every chip cannot start: the first takes them all.  One worker keeps
    libtpu's default (every chip, no variables); N > 1 workers get
    ``num_chips / N`` consecutive chips each.  A request the host cannot
    satisfy raises ValueError — at start-up, not from inside the
    relaunch loop, where elasticity would turn it into a slow pass."""
    if num_workers <= 1:
        return {}
    per_worker, left_over = divmod(num_chips, num_workers)
    if per_worker == 0 or left_over or per_worker not in _CHIP_BOUNDS:
        raise ValueError(
            "cannot give %d workers disjoint chips on a host with %d TPU "
            "chip(s): the chip count must be a multiple of --num_workers "
            "(each worker process owns its chips exclusively)"
            % (num_workers, num_chips)
        )
    first = slot * per_worker
    return {
        "TPU_VISIBLE_CHIPS": ",".join(
            str(chip) for chip in range(first, first + per_worker)
        ),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": _CHIP_BOUNDS[per_worker],
        # Each worker is its own one-process slice; workers of a
        # collective job meet through the master's rendezvous, not
        # through libtpu's slice builder.
        "TPU_PROCESS_BOUNDS": "1,1,1",
        # libtpu otherwise refuses a second load on one host.
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }


class ProcessWorkerBackend:
    """Workers as local subprocesses of `python -m elasticdl_tpu.worker.main`.

    Workers inherit the master's environment, ``JAX_PLATFORMS`` included:
    on a TPU host they train on the TPU, and CPU drills and tests pass
    ``JAX_PLATFORMS=cpu`` themselves.  Unless the job is held to the CPU
    that way, ``num_workers`` > 1 splits the host's chips between the
    worker slots (``chip_env_for_slot``)."""

    def __init__(self, worker_args=None, env=None, num_workers=1):
        self._worker_args = worker_args or []
        self._env = env or {}
        self._num_workers = num_workers
        self._num_chips = 0
        platforms = {**os.environ, **self._env}.get("JAX_PLATFORMS", "")
        if num_workers > 1 and platforms.strip() != "cpu":
            self._num_chips = count_host_tpu_chips()
        if self._num_chips:
            # Refuse an unsatisfiable request now, before any launch.
            chip_env_for_slot(0, num_workers, self._num_chips)

    def launch(self, worker_id, master_addr, slot=None, extra_env=None):
        env = dict(os.environ)
        env.update(self._env)
        if self._num_chips:
            slot = worker_id if slot is None else slot
            env.update(chip_env_for_slot(
                slot % self._num_workers, self._num_workers,
                self._num_chips,
            ))
        env.update(extra_env or {})
        env["MASTER_ADDR"] = master_addr
        env["WORKER_ID"] = str(worker_id)
        proc = subprocess.Popen(
            [sys.executable, "-m", "elasticdl_tpu.worker.main"]
            + list(self._worker_args),
            env=env,
        )
        return proc

    def wait(self, ref):
        return ref.wait()

    def kill(self, ref, force=False):
        try:
            ref.send_signal(signal.SIGKILL if force else signal.SIGTERM)
        except ProcessLookupError:
            pass

    def is_alive(self, ref):
        return ref.poll() is None


class WorkerManager:
    def __init__(
        self,
        backend,
        num_workers,
        max_relaunch_count=3,
        relaunch_on_failure=True,
        cluster_env_fn=None,
    ):
        self._backend = backend
        self._num_workers = num_workers
        self._max_relaunch = max_relaunch_count
        self._relaunch_on_failure = relaunch_on_failure
        # Optional foreign-runtime cluster-spec hook: (worker_id, slot)
        # -> {env} injected into every (re)launch, e.g. a TF_CONFIG
        # built by cluster_spec_env.make_tf_config_fn (reference
        # pod_manager.py:405-422).
        self._cluster_env_fn = cluster_env_fn
        self._master_addr = None
        self._lock = threading.Lock()
        self._workers = {}          # worker_id -> WorkerHandle
        self._next_worker_id = 0
        self._exit_callbacks = []   # fn(worker_id, should_relaunch)
        self._start_callbacks = []  # fn(worker_id)
        self._watchers = []
        self._stopped = threading.Event()
        self._preempted = set()     # worker ids killed by preemption drill

    # -- wiring -------------------------------------------------------------

    def set_master_addr(self, addr):
        with self._lock:
            self._master_addr = addr

    def add_exit_callback(self, fn):
        self._exit_callbacks.append(fn)

    def add_start_callback(self, fn):
        self._start_callbacks.append(fn)

    # -- lifecycle ----------------------------------------------------------

    def start(self):
        SETUP.mark("launch")
        for _ in range(self._num_workers):
            self._launch_worker()

    def _launch_worker(self, slot=None):
        with self._lock:
            worker_id = self._next_worker_id
            self._next_worker_id += 1
            kwargs = {}
            if self._cluster_env_fn is not None:
                kwargs["extra_env"] = self._cluster_env_fn(
                    worker_id, worker_id if slot is None else slot
                )
            ref = self._backend.launch(
                worker_id, self._master_addr, slot=slot, **kwargs
            )
            handle = WorkerHandle(worker_id, ref, slot=slot)
            handle.status = ws.PENDING
            self._workers[worker_id] = handle
        logger.info("launched worker %d", worker_id)
        # The master's set-up ends with its first worker on its way.
        SETUP.close()
        watcher = threading.Thread(
            target=self._watch_worker, args=(handle,),
            name="worker-watch-%d" % worker_id, daemon=True,
        )
        watcher.start()
        self._watchers.append(watcher)
        for fn in self._start_callbacks:
            fn(worker_id)
        return worker_id

    def _watch_worker(self, handle):
        code = self._backend.wait(handle.backend_ref)
        if self._stopped.is_set():
            return
        with self._lock:
            was_preempted = handle.worker_id in self._preempted
            self._preempted.discard(handle.worker_id)
        if code == 0:
            event = ws.EV_EXIT_0
            handle.status = ws.RUNNING  # exit implies it ran
        elif was_preempted or code in (-signal.SIGTERM, -signal.SIGKILL,
                                       143):
            # 143 = the worker's graceful-preemption exit (it caught
            # SIGTERM, checkpointed, and asked to be relaunched).
            # A raw SIGKILL is ambiguous for local processes: kernel OOM
            # kills and external preemption both yield -9.  We classify it
            # as preemption (the common case on preemptible TPU hosts);
            # the relaunch budget still bounds an OOM crash-loop.
            # Containerized backends report exit 137 and hit EV_OOM below.
            event = ws.EV_PREEMPTED
        elif code == 137:
            event = ws.EV_OOM
        else:
            event = ws.EV_EXIT_ERR
        flow = ws.get_flow(
            handle.status if handle.status != ws.PENDING else ws.RUNNING,
            event,
        )
        if flow is None:
            logger.warning(
                "worker %d: no flow for (%s, %s)",
                handle.worker_id, handle.status, event,
            )
            return
        handle.status = flow.to_status
        should_relaunch = (
            flow.should_relaunch
            and self._relaunch_on_failure
            and handle.relaunch_count < self._max_relaunch
        )
        handle.relaunch_pending = should_relaunch
        logger.info(
            "worker %d exited code=%s event=%s -> %s relaunch=%s",
            handle.worker_id, code, event, handle.status, should_relaunch,
        )
        for fn in self._exit_callbacks:
            fn(handle.worker_id, should_relaunch)
        if should_relaunch and not self._stopped.is_set():
            new_id = self._launch_worker(slot=handle.slot)
            with self._lock:
                self._workers[new_id].relaunch_count = (
                    handle.relaunch_count + 1
                )
        handle.relaunch_pending = False

    # -- control ------------------------------------------------------------

    def preempt_worker(self, worker_id, force=True):
        """Kill a worker as if the platform preempted it (drill hook)."""
        with self._lock:
            handle = self._workers.get(worker_id)
            if handle is None:
                return False
            self._preempted.add(worker_id)
            # Mark before killing so all_workers_done() can't observe a
            # dead-but-not-yet-relaunched window and abort the job.
            handle.relaunch_pending = True
        self._backend.kill(handle.backend_ref, force=force)
        return True

    def remove_worker(self, worker_id):
        """Master-initiated removal (task-timeout watchdog)."""
        with self._lock:
            handle = self._workers.get(worker_id)
            if handle is None:
                return False
            self._preempted.add(worker_id)  # treat as relaunchable
            handle.relaunch_pending = True
        self._backend.kill(handle.backend_ref, force=True)
        return True

    def launched_at(self, worker_id):
        """When this manager launched ``worker_id``; None for a worker it
        did not launch."""
        with self._lock:
            handle = self._workers.get(worker_id)
        return None if handle is None else handle.launched_at

    def live_worker_ids(self):
        with self._lock:
            return [
                wid for wid, h in self._workers.items()
                if self._backend.is_alive(h.backend_ref)
            ]

    def all_workers_exited(self):
        with self._lock:
            return all(
                not self._backend.is_alive(h.backend_ref)
                for h in self._workers.values()
            )

    def all_workers_done(self):
        """True when every worker is dead and no relaunch is pending —
        the job cannot make further progress without intervention."""
        with self._lock:
            return all(
                not self._backend.is_alive(h.backend_ref)
                and not h.relaunch_pending
                for h in self._workers.values()
            )

    def stop(self):
        self._stopped.set()
        with self._lock:
            handles = list(self._workers.values())
        for handle in handles:
            if self._backend.is_alive(handle.backend_ref):
                self._backend.kill(handle.backend_ref, force=True)
