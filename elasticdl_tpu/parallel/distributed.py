"""Multi-host collective bootstrap — elastic, master-coordinated.

The reference's AllReduce path rebuilds a Horovod/Gloo ring from the
master-hosted rendezvous (SURVEY §2.12); a worker failure surfaces
IN-BAND as a HorovodInternalError and the survivors re-rendezvous
(elasticdl/python/worker/allreduce_trainer.py:77-91).  The TPU-native
redesign here keeps the same control relationship but swaps every
mechanism:

 - The MASTER hosts the JAX coordination service
   (``MasterCoordinationService``), one fresh service per rendezvous
   epoch on a fresh port.  Workers are *clients only* — a dying worker
   can never take the coordination plane down with it (in stock
   ``jax.distributed`` the service lives in process 0, so losing that
   worker strands everyone else).
 - Workers connect with the coordination client in ``recoverable``
   mode: a peer's death surfaces as an ordinary exception from the
   failed collective (the in-band signal) instead of the default
   behavior of TERMINATING the surviving process from the error-poll
   thread.
 - Re-forming the world is a first-class operation:
   ``initialize_from_rendezvous`` disconnects, clears XLA backends (a
   new process count changes the global device world, so compiled
   programs and device arrays from the old epoch are discarded), and
   reconnects against the new epoch's service.  Callers must snapshot
   state to host first (CollectiveTrainer.snapshot_to_host).

Address convention: a master-hosted coordination service is advertised
as ``jaxsvc://host:port`` so workers know to client-only connect; a
bare ``host:port`` keeps the legacy ``jax.distributed.initialize``
behavior (worker 0 hosts the service) for single-epoch jobs.

Single-process worlds skip distributed init entirely, so the same code
path runs in tests and single-host jobs.
"""

import os
import socket
import threading

import jax

from elasticdl_tpu.parallel.mesh import build_mesh
from elasticdl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

JAXSVC_PREFIX = "jaxsvc://"


def _heartbeat_secs():
    """Peer-death detection latency knob (service + client side)."""
    return int(os.environ.get("ELASTICDL_COLLECTIVE_HEARTBEAT", "10"))


# Mirrors api/controller.py DEFAULT_SECS_TO_CHECK_RENDEZVOUS (not
# imported: this module must stay importable without the api package).
_DEFAULT_CHECK_SECS = 20.0


def derive_reap_secs(check_steps=None, check_secs=None,
                     step_secs_bound=None, margin=None):
    """Old-epoch service lifetime derived from the workers' actual
    epoch-discovery cadence (ADVICE r5 medium).

    A survivor only notices a new epoch when its controller polls the
    rendezvous — every ``check_steps`` steps (bounded by
    ``step_secs_bound`` seconds per step, env
    ``ELASTICDL_STEP_SECS_BOUND``) or every ``check_secs`` seconds —
    and must then detach from the OLD epoch's service with an explicit
    shutdown RPC that is only safe while that service is still up
    (MasterCoordinationService docstring).  A fixed reap delay shorter
    than the discovery latency therefore terminates survivors
    uncatchably; one derived from the cadence plus a heartbeat-sized
    margin cannot."""
    if step_secs_bound is None:
        step_secs_bound = float(os.environ.get(
            "ELASTICDL_STEP_SECS_BOUND", "5.0"))
    if margin is None:
        margin = 2.0 * _heartbeat_secs()
    cadence = 0.0
    if check_steps:
        cadence = max(cadence, check_steps * step_secs_bound)
    if check_secs:
        cadence = max(cadence, float(check_secs))
    if not cadence:
        cadence = _DEFAULT_CHECK_SECS
    return cadence + margin


class MasterCoordinationService:
    """Master-side JAX coordination service, one instance per epoch.

    ``start_epoch(world_size)`` starts a fresh service on a free port
    and returns its advertised ``jaxsvc://host:port`` address.  The
    PREVIOUS epoch's service is reaped on a timer after ``reap_secs``:
    survivors of a membership change must detach from it with an
    explicit client shutdown, and that RPC is only safe while the old
    service is still up — the client's heartbeat/shutdown failure
    paths TERMINATE the worker process from C++ (jaxlib 0.9.0 accepts
    a Python ``missed_heartbeat_callback`` but throws std::bad_cast
    when it fires — re-checked in PR 21 by killing the service under a
    connected client — so the fatal path cannot be intercepted).
    ``reap_secs`` therefore must exceed the workers' worst-case
    epoch-discovery time; ``reap_secs=None`` derives it from the check
    cadence via ``derive_reap_secs`` (pass the job's actual
    ``check_steps`` there, as master/main.py does)."""

    def __init__(self, host="localhost", shutdown_timeout=3,
                 reap_secs=None):
        self._host = host
        self._shutdown_timeout = shutdown_timeout
        self._reap_secs = (derive_reap_secs() if reap_secs is None
                           else reap_secs)
        self._service = None
        self._reapers = []

    def start_epoch(self, world_size):
        from jax._src.lib import _jax

        previous = self._service
        if previous is not None:
            reaper = threading.Timer(
                self._reap_secs, self._stop_service, args=(previous,)
            )
            reaper.daemon = True
            reaper.start()
            # Prune fired timers — a long-lived elastic master churns
            # through many epochs and must not accumulate dead Timers
            # (each pins its old-service arg until GC).
            self._reapers = [r for r in self._reapers if r.is_alive()]
            self._reapers.append(reaper)
            self._service = None
        if world_size <= 0:
            return ""
        service = None
        last_err = None
        for _attempt in range(3):
            # The probe socket is closed before the service binds, so
            # another process can grab the port in between (and the
            # service binds [::] while the probe used the default
            # family) — retry with a fresh port on a bind failure.
            try:
                probe = socket.socket(socket.AF_INET6)
            except OSError:
                probe = socket.socket()
            with probe:
                probe.bind(("", 0))
                port = probe.getsockname()[1]
            try:
                service = _jax.get_distributed_runtime_service(
                    "[::]:%d" % port, world_size,
                    heartbeat_timeout=_heartbeat_secs(),
                    shutdown_timeout=self._shutdown_timeout,
                )
                break
            except Exception as e:  # noqa: BLE001 — port stolen
                last_err = e
        if service is None:
            raise RuntimeError(
                "could not bind a coordination service port"
            ) from last_err
        self._service = service
        addr = "%s%s:%d" % (JAXSVC_PREFIX, self._host, port)
        logger.info("coordination service for world=%d at %s",
                    world_size, addr)
        return addr

    @staticmethod
    def _stop_service(service):
        try:
            service.shutdown()
        except Exception as e:  # noqa: BLE001 — old world died messily
            logger.info("old coordination service shutdown: %s", e)

    def stop(self):
        for reaper in self._reapers:
            reaper.cancel()
        self._reapers = []
        if self._service is not None:
            self._stop_service(self._service)
            self._service = None


def _client_connect(rank, world_size, host_port):
    """Client-only attach to a master-hosted coordination service."""
    from jax._src import distributed as jdist
    from jax._src.lib import _jax

    state = jdist.global_state
    state.coordinator_address = host_port
    state.process_id = rank
    state.num_processes = world_size
    state.client = _jax.get_distributed_runtime_client(
        host_port, rank,
        init_timeout=int(os.environ.get(
            "ELASTICDL_COLLECTIVE_INIT_TIMEOUT", "60")),
        heartbeat_timeout=_heartbeat_secs(),
        shutdown_timeout=3,
        use_compression=True,
        # A peer dying must surface as a catchable collective error in
        # the survivors, not terminate them from the error-poll thread.
        recoverable=True,
        # The DESTRUCTOR must never send ShutdownTask: GC can run
        # after the master reaped the old epoch's service, and a
        # shutdown RPC against a dead service LOG(FATAL)s in the
        # client.  The explicit _client_disconnect below DOES send it
        # deliberately — at a controlled point inside the master's
        # reap window, while the old service is guaranteed alive
        # (reap_secs is derived from the epoch-discovery cadence,
        # derive_reap_secs).
        shutdown_on_destruction=False,
    )
    state.client.connect()
    state.initialize_preemption_sync_manager()


def _client_disconnect():
    """Detach from the old epoch's (still-running) service.

    The explicit ``client.shutdown()`` is what stops the client's
    heartbeat thread — merely dropping the Python reference does not
    (the backend caches and the thread itself keep the C++ object
    alive), and a live heartbeat against a dead service terminates the
    process.  This is why the master REAPS old services on a delay
    (MasterCoordinationService) instead of at commit: the shutdown RPC
    must land on a live service."""
    from jax._src import distributed as jdist

    state = jdist.global_state
    if state.preemption_sync_manager is not None:
        try:
            state.preemption_sync_manager.shutdown()
        except Exception:  # noqa: BLE001
            pass
        state.preemption_sync_manager = None
    if state.client is not None:
        try:
            state.client.shutdown()
        except Exception as e:  # noqa: BLE001 — epoch died messily
            logger.info("coordination client shutdown: %s", e)
        state.client = None


def _discard_old_world():
    """Drop every artifact of the previous epoch's global world: the
    jit/pjit caches and XLA backends hold compiled programs, device
    arrays, AND references to the old distributed client — all invalid
    (or process-terminating, via the client's heartbeat thread) once
    the epoch is gone."""
    import gc

    import jax.extend.backend

    jax.clear_caches()
    jax.extend.backend.clear_backends()
    gc.collect()


def _reset_to_single_process():
    """Shrink to a clean single-process world (the last survivor, or a
    world-1 epoch): disconnect, discard the old world, and restore the
    default local identity so sharding sees process 0 of 1."""
    from jax._src import distributed as jdist

    state = jdist.global_state
    if state.client is None:
        return
    _client_disconnect()
    state.coordinator_address = None
    state.process_id = 0
    state.num_processes = 1
    _discard_old_world()
    logger.info("collective world left: single-process mode restored")


def reset_single_process():
    """Public alias: leave any collective world and restore clean
    single-process mode (used by idle workers stepping out of the
    world while they wait for tasks)."""
    _reset_to_single_process()


def initialize_from_rendezvous(rank, world_size, coordinator_addr):
    """(Re-)initialize the collective runtime for a membership epoch.

    Master-hosted addresses (``jaxsvc://``) use the elastic client-only
    path and support REPEATED calls with different worlds: each call
    disconnects, clears XLA backends (device arrays and compiled
    programs of the old world are invalidated — snapshot to host
    first), and reconnects.  Bare addresses keep the legacy
    ``jax.distributed.initialize`` semantics.
    """
    if world_size <= 1 or not coordinator_addr:
        _reset_to_single_process()
        return False
    if coordinator_addr.startswith(JAXSVC_PREFIX):
        host_port = coordinator_addr[len(JAXSVC_PREFIX):]
        _client_disconnect()
        _discard_old_world()
        _client_connect(rank, world_size, host_port)
        # The backend re-created under the new client must span the
        # world the master committed.  A backend that takes its topology
        # from elsewhere (libtpu, from its own slice) can come back
        # seeing only this process; training on would be N disconnected
        # jobs sharing one task queue and calling themselves a world.
        if jax.process_count() != world_size:
            raise RuntimeError(
                "collective world of %d workers was committed, but this "
                "process's %s backend spans %d process(es) and %d "
                "device(s): it did not join the world"
                % (world_size, jax.default_backend(),
                   jax.process_count(), jax.device_count())
            )
        logger.info(
            "collective world joined (client-only): rank %d / %d via %s "
            "(%d global devices)",
            rank, world_size, host_port, jax.device_count(),
        )
        return True
    try:
        jax.distributed.shutdown()
    except Exception:  # noqa: BLE001 — not initialized yet
        pass
    jax.distributed.initialize(
        coordinator_address=coordinator_addr,
        num_processes=world_size,
        process_id=rank,
    )
    logger.info(
        "jax.distributed initialized: rank %d / %d via %s",
        rank, world_size, coordinator_addr,
    )
    return True


def elastic_mesh_builder(pp=1, ep=1, tp=1, sp=1):
    """Returns a mesh_builder(rank, world_size, coordinator_addr) for
    ElasticCollectiveController: re-init the collective runtime for the
    epoch, then build the global dp x pp x ep x tp x sp mesh over all
    visible devices (dp absorbs whatever the fixed axes leave)."""

    def build(rank, world_size, coordinator_addr):
        initialize_from_rendezvous(rank, world_size, coordinator_addr)
        return build_mesh(pp=pp, ep=ep, tp=tp, sp=sp)

    return build
