"""Collective data-parallel trainer — the TPU-native AllReduce path.

Replaces the reference's Horovod/Gloo AllReduce trainer
(elasticdl/python/worker/allreduce_trainer.py:37-146) with a jitted train
step over a ``jax.sharding.Mesh``: the batch is sharded on the ``data`` axis,
parameters are replicated, and XLA inserts the gradient all-reduce over ICI.
Fixed-global-batch elasticity (reference
elasticai_api/pytorch/optimizer.py:136-169) becomes a ``lax.scan`` gradient
accumulation over microbatches, re-jitted when the accumulation count
changes with the world size.  Rebuilding for a new mesh = re-sharding params
and re-jitting — the compile cache keyed by (mesh shape, accum steps).

``--zero1`` swaps the weight update for ZeRO-1 cross-replica sharding
(worker/zero.py, docs/training_pipeline.md): optimizer state lives as
flat padded 1-D shards over the data axis (per-device optimizer memory
~1/N), the update runs shard-locally between an explicit
reduce-scatter/all-gather pair, and a world re-form re-partitions the
live shards device-to-device with Adam moments preserved bit-exactly.
"""


import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elasticdl_tpu.ops.batch_shard import DeviceRoom, batch_axis
from elasticdl_tpu.utils import tracing
from elasticdl_tpu.utils.logging import get_logger
from elasticdl_tpu.utils.pytree import flatten_with_names, to_numpy
from elasticdl_tpu.utils.timing import SETUP, Timing
from elasticdl_tpu.worker.fused_driver import PreparedBatch, StagedWindow
from elasticdl_tpu.worker.trainer import Trainer
from elasticdl_tpu.worker.zero import ZeroPartitioner

logger = get_logger(__name__)

# prepare_batch plan cache cap: keys are (record count, tree structure)
# — one full-batch entry plus a handful of tail-batch sizes per task
# shape, so the cache only grows past this if batch shapes churn.
_PAD_PLAN_CACHE_MAX = 32


class _PadPlan:
    """Host-side batch-prep plan, derived ONCE per (record count, tree
    structure) instead of re-deriving ``np.asarray``/shape math inside
    every ``train_minibatch`` (the per-step hot loop's host tax).

    Holds per-leaf pad widths (None = no pad), per-leaf accum reshape
    targets (None = no reshape), and the loss-mask weights array.  The
    weights array is shared read-only across steps — every consumer
    (device_put, np.stack) copies, never mutates.
    """

    __slots__ = ("pad_widths", "reshapes", "weights", "local")

    def __init__(self, leaves, n, local, accum, micro):
        if n > local:
            raise ValueError(
                "minibatch has %d records > the %d rows this process "
                "feeds a step" % (n, local)
            )
        pad = local - n
        self.local = local
        self.pad_widths = [
            [(0, pad)] + [(0, 0)] * (np.asarray(leaf).ndim - 1)
            if pad else None
            for leaf in leaves
        ]
        if accum > 1:
            self.reshapes = [
                (accum, micro) + tuple(np.shape(leaf)[1:])
                for leaf in leaves
            ]
        else:
            self.reshapes = [None] * len(leaves)
        weights = np.zeros((local,), dtype=np.float32)
        weights[:n] = 1.0
        if accum > 1:
            weights = weights.reshape(accum, micro)
        self.weights = weights


def _device_bytes(tree):
    """Bytes of ``tree`` on one device: a leaf's shard where it is
    sharded, all of it where it is replicated or still on the host."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = np.shape(leaf)
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None:
            shape = sharding.shard_shape(shape)
        total += int(np.prod(shape)) * np.dtype(leaf.dtype).itemsize
    return total


def _bytes_limit(devices):
    """The least ``bytes_limit`` the backend states for ``devices``, or
    None where it states none (the CPU keeps no memory statistics)."""
    limits = [(d.memory_stats() or {}).get("bytes_limit") for d in devices]
    return min(limits) if all(limits) else None


def _out_of_memory(error, args):
    """Did a step's compile end in the backend's out-of-memory error?
    (Its arguments are then still alive: a step that failed while
    running has consumed the donated ones, and cannot be tried again.)"""
    return "RESOURCE_EXHAUSTED" in str(error) and not any(
        leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(args)
        if isinstance(leaf, jax.Array))


def _masked_mean(per_example, weights):
    per_example = per_example.reshape(per_example.shape[0], -1).mean(axis=-1)
    return jnp.sum(per_example * weights) / jnp.maximum(jnp.sum(weights), 1.0)


def _pad_batch(tree, batch_size):
    """Pad every leaf to batch_size rows; returns (padded, weights)."""
    leaves = jax.tree_util.tree_leaves(tree)
    n = leaves[0].shape[0]
    if n > batch_size:
        raise ValueError(
            "minibatch has %d records > the %d rows this process "
            "feeds a step" % (n, batch_size)
        )
    weights = np.zeros((batch_size,), dtype=np.float32)
    weights[:n] = 1.0
    if n == batch_size:
        return tree, weights

    def pad(a):
        a = np.asarray(a)
        pad_width = [(0, batch_size - n)] + [(0, 0)] * (a.ndim - 1)
        return np.pad(a, pad_width)

    return jax.tree_util.tree_map(pad, tree), weights


class CollectiveTrainer(Trainer):
    """``batch_size`` is the number of rows THIS PROCESS feeds one
    (micro-)step — the figure the ``Worker`` streams minibatches of
    (``--batch_size``), whatever the mesh.  The rows are sharded over
    the process's own devices, so each device computes on
    ``ceil(batch_size / local devices)`` of them; train, evaluate and
    predict all pad a minibatch up to ``_process_rows()``."""

    def __init__(
        self,
        spec,
        batch_size,
        mesh=None,
        data_axis="data",
        accum_steps=1,
        rng_seed=0,
        master_client=None,
        report_version_steps=0,
        checkpoint_saver=None,
        checkpoint_steps=0,
        use_bf16_compute=False,
        zero1=False,
        exporter=None,
        export_steps=0,
    ):
        self._spec = spec
        self._batch_size = batch_size
        self._data_axis = data_axis
        self._accum_steps = accum_steps
        self._mc = master_client
        self._report_version_steps = report_version_steps
        self._checkpoint_saver = checkpoint_saver
        self._checkpoint_steps = checkpoint_steps
        # Continuous servable export (the online-learning loop's trainer
        # half, docs/serving.md): every export_steps optimizer steps a
        # complete versioned servable lands at the exporter's base for
        # the aggregation tier to ingest.  Worker-0-only, same guard as
        # checkpointing (worker/main zeroes export_steps elsewhere).
        self._exporter = exporter
        self._export_steps = export_steps if exporter is not None else 0
        self._use_bf16_compute = use_bf16_compute
        # ZeRO-1: shard optimizer state over the data axis instead of
        # replicating it — Adam moments cost 2x params, so an 8-way dp
        # mesh drops per-device optimizer memory ~8x (no reference
        # counterpart — deliberate beyond-reference design, SURVEY
        # §2.12).  Every optimizer leaf is flattened to 1-D, padded to
        # a multiple of the shard count, and sharded on dim 0
        # (worker/zero.py), so coverage is total regardless of leaf
        # shape; the train step updates only the local shard and
        # all-gathers fresh params (docs/training_pipeline.md).
        self._zero1 = zero1
        self._zero = None          # active partitioner (mesh worlds)
        self._opt_is_flat = False  # opt-state representation marker
        self.timing = Timing(logger=logger)
        self._version = 0
        self._ckpt_executor = None
        self._ckpt_future = None
        self._export_future = None
        self._example_features = None
        # The last per-step program's step statistics (the spec's
        # ``step_stats_fn``), lazy like the loss it left the step with:
        # fetched after that loss it costs no sync.  () without one.
        self.last_step_stats = ()
        # Set when a step's compile ran out of memory with a
        # ``DeviceRoom`` stated: every later build states none left.
        self._room_refused = False

        SETUP.mark("param_init")
        params = spec.init_fn(jax.random.PRNGKey(rng_seed))
        self._opt_state = spec.optimizer.init(params)
        self._params = params
        self._mesh = None
        self.rebuild(mesh)
        if SETUP.open:
            # The process's first step is set-up's: this one call sees
            # its marks, every later one is the class's ``_run_step``.
            self._run_step = self._first_run_step

    # -- mesh / jit management ---------------------------------------------

    def snapshot_to_host(self):
        """Pull params + optimizer state to host numpy, in place.

        Called by the elastic controller BEFORE re-forming a
        master-coordinated world: the re-init clears XLA backends, which
        invalidates every device array of the old epoch.  Replicated
        leaves always survive (each process holds a full copy).  A
        ZeRO-1 state is gathered through its unpadding view as a jitted
        on-device all-gather FIRST (``ZeroPartitioner.gather_to_host``),
        so even in a multi-controller world every process holds the full
        original-shape value before the host transfer — ``to_numpy`` on
        a raw sharded leaf would hit non-addressable shards.  Only when
        that gather itself fails (a peer died mid-epoch and took its
        shards with it) is optimizer state re-initialized from the
        (still complete) params — the same information loss the
        reference accepts when a Horovod restart reloads the last
        checkpoint without optimizer slots."""
        try:
            self._params = to_numpy(self._params)
        except Exception as e:
            raise RuntimeError(
                "parameters are not locally addressable; cannot "
                "survive a world change without a checkpoint restore"
            ) from e
        try:
            if self._opt_is_flat and self._zero is not None:
                self._opt_state = self._zero.gather_to_host(
                    self._opt_state
                )
            else:
                self._opt_state = to_numpy(self._opt_state)
        except Exception:  # noqa: BLE001 — lost ZeRO-1 shards
            logger.warning(
                "optimizer state not locally addressable (ZeRO-1 "
                "shards lost with a dead peer); re-initializing "
                "optimizer moments from params"
            )
            self._opt_state = self._spec.optimizer.init(self._params)
        self._opt_is_flat = False

    def rebuild(self, mesh):
        """(Re)shard state and (re)compile steps for a (new) mesh.

        This is the elastic-resize path: called at init and whenever the
        rendezvous epoch changes the device world.  State placement is
        device-to-device whenever the arrays are live on a surviving
        backend (``jax.device_put`` re-shards committed arrays across
        mesh shapes without a host round-trip; ZeRO-1 shards re-pad for
        the new shard count bit-exactly via
        ``ZeroPartitioner.repartition``); the host bounce survives only
        as the fallback for the multi-controller path, where the world
        re-init already cleared the backend and the controller
        snapshotted state to host numpy first.
        """
        # The elastic re-form as one span in the worker's trace
        # (docs/observability.md): epoch re-forms, device counts, and
        # reshard cost line up against the rest of the incident.
        with tracing.span(
            "worker.world_reform",
            devices=0 if mesh is None else mesh.devices.size,
            zero1=bool(self._zero1),
        ):
            self._rebuild_traced(mesh)

    def _rebuild_traced(self, mesh):
        old_zero = self._zero if self._opt_is_flat else None
        self._mesh = mesh
        # Mesh/accum-dependent caches: pad plans bake in the local batch
        # geometry, fused windows bake in shardings — both die with the
        # old world.
        self._pad_plans = {}
        self._fused_window_cache = {}
        if mesh is not None:
            replicated = NamedSharding(mesh, P())
            self._batch_sharding = NamedSharding(mesh, P(self._data_axis))
            self._replicated = replicated
            self._zero = (
                ZeroPartitioner(
                    self._spec.optimizer, self._params, mesh,
                    self._data_axis,
                )
                if self._zero1 else None
            )
            with self.timing.timeit("state_reshard"):
                self._params = self._reshard_to(
                    self._params, replicated
                )
                self._opt_state = self._place_opt_state(old_zero)
            self._opt_is_flat = self._zero is not None
            if self._zero is not None:
                self._log_zero1_placement()
        else:
            if old_zero is not None:  # leaving the mesh world entirely
                self._opt_state = old_zero.gather_to_host(
                    self._opt_state
                )
            self._opt_is_flat = False
            self._zero = None
            self._batch_sharding = None
            self._replicated = None
        self._train_step = self._build_train_step()
        self._eval_step = self._build_eval_step()
        self._local_eval_step = None  # rebuilt lazily: the old one may
        # belong to a cleared backend (world change)

    def _reshard_to(self, tree, sharding):
        """Place a pytree under ``sharding``, device-to-device when the
        leaves are live device arrays (a committed array re-shards
        across meshes without leaving the device fabric), straight
        host->device when they are numpy.  Falls back to an explicit
        host bounce only when the direct put fails (arrays from a
        cleared backend that were never snapshotted)."""
        def put(leaf):
            if isinstance(leaf, jax.Array):
                # A leaf already under the target sharding is a
                # placement no-op — only book actual moves.
                if getattr(leaf, "sharding", None) != sharding:
                    self.timing.bump(
                        "reshard_device_bytes", leaf.nbytes
                    )
            else:
                self.timing.bump(
                    "reshard_host_bytes", np.asarray(leaf).nbytes
                )
            return jax.device_put(leaf, sharding)

        try:
            return jax.tree_util.tree_map(put, tree)
        except Exception:  # noqa: BLE001 — dead backend arrays
            logger.warning(
                "device-to-device reshard unavailable; host bounce"
            )
            self.timing.bump("reshard_host_fallbacks")
            return jax.device_put(to_numpy(tree), sharding)

    def _place_opt_state(self, old_zero):
        """Place the optimizer state for the current mesh/partitioner.

        Live flat shards from a previous world re-partition
        device-to-device (Adam moments preserved bit-exactly, see
        ZeroPartitioner.repartition); original-shape state (first
        build, post-snapshot, post-restore) is flattened host-side and
        placed sharded; with zero1 off the state is simply (re)placed
        replicated.  A dead-backend failure re-initializes moments from
        params — the snapshot_to_host contract."""
        state = self._opt_state
        if self._zero is None:
            return self._reshard_to(state, self._replicated)
        try:
            if old_zero is not None:
                return self._zero.repartition(
                    state, old_zero, timing=self.timing
                )
            return self._zero.place_state(to_numpy(state))
        except Exception:  # noqa: BLE001 — dead backend / lost shards
            logger.warning(
                "zero1: live shard repartition failed; attempting "
                "host bounce"
            )
            self.timing.bump("reshard_host_fallbacks")
            try:
                if old_zero is not None:
                    state = old_zero.gather_to_host(state)
                return self._zero.place_state(
                    jax.tree_util.tree_map(np.asarray, state)
                )
            except Exception:  # noqa: BLE001
                logger.warning(
                    "zero1: optimizer shards unrecoverable; "
                    "re-initializing moments from params"
                )
                return self._zero.place_state(
                    self._spec.optimizer.init(to_numpy(self._params))
                )

    def _opt_out_shardings(self):
        """Opt-state placement for jit in/out_shardings: the ZeRO-1
        per-leaf tree when sharding is on, plain replicated otherwise
        (the exact old path)."""
        if self._zero is not None:
            return self._zero.state_shardings(self._opt_state)
        return self._replicated

    def zero1_report(self):
        """Per-device optimizer-state byte accounting, both modes.

        Returns {mode, num_shards, per_device_bytes,
        replicated_equiv_bytes, reduction_factor, padding_bytes,
        scalar_leaves_replicated}; None without a mesh."""
        if self._mesh is None:
            return None
        if self._zero is None:
            total = sum(
                getattr(leaf, "nbytes", None)
                or np.asarray(leaf).nbytes
                for leaf in jax.tree_util.tree_leaves(self._opt_state)
            )
            return {
                "mode": "replicated",
                "num_shards": int(self._mesh.shape[self._data_axis]),
                "per_device_bytes": int(total),
                "replicated_equiv_bytes": int(total),
                "reduction_factor": 1.0,
                "padding_bytes": 0,
                "scalar_leaves_replicated": 0,
            }
        replicated, sharded, padding = self._zero.state_bytes(
            self._opt_state
        )
        return {
            "mode": "zero1",
            "num_shards": self._zero.num_shards,
            "per_device_bytes": int(sharded),
            "replicated_equiv_bytes": int(replicated),
            "reduction_factor": replicated / max(1, sharded),
            "padding_bytes": int(padding),
            "scalar_leaves_replicated": sum(
                1 for s in self._zero.state_specs if s.padded == 0
            ),
        }

    def _log_zero1_placement(self):
        report = self.zero1_report()
        logger.info(
            "zero1: optimizer state sharded %d ways — %.3f MiB/device "
            "(replicated would be %.3f MiB/device, %.1fx reduction; "
            "%d padding bytes, %d scalar leaves replicated)",
            report["num_shards"],
            report["per_device_bytes"] / 2**20,
            report["replicated_equiv_bytes"] / 2**20,
            report["reduction_factor"],
            report["padding_bytes"],
            report["scalar_leaves_replicated"],
        )

    @property
    def global_device_count(self):
        return self._mesh.size if self._mesh is not None else 1

    @property
    def process_count(self):
        """Number of processes the mesh spans (1 = single-controller)."""
        if self._mesh is None:
            return 1
        return len({d.process_index for d in self._mesh.devices.flat})

    def _process_rows(self):
        """Rows this process contributes to one (micro-)step:
        ``batch_size`` rounded up to a whole number of rows per local
        device."""
        local_devices = self.global_device_count // self.process_count
        return -(-self._batch_size // local_devices) * local_devices

    def _globalize(self, tree, sharding):
        """Assemble per-process local batches into global arrays.

        Multi-controller SPMD: every process holds ITS share of the
        global batch (its own task stream's records); the global array
        is the concatenation over processes along the data axis.  The
        single-process path hands numpy straight to jit (placement via
        in_shardings) — identical math, no assembly step."""
        if self.process_count == 1:
            return tree
        return jax.tree_util.tree_map(
            lambda a: jax.make_array_from_process_local_data(
                sharding, np.asarray(a)
            ),
            tree,
        )


    def set_accum_steps(self, accum_steps):
        if accum_steps != self._accum_steps:
            self._accum_steps = accum_steps
            self._pad_plans = {}
            self._fused_window_cache = {}
            self._train_step = self._build_train_step()

    def _loss_and_grads(self, params, features, labels, weights):
        apply_fn = self._spec.apply_fn
        loss_fn = self._spec.loss_fn
        stats_fn = getattr(self._spec, "step_stats_fn", None)

        def f(p):
            x = features
            if self._use_bf16_compute:
                # Cast params AND activations: flax promotes mixed
                # bf16-param/f32-input matmuls back to f32, which would
                # silently keep the MXU off the bf16 path.
                to_bf16 = lambda a: (
                    a.astype(jnp.bfloat16)
                    if jnp.issubdtype(a.dtype, jnp.floating) else a
                )
                p = jax.tree_util.tree_map(to_bf16, p)
                x = jax.tree_util.tree_map(to_bf16, x)
            # Pallas kernels inside the model run per shard of the data
            # axis instead of being replicated by the partitioner; the
            # model's loss states its sizes per shard too, and a model
            # that can trade memory for time is told what a device has.
            with batch_axis(self._mesh, self._data_axis, self._room):
                out = apply_fn(p, x, True)
                per_example = loss_fn(out, labels).astype(jnp.float32)
            # The spec's step statistics ride out as value_and_grad's
            # aux: an empty tuple (no such spec) is no output at all.
            return _masked_mean(per_example, weights), (
                stats_fn(out) if stats_fn else ())

        (loss, stats), grads = jax.value_and_grad(f, has_aux=True)(params)
        return loss, grads, stats

    def _zero1_apply(self, tx, params, opt_state, grads):
        """ZeRO-1 weight update: reduce-scatter(grads) -> shard-local
        optimizer apply -> all-gather(params), expressed as sharding
        constraints on the flat padded views (traceable; used inside
        the jitted step).

        Two numerics pins make this program do the replicated path's
        arithmetic on the same values in the same order, which is what
        lets the elastic churn drills hold zero1 worlds to the
        replicated trajectory.  Whether the two are then the same BITS
        is the backend's to say, and on this toolchain neither backend
        says yes beyond one shard.  XLA:CPU's fusion emitters round the
        flat shard's update unlike the original shapes' (mu and nu
        differ in the last bit from the second update on, a loss within
        six steps; bit-identical again with
        ``--xla_cpu_use_fusion_emitters=false``): tests/test_zero1.py
        holds the CPU to 1e-6.  On the four chips of a v5e host a bf16
        LM under AdamW differs from the first update on (11 of 12
        losses, at most 3.1e-5 relative: ``chip_check.py zero1``, my
        chip run, PR 28; on one chip, one shard, all 12 are the same
        bits).  The pins keep the difference at that last-ulp level:

        1. grads are first constrained replicated — the cross-replica
           sum lands at the same program point as the replicated path's
           all-reduce, so the backward is never re-partitioned into a
           different accumulation order.  The flat sharded constraint
           right after is then a pure shard slice; on TPU, XLA's
           reduce-scatter creator folds the sum+slice pair into a true
           reduce-scatter.
        2. an optimization barrier between the shard-local update and
           the params all-gather — without it the partitioner
           duplicates the update computation (one sharded copy for the
           opt-state output, one differently-fused replicated copy for
           the params output) and the copies disagree in the last ulp.

        The scan carry of a fused window shrinks accordingly: opt state
        rides through the window as 1/N-sized shards.
        """
        z = self._zero
        shard_t = z.params_shardings(z.shard)
        rep_t = z.params_shardings(z.replicated)
        grads = jax.lax.with_sharding_constraint(grads, rep_t)
        flat_g = jax.lax.with_sharding_constraint(
            z.flatten_params(grads), shard_t
        )
        flat_p = jax.lax.with_sharding_constraint(
            z.flatten_params(params), shard_t
        )
        updates, opt_state = tx.update(flat_g, opt_state, flat_p)
        flat_new = optax.apply_updates(flat_p, updates)
        flat_new, opt_state = jax.lax.optimization_barrier(
            (flat_new, opt_state)
        )
        flat_new = jax.lax.with_sharding_constraint(flat_new, rep_t)
        return z.unflatten_params(flat_new), opt_state

    def _device_room(self):
        """What one device has left for the model's step once this
        trainer's state is on it: the backend's limit less the
        parameters, the optimizer state as sharded, the gradients (and
        their accumulator under accumulation) and, with
        ``use_bf16_compute``, the bfloat16 copy of the parameters.  None
        where the backend states no limit (the CPU)."""
        limit = _bytes_limit(jax.local_devices()[:1] if self._mesh is None
                             else self._mesh.local_devices)
        if limit is None:
            return None
        if self._room_refused:
            return DeviceRoom(limit, 0)
        params = _device_bytes(self._params)
        held = (params * (3 if self._accum_steps > 1 else 2)
                + _device_bytes(self._opt_state))
        if self._use_bf16_compute:
            held += params // 2
        return DeviceRoom(limit, limit - held)

    def _build_train_step(self):
        tx = self._spec.optimizer
        accum = self._accum_steps
        zero = self._zero
        self._room = self._device_room()

        def step(params, opt_state, features, labels, weights):
            if accum == 1:
                loss, grads, stats = self._loss_and_grads(
                    params, features, labels, weights
                )
            else:
                def body(carry, microbatch):
                    acc_grads, acc_loss = carry
                    f, l, w = microbatch
                    loss, grads, stats = self._loss_and_grads(
                        params, f, l, w)
                    acc_grads = jax.tree_util.tree_map(
                        jnp.add, acc_grads, grads
                    )
                    return (acc_grads, acc_loss + loss), stats

                zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
                (grads, loss_sum), stats = jax.lax.scan(
                    body, (zeros, 0.0), (features, labels, weights)
                )
                grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
                loss = loss_sum / accum
                stats = jax.tree_util.tree_map(
                    lambda s: s.sum(axis=0), stats)
            if zero is not None:
                params, opt_state = self._zero1_apply(
                    tx, params, opt_state, grads
                )
            else:
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, loss, stats

        # The fused windows chain steps and keep the losses alone.
        self._raw_step = lambda *args: step(*args)[:3]
        if self._mesh is None:
            return jax.jit(step, donate_argnums=(0, 1))
        rep = self._replicated
        opt_sharding = self._opt_out_shardings()
        if self._accum_steps == 1:
            batch_in = self._batch_sharding
        else:
            # [accum, micro, ...]: shard the microbatch axis.
            batch_in = NamedSharding(
                self._mesh, P(None, self._data_axis)
            )
        weights_in = (
            self._batch_sharding if self._accum_steps == 1
            else NamedSharding(self._mesh, P(None, self._data_axis))
        )
        return jax.jit(
            step,
            in_shardings=(rep, opt_sharding, batch_in, batch_in,
                          weights_in),
            out_shardings=(rep, opt_sharding, rep, rep),
            donate_argnums=(0, 1),
        )

    def _window_batch_sharding(self):
        """Sharding for window-stacked batch leaves: [K, batch, ...]
        shards dim 1 (the data axis); with accumulation the stack is
        [K, accum, micro, ...] and dim 2 is the data axis."""
        if self._mesh is None:
            return None
        if self._accum_steps == 1:
            return NamedSharding(self._mesh, P(None, self._data_axis))
        return NamedSharding(
            self._mesh, P(None, None, self._data_axis)
        )

    def build_fused_window(self, num_steps):
        """Compile num_steps optimizer steps over num_steps DISTINCT
        minibatches (stacked on the leading axis) into ONE XLA program —
        the fused-step path (``worker/fused_driver.py``).

        Returns fn(params, opt_state, features, labels, weights) ->
        (params, opt_state, losses[num_steps]); losses stay on device
        until the caller fetches them (fused_driver.LossRing).

        The scan is fully UNROLLED: a rolled scan double-buffers the
        params/opt-state carry every iteration (measured ~4x slower
        than sequential dispatch on CPU XLA), while the unrolled body
        is one straight-line program XLA fuses across steps (~2.4x
        faster than the per-step loop on the same rig).  Compile time
        scales with num_steps — keep --fused_steps modest (4-16); each
        distinct window length compiles once and is cached.

        With ``--zero1`` the window's opt-state carry is the flat
        sharded form: each chained step hands its successor 1/N of the
        optimizer state instead of a full replicated copy, which is
        what shrinks the rolled-scan carry-copy cost the fused driver
        measured (docs/training_pipeline.md has the carry-size math).
        """
        raw = self._raw_step

        def window(params, opt_state, features, labels, weights):
            def body(carry, batch):
                params, opt_state = carry
                f, l, w = batch
                params, opt_state, loss = raw(params, opt_state, f, l, w)
                return (params, opt_state), loss

            (params, opt_state), losses = jax.lax.scan(
                body, (params, opt_state), (features, labels, weights),
                unroll=True,
            )
            return params, opt_state, losses

        if self._mesh is None:
            return jax.jit(window, donate_argnums=(0, 1))
        rep = self._replicated
        opt_sharding = self._opt_out_shardings()
        batch_in = self._window_batch_sharding()
        return jax.jit(
            window,
            in_shardings=(rep, opt_sharding, batch_in, batch_in,
                          batch_in),
            out_shardings=(rep, opt_sharding, rep),
            donate_argnums=(0, 1),
        )

    def _build_eval_step(self):
        apply_fn = self._spec.apply_fn

        def step(params, features):
            with batch_axis(self._mesh, self._data_axis):
                return apply_fn(params, features, False)

        if self._mesh is None:
            return jax.jit(step)
        return jax.jit(
            step,
            in_shardings=(self._replicated, self._batch_sharding),
            out_shardings=self._replicated,
        )

    # -- Trainer API --------------------------------------------------------

    def _padded(self, features, labels, total):
        (features, labels), weights = _pad_batch((features, labels), total)
        return features, labels, weights

    def _accum_sharding(self):
        """Per-step batch sharding: [batch, ...] over data, or
        [accum, micro, ...] with the microbatch axis over data."""
        if self._mesh is None:
            return None
        if self._accum_steps == 1:
            return self._batch_sharding
        return NamedSharding(self._mesh, P(None, self._data_axis))

    def prepare_batch(self, features, labels, count=None):
        """Host-side batch prep (pad, accum reshape, multi-controller
        globalize) via a cached per-(count, structure) plan — the
        producer-stage half of the fused driver; ``train_minibatch``
        routes through it too, so the per-step path stops re-deriving
        shapes every step."""
        if self._example_features is None:
            # Shape/dtype skeleton of one raw minibatch — fixes the
            # serving signature of the train-end servable export.
            self._example_features = jax.tree_util.tree_map(
                lambda a: np.zeros(np.shape(a), np.asarray(a).dtype),
                features,
            )
        with self.timing.timeit("batch_prep"):
            leaves, treedef = jax.tree_util.tree_flatten(
                (features, labels)
            )
            n = int(np.shape(leaves[0])[0])
            # Trailing dims are part of the key: with accumulation the
            # plan bakes reshape targets, and a pipeline with variable
            # trailing shapes (e.g. sequence length) must not hit a
            # stale plan's targets.
            key = (n, treedef,
                   tuple(np.shape(leaf)[1:] for leaf in leaves))
            plan = self._pad_plans.get(key)
            if plan is None:
                micro = self._process_rows()
                local = micro * self._accum_steps
                plan = _PadPlan(
                    leaves, n, local, self._accum_steps, micro
                )
                if len(self._pad_plans) >= _PAD_PLAN_CACHE_MAX:
                    self._pad_plans.clear()
                self._pad_plans[key] = plan
            out = []
            for leaf, pad_width, reshape in zip(
                leaves, plan.pad_widths, plan.reshapes
            ):
                a = np.asarray(leaf)
                if pad_width is not None:
                    a = np.pad(a, pad_width)
                if reshape is not None:
                    a = a.reshape(reshape)
                out.append(a)
            features, labels = jax.tree_util.tree_unflatten(treedef, out)
            weights = plan.weights
            if self.process_count > 1:
                sharding = self._accum_sharding()
                features = self._globalize(features, sharding)
                labels = self._globalize(labels, sharding)
                weights = self._globalize(weights, sharding)
        return PreparedBatch(
            features, labels, weights, n if count is None else count
        )

    def _program(self, window):
        """The per-step program (``window`` 1) or a cached fused window."""
        if window == 1:
            return self._train_step
        fn = self._fused_window_cache.get(window)
        if fn is None:
            fn = self._fused_window_cache[window] = self.build_fused_window(
                window)
        return fn

    def _run_step(self, window, *batch):
        """``_program(window)`` on (params, opt_state, *batch).  The
        model sized what it keeps for the backward from an estimate
        (``_device_room``); when the compile behind a program's first
        call says the estimate was short, the room is restated as none
        left and the program is built once more: a job that fits with
        nothing kept trains."""
        args = (self._params, self._opt_state) + batch
        try:
            with self.timing.timeit("step_dispatch"):
                return self._program(window)(*args)
        except jax.errors.JaxRuntimeError as e:
            if (self._room_refused or not self._room
                    or not _out_of_memory(e, args)):
                raise
            logger.warning(
                "remat keep: fallback=1 the step's compile ran out of "
                "device memory with %d bytes stated free; rebuilding it "
                "with nothing kept (%s)",
                self._room.free, str(e).splitlines()[0][:200])
        self._room_refused = True
        self._fused_window_cache = {}
        self._train_step = self._build_train_step()
        with self.timing.timeit("step_dispatch"):
            return self._program(window)(*args)

    def _first_run_step(self, window, *batch):
        """The first ``_run_step`` of a process still setting up: the
        Python trace, the lowering and the compile or cache load of the
        step (the ``remat keep:`` fallback's rebuild included) are the
        timeline's ``first_dispatch``; what follows until the task's
        fence is ``first_run``."""
        del self._run_step
        SETUP.mark("first_dispatch")
        try:
            return self._run_step(window, *batch)
        finally:
            SETUP.mark("first_run")

    def train_minibatch(self, features, labels):
        """One step; returns (loss, version) where ``loss`` is a LAZY
        device scalar — no host sync here.  Callers that need a float
        (cadence logging, benches) pull it explicitly via
        ``float(loss)``; that fetch is the fence."""
        prepared = self.prepare_batch(features, labels)
        (self._params, self._opt_state, loss,
         self.last_step_stats) = self._run_step(
            1, prepared.features, prepared.labels, prepared.weights)
        self._count_zero1_traffic(1)
        self._version += 1
        self._maybe_report_and_checkpoint()
        return loss, self._version

    def _count_zero1_traffic(self, steps):
        """Logical collective payload accounting: each zero1 step
        reduce-scatters one flat grads tree and all-gathers one flat
        params tree (byte counts are the annotated payload sizes, not
        a wire capture — surfaced under Timing.summary()['zero1'])."""
        if self._zero is None:
            return
        flat_bytes = self._zero.flat_param_bytes()
        self.timing.bump("zero1_reduce_scatter_bytes",
                         flat_bytes * steps)
        self.timing.bump("zero1_all_gather_bytes", flat_bytes * steps)

    # -- fused window API (fused_driver.FusedStepDriver) --------------------

    @property
    def max_window(self):
        """None = unbounded fused windows.  Multi-controller batches
        are committed global arrays (per-process assembly) — stacking
        them host-side is impossible, so the driver is capped to
        window 1 there."""
        return 1 if self.process_count > 1 else None

    def steps_to_boundary(self):
        """Steps until the next version-report or checkpoint cadence
        boundary — the fused driver clamps windows to it so those
        events land on exactly the per-step loop's step numbers."""
        dists = []
        if self._mc is not None and self._report_version_steps:
            dists.append(
                self._report_version_steps
                - self._version % self._report_version_steps
            )
        if self._checkpoint_saver is not None and self._checkpoint_steps:
            dists.append(
                self._checkpoint_steps
                - self._version % self._checkpoint_steps
            )
        if self._export_steps:
            dists.append(
                self._export_steps - self._version % self._export_steps
            )
        return min(dists) if dists else None

    def stage_window(self, prepared, to_device=True):
        """Stack K prepared batches on a leading axis and (optionally)
        start their host→device transfer NOW — ``device_put`` is async,
        so staging window N+1 while window N executes is the device
        double-buffer."""
        k = len(prepared)
        if k > 1 and self.process_count > 1:
            raise ValueError(
                "fused windows are single-controller only (max_window)"
            )
        if k == 1:
            batch = prepared[0]
            features, labels = batch.features, batch.labels
            weights = batch.weights
            sharding = self._accum_sharding()
        else:
            stack = lambda *leaves: np.stack(leaves)
            features = jax.tree_util.tree_map(
                stack, *[b.features for b in prepared]
            )
            labels = jax.tree_util.tree_map(
                stack, *[b.labels for b in prepared]
            )
            weights = np.stack([b.weights for b in prepared])
            sharding = self._window_batch_sharding()
        if to_device and self.process_count == 1:
            if sharding is not None:
                put = lambda tree: jax.device_put(tree, sharding)
            else:
                put = jax.device_put
            features, labels, weights = (
                put(features), put(labels), put(weights)
            )
        return StagedWindow(k, features, labels, weights)

    def train_window(self, staged):
        """Dispatch one staged window (1 XLA call for its K steps);
        returns (device-resident losses, version-after-window).  The
        caller is responsible for clamping K to ``steps_to_boundary``
        (fused_driver does) — report/checkpoint cadence checks run once
        at the window boundary."""
        out = self._run_step(
            staged.size, staged.features, staged.labels, staged.weights)
        if staged.size == 1:
            (self._params, self._opt_state, losses,
             self.last_step_stats) = out
        else:
            self._params, self._opt_state, losses = out
        self._count_zero1_traffic(staged.size)
        self._version += staged.size
        self._maybe_report_and_checkpoint()
        return losses, self._version

    def _maybe_report_and_checkpoint(self):
        if (
            self._mc is not None
            and self._report_version_steps
            and self._version % self._report_version_steps == 0
        ):
            self._mc.report_version(self._version)
        if (
            self._checkpoint_saver is not None
            and self._checkpoint_steps
            and self._version % self._checkpoint_steps == 0
        ):
            self.save_checkpoint()
        if self._export_steps and self._version % self._export_steps == 0:
            self.export_servable_now()

    def _forward_local(self, features):
        """Inference on THIS process only: local device, local copy of
        the replicated params.  Eval/predict tasks are handed to
        individual workers by the task stream, so in a multi-controller
        world they must never enter a collective — a lone worker doing
        an eval task would deadlock every peer (the reference's
        allreduce mode evaluates locally for the same reason).  The
        host params copy is cached per model version (an eval task
        runs many minibatches against unchanging params)."""
        if getattr(self, "_local_eval_step", None) is None:
            apply_fn = self._spec.apply_fn
            self._local_eval_step = jax.jit(
                lambda p, x: apply_fn(p, x, False)
            )
            self._local_params_cache = None
        cache = getattr(self, "_local_params_cache", None)
        if cache is None or cache[0] != self._version:
            cache = (self._version, to_numpy(self._params))
            self._local_params_cache = cache
        return self._local_eval_step(cache[1], features)

    def evaluate_minibatch(self, features, labels):
        n = jax.tree_util.tree_leaves(features)[0].shape[0]
        features, _, _ = self._padded(
            features, labels, self._process_rows())
        if self.process_count > 1:
            outputs = self._forward_local(features)
        else:
            outputs = self._eval_step(self._params, features)
        outputs = np.asarray(outputs)[:n]
        return outputs, np.asarray(labels)

    def predict_minibatch(self, features):
        n = jax.tree_util.tree_leaves(features)[0].shape[0]
        features, _ = _pad_batch(features, self._process_rows())
        if self.process_count > 1:
            outputs = self._forward_local(features)
        else:
            outputs = self._eval_step(self._params, features)
        return np.asarray(outputs)[:n]

    # -- state --------------------------------------------------------------

    @property
    def version(self):
        return self._version

    @property
    def params(self):
        return self._params

    def set_params(self, params):
        self._params = params
        self._opt_state = self._spec.optimizer.init(params)
        self._opt_is_flat = False
        if self._mesh is not None:
            self._params = self._reshard_to(
                self._params, self._replicated
            )
            self._opt_state = self._place_opt_state(old_zero=None)
            self._opt_is_flat = self._zero is not None

    def export_parameters(self):
        named, _ = flatten_with_names(to_numpy(self._params))
        return named

    def _opt_state_on_host(self):
        """Original-shape HOST view of the optimizer state.  ZeRO-1
        shards are gathered on-device through the unpadding view first
        (multi-controller safe); replicated state converts directly."""
        if self._opt_is_flat and self._zero is not None:
            return self._zero.gather_to_host(self._opt_state)
        return to_numpy(self._opt_state)

    def serving_bundle(self):
        """(inference_fn, params, example_input) for the servable
        export; None before the first minibatch fixed the signature."""
        if self._example_features is None:
            return None
        apply_fn = self._spec.apply_fn
        return (
            lambda p, x: apply_fn(p, x, False),
            to_numpy(self._params),
            self._example_features,
        )

    def save_checkpoint(self):
        """Params AND optimizer state (``opt/``-prefixed, mirroring
        spmd_trainer) — an elastic restore must resume the Adam/momentum
        trajectory, not restart it (reference PS slot persistence,
        go/pkg/ps/checkpoint.go:98-133).

        The device->host gather is synchronous (the next step's buffer
        donation invalidates the old arrays), but the disk write runs on
        a single background thread so the train loop only ever pays
        transfer time, not serialization+IO.  ``flush_checkpoints``
        joins pending writes (called at train end).

        ZeRO-1 state is checkpointed through its unpadding view
        (``_opt_state_on_host``): the file always holds original-shape
        leaves, so checkpoints are byte-portable between ``--zero1``
        on and off, and the on-device all-gather makes the host
        transfer safe in multi-controller worlds (raw ``to_numpy`` on
        a sharded leaf would hit non-addressable shards)."""
        with self.timing.timeit("checkpoint_save"):
            payload = dict(self.export_parameters())
            opt_named, _ = flatten_with_names(self._opt_state_on_host())
            payload.update({"opt/" + k: v for k, v in opt_named.items()})
            if self._ckpt_executor is None:
                from concurrent.futures import ThreadPoolExecutor

                self._ckpt_executor = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="ckpt-write"
                )
            # Join the previous write first: bounds outstanding host
            # copies to one and guarantees its error (disk full, NFS)
            # surfaces HERE — raising out of train_minibatch so the
            # task fails visibly, exactly like the old synchronous save.
            self._surface_checkpoint_errors(wait=True)
            self._ckpt_future = self._ckpt_executor.submit(
                self._checkpoint_saver.save, self._version, dense=payload
            )
        logger.info("checkpoint at version %d queued for write",
                    self._version)

    def export_servable_now(self):
        """Continuous-export hook body (``--export_steps`` cadence):
        snapshot params on the caller (the next step's buffer donation
        invalidates device arrays, exactly the checkpoint constraint),
        then write the versioned servable on the same single background
        writer thread checkpoints use — the train loop pays host-gather
        time only, never npz serialization + fsync + rename.  The first
        export additionally traces/serializes the StableHLO program
        (ContinuousExporter caches it; steady state is weights-only).
        Errors surface on the NEXT cadence event, like checkpoint
        write errors."""
        bundle = self.serving_bundle()
        if bundle is None or self._exporter is None:
            return
        with self.timing.timeit("servable_export"):
            infer_fn, params, example = bundle
            version = self._version
            if self._ckpt_executor is None:
                from concurrent.futures import ThreadPoolExecutor

                self._ckpt_executor = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="ckpt-write"
                )
            self._surface_export_errors(wait=True)
            tracing.event("worker.servable_export", version=version)
            self._export_future = self._ckpt_executor.submit(
                self._exporter.export, version, infer_fn, params,
                example,
            )
        self.timing.bump("servable_exports")

    def _surface_checkpoint_errors(self, wait):
        future = getattr(self, "_ckpt_future", None)
        if future is None:
            return
        if wait or future.done():
            self._ckpt_future = None
            try:
                future.result()
            except Exception as e:  # noqa: BLE001 — IO errors
                raise RuntimeError(
                    "async checkpoint write failed: %s" % (e,)
                ) from e

    def _surface_export_errors(self, wait):
        future = self._export_future
        if future is None:
            return
        if wait or future.done():
            self._export_future = None
            try:
                future.result()
            except Exception as e:  # noqa: BLE001 — IO / trace errors
                raise RuntimeError(
                    "async servable export failed: %s" % (e,)
                ) from e

    def flush_checkpoints(self):
        """Join pending checkpoint writes AND retire the writer thread
        (train end / before export).  Shutting the executor down here —
        not just joining the future — is the owner's stop path (EL007):
        a lazily re-created pool costs nothing, but a leaked one keeps
        its thread alive past the trainer and can hang worker exit.
        The next async save simply recreates it."""
        try:
            self._surface_checkpoint_errors(wait=True)
            self._surface_export_errors(wait=True)
        finally:
            # Retire the pool even when the surfaced write error
            # raises — the failure path must not leak the thread.
            if self._ckpt_executor is not None:
                self._ckpt_executor.shutdown(wait=True)
                self._ckpt_executor = None

    def init_from_checkpoint(self):
        if self._checkpoint_saver is None:
            return False
        self.flush_checkpoints()
        try:
            dense, _, version = self._checkpoint_saver.load()
        except FileNotFoundError:
            return False
        from elasticdl_tpu.utils.pytree import unflatten_from_names

        params_named = {
            k: v for k, v in dense.items() if not k.startswith("opt/")
        }
        opt_named = {
            k[len("opt/"):]: v for k, v in dense.items()
            if k.startswith("opt/")
        }
        self._params = unflatten_from_names(
            to_numpy(self._params), params_named
        )
        fresh_opt = True
        if opt_named:
            # Checkpoints hold ORIGINAL leaf shapes; restore against an
            # original-shape skeleton (a flat ZeRO-1 live state would
            # reject every leaf on shape) — rebuild() re-flattens and
            # re-shards below.
            template = (
                self._spec.optimizer.init(to_numpy(self._params))
                if self._opt_is_flat
                else to_numpy(self._opt_state)
            )
            try:
                self._opt_state = unflatten_from_names(
                    template, opt_named
                )
                self._opt_is_flat = False
                fresh_opt = False
            except (KeyError, ValueError) as e:
                # Optimizer changed since the checkpoint (e.g. Adam ->
                # momentum): params are still good, trajectory is not.
                logger.warning(
                    "checkpoint optimizer state incompatible (%s); "
                    "re-initializing optimizer", e,
                )
        if fresh_opt:  # pre-opt-state checkpoint or structure mismatch
            self._opt_state = self._spec.optimizer.init(self._params)
            self._opt_is_flat = False
        if self._mesh is not None:
            self.rebuild(self._mesh)
        self._version = version
        logger.info("restored checkpoint version %d", version)
        return True
