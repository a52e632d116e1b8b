"""ZeRO-1 weight-update sharding (worker/zero.py + --zero1).

The contract under test, end to end:

 - full coverage: EVERY non-scalar optimizer leaf shards (flat padded
   dim 0 over the data axis), including the odd shapes the old stub
   silently replicated;
 - trajectory: zero1 on vs off is the same trajectory, per-step and
   through fused windows, with and without gradient accumulation: to
   the last ulp or two on this backend (``LAST_ULPS`` below says why
   not bit for bit; ``chip_check.py zero1`` says what the chip gives);
 - elastic: a world re-form re-partitions live shards device-to-device
   with Adam moments preserved bit-exactly, and a same-size re-form
   continues the trajectory bitwise;
 - persistence: checkpoints hold the original-shape unpadding view and
   round-trip sharded -> file -> sharded, and across modes;
 - off switch: ``--zero1 false`` is the exact old replicated layout.
"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from elasticdl_tpu.models import mnist
from elasticdl_tpu.utils.checkpoint import CheckpointSaver
from elasticdl_tpu.worker.collective_trainer import CollectiveTrainer
from elasticdl_tpu.worker.zero import ZeroPartitioner


@pytest.fixture(scope="module")
def spec():
    return mnist.model_spec(learning_rate=1e-3)


def make_mesh(n):
    return Mesh(np.array(jax.devices()[:n]), axis_names=("data",))


def host_state(trainer):
    """Original-shape host view of the trainer's optimizer state."""
    return trainer._opt_state_on_host()


def assert_trees_bitwise(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- trajectory equivalence ------------------------------------------------

# The two programs do the same arithmetic on the same values, but this
# toolchain's XLA:CPU compiles an elementwise AdamW update to different
# roundings for the flat 1/N shard and for the original shapes: the
# second update already differs in the last bit of mu and nu, and a
# loss follows within six steps (1.0593639612 against 1.0593638420).
# With --xla_cpu_use_fusion_emitters=false (or optimization level 0) the
# same 12 steps are bit-identical (PR 28), so the difference is the
# backend's code generation, not the sharded update.  A wrong slice, a
# missing sum or a leaked padding row is orders of magnitude over this.
LAST_ULPS = dict(rtol=1e-6, atol=0)


def test_zero1_per_step_bitwise_equivalence(spec):
    """Same seed, same batches: zero1 losses == replicated losses to
    the last ulps (``LAST_ULPS``), the first step's bit for bit (its
    update starts from zero moments and rounds once)."""
    xs, ys = mnist.synthetic_data(n=64, seed=21)
    mesh = make_mesh(8)
    base = CollectiveTrainer(spec, batch_size=64, mesh=mesh, rng_seed=7)
    z1 = CollectiveTrainer(spec, batch_size=64, mesh=mesh, rng_seed=7,
                           zero1=True)
    for step in range(12):
        loss_b, _ = base.train_minibatch(xs, ys)
        loss_z, _ = z1.train_minibatch(xs, ys)
        if step < 2:    # the loss before, and after, the first update
            assert float(loss_b) == float(loss_z)
        np.testing.assert_allclose(loss_z, loss_b, **LAST_ULPS)


@pytest.mark.parametrize("window", [1, 4])
def test_zero1_fused_window_bitwise_equivalence(spec, window):
    """K fused steps per dispatch: the zero1 window (opt-state carry =
    1/N flat shards) reproduces the replicated window (``LAST_ULPS``)."""
    xs, ys = mnist.synthetic_data(n=64, seed=23)
    mesh = make_mesh(8)
    base = CollectiveTrainer(spec, batch_size=64, mesh=mesh, rng_seed=9)
    z1 = CollectiveTrainer(spec, batch_size=64, mesh=mesh, rng_seed=9,
                           zero1=True)
    for _ in range(2):
        pb = [base.prepare_batch(xs, ys) for _ in range(window)]
        pz = [z1.prepare_batch(xs, ys) for _ in range(window)]
        lb, _ = base.train_window(base.stage_window(pb))
        lz, _ = z1.train_window(z1.stage_window(pz))
        np.testing.assert_allclose(np.asarray(lz), np.asarray(lb),
                                   **LAST_ULPS)


def test_zero1_accum_bitwise_equivalence(spec):
    """Gradient accumulation (the fixed-global-batch elastic resize
    math) composes with the sharded update bit-exactly."""
    xs, ys = mnist.synthetic_data(n=64, seed=25)
    mesh = make_mesh(4)
    base = CollectiveTrainer(spec, batch_size=32, mesh=mesh,
                             accum_steps=2, rng_seed=11)
    z1 = CollectiveTrainer(spec, batch_size=32, mesh=mesh,
                           accum_steps=2, rng_seed=11, zero1=True)
    for _ in range(6):
        loss_b, _ = base.train_minibatch(xs, ys)
        loss_z, _ = z1.train_minibatch(xs, ys)
        assert float(loss_b) == float(loss_z)


# -- full coverage + unpad fidelity ----------------------------------------


def test_zero1_full_coverage_every_nonscalar_leaf_sharded(spec):
    """The old stub replicated any leaf whose dim 0 didn't divide the
    shard count (e.g. the [10] output bias).  The flat padded layout
    shards them ALL; only rank-0 scalars (Adam's step count) remain
    replicated."""
    mesh = make_mesh(8)
    z1 = CollectiveTrainer(spec, batch_size=64, mesh=mesh, zero1=True)
    xs, ys = mnist.synthetic_data(n=64, seed=27)
    z1.train_minibatch(xs, ys)
    replicated_nonscalar = [
        np.shape(leaf)
        for leaf in jax.tree_util.tree_leaves(z1._opt_state)
        if np.ndim(leaf) >= 1 and leaf.sharding.spec != P("data")
    ]
    assert replicated_nonscalar == []
    report = z1.zero1_report()
    assert report["mode"] == "zero1"
    # moments ~2x params >> padding + the scalar count: the measured
    # per-device bytes must sit within 1% of replicated/N
    assert report["per_device_bytes"] <= (
        report["replicated_equiv_bytes"] / report["num_shards"] * 1.01
    )


def test_unpad_fidelity_odd_shapes():
    """Flat-pad then unpad is the identity for shapes that do NOT
    divide the shard count (the [10] bias pads to [16]), bit-exact,
    with padding zeros never leaking."""
    mesh = make_mesh(8)
    import optax

    tx = optax.adam(1e-3)
    rng = np.random.RandomState(0)
    params = {
        "odd_bias": rng.randn(10).astype(np.float32),
        "odd_mat": rng.randn(7, 3).astype(np.float32),
        "even": rng.randn(16).astype(np.float32),
    }
    part = ZeroPartitioner(tx, params, mesh)
    flat = part.flatten_params(params)
    assert np.shape(flat["odd_bias"]) == (16,)
    assert np.shape(flat["odd_mat"]) == (24,)
    assert np.asarray(flat["odd_bias"])[10:].tolist() == [0.0] * 6
    back = part.unflatten_params(flat)
    assert_trees_bitwise(params, back)
    # state round-trip through the same specs (moments mirror params)
    state = tx.init(params)
    back_state = part.unflatten_state(part.flatten_state(state))
    assert_trees_bitwise(state, back_state)


# -- elastic re-partition --------------------------------------------------


def test_repartition_preserves_moments_bitwise(spec):
    """World resize 8 -> 4 -> 8 with live shards: the unpadded moment
    view is bit-identical across every re-partition, and the moves are
    device-to-device (no host bounce counter)."""
    xs, ys = mnist.synthetic_data(n=64, seed=29)
    z1 = CollectiveTrainer(spec, batch_size=64, mesh=make_mesh(8),
                           zero1=True, rng_seed=13)
    for _ in range(3):
        z1.train_minibatch(xs, ys)
    before = host_state(z1)
    z1.rebuild(make_mesh(4))  # half the world died
    assert_trees_bitwise(before, host_state(z1))
    counters = z1.timing.counters()
    assert counters.get("zero1_repartitions") == 1
    assert counters.get("zero1_reshard_bytes", 0) > 0
    assert counters.get("reshard_host_fallbacks", 0) == 0
    loss, _ = z1.train_minibatch(xs[:32], ys[:32])
    assert np.isfinite(float(loss))
    mid = host_state(z1)
    z1.rebuild(make_mesh(8))  # the replacements arrived
    assert_trees_bitwise(mid, host_state(z1))
    assert z1.timing.counters().get("zero1_repartitions") == 2


def test_same_size_reform_trajectory_bitwise(spec):
    """The common churn case — a peer is replaced, world SIZE is
    unchanged: the re-formed trainer continues the no-churn loss
    trajectory bit-for-bit (the VirtualFlow-style exactness the churn
    drills verify)."""
    xs, ys = mnist.synthetic_data(n=64, seed=31)
    ref = CollectiveTrainer(spec, batch_size=64, mesh=make_mesh(8),
                            zero1=True, rng_seed=15)
    churn = CollectiveTrainer(spec, batch_size=64, mesh=make_mesh(8),
                              zero1=True, rng_seed=15)
    ref_losses = [float(ref.train_minibatch(xs, ys)[0])
                  for _ in range(6)]
    churn_losses = [float(churn.train_minibatch(xs, ys)[0])
                    for _ in range(3)]
    churn.rebuild(make_mesh(8))  # epoch re-form, same world size
    churn_losses += [float(churn.train_minibatch(xs, ys)[0])
                     for _ in range(3)]
    assert churn_losses == ref_losses


def test_snapshot_to_host_gathers_sharded_state(spec):
    """snapshot_to_host on a zero1 world gathers the flat shards into
    original-shape host numpy (the multi-controller-safe path), and a
    rebuild from that snapshot resumes the exact trajectory."""
    xs, ys = mnist.synthetic_data(n=64, seed=33)
    ref = CollectiveTrainer(spec, batch_size=64, mesh=make_mesh(8),
                            zero1=True, rng_seed=17)
    t = CollectiveTrainer(spec, batch_size=64, mesh=make_mesh(8),
                          zero1=True, rng_seed=17)
    ref_losses = [float(ref.train_minibatch(xs, ys)[0])
                  for _ in range(4)]
    [t.train_minibatch(xs, ys) for _ in range(2)]
    t.snapshot_to_host()
    state = t._opt_state
    leaves, _ = jax.tree_util.tree_flatten(state)
    assert all(isinstance(leaf, np.ndarray) for leaf in leaves)
    # original (unpadded) shapes on host — not the flat wire form
    shapes = {np.shape(leaf) for leaf in leaves if np.ndim(leaf) >= 1}
    assert (3136, 128) in {s for s in shapes}
    t.rebuild(make_mesh(8))
    resumed = [float(t.train_minibatch(xs, ys)[0]) for _ in range(2)]
    assert resumed == ref_losses[2:]


# -- persistence -----------------------------------------------------------


def test_zero1_checkpoint_roundtrip_sharded(spec, tmp_path):
    """sharded -> checkpoint -> restore -> sharded: the file holds
    original shapes, the restored trainer resumes the exact
    trajectory, and its state is sharded again after rebuild."""
    saver = CheckpointSaver(str(tmp_path))
    xs, ys = mnist.synthetic_data(n=64, seed=35)
    mesh = make_mesh(8)
    ref = CollectiveTrainer(spec, batch_size=64, mesh=mesh,
                            zero1=True, rng_seed=19)
    ref_losses = [float(ref.train_minibatch(xs, ys)[0])
                  for _ in range(4)]
    t1 = CollectiveTrainer(spec, batch_size=64, mesh=mesh, zero1=True,
                           rng_seed=19, checkpoint_saver=saver,
                           checkpoint_steps=2)
    t1.train_minibatch(xs, ys)
    t1.train_minibatch(xs, ys)
    t1.flush_checkpoints()
    dense, _, _ = saver.load()
    # checkpoint holds the UNPADDED original shapes (mode-portable)
    assert dense["opt/0/mu/Dense_0/kernel"].shape == (3136, 128)
    assert dense["opt/0/mu/Dense_1/bias"].shape == (10,)
    t2 = CollectiveTrainer(spec, batch_size=64, mesh=mesh, zero1=True,
                           rng_seed=99, checkpoint_saver=saver)
    assert t2.init_from_checkpoint() and t2.version == 2
    sharded = [
        leaf for leaf in jax.tree_util.tree_leaves(t2._opt_state)
        if np.ndim(leaf) >= 1 and leaf.sharding.spec == P("data")
    ]
    assert sharded
    resumed = [float(t2.train_minibatch(xs, ys)[0]) for _ in range(2)]
    assert resumed == ref_losses[2:]


def test_zero1_checkpoint_portable_to_replicated(spec, tmp_path):
    """A checkpoint written by a zero1 trainer restores into a
    replicated trainer (and the trajectory matches bitwise) — the
    on-disk format is mode-independent."""
    saver = CheckpointSaver(str(tmp_path))
    xs, ys = mnist.synthetic_data(n=64, seed=37)
    mesh = make_mesh(8)
    ref = CollectiveTrainer(spec, batch_size=64, mesh=mesh, rng_seed=20)
    ref_losses = [float(ref.train_minibatch(xs, ys)[0])
                  for _ in range(4)]
    t1 = CollectiveTrainer(spec, batch_size=64, mesh=mesh, zero1=True,
                           rng_seed=20, checkpoint_saver=saver,
                           checkpoint_steps=2)
    t1.train_minibatch(xs, ys)
    t1.train_minibatch(xs, ys)
    t1.flush_checkpoints()
    t2 = CollectiveTrainer(spec, batch_size=64, mesh=mesh, rng_seed=99,
                           checkpoint_saver=saver)
    assert t2.init_from_checkpoint()
    resumed = [float(t2.train_minibatch(xs, ys)[0]) for _ in range(2)]
    assert resumed == ref_losses[2:]


# -- off switch + observability --------------------------------------------


def test_zero1_off_is_exact_old_layout(spec):
    """--zero1 false keeps the replicated layout: original leaf
    shapes, every leaf replicated, no partitioner, no zero1 counters."""
    mesh = make_mesh(8)
    t = CollectiveTrainer(spec, batch_size=64, mesh=mesh)
    xs, ys = mnist.synthetic_data(n=64, seed=39)
    t.train_minibatch(xs, ys)
    assert t._zero is None and not t._opt_is_flat
    for leaf in jax.tree_util.tree_leaves(t._opt_state):
        if np.ndim(leaf) >= 1:
            assert leaf.sharding.spec == P()
    shapes = {np.shape(leaf)
              for leaf in jax.tree_util.tree_leaves(t._opt_state)}
    assert (3136, 128) in shapes  # not flattened
    assert t.zero1_report()["mode"] == "replicated"
    counters = t.timing.counters()
    assert not any(k.startswith("zero1_") for k in counters)
    assert "zero1" not in t.timing.summary()


def test_zero1_timing_section_and_report(spec):
    """Dispatch counts reduce-scatter/all-gather payload bytes; the
    counters surface as the ``zero1`` section of Timing.summary() and
    report() handles the mixed summary without crashing."""
    mesh = make_mesh(8)
    z1 = CollectiveTrainer(spec, batch_size=64, mesh=mesh, zero1=True)
    xs, ys = mnist.synthetic_data(n=64, seed=41)
    z1.train_minibatch(xs, ys)
    prepared = [z1.prepare_batch(xs, ys) for _ in range(3)]
    z1.train_window(z1.stage_window(prepared))
    section = z1.timing.summary()["zero1"]
    flat_bytes = z1._zero.flat_param_bytes()
    assert section["zero1_reduce_scatter_bytes"] == flat_bytes * 4
    assert section["zero1_all_gather_bytes"] == flat_bytes * 4
    z1.timing.report()  # must tolerate the counter section


def test_zero1_single_device_mesh(spec):
    """A 1-device mesh world degenerates gracefully: zero1 stays
    active (1 shard == replicated) and steps run."""
    z1 = CollectiveTrainer(spec, batch_size=16, mesh=make_mesh(1),
                           zero1=True)
    xs, ys = mnist.synthetic_data(n=16, seed=43)
    loss, _ = z1.train_minibatch(xs, ys)
    assert np.isfinite(float(loss))
    assert z1.zero1_report()["num_shards"] == 1
