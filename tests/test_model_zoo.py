"""Model-zoo smoke + convergence tests across the families."""

import os

import numpy as np
import pytest

from elasticdl_tpu.models import (
    census_dnn,
    census_sqlflow,
    dcn,
    iris,
    mobilenet,
    wide_deep,
    xdeepfm,
)
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.worker.collective_trainer import CollectiveTrainer
from elasticdl_tpu.worker.ps_trainer import ParameterServerTrainer
from tests.test_pserver import start_ps, stop_all


def test_load_model_spec_by_short_name():
    spec = load_model_spec("mnist")
    assert spec.name == "mnist"
    spec = load_model_spec("elasticdl_tpu.models.iris")
    assert spec.name == "iris"


def test_mobilenetv2_param_count_near_reference():
    """Reference MobileNetV2 has 2,236,682 params
    (ftlib_benchmark.md:45); ours should land in the same ballpark
    (GroupNorm vs BatchNorm shifts the count slightly)."""
    import jax

    spec = mobilenet.model_spec()
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    count = sum(np.prod(p.shape) for p in
                jax.tree_util.tree_leaves(params))
    assert 1.8e6 < count < 2.8e6, count


def _jitted_init(spec):
    """``spec`` with its ``init_fn`` under one ``jax.jit``: the trainer
    calls it as it is, and an image model's initialisation run
    operation by operation is hundreds of programs compiled one by one
    (366 for MobileNetV2: 20 s of its 30; ROADMAP C16 (b))."""
    import dataclasses

    import jax

    return dataclasses.replace(spec, init_fn=jax.jit(spec.init_fn))


def test_resnet_s2d_stem():
    """Space-to-depth stem (MXU-shaped first conv, VERDICT r3 #5):
    the transform is an exact invertible reshuffle, the s2d model's
    feature maps keep the standard resnet50 shapes from the pool down
    (so every later layer is identical), and a step trains."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.models import resnet

    x = np.arange(2 * 8 * 8 * 3, dtype=np.float32).reshape(2, 8, 8, 3)
    s = resnet.space_to_depth(jnp.asarray(x), 2)
    assert s.shape == (2, 4, 4, 12)
    # block (i,j) of the input is channel-sliced intact: position
    # [b, h, w, (di*2+dj)*3 + c] == input [b, 2h+di, 2w+dj, c]
    np.testing.assert_array_equal(
        np.asarray(s)[0, 1, 2, :3], x[0, 2, 4, :3])
    np.testing.assert_array_equal(
        np.asarray(s)[0, 1, 2, 9:], x[0, 3, 5, :3])

    spec = resnet.model_spec(variant="resnet50_s2d", num_classes=10,
                             image_size=32, learning_rate=0.1)
    # shapes alone: nothing is initialised or run before the trainer's step
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    stem = params["Conv_0"]["kernel"]
    assert stem.shape == (4, 4, 12, 64)  # vs (7, 7, 3, 64) baseline
    logits = jax.eval_shape(
        lambda p, x: spec.apply_fn(p, x, True), params,
        jax.ShapeDtypeStruct((2, 32, 32, 3), np.float32))
    assert logits.shape == (2, 10)
    trainer = CollectiveTrainer(_jitted_init(spec), batch_size=4)
    xs = np.random.RandomState(0).rand(4, 32, 32, 3).astype(np.float32)
    ys = np.arange(4, dtype=np.int32) % 10
    loss, _ = trainer.train_minibatch(xs, ys)
    assert np.isfinite(loss)


def test_mobilenetv2_trains():
    spec = _jitted_init(mobilenet.model_spec(learning_rate=0.01))
    trainer = CollectiveTrainer(spec, batch_size=8)
    rng = np.random.RandomState(0)
    xs = rng.rand(8, 32, 32, 3).astype(np.float32)
    ys = rng.randint(0, 10, 8).astype(np.int32)
    loss, _ = trainer.train_minibatch(xs, ys)
    assert np.isfinite(loss)


def test_iris_learns_from_csv(tmp_path):
    path = iris.synthetic_iris_csv(str(tmp_path / "iris.csv"), n=120)
    with open(path) as f:
        records = [line.strip().split(",") for line in f]
    spec = iris.model_spec(learning_rate=0.05)
    trainer = CollectiveTrainer(spec, batch_size=32)
    for _ in range(12):
        for i in range(0, 120, 32):
            xs, ys = spec.feed(records[i:i + 32])
            trainer.train_minibatch(xs, ys)
    xs, ys = spec.feed(records)
    correct = 0
    for i in range(0, 120, 32):
        out, labels = trainer.evaluate_minibatch(xs[i:i + 32],
                                                 ys[i:i + 32])
        correct += (np.argmax(out, -1) == labels).sum()
    assert correct / 120 > 0.8


@pytest.mark.parametrize("module", [dcn, xdeepfm])
def test_ctr_models_train_through_ps(module):
    spec = module.model_spec(vocab_size=500, embedding_dim=4,
                             hidden=(16,))
    client, servicers, servers = start_ps(
        num_ps=1, opt_type="adam", opt_args="learning_rate=0.01",
    )
    try:
        trainer = ParameterServerTrainer(spec, client, batch_size=32)
        dense, ids, labels = module.synthetic_data(n=64, vocab_size=500)
        records = [(dense[i], ids[i], labels[i]) for i in range(64)]
        feats, ys = spec.feed(records[:32])
        loss1, _ = trainer.train_minibatch(feats, ys)
        for _ in range(10):
            loss2, _ = trainer.train_minibatch(feats, ys)
        assert np.isfinite(loss2) and loss2 < loss1
    finally:
        stop_all(servers)


def test_wide_deep_census_through_ps():
    spec = wide_deep.model_spec(embedding_dim=4, hidden=(16,))
    client, servicers, servers = start_ps(
        num_ps=2, opt_type="adam", opt_args="learning_rate=0.01",
    )
    try:
        trainer = ParameterServerTrainer(spec, client, batch_size=32)
        rows = wide_deep.synthetic_census_rows(n=256)
        losses = []
        for epoch in range(4):
            for i in range(0, 256, 32):
                feats, ys = spec.feed(rows[i:i + 32])
                loss, _ = trainer.train_minibatch(feats, ys)
                losses.append(loss)
        assert losses[-1] < losses[0]
    finally:
        stop_all(servers)


@pytest.mark.parametrize("make_spec", [
    lambda: census_dnn.model_spec(embedding_dim=4, hidden=(16,)),
    lambda: census_sqlflow.model_spec("wide_and_deep",
                                      embedding_dim=4, hidden=(16,)),
    lambda: census_sqlflow.model_spec("dnn", embedding_dim=4,
                                      hidden=(16,)),
], ids=["census_dnn", "sqlflow_wide_deep", "sqlflow_dnn"])
def test_census_models_train_through_ps(make_spec):
    spec = make_spec()
    client, servicers, servers = start_ps(
        num_ps=2, opt_type="adam", opt_args="learning_rate=0.01",
    )
    try:
        trainer = ParameterServerTrainer(spec, client, batch_size=32)
        records = census_dnn.synthetic_census_records(n=256)
        losses = []
        for epoch in range(4):
            for i in range(0, 256, 32):
                feats, ys = spec.feed(records[i:i + 32])
                loss, _ = trainer.train_minibatch(feats, ys)
                losses.append(loss)
        assert np.isfinite(losses[-1]) and losses[-1] < losses[0]
    finally:
        stop_all(servers)


def test_census_sqlflow_clause_compiles_to_disjoint_id_spaces():
    groups = census_sqlflow.build_groups()
    # Groups mirror the .sql's three CONCAT clauses.
    assert sorted(groups) == ["group_1", "group_2", "group_3"]
    records = census_dnn.synthetic_census_records(n=64)
    columns = {k: [r[k] for r in records] for k in records[0]}
    for concat in groups.values():
        ids = concat.transform(columns)
        assert ids.shape == (64, len(concat.columns))
        assert ids.min() >= 0 and ids.max() < concat.num_buckets
        # Per-field slices live in disjoint offset ranges.
        for j, (col, off) in enumerate(
            zip(concat.columns, concat.offsets)
        ):
            assert ids[:, j].min() >= off
            assert ids[:, j].max() < off + col.num_buckets


def test_census_dnn_stats_standardization(monkeypatch):
    # Analyzer-exported stats flow into the numeric columns
    # (use_stats=True), the reference's _ELASTICDL_* env scheme.
    from elasticdl_tpu.preprocessing import analyzer_utils

    monkeypatch.setenv("_EDL_TPU_AGE_AVG", "40")
    monkeypatch.setenv("_EDL_TPU_AGE_STDDEV", "10")
    assert analyzer_utils.get_mean("age") == 40.0
    numeric, _ = census_dnn.build_columns(use_stats=True)
    age = [c for c in numeric if c.key == "age"][0]
    out = age.transform(["50", "30"])
    assert np.allclose(out, [1.0, -1.0])


@pytest.mark.slow
def test_transformer_lm_managed_job_e2e(tmp_path):
    """The flagship LM trains through the FULL managed path: master,
    dynamic shards over the synthetic-LM origin, worker subprocess,
    model_params plumbing — and the loss on the structured sequences
    drops."""
    import subprocess
    import sys

    log = tmp_path / "job.log"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    with open(log, "w") as f:
        proc = subprocess.run(
            [sys.executable, "-m", "elasticdl_tpu.master.main",
             "--data_origin", "synthetic_lm:512:64:512",
             "--model_zoo", "transformer",
             "--model_params",
             "vocab_size=512;dim=64;num_heads=4;num_layers=2;seq_len=64",
             "--batch_size", "16", "--num_epochs", "2",
             "--num_workers", "1", "--num_minibatches_per_task", "4",
             "--log_loss_steps", "8"],
            stdout=f, stderr=subprocess.STDOUT, env=env, timeout=420,
        )
    text = log.read_text()
    assert proc.returncode == 0, text[-2000:]
    assert "job finished" in text
    import re

    losses = [float(m) for m in re.findall(r"loss[=: ]+([0-9.]+)", text)]
    assert len(losses) >= 2, text[-2000:]
    assert losses[-1] < losses[0], losses
