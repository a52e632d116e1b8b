"""Overlapped PS hot path: bf16 wire compression, pipelined async push,
embedding-pull prefetch, and the empty-ids shape fix.

In-process gRPC PS shards (same rig as test_pserver) so every assertion
runs against the real codec + servicer + optimizer stack."""

import numpy as np
import pytest

from elasticdl_tpu.models import deepfm
from elasticdl_tpu.proto import rpc
from elasticdl_tpu.ps.optimizer import create_optimizer
from elasticdl_tpu.ps.parameters import Parameters
from elasticdl_tpu.ps.servicer import PserverServicer
from elasticdl_tpu.utils import grpc_utils
from elasticdl_tpu.worker.ps_client import PSClient
from elasticdl_tpu.worker.ps_trainer import (
    GradientsRejected,
    ParameterServerTrainer,
)

VOCAB = 500


def start_ps(num_ps=1, opt_type="sgd", opt_args="learning_rate=0.1",
             **kwargs):
    """Boot N in-process PS shards; returns (addrs, servicers, servers)
    — addrs (not a client) so tests can build clients with any
    wire_dtype / push-channel configuration."""
    servers, servicers, addrs = [], [], []
    for i in range(num_ps):
        servicer = PserverServicer(
            Parameters(),
            create_optimizer(opt_type, opt_args),
            ps_id=i, num_ps=num_ps, **kwargs,
        )
        server = grpc_utils.build_server(max_workers=8)
        rpc.add_pserver_servicer(servicer, server)
        port = server.add_insecure_port("[::]:0")
        server.start()
        servers.append(server)
        servicers.append(servicer)
        addrs.append("localhost:%d" % port)
    return addrs, servicers, servers


def make_client(addrs, wire_dtype=None, dedicated_push_channels=False):
    def connect():
        channels = []
        for addr in addrs:
            ch = grpc_utils.build_channel(addr)
            grpc_utils.wait_for_channel_ready(ch)
            channels.append(ch)
        return channels

    return PSClient(
        connect(), wire_dtype=wire_dtype,
        push_channels=connect() if dedicated_push_channels else None,
    )


def stop_all(servers):
    for s in servers:
        s.stop(grace=None)


def batches(spec, n=256, batch_size=64, seed=3):
    dense, ids, labels = deepfm.synthetic_data(
        n=n, vocab_size=VOCAB, seed=seed
    )
    out = []
    for i in range(0, len(labels), batch_size):
        records = [
            (dense[j], ids[j], labels[j])
            for j in range(i, min(i + batch_size, len(labels)))
        ]
        out.append(spec.feed(records))
    return out


# -- bf16 wire ----------------------------------------------------------


def test_bf16_push_accumulates_f32_on_ps():
    """A bf16-wire gradient push must land on f32 master copies with
    only bf16 quantization error — never bf16 accumulation."""
    addrs, servicers, servers = start_ps(
        num_ps=1, opt_type="sgd", opt_args="learning_rate=1.0"
    )
    try:
        client = make_client(addrs, wire_dtype="bfloat16")
        rng = np.random.default_rng(0)
        dense = {"w": rng.standard_normal(64).astype(np.float32)}
        client.push_model(dense)
        grad = rng.standard_normal(64).astype(np.float32) * 1e-3
        accepted, _ = client.push_gradients({"w": grad})
        assert accepted
        param = servicers[0]._params.dense["w"]
        assert param.dtype == np.float32
        # lr=1.0: param == init - bf16(grad); bf16 has ~3 decimal
        # digits, grads are ~1e-3, so error <= ~1e-5 per element.
        np.testing.assert_allclose(
            param, dense["w"] - grad, atol=2e-5
        )
        # and the tiny update must not be lost entirely
        assert np.abs(param - dense["w"]).max() > 1e-5
    finally:
        stop_all(servers)


def test_pull_embedding_bf16_wire_matches_f32():
    addrs, servicers, servers = start_ps(num_ps=2)
    try:
        f32 = make_client(addrs)
        bf16 = make_client(addrs, wire_dtype="bfloat16")
        infos = [{"name": "t", "dim": 8, "initializer": "uniform"}]
        f32.push_model({"w": np.zeros(2, np.float32)},
                       embedding_infos=infos)
        ids = np.array([3, 11, 7, 3], np.int64)
        exact = f32.pull_embedding_vectors("t", ids)
        approx = bf16.pull_embedding_vectors("t", ids)
        assert exact.dtype == approx.dtype == np.float32
        assert exact.shape == approx.shape == (4, 8)
        # init rows are U(-0.05, 0.05): bf16 relative error ~2^-8
        np.testing.assert_allclose(exact, approx, atol=4e-4)
        assert np.array_equal(approx[0], approx[3])  # same id, same row
    finally:
        stop_all(servers)


def test_bad_wire_dtype_rejected():
    with pytest.raises(ValueError):
        PSClient([], wire_dtype="float8")


# -- empty-ids pull shape -----------------------------------------------


def test_empty_ids_pull_keeps_dim():
    addrs, servicers, servers = start_ps(num_ps=2)
    try:
        client = make_client(addrs)
        # explicit dim wins even before any infos are known
        assert client.pull_embedding_vectors("t", [], dim=6).shape == (0, 6)
        infos = [{"name": "t", "dim": 8, "initializer": "zeros"}]
        client.push_embedding_table_infos(infos)
        out = client.pull_embedding_vectors("t", [])
        assert out.shape == (0, 8)
        assert out.dtype == np.float32
    finally:
        stop_all(servers)


def test_parameters_empty_ids_pull_keeps_dim():
    params = Parameters()
    params.set_embedding_infos(
        [{"name": "t", "dim": 5, "initializer": "zeros"}]
    )
    out = params.pull_embedding_vectors("t", np.zeros((0,), np.int64))
    assert out.shape == (0, 5)


# -- pipelined push -----------------------------------------------------


def test_pipelined_stale_reject_drains_and_recovers():
    """Forced stale reject: the pipelined trainer surfaces
    GradientsRejected on a LATER minibatch, with the pipeline drained
    and dense params re-pulled, and the retry then converges with the
    server version."""
    spec = deepfm.model_spec(vocab_size=VOCAB, embedding_dim=4,
                             hidden=(16,))
    addrs, servicers, servers = start_ps(
        num_ps=1, opt_type="sgd", opt_args="learning_rate=0.01",
        use_async=False, grads_to_wait=1, sync_version_tolerance=0,
    )
    try:
        t1 = ParameterServerTrainer(
            spec, make_client(addrs), batch_size=64
        )
        t2 = ParameterServerTrainer(
            spec, make_client(addrs, dedicated_push_channels=True),
            batch_size=64, get_model_steps=100, async_push_window=1,
        )
        data = batches(spec)
        t2.train_minibatch(*data[0])       # push P1 in flight @v0
        t2.drain_pushes()                  # P1 accepted -> server v1
        t1.train_minibatch(*data[1])       # t1 pulls v1, push -> v2
        t2.train_minibatch(*data[2])       # P2 submitted @stale v0
        with pytest.raises(GradientsRejected):
            # draining P2 at the next submit surfaces the reject
            t2.train_minibatch(*data[3])
        assert not t2._push_inflight       # pipeline drained
        assert t2.version == servicers[0]._params.version  # re-pulled
        assert servicers[0].counters["push_rejected"] >= 1
        # the worker's retry path: same minibatch goes through now
        t2.train_minibatch(*data[3])
        t2.drain_pushes()
        assert servicers[0]._params.version == t2.version + 1
        t1.close()
        t2.close()
    finally:
        stop_all(servers)


def test_pipelined_matches_serialized_exactly_when_draining_each_pull():
    """window=1 with a dense pull every step drains the pipeline every
    step: the push merely moves to the next step's start, so the update
    sequence on the PS — and the converged dense params — are
    IDENTICAL to the serialized loop."""
    results = []
    for window in (0, 1):
        spec = deepfm.model_spec(vocab_size=VOCAB, embedding_dim=4,
                                 hidden=(16,))
        addrs, _servicers, servers = start_ps(
            num_ps=2, opt_type="sgd", opt_args="learning_rate=0.01",
            use_async=True,
        )
        try:
            trainer = ParameterServerTrainer(
                spec,
                make_client(addrs, dedicated_push_channels=window > 0),
                batch_size=64, get_model_steps=1, rng_seed=7,
                async_push_window=window,
            )
            data = batches(spec, n=320)
            losses = []
            for step in range(50):
                loss, _ = trainer.train_minibatch(
                    *data[step % len(data)]
                )
                losses.append(loss)
            trainer.drain_pushes()
            client = make_client(addrs)
            _, version, dense = client.pull_dense_parameters(-1)
            results.append((losses, version, dense))
            trainer.close()
        finally:
            stop_all(servers)
    (loss_a, ver_a, dense_a), (loss_b, ver_b, dense_b) = results
    assert ver_a == ver_b
    np.testing.assert_allclose(loss_a, loss_b, rtol=1e-6)
    assert set(dense_a) == set(dense_b)
    for name in dense_a:
        np.testing.assert_allclose(
            dense_a[name], dense_b[name], rtol=1e-6, atol=1e-7,
            err_msg=name,
        )


def test_full_pipeline_converges_close_to_serialized():
    """The full overlapped path (window 1 + prefetch + pull cadence 5 +
    bf16 wire) trains to the same place within bounded-staleness +
    quantization tolerance on a fixed-seed 50-step run."""
    results = []
    for pipelined in (False, True):
        spec = deepfm.model_spec(vocab_size=VOCAB, embedding_dim=4,
                                 hidden=(16,))
        addrs, _servicers, servers = start_ps(
            num_ps=2, opt_type="sgd", opt_args="learning_rate=0.01",
            use_async=True,
        )
        try:
            trainer = ParameterServerTrainer(
                spec,
                make_client(
                    addrs,
                    wire_dtype="bfloat16" if pipelined else None,
                    dedicated_push_channels=pipelined,
                ),
                batch_size=64, get_model_steps=5, rng_seed=7,
                async_push_window=1 if pipelined else 0,
            )
            data = batches(spec, n=320)
            losses = []
            for step in range(50):
                if pipelined:
                    trainer.prefetch_embeddings(
                        data[(step + 1) % len(data)][0]
                    )
                loss, _ = trainer.train_minibatch(
                    *data[step % len(data)]
                )
                losses.append(float(loss))
            trainer.drain_pushes()
            client = make_client(addrs)
            _, _, dense = client.pull_dense_parameters(-1)
            # Like with like: the same batches, ten passes apart (one
            # batch's loss is no yardstick for another's: at this
            # learning rate they differ by more than 50 steps move them).
            epoch = len(data)
            results.append((np.mean(losses[:epoch]),
                            np.mean(losses[-epoch:]), dense))
            if pipelined:
                hits = trainer.timing.counters().get("prefetch_hit", 0)
                assert hits > 0  # the prefetcher actually served pulls
            trainer.close()
        finally:
            stop_all(servers)
    (first_a, last_a, dense_a), (first_b, last_b, dense_b) = results
    assert last_a < first_a and last_b < first_b  # both trained
    for name in dense_a:
        np.testing.assert_allclose(
            dense_a[name], dense_b[name], atol=5e-2, err_msg=name,
        )


def test_atomic_sync_ignores_push_window():
    """Sync 2PC jobs stay strictly ordered: the window is overridden to
    0 and every push is the blocking prepare/commit, exactly as before
    the pipeline existed."""
    spec = deepfm.model_spec(vocab_size=VOCAB, embedding_dim=4,
                             hidden=(16,))
    addrs, servicers, servers = start_ps(
        num_ps=2, opt_type="sgd", opt_args="learning_rate=0.01",
        use_async=False, grads_to_wait=1,
    )
    try:
        trainer = ParameterServerTrainer(
            spec, make_client(addrs), batch_size=64,
            atomic_sync=True, async_push_window=4,
        )
        assert trainer._push_window == 0
        before = [s._params.version for s in servicers]
        trainer.train_minibatch(*batches(spec)[0])
        assert not trainer._push_inflight
        # blocking 2PC: both shards applied before train_minibatch
        # returned
        for s, v in zip(servicers, before):
            assert s._params.version == v + 1
        trainer.close()
    finally:
        stop_all(servers)


def test_prefetch_rows_match_direct_pull():
    """Two identical trainers on two identical PS setups (table init is
    seeded by table name, so separate instances start bit-identical):
    the prefetched step must produce exactly the direct step's loss."""
    spec = deepfm.model_spec(vocab_size=VOCAB, embedding_dim=4,
                             hidden=(16,))
    losses = []
    for use_prefetch in (False, True):
        addrs, _servicers, servers = start_ps(num_ps=2)
        try:
            trainer = ParameterServerTrainer(
                spec,
                make_client(addrs, dedicated_push_channels=use_prefetch),
                batch_size=64, rng_seed=5,
                # prefetch is a pipelined-mode feature; outside it the
                # call must be a no-op (ordering guarantee)
                async_push_window=1 if use_prefetch else 0,
            )
            feats, labels = batches(spec)[0]
            trainer.prefetch_embeddings(feats)
            counters = trainer.timing.counters()
            if not use_prefetch:
                assert not trainer._prefetched  # no-op outside pipeline
            loss, _ = trainer.train_minibatch(feats, labels)
            losses.append(loss)
            if use_prefetch:
                counters = trainer.timing.counters()
                assert counters.get("prefetch_hit") == 2  # both tables
                assert not counters.get("prefetch_miss")
            trainer.close()
        finally:
            stop_all(servers)
    np.testing.assert_allclose(losses[1], losses[0], rtol=1e-6)
