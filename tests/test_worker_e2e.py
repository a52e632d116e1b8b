"""End-to-end: master-dispatched shards train MNIST via a real worker.

The integration harness pattern from the reference
(elasticdl/python/tests/test_utils.py:330-472): real TaskManager, real gRPC
master service, real Worker — one process, no cluster.
"""

import numpy as np
import pytest

from elasticdl_tpu.data.reader import ArrayDataReader
from elasticdl_tpu.models import mnist
from elasticdl_tpu.proto import elastic_pb2 as pb
from elasticdl_tpu.utils import metrics
from elasticdl_tpu.worker.collective_trainer import CollectiveTrainer
from elasticdl_tpu.worker.worker import Worker
from tests.test_utils import create_master, create_master_client


@pytest.fixture(scope="module")
def dataset():
    return mnist.synthetic_data(n=256, seed=1)


def run_job(dataset, num_epochs=2, evaluation_steps=0):
    xs, ys = dataset
    reader = ArrayDataReader((xs, ys), records_per_shard=64)
    master = create_master(
        training_shards=reader.create_shards(),
        evaluation_shards=reader.create_shards() if evaluation_steps else None,
        records_per_task=64,
        num_epochs=num_epochs,
        evaluation_steps=evaluation_steps,
        metrics_factory=(
            (lambda: {"accuracy": metrics.Accuracy()})
            if evaluation_steps else None
        ),
    )
    try:
        mc = create_master_client(master)
        spec = mnist.model_spec(learning_rate=5e-3)
        trainer = CollectiveTrainer(
            spec, batch_size=32, master_client=mc,
            report_version_steps=2 if evaluation_steps else 0,
        )
        worker = Worker(mc, reader, spec, trainer, batch_size=32)
        worker.run()
        assert master.task_manager.finished()
        return master, trainer
    finally:
        master.stop()


def test_training_completes_all_tasks(dataset):
    master, trainer = run_job(dataset)
    counts = master.task_manager.counts()
    assert counts["completed"][pb.TRAINING] == 8  # 4 shards x 2 epochs
    assert counts["failed"][pb.TRAINING] == 0
    assert trainer.version == 16  # 2 batches per task


def test_training_learns(dataset):
    xs, ys = dataset
    _, trainer = run_job(dataset, num_epochs=4)
    correct, total = 0, 0
    for i in range(0, 128, 32):
        outputs, labels = trainer.evaluate_minibatch(
            xs[i : i + 32], ys[i : i + 32]
        )
        correct += (np.argmax(outputs, -1) == labels).sum()
        total += len(labels)
    accuracy = correct / total
    assert accuracy > 0.5, f"model did not learn (acc={accuracy})"


def test_permanent_task_failure_fails_the_job(dataset):
    # A job that "finishes" after dropping tasks must exit nonzero —
    # permanently-failed tasks are unprocessed data, not success.
    xs, ys = dataset
    reader = ArrayDataReader((xs, ys), records_per_shard=64)
    master = create_master(
        training_shards=reader.create_shards(), records_per_task=64,
    )
    try:
        tm = master.task_manager
        while True:
            task = tm.get(worker_id=0)
            if task is None:
                break
            tm.report(task.id, success=False, err_message="boom")
        assert sum(tm.counts()["failed"].values()) > 0
        master._poll_secs = 0.05
        assert master.run() == 1
    finally:
        master.stop()


def test_evaluation_service_runs(dataset):
    master, _ = run_job(dataset, num_epochs=2, evaluation_steps=4)
    assert master.evaluation_service.history, "no evaluation completed"


def test_worker_death_tasks_recovered(dataset):
    """Kill a worker mid-job; a second worker finishes everything."""
    xs, ys = dataset
    reader = ArrayDataReader((xs, ys), records_per_shard=64)
    master = create_master(
        training_shards=reader.create_shards(), records_per_task=64
    )
    try:
        spec = mnist.model_spec()

        mc1 = create_master_client(master, worker_id=1)
        # Worker 1 grabs a task and "dies" (never reports).
        t = mc1.get_task()
        assert t.id > 0
        master.task_manager.recover_tasks(1)

        mc2 = create_master_client(master, worker_id=2)
        trainer = CollectiveTrainer(spec, batch_size=32)
        worker = Worker(mc2, reader, spec, trainer, batch_size=32)
        worker.run()
        counts = master.task_manager.counts()
        assert master.task_manager.finished()
        assert counts["completed"][pb.TRAINING] == 4
    finally:
        master.stop()


@pytest.mark.slow
def test_predict_job_writes_outputs(tmp_path):
    """Train -> checkpoint -> predict: the managed predict job restores
    the checkpoint and writes one npz of predictions per worker."""
    import os
    import subprocess
    import sys

    import numpy as np

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    ckpt = str(tmp_path / "ckpt")
    base = [
        sys.executable, "-m", "elasticdl_tpu.master.main",
        "--model_zoo", "mnist", "--batch_size", "32",
        "--num_workers", "1", "--num_minibatches_per_task", "4",
        "--checkpoint_dir", ckpt,
    ]
    train = subprocess.run(
        base + ["--data_origin", "synthetic_mnist:256",
                "--checkpoint_steps", "4"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert train.returncode == 0, train.stderr[-2000:]

    outputs = str(tmp_path / "preds")
    predict = subprocess.run(
        base + ["--job_type", "predict",
                "--data_origin", "synthetic_mnist:96",
                "--prediction_outputs", outputs],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert predict.returncode == 0, predict.stderr[-2000:]
    files = [f for f in os.listdir(outputs) if f.endswith(".npz")]
    assert files, "no prediction outputs written"
    total = 0
    for f in files:
        with np.load(os.path.join(outputs, f)) as z:
            preds = z["predictions"]
            assert preds.shape[-1] == 10  # mnist logits
            total += preds.shape[0]
    assert total == 96


@pytest.mark.slow
def test_evaluate_job_reports_metrics(tmp_path):
    """Train -> checkpoint -> standalone evaluate job: metrics are
    aggregated and logged by the master's evaluation service."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    ckpt = str(tmp_path / "ckpt")
    base = [
        sys.executable, "-m", "elasticdl_tpu.master.main",
        "--model_zoo", "mnist", "--batch_size", "32",
        "--num_workers", "1", "--num_minibatches_per_task", "4",
        "--checkpoint_dir", ckpt,
    ]
    train = subprocess.run(
        base + ["--data_origin", "synthetic_mnist:256",
                "--checkpoint_steps", "4"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert train.returncode == 0, train.stderr[-2000:]
    ev = subprocess.run(
        base + ["--job_type", "evaluate",
                "--data_origin", "synthetic_mnist:96"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert ev.returncode == 0, ev.stderr[-2000:]
    text = ev.stdout + ev.stderr
    assert "job finished" in text
    assert "accuracy" in text, text[-2000:]


@pytest.mark.slow
def test_managed_collective_two_workers_form_world():
    """Managed elastic AllReduce (SURVEY §2.12): a two-worker managed
    job with --distribution_strategy collective forms a REAL
    cross-process world through the master-hosted coordination plane —
    both worker processes join one 2-device world, train global
    batches in lockstep, survive the end-of-data membership change
    (the first worker to drain the queue leaves; the other re-forms
    and finishes), and the job completes with zero lost tasks."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["ELASTICDL_COLLECTIVE_HEARTBEAT"] = "5"
    proc = subprocess.run(
        [
            sys.executable, "-m", "elasticdl_tpu.master.main",
            "--model_zoo", "mnist", "--batch_size", "16",
            "--num_workers", "2", "--num_minibatches_per_task", "4",
            "--data_origin", "synthetic_mnist:1024",
            "--distribution_strategy", "collective",
        ],
        capture_output=True, text=True, env=env, timeout=420,
    )
    text = proc.stdout + proc.stderr
    assert proc.returncode == 0, text[-4000:]
    assert "job finished" in text
    assert "'failed': {0: 0" in text, text[-2000:]
    # Both workers client-only joined the same 2-process world.
    assert "collective world joined (client-only): rank 0 / 2" in text
    assert "collective world joined (client-only): rank 1 / 2" in text


@pytest.mark.slow
def test_graceful_preemption_checkpoints_before_exit(tmp_path):
    """SIGTERM mid-run (the preemptible-VM grace signal): the worker
    finishes its minibatch, saves a checkpoint (checkpoint_steps=0 —
    no periodic saves, so any checkpoint on disk came from the
    graceful path), exits 143, the manager classifies it as a
    preemption and relaunches, and the job finishes with zero lost
    tasks."""
    import os
    import signal as _signal
    import subprocess
    import sys
    import time

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    ckpt = str(tmp_path / "ckpt")
    job = "graceful-preempt-drill"
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "elasticdl_tpu.master.main",
            "--job_name", job,
            "--model_zoo", "mnist", "--batch_size", "32",
            "--num_workers", "1", "--num_minibatches_per_task", "4",
            "--data_origin", "synthetic_mnist:4096", "--num_epochs", "2",
            "--checkpoint_dir", ckpt, "--checkpoint_steps", "0",
        ],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        deadline = time.time() + 120
        wpid = None
        while time.time() < deadline and wpid is None:
            out = subprocess.run(
                ["pgrep", "-f",
                 "elasticdl_tpu.worker.main.*%s" % job],
                capture_output=True, text=True,
            )
            pids = [int(p) for p in out.stdout.split()]
            if pids:
                wpid = pids[0]
            else:
                time.sleep(0.5)
        assert wpid, "worker never appeared"
        time.sleep(20)  # let it get into training
        os.kill(wpid, _signal.SIGTERM)
        out, _ = proc.communicate(timeout=300)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 0, out[-4000:]
    assert "job finished" in out
    assert "'failed': {0: 0" in out, out[-2000:]
    assert "graceful preemption: saving checkpoint" in out
    # exit 143 classified as preemption -> relaunch, not failure
    assert "exited code=143 event=preempted" in out, out[-3000:]
    # With checkpoint_steps=0 the ONLY possible checkpoint is the
    # graceful-preemption one.
    assert os.path.isdir(ckpt) and any(
        name.startswith("version-") for name in os.listdir(ckpt)
    ), os.listdir(ckpt) if os.path.isdir(ckpt) else "no ckpt dir"


@pytest.mark.slow
def test_managed_collective_lora_finetune():
    """Elastic fine-tuning: the LoRA zoo entry under a managed
    2-worker collective world — multi_transform masking, the
    {base, lora} param tree, and snapshot_to_host all ride the
    cross-process global-batch path; zero lost tasks."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["ELASTICDL_COLLECTIVE_HEARTBEAT"] = "5"
    proc = subprocess.run(
        [
            sys.executable, "-m", "elasticdl_tpu.master.main",
            "--model_zoo", "lora",
            "--model_params",
            "rank=4;vocab_size=128;dim=32;num_heads=4;num_layers=2;"
            "seq_len=16;dtype=float32",
            "--data_origin", "synthetic_lm:512:16:128",
            "--batch_size", "8", "--num_workers", "2",
            "--num_minibatches_per_task", "4",
            "--distribution_strategy", "collective",
        ],
        capture_output=True, text=True, env=env, timeout=420,
    )
    text = proc.stdout + proc.stderr
    assert proc.returncode == 0, text[-4000:]
    assert "job finished" in text
    assert "'failed': {0: 0" in text, text[-2000:]
    assert "collective world joined (client-only): rank 0 / 2" in text
    assert "LoRA r=4" in text
