"""The harness's own pieces: seeded data, the command line it builds,
what it reads from a job's log, and the references at tiny sizes."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.lib import datagen, job, manifest, runner

ROOT = os.path.dirname(manifest.BENCH_DIR)

LOG = """\
[2026-09-27 02:00:00,000] [INFO] [master] [m:1:x] launched worker 0
[2026-09-27 02:00:05,250] [INFO] [worker-0] [__main__:305:main] worker device: platform=tpu device_kind=TPU_v5_lite local_devices=1 global_devices=1 device_ids=0 visible_chips=all flash=tpu fused_gn=tpu peak_bytes_in_use=0 compile_cache=/x/.jax_cache
[2026-09-27 02:01:00,000] [INFO] [worker-0] [w:1:x] step 8 loss 6.907755 (version 8)
[2026-09-27 02:01:30,500] [INFO] [master] [m:2:x] worker 0 exited code=-9 event=preempted -> Deleted relaunch=True
[2026-09-27 02:01:30,750] [INFO] [master] [m:1:x] launched worker 1
[2026-09-27 02:01:40,000] [INFO] [worker-1] [__main__:305:main] worker device: platform=tpu device_kind=TPU_v5_lite local_devices=1 global_devices=1 device_ids=0 visible_chips=all flash=tpu fused_gn=tpu peak_bytes_in_use=0 compile_cache=/x/.jax_cache
[2026-09-27 02:03:00,000] [INFO] [worker-1] [elasticdl_tpu.worker.worker:212:report] timing[loss_sync]: total=6.800s count=5 mean=1.3600s
[2026-09-27 02:03:00,000] [INFO] [worker-1] [elasticdl_tpu.worker.worker:212:report] timing[task_process]: total=9.000s count=4 mean=2.2500s
[2026-09-27 02:03:00,001] [INFO] [worker-1] [__main__:330:main] worker end-of-run: steps=17 platform=tpu device_kind=TPU_v5_lite local_devices=1 global_devices=1 device_ids=0 visible_chips=all flash=tpu fused_gn=tpu peak_bytes_in_use=6337300000
"""


def test_parse_log_reads_devices_exits_launches_and_losses():
    log = job.parse_log(LOG)
    assert log["devices"][0]["platform"] == "tpu"
    assert log["device_at"][1] - log["device_at"][0] == pytest.approx(94.75)
    assert log["exits"] == [{"worker": 0, "code": "-9", "relaunch": True,
                             "at": log["exits"][0]["at"]}]
    assert log["launched"][1] - log["exits"][0]["at"] == pytest.approx(0.25)
    assert log["losses"] == [6.907755] and log["losses_finite"]
    assert log["ends"] == {1: {"steps": 17, "timing": {
        "loss_sync": 6.8, "task_process": 9.0}}}


def test_loss_sync_per_step_is_the_programs_total_over_its_steps():
    import types

    read = manifest.Manifest(ROOT).reader("loop.loss_sync_ms_per_step")
    run = types.SimpleNamespace(log=job.parse_log(LOG))
    assert read(run) == pytest.approx(400.0)
    run.log["ends"][1]["timing"].pop("loss_sync")
    assert read(run) is None


def test_bad_lines_are_what_a_measured_run_may_not_hold():
    assert job.BAD_LINES.search("x minibatch failed (attempt 1): boom")
    assert job.BAD_LINES.search("attention fallback: no kernel")
    assert not job.BAD_LINES.search("task 3 failed (worker 0 died), retry")


def test_flags_name_only_what_config_and_traffic_name():
    load = lambda *parts: json.load(open(os.path.join(manifest.BENCH_DIR,
                                                      *parts)))
    flags = runner.build_flags(load("configs", "olmo1b.json"),
                               load("traffic", "tokens-b32-dp4.json"), "/d")
    pairs = dict(zip(flags[::2], flags[1::2]))
    assert pairs["--model_zoo"] == "benchmark.lib.bench_zoo"
    assert pairs["--model_params"].startswith(
        "zoo=transformer;dim=2048;num_heads=16;num_layers=7;")
    assert pairs["--batch_size"] == "32" and pairs["--data_origin"] == "/d"
    assert pairs["--distribution_strategy"] == "collective"
    for default in ("--fused_steps", "--device_prefetch", "--shuffle",
                    "--use_bf16"):
        assert default not in pairs


def test_same_seed_same_bytes_other_seed_same_sizes(tmp_path):
    params = {"sequences": 16, "seq_len": 32, "vocab_size": 1000}
    big = 2 ** 31 + 12345
    a = datagen.ensure(str(tmp_path / "a"), "tokens_zipf", params, big)
    b = datagen.ensure(str(tmp_path / "b"), "tokens_zipf", params, big)
    read = lambda origin: np.fromfile(origin.split(":")[1], np.uint16)
    assert (read(a) == read(b)).all() and read(a).max() < 1000
    c = datagen.ensure(str(tmp_path / "a"), "tokens_zipf", params, 7)
    assert read(c).shape == read(b).shape and (read(c) != read(b)).any()
    assert a.endswith(":32:uint16")
    # the first seed's data made room for the second's
    assert len(os.listdir(tmp_path / "a")) == 1
    with pytest.raises(manifest.ManifestError, match="tokens_zipf"):
        datagen.ensure(str(tmp_path), "no_such_generator", {}, 1)


def test_product_loss_agrees_with_the_plain_reference_at_tiny_size():
    done = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "lib",
                                      "compare.py"),
         "--config-file", os.path.join(manifest.BENCH_DIR, "configs",
                                       "olmo1b.json"),
         "--seed", "11", "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    row = json.loads(done.stdout.strip().splitlines()[-1])
    assert row["ok"] and row["rel_diff"] <= row["tolerance"], row


def test_without_an_accelerator_there_is_no_result():
    done = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", "olmo1b.seq2048", "--seed", "1", "--seconds", "5",
         "--trace", "0"], env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=60)
    assert done.returncode != 0 and done.stdout.strip() == ""


def _traced_main(monkeypatch, spawned):
    """``runner.main`` on a traced run whose job, window and trace are
    stubbed away, so that the reference comparison is the first thing
    that really happens; ``spawned(argv, **kw)`` stands for the child."""
    for name in ("launch", "measure", "finish", "throughput"):
        monkeypatch.setattr(runner.Run, name, lambda self, *a: None)
    monkeypatch.setattr(runner.Run, "make_data", lambda self: "origin")
    monkeypatch.setattr(runner, "reduce_trace", lambda run: None)
    monkeypatch.setattr(runner.subprocess, "run", spawned)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    return runner.main(["--workload", "trinity-mini.seq16384", "--seed",
                        "3411220957", "--seconds", "20", "--trace", "1"])


def _expires(argv, timeout=None, **kw):
    raise subprocess.TimeoutExpired(argv, timeout)


def _cannot_start(argv, **kw):
    raise OSError(12, "Cannot allocate memory")


def _ends(code, stdout, stderr="Traceback: the child's own"):
    return lambda argv, **kw: subprocess.CompletedProcess(
        argv, code, stdout=stdout, stderr=stderr)


@pytest.mark.parametrize("spawned, says", [
    (_expires, "cap of %d s" % runner.COMPARE_CAP_S),
    (_cannot_start, "did not start: [Errno 12]"),
    (_ends(1, ""), "failed (exit 1): Traceback: the child's own"),
    (_ends(-9, '{"ok": true}'), "failed (exit -9)"),
    (_ends(0, "no line of JSON"), "failed (list index out of range)"),
    (_ends(0, "{half a line"), "the reference comparison failed ("),
])
def test_whatever_the_comparison_meets_is_a_failed_line(
        monkeypatch, capsys, spawned, says):
    """A traced run ends in a result or in a ``FAILED`` line: exit 1, no
    result on stdout, and never a traceback of this process (PR 59 and
    PR 64 were refused over a ``subprocess.TimeoutExpired`` that nothing
    caught)."""
    assert _traced_main(monkeypatch, spawned) == 1
    out, err = capsys.readouterr()
    assert out == ""
    failed = [l for l in err.splitlines() if "FAILED" in l]
    assert len(failed) == 1 and says in failed[0], err
    assert failed[0].startswith("[benchmark] FAILED trinity-mini.seq16384: ")
    assert "the reference comparison" in failed[0]


def test_the_comparison_gets_the_jobs_cache_and_its_seconds_are_kept(
        monkeypatch, capsys, tmp_path):
    seen = {}

    def spawned(argv, env=None, timeout=None, **kw):
        seen.update(argv=argv, env=env, timeout=timeout)
        return subprocess.CompletedProcess(argv, 0, stderr="", stdout=(
            "a warning\n" + json.dumps({"ok": True, "rel_diff": 1e-4})))

    monkeypatch.setattr(runner.subprocess, "run", spawned)
    monkeypatch.setenv("ELASTICDL_FLASH", "off")
    cell = manifest.Manifest(ROOT).cell("trinity-mini.seq16384")
    run = runner.Run(ROOT, cell, 7, 20.0, True, False)
    run.cache_dir = str(tmp_path / "cache")
    runner.compare_reference(run)
    assert run.reference == {"ok": True, "rel_diff": 1e-4}
    assert seen["timeout"] == runner.COMPARE_CAP_S >= 600
    assert seen["env"]["JAX_COMPILATION_CACHE_DIR"] == run.cache_dir
    assert "ELASTICDL_FLASH" not in seen["env"]    # kernels on their defaults
    assert seen["argv"][-4:] == ["--config-file", cell["config_file"],
                                 "--seed", "7"]
    assert 0 <= run.times["compare_s"] < 5
    assert "[benchmark] reference comparison: %.1f s of %d" % (
        run.times["compare_s"], runner.COMPARE_CAP_S) in capsys.readouterr().err


def test_every_number_held_to_a_limit_is_stated_beside_it():
    cell = manifest.Manifest(ROOT).cell("olmo1b.seq2048")
    run = runner.Run(ROOT, cell, 7, 20.0, True, False)
    run.failed, run.compiles_in_window = 0, 2
    run.problems = ["2 compile(s) inside the window"]
    assert run.compared() == {
        "tasks_failed": {"value": 0, "limit": 0},
        "compiles_in_window": {"value": 2, "limit": 0},
        "problems": {"value": 1, "limit": 0}}
    run.reference = {"rel_diff": 1.05e-4, "tolerance": 3e-3, "ok": True}
    assert run.compared()["loss_rel_diff"] == {"value": 1.05e-4,
                                               "limit": 3e-3}
