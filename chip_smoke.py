#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py            # every leg, one after another
    python3 chip_smoke.py --legs lm_local,lm_local   # chosen legs

Drives the managed training job through the entry point a user calls
(``python -m elasticdl_tpu.master.main`` launching its worker
subprocesses) at the full width of the flagship LM — dim 1024, 24 layers,
T=2048, vocab 32768, batch 8 per chip, random weights from a seed,
synthetic tokens from a seed — then the Pallas kernels against their
references, the one-process-per-chip-set launch, and the parameter-server
path.  Each leg is a fresh process tree; this parent never imports jax
(a parent that touched JAX would hold the chip its children need), reads
only exit codes and logs, and kills every process group it started.

A leg fails if the worker's stated platform is not ``tpu``, the master's
exit code is non-zero, a worker exited non-zero or was relaunched, the
log holds a swallowed ``minibatch failed`` / ``training task ... failed``
or (LM legs) an ``attention fallback:`` line, a loss is not finite, or
fewer steps ran than were asked for.  The kernels' one switch
(``ELASTICDL_FLASH``, ``elasticdl_tpu/ops/mode.py``) is neither set nor
inherited.

Exit 0 and, as the last line of stdout, one JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
only when JAX found a TPU and every leg passed.  Otherwise a non-zero
exit and no JSON on stdout: without an accelerator it stops within
seconds, naming the platform it found.  What each leg observed goes to
stderr and to ``chiprun_out/smoke/summary.json``.
"""

import datetime
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(HERE, "chiprun_out", "smoke")

LM_PARAMS = ("dim=1024;num_heads=16;num_layers=24;seq_len=2048;"
             "vocab_size=32768;remat=true")
LM_BATCH_PER_CHIP = 8
LM_STEPS = 20          # >= 16 after the step that compiles
LM_MINIBATCHES_PER_TASK = 4

KERNEL_SWITCH = "ELASTICDL_FLASH"

_STAMP = re.compile(r"^\[(\d{4}-\d\d-\d\d \d\d:\d\d:\d\d),(\d{3})\]")
_DEVICE = re.compile(r"\[worker-(\d+)\].*worker device: (.*)$")
_END = re.compile(r"\[worker-(\d+)\].*worker end-of-run: steps=(\d+) (.*)$")
_STEP = re.compile(r"step (\d+) loss (\S+)")
_EXIT = re.compile(r"worker (\d+) exited code=(\S+) .* relaunch=(\w+)")


class LegFailed(Exception):
    pass


def _say(message):
    print("[chip_smoke] %s" % message, file=sys.stderr, flush=True)


def _clean_ignored_artifacts():
    """Start from what git would commit.  The chip tool copies the tree
    as it is on disk, so a native library built for another CPU
    (``-march=native``), another machine's compile cache and stale
    bytecode would ride along.  A cache directory placed from outside
    (``JAX_COMPILATION_CACHE_DIR``) is not ours and is left alone."""
    native = os.path.join(HERE, "elasticdl_tpu", "native")
    for name in os.listdir(native):
        if ".so" in name:
            os.remove(os.path.join(native, name))
    for name in os.listdir(HERE):
        if name.startswith(".jax_cache"):
            shutil.rmtree(os.path.join(HERE, name), ignore_errors=True)
    for root, dirs, _ in os.walk(HERE):
        if "__pycache__" in dirs:
            shutil.rmtree(os.path.join(root, "__pycache__"),
                          ignore_errors=True)
            dirs.remove("__pycache__")


def _child_env():
    env = {k: v for k, v in os.environ.items() if k != KERNEL_SWITCH}
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _run(name, argv, timeout, abort_on=None):
    """One leg's process tree: own session, output to a log file, killed
    as a group whatever happens.  ``abort_on``: a compiled pattern that
    fails the leg the moment the log holds it, instead of waiting out a
    retry loop.  Returns (exit code, log text, secs)."""
    os.makedirs(LOG_DIR, exist_ok=True)
    log_path = os.path.join(LOG_DIR, name + ".log")
    start = time.monotonic()
    code, text = None, ""
    with open(log_path, "w") as log, open(log_path,
                                          errors="replace") as tail:
        proc = subprocess.Popen(
            argv, cwd=HERE, env=_child_env(), stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
        try:
            while time.monotonic() - start < timeout:
                try:
                    code = proc.wait(timeout=2)
                except subprocess.TimeoutExpired:
                    pass
                text += tail.read()
                hit = abort_on and abort_on.search(text)
                if hit:
                    line = text[text.rfind("\n", 0, hit.start()) + 1:]
                    raise LegFailed("log holds %r (log: %s)" % (
                        line.split("\n", 1)[0][-300:], log_path))
                if code is not None:
                    break
        finally:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    if code is None:
        raise LegFailed("timed out after %ds (log: %s)" % (timeout, log_path))
    return code, text, time.monotonic() - start


def _fields(report):
    return dict(item.split("=", 1) for item in report.split()
                if "=" in item)


def _seconds(line):
    m = _STAMP.match(line)
    if not m:
        return None
    whole = datetime.datetime.strptime(m.group(1), "%Y-%m-%d %H:%M:%S")
    return whole.timestamp() + int(m.group(2)) / 1000.0


def _master_argv(*flags):
    return [sys.executable, "-m", "elasticdl_tpu.master.main", *flags]


def check_job(name, argv, timeout, num_workers, want_steps,
              forbid=()):
    """Run a managed job and hold its log to the leg contract.  Returns
    the observations the summary prints."""
    # worker/worker.py logs these and retries; a smoke must not.
    swallowed = re.compile("|".join(
        ("minibatch failed", r"training task \d+ failed") + tuple(forbid)))
    code, text, secs = _run(name, argv, timeout, abort_on=swallowed)
    lines = text.splitlines()
    if code != 0:
        raise LegFailed("master exit code %d; log tail:\n%s"
                        % (code, "\n".join(lines[-25:])))
    devices, ends, exits = {}, {}, {}
    first_step_at, device_at, step_times, losses = None, None, [], []
    for line in lines:
        m = _DEVICE.search(line)
        if m:
            devices[int(m.group(1))] = _fields(m.group(2))
            device_at = device_at or _seconds(line)
            continue
        m = _END.search(line)
        if m:
            ends[int(m.group(1))] = (int(m.group(2)), _fields(m.group(3)))
            continue
        m = _EXIT.search(line)
        if m:
            exits[int(m.group(1))] = (m.group(2), m.group(3))
            continue
        m = _STEP.search(line)
        if m:
            losses.append(float(m.group(2)))
            step_times.append(_seconds(line))
            first_step_at = first_step_at or step_times[-1]
    if len(devices) != num_workers or len(ends) != num_workers:
        raise LegFailed("%d worker(s) stated a device and %d reported an "
                        "end of run; expected %d"
                        % (len(devices), len(ends), num_workers))
    for wid, report in list(devices.items()) + [
            (w, r) for w, (_, r) in ends.items()]:
        if report.get("platform") != "tpu":
            raise LegFailed("worker %d states platform=%s, not tpu"
                            % (wid, report.get("platform")))
    if sorted(exits) != sorted(devices):
        raise LegFailed("workers %s were launched but the master saw "
                        "exits of %s (a relaunch or a lost worker)"
                        % (sorted(devices), sorted(exits)))
    for wid, (exit_code, relaunch) in exits.items():
        if exit_code != "0" or relaunch != "False":
            raise LegFailed("worker %d exited code=%s relaunch=%s"
                            % (wid, exit_code, relaunch))
    steps = sum(n for n, _ in ends.values())
    if steps < want_steps:
        raise LegFailed("%d steps ran, %d were asked for"
                        % (steps, want_steps))
    if not losses or not all(math.isfinite(v) for v in losses):
        raise LegFailed("losses missing or not finite: %s" % losses[-5:])
    gaps = sorted(b - a for a, b in zip(step_times[1:], step_times[2:]))
    return {
        "secs": round(secs, 1),
        "steps": steps,
        "first_loss": losses[0],
        "last_loss": losses[-1],
        "worker_start_to_first_step_secs": round(
            first_step_at - device_at, 1),
        "median_step_secs": round(gaps[len(gaps) // 2], 3) if gaps else None,
        "chips": {w: r.get("visible_chips") for w, r in devices.items()},
        "device_ids": {w: r.get("device_ids") for w, r in devices.items()},
        "peak_bytes_in_use": {
            w: r.get("peak_bytes_in_use") for w, (_, r) in ends.items()},
        "modes": {k: devices[min(devices)].get(k)
                  for k in ("flash", "fused_gn")},
    }


def _lm_leg(name, strategy, chips):
    batch = LM_BATCH_PER_CHIP * chips
    out = check_job(
        name,
        _master_argv(
            "--model_zoo", "transformer", "--model_params", LM_PARAMS,
            "--data_origin",
            "synthetic_lm:%d:2048:32768" % (batch * LM_STEPS),
            "--batch_size", str(batch),
            "--num_minibatches_per_task", str(LM_MINIBATCHES_PER_TASK),
            "--num_epochs", "1", "--num_workers", "1",
            "--distribution_strategy", strategy,
            "--log_loss_steps", "1",
        ),
        timeout=600, num_workers=1, want_steps=LM_STEPS,
        forbid=("attention fallback:",),
    )
    if out["modes"] != {"flash": "tpu", "fused_gn": "tpu"}:
        raise LegFailed("kernel modes resolved to %s" % out["modes"])
    if strategy == "collective":
        peaks = [int(v) for v in
                 out["peak_bytes_in_use"][0].split(",")]
        if len(peaks) != chips or not all(peaks):
            raise LegFailed("batch not on every chip: peak bytes %s"
                            % peaks)
    return out


def leg_lm_local(device):
    return _lm_leg("lm_local", "local", 1)


def leg_lm_collective(device):
    return _lm_leg("lm_collective", "collective", device["count"])


def leg_kernels(device):
    code, text, secs = _run(
        "kernels", [sys.executable, os.path.join(HERE, "chip_check.py")],
        timeout=900)
    rows = [json.loads(line) for line in text.splitlines()
            if line.startswith("{")]
    summary = rows[-1] if rows else {}
    if code != 0 or not summary.get("ok"):
        raise LegFailed("exit %d, failed cases %s; log tail:\n%s" % (
            code, summary.get("failed"),
            "\n".join(text.splitlines()[-15:])))
    if summary["device"]["platform"] != "tpu":
        raise LegFailed("ran on %s" % summary["device"]["platform"])
    return {"secs": round(secs, 1), "cases": summary["cases"]}


def leg_workers_per_chip(device):
    chips = device["count"]
    mnist = ("--model_zoo", "mnist", "--batch_size", "32",
             "--num_minibatches_per_task", "4", "--num_epochs", "1",
             "--distribution_strategy", "local", "--log_loss_steps", "4")
    if chips == 1:
        # Two workers cannot own one chip: refused at start-up, not
        # discovered by the relaunch loop.
        code, text, secs = _run(
            "workers_per_chip",
            _master_argv("--data_origin", "synthetic_mnist:512",
                         "--num_workers", "2", *mnist),
            timeout=120)
        if code == 0 or "cannot give 2 workers disjoint chips" not in text:
            raise LegFailed("2 workers on 1 chip were not refused "
                            "(exit %d)" % code)
        if "launched worker" in text:
            raise LegFailed("a worker was launched before the refusal")
        return {"secs": round(secs, 1), "refused": "2 workers, 1 chip"}
    # 16 tasks per worker: every worker is still fetching when the
    # slowest one comes up, so each trains at least one.
    out = check_job(
        "workers_per_chip",
        _master_argv("--data_origin",
                     "synthetic_mnist:%d" % (32 * 4 * 16 * chips),
                     "--num_workers", str(chips), *mnist),
        timeout=600, num_workers=chips, want_steps=4 * 16 * chips)
    if len(set(out["chips"].values())) != chips or (
            "all" in out["chips"].values()):
        raise LegFailed("workers do not each own a different chip: %s"
                        % out["chips"])
    return out


def leg_ps(device):
    native = os.path.join(HERE, "elasticdl_tpu", "native",
                          "libedlkernels.so")
    if os.path.exists(native):
        raise LegFailed("%s exists before the leg built it" % native)
    out = check_job(
        "ps",
        _master_argv(
            "--model_zoo", "deepfm", "--data_origin", "synthetic_ctr:512",
            "--batch_size", "32", "--num_minibatches_per_task", "4",
            "--num_epochs", "1", "--num_workers", "1",
            "--distribution_strategy", "ps", "--num_ps", "2",
            "--log_loss_steps", "4",
        ),
        timeout=600, num_workers=1, want_steps=16)
    if not os.path.exists(native):
        raise LegFailed("the PS shards did not build %s here" % native)
    return out


LEGS = {
    "lm_local": leg_lm_local,
    "lm_collective": leg_lm_collective,
    "kernels": leg_kernels,
    "workers_per_chip": leg_workers_per_chip,
    "ps": leg_ps,
}


def probe_device():
    """Ask a child that exits what JAX sees; the chip is free again
    before the first leg starts."""
    code, text, _ = _run(
        "device",
        [sys.executable, "-c",
         "import json; from elasticdl_tpu.utils.device import "
         "device_report; print('DEVICE ' + json.dumps(device_report()))"],
        timeout=300)
    report = next((json.loads(line[7:]) for line in text.splitlines()
                   if line.startswith("DEVICE ")), None)
    if code != 0 or report is None:
        raise LegFailed("device probe exit %d; log tail:\n%s"
                        % (code, "\n".join(text.splitlines()[-15:])))
    if report["platform"] != "tpu":
        raise LegFailed(
            "JAX found no accelerator: platform is %r (%s, %d device(s))"
            % (report["platform"], report["device_kind"],
               report["global_devices"]))
    return {"platform": report["platform"],
            "kind": report["device_kind"],
            "count": report["global_devices"]}


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    names = list(LEGS)
    if "--legs" in argv:
        names = argv[argv.index("--legs") + 1].split(",")
    if not os.path.isdir(os.path.join(HERE, "elasticdl_tpu")):
        _say("no elasticdl_tpu package beside %s: nothing to smoke"
             % __file__)
        return 2
    try:
        device = probe_device()
    except LegFailed as e:
        _say("FAIL device: %s" % e)
        return 3
    _say("device: %s" % device)
    _clean_ignored_artifacts()
    results, failed = {}, []
    for name in names:
        _say("leg %s ..." % name)
        key = name if name not in results else name + "#2"
        try:
            results[key] = LEGS[name](device)
            _say("ok   %s %s" % (key, json.dumps(results[key])))
        except LegFailed as e:
            failed.append(key)
            _say("FAIL %s: %s" % (key, e))
    with open(os.path.join(LOG_DIR, "summary.json"), "w") as fh:
        json.dump({"device": device, "legs": results, "failed": failed},
                  fh, indent=1)
    if failed:
        _say("failed legs: %s" % failed)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
