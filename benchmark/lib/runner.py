"""One run of one cell: data, job, window, checks, one JSON line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1> [--rehearse]

The measured window opens when the job is in steady state (the second
whole task is reported done) and closes at the first task completion at
or after ``--seconds`` later (benchmark/lib/slices.py).  Without a TPU
nothing is printed and the exit code is not 0; ``--rehearse`` runs the
whole harness at the tiny sizes the configuration and traffic files give
for it, prints the device it found and no metric values, and exits 3.
A run ends in its result's line or in a ``[benchmark] FAILED <cell>: ..``
line on stderr with exit 1, the reference comparison's expiry
(``COMPARE_CAP_S``) too; the result's last key, ``compared``, and the
last lines of stderr hold each number ``judge`` held to a limit.
"""

import argparse
import glob
import json
import os
import re
import shutil
import subprocess
import sys
import time

from benchmark.lib import datagen, job as joblib, manifest, peaks, slices
from benchmark.lib import xplane

POLL_S = 0.025
STARTUP_CAP_S = 1100     # a cold first run compiles
CLOSE_CAP_S = 30         # the task that closes the window, past --seconds
COMPARE_CAP_S = 900      # the reference comparison, on an empty compile cache
WINDOW_TASKS_BEFORE = 2  # whole tasks done before the window opens


class RunFailed(Exception):
    pass


def _say(message):
    print("[benchmark] %s" % message, file=sys.stderr, flush=True)


def merge(base, over):
    out = dict(base)
    for key, value in (over or {}).items():
        out[key] = merge(out[key], value) if isinstance(
            value, dict) and isinstance(out.get(key), dict) else value
    return out


def params_string(params):
    return ";".join("%s=%s" % (k, str(v).lower() if isinstance(v, bool) else v)
                    for k, v in params.items())


def build_flags(config, traffic, data_origin):
    """The master's command line: what the configuration and the traffic
    name, and nothing else: every other flag stays at its default."""
    cli = config["cli"]
    # The product's ModelSpec through benchmark/lib/bench_zoo.py: the door
    # through which the worker states its memory (and, traced, its trace).
    params = dict(zoo=cli["model_zoo"], **cli["model_params"])
    flags = {"model_zoo": "benchmark.lib.bench_zoo",
             "model_params": params_string(params),
             "data_origin": data_origin}
    flags.update(cli.get("flags", {}))
    flags.update(traffic["flags"])
    out = []
    for key, value in flags.items():
        out += ["--" + key, str(value).lower() if isinstance(value, bool)
                else str(value)]
    return out


def _cache_entries(cache_dir):
    try:
        return set(os.listdir(cache_dir))
    except OSError:
        return set()


class Run:
    """The artefacts of one run, as the per-layer readers see them."""

    def __init__(self, root, cell, seed, seconds, traced, rehearse):
        self.root, self.cell, self.seed = root, cell, seed
        self.seconds, self.traced, self.rehearse = seconds, traced, rehearse
        self.config, self.traffic = cell["config"], cell["traffic"]
        if rehearse:
            self.config = merge(self.config, self.config.get("rehearsal"))
            self.traffic = merge(self.traffic, self.traffic.get("rehearsal"))
        self.work = os.path.join(root, ".bench_work", cell["name"])
        self.trace_dir = os.path.join(self.work, "trace")
        self.cache_dir = os.environ.get(
            "JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache")
        self.times = {}          # named instants, seconds of the epoch
        self.e2e = {}            # end-to-end values by metric name
        self.window = None       # slices.throughput() of the window
        self.log = None          # job.parse_log()
        self.trace = None        # xplane.reduce()
        self.reference = None    # the comparison with the plain reference
        self.status_close = None
        self.compiles_in_window = None
        self.job = None
        self.device = None
        self.problems = []
        self.attempted = self.failed = 0

    @property
    def records_per_task(self):
        flags = self.traffic["flags"]
        return flags["batch_size"] * flags["num_minibatches_per_task"]

    # -- set-up --------------------------------------------------------------

    def make_data(self):
        t = time.time()
        params = dict(self.traffic["params"])
        for key, source in self.traffic.get("params_from_config", {}).items():
            params[key] = self.config[source]
        origin = datagen.ensure(os.path.join(self.root, ".bench_work", "data"),
                                self.traffic["generator"], params, self.seed)
        self.times["datagen_s"] = time.time() - t
        return origin

    def launch(self, origin):
        shutil.rmtree(self.work, ignore_errors=True)
        os.makedirs(self.trace_dir)
        env = {"BENCH_WORK_DIR": self.work}
        if self.traced:
            env["BENCH_TRACE_DIR"] = self.trace_dir
        if self.rehearse and self.cell["chips"] > 1:
            env["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                " --xla_force_host_platform_device_count=%d"
                                % self.cell["chips"]).strip()
        flags = build_flags(self.config, self.traffic, origin)
        self.job = joblib.Job(self.root, flags,
                              os.path.join(self.work, "job.log"), env)
        self.job.start()
        self.times["master_start"] = self.job.started_at

    def _wait(self, until, cap, what):
        """Poll until ``until(status)`` is true; returns that status."""
        deadline = time.time() + cap
        while time.time() < deadline:
            status = self.job.poll()
            self._check_device()
            if status is not None and until(status):
                return status
            time.sleep(POLL_S)
        raise RunFailed("%s did not happen within %d s" % (what, cap))

    def _check_device(self):
        if "device" in self.times or "worker device:" not in self.job.text:
            return
        log = joblib.parse_log(self.job.text)
        self.times["device"] = min(log["device_at"].values())
        report = next(iter(log["devices"].values()))
        self.device = {"platform": report["platform"],
                       "kind": report["device_kind"].replace("_", " "),
                       "count": int(report["local_devices"])}
        if self.rehearse:
            return
        if self.device["platform"] != "tpu":
            raise RunFailed("the worker runs on %s, not on a TPU"
                            % self.device["platform"])
        if self.device["count"] < self.cell["chips"]:
            raise RunFailed("the cell asks for %d chip(s), the worker has %d"
                            % (self.cell["chips"], self.device["count"]))
        peaks.peaks_of(self.device["kind"])

    # -- the window ----------------------------------------------------------

    def measure(self, t0):
        job = self.job
        self._wait(lambda s: len(job.completions) >= WINDOW_TASKS_BEFORE,
                   STARTUP_CAP_S, "steady state (two tasks done)")
        t_open = job.completions[WINDOW_TASKS_BEFORE - 1]
        self.times["open"] = t_open
        self.times["first_step"] = min(job.first_progress.values())
        self.e2e["setup_s"] = t_open - t0
        cache_at_open = _cache_entries(self.cache_dir)
        if self.traced:
            self._wait(lambda s: time.time() >= t_open + self.traffic.get(
                "trace_at_s", 2), 60, "the trace's start")
            request = os.path.join(self.trace_dir, "start")
            with open(request + ".tmp", "w") as fh:
                fh.write("%g\n" % self.traffic.get("trace_seconds", 6))
            os.replace(request + ".tmp", request)
        t_end = t_open + self.seconds
        self._wait(lambda s: job.completions[-1] >= t_end,
                   self.seconds + CLOSE_CAP_S,
                   "a task completion at or after the window's %g s"
                   % self.seconds)
        self.times["close"] = next(t for t in job.completions if t >= t_end)
        self._trace_written()
        self.compiles_in_window = len(
            _cache_entries(self.cache_dir) - cache_at_open)
        self.status_close = job.status()

    def _trace_written(self):
        """The worker writes the trace out after its last traced second;
        a worker stopped before that leaves none."""
        if self.traced:
            done = os.path.join(self.trace_dir, "done")
            error = os.path.join(self.trace_dir, "error")
            self._wait(lambda s: os.path.exists(done) or os.path.exists(
                error), 150, "the trace being written out (%s holds %s)" % (
                    self.trace_dir, sorted(os.listdir(self.trace_dir))))
            if os.path.exists(error):
                with open(error) as fh:
                    raise RunFailed("the worker could not trace: %s"
                                    % fh.read()[-1500:])

    def finish(self):
        """Let the workers state their end of run, then end the job."""
        self.times["term"] = time.time()
        if self.job.proc.poll() is None:
            if not self.job.stop_workers_gracefully():
                self.problems.append("no end-of-run line after SIGTERM")
            deadline = time.time() + 20   # the workers' exit hooks
            while time.time() < deadline and not glob.glob(
                    os.path.join(self.work, "memory-*.json")):
                time.sleep(0.1)
        self.job.kill()
        self.log = joblib.parse_log(self.job.text)

    # -- after the job -------------------------------------------------------

    def throughput(self):
        if not any(m["name"] == "records_per_s"
                   for m in self.cell["end_to_end"]):
            return
        self.window = slices.throughput(
            self.job.completions, self.times["open"], self.seconds,
            self.records_per_task)
        self.e2e["records_per_s"] = self.window["records_per_s"]

    def judge(self):
        """``correct``, with the reason for each failure in ``problems``."""
        log, problems = self.log, self.problems
        if self.device is None:
            raise RunFailed("no worker stated its device")
        for wid, report in log["devices"].items():
            if report["platform"] != self.device["platform"]:
                problems.append("worker %d on %s" % (wid, report["platform"]))
        if joblib.BAD_LINES.search(self.job.text.split(
                "SIGTERM received")[0]):
            problems.append("the log holds a swallowed failure")
        if not log["losses"]:
            problems.append("no loss was logged")
        elif not log["losses_finite"]:
            problems.append("a loss is not finite")
        for ex in log["exits"]:
            ours = ex["at"] is not None and ex["at"] >= self.times["term"]
            if ex["code"] != "0" and not ours:
                problems.append("worker %d exited code=%s"
                                % (ex["worker"], ex["code"]))
        relaunches = [w for w in log["launched"] if w > 0
                      and log["launched"][w] < self.times["term"]]
        if relaunches:
            problems.append("%d relaunch(es)" % len(relaunches))
        tasks = (self.status_close or {}).get("tasks", {})
        in_window = [t for t in self.job.completions
                     if self.times["open"] < t <= self.times["close"]]
        self.attempted = len(in_window) + tasks.get("doing", 0)
        self.failed = sum(tasks.get("failed", {}).values())
        if self.failed:
            problems.append("%d task(s) failed for good" % self.failed)
        if re.search(r"task \d+ failed", self.job.text):
            problems.append("a task was retried")
        if self.compiles_in_window:
            problems.append("%d compile(s) inside the window"
                            % self.compiles_in_window)
        if not glob.glob(os.path.join(self.work, "memory-*.json")):
            problems.append("no worker stated its memory at exit")
        if self.reference is not None and not self.reference["ok"]:
            problems.append("loss differs from the plain reference: %s"
                            % self.reference)
        return not problems

    def compared(self):
        """Each number ``judge`` held to a limit, beside that limit."""
        out = {"tasks_failed": (self.failed, 0),
               "compiles_in_window": (self.compiles_in_window or 0, 0),
               "problems": (len(self.problems), 0)}
        if self.reference is not None:
            out["loss_rel_diff"] = (self.reference["rel_diff"],
                                    self.reference["tolerance"])
        return {name: {"value": value, "limit": limit}
                for name, (value, limit) in out.items()}

    def memory_peak_bytes(self):
        """Peak on the fullest chip: buffers in use plus the programs'
        reserved temporaries, as each worker stated them at its exit."""
        peaks_ = []
        for path in glob.glob(os.path.join(self.work, "memory-*.json")):
            with open(path) as fh:
                peaks_ += [chip.get("peak_bytes_in_use", 0)
                           + chip.get("peak_bytes_reserved", 0)
                           for chip in json.load(fh)]
        return max(peaks_, default=0)


def reduce_trace(run):
    raw = xplane.load_in_child(run.trace_dir, run.root)
    if raw is None:
        raise RunFailed("the traced run left no trace in %s" % run.trace_dir)
    with open(os.path.join(run.work, "trace_lines.json"), "w") as fh:
        json.dump(raw.get("lines"), fh, indent=1)
    run.trace = xplane.reduce(raw)
    if run.rehearse and run.trace is None:
        return   # the CPU backend has no device plane to reduce
    if run.trace is None or run.trace["busy_s"] <= 0:
        raise RunFailed("no operation ran on the device in the traced "
                        "window; planes and lines: %s" % raw.get("lines"))


def spawn_compare(root, config_file, seed, cache_dir, rehearse=False):
    """``lib/compare.py`` in a process of its own, its compile cache at
    ``cache_dir``: (its JSON line, the seconds it took).  Whatever goes
    wrong there is a RunFailed, its expiry at COMPARE_CAP_S too: the
    child is killed and waited for before this returns or raises."""
    env = joblib.child_env(root, {"JAX_COMPILATION_CACHE_DIR": cache_dir})
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    argv = [sys.executable, os.path.join(manifest.BENCH_DIR, "lib",
                                         "compare.py"),
            "--config-file", config_file, "--seed", str(seed)]
    if rehearse:
        argv.append("--rehearse")
    started = time.time()
    try:
        done = subprocess.run(argv, cwd=root, env=env, capture_output=True,
                              text=True, timeout=COMPARE_CAP_S)
    except subprocess.TimeoutExpired:
        raise RunFailed(
            "the reference comparison was not done within its cap of %d s "
            "(COMPARE_CAP_S; stopped after %.0f s)"
            % (COMPARE_CAP_S, time.time() - started))
    except OSError as e:
        raise RunFailed("the reference comparison did not start: %s" % e)
    lines = [l for l in done.stdout.splitlines() if l.startswith("{")]
    try:
        if done.returncode != 0:
            raise ValueError("exit %d" % done.returncode)
        return json.loads(lines[-1]), time.time() - started
    except (IndexError, ValueError) as e:
        raise RunFailed("the reference comparison failed (%s): %s"
                        % (e, done.stderr[-1500:]))


def compare_reference(run):
    """The product's loss against the plain float32 reference on one
    seeded microbatch, in a process of its own now that the chip is free
    and the window's compiles are counted: what it compiles goes into the
    job's cache, so that a checkout's second traced run finds it."""
    ref = run.config.get("reference")
    if not ref or not run.traffic.get("reference_check", True):
        return
    run.reference, seconds = spawn_compare(
        run.root, run.cell["config_file"], run.seed, run.cache_dir,
        run.rehearse)
    run.times["compare_s"] = seconds
    _say("reference comparison: %.1f s of %d" % (seconds, COMPARE_CAP_S))


def main(argv=None):
    t0 = time.time()
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)
    root = os.path.dirname(manifest.BENCH_DIR)
    if not os.path.isdir(os.path.join(root, "elasticdl_tpu")):
        _say("no elasticdl_tpu package beside %s: nothing to measure"
             % manifest.BENCH_DIR)
        return 2
    if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu" and \
            not args.rehearse:
        _say("JAX_PLATFORMS=cpu: no accelerator, no result "
             "(--rehearse runs the harness without measuring)")
        return 3
    book = manifest.Manifest(root)
    run = Run(root, book.cell(args.workload), args.seed, args.seconds,
              bool(args.trace), args.rehearse)
    readers = {m["name"]: book.reader(m["name"])
               for m in run.cell["per_layer"]}
    try:
        run.launch(run.make_data())
        try:
            run.measure(t0)
        finally:
            run.finish()
        run.throughput()
        if run.traced:
            reduce_trace(run)
            compare_reference(run)
        correct = run.judge()
    except (RunFailed, joblib.JobFailed, slices.NoWholeTask) as e:
        if run.job is not None:
            run.job.kill()
        _say("FAILED %s: %s (log: %s)" % (args.workload, e, os.path.join(
            run.work, "job.log")))
        return 1
    metrics = {}
    if run.traced:
        for meta in run.cell["per_layer"]:
            try:
                value = readers[meta["name"]](run)
            except KeyError:
                if not args.rehearse:   # e.g. no peaks for a CPU
                    raise
                value = None
            if value is not None:
                metrics[meta["name"]] = {"value": value,
                                         "unit": meta["unit"]}
    else:
        for meta in run.cell["end_to_end"]:
            metrics[meta["name"]] = {"value": run.e2e[meta["name"]],
                                     "unit": meta["unit"]}
    device = dict(run.device, memory_peak_bytes=run.memory_peak_bytes())
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if run.traced and run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    detail = {"problems": run.problems, "times": run.times,
              "window": run.window, "e2e": run.e2e,
              "reference": run.reference, "seed": run.seed,
              "trace": run.trace, "metrics": metrics,
              "seconds": run.seconds, "traced": run.traced,
              "completions": [t - run.times["open"]
                              for t in run.job.completions]}
    with open(os.path.join(run.work, "detail.json"), "w") as fh:
        json.dump(detail, fh, indent=1)
    _say("detail: %s" % json.dumps(
        {k: v for k, v in detail.items() if k != "completions"}))
    result["compared"] = run.compared()      # last in the line, and on stderr
    for name, pair in result["compared"].items():
        _say("compared %s: %r (limit %r)" % (name, pair["value"],
                                             pair["limit"]))
    if args.rehearse or run.device["platform"] != "tpu":
        result["metrics"] = {}
        result["rehearsal"] = sorted(metrics)
        print(json.dumps(result), flush=True)
        return 3
    print(json.dumps(result), flush=True)
    return 0
