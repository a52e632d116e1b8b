"""One managed training job, driven as a user drives it: the master CLI in
a process group of its own, observed through ``/status`` and its log.

This parent never imports JAX (a parent that touched JAX would hold the
chip its workers need).  Whatever happens, the whole process group is
killed and waited for at the end.
"""

import datetime
import json
import math
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.request

KERNEL_SWITCHES = ("ELASTICDL_FLASH", "ELASTICDL_FLASH_BWD",
                   "ELASTICDL_FUSED_GN")

_STAMP = re.compile(r"^\[(\d{4}-\d\d-\d\d \d\d:\d\d:\d\d),(\d{3})\]")
_DEVICE = re.compile(r"\[worker-(\d+)\].*worker device: (.*)$")
_END = "worker end-of-run:"
_END_LINE = re.compile(r"\[worker-(\d+)\].*worker end-of-run: (.*)$")
_TIMING = re.compile(
    r"\[worker-(\d+)\].*timing\[(\w+)\]: total=([\d.]+)s count=(\d+)")
_STEP = re.compile(r"\[worker-(\d+)\].*step (\d+) loss (\S+)")
_EXIT = re.compile(r"worker (\d+) exited code=(\S+) .* relaunch=(\w+)")
_LAUNCH = re.compile(r"launched worker (\d+)")
# worker/worker.py logs these and retries; a measured run must hold none.
BAD_LINES = re.compile(
    r"minibatch failed|training task \d+ failed|attention fallback:")


class JobFailed(Exception):
    pass


def stamp_seconds(line):
    """The log line's own time stamp as seconds of the epoch, or None."""
    m = _STAMP.match(line)
    if not m:
        return None
    whole = datetime.datetime.strptime(m.group(1), "%Y-%m-%d %H:%M:%S")
    return whole.timestamp() + int(m.group(2)) / 1000.0


def fields(report):
    return dict(item.split("=", 1) for item in report.split() if "=" in item)


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(root, extra=None):
    env = {k: v for k, v in os.environ.items() if k not in KERNEL_SWITCHES}
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra or {})
    return env


def _children(pid):
    """(pid, cmdline) of the direct children of ``pid`` (Linux /proc)."""
    out = []
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open("/proc/%s/stat" % name) as fh:
                stat = fh.read()
            ppid = int(stat[stat.rindex(")") + 2:].split()[1])
            if ppid != pid:
                continue
            with open("/proc/%s/cmdline" % name, "rb") as fh:
                cmd = fh.read().replace(b"\0", b" ").decode(errors="replace")
        except (OSError, ValueError):
            continue
        out.append((int(name), cmd))
    return out


class Job:
    """The master process group, its log and its status samples."""

    def __init__(self, root, flags, log_path, env=None):
        self.root = root
        self.port = free_port()
        self.log_path = log_path
        self.argv = [sys.executable, "-m", "elasticdl_tpu.master.main",
                     *flags, "--status_port", str(self.port)]
        self._env = child_env(root, env)
        self.proc = None
        self.started_at = None
        self.completions = []    # time of each training-task completion
        self.first_progress = {}  # worker id -> time of its first step seen
        self._completed = 0
        self._log = None
        self._tail = None
        self.text = ""

    def start(self):
        self._log = open(self.log_path, "w")
        self._tail = open(self.log_path, errors="replace")
        self.started_at = time.time()
        self.proc = subprocess.Popen(
            self.argv, cwd=self.root, env=self._env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True)

    # -- observation ---------------------------------------------------------

    def status(self):
        try:
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/status" % self.port,
                    timeout=2) as reply:
                return json.loads(reply.read())
        except (OSError, ValueError):
            return None

    def poll(self):
        """One observation: read the log's new lines, sample ``/status``,
        note task completions and each worker's first progress.  Raises
        JobFailed on a swallowed failure or a master that is gone."""
        now = time.time()
        new = self._tail.read()
        if new:
            self.text += new
            bad = BAD_LINES.search(new)
            if bad:
                line = new[new.rfind("\n", 0, bad.start()) + 1:]
                raise JobFailed("log holds %r" % line.split("\n", 1)[0][-300:])
        code = self.proc.poll()
        if code is not None:
            raise JobFailed("master exited with code %s before the run "
                            "was over" % code)
        status = self.status()
        if status is None:
            return None
        done = int(status["tasks"]["completed"].get("0", 0))
        if done > self._completed:
            # Several completions between two samples share one stamp;
            # the poll interval is far below a task's length.
            self.completions.extend([now] * (done - self._completed))
            self._completed = done
        workers = status.get("telemetry", {}).get("workers", {})
        for wid, tele in workers.items():
            if tele.get("steps_done", 0) >= 1 and wid not in \
                    self.first_progress:
                self.first_progress[wid] = now
        return status

    def worker_pids(self):
        return [pid for pid, cmd in _children(self.proc.pid)
                if "elasticdl_tpu.worker.main" in cmd]

    # -- the end -------------------------------------------------------------

    def stop_workers_gracefully(self, timeout=45):
        """SIGTERM the workers so that each prints its end-of-run line
        and runs its exit hooks (peak memory), and wait for those lines."""
        pids = self.worker_pids()
        want = self.text.count(_END) + len(pids)
        for pid in pids:
            try:
                os.kill(pid, signal.SIGTERM)
            except ProcessLookupError:
                pass
        deadline = time.time() + timeout
        while time.time() < deadline:
            self.text += self._tail.read()
            if self.text.count(_END) >= want:
                return True
            time.sleep(0.1)
        return False

    def kill(self, timeout=90):
        """SIGKILL the group and wait until every member has ended.  A
        killed worker that maps the chip takes 3-12 s to die (chip run,
        PR 23); left to die on its own it slows the next run's set-up."""
        if self.proc is not None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
            deadline = time.time() + timeout
            while time.time() < deadline:
                try:
                    os.killpg(self.proc.pid, 0)
                except ProcessLookupError:
                    break
                time.sleep(0.1)
        if self._tail is not None:
            self.text += self._tail.read()
            self._tail.close()
            self._log.close()
            self._tail = None


def parse_log(text):
    """What the log states, as plain values keyed by worker id."""
    out = {"devices": {}, "device_at": {}, "exits": [],
           "launched": {}, "losses": [], "ends": {}}
    end_of = lambda wid: out["ends"].setdefault(
        wid, {"steps": 0, "timing": {}})
    for line in text.splitlines():
        m = _DEVICE.search(line)
        if m:
            wid = int(m.group(1))
            out["devices"][wid] = fields(m.group(2))
            out["device_at"][wid] = stamp_seconds(line)
            continue
        m = _EXIT.search(line)
        if m:
            out["exits"].append({"worker": int(m.group(1)),
                                 "code": m.group(2),
                                 "relaunch": m.group(3) == "True",
                                 "at": stamp_seconds(line)})
            continue
        m = _LAUNCH.search(line)
        if m:
            out["launched"][int(m.group(1))] = stamp_seconds(line)
            continue
        m = _TIMING.search(line)
        if m:   # the worker's Timing report: span totals of its whole run
            end_of(int(m.group(1)))["timing"][m.group(2)] = float(
                m.group(3))
            continue
        m = _END_LINE.search(line)
        if m:
            end_of(int(m.group(1)))["steps"] = int(
                fields(m.group(2)).get("steps", 0))
            continue
        m = _STEP.search(line)
        if m:
            out["losses"].append(float(m.group(3)))
    out["losses_finite"] = all(math.isfinite(v) for v in out["losses"])
    return out
