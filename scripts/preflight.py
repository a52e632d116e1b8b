"""Green-HEAD gate: refuse to snapshot a broken tree (VERDICT r3 #4).

Runs, in order, each in a fresh subprocess with the CPU platform pinned:

  1. elastic-lint + compileall (scripts/lint.sh — static analysis of
     the elastic control plane: per-file EL001-EL004/EL007 plus the
     whole-program EL005 lock-order / EL006 blocking-under-lock /
     EL008 RPC-conformance pass; emits the EL005 lock-order graph to
     artifacts/lock_graph.dot)
  2. the Prometheus exposition-format conformance tests (every
     /metrics renderer vs the strict parser + metric registry)
  3. the full test suite (pytest tests -q)
  4. the driver's multi-chip dry run (__graft_entry__.dryrun_multichip(8))
  5. bench_serving.py --wire: the binary serving data plane's gates
     (e2e ratio within 25% of the endpoint-layer ratio, binary p99
     within 10% of JSON's, JSON-vs-binary bit-identity, router
     byte-identical pass-through)
  6. bench_ps_wire.py --frame_only: the frame-native PS data plane's
     gates (decode-copy bytes >= 1.3x smaller than TensorPB at equal
     wire dtype, loopback steps/s >= 1.0x, same-seed serialized
     losses bit-identical frame-vs-pb)

These are CPU gates.  The chip is checked separately, by sending
``python chip_smoke.py`` through the chip tool, and measured by
``python3 benchmark/run.py`` there (neither is a stage here).  What the
observability plane costs is measured on the chip too, by the benchmark
(``BENCHMARK.json``; PERF.md section 6 has the traced rate beside the
untraced one), not by a steps/s ratio on this CPU.

Exits nonzero on the FIRST failure with the failing stage named.  Run it
before every end-of-round snapshot — round 2 shipped a broken HEAD
because nothing enforced this mechanically (reference analog: the CI job
gate, scripts/validate_job_status.py + scripts/travis/run_job.sh:1-30).

Usage: python scripts/preflight.py [--fast]
  --fast skips the bench gates (suite + dryrun only).
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CPU_ENV = {"JAX_PLATFORMS": "cpu"}


def run_stage(name, argv, extra_env=None, timeout=2400):
    print("[preflight] %s ..." % name, flush=True)
    t0 = time.monotonic()
    try:
        proc = subprocess.run(
            argv, cwd=REPO, timeout=timeout,
            env={**os.environ, **CPU_ENV, **(extra_env or {})},
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
    except subprocess.TimeoutExpired:
        print("[preflight] FAIL %s: timed out after %ds" % (name, timeout))
        return False, ""
    secs = time.monotonic() - t0
    if proc.returncode != 0:
        print(proc.stdout[-4000:])
        print("[preflight] FAIL %s: exit %d after %.0fs"
              % (name, proc.returncode, secs))
        return False, proc.stdout
    print("[preflight] ok %s (%.0fs)" % (name, secs), flush=True)
    return True, proc.stdout


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    fast = "--fast" in argv

    # Cheapest gate first: static analysis + compile sweep (~seconds)
    # catches control-plane lock/servicer/thread regressions before
    # the 10-minute suite spends any time.
    ok, _ = run_stage(
        "elastic-lint",
        ["bash", os.path.join(REPO, "scripts", "lint.sh")],
        timeout=300,
    )
    if not ok:
        return 1

    # Exposition-format conformance next (seconds): every /metrics
    # renderer against the strict parser + the metric registry —
    # a malformed scrape or an undeclared series fails before the
    # full suite spends any time.
    ok, _ = run_stage(
        "prom-exposition",
        [sys.executable, "-m", "pytest",
         "tests/test_prom_exposition.py", "-q"],
        timeout=300,
    )
    if not ok:
        return 1

    ok, _ = run_stage(
        "pytest", [sys.executable, "-m", "pytest", "tests", "-q"],
        extra_env={
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8"
        },
    )
    if not ok:
        return 1

    ok, _ = run_stage(
        "dryrun_multichip(8)",
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(8)"],
        extra_env={
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8"
        },
        timeout=900,
    )
    if not ok:
        return 1

    if not fast:
        sys.path.insert(0, REPO)
        from elasticdl_tpu.utils.jsonline import last_json_line

        # Binary serving data plane (ISSUE 15): the e2e-approaches-
        # endpoint ratio gate, the serving.request p99 gate, JSON-vs-
        # binary bit-identity, and router byte-identical pass-through
        # — bench_serving.py --wire exits nonzero itself when any gate
        # fails; the detail check below keeps the verdict visible.
        ok, out = run_stage(
            "bench_serving.py --wire (binary-plane gates)",
            [sys.executable, "bench_serving.py", "--wire",
             "--requests_per_client", "30", "--blocks", "4"],
            timeout=900,
        )
        if not ok:
            return 1
        parsed = last_json_line(out)
        detail = (parsed or {}).get("detail", {})
        if not detail.get("all_green"):
            print("[preflight] FAIL bench_serving --wire: gates %s"
                  % detail.get("gates"))
            return 1
        print("[preflight] binary plane: e2e/endpoint %s (json %s), "
              "p99 %s vs %s ms"
              % (parsed.get("value"), parsed.get("vs_baseline"),
                 detail.get("p99_ms_binary_server_side"),
                 detail.get("p99_ms_json_server_side")))

        # Frame-native PS data plane (ISSUE 17): frame-vs-TensorPB at
        # equal wire dtype — decode-copy bytes >= 1.3x smaller,
        # loopback steps/s >= 1.0x, and same-seed serialized losses
        # bit-identical.  bench_ps_wire --frame_only exits nonzero
        # itself when any gate fails.
        ok, out = run_stage(
            "bench_ps_wire.py --frame_only (frame-wire gates)",
            [sys.executable, "bench_ps_wire.py", "--frame_only"],
            timeout=900,
        )
        if not ok:
            return 1
        parsed = last_json_line(out)
        gates = (parsed or {}).get("gates", {})
        if not (parsed or {}).get("pass"):
            print("[preflight] FAIL bench_ps_wire --frame_only: "
                  "gates %s" % gates)
            return 1
        detail = (parsed or {}).get("detail", {})
        print("[preflight] frame wire: decode-copy %sx, loopback "
              "steps %sx, bit-identical %s"
              % (parsed.get("value"),
                 detail.get("steps_ratio_frame_over_pb_loopback"),
                 gates.get("losses_bit_identical")))

    print("[preflight] ALL GREEN")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
