"""The benchmark's model zoo entry: the product's own ModelSpec, loaded
through the door any user's own zoo module uses (``--model_zoo`` as a
dotted path, the real zoo module in ``zoo=``), so that the worker process,
the only one that holds the chip, can tell the benchmark two things the
program does not.  It uses JAX's public API alone and touches nothing of
the program: no attribute of it is replaced, nothing on the training path.

In every run, at the worker's exit, it writes each chip's whole
``memory_stats()`` to ``$BENCH_WORK_DIR/memory-<pid>.json``.  The worker's
own end-of-run line states ``peak_bytes_in_use`` alone, and on this
backend a program's temporaries (activations, logits) are not in that
figure but in ``peak_bytes_reserved`` (chip run, PR 23:
benchmark/tools/memprobe.py).

In a ``--trace 1`` run (``$BENCH_TRACE_DIR`` set) it also starts a thread
that, when ``$BENCH_TRACE_DIR/start`` appears, records a device trace of
that many seconds into that directory (Python tracer off) and then writes
``done``.  The worker has no door for a window of a trace:
``--profile_dir`` traces the whole run, Python tracer on.

What must move into the program itself (the memory figure into
``device_report``, a door for a traced window, its ``Timing`` spans onto
the profiler's clock) is listed in PERF.md for the ``tracing`` issue.
"""

import os
import sys
import threading
import time

from elasticdl_tpu.models.spec import load_model_spec

_installed = False


def _in_worker():
    main = sys.modules.get("__main__")
    spec = getattr(main, "__spec__", None)
    return getattr(spec, "name", "") == "elasticdl_tpu.worker.main"


def _trace_on_request(trace_dir):
    try:
        _trace(trace_dir)
    except BaseException as e:  # noqa: BLE001 - reported, then re-raised
        import traceback

        with open(os.path.join(trace_dir, "error"), "w") as fh:
            fh.write("%r\n%s" % (e, traceback.format_exc()))
        raise


def _trace(trace_dir):
    import jax

    start = os.path.join(trace_dir, "start")
    taken = start + ".taken"
    while True:
        try:
            # Claimed by renaming: a relaunched worker finds no request.
            os.replace(start, taken)
            break
        except FileNotFoundError:
            time.sleep(0.05)
    with open(taken) as fh:
        seconds = float(fh.read().strip() or 5)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    # Level 1 holds the runtime's and the program's annotations; level 2
    # adds millions of futex waits of the gRPC threads (3.4 M events in
    # 6 s of a ResNet-50 job, a 118 MB trace that took 98 s to write: chip
    # run, PR 23).
    options.host_tracer_level = 1
    # The HLO protos carry every Pallas kernel's compiled body: ResNet-50's
    # hundred custom calls made a 234 MB trace that took minutes to write.
    options.enable_hlo_proto = False
    began = time.time()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    time.sleep(seconds)
    stopping = time.time()
    jax.profiler.stop_trace()
    with open(os.path.join(trace_dir, "done"), "w") as fh:
        fh.write("%r %r %r\n" % (began, stopping, time.time()))


def _state_memory_at_exit(work_dir):
    import atexit
    import json

    import jax

    def report():
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        path = os.path.join(work_dir, "memory-%d.json" % os.getpid())
        with open(path + ".tmp", "w") as fh:
            json.dump(stats, fh)
        os.replace(path + ".tmp", path)

    atexit.register(report)


def model_spec(zoo, **params):
    global _installed
    spec = load_model_spec(zoo, **params)
    if not _in_worker() or _installed:
        return spec
    _installed = True
    work_dir = os.environ.get("BENCH_WORK_DIR")
    if work_dir:
        _state_memory_at_exit(work_dir)
    trace_dir = os.environ.get("BENCH_TRACE_DIR")
    if trace_dir:
        threading.Thread(target=_trace_on_request, args=(trace_dir,),
                         name="bench-trace", daemon=True).start()
    return spec
