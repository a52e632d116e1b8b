"""Native PS core vs pure numpy: does the C++ layer earn its place?

CPU-valid measurement (host-side code only) of the two hot paths the
reference keeps native (its Go PS wraps C++/Eigen optimizer kernels,
SURVEY §2.3):

  dense Adam apply   N=10M floats, kernels.cc edl_adam vs a numpy Adam
  embedding lookup+Adam  1M-row x 64 table, 4096-id batches (with
                     duplicates), Table.apply_adam vs a numpy
                     gather/scatter Adam

Prints one JSON line with both ratios.  Methodology: median of 5
timed runs per path; arrays touched once before timing so page
faults don't land in the measured region.
"""

import json
import sys
import time

import numpy as np


def _median_secs(fn, repeats=5):
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def numpy_adam(param, grad, m, v, lr, step, b1=0.9, b2=0.999, eps=1e-8):
    m *= b1
    m += (1 - b1) * grad
    v *= b2
    v += (1 - b2) * grad * grad
    mhat = m / (1 - b1 ** step)
    vhat = v / (1 - b2 ** step)
    param -= lr * mhat / (np.sqrt(vhat) + eps)


def bench_dense(n=10_000_000):
    from elasticdl_tpu.native import bindings

    rng = np.random.RandomState(0)
    grad = rng.randn(n).astype(np.float32)

    p1 = np.ones(n, np.float32)
    m1 = np.zeros(n, np.float32)
    v1 = np.zeros(n, np.float32)
    bindings.adam(p1, grad, m1, v1, 1e-3, 1)  # warm/touch
    native = _median_secs(
        lambda: bindings.adam(p1, grad, m1, v1, 1e-3, 2))

    p2 = np.ones(n, np.float32)
    m2 = np.zeros(n, np.float32)
    v2 = np.zeros(n, np.float32)
    numpy_adam(p2, grad, m2, v2, 1e-3, 1)
    ref = _median_secs(lambda: numpy_adam(p2, grad, m2, v2, 1e-3, 2))
    return {
        "n_params": n,
        "native_ms": round(native * 1e3, 2),
        "numpy_ms": round(ref * 1e3, 2),
        "native_speedup": round(ref / native, 2),
        "native_gparams_per_sec": round(n / native / 1e9, 2),
    }


def bench_table(rows=1_000_000, dim=64, batch=4096):
    from elasticdl_tpu.native import bindings

    rng = np.random.RandomState(1)
    ids = rng.randint(0, rows, size=batch).astype(np.int64)
    grads = rng.randn(batch, dim).astype(np.float32)

    table = bindings.NativeEmbeddingTable(dim, initializer="zeros")
    m_t = bindings.NativeEmbeddingTable(dim, initializer="zeros")
    v_t = bindings.NativeEmbeddingTable(dim, initializer="zeros")
    table.apply_adam(ids, grads, m_t, v_t, 1e-3, 1)  # warm (lazy init)
    native = _median_secs(
        lambda: table.apply_adam(ids, grads, m_t, v_t, 1e-3, 2))
    lookup = _median_secs(lambda: table.get(ids))

    # numpy reference: dict-of-rows is the honest pure-Python PS
    # baseline (the reference's pre-Go Python PS held per-id arrays);
    # a dense ndarray table would hold rows x dim resident for EVERY
    # table regardless of how few ids ever occur.
    store = {}
    ms = {}
    vs = {}

    def np_apply():
        for i in range(batch):
            key = int(ids[i])
            p = store.setdefault(key, np.zeros(dim, np.float32))
            m = ms.setdefault(key, np.zeros(dim, np.float32))
            v = vs.setdefault(key, np.zeros(dim, np.float32))
            numpy_adam(p, grads[i], m, v, 1e-3, 2)

    np_apply()
    ref = _median_secs(np_apply)

    def np_lookup():
        # every id is present after np_apply; indexing (not .get with
        # an eagerly-built default) keeps the baseline honest
        np.stack([store[int(i)] for i in ids])

    ref_lookup = _median_secs(np_lookup)
    return {
        "rows_touched": int(len(np.unique(ids))),
        "dim": dim, "batch": batch,
        "native_apply_ms": round(native * 1e3, 2),
        "python_apply_ms": round(ref * 1e3, 2),
        "apply_speedup": round(ref / native, 2),
        "native_lookup_ms": round(lookup * 1e3, 3),
        "python_lookup_ms": round(ref_lookup * 1e3, 3),
        "lookup_speedup": round(ref_lookup / lookup, 2),
    }


def main():
    dense = bench_dense()
    table = bench_table()
    print(json.dumps({
        "metric": "native_kernel_speedup",
        "value": dense["native_speedup"],
        "unit": "x vs numpy (dense adam)",
        "vs_baseline": None,
        "detail": {"dense_adam": dense, "embedding_table": table},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
