"""Kernel A/B matrix on the real chip.

Runs the promoted-kernel candidates as subprocesses, one after another
(a chip belongs to one process at a time; this parent stays off JAX),
each with its own timeout and the shared persistent compilation cache,
and prints one JSON line with every measured row.  Configs:

ResNet-50 (bench.py, batch 128, img/s):
  baseline      XLA GroupNorm, 7x7 stem
  fusedgn       Pallas fused GroupNorm(+ReLU)
  s2d           space-to-depth stem (4x4/1 conv on C=12)
  s2d+fusedgn   both

Flagship LM (bench_transformer.py, 436M params, tok/s):
  default           Pallas flash fwd+bwd, full per-layer remat
  xla_bwd           flash fwd + XLA block-recompute bwd
  remat_attn        Pallas flash fwd+bwd, remat="attn" (no flash
                    recompute in the backward)
  chunked_xent      no-[B,T,V]-logits loss (T-chunked ln_f+head+xent)
  attn+chunked      remat="attn" + chunked loss
  attn+chunked_b16  same at batch 16 (memory freed by the above)

Decode (bench_transformer.py --decode, generated tok/s):
  decode_mha        KV-cache decode, full head count
  decode_gqa4       grouped-query attention, 4 KV heads (4x smaller
                    cache on the HBM-bound decode path)

Each child exits non-zero without a TPU, so the matrix cannot record a
CPU number.  Not measured on the current code.
"""

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

RESNET_CONFIGS = [
    ("baseline", {"ELASTICDL_FUSED_GN": "off"}),
    ("fusedgn", {"ELASTICDL_FUSED_GN": "tpu"}),
    ("s2d", {"ELASTICDL_FUSED_GN": "off", "ELASTICDL_RESNET_S2D": "1"}),
    ("s2d+fusedgn",
     {"ELASTICDL_FUSED_GN": "tpu", "ELASTICDL_RESNET_S2D": "1"}),
]

DECODE_CONFIGS = [
    ("decode_mha", {}),
    ("decode_gqa4", {"ELASTICDL_BENCH_KV_HEADS": "4"}),
]

LM_CONFIGS = [
    ("default", {}),
    ("xla_bwd", {"ELASTICDL_FLASH_BWD": "xla"}),
    ("remat_attn", {"ELASTICDL_BENCH_REMAT": "attn"}),
    ("chunked_xent", {"ELASTICDL_BENCH_CHUNKED_XENT": "512"}),
    ("attn+chunked", {"ELASTICDL_BENCH_REMAT": "attn",
                      "ELASTICDL_BENCH_CHUNKED_XENT": "512"}),
    ("attn+chunked_b16", {"ELASTICDL_BENCH_REMAT": "attn",
                          "ELASTICDL_BENCH_CHUNKED_XENT": "512",
                          "ELASTICDL_BENCH_BATCH": "16"}),
]


def _run(argv, env, timeout):
    """Returns (parsed_json|None, reason, returncode|None)."""
    from elasticdl_tpu.utils.jsonline import last_json_line

    try:
        proc = subprocess.run(
            [sys.executable] + argv, capture_output=True, text=True,
            timeout=timeout, env={**os.environ, **env}, cwd=HERE,
        )
    except subprocess.TimeoutExpired as e:
        stderr = e.stderr
        if isinstance(stderr, bytes):
            stderr = stderr.decode("utf-8", "replace")
        marks = [ln for ln in (stderr or "").splitlines()
                 if ln.startswith("BENCHMARK-MARK ")]
        return None, "timeout %ds at %s" % (
            timeout, marks[-1].split(" ", 1)[1] if marks else "?"), None
    result = last_json_line(proc.stdout)
    if result is not None:
        return result, "", proc.returncode
    return None, "no JSON (exit %d); stderr: %s" % (
        proc.returncode, (proc.stderr or "")[-200:]), proc.returncode


def main():
    per_cfg = int(os.environ.get("ELASTICDL_AB_TIMEOUT", "420"))
    rows = {"resnet": {}, "lm": {}}

    for name, env in RESNET_CONFIGS:
        t0 = time.monotonic()
        res, reason, _rc = _run(
            ["bench.py", "--batch", "128"], env, per_cfg)
        rows["resnet"][name] = (
            {"img_per_sec": res["value"],
             "ms_per_step": res["detail"]["ms_per_step"],
             "mfu": res["detail"]["mfu_estimate"],
             "compile_secs": res["detail"]["compile_secs"],
             "samples": res["detail"].get("samples")}
            if res else {"error": reason}
        )
        if res and "device" not in rows:
            rows["device"] = res["detail"].get("device")
        print("resnet/%s: %s (%.0fs)" % (
            name, rows["resnet"][name], time.monotonic() - t0),
            file=sys.stderr, flush=True)

    for name, env in LM_CONFIGS:
        t0 = time.monotonic()
        res, reason, _rc = _run(["bench_transformer.py"], env, per_cfg)
        rows["lm"][name] = (
            {"tok_per_sec": res["value"],
             "ms_per_step": res["detail"]["ms_per_step"],
             "mfu": res["detail"]["mfu_estimate"],
             "compile_secs": res["detail"]["compile_secs"],
             "samples": res["detail"].get("samples")}
            if res else {"error": reason}
        )
        if res and "device" not in rows:
            rows["device"] = res["detail"].get("device")
        print("lm/%s: %s (%.0fs)" % (
            name, rows["lm"][name], time.monotonic() - t0),
            file=sys.stderr, flush=True)

    rows["decode"] = {}
    for name, env in DECODE_CONFIGS:
        t0 = time.monotonic()
        res, reason, _rc = _run(
            ["bench_transformer.py", "--decode"], env, per_cfg)
        rows["decode"][name] = (
            {"tok_per_sec": res["value"],
             "ms_per_token_batch": res["detail"]["ms_per_token_batch"],
             "kv_heads": res["detail"]["kv_heads"],
             "compile_secs": res["detail"]["compile_secs"],
             "samples": res["detail"].get("samples")}
            if res else {"error": reason}
        )
        if res and "device" not in rows:
            rows["device"] = res["detail"].get("device")
        print("decode/%s: %s (%.0fs)" % (
            name, rows["decode"][name], time.monotonic() - t0),
            file=sys.stderr, flush=True)

    print(json.dumps({"metric": "kernel_ab_matrix", "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
