"""Flagship transformer-LM training throughput (tokens/sec/chip).

The long-context path end to end on one chip: RoPE + RMSNorm decoder
with the Pallas flash-attention kernel (ELASTICDL_FLASH=auto resolves
to the compiled kernel on TPU), bf16 compute, f32 Adam.  The reference
has no LM benchmark — this is the framework's own flagship number and
the single-chip anchor for the sharded configurations that
`__graft_entry__.dryrun_multichip` validates on a virtual mesh.

Measures the chip: with no ``tpu`` platform, or a ``device_kind`` whose
peak is not in ``elasticdl_tpu.utils.device.PEAK_BF16_FLOPS``, it exits
non-zero and prints no number.

Prints exactly one JSON line:
  {"metric": "transformer_lm_train_throughput", "value": N,
   "unit": "tokens/sec/chip", "vs_baseline": null, ...}
(vs_baseline is null: BASELINE.json names no reference LM metric.)
"""

import json
import os
import sys
import time

# ~400M-param config: dim 1024, 24 layers, seq 2048 — big enough that
# the MXU, not dispatch, is the bottleneck; small enough for one v5e.
DIM = 1024
LAYERS = 24
HEADS = 16
VOCAB = 32768
SEQ = 2048
BATCH = int(os.environ.get("ELASTICDL_BENCH_BATCH", "8"))


def run_bench(warmup=2, iters=10):
    import jax
    import numpy as np
    import optax

    from elasticdl_tpu.models import transformer as tfm
    from elasticdl_tpu.utils.device import (
        PEAK_BF16_FLOPS,
        place_compile_cache,
        require_tpu,
    )

    place_compile_cache()
    device_report = require_tpu()  # exits non-zero on any other backend
    platform = device_report["platform"]
    dim, layers, seq, batch, iters_ = DIM, LAYERS, SEQ, BATCH, iters

    # remat: "dots" saves matmul outputs (fewer re-FLOPs, more memory),
    # "attn" saves only attention outputs (skips recomputing flash in
    # the backward), anything else full per-layer remat.
    remat = {"dots": "dots", "attn": "attn"}.get(
        os.environ.get("ELASTICDL_BENCH_REMAT", ""), True
    )
    cfg = tfm.TransformerConfig(
        vocab_size=VOCAB, dim=dim, num_heads=HEADS, num_layers=layers,
        max_seq_len=seq, dtype="bfloat16", remat=remat,
    )
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    n_params = sum(
        int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params)
    )
    tx = optax.adamw(3e-4)
    opt_state = tx.init(params)

    tokens = jax.device_put(np.random.RandomState(0).randint(
        0, VOCAB, size=(batch, seq)
    ).astype(np.int32))

    # Chunked cross-entropy: never materialize the [B, T, V] logits
    # (~2 GB f32 at this config) — ln_f+head+xent run per T-chunk under
    # jax.checkpoint (models/transformer.py next_token_loss_chunked).
    xent_chunk = int(os.environ.get("ELASTICDL_BENCH_CHUNKED_XENT", "0"))

    def loss_fn(p):
        if xent_chunk:
            hidden, _aux = tfm.forward_hidden(p, tokens, cfg, mesh=None)
            return tfm.next_token_loss_chunked(
                p, hidden, tokens, cfg, chunk=xent_chunk
            ).mean()
        logits = tfm.forward(p, tokens, cfg, mesh=None)
        return tfm.next_token_loss(logits, tokens).mean()

    def step(p, s):
        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, s = tx.update(grads, s, p)
        p = optax.apply_updates(p, updates)
        return p, s, loss

    step = jax.jit(step, donate_argnums=(0, 1))

    compile_start = time.perf_counter()
    params, opt_state, loss = step(params, opt_state)
    float(loss)  # value fetch: fences, and is the loss we report
    compile_secs = time.perf_counter() - compile_start
    for _ in range(warmup):
        params, opt_state, loss = step(params, opt_state)
    float(loss)

    # Per-block samples: fence every 2 steps with a value fetch.  Each
    # sample is [iters_in_block, ms] so a trailing partial block stays
    # truthful.
    block = 2
    blocks = []
    start = time.perf_counter()
    t_block = start
    done_at_fence = 0
    for k in range(iters_):
        params, opt_state, loss = step(params, opt_state)
        if (k + 1) % block == 0 or k == iters_ - 1:
            float(loss)
            now = time.perf_counter()
            blocks.append([k + 1 - done_at_fence,
                           round((now - t_block) * 1000.0, 2)])
            t_block, done_at_fence = now, k + 1
    last_loss = float(loss)
    elapsed = time.perf_counter() - start
    samples = {"blocks": blocks, "format": "[iters, ms] per block"}
    device, env_snap = _provenance(jax)

    tokens_per_step = batch * seq
    tokens_per_sec = tokens_per_step * iters_ / elapsed
    # 6N per token fwd+bwd, plus causal attention ~ 6*L*T*dim per token
    flops_per_token = 6.0 * n_params + 6.0 * layers * seq * dim
    peak = PEAK_BF16_FLOPS[device_report["device_kind"]]
    mfu = round(tokens_per_sec * flops_per_token / peak, 4)
    return {
        "metric": "transformer_lm_train_throughput",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": None,
        "detail": {
            "platform": platform,
            "params_m": round(n_params / 1e6, 1),
            "dim": dim, "layers": layers, "seq": seq, "batch": batch,
            "ms_per_step": round(1000.0 * elapsed / iters_, 2),
            "mfu_estimate": mfu,
            "compile_secs": round(compile_secs, 1),
            "last_loss": last_loss,
            "flash": os.environ.get("ELASTICDL_FLASH", "auto"),
            "flash_bwd": os.environ.get("ELASTICDL_FLASH_BWD", "pallas"),
            "remat": str(remat),
            "xent_chunk": xent_chunk,
            "samples": samples,
            "device": device,
            "env": env_snap,
        },
    }


def _provenance(jax_mod):
    """(device fingerprint, env snapshot) — shared with bench.py."""
    import bench as _bench

    return _bench._device_fingerprint(jax_mod), _bench._env_snapshot()


def run_decode_bench(batch=8, prompt_len=128, new_tokens=128):
    """Serving-side decode throughput: batched prefill + KV-cache
    decode as ONE jitted program (generated tokens/sec/chip).

    ELASTICDL_BENCH_KV_HEADS picks the GQA group count (0 = MHA) — the
    A/B axis where the smaller KV cache pays on HBM-bound decode.
    """
    import jax
    import numpy as np

    from elasticdl_tpu.models import transformer as tfm
    from elasticdl_tpu.utils.device import place_compile_cache, require_tpu

    place_compile_cache()
    platform = require_tpu()["platform"]  # exits non-zero otherwise
    dim, layers, heads = DIM, LAYERS, HEADS

    kv_heads = int(os.environ.get("ELASTICDL_BENCH_KV_HEADS", "0"))
    cfg = tfm.TransformerConfig(
        vocab_size=VOCAB, dim=dim, num_heads=heads, num_layers=layers,
        max_seq_len=prompt_len + new_tokens, dtype="bfloat16",
        num_kv_heads=kv_heads,
    )
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.device_put(np.random.RandomState(0).randint(
        0, VOCAB, size=(batch, prompt_len)).astype(np.int32))

    gen = jax.jit(
        lambda p, t: tfm.generate(p, cfg, t, max_new_tokens=new_tokens)
    )
    compile_start = time.perf_counter()
    out = gen(params, prompt)
    int(out[0, -1])  # fence: value fetch
    compile_secs = time.perf_counter() - compile_start
    iters = 3
    blocks = []
    start = time.perf_counter()
    t_block = start
    for _ in range(iters):
        out = gen(params, prompt)
        int(out[0, -1])  # fence each full generate
        now = time.perf_counter()
        blocks.append([1, round((now - t_block) * 1000.0, 2)])
        t_block = now
    elapsed = time.perf_counter() - start
    device, env_snap = _provenance(jax)

    tok_per_sec = batch * new_tokens * iters / elapsed
    return {
        "metric": "transformer_lm_decode_throughput",
        "value": round(tok_per_sec, 1),
        "unit": "generated tokens/sec/chip",
        "vs_baseline": None,
        "detail": {
            "platform": platform,
            "batch": batch, "prompt_len": prompt_len,
            "new_tokens": new_tokens,
            "kv_heads": kv_heads or heads,
            "num_heads": heads, "dim": dim, "layers": layers,
            "ms_per_token_batch": round(
                1000.0 * elapsed / (new_tokens * iters), 3),
            "compile_secs": round(compile_secs, 1),
            "samples": {"blocks": blocks,
                        "format": "[generates, ms] per block"},
            "device": device,
            "env": env_snap,
        },
    }


if __name__ == "__main__":
    if "--decode" in sys.argv:
        print(json.dumps(run_decode_bench()))
    else:
        print(json.dumps(run_bench()))
