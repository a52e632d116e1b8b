"""PS wire microbenchmark: serialized vs. overlapped hot path, f32 vs. bf16.

Measures the two levers of the overlapped PS communication path on a
synthetic DeepFM-shaped workload against real PS shard subprocesses
(separate processes, like a real deployment — the PS applies gradients
under its own GIL, so overlap has actual server-side parallelism to
hide):

 - bytes-on-wire: gradient-push payload per step with float32 vs.
   bfloat16 wire encoding (the PS accumulates in f32 either way), plus
   the embedding-pull payload both ways;
 - steps/sec: the strictly serialized loop (pull -> pull-emb -> step ->
   blocking push) vs. the pipelined loop (async push window 1 on
   dedicated channels + one-batch embedding-pull prefetch), same model,
   same data, same wire dtype.

Each serialized/pipelined pair runs as INTERLEAVED timed blocks
(A,B,A,B,...) with the best block kept per mode — this container is
shared, so wall-clock noise between back-to-back runs is larger than
the effect under test, and pairing decorrelates it.  Prints one JSON
line per configuration and a final summary line with the ratios (the
acceptance artifact).  Runs fully on CPU — the PS path is host-side
numpy + gRPC and the jitted step is tiny.
"""

import json
import os
import subprocess
import sys
import time

# A host-side CPU bench (the PS path is numpy + gRPC; the jitted step is
# tiny): pinned to the CPU so that it takes no chip on a TPU host.
os.environ["JAX_PLATFORMS"] = "cpu"

BATCH_SIZE = 256
VOCAB_SIZE = 50_000
NUM_FIELDS = 10
EMBEDDING_DIM = 16
GET_MODEL_STEPS = 5
ITERS = 40
WARMUP = 5
BLOCKS = 3


def _start_ps(num_ps, opt_type="adam", opt_args="learning_rate=0.001",
              rpc_delay_ms=0.0):
    """Spawn num_ps PS shard subprocesses; returns (procs, addrs).

    ``rpc_delay_ms`` > 0 turns on the PS server's latency interceptor,
    emulating the cross-host link of a real deployment on this
    single-host rig (see utils/grpc_utils.RpcDelayInterceptor)."""
    from elasticdl_tpu.utils import grpc_utils

    ports = [grpc_utils.find_free_port() for _ in range(num_ps)]
    procs = []
    for i, port in enumerate(ports):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # PS is host-side numpy/C++
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "elasticdl_tpu.ps.server",
             "--port", str(port), "--ps_id", str(i),
             "--num_ps", str(num_ps),
             "--opt_type", opt_type, "--opt_args", opt_args,
             "--rpc_delay_ms", str(rpc_delay_ms)],
            env=env,
        ))
    return procs, ["localhost:%d" % p for p in ports]


def _connect(addrs):
    from elasticdl_tpu.utils import grpc_utils

    channels = []
    for addr in addrs:
        ch = grpc_utils.build_channel(addr)
        grpc_utils.wait_for_channel_ready(ch, timeout=30)
        channels.append(ch)
    return channels


def _make_batches(spec, n_batches, seed=0):
    from elasticdl_tpu.models import deepfm

    dense, ids, labels = deepfm.synthetic_data(
        n=BATCH_SIZE * n_batches, num_fields=NUM_FIELDS,
        vocab_size=VOCAB_SIZE, seed=seed,
    )
    return [
        spec.feed([
            (dense[j], ids[j], labels[j])
            for j in range(s, s + BATCH_SIZE)
        ])
        for s in range(0, BATCH_SIZE * n_batches, BATCH_SIZE)
    ]


class _Mode:
    """One benchmark configuration: its own PS shards + trainer, so the
    interleaved timed blocks never share server state."""

    def __init__(self, label, wire_dtype, async_push_window, prefetch,
                 rpc_delay_ms=0.0, frame_wire="auto"):
        from elasticdl_tpu.models import deepfm
        from elasticdl_tpu.worker.ps_client import PSClient
        from elasticdl_tpu.worker.ps_trainer import ParameterServerTrainer

        self.label = label
        self.wire_dtype = wire_dtype
        self.window = async_push_window
        self.prefetch = prefetch
        self.rpc_delay_ms = rpc_delay_ms
        self.frame_wire = frame_wire
        self.procs, addrs = _start_ps(2, rpc_delay_ms=rpc_delay_ms)
        self.client = PSClient(
            _connect(addrs), wire_dtype=wire_dtype,
            # A background push sharing the pull connection's completion
            # queue convoys every foreground pull behind it.
            push_channels=(
                _connect(addrs) if async_push_window > 0 else None
            ),
            frame_wire=frame_wire,
        )
        spec = deepfm.model_spec(
            num_fields=NUM_FIELDS, vocab_size=VOCAB_SIZE,
            embedding_dim=EMBEDDING_DIM,
        )
        self.trainer = ParameterServerTrainer(
            spec, self.client, batch_size=BATCH_SIZE,
            get_model_steps=GET_MODEL_STEPS, rng_seed=0,
            async_push_window=async_push_window,
        )
        self.batches = _make_batches(spec, 8)
        self.best_elapsed = None
        self.last_loss = None
        for k in range(WARMUP):
            self._step(k)
        self.trainer.drain_pushes()

    def _step(self, k):
        if self.prefetch:
            self.trainer.prefetch_embeddings(
                self.batches[(k + 1) % len(self.batches)][0]
            )
        return self.trainer.train_minibatch(
            *self.batches[k % len(self.batches)]
        )

    def timed_block(self):
        for key in self.client.wire_stats:
            self.client.wire_stats[key] = 0
        start = time.perf_counter()
        for k in range(ITERS):
            self.last_loss, _ = self._step(k)
        self.trainer.drain_pushes()
        elapsed = time.perf_counter() - start
        if self.best_elapsed is None or elapsed < self.best_elapsed:
            self.best_elapsed = elapsed
        return elapsed

    def result(self):
        # wire_stats attributes payload bytes per ENCODING (the _pb /
        # _frame split, PR 17); sum both so each per-step number covers
        # the mode's whole wire regardless of which plane carried it,
        # and report the decode-copy bytes — what frame-native RPCs
        # exist to shrink (np.frombuffer views vs protobuf copy-out).
        stats = self.client.wire_stats
        push_bytes = (stats["push_gradient_bytes_pb"]
                      + stats["push_gradient_bytes_frame"])
        pull_dense = (stats["pull_dense_bytes_pb"]
                      + stats["pull_dense_bytes_frame"])
        decode_copy = (stats["push_decode_copy_bytes_pb"]
                       + stats["push_decode_copy_bytes_frame"]
                       + stats["pull_dense_decode_copy_bytes_pb"]
                       + stats["pull_dense_decode_copy_bytes_frame"])
        return {
            "mode": self.label,
            "wire_dtype": self.wire_dtype or "float32",
            "frame_wire": self.frame_wire,
            "frame_shards": self.client.frame_shards(),
            "async_push_window": self.window,
            "prefetch": bool(self.prefetch),
            "rpc_delay_ms": self.rpc_delay_ms,
            "get_model_steps": GET_MODEL_STEPS,
            "steps_per_sec": round(ITERS / self.best_elapsed, 2),
            "ms_per_step": round(
                1000.0 * self.best_elapsed / ITERS, 2
            ),
            "push_gradient_bytes_per_step": push_bytes // ITERS,
            "pull_embedding_bytes_per_step":
                stats["pull_embedding_bytes"] // ITERS,
            "pull_dense_bytes_per_step": pull_dense // ITERS,
            "decode_copy_bytes_per_step": decode_copy // ITERS,
            "last_loss": float(self.last_loss),
            "overlap_counters": self.trainer.timing.counters(),
        }

    def close(self):
        self.trainer.close()
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


def _run_pair(wire_dtype, tag, rpc_delay_ms=0.0):
    """Serialized vs pipelined at one wire dtype, interleaved blocks."""
    serialized = _Mode("serialized_" + tag, wire_dtype, 0, False,
                       rpc_delay_ms=rpc_delay_ms)
    pipelined = _Mode("pipelined_" + tag, wire_dtype, 1, True,
                      rpc_delay_ms=rpc_delay_ms)
    try:
        for _ in range(BLOCKS):
            serialized.timed_block()
            pipelined.timed_block()
        return serialized.result(), pipelined.result()
    finally:
        serialized.close()
        pipelined.close()


def _run_frame_pair(wire_dtype, tag, rpc_delay_ms=0.0):
    """Frame wire vs TensorPB wire, SAME everything else (pipelined
    loop, same wire dtype, same seed/batches), interleaved blocks.
    This is the PR-17 artifact: the only variable is whether push/pull
    RPCs carry one frame blob (``frame_wire="on"``) or repeated
    TensorPB messages (``"off"``)."""
    pb_mode = _Mode("pb_" + tag, wire_dtype, 1, True,
                    rpc_delay_ms=rpc_delay_ms, frame_wire="off")
    frame_mode = _Mode("frame_" + tag, wire_dtype, 1, True,
                       rpc_delay_ms=rpc_delay_ms, frame_wire="on")
    try:
        for _ in range(BLOCKS):
            pb_mode.timed_block()
            frame_mode.timed_block()
        return pb_mode.result(), frame_mode.result()
    finally:
        pb_mode.close()
        frame_mode.close()


def _frame_bit_identity(wire_dtype):
    """Same-seed SERIALIZED runs, pb wire vs frame wire: every loss
    along the way must match bit for bit — any wire-path numerics
    difference (encode rounding, decode upcast, tensor ordering)
    surfaces here.  The serialized loop is used deliberately: the
    pipelined loop is nondeterministic on ANY wire (async pushes race
    embedding prefetches row-by-row, per-row atomicity by design), so
    it cannot distinguish wire numerics from scheduling noise."""
    pb_mode = _Mode("pb_bitid", wire_dtype, 0, False,
                    frame_wire="off")
    frame_mode = _Mode("frame_bitid", wire_dtype, 0, False,
                       frame_wire="on")
    try:
        pb_losses, frame_losses = [], []
        for k in range(ITERS):
            pb_losses.append(float(pb_mode._step(k)[0]))
            frame_losses.append(float(frame_mode._step(k)[0]))
        return {
            "bit_identical": pb_losses == frame_losses,
            "steps_compared": ITERS,
            "last_loss_pb": pb_losses[-1],
            "last_loss_frame": frame_losses[-1],
        }
    finally:
        pb_mode.close()
        frame_mode.close()


def _frame_gate(pb_loop, frame_loop, pb_net, frame_net, bitid,
                rpc_delay_ms):
    """The ``--frame`` acceptance artifact: decode-copy savings, wire
    bytes, steps/s both at loopback and over the emulated cross-host
    link, and bit-identity of the same-seed serialized losses."""
    dc_ratio = (pb_loop["decode_copy_bytes_per_step"]
                / max(1, frame_loop["decode_copy_bytes_per_step"]))
    wire_ratio = (
        (pb_loop["push_gradient_bytes_per_step"]
         + pb_loop["pull_dense_bytes_per_step"])
        / max(1, frame_loop["push_gradient_bytes_per_step"]
              + frame_loop["pull_dense_bytes_per_step"])
    )
    loop_speed = (frame_loop["steps_per_sec"]
                  / max(1e-9, pb_loop["steps_per_sec"]))
    net_speed = (frame_net["steps_per_sec"]
                 / max(1e-9, pb_net["steps_per_sec"]))
    bit_identical = bool(bitid["bit_identical"])
    return {
        "metric": "ps_frame_wire",
        "value": round(dc_ratio, 2),
        "unit": "x fewer decode-copy bytes (frame vs TensorPB, equal "
                "wire dtype)",
        "vs_baseline": None,
        "gates": {
            "decode_copy_ratio_ge_1.3": dc_ratio >= 1.3,
            "loopback_steps_ratio_ge_1.0": loop_speed >= 1.0,
            "losses_bit_identical": bit_identical,
        },
        "pass": bool(dc_ratio >= 1.3 and loop_speed >= 1.0
                     and bit_identical),
        "detail": {
            "decode_copy_bytes_ratio_pb_over_frame": round(
                dc_ratio, 2),
            "wire_bytes_ratio_pb_over_frame": round(wire_ratio, 3),
            "steps_ratio_frame_over_pb_loopback": round(
                loop_speed, 3),
            "steps_ratio_frame_over_pb_xhost_%.0fms" % rpc_delay_ms:
                round(net_speed, 3),
            "bit_identity": bitid,
            "baseline": "self-relative: the TensorPB wire IS the "
                        "baseline, same pipelined loop and wire "
                        "dtype on both legs",
        },
    }


def main(argv=None):
    import argparse

    parser = argparse.ArgumentParser("bench_ps_wire")
    parser.add_argument(
        "--rpc_delay_ms", type=float, default=10.0,
        help="emulated cross-host RPC latency for the overlap pair; "
             "the bytes pair always runs at loopback (0)",
    )
    parser.add_argument(
        "--frame", action="store_true",
        help="also run the frame-vs-TensorPB pairs (loopback + "
             "emulated cross-host) and print the ps_frame_wire gate",
    )
    parser.add_argument(
        "--frame_only", action="store_true",
        help="run ONLY the frame-vs-TensorPB leg (implies --frame); "
             "what scripts/preflight.py invokes",
    )
    args = parser.parse_args(argv)
    if args.frame_only:
        args.frame = True

    if args.frame:
        # Frame-vs-TensorPB at equal (bf16) wire dtype: loopback shows
        # the CPU-side decode/encode savings, the emulated cross-host
        # leg shows the same ranking holds when the link dominates.
        # bf16 is the honest dtype for the decode-copy gate — at f32
        # the frame side's upcast cost is ZERO and the ratio diverges.
        pb_loop, frame_loop = _run_frame_pair("bfloat16", "bf16_loop")
        pb_net, frame_net = _run_frame_pair(
            "bfloat16", "bf16_xhost", rpc_delay_ms=args.rpc_delay_ms)
        bitid = _frame_bit_identity("bfloat16")
        for r in (pb_loop, frame_loop, pb_net, frame_net):
            print(json.dumps(r))
        gate = _frame_gate(pb_loop, frame_loop, pb_net, frame_net,
                           bitid, args.rpc_delay_ms)
        print(json.dumps(gate))
        if args.frame_only:
            return 0 if gate["pass"] else 1
    # Pair 1 — loopback, f32 vs bf16 wire: the bytes-on-wire artifact,
    # plus the loopback overlap number (on a 2-core single-host rig the
    # worker, both PS shards, and XLA contend for the same cores, so
    # overlap buys little HERE; it exists to be reported honestly).
    ser_f32, pipe_f32 = _run_pair(None, "f32")
    ser_bf16, pipe_bf16 = _run_pair("bfloat16", "bf16")
    # Pair 2 — emulated cross-host link (the deployment this path is
    # for: PS shards on other hosts): wire latency is idle time the
    # pipelined loop hides behind compute.
    ser_net, pipe_net = _run_pair(
        "bfloat16", "bf16_xhost", rpc_delay_ms=args.rpc_delay_ms
    )
    for r in (ser_f32, pipe_f32, ser_bf16, pipe_bf16, ser_net,
              pipe_net):
        print(json.dumps(r))

    grad_ratio = (
        ser_f32["push_gradient_bytes_per_step"]
        / max(1, ser_bf16["push_gradient_bytes_per_step"])
    )
    pull_ratio = (
        ser_f32["pull_embedding_bytes_per_step"]
        / max(1, ser_bf16["pull_embedding_bytes_per_step"])
    )
    print(json.dumps({
        "metric": "ps_wire_overlap",
        "value": round(
            pipe_net["steps_per_sec"]
            / max(1e-9, ser_net["steps_per_sec"]), 3
        ),
        "unit": "x steps/sec (pipelined vs serialized, bf16 wire, "
                "%.0fms emulated cross-host RPC latency)"
                % args.rpc_delay_ms,
        "vs_baseline": None,
        "detail": {
            "gradient_bytes_ratio_f32_over_bf16": round(grad_ratio, 2),
            "embedding_pull_bytes_ratio_f32_over_bf16": round(
                pull_ratio, 2
            ),
            "speedup_xhost_pipelined_vs_serialized": round(
                pipe_net["steps_per_sec"]
                / max(1e-9, ser_net["steps_per_sec"]), 3
            ),
            "speedup_loopback_pipelined_vs_serialized_f32": round(
                pipe_f32["steps_per_sec"]
                / max(1e-9, ser_f32["steps_per_sec"]), 3
            ),
            "speedup_loopback_pipelined_vs_serialized_bf16": round(
                pipe_bf16["steps_per_sec"]
                / max(1e-9, ser_bf16["steps_per_sec"]), 3
            ),
            "baseline": "self-relative: the serialized loop IS the "
                        "baseline; reference publishes no PS wire "
                        "numbers",
        },
    }))


if __name__ == "__main__":
    sys.exit(main())
