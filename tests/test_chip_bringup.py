"""What the chip bring-up relies on, as far as a CPU can check it: the
smoke refuses to pass without an accelerator and its parent stays off
JAX, worker slots get disjoint chips or are refused, the compile cache
can be placed from outside, a reference fallback in the compiled
attention mode is announced, kernels run per shard of the trainer's
batch axis, and the on-chip kernel checker's own code runs."""

import os
import subprocess
import sys
from unittest import mock

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_on_cpu_and_names_the_platform():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""          # no result line
    assert "platform is 'cpu'" in proc.stderr


def test_chip_smoke_parent_imports_without_jax():
    code = (
        "import sys; sys.path.insert(0, %r); import chip_smoke; "
        "bad = [m for m in sys.modules if m.split('.')[0] in "
        "('jax', 'jaxlib', 'flax', 'optax', 'elasticdl_tpu')]; "
        "assert not bad, bad" % REPO
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_chip_subsets_are_disjoint_and_cover_the_host():
    from elasticdl_tpu.master.worker_manager import chip_env_for_slot

    assert chip_env_for_slot(0, 1, 4) == {}   # one worker: every chip
    for workers, bounds in ((2, "2,1,1"), (4, "1,1,1")):
        envs = [chip_env_for_slot(s, workers, 4) for s in range(workers)]
        owned = [e["TPU_VISIBLE_CHIPS"].split(",") for e in envs]
        assert sorted(c for o in owned for c in o) == ["0", "1", "2", "3"]
        assert all(len(o) == 4 // workers for o in owned)
        assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {bounds}
        assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}


@pytest.mark.parametrize("workers,chips", [(2, 1), (3, 4), (8, 4)])
def test_unsatisfiable_chip_requests_are_refused(workers, chips):
    from elasticdl_tpu.master import worker_manager as wm

    with pytest.raises(ValueError, match="disjoint chips"):
        wm.chip_env_for_slot(0, workers, chips)
    # ... and at start-up, before any launch, unless held to the CPU.
    with mock.patch.object(wm, "count_host_tpu_chips", return_value=chips):
        with pytest.raises(ValueError, match="disjoint chips"):
            wm.ProcessWorkerBackend(num_workers=workers,
                                    env={"JAX_PLATFORMS": "tpu"})
        wm.ProcessWorkerBackend(num_workers=workers,
                                env={"JAX_PLATFORMS": "cpu"})


def test_compile_cache_placement(monkeypatch):
    import jax

    from elasticdl_tpu.utils import device

    writes = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: writes.append((key, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert device.place_compile_cache() == "/somewhere/else"
    assert writes == []                       # placed from outside
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_cache")
    assert device.place_compile_cache() == want
    assert writes == [("jax_compilation_cache_dir", want)]


def test_reference_fallback_in_compiled_mode_is_announced():
    import jax.numpy as jnp

    from elasticdl_tpu.ops import flash_attention as fa

    q = jnp.asarray(np.random.RandomState(0).randn(1, 2, 100, 48),
                    jnp.float32)
    fa._announce_once.cache_clear()
    with mock.patch.object(fa.logger, "warning") as warning:
        fa.flash_attention(q, q, q, interpret=False)   # the "tpu" mode
        fa.flash_attention(q, q, q, interpret=False)   # once per shape
        fa.flash_attention(q, q, q, interpret=True)    # CPU test mode
    assert warning.call_count == 1
    message = warning.call_args[0][0] % warning.call_args[0][1:]
    assert message.startswith(fa.FALLBACK_PREFIX)
    assert "(1, 2, 100, 48)" in message and "seq 100" in message


def test_kernels_run_per_shard_of_the_declared_batch_axis(monkeypatch):
    """Under the trainer's batch axis a kernel runs inside a shard_map:
    same values and gradients as the direct call (the parameter
    cotangents are summed over the shards), and a batch the axis does
    not divide is refused with the cause named."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from elasticdl_tpu.ops import group_norm as gn
    from elasticdl_tpu.ops.batch_shard import batch_axis

    monkeypatch.setenv("ELASTICDL_FLASH", "interpret")
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(8, 16, 64), jnp.float32)
    scale = jnp.asarray(1 + 0.1 * rng.randn(64), jnp.float32)
    bias = jnp.asarray(0.1 * rng.randn(64), jnp.float32)

    def grads(x, scale, bias):
        return jax.grad(
            lambda *a: (gn.fused_group_norm(*a, 32, relu=True) ** 2).sum(),
            argnums=(0, 1, 2))(x, scale, bias)

    def sharded(x, scale, bias):
        with batch_axis(mesh, "data"):
            return grads(x, scale, bias)

    rows, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    got = jax.jit(sharded, in_shardings=(rows, whole, whole))(
        x, scale, bias)
    for g, w in zip(got, grads(x, scale, bias)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
    with batch_axis(mesh, "data"):
        with pytest.raises(ValueError, match="'data' of 4 shards"):
            gn.fused_group_norm(x[:6], scale, bias, 32)


def test_chip_check_tiny_mode_runs_the_checker(monkeypatch, capsys,
                                               tmp_path):
    """The on-chip kernel checker at toy shapes through the Pallas
    interpreter: its case list, comparisons, per-output tolerances and
    summary line run here, so a chip call is not spent finding a typo."""
    import json

    monkeypatch.syspath_prepend(REPO)
    # main() places the compile cache and, for --tiny, the GN mode:
    # with both already in the environment it changes nothing here.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("ELASTICDL_FLASH", "interpret")
    import chip_check

    assert chip_check.main([]) == 1           # full size: needs the chip
    assert "not tpu" in capsys.readouterr().err
    code = chip_check.main(
        ["--tiny", "flash_partial/B1.H2.T256.D64.causal1", "HW16.C512"])
    rows = [json.loads(line)
            for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    assert code == 0 and rows[-1]["ok"] and rows[-1]["cases"] == 2, rows
    errs = {r["case"].split("/")[0]: r["errs"] for r in rows if "case" in r}
    assert set(errs["flash_partial"]) == {"o", "lse", "dq", "dk", "dv"}
    assert set(errs["group_norm"]) == {"fwd", "dx", "dscale", "dbias"}
    # float32 accumulations are held to a float32 tolerance
    assert chip_check.TOLERANCES["dbias"] == 1e-4 > errs["group_norm"]["dbias"]
    assert chip_check.TOLERANCES["lse"] == 1e-4 > errs["flash_partial"]["lse"]
    assert chip_check.main(["--tiny", "no_such_case"]) == 1


def test_chip_check_tiny_mode_has_a_grouped_matmul_leg(monkeypatch, capsys,
                                                       tmp_path):
    """Skewed and empty groups, forward and both gradients, at the same
    bf16 tolerances as on the chip."""
    import json

    monkeypatch.syspath_prepend(REPO)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("ELASTICDL_FLASH", "interpret")
    import chip_check

    assert chip_check.main(["--tiny", "grouped_matmul"]) == 0
    rows = [json.loads(line)
            for line in capsys.readouterr().out.splitlines()
            if line.startswith('{"case"')]
    assert [r["case"].rsplit(".", 1)[1] for r in rows] == ["zipf", "empty"]
    for row in rows:
        assert set(row["errs"]) == {"fwd", "dlhs", "drhs"} and row["ok"]


def test_chip_check_tiny_mode_has_a_latent_attention_leg(monkeypatch,
                                                         capsys, tmp_path):
    """Forward, dq and dk in their two parts and dv against the jnp
    math; the one RoPE key's gradient as a head's part and as the
    heads' sum."""
    import json

    monkeypatch.syspath_prepend(REPO)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("ELASTICDL_FLASH", "interpret")
    import chip_check

    assert chip_check.main(["--tiny", "latent"]) == 0
    row, = [json.loads(line)
            for line in capsys.readouterr().out.splitlines()
            if line.startswith('{"case"')]
    assert row["case"] == "latent/B1.H2.T256.QK192.V128" and row["ok"]
    assert set(row["errs"]) == {"fwd", "dq", "dq_rope", "dk", "dk_rope",
                                "dk_rope_sum", "dv"}


def test_chip_check_tiny_mode_has_a_head_loss_leg(monkeypatch, capsys,
                                                  tmp_path):
    """Tied and untied, the loss and both gradients against float32
    logits, at the bf16 tolerances the chip is held to."""
    import json

    monkeypatch.syspath_prepend(REPO)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("ELASTICDL_FLASH", "interpret")
    import chip_check

    assert chip_check.main(["--tiny", "head_loss"]) == 0
    rows = [json.loads(line)
            for line in capsys.readouterr().out.splitlines()
            if line.startswith('{"case"')]
    assert [r["case"].rsplit(".", 1)[1] for r in rows] == ["untied", "tied"]
    for row in rows:
        assert set(row["errs"]) == {"loss", "dx", "dhead"} and row["ok"]


def test_chip_check_tiny_mode_has_a_remat_keep_leg(monkeypatch, capsys,
                                                   tmp_path):
    """An LM's loss and gradients through the trainer with every name
    kept against nothing kept, at the bf16 tolerances the chip is held
    to; the case says which names the room chose."""
    import json

    monkeypatch.syspath_prepend(REPO)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("ELASTICDL_FLASH", "interpret")
    import chip_check

    assert chip_check.main(["--tiny", "remat_keep"]) == 0
    rows = [json.loads(line)
            for line in capsys.readouterr().out.splitlines()
            if line.startswith('{"')]
    chosen = [r for r in rows if r.get("remat_keep") == "chosen"]
    assert chosen[0]["names"][:2] == ["flash_out", "flash_lse"]
    assert "ffn_up" in chosen[0]["names"]
    case = [r for r in rows if "case" in r]
    assert len(case) == 1 and case[0]["ok"]
    assert set(case[0]["errs"]) == {"keep_loss", "keep_grad", "keep_grad_l2"}
