"""The flash-attention and grouped-matmul kernels, and the head-and-loss
op, through the TPU's own compiler, for a v5e that is described and not
attached.

Interpret mode cannot see what Mosaic refuses (a slice off the tiling, a
transpose it has no lowering for, more scoped VMEM than a kernel may
use); this does, at the widths the benchmark's cells run, in a few
seconds and without chip time.  Nothing runs, so no result or time is
checked here: ``chip_check.py`` does that on the chip.  The topology is
described inside a fixture, never at import: only the worker that is
given this file loads the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from elasticdl_tpu.models import transformer as tfm
from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.ops import grouped_matmul as gm
from elasticdl_tpu.ops import head_loss as hl


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any refusal means no compiler
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("t,d,dtype,window", [
    (2048, 128, jnp.bfloat16, 0),     # the benchmark's cells: 1024 tile
    (2048, 64, jnp.bfloat16, 512),    # both edges, narrow head
    (1536, 256, jnp.float32, 0),      # wide f32 rows: the 512 tile
])
def test_flash_fwd_bwd_compile_for_v5e(one_chip, t, d, dtype, window):
    x = jax.ShapeDtypeStruct((1, 16, t, d), dtype, sharding=one_chip)
    static = (True, d ** -0.5, False, window)

    def fwd_bwd(q, k, v, g):
        out, res = fa._flash_fwd(q, k, v, *static)
        return out, fa._flash_bwd(*static, res, g)

    text = jax.jit(fwd_bwd).lower(x, x, x, x).compile().as_text()
    # forward, dq, dk-dv: three Mosaic calls, told apart downstream by
    # their result counts (3, 1, 2: benchmark/kernels/flash_attention.py)
    assert text.count('custom_call_target="tpu_custom_call"') == 3


@pytest.mark.parametrize("causal", [True, False])
def test_flash_partial_compiles_for_v5e(one_chip, causal):
    x = jax.ShapeDtypeStruct((1, 16, 2048, 64), jnp.bfloat16,
                             sharding=one_chip)
    text = jax.jit(
        lambda q, k, v: fa._flash_forward(
            q, k, v, causal, 0.125, False, normalize=False)
    ).lower(x, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("k,n,dtype", [
    (2048, 1024, jnp.bfloat16),   # olmoe1b7b.seq4096: gate and up
    (1024, 2048, jnp.bfloat16),   # ... and down
    (512, 384, jnp.float32),      # float32 rows, a width that is 3 x 128
])
def test_grouped_matmul_fwd_bwd_compile_for_v5e(one_chip, k, n, dtype):
    """M = 131,072 sorted rows over 64 groups: the forward, the input
    gradient (the same kernel on transposed weights) and the weight
    gradient, whole contraction resident, under the VMEM limit the
    kernels ask for."""
    rows, groups = 131072, 64
    assert gm._unfriendly(k, n, gm.row_tile(rows),
                          jnp.dtype(dtype).itemsize) == ""
    lhs = jax.ShapeDtypeStruct((rows, k), dtype, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((groups, k, n), dtype, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=one_chip)
    cot = jax.ShapeDtypeStruct((rows, n), dtype, sharding=one_chip)

    def fwd_bwd(lhs, rhs, sizes, cot):
        out, vjp = jax.vjp(
            lambda lhs, rhs: gm.grouped_matmul(lhs, rhs, sizes,
                                               interpret=False),
            lhs, rhs)
        return out, vjp(cot)

    text = jax.jit(fwd_bwd).lower(lhs, rhs, sizes, cot).compile().as_text()
    # told apart downstream by the names the calls carry
    # (benchmark/kernels/grouped_matmul.py)
    calls = [l.split(" = ")[0] for l in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    for name in ("gmm_nn", "gmm_nt", "gmm_tn"):
        assert len([c for c in calls if name in c]) == 1, (name, calls)


@pytest.mark.parametrize("b,t,tied", [
    (4, 4096, False),    # olmoe1b7b.seq4096: an untied head
    (8, 2048, True),     # olmo1b.seq2048: the tied embedding
])
def test_head_loss_holds_no_float32_logits_on_v5e(one_chip, b, t, tied):
    """Forward + backward at the benchmark's head shapes (E 2048,
    V 50,304): no float32 buffer of tokens x vocabulary elements, and
    under 4 GB of temporaries where ``next_token_loss`` over ``_head``'s
    float32 logits, compiled beside it, takes more than 4.5."""
    dim, vocab = 2048, 50304
    x = jax.ShapeDtypeStruct((b, t, dim), jnp.bfloat16, sharding=one_chip)
    head = jax.ShapeDtypeStruct((vocab, dim) if tied else (dim, vocab),
                                jnp.bfloat16, sharding=one_chip)
    tokens = jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=one_chip)
    weights = jax.ShapeDtypeStruct((b,), jnp.float32, sharding=one_chip)

    def separate(x, head, tokens, tied):
        logits = (x @ (head.T if tied else head)).astype(jnp.float32)
        return tfm.next_token_loss(logits, tokens)

    def compiled(loss):
        def f(x, head, tokens, weights):
            return (loss(x, head, tokens, tied) * weights).sum()
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1))).lower(
            x, head, tokens, weights).compile()

    from tools.head_loss_on_chip import entry_results

    op, parent = compiled(hl.head_loss), compiled(separate)
    logits = b * (t - 1) * vocab       # elements; a bf16 one is 2 B each
    big = lambda c: {dtype for _, _, _, results in entry_results(c.as_text())
                     for dtype, size in results if size >= 2 * logits}
    assert big(op) == {"bf16"}
    assert "f32" in big(parent)        # the check can see one
    assert op.memory_analysis().temp_size_in_bytes < 4e9
    assert parent.memory_analysis().temp_size_in_bytes > 4.5e9
