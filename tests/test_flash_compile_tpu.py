"""The flash-attention and grouped-matmul kernels, and the head-and-loss
op, through the TPU's own compiler, for a v5e that is described and not
attached.

Interpret mode cannot see what Mosaic refuses (a slice off the tiling, a
transpose it has no lowering for, more scoped VMEM than a kernel may
use); this does, at the widths the benchmark's cells run, in a few
seconds and without chip time.  Nothing runs, so no result or time is
checked here: ``chip_check.py`` does that on the chip.  Where the
cells' whole training steps are compiled ``test_step_compile_tpu.py``
says; what those files share is ``tests/tpu_compile.py``.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest

from elasticdl_tpu.models import transformer as tfm
from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.ops import grouped_matmul as gm
from elasticdl_tpu.ops import head_loss as hl
from elasticdl_tpu.ops import hyper_mix as hm
from elasticdl_tpu.ops import row_moves

from tests.tpu_compile import (  # noqa: F401 (one_chip: a fixture)
    V5E_LIMIT, _cell_step, _entry_ops, _model_params, _mosaic_calls,
    _moved_bytes, _names, _products, one_chip)


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
    # the forward and the one backward: two Mosaic calls
    assert text.count('custom_call_target="tpu_custom_call"') == 2


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


# -- the lfm2-24b-a2b cell's shapes (benchmark/configs/lfm2-24b-a2b.json) --


def test_short_conv_fwd_bwd_compile_for_v5e(one_chip):
    """4 sequences of 8,192 x 2,048 channels, three taps: two Mosaic
    calls, told apart downstream by the names they carry
    (benchmark/kernels/short_conv.py), every result 2-D."""
    from elasticdl_tpu.ops import short_conv as sc

    bcu = jax.ShapeDtypeStruct((4, 8192, 3 * 2048), jnp.bfloat16,
                               sharding=one_chip)
    cot = jax.ShapeDtypeStruct((4, 8192, 2048), jnp.bfloat16,
                               sharding=one_chip)
    w = jax.ShapeDtypeStruct((2048, 3), jnp.float32, sharding=one_chip)

    def fwd_bwd(bcu, w, cot):
        out, pull = jax.vjp(
            lambda bcu, w: sc.short_conv(bcu, w, interpret=False), bcu, w)
        return out, pull(cot)

    text = jax.jit(fwd_bwd).lower(bcu, w, cot).compile().as_text()
    calls = [l.strip() for l in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    assert len(calls) == 2, calls
    fwd = next(c for c in calls if "sconv_fwd" in c.split(" = ")[0])
    bwd = next(c for c in calls if "sconv_bwd" in c.split(" = ")[0])
    assert " bf16[32768,2048]" in " " + fwd.split(" custom-call(")[0]
    assert "bf16[32768,6144]" in bwd and "f32[512,2048]" in bwd


@pytest.mark.parametrize("kv_heads", [32, 8])
def test_flash_compiles_at_head_size_64_and_8192_positions(one_chip,
                                                           kv_heads):
    """``lfm2-24b-a2b.seq8192``'s attention, 4 sequences of 8,192 at 32
    heads of 64: with K and V at the query heads, and as the cell runs
    it since PR 42, at their own 8 (a K/V head's float32 dk and dv
    planes resident, 64 lanes padded to 128: 4 MB each)."""
    x, kv = (jax.ShapeDtypeStruct((4, heads, 8192, 64), jnp.bfloat16,
                                  sharding=one_chip)
             for heads in (32, kv_heads))
    static = (True, 64 ** -0.5, False, 0)

    def fwd_bwd(q, k, v, g):
        out, res = fa._flash_fwd(q, k, v, *static)
        return out, fa._flash_bwd(*static, res, g)

    compiled = jax.jit(fwd_bwd).lower(x, kv, kv, x).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 2
    dq, dk, dv = compiled.out_info[1]
    assert (dq.shape, dk.shape, dv.shape) == (x.shape, kv.shape, kv.shape)


def test_grouped_matmul_over_a_share_compiles_for_v5e(one_chip):
    """8 held experts' groups at the head of 131,072 sorted rows, the
    rows past them zeroed (``zero_tail``): the three kernels."""
    rows, k, n, held = 131072, 2048, 1536, 8
    lhs = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip)
    cot = jax.ShapeDtypeStruct((rows, n), jnp.bfloat16, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((held, k, n), jnp.bfloat16,
                               sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((held,), jnp.int32, sharding=one_chip)

    def fwd_bwd(lhs, rhs, sizes, cot):
        out, pull = jax.vjp(lambda lhs, rhs: gm.grouped_matmul(
            lhs, rhs, sizes, interpret=False, zero_tail=True), lhs, rhs)
        return out, pull(cot)

    text = jax.jit(fwd_bwd).lower(lhs, rhs, sizes, cot).compile().as_text()
    calls = [l.split(" = ")[0] for l in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    for name in ("gmm_nn", "gmm_nt", "gmm_tn"):
        assert len([c for c in calls if name in c]) == 1, (name, calls)


# -- a held share's row moves at three share cells' shapes --


@pytest.mark.parametrize("n,w,k,bound", [
    (32768, 2048, 4, 32768),      # lfm2-24b-a2b.seq8192
    (16384, 2560, 6, 49152),      # smallthinker-21b-a3b.seq16384
    (16384, 2688, 6, 12288),      # nemotron-3-nano-30b-a3b.seq16384
    (16384, 4096, 8, 6656),       # solar-open2-250b.seq16384
])
def test_the_row_kernel_compiles_for_v5e(one_chip, n, w, k, bound):
    """The four row moves of a share's block (``ops/row_moves.py``: a
    gather of bfloat16 rows, the weighted token-order sum into float32,
    its pullback from float32 rows with the row dots, the gather's
    pullback) through Mosaic: the one-row DMAs from ``[R, 1, words]``,
    the SMEM index blocks, the VMEM the slots take.  Each move is a
    ``rows_pack`` and one named call, and no XLA op makes a float32
    array of the buffer's rows.  The third shape's 16-bit row is 21
    lane tiles, an odd number: its words are 11 tiles, the low halves'
    last padded, and every slice of both kernels still starts and ends
    on a tile (3,072 slots of 1,408 words: 17.3 MB of an 18 MiB
    budget).  The fourth is the thinnest share's and the widest row,
    eight slots a token: the sums' seven bodies (which one from
    SMEM) beside the widest float32 gather."""
    shape = lambda rows, cols, dtype: jax.ShapeDtypeStruct(
        (rows, cols), dtype, sharding=one_chip)
    x, y = shape(n, w, jnp.bfloat16), shape(bound, w, jnp.bfloat16)
    g = shape(n, w, jnp.float32)
    tok, pos = shape(bound, 1, jnp.int32), shape(n, k, jnp.int32)
    gates, scale = shape(n, k, jnp.float32), shape(bound, 1, jnp.float32)

    def moves(x, y, g, tok, pos, gates, scale):
        live = (tok[:, 0] < n).sum()
        return (row_moves.row_sum(x, tok, interpret=False)[0],
                row_moves.row_sum(y, pos, gates, live=live,
                                  out_dtype=jnp.float32,
                                  interpret=False)[0],
                row_moves.row_sum(g, tok, scale, other=y,
                                  out_dtype=jnp.bfloat16, interpret=False),
                row_moves.row_sum(y, pos, live=live, interpret=False)[0])

    text = jax.jit(moves).lower(
        x, y, g, tok, pos, gates, scale).compile().as_text()
    calls = [l.split(" = ")[0].strip().lstrip("%").rsplit(".", 1)[0]
             for l in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    assert sorted(calls) == ["rows_gather"] * 2 + ["rows_pack"] * 4 + [
        "rows_sum"] * 2, calls
    # f32[n, w] is the sum's result (and n == bound in one cell)
    floats = re.findall(r"= f32\[%d,%d\]\S* (\w[\w-]*)\(" % (bound, w), text)
    assert set(floats) <= {"custom-call", "get-tuple-element",
                           "parameter"}, floats


# -- the smallthinker-21b-a3b cell's shapes
# (benchmark/configs/smallthinker-21b-a3b.json), and the trinity-mini
# cell's (benchmark/configs/trinity-mini.json) --


def _flash_calls(q_shape, window, one_chip, kv_heads=None):
    """Names of the Mosaic calls of a forward and backward at these
    shapes (K and V at ``kv_heads``; None: the queries'), compiled for
    the described chip."""
    b, h, t, d = q_shape
    x, kv = (jax.ShapeDtypeStruct((b, heads, t, d), jnp.bfloat16,
                                  sharding=one_chip)
             for heads in (h, kv_heads or h))
    static = (True, q_shape[-1] ** -0.5, False, window)

    def fwd_bwd(q, k, v, g):
        out, res = fa._flash_fwd(q, k, v, *static)
        return out, fa._flash_bwd(*static, res, g)

    text = jax.jit(fwd_bwd).lower(x, kv, kv, x).compile().as_text()
    return sorted(l.split(" = ")[0].strip().lstrip("%").rsplit(".", 1)[0]
                  for l in text.splitlines()
                  if 'custom_call_target="tpu_custom_call"' in l)


@pytest.mark.parametrize("kv_heads", [None, 4])
@pytest.mark.parametrize("heads,window", [(28, 0), (28, 4096), (32, 0),
                                          (32, 2048)])
def test_flash_compiles_at_16384_positions_full_and_windowed(
        one_chip, heads, window, kv_heads):
    """One sequence of 16,384, 28 heads of 128 (a window of 4,096, a
    band 32 sub-tiles wide) and 32 heads of 128 (a window of 2,048, 16
    wide), K and V at the query heads and, as the two cells run them
    since PR 42, at their own 4: the forward and the one backward under
    both tile plans, a head's 8 MB float32 dq and its output block's
    two buffers (and a K/V head's two 8 MB float32 planes with their
    blocks' buffers: 48 MB in all) inside the VMEM limit the call sets,
    each call carrying its kernel's name and its window's into the
    compiled program (benchmark/kernels/banded_attention.py tells the
    forward by it)."""
    assert fa._backward_plan(16384, 128, 0, 2) == ("fused", "dq_acc_mb=16")
    assert fa._backward_plan(16384, 128, 0, 2, heads // 4) == (
        "fused", "dq_acc_mb=16 dkv_acc_mb=32")
    tail = "_w%d" % window if window else ""
    assert _flash_calls((1, heads, 16384, 128), window, one_chip,
                        kv_heads) == ["flash_bwd" + tail, "flash_fwd" + tail]


@pytest.mark.parametrize("t,kv_heads,calls", [
    (65536, 2, ["flash_bwd", "flash_fwd"]),
    (131072, 2, ["flash_dkv", "flash_dq", "flash_fwd"]),
    (32768, 1, ["flash_dkv", "flash_dq", "flash_fwd"])])
def test_the_longest_dq_that_fits_vmem_compiles_and_the_next_is_a_pair(
        one_chip, t, kv_heads, calls):
    """65,536 positions at head size 128: a head's dq takes 64 MB of the
    96 the fused call may hold, and Mosaic takes it; at 131,072 the
    backward is the dk-dv pass and the dq pass, a tile's each; so it is
    at 32,768 with two query heads to a K/V head, whose planes (64 MB)
    do not fit beside dq's 32: both passes read K/V head ``head // 2``
    and the dk-dv pass's results are summed outside."""
    assert _flash_calls((1, 2, t, 128), 0, one_chip, kv_heads) == calls


def test_latent_attention_compiles_at_the_cells_shapes(one_chip):
    """``kanana-2-30b-a3b.seq16384``'s attention: one sequence of
    16,384, 32 heads, scores over 128 + 64 and values of 128, the RoPE
    key one [T, 64] plane: the forward and the one backward through
    Mosaic at the 1,024 tile (a head's dq kept transposed, 12 MB of
    float32 beside its two outputs' buffers, under the VMEM limit the
    call sets), each call carrying its kernel's name and the widths
    (benchmark/kernels/latent_attention.py tells the forward by it),
    among the backward's results dq's RoPE part and each head's part of
    the RoPE key's gradient in float32."""
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16,
                                               sharding=one_chip)
    heads, t = 32, 16384
    wide, rope = shape(1, heads, t, 128), shape(1, heads, t, 64)
    k_rope = shape(1, t, 64)
    static = (True, 192 ** -0.5, False, 0)
    assert fa.latent_mode(t, 128, 64, 128, interpret=False) == (
        "tpu", 1024, "")

    def fwd_bwd(q_nope, q_rope, k_nope, k_rope, v, g):
        out, res = fa._latent_fwd(q_nope, q_rope, k_nope, k_rope, v,
                                  *static)
        return out, fa._latent_bwd(*static, res, g)

    text = jax.jit(fwd_bwd).lower(
        wide, rope, wide, k_rope, wide, wide).compile().as_text()
    calls = {l.split(" = ")[0].strip().lstrip("%").rsplit(".", 1)[0]:
             l.split(" custom-call(")[0]
             for l in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l}
    assert sorted(calls) == ["flash_bwd_qk192_v128", "flash_fwd_qk192_v128"]
    assert "bf16[32,16384,64]" in calls["flash_bwd_qk192_v128"]
    assert "f32[32,16384,64]" in calls["flash_bwd_qk192_v128"]
    assert fa._backward_plan(t, 128, 64, 2) == ("fused", "dq_acc_mb=28")


def _head_sized_ops(text, rows, heads):
    """(standalone, matmul fusions, Mosaic calls): the instructions of
    a compiled program, outside every fusion's body, with a result of
    rows x heads x width elements (a dimension ``rows``, one ``heads``,
    width 32 and up), by what makes them.  Standalone is what is
    neither a Mosaic call nor a fusion around a convolution or dot:
    the transposes, pads, slices, copies and elementwise fusions that
    move an activation and compute nothing a matmul could not write
    itself."""
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            bodies[name].append(line)
    called = lambda line: re.search(r"calls=%?([\w.\-]+)", line).group(1)
    fused = {called(line) for lines in bodies.values() for line in lines
             if " fusion(" in line}
    matmul = {name for name in fused if any(re.search(
        r" (convolution|dot)\(", line) for line in bodies[name])}
    found = {"standalone": [], "matmul": [], "mosaic": []}
    for name, lines in bodies.items():
        if name in fused:
            continue
        for line in lines:
            op = re.match(
                r"\s*(?:ROOT )?%?(\S+) = \(?(.*?)\)? ([\w-]+)\(", line)
            if not op or op.group(3) in (
                    "parameter", "get-tuple-element", "tuple", "bitcast",
                    "while", "conditional", "call", "constant"):
                continue
            sized = []
            for dims in re.findall(r"\w+\[([\d,]+)\]", op.group(2)):
                dims = [int(d) for d in dims.split(",")]
                size = math.prod(dims)
                if (rows in dims and heads in dims
                        and size >= 32 * rows * heads
                        and not size % (rows * heads)):
                    sized.append(dims)
            if not sized:
                continue
            if "tpu_custom_call" in line:
                kind = "mosaic"
            elif op.group(3) == "custom-call":
                continue              # a buffer's allocation, no move
            elif op.group(3) == "fusion" and called(line) in matmul:
                kind = "matmul"
            else:
                kind = "standalone"
            found[kind].append((op.group(1), sized))
    return found["standalone"], found["matmul"], found["mosaic"]


def test_a_latent_layer_moves_no_activation_between_matmuls_and_kernels(
        one_chip, monkeypatch):
    """One latent-attention layer of ``kanana-2-30b-a3b.seq16384``
    (``_latent_mix`` on one sequence of 16,384: the five projections,
    RoPE, the two kernels, ``W_o``), forward + backward through the
    TPU's compiler: between ``W_q`` / ``W_kv_b`` / ``W_o`` and the
    flash calls **no** instruction stands alone whose result has rows x
    heads x width elements: every such array is written by a matmul
    fusion or a Mosaic call in the layout its reader takes.  Seven
    matmul fusions write one: q_nope, k_nope, v and the RoPE part of
    ``W_q``'s product, RoPE's 64 x 64 permutation product forward and
    its transpose backward, ``W_o``'s backward (dO with the kernels'
    row sums); two Mosaic calls, the forward and the one backward.

    The parent (d5e277b, one product a weight on [T, H, 192] / [T, H,
    256], sliced, turned and transposed) compiled to **12** standalone
    ops here: two slice-and-transpose fusions and RoPE's two forward,
    the output's token-major copy, RoPE's three backward, two pads and
    two copies of the cotangents; 36 in the whole step where this tree
    has 5 (three loads and a store of kept values on the scan's stack,
    a copy in the leading layer).  On the chip those 12 were 51 ms of a
    785 ms step (PERF.md section 6, PR 38).  No time is read here: a
    count of what the compiler emits is what can be held without a
    chip."""
    from elasticdl_tpu.ops.mode import SWITCH

    monkeypatch.setenv(SWITCH, "tpu")     # the ops' own choice on a chip
    spec = tfm.model_spec(**_model_params("kanana-2-30b-a3b"))
    cfg = spec.config
    rows, heads = 16384, cfg.num_heads
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    layer = params["layers"]["lead"]["0"]
    on_chip = lambda a, dtype=None: jax.ShapeDtypeStruct(
        a.shape, dtype or a.dtype, sharding=one_chip)
    w = {name: on_chip(layer[name])
         for name in ("wq", "w_kv_a", "kv_norm", "w_kv_b", "wo")}
    h = on_chip(jax.ShapeDtypeStruct((1, rows, cfg.dim), jnp.bfloat16))

    def fwd_bwd(h, w, g):
        out, vjp = jax.vjp(
            lambda h, w: tfm._latent_mix(h, w, cfg, jnp.arange(rows),
                                         cfg.kinds[0]), h, w)
        return out, vjp(g)

    text = jax.jit(fwd_bwd).lower(h, w, h).compile().as_text()
    standalone, matmul, mosaic = _head_sized_ops(text, rows, heads)
    assert standalone == [], standalone
    assert len(mosaic) == 2, mosaic
    assert len(matmul) <= 7, matmul


@pytest.mark.parametrize("config,kind,query_sized,matmuls", [
    ("trinity-mini", "w", 5, 5),           # 32 on 4: QK norm, RoPE, gate
    ("trinity-mini", "a", 6, 3),           # the NoPE layer
    ("smallthinker-21b-a3b", "w", 1, 4),   # 28 on 4: RoPE
    ("smallthinker-21b-a3b", "a", 1, 2),   # NoPE, nothing but matmuls
])
def test_a_gqa_layer_moves_no_activation_between_matmuls_and_kernels(
        one_chip, monkeypatch, config, kind, query_sized, matmuls):
    """One attention layer of the two grouped-query cells at 16,384
    positions (``_attention_mix`` on one sequence: ``wq`` / ``wk`` /
    ``wv``, the QK norm and RoPE where the layer has them, the two
    kernels, the gate, ``wo``), forward + backward through the TPU's
    compiler, the form PR 38 gave latent attention's
    (``test_a_latent_layer_moves_...``): the two Mosaic calls take q at
    32 / 28 heads and K, V at 4 (the backward's dk and dv leave at 4:
    nothing is repeated to the query heads or summed over a group
    outside the kernels), and the forward holds no instruction that
    stands alone with a rows x query heads x head size result but the
    gate's multiply in ``trinity-mini``'s NoPE layer: q, k, v and the
    gate are written by matmul fusions, QK norm and RoPE inside them.
    What still stands alone is the backward's (``query_sized``, held as
    the most there may be): the pass over dO and O that makes
    ``delta`` (the parent has it too) and, where elementwise work
    stands between a kernel's cotangent and a weight gradient's matmul
    (the QK norm's and the gate's backward in ``trinity-mini``: a
    fusion and a copy each; one copy of dq in ``smallthinker``), XLA
    turns that operand to ``[H, D, T]`` for the matmul: a layout the
    TPU compiler prefers for a contraction over T whenever the
    producer's layout is its to choose (a Mosaic call's result, whose
    layout is fixed, it reads in place).

    The parent (0ecece1: ``jnp.repeat`` of K and V, q / k / v / the
    output transposed between ``[B, T, H, D]`` and ``[B, H, T, D]``,
    RoPE by slices and a concatenate) compiled these four layers to 9,
    5, 19 and 14 instructions with a result of that size, K and V at
    the query heads among them: PERF.md section 6, PR 42, has what they
    took on the chip.  No time is read here."""
    from elasticdl_tpu.ops.mode import SWITCH

    monkeypatch.setenv(SWITCH, "tpu")     # the ops' own choice on a chip
    spec = tfm.model_spec(**_model_params(config))
    cfg = spec.config
    rows, heads, kv_heads = 16384, cfg.num_heads, cfg.kv_heads
    assert heads // kv_heads in (7, 8)
    the_kind = next(k for k in cfg.kinds if k.op == "a" and bool(
        k.window) == (kind == "w"))
    names = ["wq", "wk", "wv", "wo"] + (
        ["q_norm", "k_norm"] if cfg.qk_norm else []) + (
        ["w_attn_gate"] if cfg.attn_gate else [])
    hd = cfg.head_dim
    shapes = {"wq": (cfg.dim, heads * hd), "wk": (cfg.dim, kv_heads * hd),
              "wv": (cfg.dim, kv_heads * hd), "wo": (heads * hd, cfg.dim),
              "q_norm": (hd,), "k_norm": (hd,),
              "w_attn_gate": (cfg.dim, heads * hd)}
    w = {name: jax.ShapeDtypeStruct(shapes[name], jnp.float32,
                                    sharding=one_chip) for name in names}
    h = jax.ShapeDtypeStruct((1, rows, cfg.dim), jnp.bfloat16,
                             sharding=one_chip)

    def fwd_bwd(h, w, g):
        out, vjp = jax.vjp(
            lambda h, w: tfm._attention_mix(
                h, w, cfg, None, jnp.arange(rows), the_kind)[0], h, w)
        return out, vjp(g)

    text = jax.jit(fwd_bwd).lower(h, w, h).compile().as_text()
    standalone, matmul, mosaic = _head_sized_ops(text, rows, heads)
    assert len(mosaic) == 2, mosaic
    # a result of exactly rows x query heads x head size
    # (``slice-start``: XLA's prefetch of the kernel's output into
    # fast memory for ``wo``'s matmul, no layout's move)
    standalone = [(name, dims) for name, sized in standalone
                  for dims in sized
                  if math.prod(dims) == rows * heads * hd
                  and not name.startswith("slice-start")]
    assert len(standalone) <= query_sized, standalone
    # nothing at the repeat's shape, [., K/V heads, group, .]
    repeated = [dims for dims in re.findall(r"\[([\d,]+)\]", text)
                if str(rows) in dims.split(",") and re.search(
                    r"(^|,)%d,%d(,|$)" % (kv_heads, heads // kv_heads), dims)]
    assert not repeated, repeated[:3]
    assert len(matmul) <= matmuls, matmul
    calls = [l for l in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    bwd = next(l for l in calls if "flash_bwd" in l.split(" = ")[0])
    wide, narrow = ("bf16[%d,%d,%d]" % (n, rows, hd)
                    for n in (heads, kv_heads))
    # dk, dv at the K/V heads, dq at the query heads
    assert bwd.split(" custom-call(")[0].count(narrow) == 2, bwd
    assert bwd.split(" custom-call(")[0].count(wide) == 1, bwd


def test_head_loss_compiles_for_a_vocabulary_off_the_lanes(one_chip):
    """The head-and-loss op at [16,384, 37,984] (a quarter of 151,936:
    no multiple of 128), untied, hidden 2,560: forward + backward, the
    one tokens x vocabulary buffer in bfloat16."""
    dim, vocab, t = 2560, 37984, 16384
    assert vocab % 128
    x = jax.ShapeDtypeStruct((1, t, dim), jnp.bfloat16, sharding=one_chip)
    head = jax.ShapeDtypeStruct((dim, vocab), jnp.bfloat16,
                                sharding=one_chip)
    tokens = jax.ShapeDtypeStruct((1, t), jnp.int32, sharding=one_chip)

    def f(x, head, tokens):
        return hl.head_loss(x, head, tokens, False).sum()

    compiled = jax.jit(jax.value_and_grad(f, argnums=(0, 1))).lower(
        x, head, tokens).compile()

    from tools.head_loss_on_chip import entry_results

    logits = (t - 1) * vocab
    big = {dtype for _, _, _, results in entry_results(compiled.as_text())
           for dtype, size in results if size >= 2 * logits}
    assert big == {"bf16"}
    assert compiled.memory_analysis().temp_size_in_bytes < 3.2e9


# -- the olmo-hybrid-7b cell's shapes (benchmark/configs/olmo-hybrid-7b.json)


@pytest.mark.parametrize("chunk", [64, 128])
def test_the_delta_scan_compiles_at_the_cells_shape(one_chip, chunk):
    """15 heads x 16,384 x 96 | 192, one sequence: ``gdn_fwd`` (a block
    of 5 heads' float32 states resident, the inverses of two chunks of
    64 by 10 float32 matmuls of [128, 128] at the highest precision, of
    one of 128 by 12) and ``gdn_bwd`` (the states' cotangents resident,
    the chunks walked backwards) for a described v5e, under their names
    and the VMEM limit the calls set; the forward writes the output, a
    float32 state a chunk a head and then the inverses in the compute
    dtype, a row of them 128 wide, which the backward takes as an
    operand; the backward writes dq, dk, dv and the two gates'
    cotangents."""
    from elasticdl_tpu.ops import gated_delta as gd

    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    q, v = shape(1, 15, 16384, 96), shape(1, 15, 16384, 192)
    g = shape(1, 15, 16384, dtype=jnp.float32)

    def fwd_bwd(q, k, v, g, beta, cot):
        out, pull = jax.vjp(lambda *a: gd.gated_delta(
            *a, chunk=chunk, interpret=False), q, k, v, g, beta)
        return out, pull(cot)

    assert gd.delta_mode(16384, 96, 192, chunk, interpret=False) == (
        "tpu", "")
    assert gd.VMEM_LIMIT <= 64 * 2 ** 20
    text = jax.jit(fwd_bwd).lower(q, q, v, g, g, v).compile().as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 2, calls
    fwd = next(c for c in calls if "gdn_fwd" in c.split(" = ")[0])
    bwd = next(c for c in calls if "gdn_bwd" in c.split(" = ")[0])
    chunks = 16384 // chunk
    results = lambda call: call.split(" custom-call(")[0]
    inverse = "bf16[15,128,%d,128]" % chunk
    assert re.findall(r"\w+\[[\d,]+\]", results(fwd)) == [
        "bf16[15,16384,192]", "f32[15,%d,96,192]" % chunks, inverse]
    # the backward's seven operands: the forward's third result is one
    operands = re.findall(r"%[\w.]+", bwd.split(" custom-call(")[1].split(
        "), custom_call_target")[0])
    made = [l for l in text.splitlines() for name in operands
            if l.strip().startswith(name + " = ")]
    assert len(operands) == 7 and any(
        inverse in l and "get-tuple-element(" in l and "index=2" in l
        for l in made), made
    assert results(bwd).count("bf16[15,16384,96]") == 2       # dq, dk
    assert "bf16[15,16384,192]" in results(bwd)               # dv
    assert "f32[15,%d,2,%d]" % (chunks, chunk) in results(bwd)


def test_the_convolution_with_a_silu_compiles_at_the_cells_shape(one_chip):
    """One sequence of 16,384 x 5,760 channels (15 heads' q, k and v
    side by side), four taps: two Mosaic calls under names the gated
    convolution's reader does not match."""
    from elasticdl_tpu.ops import short_conv as sc

    x = jax.ShapeDtypeStruct((1, 16384, 5760), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((5760, 4), jnp.float32, sharding=one_chip)

    def fwd_bwd(x, w, cot):
        out, pull = jax.vjp(
            lambda x, w: sc.conv_silu(x, w, interpret=False), x, w)
        return out, pull(cot)

    assert sc.tiles(16384, 5760) == (512, 128)
    calls = _mosaic_calls(jax.jit(fwd_bwd).lower(x, w, x).compile().as_text())
    # (under ``jax.vjp`` XLA wraps the names: jvp_.._fwd_, transpose_..)
    assert len(calls) == 2 and any(
        "sconv_silu_fwd" in c.split(" = ")[0] for c in calls), calls
    bwd = next(c for c in calls if "sconv_silu_bwd" in c.split(" = ")[0])
    assert "bf16[16384,5760]" in bwd and "f32[256,5760]" in bwd


def test_flash_compiles_at_15_heads_of_128_on_as_many_kv_heads(one_chip):
    """The cell's full layer: 15 query heads on 15 K/V heads at 16,384,
    the first equal count at that length (a group of 1: no group sum in
    the fused backward)."""
    assert fa._backward_plan(16384, 128, 0, 2, 1) == ("fused",
                                                     "dq_acc_mb=16")
    assert _flash_calls((1, 15, 16384, 128), 0, one_chip, 15) == [
        "flash_bwd", "flash_fwd"]


# -- the solar-open2-250b cell's shapes (benchmark/configs/solar-open2-250b.json)


@pytest.mark.parametrize("floor", [0.0, -5.0], ids=["no_floor", "floor-5"])
def test_the_vector_decay_scan_compiles_at_the_cells_shape(one_chip, floor):
    """8 heads x 16,384 x 128 | 128, one sequence, a log decay a channel
    of the key: ``kda_fwd`` (a block of 4 heads' float32 states
    resident; a pack's two score matrices by 19 products of [256, 128]
    x [128, 128], every exponent against a reference row, or by the 4
    of [64, 128] x [128, 128] a promised floor of -5 allows, the
    ``ling-3.0-flash`` cell's; the inverses by the scalar decay's ten
    joins) and ``kda_bwd`` for a described v5e, under names the scalar decay's reader (``gdn_(fwd|bwd)``) does
    not match and the VMEM limit the calls set.  The forward writes what
    ``gdn_fwd`` writes; the backward dq, dk, dv, the decay's cotangent a
    channel in float32 and the write strength's."""
    from elasticdl_tpu.ops import gated_delta as gd

    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    q = shape(1, 8, 16384, 128)
    g = shape(1, 8, 16384, 128, dtype=jnp.float32)
    beta = shape(1, 8, 16384, dtype=jnp.float32)

    def fwd_bwd(q, k, v, g, beta, cot):
        out, pull = jax.vjp(lambda *a: gd.gated_delta(
            *a, interpret=False, floor=floor), q, k, v, g, beta)
        return out, pull(cot)

    assert gd.pairs_of(floor) == ("block" if floor else "columns")
    assert gd.delta_mode(16384, 128, 128, interpret=False, vector=True) == (
        "tpu", "")
    assert gd.delta_mode(16384, 128, 128, 40, interpret=False,
                         vector=True)[0] == "off"
    text = jax.jit(fwd_bwd).lower(q, q, q, g, beta, q).compile().as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 2, calls
    fwd = next(c for c in calls if "kda_fwd" in c.split(" = ")[0])
    bwd = next(c for c in calls if "kda_bwd" in c.split(" = ")[0])
    assert not any(re.search(r"gdn_(fwd|bwd)", c) for c in calls)
    results = lambda call: call.split(" custom-call(")[0]
    assert re.findall(r"\w+\[[\d,]+\]", results(fwd)) == [
        "bf16[8,16384,128]", "f32[8,256,128,128]", "bf16[8,128,64,128]"]
    assert results(bwd).count("bf16[8,16384,128]") == 3       # dq, dk, dv
    assert "f32[8,16384,128]" in results(bwd)                 # dg a channel
    assert "f32[8,256,1,64]" in results(bwd)                  # dbeta


# -- the hyper-connection kernels (PR 54) ------------------------------------

HC_ROWS, HC_N, HC_C = 8192, 4, 3584


def test_the_four_calls_compile_at_the_cells_shape(one_chip):
    """Two sequences of 4,096 tokens of a stream 4 x 3,584 wide,
    bfloat16: one call each of ``hc_pre_fwd`` and ``hc_post_fwd``
    forward, ``hc_post_bwd`` and ``hc_pre_bwd`` backward, in 128-row
    tiles under the 64 MB of VMEM the calls ask for; the maps between
    one call each way (PR 55), 1,024 tokens a block, and no loop of the
    program's for their 20 rounds."""
    on_chip = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    x = on_chip((2, 4096, HC_N * HC_C), jnp.bfloat16)
    y = on_chip((2, 4096, HC_C), jnp.bfloat16)
    phi = on_chip((HC_N * HC_C, hm.columns(HC_N)), jnp.float32)
    alpha = on_chip((3,), jnp.float32)
    bias = on_chip((hm.columns(HC_N),), jnp.float32)
    assert hm.hyper_mode(HC_ROWS, HC_N, HC_C, interpret=False) == (
        "tpu", 128, "")
    assert hm.maps_mode(HC_ROWS, HC_N, hm.LANES, interpret=False) == (
        "tpu", 1024, "")

    def loss(x, phi, alpha, bias, y):
        u, through, maps, err = hm.pre(x, phi, alpha, bias, HC_N, 20, 1e-6,
                                       1e-6, interpret=False)
        out = hm.post(through, u * y, maps, HC_N, interpret=False)
        return jnp.square(out.astype(jnp.float32)).sum() + err

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        x, phi, alpha, bias, y).compile()
    text = compiled.as_text()
    assert _names(text) == {
        "hc_pre_fwd": 1, "hc_post_fwd": 1, "hc_post_bwd": 1,
        "hc_pre_bwd": 1, "hc_maps_fwd": 1, "hc_maps_bwd": 1}
    assert " while(" not in text
    # the stream in, its gradient out, and under two streams between
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < 2.2 * HC_ROWS * HC_N * HC_C * 2


def test_a_layers_fused_pair_compiles_at_the_cells_shape(one_chip):
    """A layer of two sublayers at the cell's shape: its first read, the
    fused write-and-read between the sublayers (``hc_post_pre_fwd``,
    and ``hc_pre_post_bwd`` with four wide blocks twice over and the
    summed cotangent in VMEM, 128 rows inside the 64 MB the calls ask
    for), its last write; X' is neither read back forward nor its
    cotangent written backward."""
    on_chip = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    x = on_chip((2, 4096, HC_N * HC_C), jnp.bfloat16)
    y = on_chip((2, 4096, HC_C), jnp.bfloat16)
    phi = on_chip((HC_N * HC_C, hm.columns(HC_N)), jnp.float32)
    alpha = on_chip((3,), jnp.float32)
    bias = on_chip((hm.columns(HC_N),), jnp.float32)
    assert hm.back_tile(HC_ROWS, HC_N, HC_C, 2, 128) == 128

    def loss(x, y, phi, alpha, bias, phi2, alpha2, bias2):
        mix = (HC_N, 20, 1e-6, 1e-6)
        u, through, maps, err = hm.pre(x, phi, alpha, bias, *mix,
                                       interpret=False)
        u2, x2, maps2, err2 = hm.post_pre(through, u * y, maps, phi2, alpha2,
                                          bias2, *mix, "attn_stream",
                                          interpret=False)
        out = hm.post(x2, u2 * y, maps2, HC_N, interpret=False)
        return jnp.square(out.astype(jnp.float32)).sum() + err + err2

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(8)))).lower(
        x, y, phi, alpha, bias, phi, alpha, bias).compile()
    text = compiled.as_text()
    assert _names(text) == {
        "hc_pre_fwd": 1, "hc_post_pre_fwd": 1, "hc_post_fwd": 1,
        "hc_post_bwd": 1, "hc_pre_post_bwd": 1, "hc_pre_bwd": 1,
        "hc_maps_fwd": 2, "hc_maps_bwd": 2}
    fwd, = [c for c in _mosaic_calls(text) if "hc_post_pre_fwd" in c]
    bwd, = [c for c in _mosaic_calls(text) if "hc_pre_post_bwd" in c]
    results = lambda call: re.findall(
        r"\w+\[[\d,]+\]", call.split(" custom-call(")[0])
    assert results(fwd) == ["bf16[8192,14336]", "bf16[8192,3584]",
                            "f32[8192,128]"]
    assert results(bwd) == ["bf16[8192,14336]", "bf16[8192,3584]"] + [
        "f32[8192,128]"] * 3
    # the stream in and its gradient out, X', X'' and the cotangent that
    # the last write's backward hands the pair: under four streams
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < 3.7 * HC_ROWS * HC_N * HC_C * 2


# -- the state-space hybrid's kernels and count (PR 61) -----------------------

NEMOTRON3_PARAMETERS = 666963456


def test_the_state_space_scan_compiles_at_the_cells_shape(one_chip):
    """One sequence of 16,384 tokens, 64 heads of 64 over a state of 128,
    B and C in 8 groups, token-major as the projection leaves them: one
    ``ssd_fwd`` that writes y and the chunk-start states, one
    ``ssd_bwd`` that writes dx, dB and dC (once a group) and the gates'
    cotangents; no copy of x, B or C head-major beside them."""
    from elasticdl_tpu.ops import ssd

    on_chip = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    x = on_chip((1, 16384, 64, 64), jnp.bfloat16)
    bc = on_chip((1, 16384, 8, 128), jnp.bfloat16)
    gate = on_chip((1, 16384, 64), jnp.float32)
    assert ssd.ssd_mode(16384, 8, 64, 128, interpret=False) == ("tpu", "")

    def fwd_bwd(x, b, c, g, dt, cot):
        out, pull = jax.vjp(
            lambda *a: ssd.ssd(*a, interpret=False), x, b, c, g, dt)
        return out, pull(cot)

    text = jax.jit(fwd_bwd).lower(x, bc, bc, gate, gate, x).compile(
        ).as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 2, calls
    fwd = next(c for c in calls if "ssd_fwd" in c.split(" = ")[0])
    bwd = next(c for c in calls if "ssd_bwd" in c.split(" = ")[0])
    results = lambda call: call.split(" custom-call(")[0]
    assert "bf16[1,16384,4096]" in results(fwd)
    assert "f32[1,8,128,512,128]" in results(fwd)      # the states
    assert results(bwd).count("bf16[1,16384,1024]") == 2      # dB, dC
    assert "f32[1,64,128,2,128]" in results(bwd)


def test_the_convolution_with_a_bias_compiles_at_the_cells_shape(one_chip):
    """One sequence of 16,384 x 6,144 channels (x | B | C), four taps and
    a bias a channel in row 7 of the taps' block."""
    from elasticdl_tpu.ops import short_conv as sc

    x = jax.ShapeDtypeStruct((1, 16384, 6144), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((6144, 4), jnp.float32, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((6144,), jnp.float32, sharding=one_chip)

    def fwd_bwd(x, w, bias, cot):
        out, pull = jax.vjp(lambda x, w, b: sc.conv_silu(
            x, w, interpret=False, bias=b), x, w, bias)
        return out, pull(cot)

    calls = _mosaic_calls(
        jax.jit(fwd_bwd).lower(x, w, bias, x).compile().as_text())
    assert len(calls) == 2 and any(
        "sconv_silu_bwd" in c.split(" = ")[0] for c in calls), calls


def _norm_of_the_view(x, scale, groups, eps):
    """The grouped norm as ``_ssm_mix`` wrote it before PR 62."""
    y = x.reshape(*x.shape[:-1], groups, -1)
    return tfm._rmsnorm(y, scale.reshape(groups, -1), eps).reshape(x.shape)


@pytest.mark.parametrize("norm", ["rows", "view"])
def test_a_mamba2_layers_grouped_norm_stays_on_the_rows_tiling(
        one_chip, monkeypatch, norm):
    """One Mamba-2 layer of ``nemotron-3-nano-30b-a3b.seq16384``
    (``_ssm_mix`` on one sequence of 16,384: the projection, the
    convolution, the scan, the gate, the grouped norm over 8 groups of
    512, ``ssm_out``), forward + backward through the TPU's compiler:
    no array of the ``[.., 8, 512]`` view's shapes anywhere in the
    program, and no ``copy`` or ``reshape`` standing alone whose result
    is a float32 ``[16384, 4096]`` plane.  ``view`` runs the parent's
    expression in the norm's place and the same reader finds both (8 on
    the sublanes is another tiling than ``[16384, 4096]``'s: the
    compiler converts to float32, re-tiles, writes the statistic out as
    a plane and re-tiles back; 36.9 ms of a 441.1 ms step on the chip,
    PERF.md section 6, PR 62), so the first case cannot pass by finding
    nothing.  No time is read here."""
    from elasticdl_tpu.ops.mode import SWITCH

    monkeypatch.setenv(SWITCH, "tpu")     # the ops' own choice on a chip
    if norm == "view":
        monkeypatch.setattr(tfm, "_group_rmsnorm", _norm_of_the_view)
    spec = tfm.model_spec(**_model_params("nemotron-3-nano-30b-a3b"))
    cfg = spec.config
    rows, inner = 16384, cfg.ssm_heads * cfg.ssm_head_dim
    assert (cfg.ssm_groups, inner) == (8, 4096)
    layer = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))[
        "layers"]["period"]["0"]
    w = {name: jax.ShapeDtypeStruct(a.shape[1:], a.dtype, sharding=one_chip)
         for name, a in layer.items() if name != "ln1"}
    h = jax.ShapeDtypeStruct((1, rows, cfg.dim), jnp.bfloat16,
                             sharding=one_chip)

    def fwd_bwd(h, w, g):
        out, vjp = jax.vjp(lambda h, w: tfm._ssm_mix(h, w, cfg)[0], h, w)
        return out, vjp(g)

    text = jax.jit(fwd_bwd).lower(h, w, h).compile().as_text()
    assert _names(text) == {"sconv_silu_fwd": 1, "sconv_silu_bwd": 1,
                            "ssd_fwd": 1, "ssd_bwd": 1}
    viewed = [shape for shape in ("f32[16384,8,512]", "f32[2048,8,8,512]",
                                  "f32[1,16384,8,512]") if shape in text]
    ops = _entry_ops(text)
    planes = [op.name for op in ops
              if op.op in ("copy", "reshape") and any(
                  kind == "f32" and dims[-2:] == (rows, inner)
                  and math.prod(dims) == rows * inner
                  for kind, dims in op.results)]
    # what the layer's copies, reshapes and broadcasts move, at HBM's
    # 819 GB/s: 0.1 ms on the rows, 4.2 ms round the view (the method:
    # docs/designs/nemotron_h_layers.md)
    moved = _moved_bytes(ops)
    priced = sum(moved[op.name] for op in ops
                 if op.op in ("copy", "reshape", "broadcast")) / 819e9
    if norm == "rows":
        assert not viewed and not planes, (viewed, planes)
        assert priced < 0.3e-3, priced
    else:
        assert len(viewed) == 3 and len(planes) >= 3, (viewed, planes)
        assert priced > 3e-3, priced


@pytest.mark.parametrize("k,n", [(2688, 1920), (1920, 2688)])
def test_grouped_matmul_compiles_at_an_odd_number_of_lane_tiles(one_chip, k,
                                                                n):
    """A hidden size of 2,688 = 21 tiles of 128 lanes beside experts of
    1,856 run as 1,920 = 15 (``moe_dispatch.whole_lanes``): no power of
    two above 128 divides either, and the column tiles are their own
    divisors (640 | 896), the weight gradient's blocks a third."""
    rows, groups = 12288, 8
    assert gm._unfriendly(k, n, gm.row_tile(rows), 2) == ""
    assert gm._column_tile(512, k, n, 2) == {1920: 640, 2688: 896}[n]
    assert gm._tgmm_tiles(512, 2688, 1920, 2) == (896, 1920)
    lhs = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((groups, k, n), jnp.bfloat16,
                               sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=one_chip)
    cot = jax.ShapeDtypeStruct((rows, n), jnp.bfloat16, sharding=one_chip)

    def fwd_bwd(lhs, rhs, sizes, cot):
        out, vjp = jax.vjp(lambda lhs, rhs: gm.grouped_matmul(
            lhs, rhs, sizes, interpret=False, zero_tail=True), lhs, rhs)
        return out, vjp(cot)

    text = jax.jit(fwd_bwd).lower(lhs, rhs, sizes, cot).compile().as_text()
    assert _names(text) == {"gmm_nn": 1, "gmm_nt": 1, "gmm_tn": 1}


def test_the_state_space_hybrids_parameters_are_the_configurations_count():
    """``nemotron-3-nano-30b-a3b`` as ``init_params`` builds it: four
    Mamba-2 layers (38,744,896 each), four expert layers of two-matrix
    MLPs (100,125,440 each: 8 held experts of 9,977,856, the router, its
    bias, the shared expert of 3,712 and ONE norm), the attention layer
    (23,399,040), the untied 16,384-id vocabulary (88,080,384) and the
    last norm: the count the configuration's ``reduced_why`` states,
    shapes alone; no ``w_gate`` anywhere."""
    spec = tfm.model_spec(**_model_params("nemotron-3-nano-30b-a3b"))
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    count = lambda tree: sum(a.size for a in jax.tree_util.tree_leaves(tree))
    period = params["layers"]["period"]
    assert [count(period[str(i)]) for i in range(9)] == [
        {"m": 38744896, "e": 100125440, "a": 23399040}[letter]
        for letter in "mememaeme"]
    assert count(params) == NEMOTRON3_PARAMETERS
    names = {name for layer in period.values() for name in layer}
    assert not names & {"w_gate", "ws_gate"}
    assert count(period["1"]["w_up"]) + count(
        period["1"]["w_down"]) == 8 * 9977856


# six layers of ``ouro-2.6b``; a layer more or fewer
OURO_PARAMETERS, OURO_LAYER = 509661185, 51388416


def test_the_looped_stacks_step_compiles_with_its_turns_as_one_loop(one_chip):
    """``ouro-2.6b.seq8192``'s whole training step for the described chip
    with ``remat_keep``'s list kept: the parameters are the
    configuration's count (a layer's 51,388,416 a layer, the untied
    49,152-id vocabulary, the final norm and the gate's 2,049), the
    flash calls carry the plain names the accepted reader takes, one
    call each way stands in the layers' loop however many turns run
    it, the heads are four calls of ``[8192, 2048] x [2048, 49152]``,
    and the compiler's count of the step lies over what ``remat_keep``
    predicts by no more than what it counts twice (PERF.md section 6,
    PR 66: a kept entry and the stacked gradient, which the chip holds
    once and twice)."""
    step = _cell_step(one_chip, "ouro-2.6b", 1, 8192, True)
    cfg = step.spec.config
    count = sum(a.size for a in jax.tree_util.tree_leaves(step.params))
    assert count == OURO_PARAMETERS + (cfg.num_layers - 6) * OURO_LAYER
    assert (cfg.ut_steps, cfg.dim, cfg.num_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.vocab_size) == (4, 2048, 16, 128, 5632, 49152)
    names, kept, budget, predicted = step.chosen
    assert names[:3] == ("flash_out", "flash_lse", "head_logits")
    assert 0 <= kept <= budget and predicted <= 0.95 * V5E_LIMIT
    text = step.compiled.as_text()
    assert _names(text) == {"flash_fwd": 1, "flash_bwd": 1, "embed_grad": 1}
    # the four heads' forward (their logits kept, none is made again),
    # and each call's weight gradient in the compute dtype
    assert _products(text, "bf16[8192,49152]") == 4
    assert _products(text, "bf16[2048,49152]") == 4
    assert 0 < step.counted - predicted < 2.0e9 + kept
