"""A share's bound and the rows past it, and the same moves by the row
kernel (``ops/moe_dispatch.py``, ``ops/row_moves.py``): the half of
tests/test_mixed_stack.py that is about rows, in a file of its own so
that the suite's longest file is not one worker's whole run
(``--dist loadfile``).  Float32 on the CPU at tiny widths."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models import transformer as tfm
from elasticdl_tpu.ops import moe_dispatch as md
from elasticdl_tpu.ops import row_moves
from tests.test_mixed_stack import TINY, _eqns, _loss


# -- a share's bound, and the rows past it -----------------------------------


def test_the_bound_is_twice_the_balanced_share_in_whole_tiles():
    assert md.row_bound(131072, 8, 64) == 32768       # the benchmark's cell
    assert md.row_bound(131072, 64, 64) == 131072     # all held: every row
    assert md.row_bound(512, 4, 32) == 128            # 2 x 64, one 128 tile
    assert md.row_bound(1040, 8, 16) == 1040          # never past the rows
    assert md.row_bound(6000, 2, 16) == 1536          # 1500 -> 3 tiles of 512


def _held_first(n, k, total, first, held_rows, seed):
    """[1, n, k] choices of ``total`` experts with exactly ``held_rows``
    of the n * k on the k experts ``first ..``: the first tokens have all
    k choices there, one the remainder, the rest none."""
    rng = np.random.default_rng(seed)
    absent = np.setdiff1d(np.arange(total), first + np.arange(k))
    experts = np.empty((n, k), np.int64)
    for t in range(n):
        mine = min(k, max(held_rows - t * k, 0))
        experts[t] = rng.permutation(np.concatenate(
            [first + rng.permutation(k)[:mine],
             rng.permutation(absent)[:k - mine]]))
    assert (np.isin(experts, first + np.arange(k))).sum() == held_rows
    return jnp.asarray(experts[None], jnp.int32)


def _plain_share(h, gates, experts, w_gate, w_up, w_down, first):
    """The held experts' part of the layer, every expert over every
    token and the gates of the tokens that chose it."""
    out = 0.0
    for x in range(w_gate.shape[0]):
        y = (jax.nn.silu(h @ w_gate[x]) * (h @ w_up[x])) @ w_down[x]
        mine = (gates * (experts == first + x)).sum(-1)
        out = out + mine[..., None] * y
    return out


@functools.lru_cache(maxsize=None)
def _share_layer(mode, total, first):
    """A held share's layer with its load and gradients, traced under
    the mode its caller has set; the choice of experts is an operand, so
    one compile a shape serves every count of held rows."""
    def loss(h, gates, experts, cot, *weights):
        out, load = md.moe_experts(h, gates, experts, *weights,
                                   total=total, first=first)
        return (out * cot).sum(), (out, load)

    return jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 4, 5, 6), has_aux=True))


@functools.lru_cache(maxsize=None)
def _three_layers(mode, k, total, first):
    """(the share's layer, the whole-buffer dispatch, the plain layer),
    each with its gradients, as ``_share_layer`` has the first."""
    def whole(h, gates, experts, cot, *weights):
        full = tuple(jnp.zeros((total,) + w.shape[1:]).at[
            first:first + k].set(w) for w in weights)
        out, _ = md.moe_experts(h, gates, experts, *full)
        return (out * cot).sum(), out

    def plain(h, gates, experts, cot, *weights):
        out = _plain_share(h, gates, experts, *weights, first)
        return (out * cot).sum(), out

    return (_share_layer(mode, total, first),) + tuple(
        jax.jit(jax.value_and_grad(
            fn, argnums=(0, 1, 4, 5, 6), has_aux=True))
        for fn in (whole, plain))


@pytest.mark.parametrize("mode", ["off", "interpret"])
@pytest.mark.parametrize("held_rows", [100, 128, 129, 256, 512])
def test_a_share_multiplies_every_held_row_whatever_the_bound(
        monkeypatch, mode, held_rows):
    """128 tokens, 4 choices of 32 experts, experts 8 .. 12 held: the
    bound is 128 rows of the 512.  Held rows under it, exactly at it, one
    over it, two blocks full and every row: result, load and every
    gradient are the plain layer's and the whole-buffer dispatch's (all
    32 experts held, the absent ones' weights zeros), with the reference
    product and with the kernels."""
    monkeypatch.setenv("ELASTICDL_FLASH", mode)
    n, k, total, first = 128, 4, 32, 8
    assert md.row_bound(n * k, k, total) == 128
    rng = np.random.default_rng(held_rows)
    h = jnp.asarray(rng.standard_normal((1, n, 32)), jnp.float32)
    gates = jnp.asarray(rng.random((1, n, k)), jnp.float32)
    experts = _held_first(n, k, total, first, held_rows, held_rows)
    weights = tuple(jnp.asarray(rng.standard_normal(s) * 0.2, jnp.float32)
                    for s in ((k, 32, 48), (k, 32, 48), (k, 48, 32)))
    cot = jnp.asarray(rng.standard_normal((1, n, 32)), jnp.float32)
    share, whole, plain = _three_layers(mode, k, total, first)
    grad = lambda fn: fn(h, gates, experts, cot, *weights)
    (_, (out, load)), grads = grad(share)
    blocks = max(-(-held_rows // 128), 1)
    np.testing.assert_array_equal(
        load[0, total + 1:total + 3], [128 * blocks, blocks > 1])
    # beside them, where the row kernel moved the rows, what its sums
    # walked: two calls a block, n x k slots a call without the ranks
    assert load.shape[1] == total + 3 + 2 * (mode == "interpret")
    if mode == "interpret":
        assert 0 < load[0, -2] <= load[0, -1] == 2 * blocks * n * k
    assert int(load[0, first:first + k].sum()) == held_rows
    for other in (whole, plain):
        (_, want), want_grads = grad(other)
        np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
        for g, w in zip(grads, want_grads):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("claims", ["k-on-one-token", "none", "mixed"])
def test_rows_to_tokens_and_tokens_to_rows_are_transposes(claims):
    """<rows_to_tokens(y), x> = <y, tokens_to_rows(x)> over the rows
    that are a token's, with all 3 rows one token's, with no row any
    token's (``tok == n``), and mixed; a scale's gradients check out
    numerically."""
    from jax.test_util import check_grads

    n, c, w = 6, 8, 5
    tok = {"k-on-one-token": [4, 4, 4] + [n] * 5, "none": [n] * c,
           "mixed": [2, 0, 2, 5, 0, 2, n, n]}[claims]
    tok = jnp.asarray(tok, jnp.int32)
    rng = np.random.default_rng(len(claims))
    x = jnp.asarray(rng.standard_normal((n, w)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((c, w)), jnp.float32)
    scale = jnp.asarray(rng.random(c), jnp.float32)
    live = np.asarray(tok) < n
    by_hand = np.zeros((n, w), np.float32)
    for i in np.flatnonzero(live):
        by_hand[tok[i]] += np.asarray(y)[i]
    summed = md.rows_to_tokens(n, y, tok, None)
    np.testing.assert_allclose(summed, by_hand, rtol=1e-6, atol=1e-6)
    taken = md.tokens_to_rows(n, x, tok)
    np.testing.assert_array_equal(np.asarray(taken)[live],
                                  np.asarray(x)[np.asarray(tok)[live]])
    assert float((summed * x).sum()) == pytest.approx(
        float((y * taken)[live].sum()), rel=1e-5, abs=1e-6)
    # each is the other's pullback
    pulled = jax.vjp(lambda x: md.tokens_to_rows(n, x, tok), x)[1](y)[0]
    np.testing.assert_allclose(pulled, by_hand, rtol=1e-6, atol=1e-6)
    pulled = jax.vjp(lambda y: md.rows_to_tokens(n, y, tok, None),
                     y)[1](x)[0]
    np.testing.assert_array_equal(pulled, jnp.where(live[:, None], taken, 0))
    check_grads(lambda y, scale: md.rows_to_tokens(n, y, tok, scale),
                (y, scale), order=1, modes=["rev"])


# -- the same moves by the row kernel (ops/row_moves.py) --------------------


def _a_block(n, k, c, live, seed, one_tokens=True):
    """(tok [c], pos [n, k], claims [c]) of a block of ``c`` rows whose
    first ``live`` are some token's: with ``one_tokens`` the first
    tokens hold all k of their choices in the block (k rows of one
    block a token), the last one the remainder."""
    rng = np.random.default_rng(seed)
    pairs = np.arange(n * k)[:live] if one_tokens else np.sort(
        rng.permutation(n * k)[:live])
    claims = np.full(c, n * k, np.int64)
    claims[:live] = rng.permutation(pairs)
    pos = np.full(n * k, -1, np.int64)
    pos[claims[:live]] = np.arange(live)
    tok = np.where(np.arange(c) < live, claims // k, n)
    return (jnp.asarray(tok, jnp.int32),
            jnp.asarray(pos.reshape(n, k), jnp.int32),
            jnp.asarray(claims, jnp.int32))


@functools.lru_cache(maxsize=None)
def _moves(n, k):
    """{(move, by the kernel?): the move with its gradients}, the block's
    indices and its count of live rows operands: one compile a shape for
    every filling of the block.  Traced under the interpreter, which the
    caller has set."""
    def gather(kernel):
        def loss(x, tok, pos, live, named, cot_rows):
            out = md._gather_rows(n, x, tok, pos, live,
                                  jnp.where(live > 0, n, 0)) \
                if kernel else md.tokens_to_rows(n, x, tok)
            return (jnp.where(named, out, 0) * cot_rows).sum(), out
        return jax.jit(jax.value_and_grad(loss, has_aux=True))

    def summed(kernel):
        def loss(y, gates, tok, pos, live, claims, cot):
            if kernel:
                out = md._sum_rows(n, y, tok, pos, live, claims, gates,
                                   jnp.where(live > 0, n, 0))
            else:
                scale = gates.reshape(n * k).at[claims].get(
                    mode="fill", fill_value=0)
                out = md.rows_to_tokens(n, y, tok, scale)
            return (out * cot).sum(), out
        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))

    return {(name, kernel): move(kernel) for name, move in (
        ("gather", gather), ("sum", summed)) for kernel in (True, False)}


@pytest.mark.parametrize("width", [128, 40])
@pytest.mark.parametrize("k", [1, 4, 6])
@pytest.mark.parametrize("rows", ["none", "some", "k-on-one-token", "all"])
def test_the_row_kernel_moves_rows_as_the_jnp_moves_do(monkeypatch, k,
                                                       width, rows):
    """``_gather_rows`` and ``_sum_rows`` through the Pallas interpreter
    against ``tokens_to_rows`` and ``rows_to_tokens``: values, and the
    gradient of every operand (x; y and the gates), with no row of the
    block live, with some, with tokens that hold all K of their rows in
    the block, and with every row live; at a width that is whole lanes
    and one that is not.  Rows that are no token's come back zero from
    the kernel, whatever the reference leaves there."""
    monkeypatch.setenv("ELASTICDL_FLASH", "interpret")
    n, c = 24, 48 if k > 1 else 24
    live = {"none": 0, "some": 17, "k-on-one-token": 3 * k, "all": c}[rows]
    live = min(live, n * k)
    tok, pos, claims = _a_block(n, k, c, live, seed=k + width,
                                one_tokens=rows != "some")
    rng = np.random.default_rng(width + live)
    x = jnp.asarray(rng.standard_normal((n, width)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((c, width)), jnp.float32)
    gates = jnp.asarray(rng.random((n, k)), jnp.float32)
    cot_rows = jnp.asarray(rng.standard_normal((c, width)), jnp.float32)
    cot = jnp.asarray(rng.standard_normal((n, width)), jnp.float32)
    named = jnp.asarray((np.arange(c) < live)[:, None])
    moves, count = _moves(n, k), jnp.int32(live)
    gather = lambda kernel: moves["gather", kernel](
        x, tok, pos, count, named, cot_rows)
    summed = lambda kernel: moves["sum", kernel](
        y, gates, tok, pos, count, claims, cot)
    (_, got), dx = gather(True)
    (_, want), want_dx = gather(False)
    np.testing.assert_array_equal(np.asarray(got)[:live],
                                  np.asarray(want)[:live])
    assert not np.asarray(got)[live:].any()
    np.testing.assert_allclose(dx, want_dx, rtol=1e-6, atol=1e-6)
    (_, got), grads = summed(True)
    (_, want), want_grads = summed(False)
    assert got.dtype == jnp.float32 and got.shape == (n, width)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("width", [128, 40])
@pytest.mark.parametrize("k", [1, 4, 6])
def test_a_row_no_index_names_never_reaches_a_result(k, width, dtype):
    """``row_sum`` with NaN in every row of the source that no index
    names, and in the rows of ``other`` that have no source: the sum,
    its weighted form and the row dots are finite and the reference's;
    a 16-bit source comes through its 32-bit words exactly."""
    rows, m = 50, 70
    rng = np.random.default_rng(k * width)
    idx = rng.integers(-3, rows + 4, (m, k))
    idx[5] = -1                        # a result row with no source
    idx[6] = rows + 2
    named = np.zeros(rows, bool)
    named[idx[(idx >= 0) & (idx < rows)]] = True
    named[7] = False                   # at least one row is no index's
    idx[idx == 7] = -1
    src = jnp.asarray(np.where(
        named[:, None], rng.standard_normal((rows, width)), np.nan), dtype)
    has_first = ((idx[:, 0] >= 0) & (idx[:, 0] < rows))[:, None]
    other = jnp.asarray(np.where(
        has_first, rng.standard_normal((m, width)), np.nan), dtype)
    weight = jnp.asarray(rng.random((m, k)), jnp.float32)
    idx = jnp.asarray(idx, jnp.int32)
    # the row dots go with one index a result row
    for w, o in ((None, None), (weight, None), (weight, other))[
            :2 + (k == 1)]:
        got = row_moves.row_sum(src, idx, w, o, out_dtype=jnp.float32,
                                interpret=True)
        want = row_moves.row_sum_ref(src, idx, w, o, jnp.float32)
        for a, b in zip(got, want):
            if b is not None:
                assert bool(jnp.isfinite(a).all())
                np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    assert not np.asarray(got[0])[5:7].any()
    if k == 1:      # a gather moves bits
        taken = row_moves.row_sum(src, idx, interpret=True)[0]
        live = np.asarray((idx[:, 0] >= 0) & (idx[:, 0] < rows))
        np.testing.assert_array_equal(
            np.asarray(taken, np.float32)[live],
            np.asarray(src, np.float32)[np.asarray(idx[:, 0])[live]])


@pytest.mark.parametrize("e", [128, 40])
@pytest.mark.parametrize("k", [1, 4, 6])
@pytest.mark.parametrize("held_rows", ["none", "part", "all-of-a-block",
                                       "two-blocks"])
def test_a_shares_layer_is_one_by_the_kernel_and_by_the_jnp_moves(
        monkeypatch, k, e, held_rows):
    """A held share's layer, 256 tokens x K choices of 32 experts, K of
    them held: the result, the load and every gradient with the row
    kernel (the Pallas interpreter) are those of the jnp moves
    (``off``), with no held row, with a block partly live, with a block
    all live, and with held rows past the bound, which ``_further_blocks``
    takes through the same kernels."""
    n, total, first = 256, 32, 8
    bound = md.row_bound(n * k, k, total)
    rows = {"none": 0, "part": bound // 2 + 3, "all-of-a-block": bound,
            "two-blocks": min(bound + 37, n * k)}[held_rows]
    rng = np.random.default_rng(rows + e)
    h = jnp.asarray(rng.standard_normal((1, n, e)), jnp.float32)
    gates = jnp.asarray(rng.random((1, n, k)), jnp.float32)
    experts = _held_first(n, k, total, first, rows, rows + k)
    weights = tuple(jnp.asarray(rng.standard_normal(s) * 0.2, jnp.float32)
                    for s in ((k, e, 48), (k, e, 48), (k, 48, e)))
    cot = jnp.asarray(rng.standard_normal((1, n, e)), jnp.float32)

    def run(mode):
        monkeypatch.setenv("ELASTICDL_FLASH", mode)
        assert md.rows_by_kernel(n, bound, e, jnp.float32, k) == (
            mode != "off")
        return _share_layer(mode, total, first)(
            h, gates, experts, cot, *weights)

    (_, (out, load)), grads = run("interpret")
    (_, (want, want_load)), want_grads = run("off")
    blocks = max(-(-rows // bound), 1)
    np.testing.assert_array_equal(load[0, total + 1:total + 3],
                                  [bound * blocks, blocks > 1])
    assert want_load.shape[1] == total + 3
    # what the kernel's sums walked, two calls a block that ran: sixteen
    # tokens as many terms as the fullest has rows in the block
    flat = (np.asarray(experts).reshape(n * k) - first) % total
    at = np.full(n * k, -1)
    held = np.flatnonzero(flat < k)
    at[held[np.argsort(flat[held], kind="stable")]] = np.arange(rows)
    most = np.concatenate([
        ((at // bound == i) & (at >= 0)).reshape(n // 16, 16, k).sum(
            axis=2).max(axis=1) for i in range(blocks)])
    terms = np.where(most == 5, 6, most).sum()    # ``row_moves._bodies``
    np.testing.assert_array_equal(
        load[0, total + 3:], [2 * 16 * terms, 2 * blocks * n * k])
    np.testing.assert_array_equal(load[0, :total], want_load[0, :total])
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


def test_held_positions_are_the_sorts_inverse_over_the_held_experts():
    """``held_positions``: for every assignment to a held expert, where
    the stable sort (held experts first) puts it; -1 for the others."""
    rng = np.random.default_rng(3)
    total, held, rows = 16, 5, 600
    flat = jnp.asarray(rng.integers(0, total, rows), jnp.int32)
    flat = flat.at[:40].set(2)        # an expert chosen again and again
    sizes = jnp.bincount(flat, length=total)[:held].astype(jnp.int32)
    order = np.argsort(np.asarray(flat), kind="stable")
    inverse = np.empty(rows, np.int64)
    inverse[order] = np.arange(rows)
    want = np.where(np.asarray(flat) < held, inverse, -1)
    np.testing.assert_array_equal(md.held_positions(flat, sizes), want)
    none = md.held_positions(jnp.full((rows,), total - 1, jnp.int32),
                             jnp.zeros((held,), jnp.int32))
    assert (np.asarray(none) == -1).all()


@pytest.mark.parametrize("mode", ["interpret", "off"])
def test_a_shares_step_by_the_kernel_has_no_float32_rows_and_no_scatter(
        monkeypatch, mode):
    """The bfloat16 training step of one expert layer, 128 tokens x 4
    choices, 4 of 16 experts held (a bound of 256 rows): where the row
    kernel moves the rows no equation's result is float32 [bound, width]
    and nothing is scattered into a float32 [tokens, width]; the jnp
    moves do both (so the walk would see them), and the kernel's calls
    are there by their names."""
    monkeypatch.setenv("ELASTICDL_FLASH", mode)
    spec = tfm.model_spec(**dict(
        TINY, num_layers=1, layer_pattern="c", moe_experts_held=4,
        dtype="bfloat16"))
    cfg = spec.config
    shapes = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((4, 32), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, t: _loss(spec, t)(p)))(
        shapes, tokens)
    n, bound = 4 * 32, md.row_bound(4 * 32 * 4, 4, 16)
    assert bound == 256 != cfg.dim

    def outside_kernels(jaxpr):    # a kernel's own values are VMEM's
        for eqn in jaxpr.eqns:
            yield eqn
            if eqn.primitive.name != "pallas_call":
                for inner in jax.core.jaxprs_in_params(eqn.params):
                    yield from outside_kernels(inner)

    eqns = list(outside_kernels(jaxpr.jaxpr))
    float_rows = {eqn.primitive.name for eqn in eqns for v in eqn.outvars
                  if tuple(v.aval.shape) == (bound, cfg.dim)
                  and v.aval.dtype == jnp.float32
                  and eqn.primitive.name != "pallas_call"}
    scatters = [eqn for eqn in eqns
                if eqn.primitive.name.startswith("scatter")
                and tuple(eqn.outvars[0].aval.shape) == (n, cfg.dim)]
    names = {eqn.params["name"] for eqn in eqns
             if eqn.primitive.name == "pallas_call"
             and eqn.params["name"].startswith("rows_")}
    if mode == "off":
        assert float_rows and scatters and not names
    else:
        assert not float_rows and not scatters
        assert names == {"rows_pack", "rows_gather", "rows_sum"}


@pytest.mark.parametrize("held", [4, 0])
def test_a_shares_step_has_no_buffer_of_all_the_rows(held):
    """The training step of one expert layer, 64 tokens x 4 choices: with
    4 of 16 experts held (a bound of 128 rows) no result of any
    equation, forward or backward, is [n * K, width] or [n, K, width];
    with all held both are there (so the walk would see them)."""
    spec = tfm.model_spec(**dict(
        TINY, num_layers=1, layer_pattern="c", moe_experts_held=held))
    cfg = spec.config
    shapes = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, t: _loss(spec, t)(p)))(
        shapes, tokens)
    seen = {tuple(v.aval.shape) for eqn in _eqns(jaxpr.jaxpr)
            for v in eqn.outvars}
    n, k = 2 * 32, cfg.moe_top_k
    whole = {(n * k, cfg.dim), (n * k, cfg.mlp_dim), (n, k, cfg.dim),
             (n, k, cfg.mlp_dim)}
    if held:
        assert not seen & whole
        assert {(128, cfg.dim), (128, cfg.mlp_dim)} <= seen
    else:
        assert {(n * k, cfg.dim), (n * k, cfg.mlp_dim),
                (n, k, cfg.dim)} <= seen


# -- the cell's whole step for a described v5e: last in the file, since
# ``one_chip`` turns XLA's optimisations on for its module (ROADMAP C16)

from tests.tpu_compile import (  # noqa: E402,F401 (the fixtures)
    V5E_LIMIT, _bare_estimate_is_bounded, _inventory_is_held, cell_steps,
    one_chip)


def test_the_mixed_stacks_step_fits_a_v5e_as_remat_keep_predicts(
        cell_steps):
    """The ``lfm2-24b-a2b.seq8192`` cell's whole training step (4
    sequences of 8,192 through a dense conv layer and a period of
    attention + 3 conv layers over 8 of 64 experts, AdamW) through the
    TPU's compiler with the names ``remat_keep`` chose: its predicted
    peak is held to the compiler's own byte count (arguments +
    temporaries; the updated state aliases the donated one): over, never
    under.  This is the band that guards the chip: the cell runs under
    these names.
    With the names chosen, the convolutions' result and the sorted rows
    beside PR 58's ten entries since PR 60 (6.62 GB): 15.60 against
    15.37 (+0.23; PR 58's tree read 15.81 against 15.41 with 5.55 GB
    kept, the stack's 1.8 GB of gradients counted whole where the
    dispatch's temporaries stood).  The count does not grow with the
    list: 15.39 with the ten entries PR 58 kept, 14.95 with the
    convolutions' result beside them, 15.37 with the sorted rows too;
    in the first the tied head's cotangent (0.54 GB) still stands in
    the first layer back-propagated, in the second it does not
    (PERF.md section 6, PR 60).
    (The estimate with nothing kept is
    ``..step_with_nothing_kept_is_under_remat_keeps_estimate``'s, which
    reads this compile.)"""
    from elasticdl_tpu.models import remat_keep as rk
    from elasticdl_tpu.ops import moe_dispatch, short_conv

    step = cell_steps("lfm2-24b-a2b", 4, 8192, True)
    names, kept, budget, peak = step.chosen
    assert kept <= budget
    assert set(names) >= set(rk.ATTN_NAMES) | {
        rk.KEEP_STREAM, rk.KEEP_GATE, rk.KEEP_UP, short_conv.KEEP_IN,
        short_conv.KEEP_OUT, moe_dispatch.KEEP_UP,
        moe_dispatch.KEEP_ROWS}, names
    assert peak <= (1 - rk.RESERVE) * V5E_LIMIT
    assert 0 < peak - step.counted < 0.5e9, (peak, step.counted, names)


def test_the_mixed_stacks_step_with_nothing_kept_is_under_remat_keeps_estimate(
        cell_steps):
    """The same cell's step with no room stated, so with nothing kept
    (no cell runs so: the trainer states the room): ``remat_keep``'s
    estimate of the step's own need, ``step_bytes``, the term every
    choice starts from, 10.36 GB.  A compile of that step read 9.80
    (+0.57: the leading dense layer's term, 3.09 GB, stands over the
    expert layers' 2.51) and was 100 s of tier-1 for a band of 0.9 GB
    that refused nothing the kept names' band passed; the estimate is
    held to what the compile with the names kept bounds that count by
    (``tpu_compile._bare_estimate_is_bounded``)."""
    _bare_estimate_is_bounded(cell_steps("lfm2-24b-a2b", 4, 8192, True),
                              32768)


@pytest.mark.parametrize("config,batch,rows,keep", [
    ("lfm2-24b-a2b", 4, 8192, True), ("lfm2-24b-a2b", 4, 8192, False)])
def test_the_expert_layers_inventory_is_held_to_the_compilers_count(
        cell_steps, config, batch, rows, keep):
    """``tests/test_step_compile_tpu.py``'s test of the same name for
    this cell: +0.23 GB with ``choose``'s
    list kept; with nothing kept what that compile bounds."""
    _inventory_is_held(cell_steps, config, batch, rows, keep)
