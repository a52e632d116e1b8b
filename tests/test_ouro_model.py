"""The ``ouro-2.6b`` model at its rehearsal size (two layers run three
times on one set of weights, so that first, middle and last turns
differ) against ``benchmark/reference/ouro-2.6b.py``: the loss, the
turns' losses, the exit distribution and every parameter's gradient
(the stacked weights' a sum over the turns, the final norm's and the
head's a sum of three, the gate's), what the reference's limits can
tell apart, the head-loss op a token, and every caller that refuses a
looped stack.  docs/designs/looped_stack.md has the equations.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import manifest
from benchmark.lib.runner import params_string
from elasticdl_tpu.models import transformer as tfm
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.ops import head_loss as op
from elasticdl_tpu.worker.worker import _loss_fields
from tests import reference_check as rc

NAME = "ouro-2.6b"
REF = manifest.load_named("reference", NAME)
PUBLISHED, CONFIG = rc.configuration(NAME, None), rc.configuration(NAME)
SHAPE = REF.shape_of(CONFIG)
_spec = functools.partial(rc.spec_of, NAME)
LOSS_TOLERANCE = 2e-6
GRAD_TOLERANCE = 2e-4
# bfloat16 against float32 at 64 tokens: a gradient leaf's relative
# distance (norms over the leaf), four to eight bfloat16 steps
BF16_GRAD_TOLERANCE = 4e-2


def _first_layers(params, layers):
    """``params`` with the first ``layers`` of the stack alone (None:
    all of them)."""
    return dict(params, layers=jax.tree_util.tree_map(
        lambda a: a[:layers], params["layers"]))


# the case, and the case of the stack's first ``layers`` alone
CASE = lambda layers=None: rc.rehearsal(NAME, 3, _first_layers, layers)


def test_the_rehearsal_model_is_the_cells_with_smaller_numbers():
    cfg = _spec().config
    cell = load_model_spec("transformer", model_params=params_string(
        PUBLISHED["cli"]["model_params"])).config
    assert (cell.dim, cell.num_heads, cell.head_dim, cell.kv_heads,
            cell.mlp_dim, cell.vocab_size, cell.ut_steps,
            cell.max_seq_len) == (2048, 16, 128, 16, 5632, 49152, 4, 8192)
    assert (cfg.num_layers, cfg.ut_steps, cfg.dtype) == (2, 3, "float32")
    assert PUBLISHED["reduced"] == ["num_hidden_layers"]
    assert 4 <= cell.num_layers == PUBLISHED["num_hidden_layers"] < (
        PUBLISHED["published"]["num_hidden_layers"]) == 48
    for field in ("post_norms", "pre_norms", "tied_embeddings", "remat",
                  "rope_theta", "norm_eps", "ut_entropy_weight",
                  "layer_pattern", "moe_experts"):
        assert getattr(cfg, field) == getattr(cell, field), field
    assert cell.post_norms and cell.pre_norms and not cell.tied_embeddings
    assert tfm.stack_plan(cell) is None
    assert PUBLISHED["total_ut_steps"] == cell.ut_steps
    assert SHAPE["turns"] == 3 and SHAPE["beta"] == 0.1


@pytest.mark.parametrize("how", [
    dict(remat=False), dict(remat=True),
    dict(remat=True, num_layers=1, mode="interpret"),
    dict(remat=True, dtype="bfloat16")], ids=lambda how: "-".join(
        "%s=%s" % item for item in how.items()))
def test_the_looped_stack_matches_the_reference(how):
    """The loss, the turns' losses, the exit distribution, its entropy
    and EVERY gradient leaf against the plain reference: float32 tight
    with and without ``remat`` (the jnp twins), the flash kernels in
    the interpreter at one layer (the wiring is a layer's), bfloat16 at
    the reference's own tolerance."""
    how = dict(how)
    mode = how.pop("mode", "off")
    coarse = how.get("dtype") == "bfloat16"
    loss_tol = REF.TOLERANCE if coarse else LOSS_TOLERANCE
    # (one layer: the first layer alone, both sides)
    far, still, stats, (seen,) = rc.check(
        CASE(how.get("num_layers")), mode, loss_tol,
        BF16_GRAD_TOLERANCE if coarse else GRAD_TOLERANCE, **how)
    close = lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=5 * loss_tol)
    close(stats["ut_loss"], seen.turn_losses)
    close(stats["ut_exit"], seen.exit)
    close(stats["ut_exit_entropy"], seen.entropy)
    # a layer's 11 leaves, embed, ln_f, lm_head, the gate's two
    assert len(far) == 11 + 3 + 2 and not still


# the limit of the comparison that sees each piece of ``REF.PIECES`` gone
SEEN_BY = {"gate": "exit", "entropy": "loss", "last_turn": "turns",
           "carry_norm": "state", "post_norms": "state"}


@pytest.mark.parametrize("piece, limit", sorted(SEEN_BY.items()))
def test_the_reference_without_one_piece_is_past_a_limit(piece, limit):
    """A gate left at zero, the entropy term dropped, the last turn
    dropped, the carry taken un-normed or the sublayers' output norms
    left out: each is past one of the limits the product is held to on
    the chip (the loss's tolerance, a turn's state, the mean exit
    distribution, the number of turns), the one named here."""
    assert set(SEEN_BY) == set(REF.PIECES)
    case = CASE()
    params, tokens = case.params, case.tokens
    (want, (seen,)), _ = rc.wanted(case)
    other, got = REF.loss(params, tokens, without=(piece,), **SHAPE)
    turns = min(len(got.states), len(seen.states))
    past = {
        "loss": abs(float(other.mean()) - float(want)) > (
            REF.TOLERANCE * float(want)),
        "turns": len(got.states) != len(seen.states),
        "state": max(REF.turn_errors(
            got.states[:turns], seen.states[:turns])) > (
                REF.TURN_STATE_CEILING),
        "exit": float(jnp.abs(got.exit[:turns] - seen.exit[:turns]).max())
        > REF.EXIT_CEILING}
    assert past[limit], (piece, past)


def test_the_turn_check_passes_in_float32_and_refuses_float8():
    """``case`` as ``lib/compare.py`` calls it holds every turn's state
    under its ceiling; the reference with its matmul operands rounded to
    float8, the nearest precision below the one the configuration
    states, is past it on every turn."""
    params, tokens = CASE().params, CASE().tokens[:1]
    _, seen = REF.loss(params, tokens, **SHAPE)
    REF.check_turns(CONFIG, params, tokens, seen)
    _, low = REF.loss(params, tokens, rounded=jnp.float8_e4m3fn, **SHAPE)
    errors = REF.turn_errors(low.states, seen.states)
    assert min(errors) > REF.TURN_STATE_CEILING, errors
    with pytest.raises(AssertionError, match="final-normed state"):
        REF.check_turns(CONFIG, params, tokens,
                        seen._replace(states=low.states))
    # a gate that fell out of the step: (1/2, 1/4, 1/4) whatever it holds
    _, gateless = REF.loss(params, tokens, without=("gate",), **SHAPE)
    with pytest.raises(AssertionError, match="mean exit distribution"):
        REF.check_turns(CONFIG, params, tokens,
                        seen._replace(exit=gateless.exit))


def test_one_turn_is_the_model_without_the_field():
    """``ut_steps=1`` is the program a model that never names the field
    runs: the same parameters (no gate) and the same step, letter for
    letter."""
    plain = dict(CONFIG["cli"]["model_params"])
    del plain["ut_steps"], plain["ut_entropy_weight"]
    tokens = jnp.zeros((2, 64), jnp.int32)
    texts = []
    for params in (plain, dict(plain, ut_steps=1, ut_entropy_weight=0.5)):
        spec = load_model_spec("transformer",
                               model_params=params_string(params))
        shapes = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
        assert "ut_gate_w" not in shapes and spec.step_stats_fn is None
        texts.append(jax.jit(jax.value_and_grad(lambda p: spec.loss_fn(
            spec.apply_fn(p, tokens, True), tokens).mean())).lower(
                shapes).as_text())
    assert texts[0] == texts[1]


def test_the_exit_distribution_sums_to_one_whatever_the_gate():
    logits = jnp.asarray(np.random.default_rng(0).normal(
        0.0, 4.0, (3, 2, 7)), jnp.float32).at[:, 0, 0].set(
            jnp.asarray([40.0, -40.0, 0.0]))
    p = jnp.exp(tfm.exit_distribution(logits))
    assert p.shape == (4, 2, 7)
    np.testing.assert_allclose(np.asarray(p.sum(axis=0)), 1.0, atol=1e-6)
    lam = jax.nn.sigmoid(logits)
    np.testing.assert_allclose(np.asarray(p[1]),
                               np.asarray(lam[1] * (1 - lam[0])), rtol=1e-5,
                               atol=1e-7)
    np.testing.assert_allclose(
        np.asarray(p[3]), np.asarray((1 - lam).prod(axis=0)), rtol=1e-5,
        atol=1e-7)


def test_a_gate_that_never_leaves_and_no_entropy_is_the_last_turns_loss():
    """``beta = 0`` and a gate whose bias is minus infinity (every
    lambda 0, ``p_R`` 1): the loss is the plain next-token loss of the
    last turn's logits, which is what evaluation reads."""
    spec = _spec(ut_entropy_weight=0.0)
    params, tokens = CASE().params, CASE().tokens
    params = dict(params, ut_gate_b=jnp.float32(-jnp.inf))
    got = spec.loss_fn(spec.apply_fn(params, tokens, True), tokens)
    logits = spec.apply_fn(params, tokens, False)
    want = tfm.next_token_loss(logits, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6)
    _, seen = REF.loss(params, tokens, **SHAPE)
    head = params["lm_head"]
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(seen.states[-1] @ head), rtol=2e-4,
        atol=2e-4)


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("shift", [1, 2])
def test_the_loss_a_token_is_next_token_loss_a_token(tied, shift):
    """``token_loss`` against optax's arithmetic a position, zero where
    there is no target, and its gradients under a random [B, T]
    cotangent against JAX's own of the same weighted sum; ``head_loss``
    is its mean over ``T - shift``; with the logits made again the same
    numbers."""
    import optax

    rng = np.random.default_rng(5)
    b, t, e, v = 2, 16, 32, 64
    x = jnp.asarray(rng.normal(size=(b, t, e)), jnp.float32)
    head = jnp.asarray(rng.normal(size=(v, e) if tied else (e, v)),
                       jnp.float32) * 0.3
    tokens = jnp.asarray(rng.integers(0, v, (b, t)), jnp.int32)
    weights = jnp.asarray(rng.normal(size=(b, t)), jnp.float32)

    def plain(x, head):
        logits = x @ (head.T if tied else head)
        per_token = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :t - shift], tokens[:, shift:])
        return jnp.pad(per_token, ((0, 0), (0, shift)))

    want = plain(x, head)
    for kept in (True, False):
        fn = lambda x, head: op.token_loss(
            x, head, tokens, tied=tied, shift=shift, logits_kept=kept)
        got = fn(x, head)
        assert got.shape == (b, t) and not float(
            jnp.abs(got[:, t - shift:]).max())
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
        grads = jax.grad(lambda x, head: (fn(x, head) * weights).sum(),
                         argnums=(0, 1))(x, head)
        wanted = jax.grad(lambda x, head: (plain(x, head) * weights).sum(),
                          argnums=(0, 1))(x, head)
        for g, w in zip(grads, wanted):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(op.head_loss(x, head, tokens, tied=tied, shift=shift)),
        np.asarray(want.sum(axis=-1) / (t - shift)), rtol=1e-6)


def test_the_gate_is_drawn_at_zero_and_not_decayed():
    spec = _spec()
    params = spec.init_fn(jax.random.PRNGKey(0))
    assert params["ut_gate_w"].shape == (64,) and not float(
        jnp.abs(params["ut_gate_w"]).max()) and not float(
            params["ut_gate_b"])
    mask = tfm._decayed(params)
    assert not mask["ut_gate_w"] and not mask["ut_gate_b"]
    assert mask["lm_head"] and mask["ln_f"] and not mask["layers"][
        "ln1_post"]
    # every lambda 1/2: (1/2, 1/4, 1/4), ln 2 + ln 2 / 2 nats
    tokens = CASE().tokens
    out = spec.apply_fn(params, tokens, True)
    spec.loss_fn(out, tokens)
    stats = spec.step_stats_fn(out)
    np.testing.assert_allclose(np.asarray(stats["ut_exit"]),
                               [0.5, 0.25, 0.25], rtol=1e-6)
    np.testing.assert_allclose(float(stats["ut_exit_entropy"]),
                               1.5 * np.log(2.0), rtol=1e-6)
    assert _loss_fields(stats).startswith(" ut_loss=") and (
        " exit=0.500000/0.250000/0.250000 exit_entropy=1.039721"
        in _loss_fields(stats))
    assert _loss_fields({"mtp_loss": 1.0}) == " mtp=1.000000"


def _mesh():
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "pp", "sp", "tp", "ep"))


@pytest.mark.parametrize("caller", [
    "prefill", "decode_step", "generate", "export_generate",
    "a model-parallel mesh", "forward_pipelined"])
def test_a_caller_that_has_no_loop_refuses_it_by_name(caller, tmp_path):
    cfg = tfm.TransformerConfig(
        vocab_size=64, dim=32, num_heads=2, num_layers=2, ut_steps=3,
        max_seq_len=16)
    params = jax.eval_shape(lambda: tfm.init_params(
        jax.random.PRNGKey(0), cfg))
    prompt = jnp.zeros((1, 4), jnp.int32)
    calls = {
        "prefill": lambda: tfm.prefill(params, cfg, prompt, 8),
        "decode_step": lambda: tfm.decode_step(
            params, cfg, tfm.init_kv_cache(cfg, 1, 8), 0, prompt[:, 0]),
        "generate": lambda: tfm.generate(params, cfg, prompt, 2),
        "export_generate": lambda: tfm.export_generate(
            str(tmp_path), params, cfg, 2, 4),
        "a model-parallel mesh": lambda: tfm.param_specs(cfg),
        "forward_pipelined": lambda: tfm.forward_pipelined(
            params, prompt, cfg, _mesh(), 2),
    }
    with pytest.raises(NotImplementedError) as refused:
        calls[caller]()
    said = str(refused.value)
    assert said.startswith(caller + " does not run a looped stack "
                           "(ut_steps=3: ut_gate_w, ut_gate_b)"), said
    assert "a K/V cache a turn a layer" in said and (
        "decided a token" in said) and "no final norm between turns" in said


@pytest.mark.parametrize("with_it", [
    dict(layer_pattern="aw", window=8), dict(moe_experts=4, mtp_modules=1),
    dict(xent_chunk=16), dict(pipeline_microbatches=2)],
    ids=lambda d: next(iter(d)))
def test_a_loop_round_anything_but_the_plain_stack_is_refused(with_it):
    with pytest.raises(ValueError) as refused:
        tfm.model_spec(seq_len=16, vocab_size=64, dim=32, num_heads=2,
                       num_layers=2, ut_steps=3, **with_it)
    said = str(refused.value)
    assert said.startswith("ut_steps=3: "), said
    if set(with_it) & {"xent_chunk", "pipeline_microbatches"}:
        assert "xent_chunk (%d)" % with_it.get("xent_chunk", 0) in said
        assert "pipeline_microbatches=%d" % with_it.get(
            "pipeline_microbatches", 0) in said
    else:
        assert "the plain scanned one" in said
        assert "moe_experts=%d" % with_it.get("moe_experts", 0) in said
        assert "no mtp_modules (%d)" % with_it.get("mtp_modules", 0) in said
        assert "hyper_streams=0" in said
    with pytest.raises(ValueError, match="a count >= 1"):
        tfm.TransformerConfig(ut_steps=0)
