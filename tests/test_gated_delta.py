"""``ops/gated_delta.py`` and ``ops/short_conv.conv_silu`` against the
plain definitions: the delta rule token by token (``lax.scan`` over T,
no chunks), the convolution tap by tap.  Float32 unless said."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from elasticdl_tpu.ops import gated_delta as gd
from elasticdl_tpu.ops import short_conv as sc

B, H, T, DK, DV = 2, 3, 96, 16, 24


def recurrence(q, k, v, g, beta):
    """S' = alpha S; u = beta (v - S' k); S = S' + u k^T; o = S q."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))

    def token(S, x):
        q, k, v, g, beta = x
        S = S * jnp.exp(g)[..., None, None]
        u = beta[..., None] * (v - jnp.einsum("bhvk,bhk->bhv", S, k))
        S = S + u[..., :, None] * k[..., None, :]
        return S, jnp.einsum("bhvk,bhk->bhv", S, q)

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta))
    start = jnp.zeros(q.shape[:2] + (v.shape[-1], q.shape[-1]), jnp.float32)
    return jnp.moveaxis(lax.scan(token, start, xs)[1], 0, 2)


def draw(seed, big_beta, dtype=jnp.float32, batch=B, seq=T, d_k=DK):
    """Operands as a layer hands them: unit keys, queries at d_k^-1/2;
    head 0 decays by 0.4-0.99 a token, head 1 forgets nearly all of its
    state at every token (alpha ~ 1e-9) and head 2 nearly nothing
    (alpha > 0.999); ``big_beta`` puts half the write strengths in (1,
    2), else all lie in (0, 1)."""
    r = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(r.standard_normal((batch, H, seq, d_k))) / np.sqrt(d_k)
    k = unit(r.standard_normal((batch, H, seq, d_k)))
    v = r.standard_normal((batch, H, seq, DV))
    beta = r.uniform(0.05, 2.0 if big_beta else 1.0, (batch, H, seq))
    g = -np.stack([r.uniform(0.01, 1.0, (batch, seq)),
                   r.uniform(10.0, 30.0, (batch, seq)),
                   r.uniform(0.0, 1e-3, (batch, seq))], axis=1)
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype), jnp.asarray(g, jnp.float32),
            jnp.asarray(beta, jnp.float32))


def weights(shape):
    return jnp.asarray(np.random.default_rng(7).standard_normal(shape),
                       jnp.float32)


def out_and_grads(fn, operands):
    """(o, its five gradients under a fixed random cotangent), one
    compiled program for the six."""
    cotangent = weights(operands[2].shape)

    def loss(*a):
        out = fn(*a)
        return jnp.sum(out.astype(jnp.float32) * cotangent), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*operands)
    return (out,) + grads


@functools.lru_cache(maxsize=None)
def wanted(big_beta):
    return out_and_grads(recurrence, draw(1, big_beta))


def far(got, want):
    got, want = (a.astype(jnp.float32) for a in (got, want))
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


IMPLEMENTATIONS = {
    "twin": lambda chunk: functools.partial(gd.gated_delta_ref, chunk=chunk),
    "kernel": lambda chunk: functools.partial(gd.gated_delta, chunk=chunk,
                                              interpret=True),
}


@pytest.mark.parametrize("big_beta", [True, False],
                         ids=["beta_to_2", "beta_to_1"])
@pytest.mark.parametrize("chunk", [16, 32])
@pytest.mark.parametrize("which", sorted(IMPLEMENTATIONS))
def test_the_chunk_form_is_the_recurrence_in_the_output_and_every_gradient(
        which, chunk, big_beta):
    """Six and three chunks a sequence; a head that forgets everything
    and one that forgets nothing beside an ordinary one; dq, dk, dv, dg
    and dbeta each within 2e-5 of the recurrence's (float32 rounding
    over 96 tokens reads 2e-7 .. 1e-6; dg of the head that forgets
    everything is ~1e-9 of the others' and is held with them)."""
    got = out_and_grads(IMPLEMENTATIONS[which](chunk), draw(1, big_beta))
    names = ("o", "dq", "dk", "dv", "dg", "dbeta")
    errors = {name: far(a, b) for name, a, b in zip(
        names, got, wanted(big_beta))}
    assert max(errors.values()) < 2e-5, errors
    # the head that forgets nothing, alone: its gradients are not lost
    # in the norm of the others'
    for name, a, b in zip(names, got, wanted(big_beta)):
        assert far(a[:, 2], b[:, 2]) < 2e-5, name


def test_bfloat16_operands_stay_within_their_own_tolerance():
    """bfloat16 q, k, v (the decays, the inverse and the state float32):
    2^-9 a rounding, a few dozen roundings a chunk: under 1e-2 of the
    float32 recurrence on the same bfloat16 values, in the output and
    every gradient (3-4e-3 read)."""
    operands = draw(2, True, jnp.bfloat16)
    got = out_and_grads(IMPLEMENTATIONS["kernel"](16), operands)
    want = out_and_grads(recurrence, operands)
    assert got[0].dtype == jnp.bfloat16 and got[1].dtype == jnp.bfloat16
    assert got[4].dtype == jnp.float32
    errors = [far(a, b) for a, b in zip(got, want)]
    assert max(errors) < 1e-2, errors
    assert max(errors) > 1e-4      # and it is bfloat16 that ran


def test_two_sequences_in_a_batch_leak_no_state_into_each_other():
    operands = draw(3, True)
    both = gd.gated_delta(*operands, chunk=32, interpret=True)
    for i in range(B):
        alone = gd.gated_delta(*(x[i:i + 1] for x in operands), chunk=32,
                               interpret=True)
        np.testing.assert_allclose(both[i:i + 1], alone, rtol=0, atol=1e-6)
    # and the second is not the first's continuation
    joined = recurrence(*(jnp.concatenate([x[0:1], x[1:2]], axis=2)
                          for x in operands))
    assert far(both[1:2], joined[:, :, T:]) > 1e-2


def shared_direction():
    """Keys behind a SiLU share a direction; here k_i . k_j ~ 0.9 for
    every pair, no decay and beta near 2, 256 tokens."""
    r = np.random.default_rng(5)
    q, k, v, g, beta = draw(5, True, seq=256)
    k = k + 0.7 * jnp.asarray(r.standard_normal((1, 1, 1, DK)), jnp.float32)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = jnp.zeros_like(g) - 1e-4
    return q, k, v, g, jnp.clip(beta + 0.9, 0.0, 1.98)


@functools.lru_cache(maxsize=None)
def wanted_of_shared_direction():
    return recurrence(*shared_direction())


@pytest.mark.parametrize("chunk", [64, 128])
@pytest.mark.parametrize("which", sorted(IMPLEMENTATIONS))
def test_keys_that_share_a_direction_do_not_break_the_inverse(which, chunk):
    """The Neumann series of the chunk's nilpotent matrix over a whole
    chunk of 64 reads NaN in float32 on these keys and over diagonal
    blocks of 16 1e-2; block forward substitution from pairs of tokens
    reads 2-3e-6, in the twin and in the kernel, whose chunks of 64 go
    through the joins two at a time on one [128, 128] operand and whose
    chunks of 128 one at a time."""
    assert gd.pack_of(256, chunk) == {64: 2, 128: 1}[chunk]
    got = IMPLEMENTATIONS[which](chunk)(*shared_direction())
    assert far(got, wanted_of_shared_direction()) < 1e-5


# -- what the forward hands the backward -------------------------------------


def forward_results(operands, chunk):
    """``gdn_fwd``'s three results on [B, H, T, .] operands."""
    q, k, v, g, beta = operands
    planes = lambda x: x.reshape(-1, *x.shape[2:])
    return gd._fwd_call(planes(q), planes(k), planes(v),
                        gd._gates(g, beta, chunk), chunk, H,
                        gd.pack_of(q.shape[2], chunk), True)


def twins_inverses(operands, chunk):
    """``_inverse`` of the twin's ``A``, [B * H, T / C, C, C] float32: a
    chunk's alone, a [C, C] operand."""
    _, k, _, g, beta = operands
    split = lambda x: x.astype(jnp.float32).reshape(
        B * H, -1, chunk, *x.shape[3:])
    k, g, beta = split(k), split(g), split(beta)
    b = jnp.cumsum(g, axis=-1)
    _, lower, strict = gd._masks(chunk)
    diff = b[..., :, None] - b[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kk = jnp.matmul(k, jnp.swapaxes(k, -1, -2),
                    precision=lax.Precision.HIGHEST)
    return gd._inverse(jnp.where(strict, kk * decay, 0.0) * beta[..., None])


INVERSE_DRAWS = {
    "keys_share_a_direction": (shared_direction, jnp.float32),
    "two_sequences": (lambda: draw(3, True, seq=256), jnp.float32),
    "two_sequences_bfloat16": (lambda: draw(3, True, seq=256),
                               jnp.bfloat16),
}


@pytest.mark.parametrize("chunk,pack", [(64, 2), (32, 2), (128, 1)])
@pytest.mark.parametrize("case", sorted(INVERSE_DRAWS))
def test_the_forwards_third_result_is_the_inverse_of_the_twins_matrix(
        case, chunk, pack):
    """Chunk by chunk and head by head, in the compute dtype, P chunks'
    side by side in a row of P C: the joins over a [P C, P C] operand
    with P chunks' ``A`` on its diagonal are the joins over each [C, C]
    alone (zeros added: 1e-6 apart in float32 here, where the
    interpreter's products sum in another order; the same bfloat16
    values but for a rounding's tie); the states come second, float32,
    and both sequences of the batch have their own."""
    make, dtype = INVERSE_DRAWS[case]
    q, k, v, g, beta = make()
    operands = (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta)
    o, states, inv = forward_results(operands, chunk)
    chunks = 256 // chunk
    assert gd.pack_of(256, chunk) == pack
    assert o.shape == (B * H, 256, DV) and o.dtype == dtype
    assert states.shape == (B * H, chunks, DK, DV)
    assert states.dtype == jnp.float32
    assert inv.shape == (B * H, chunks // pack, chunk, pack * chunk)
    assert inv.dtype == dtype
    apart = jnp.moveaxis(inv.reshape(B * H, chunks // pack, chunk, pack,
                                     chunk), 3, 2)
    got = apart.reshape(B * H, chunks, chunk, chunk).astype(jnp.float32)
    want = twins_inverses(operands, chunk)
    # no identity matrices, but for the head that forgets everything:
    # every chunk's has entries under its diagonal
    under = jnp.abs(want - jnp.eye(chunk)).max(axis=(2, 3))
    assert float(under.reshape(B, H, -1)[:, (0, 2)].min()) > 0.1
    tolerance = 2e-5 if dtype == jnp.float32 else 2 ** -8
    for head in range(B * H):
        for c in range(chunks):
            np.testing.assert_allclose(
                got[head, c], want[head, c].astype(dtype).astype(jnp.float32),
                rtol=tolerance, atol=tolerance, err_msg="%d %d" % (head, c))
    assert gd.inverse_bytes(B * 256, H, jnp.dtype(dtype).itemsize, pack,
                            chunk) == B * H * chunks // pack * chunk * max(
        128, pack * chunk) * jnp.dtype(dtype).itemsize


def kernels_jaxprs(chunk, seq=256):
    """{call name: its kernel's jaxpr} of the op's forward and backward."""
    operands = draw(3, True, seq=seq)
    fn = functools.partial(gd.gated_delta, chunk=chunk, interpret=True)
    whole = jax.make_jaxpr(
        lambda *a: jax.vjp(fn, *a)[1](a[2]))(*operands)
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(whole.jaxpr)
    return found


def highest(eqn):
    return sum("HIGHEST" in str(e.params["precision"])
               for e in eqn.params["jaxpr"].eqns
               if e.primitive.name == "dot_general")


@pytest.mark.parametrize("chunk,joins", [(64, 10), (32, 8), (128, 12)])
def test_the_inverse_is_built_once_a_pack_in_the_forward_and_never_behind_it(
        chunk, joins):
    """A grid step of ``gdn_fwd`` runs the joins once a head for the P
    chunks it walks (ten at 64, whether it walks one or two), and
    ``gdn_bwd``, which reads the result, holds no product at the highest
    precision at all."""
    calls = kernels_jaxprs(chunk)
    assert sorted(calls) == ["gdn_bwd", "gdn_fwd"]
    fwd, bwd = calls["gdn_fwd"], calls["gdn_bwd"]
    assert highest(fwd) == joins * H          # H heads a grid step
    assert highest(bwd) == 0
    pack = gd.pack_of(256, chunk)
    block = lambda eqn, at: eqn.params["grid_mapping"].block_mappings[
        at].block_shape
    # q's block: H heads x P chunks' rows
    assert tuple(int(getattr(n, "block_size", n) or 1)
                 for n in block(fwd, 0))[:2] == (H, pack * chunk)
    # the backward's sixth operand is the forward's third result
    assert tuple(bwd.invars[5].aval.shape) == tuple(
        fwd.params["out_avals"][2].shape)


def test_a_length_the_kernel_does_not_tile_takes_the_twin_and_says_so(
        monkeypatch):
    said = []
    monkeypatch.setattr(gd.flash_attention, "announce_fallback",
                        lambda *a: said.append(a))
    assert gd.delta_mode(100, DK, DV, 32, interpret=False) == (
        "off", "seq 100 is not a multiple of the chunk 32")
    assert gd.delta_mode(96, DK, DV, 32, interpret=True) == ("interpret", "")
    operands = draw(4, True, seq=100)
    got = gd.gated_delta(*operands, chunk=32, interpret=False)
    assert said and said[0][0] == "gated_delta" and said[0][3] == "tpu"
    assert far(got, recurrence(*operands)) < 2e-5


# -- a decay that is a vector a head (Kimi Delta Attention) --------------------


def channel_recurrence(q, k, v, g, beta):
    """S' = S Diag(alpha); u = beta (v - S' k); S = S' + u k^T; o = S q,
    ``g`` [B, H, T, d_k] a log decay a channel of the key."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))

    def token(S, x):
        q, k, v, g, beta = x
        S = S * jnp.exp(g)[..., None, :]
        u = beta[..., None] * (v - jnp.einsum("bhvk,bhk->bhv", S, k))
        S = S + u[..., :, None] * k[..., None, :]
        return S, jnp.einsum("bhvk,bhk->bhv", S, q)

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta))
    start = jnp.zeros(q.shape[:2] + (v.shape[-1], q.shape[-1]), jnp.float32)
    return jnp.moveaxis(lax.scan(token, start, xs)[1], 0, 2)


def draw_channels(seed, dtype=jnp.float32, seq=T):
    """``draw``'s operands with a decay a channel: head 0's channels
    decay by 0.4-0.99 a token each; head 1's EVEN channels forget
    everything at every token (alpha ~ 1e-9) beside ODD ones that forget
    nothing (alpha > 0.999): the product (K * exp(b)) (K * exp(-b))^T
    would overflow in the first and the kernel must not lose the second;
    head 2 nearly nothing anywhere."""
    q, k, v, _, beta = draw(seed, True, dtype, seq=seq)
    r = np.random.default_rng(seed + 100)
    g = -r.uniform(0.01, 1.0, (B, H, seq, DK))
    g[:, 1, :, 0::2] = -r.uniform(10.0, 30.0, (B, seq, DK // 2))
    g[:, 1, :, 1::2] = -r.uniform(0.0, 1e-3, (B, seq, DK // 2))
    g[:, 2] = -r.uniform(0.0, 1e-3, (B, seq, DK))
    return q, k, v, jnp.asarray(g, jnp.float32), beta


FLOOR = -5.0


def draw_floored(seed, dtype=jnp.float32, seq=T):
    """``draw_channels`` under a gate with a floor, ``g`` in [FLOOR, 0]:
    head 0's channels anywhere in the range; head 1's EVEN channels AT
    the floor every token (a sub-block's last row lies 15 x 5 = 75 under
    its first: the right scale reaches exp(75) = 3.7e32) beside ODD ones
    that forget nothing; head 2 nearly nothing anywhere."""
    q, k, v, _, beta = draw(seed, True, dtype, seq=seq)
    r = np.random.default_rng(seed + 200)
    g = r.uniform(FLOOR, 0.0, (B, H, seq, DK))
    g[:, 1, :, 0::2] = FLOOR
    g[:, 1, :, 1::2] = -r.uniform(0.0, 1e-3, (B, seq, DK // 2))
    g[:, 2] = -r.uniform(0.0, 1e-3, (B, seq, DK))
    return q, k, v, jnp.asarray(g, jnp.float32), beta


CHANNEL_DRAWS = {"any_decay": draw_channels, "floored": draw_floored}


@functools.lru_cache(maxsize=None)
def wanted_channels(draw="any_decay", seq=T):
    return out_and_grads(channel_recurrence, CHANNEL_DRAWS[draw](1, seq=seq))


@pytest.mark.parametrize("chunk,seq,draw,floor", [
    (16, T, "any_decay", 0.0), (32, T, "any_decay", 0.0),
    (96, T, "any_decay", 0.0),
    (32, T, "floored", FLOOR), (64, 128, "floored", FLOOR)],
    ids=["16", "32", "96", "32-floor-5", "64-floor-5"])
@pytest.mark.parametrize("which", sorted(IMPLEMENTATIONS))
def test_the_vector_decays_chunk_form_is_the_recurrence_and_every_gradient(
        which, chunk, seq, draw, floor):
    """Sub-blocks of 16 in chunks of 16 (the direct columns alone), 32
    and 96 (reference rows between sub-blocks too; six of them a chunk),
    packs of two chunks and of one: o, dq, dk, dv, dg A CHANNEL and
    dbeta each within 2e-5 of the token-by-token recurrence's, and the
    head whose channels forget everything beside channels that forget
    nothing held alone (every exponent is a difference against a row
    between the pair, so nothing overflows and nothing is lost).

    Under a promised floor of -5 (``pairs_of``: "block") a sub-block's
    own scores stand against its FIRST row, one term for it and every
    earlier sub-block of its chunk, two and four a chunk here: the same
    2e-5, every entry finite, the head whose even channels sit at the
    floor beside odd ones that forget nothing held alone."""
    assert gd.pairs_of(floor) == ("block" if floor else "columns")
    operands = CHANNEL_DRAWS[draw](1, seq=seq)
    wanted = wanted_channels(draw, seq)
    got = out_and_grads(functools.partial(
        IMPLEMENTATIONS[which](chunk), floor=floor), operands)
    names = ("o", "dq", "dk", "dv", "dg", "dbeta")
    assert got[4].shape == operands[3].shape
    errors = {name: far(a, b) for name, a, b in zip(names, got, wanted)}
    assert max(errors.values()) < 2e-5, errors
    assert all(bool(jnp.isfinite(a).all()) for a in got)
    for head in (1, 2):
        for name, a, b in zip(names, got, wanted):
            assert far(a[:, head], b[:, head]) < 2e-5, (head, name)


@pytest.mark.parametrize("which", sorted(IMPLEMENTATIONS))
def test_a_vector_decay_constant_across_channels_is_the_scalar_kernels(
        which):
    """The scalar decay broadcast to every channel of the key is the
    scalar recurrence: the vector path's output and q, k, v, beta
    gradients are the scalar path's, and its dg summed over the channels
    the scalar's dg."""
    q, k, v, g, beta = draw(1, True)
    wide = jnp.broadcast_to(g[..., None], g.shape + (DK,))
    fn = IMPLEMENTATIONS[which](32)
    scalar = out_and_grads(fn, (q, k, v, g, beta))
    vector = out_and_grads(fn, (q, k, v, wide, beta))
    for at, (a, b) in enumerate(zip(vector, scalar)):
        a = a.sum(-1) if at == 4 else a
        assert far(a, b) < 2e-5, at


@pytest.mark.parametrize("draw,floor", [("any_decay", 0.0),
                                        ("floored", FLOOR)])
def test_the_vector_decay_in_bfloat16_stays_within_its_own_tolerance(
        draw, floor):
    """bfloat16 q, k, v; the decays, their cumulative sums, ``A``, the
    inverse's joins and the state float32: under 1e-2 of the float32
    recurrence on the same bfloat16 values in the output and every
    gradient, dg a channel in float32.  Under a promised floor the right
    operand of a sub-block's term is scaled by up to exp(75) before it
    is cast: bfloat16 ends where float32 does, and the tolerance is the
    same."""
    operands = CHANNEL_DRAWS[draw](2, jnp.bfloat16)
    got = out_and_grads(functools.partial(
        IMPLEMENTATIONS["kernel"](32), floor=floor), operands)
    want = out_and_grads(channel_recurrence, operands)
    assert got[0].dtype == jnp.bfloat16 and got[4].dtype == jnp.float32
    errors = [far(a, b) for a, b in zip(got, want)]
    assert 1e-4 < max(errors) < 1e-2, errors


def products(eqn):
    return sum(e.primitive.name == "dot_general"
               for e in eqn.params["jaxpr"].eqns)


# ``dot_general``s of a grid step of (``kda_fwd``, ``kda_bwd``), a head,
# at a pack of two chunks of 64: the score products of ``_pair_terms``
# | ``_block_terms`` (19 | 4, the four over a sub-block's own 64 of the
# 256 rows; the backward rebuilds them and adds two cotangent products a
# term) beside what does not change: the forward's ten joins, the chunk
# walk's five products a chunk forward and twelve backward.
PRODUCTS = {"columns": (19 + 20, 19 + 38 + 24), "block": (4 + 20, 4 + 8 + 24)}


@pytest.mark.parametrize("floor,pairs", [
    (0.0, "columns"), (FLOOR, "block"), (-6.0, "columns")])
def test_a_vector_decays_calls_have_names_of_their_own_and_share_the_joins(
        floor, pairs):
    """``kda_fwd`` / ``kda_bwd``: names the scalar decay's trace reader
    (``gdn_(fwd|bwd)``) does not match; the forward runs the scalar
    kernel's ten joins once a head for a pack of two chunks of 64, the
    backward none, and takes the forward's inverses as an operand.  A
    promised floor of -5 takes 15 products a head a pack out of the
    forward and 45 out of the backward and leaves the joins as they are;
    one of -6, too low for a sub-block of 16, takes none."""
    q, k, v, g, beta = draw_channels(3, seq=256)
    assert gd.pairs_of(floor) == pairs
    fn = functools.partial(gd.gated_delta, chunk=64, interpret=True,
                           floor=floor)
    whole = jax.make_jaxpr(lambda *a: jax.vjp(fn, *a)[1](a[2]))(
        q, k, v, g, beta)
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(whole.jaxpr)
    assert sorted(found) == ["kda_bwd", "kda_fwd"]
    assert highest(found["kda_fwd"]) == 10 * H
    assert highest(found["kda_bwd"]) == 0
    assert (products(found["kda_fwd"]), products(found["kda_bwd"])) == tuple(
        H * n for n in PRODUCTS[pairs])
    assert tuple(found["kda_bwd"].invars[6].aval.shape) == tuple(
        found["kda_fwd"].params["out_avals"][2].shape)


def test_a_vector_decay_at_a_length_off_the_chunk_takes_the_twin(monkeypatch):
    """T = 100 is no multiple of the chunk 32: the twin pads the last
    chunk with tokens that neither decay nor write, and says so; a chunk
    that is no multiple of the sub-block is refused by name."""
    said = []
    monkeypatch.setattr(gd.flash_attention, "announce_fallback",
                        lambda *a: said.append(a))
    assert gd.delta_mode(80, DK, DV, 40, interpret=True, vector=True) == (
        "off", "the chunk 40 is not a multiple of the sub-block 16")
    assert gd.delta_mode(80, DK, DV, 40, interpret=True) == ("interpret", "")
    operands = draw_channels(4, seq=100)
    got = gd.gated_delta(*operands, chunk=32, interpret=False)
    assert said and said[0][0] == "gated_delta" and said[0][3] == "tpu"
    assert far(got, channel_recurrence(*operands)) < 2e-5


# -- the convolution with a SiLU behind it -----------------------------------


def explicit_conv_silu(x, w):
    seq, taps = x.shape[1], w.shape[1]
    conv = jnp.zeros_like(x)
    for k in range(taps):
        back = taps - 1 - k
        moved = x if not back else jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :seq - back]], axis=1)
        conv = conv + w[:, k] * moved
    return conv * jax.nn.sigmoid(conv)


@pytest.mark.parametrize("which", ["reference", "kernel"])
def test_conv_silu_is_the_explicit_taps_first_rows_included(which):
    """4 taps over 3 row tiles of 32 a sequence, 2 sequences, 2 channel
    tiles: the rows at a tile's edges read the tile above (forward) and
    below (backward), a sequence's first rows read zeros."""
    r = np.random.default_rng(0)
    x = jnp.asarray(r.standard_normal((2, 96, 256)), jnp.float32)
    w = jnp.asarray(0.5 * r.standard_normal((256, 4)), jnp.float32)
    fn = sc.conv_silu_ref if which == "reference" else functools.partial(
        sc.conv_silu, interpret=True)
    assert sc.tiles(96, 256) == (32, 256)
    want = explicit_conv_silu(x, w)
    got = fn(x, w)
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(got[:, :4], want[:, :4], atol=2e-6)
    cot = weights(x.shape)
    grads = lambda f: jax.grad(lambda x, w: jnp.sum(f(x, w) * cot),
                               argnums=(0, 1))(x, w)
    for a, b in zip(grads(fn), grads(explicit_conv_silu)):
        np.testing.assert_allclose(a, b, atol=3e-5)


def test_conv_silu_keeps_the_gated_ops_line_apart(caplog):
    from elasticdl_tpu.ops import flash_attention as fa

    sc.announce_conv.cache_clear()
    fa.logger.addHandler(caplog.handler)
    try:
        with caplog.at_level("INFO"):
            sc.conv_silu(jnp.zeros((1, 32, 128)), jnp.zeros((128, 4)))
            sc.short_conv(jnp.zeros((1, 32, 384)), jnp.zeros((128, 3)))
    finally:
        fa.logger.removeHandler(caplog.handler)
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("short conv:")]
    assert lines == [
        "short conv: rows=32 channels=128 tile=- kernel=off epilogue=silu",
        "short conv: rows=32 channels=128 tile=- kernel=off"]
