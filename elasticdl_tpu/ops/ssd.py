"""State-space scan (Pallas TPU): the sequence mixer of a Mamba-2 layer,
a linear recurrence over T run a chunk of tokens at a time.

``ssd(x [B, T, H, P], b, c [B, T, G, N], g, dt [B, T, H]) -> y [B, T, H,
P]``.  A head carries a float32 state ``S`` [P, N], zero before a
sequence's first token, and at every token

    S_t = exp(g_t) S_(t-1) + dt_t x_t B_t^T
    y_t = S_t C_t

with ``g_t = dt_t A`` the log decay (<= 0) and ``dt_t`` the step, both
float32 a head; ``B`` and ``C`` (the keys and queries of the dual form)
are shared by the ``H / G`` heads of a group.  The skip ``D x`` is the
caller's (elementwise).  It is the gated delta rule's chunk form
(``ops/gated_delta.py``) WITHOUT the delta correction: no inverse, no
write strength inside a chunk's own system, ``U = dt X``.  Inside a
chunk of C tokens with start state S, ``b`` the cumulative sum of ``g``
from the chunk's first token:

    M   = lower(C B^T * exp(b_r - b_i)) diag(dt)            [C, C]
    Y   = M X + diag(exp(b)) (C S^T)
    S'  = exp(b_C) S + (diag(exp(b_C - b) dt) X)^T B

Every exponent is a difference ``b_r - b_i`` with i <= r, never
positive: nothing overflows however fast a head forgets, and a scalar
decay needs no factoring.

Two kernels on one grid (batch, group, pack of chunks; the pack axis
sequential), TOKEN-MAJOR: a grid step reads its group's ``B`` and ``C``
[rows, N] ONCE for all the group's heads and the heads' x side by side,
[rows, (H / G) P], as the projection and the convolution leave them: no
head-major copy of anything is made, and a head of 64 wastes no lanes.
``C B^T`` is one product a chunk for the whole group; the products with
the state run over all the group's heads at once ([C, N] x [N, (H / G)
P]); a head's own work is its [C, C] decay matrix and one product with
it, on the 128-lane block its x lies in.

 - ``ssd_fwd``: the group's states resident in VMEM in float32 across
   the pack axis; writes ``y`` and each chunk's START state (float32,
   [B, G, T / C, (H / G) P, N]: 268 MB a layer at 64 heads x 16,384 x
   64 | 128);
 - ``ssd_bwd``: the same grid walked from the last chunk to the first,
   the states' cotangent resident; takes the start states, writes dx,
   dB and dC (summed over the group's heads) and the cotangents of
   ``b`` and ``dt``.

Float32 whatever the compute dtype: ``dt``, the log decays and their
cumulative sums, the decay matrices, the state and its cotangent.  The
matmuls take their operands in the compute dtype (x's) and accumulate
in float32.

The forward's two results carry names for a remat policy
(models/remat_keep.py): with both kept the backward of a rematerialized
layer does not run ``ssd_fwd`` a second time.

Reference: ``ssd_ref``, the same chunk form in plain ``jax.numpy``
(float32 inside), differentiated by JAX: what ``ssd`` returns wherever
``ops/mode.py`` answers ``off`` or the kernel does not tile the shape
(it says so: ``announce_fallback``).  The token-by-token recurrence both
are held to is the tests' and the benchmark reference's, not this
file's.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops import flash_attention
from elasticdl_tpu.ops.batch_shard import per_batch_shard
from elasticdl_tpu.ops.gated_delta import (_NT, _TN, _col, _dot, _masks,
                                           _row)
from elasticdl_tpu.ops.mode import resolve

# ``checkpoint_name``s of the forward's results that the backward reads
# or the layer goes on with: the output and the chunk-start states.
KEEP_OUT, KEEP_STATES = "ssd_out", "ssd_states"

# Tokens a chunk: the MXU's width, so that a chunk's [C, C] products
# fill it.  Not the published ``chunk_size`` (which is also 128 for the
# model in the benchmark): the recurrence is the same whatever the chunk.
CHUNK = 128
# Chunks a grid step walks, the largest that divides the chunks.
PACKS = (2, 1)
LANES = 128
VMEM_LIMIT = 64 * 1024 * 1024

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


def pack_of(seq, chunk=CHUNK):
    """Chunks of a sequence of ``seq`` a grid step of the kernels walks."""
    return next(n for n in PACKS if seq // chunk % n == 0)


def states_bytes(rows, heads, width, state, chunk=CHUNK):
    """HBM bytes of the chunk-start states ``ssd_fwd`` writes for
    ``rows`` tokens of ``heads`` heads of ``width`` over a state of
    ``state`` (float32, a state's rows whole 128-lane tiles)."""
    return rows // chunk * heads * width * -(-state // LANES) * LANES * 4


# -- the plain twin ----------------------------------------------------------


def ssd_ref(x, b, c, g, dt, chunk=CHUNK):
    """The chunk form in plain ``jax.numpy``, float32 inside, x's dtype
    out; any T (the last chunk padded with tokens that neither decay nor
    write)."""
    batch, seq, heads, width = x.shape
    groups, state = b.shape[2:]
    per = heads // groups
    dtype = x.dtype
    pad = -seq % chunk
    if pad:
        widths = lambda a: [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)
        x, b, c, g, dt = (jnp.pad(a, widths(a)) for a in (x, b, c, g, dt))
    n = (seq + pad) // chunk
    # [n, B, C, G, ..]: chunks first, a group's heads an axis of their own
    split = lambda a, *tail: jnp.moveaxis(
        a.astype(_F32).reshape(batch, n, chunk, groups, *tail), 1, 0)
    x, b, c = split(x, per, width), split(b, state), split(c, state)
    g, dt = split(g, per), split(dt, per)
    _, lower, _ = _masks(chunk)
    lower = lower[:, :, None, None]

    def step(s, xs):
        x, b, c, g, dt = xs
        cum = jnp.cumsum(g, axis=1)                     # [B, C, G, per]
        last = cum[:, -1:]
        diff = cum[:, :, None] - cum[:, None]           # [B, r, i, G, per]
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
        qk = jnp.einsum("brgs,bigs->brig", c, b, precision=_HIGHEST)
        m = qk[..., None] * decay * dt[:, None]
        y = jnp.einsum("brigh,bighp->brghp", m, x, precision=_HIGHEST)
        y += jnp.exp(cum)[..., None] * jnp.einsum(
            "brgs,bghps->brghp", c, s, precision=_HIGHEST)
        u = x * (jnp.exp(last - cum) * dt)[..., None]
        s = jnp.exp(last[:, 0])[..., None, None] * s + jnp.einsum(
            "bighp,bigs->bghps", u, b, precision=_HIGHEST)
        return s, y

    s0 = jnp.zeros((batch, groups, per, width, state), _F32)
    _, y = lax.scan(step, s0, (x, b, c, g, dt))
    y = jnp.moveaxis(y, 0, 1).reshape(batch, seq + pad, heads, width)
    return y[:, :seq].astype(dtype)


# -- the kernels -------------------------------------------------------------


def _expand(heads, width):
    """[heads, heads * width] float32 of 0 / 1: row h is 1 over head h's
    lanes.  ``rows^T @ expand`` turns a row vector a head, [heads, C],
    into the columns that scale the heads' values side by side, [C,
    heads * width], and ``expand @ values^T`` sums a head's lanes back:
    on the MXU, where a transpose of a one-row array or a lane shuffle
    would stand."""
    head = lax.broadcasted_iota(jnp.int32, (heads, heads * width), 0)
    lane = lax.broadcasted_iota(jnp.int32, (heads, heads * width), 1)
    return (lane // width == head).astype(_F32)


def _columns(rows, expand):
    """rows [heads, C] float32 -> [C, heads * width]: column block h is
    rows[h] down the tokens (exact: one term a sum)."""
    return lax.dot_general(rows, expand, _TN, precision=_HIGHEST,
                           preferred_element_type=_F32)


def _head_sums(values, expand):
    """values [C, heads * width] float32 -> [heads, C]: a head's lanes
    summed."""
    return lax.dot_general(expand, values, _NT, precision=_HIGHEST,
                           preferred_element_type=_F32)


def _lane_blocks(heads, width):
    """[(first lane, lanes, the heads whose values lie there)]: the
    128-lane blocks of the heads' values side by side (a head wider than
    128 lanes is a block of its own)."""
    lanes = min(max(width, LANES), heads * width)
    per = lanes // width
    return [(at * lanes, lanes, range(at * per, (at + 1) * per))
            for at in range(heads // per)]


def _chunk(b, c, gates, heads, chunk):
    """What both kernels build of a chunk before its state enters: b, c
    [C, N] in the compute dtype, gates [heads, 2, C] float32 (a head's
    cumulative log decay above its step).  ``cum``, ``dt``, ``gamma``,
    ``to_end`` [heads, C], ``carry`` [heads, 1]; ``qk`` [C, C] float32,
    the group's; ``decay[h]`` [C, C] lower triangular."""
    eye, lower, _ = _masks(chunk)
    cum, dt = gates[:, 0, :], gates[:, 1, :]
    lane = lax.broadcasted_iota(jnp.int32, cum.shape, 1)
    last = jnp.sum(jnp.where(lane == chunk - 1, cum, 0.0), axis=1,
                   keepdims=True)
    decay = []
    for h in range(heads):
        row = cum[h:h + 1]
        diff = _col(row, eye) - row
        decay.append(jnp.where(
            lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0))
    return dict(eye=eye, lower=lower, cum=cum, dt=dt, gamma=jnp.exp(cum),
                to_end=jnp.exp(last - cum), carry=jnp.exp(last),
                qk=_dot(c, b, _NT), decay=decay)


def _scaled(s, carry, heads, width):
    """s [heads * width, N] with head h's rows times carry[h]."""
    return jnp.concatenate(
        [s[h * width:(h + 1) * width] * carry[h:h + 1]
         for h in range(heads)], axis=0)


def _own_lanes(shape, width):
    """[C, lanes] int32: which head of its lane block a lane is."""
    return lax.broadcasted_iota(jnp.int32, shape, 1) // width


def _fwd_kernel(x_ref, b_ref, c_ref, gates_ref, y_ref, states_ref, s_scr,
                *, heads, width, chunk):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    pack = x_ref.shape[0] // chunk
    expand = _expand(heads, width)
    s = s_scr[...]
    for p in range(pack):
        rows = slice(p * chunk, (p + 1) * chunk)
        x, b, c = x_ref[rows], b_ref[rows], c_ref[rows]
        dtype = x.dtype
        k = _chunk(b, c, gates_ref[:, p], heads, chunk)
        states_ref[p] = s
        # the state's part: every head of the group at once
        y = _dot(c, s.astype(dtype), _NT) * _columns(k["gamma"], expand)
        # a head's own: its decayed scores on the lane block its x is in
        parts = []
        for first, lanes, held in _lane_blocks(heads, width):
            block = x[:, first:first + lanes]
            own = _own_lanes((chunk, lanes), width)
            part = None
            for at, h in enumerate(held):
                m = (k["qk"] * k["decay"][h] * k["dt"][h:h + 1]).astype(dtype)
                mine = _dot(m, block)
                part = mine if part is None else jnp.where(
                    own == at, mine, part)
            parts.append(part)
        y += parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        y_ref[rows] = y.astype(y_ref.dtype)
        u = (x.astype(_F32) * _columns(k["to_end"] * k["dt"], expand)
             ).astype(dtype)
        s = _scaled(s, k["carry"], heads, width) + _dot(u, b, _TN)
    s_scr[...] = s


def _bwd_kernel(x_ref, b_ref, c_ref, gates_ref, states_ref, dy_ref, dx_ref,
                db_ref, dc_ref, dgates_ref, ds_scr, *, heads, width, chunk):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    pack = x_ref.shape[0] // chunk
    expand = _expand(heads, width)
    rowsum = lambda a: jnp.sum(a, axis=1, keepdims=True)
    colsum = lambda a: jnp.sum(a, axis=0, keepdims=True)
    ds = ds_scr[...]
    for p in reversed(range(pack)):
        rows = slice(p * chunk, (p + 1) * chunk)
        x, b, c, dy = (ref[rows] for ref in (x_ref, b_ref, c_ref, dy_ref))
        dtype = x.dtype
        cast = lambda a: a.astype(dtype)
        k = _chunk(b, c, gates_ref[:, p], heads, chunk)
        s = states_ref[p]
        xf, dyf = x.astype(_F32), dy.astype(_F32)
        sc, dsc = cast(s), cast(ds)
        gamma_cols = _columns(k["gamma"], expand)
        w = k["to_end"] * k["dt"]
        w_cols = _columns(w, expand)
        # Y = gamma (C S^T) + M X;  S' = carry S + (w X)^T B
        dyg = cast(dyf * gamma_cols)
        dgamma = _head_sums(_dot(c, sc, _NT) * dyf, expand)
        du = _dot(b, dsc, _NT)                        # [C, heads P]
        dw = _head_sums(du * xf, expand)
        dc = _dot(dyg, sc)
        db = _dot(cast(xf * w_cols), dsc)
        dx_state = du * w_cols
        # carry = exp(b_C) a head: <dS', S>, a scalar each
        dcarry = [jnp.sum(ds[h * width:(h + 1) * width]
                          * s[h * width:(h + 1) * width])
                  for h in range(heads)]
        ds = _scaled(ds, k["carry"], heads, width) + _dot(dyg, c, _TN)
        # a head's own: M = lower(qk decay) dt
        dqk = jnp.zeros_like(k["qk"])
        parts, dcum_rows, ddt_rows = [], [None] * heads, [None] * heads
        for first, lanes, held in _lane_blocks(heads, width):
            block, dblock = x[:, first:first + lanes], dy[:, first:first + lanes]
            own = _own_lanes((chunk, lanes), width)
            part = None
            for at, h in enumerate(held):
                decay, dt_row = k["decay"][h], k["dt"][h:h + 1]
                scores = k["qk"] * decay
                mine = _dot(cast(scores * dt_row), dblock, _TN)
                part = mine if part is None else jnp.where(
                    own == at, mine, part)
                dm = jnp.where(k["lower"], _dot(jnp.where(
                    own == at, dblock, jnp.zeros_like(dblock))
                    if len(held) > 1 else dblock, block, _NT), 0.0)
                dqk += dm * decay * dt_row
                f = dm * scores
                ddt_rows[h] = colsum(f)
                e = f * dt_row
                # decay = exp(b_r - b_i): a row's cotangents add, a
                # column's subtract
                dcum_rows[h] = _row(rowsum(e), k["eye"]) - colsum(e)
            parts.append(part)
        dx = dx_state + (parts[0] if len(parts) == 1
                         else jnp.concatenate(parts, axis=1))
        dx_ref[rows] = dx.astype(dx_ref.dtype)
        dqk = cast(dqk)
        dc_ref[rows] = (dc + _dot(dqk, b)).astype(dc_ref.dtype)
        db_ref[rows] = (db + _dot(dqk, c, _TN)).astype(db_ref.dtype)
        # w = exp(b_C - b) dt;  gamma = exp(b);  carry = exp(b_C)
        to_end = dw * w                              # d(b_C - b)
        dcum = dgamma * k["gamma"] - to_end
        ddt = dw * k["to_end"]
        at_last = lax.broadcasted_iota(jnp.int32, dcum.shape, 1) == chunk - 1
        dcum += jnp.where(at_last, rowsum(to_end), 0.0)
        for h in range(heads):
            dgates_ref[h, p, 0:1, :] = dcum[h:h + 1] + dcum_rows[h] + (
                jnp.where(at_last[h:h + 1],
                          dcarry[h] * k["carry"][h:h + 1], 0.0))
            dgates_ref[h, p, 1:2, :] = ddt[h:h + 1] + ddt_rows[h]
    ds_scr[...] = ds


def _specs(per, width, state, chunk, pack, steps, reverse):
    """BlockSpecs, for a grid (batch, group, pack of chunks), of x [B,
    T, H P] (a group's heads side by side), of B or C [B, T, G N], of
    the gates [B, H, T / C, 2, C] and of the states [B, G, T / C, per P,
    N]; the packs walked backwards with ``reverse``."""
    at = (lambda j: steps - 1 - j) if reverse else (lambda j: j)
    rows = pack * chunk
    return (pl.BlockSpec((None, rows, per * width),
                         lambda i, g, j: (i, at(j), g)),
            pl.BlockSpec((None, rows, state), lambda i, g, j: (i, at(j), g)),
            pl.BlockSpec((None, per, pack, 2, chunk),
                         lambda i, g, j: (i, g, at(j), 0, 0)),
            pl.BlockSpec((None, None, pack, per * width, state),
                         lambda i, g, j: (i, g, at(j), 0, 0)))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


# Both calls are jitted, so that the layers of a stack and a layer's
# second forward under remat, which trace them at the same shapes, trace
# and lower a kernel once.
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _fwd_call(x, b, c, gates, heads, width, chunk, pack, interpret):
    """(y [B, T, H P], chunk-start states [B, G, T / C, per P, N]
    float32); x [B, T, H P], b and c [B, T, G N], gates [B, H, T / C, 2,
    C] float32."""
    batch, seq, _ = x.shape
    groups = gates.shape[1] // heads
    state = b.shape[-1] // groups
    chunks = seq // chunk
    xs, bc, gate, states = _specs(heads, width, state, chunk, pack,
                                  chunks // pack, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, width=width,
                          chunk=chunk),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(
                       (batch, groups, chunks, heads * width, state), _F32)),
        grid=(batch, groups, chunks // pack),
        in_specs=[xs, bc, bc, gate],
        out_specs=(xs, states),
        scratch_shapes=[pltpu.VMEM((heads * width, state), _F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="ssd_fwd",
    )(x, b, c, gates)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _bwd_call(x, b, c, gates, states, dy, heads, width, chunk, pack,
              interpret):
    """(dx, db, dc, dgates)."""
    batch, seq, _ = x.shape
    groups = gates.shape[1] // heads
    state = b.shape[-1] // groups
    steps = seq // chunk // pack
    xs, bc, gate, held = _specs(heads, width, state, chunk, pack, steps,
                                True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, width=width,
                          chunk=chunk),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype),
                   jax.ShapeDtypeStruct(gates.shape, _F32)),
        grid=(batch, groups, steps),
        in_specs=[xs, bc, bc, gate, held, xs],
        out_specs=(xs, bc, bc, gate),
        scratch_shapes=[pltpu.VMEM((heads * width, state), _F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="ssd_bwd",
    )(x, b, c, gates, states, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _ssd(x, b, c, gates, heads, width, chunk, pack, interpret):
    return _fwd_call(x, b, c, gates, heads, width, chunk, pack, interpret)[0]


def _ssd_fwd(x, b, c, gates, heads, width, chunk, pack, interpret):
    y, states = _fwd_call(x, b, c, gates, heads, width, chunk, pack,
                          interpret)
    # named where they are made: a policy that saves both does not run
    # this forward a second time
    y = checkpoint_name(y, KEEP_OUT)
    states = checkpoint_name(states, KEEP_STATES)
    return y, (x, b, c, gates, states)


def _ssd_bwd(heads, width, chunk, pack, interpret, res, dy):
    return _bwd_call(*res, dy, heads, width, chunk, pack, interpret)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def _gates(g, dt, chunk):
    """g, dt [B, T, H] as the kernels take them, [B, H, T / C, 2, C]
    float32: ``b``, the cumulative sum of g from a chunk's first token,
    above dt."""
    rows = lambda a: jnp.swapaxes(a.astype(_F32), 1, 2).reshape(
        a.shape[0], a.shape[2], a.shape[1] // chunk, 1, chunk)
    return jnp.concatenate([jnp.cumsum(rows(g), axis=-1), rows(dt)], axis=3)


def _unfriendly(seq, per, width, state, chunk, mode):
    """Why the kernels cannot take this shape, or "" when they can."""
    if seq % chunk:
        return "seq %d is not a multiple of the chunk %d" % (seq, chunk)
    if mode == "tpu" and (chunk % LANES or state % LANES):
        return "the chunk %d or the state %d is not a multiple of %d" % (
            chunk, state, LANES)
    # the interpreter takes a group narrower than one lane block as it is
    if (mode == "tpu" or per * width > LANES) and (
            per * width % LANES or (LANES % width and width % LANES)):
        return ("a group's %d heads of %d side by side are not whole "
                "%d-lane blocks of whole heads" % (per, width, LANES))
    return ""


def ssd_mode(seq, per, width, state, chunk=CHUNK, interpret=None):
    """(mode: "tpu" | "interpret" | "off" as ``ssd`` runs a sequence of
    ``seq`` here for groups of ``per`` heads of ``width`` over a state
    of ``state``; why not the kernel or "")."""
    mode = resolve(interpret)
    why = "" if mode == "off" else _unfriendly(seq, per, width, state,
                                               chunk, mode)
    return ("off" if why else mode), why


def ssd(x, b, c, g, dt, chunk=CHUNK, interpret=None):
    """x [B, T, H, P] and b, c [B, T, G, N] in the compute dtype, g (the
    log decay, <= 0) and dt (the step) [B, T, H] -> y [B, T, H, P] in
    x's dtype; every sequence and head starts from a zero state, head h
    reads group ``h // (H / G)``.  Differentiable in all five.  The
    kernels where ``ops/mode.py`` allows them and the shape tiles, per
    shard of the declared batch axis; else ``ssd_ref``."""
    batch, seq, heads, width = x.shape
    groups, state = b.shape[2:]
    per = heads // groups
    mode, why = ssd_mode(seq, per, width, state, chunk, interpret)
    if mode == "off":
        if why:
            flash_attention.announce_fallback(
                "ssd", x.shape, why, resolve(interpret))
        return checkpoint_name(ssd_ref(x, b, c, g, dt, chunk), KEEP_OUT)

    def op(x, b, c, g, dt):
        flat = lambda a: a.reshape(*a.shape[:2], -1)
        y = _ssd(flat(x), flat(b), flat(c), _gates(g, dt, chunk), per,
                 width, chunk, pack_of(seq, chunk), mode == "interpret")
        return y.reshape(-1, seq, heads, width)

    return per_batch_shard(op, (x, b, c, g, dt))
