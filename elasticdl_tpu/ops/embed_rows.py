"""The embedding lookup with a derivative of its own.

``embed_rows(table [V, E], tokens [B, T], dtype)`` is
``table.astype(dtype)[tokens]``; its backward hands the table

    grad[v] = sum of g[b, t] over the (b, t) with tokens[b, t] == v

accumulated in float32 and in the table's own dtype.  JAX's derivative
of the two functions leaves XLA a scatter of bfloat16 rows into a
bfloat16 ``[V, E]`` and a convert pass.  What that scatter costs on a
v5e hangs on the row's width and not on its bytes: 16,384 rows into
37,984 take 0.5 / 1.1 / 2.2 / 3.0 / 4.5 / 4.1 ms at 512 / 1,024 / 1,536 /
2,048 / 3,072 / 4,096 wide but 16.0 at 2,560 and 22.6 at 3,840, float32
and bfloat16 alike, Zipf ids and uniform alike (PERF.md section 6, PR
53): a head matmul's time in ``smallthinker-21b-a3b.seq16384``'s step.
And a frequent id's thousand addends a step meet 8 bits of mantissa.

The kernel (``embed_grad`` in the compiled program and a device trace):
the ids are sorted once (XLA: 16,384 keys, 8 us) and the cotangent's
rows gathered into that order, so the rows of a block of ``TILE``
consecutive ids are consecutive.  The grid walks (block of the table,
chunk of ``CHUNK`` sorted rows) pairs that share a row, a list made
from the sorted ids (``_schedule``: at most blocks + chunks of them,
every block at least once: a block no id names writes its zeros).  A
step compares the chunk's ids with the block's and multiplies the 0 / 1
matrix ``[TILE, CHUNK]`` by the chunk's rows on the MXU into the block,
which stays in VMEM while consecutive steps name it: products with 1
are exact and the MXU's sums are float32, every row of the table is
written once, and no step looks at a single row.

Under the trainer's data axis (``axis``: what ``batch_shard.declared()``
gave where the lookup was traced) each shard adds its own rows and the
shards' tables are summed in ``dtype``, the all-reduce JAX's derivative
had: left to the partitioner the float32 table is what crosses the
chips, twice the bytes.

Reference: ``rows_added_ref``, one ``scatter-add`` of float32 rows, which
is also what runs wherever ``ops/mode.py`` answers ``off`` (the CPU, a
model-parallel mesh, whose partitioner owns the sharded table's sum) or
the row is no whole number of 128 lanes.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.ops import batch_shard
from elasticdl_tpu.ops.flash_attention import logger
from elasticdl_tpu.ops.mode import kernel_mode

# Consecutive ids a block of the table holds, and sorted rows a grid
# step multiplies: the MXU's width, twice.
TILE = 256
CHUNK = 256
# The widest block of columns a step takes (a float32 block of the
# table twice, the chunk's rows twice, the product once: 20 MB).
COLUMNS = 4096
VMEM_LIMIT = 48 * 1024 * 1024
# An id no row of the table has: the rows that pad the last chunk.
_NO_ID = jnp.iinfo(jnp.int32).max


@functools.lru_cache(maxsize=None)
def announce_embed_grad(rows, vocab, dim, form):
    """Once per compiled shape, by the logger ``announce_tiles`` uses:
    the tokens (of one shard of the trainer's data axis, where there is
    one) whose rows of the stream's cotangent are added into a
    ``[vocab, dim]`` table, and by what."""
    logger.info("embed grad: tokens=%d vocab=%d dim=%d form=%s "
                "acc=float32", rows, vocab, dim, form)


def rows_added_ref(tokens, g, vocab):
    """The same in plain ``jax.numpy``: float32 ``[vocab, E]``."""
    return jnp.zeros((vocab, g.shape[-1]), jnp.float32).at[tokens].add(
        g.astype(jnp.float32), mode="promise_in_bounds")   # as the gather


def _schedule(ids, vocab):
    """The (block, chunk) pairs a call walks, from the sorted ids:
    ``(block of step [S], chunk of step [S], live steps [1])`` with S =
    blocks + chunks, the most there can be: a block owns the chunks its
    ids' rows touch, so consecutive blocks share at most one.  An empty
    block has one step (its zeros are written), whose chunk holds none
    of its ids.  Steps past the live ones name the last block, so no
    block is written twice."""
    blocks, chunks = -(-vocab // TILE), ids.shape[0] // CHUNK
    starts = jnp.searchsorted(
        ids, jnp.arange(blocks + 1, dtype=jnp.int32) * TILE).astype(
            jnp.int32)
    lo = jnp.minimum(starts[:-1] // CHUNK, chunks - 1)
    hi = jnp.maximum(-(-starts[1:] // CHUNK), lo + 1)
    ends = jnp.cumsum(hi - lo)
    step = jnp.arange(blocks + chunks, dtype=jnp.int32)
    block = jnp.minimum(
        jnp.searchsorted(ends, step, side="right"), blocks - 1).astype(
            jnp.int32)
    chunk = jnp.minimum(lo[block] + step - (ends - (hi - lo))[block],
                        chunks - 1)
    return block, chunk.astype(jnp.int32), ends[-1:].astype(jnp.int32)


def _kernel(block_of, chunk_of, live, ids_ref, g_ref, out_ref):
    step = pl.program_id(1)
    block = block_of[step]

    @pl.when((step == 0) | (block_of[jnp.maximum(step - 1, 0)] != block))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(step < live[0])
    def _():
        rows = block * TILE + lax.broadcasted_iota(
            jnp.int32, (TILE, CHUNK), 0)
        named = jnp.where(rows == ids_ref[...], 1.0, 0.0).astype(g_ref.dtype)
        out_ref[...] += jnp.dot(
            named, g_ref[...], preferred_element_type=jnp.float32,
            precision=(lax.Precision.HIGHEST
                       if g_ref.dtype == jnp.float32 else None))


def _columns(width):
    """Columns of one block: all of them up to COLUMNS, else the
    largest half, quarter, .. that is whole lanes."""
    tn = width
    while tn > COLUMNS and tn % 256 == 0:
        tn //= 2
    return tn


def rows_added(tokens, g, vocab, interpret=False):
    """float32 ``[vocab, E]``, by the kernel (the module's docstring):
    ``tokens`` any shape of int32 ids, ``g`` the same shape and E."""
    width = g.shape[-1]
    ids, order = lax.sort_key_val(
        tokens.reshape(-1), jnp.arange(tokens.size, dtype=jnp.int32))
    rows = g.reshape(-1, width)[order]
    short = -tokens.size % CHUNK
    if short:
        ids = jnp.pad(ids, (0, short), constant_values=_NO_ID)
        rows = jnp.pad(rows, ((0, short), (0, 0)))
    block, chunk, live = _schedule(ids, vocab)
    tn = _columns(width)
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((vocab, width), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(width // tn, block.shape[0]),
            in_specs=[
                pl.BlockSpec((1, CHUNK),
                             lambda j, i, block, chunk, live: (0, chunk[i])),
                pl.BlockSpec((CHUNK, tn),
                             lambda j, i, block, chunk, live: (chunk[i], j)),
            ],
            out_specs=pl.BlockSpec(
                (TILE, tn), lambda j, i, block, chunk, live: (block[i], j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        # The HLO instruction's name, so the trace's.
        name="embed_grad",
    )(block, chunk, live, ids.reshape(1, -1), rows)


def embed_mode(width):
    """``kernel_mode()`` for a table of rows ``width`` wide: ``off``
    too where a row is no whole number of lanes."""
    return kernel_mode() if width % 128 == 0 else "off"


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _embed_rows(table, tokens, dtype, axis, mode):
    return table.astype(dtype)[tokens]


def _embed_rows_fwd(table, tokens, dtype, axis, mode):
    form = {"tpu": "kernel", "interpret": "interpreter",
            "off": "scatter_add"}[mode]
    announce_embed_grad(tokens.size // batch_shard.shards_of(axis),
                        *table.shape, form)
    # the table for its shape and dtype alone: the backward reads no
    # value of it
    return _embed_rows(table, tokens, dtype, axis, mode), (table, tokens)


def _embed_rows_bwd(dtype, axis, mode, residuals, g):
    table, tokens = residuals
    vocab = table.shape[0]
    added = (rows_added_ref if mode == "off" else functools.partial(
        rows_added, interpret=mode == "interpret"))
    if batch_shard.shards_of(axis) == 1:
        grad = added(tokens, g, vocab)
    else:
        mesh, name = axis
        grad = jax.shard_map(
            lambda tokens, g: lax.psum(
                added(tokens, g, vocab).astype(dtype), name),
            mesh=mesh, in_specs=(P(name), P(name)), out_specs=P(),
            check_vma=False)(tokens, g)
    return grad.astype(table.dtype), None


_embed_rows.defvjp(_embed_rows_fwd, _embed_rows_bwd)


def embed_rows(table, tokens, dtype, mesh=None):
    """``table.astype(dtype)[tokens]``, the table's gradient this
    module's.  ``mesh``: a model-parallel mesh, which shards the table
    and whose partitioner owns its gradient's sum.  The tracing
    context's facts (the data axis, which code may run) are read here,
    where the lookup is traced: the backward is traced after their
    blocks have closed."""
    model_parallel = mesh is not None
    return _embed_rows(
        table, tokens, jnp.dtype(dtype),
        None if model_parallel else batch_shard.declared(),
        "off" if model_parallel else embed_mode(table.shape[1]))
