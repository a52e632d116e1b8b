"""The row kernel (``ops/row_moves.py``) alone: ``row_sum`` by the Pallas
interpreter against ``row_sum_ref`` at the widths the share cells move,
one of them a 16-bit row of an odd number of lane tiles (2,688 columns:
the low halves padded to 1,408 words), and which rows ``unfriendly``
takes on a chip.  Mosaic's own word on the padded row is
``tests/test_flash_compile_tpu.py``'s (a described v5e, one file of
kernels); a share's layer through these moves is
``tests/test_mixed_stack_rows.py``'s.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import row_moves


def _in_the_kernels_order(src, idx):
    """``out[i] = sum_j src[idx[i, j]]`` in float32, the terms added one
    after another from j = 0 as the kernel adds them."""
    rows = src.shape[0]
    src = np.asarray(src.astype(jnp.float32))
    out = np.zeros((idx.shape[0], src.shape[1]), np.float32)
    for j in range(idx.shape[1]):
        named = (idx[:, j] >= 0) & (idx[:, j] < rows)
        term = np.where(named[:, None], src[np.where(named, idx[:, j], 0)],
                        np.float32(0))
        out = term if j == 0 else out + term
    return out


@pytest.mark.parametrize("k", [1, 6])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("width", [2048, 2560, 2688])
def test_row_sum_is_the_reference_at_the_cells_widths(width, dtype, k):
    """Three tiles of result rows, the middle one naming no row, the
    others with indices outside ``0 .. R`` among them, every row of the
    source at or past ``live`` NaN: plain, weighted, and weighted with
    the row dots.  The sums are float32 added in the choices' order: the
    plain sum is the bits of that sum in numpy at every width, and the
    weighted one of the padded row (whose products the CPU's compiler
    may fuse into the adds) the bits the kernel gives the row's last
    2,560 columns as a row of their own, in the layout that has no pad;
    a gather moves bits.  Six float32 rows of these widths a result row
    are more slots than the kernel has: it says so, and ``row_sum`` is
    the reference."""
    rows, live = 200, 150
    refused = row_moves.unfriendly(width, dtype, k, "interpret", rows)
    assert ("do not fit" in refused) == (k == 6 and dtype == jnp.float32)
    tm = row_moves.row_tile(k, row_moves.row_words(width, dtype)) or 512
    m = 2 * tm + 40
    rng = np.random.default_rng(width + k)
    idx = rng.integers(-2, live + 2, (m, k))
    idx[idx >= live] = rows + 3               # past the source, not NaN rows
    idx[tm:2 * tm] = -1                       # a tile that names none
    idx[3], idx[2 * tm + 5] = -1, rows        # result rows with no source
    src = rng.standard_normal((rows, width), np.float32)
    src[live:] = np.nan
    src = jnp.asarray(src, dtype)
    has_first = (idx[:, :1] >= 0) & (idx[:, :1] < rows)
    other = jnp.asarray(np.where(
        has_first, rng.standard_normal((m, width)), np.nan), dtype)
    weight = jnp.asarray(rng.random((m, k), np.float32))
    index = jnp.asarray(idx, jnp.int32)
    kernel = lambda src, w, o: row_moves.row_sum(
        src, index, w, o, live=jnp.int32(live), out_dtype=jnp.float32,
        interpret=True)
    for w, o in ((None, None), (weight, None), (weight, other)):
        out, dots = kernel(src, w, o)
        want, want_dots = row_moves.row_sum_ref(src, index, w, o, jnp.float32)
        if w is None and not refused:
            np.testing.assert_array_equal(
                out, _in_the_kernels_order(src, idx))
        elif o is None and row_moves.row_words(width, dtype) * 2 != width:
            np.testing.assert_array_equal(
                out[:, -2560:], kernel(src[:, -2560:], w, None)[0])
        np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)
        if o is not None:
            np.testing.assert_allclose(dots, want_dots, rtol=1e-5, atol=1e-4)
    assert not np.asarray(out)[tm:2 * tm].any()
    assert not np.asarray(dots)[[3, 2 * tm + 5]].any()
    if k == 1:
        taken = row_moves.row_sum(src, index, interpret=True)[0]
        assert taken.dtype == src.dtype
        named = has_first[:, 0]
        np.testing.assert_array_equal(
            np.asarray(taken.astype(jnp.float32))[named],
            np.asarray(src.astype(jnp.float32))[idx[named, 0]])


@pytest.mark.parametrize("width,dtype,k,tile,why", [
    # the odd whole number of lane tiles: eleven tiles of words
    (2688, jnp.bfloat16, 1, 1024, ""),
    (2688, jnp.bfloat16, 6, 512, ""),
    (2688, jnp.float32, 1, 1024, ""),
    # no whole number of lanes itself
    (2624, jnp.bfloat16, 1, None, "no whole 128 lanes"),
    (2624, jnp.float32, 1, None, "no whole 128 lanes"),
    (2689, jnp.bfloat16, 1, None, "no whole 32-bit words"),
    # thirteen tiles of words six a result row: over the slots' budget
    (3328, jnp.bfloat16, 6, None, "do not fit"),
    # the seven cells that move their rows by the kernel
    (2048, jnp.bfloat16, 6, 512, ""),     # kanana-2-30b-a3b
    (2048, jnp.bfloat16, 4, 256, ""),     # lfm2-24b-a2b
    (2560, jnp.bfloat16, 8, 128, ""),     # ling-3.0-flash
    (2560, jnp.bfloat16, 6, 512, ""),     # smallthinker-21b-a3b
    (4096, jnp.bfloat16, 8, 128, ""),     # solar-open2-250b
    (2048, jnp.bfloat16, 8, 128, ""),     # trinity-mini
    (3584, jnp.bfloat16, 4, 256, ""),     # xing4.0-29b-a4b
])
def test_which_rows_the_kernel_takes_on_a_chip(width, dtype, k, tile, why):
    """``unfriendly`` in ``tpu`` mode and the tile ``row_sum`` then
    runs: a 16-bit row that is whole lanes is taken whether or not its
    half is, one that is not is refused by name, and the seven cells'
    rows keep their words (w / 2) and their tiles."""
    said = row_moves.unfriendly(width, dtype, k, "tpu", 16384)
    assert (why in said) if why else not said, said
    if not why:
        words = row_moves.row_words(width, dtype)
        assert words % 128 == 0 and words >= width * jnp.dtype(
            dtype).itemsize // 4
        if width != 2688:
            assert words == width * jnp.dtype(dtype).itemsize // 4
        assert row_moves.row_tile(k, words) == tile
