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


def _in_the_kernels_order(src, idx, weight=None):
    """``out[i] = sum_j weight[i, j] * src[idx[i, j]]`` in float32, the
    terms added one after another from j = 0, a choice that names no
    row as a zero: the order the kernel adds a row's terms in (it walks
    the named ones alone, and a zero added changes no bit)."""
    rows = src.shape[0]
    src = np.asarray(src.astype(jnp.float32))
    out = np.zeros((idx.shape[0], src.shape[1]), np.float32)
    for j in range(idx.shape[1]):
        named = (idx[:, j] >= 0) & (idx[:, j] < rows)
        term = np.where(named[:, None], src[np.where(named, idx[:, j], 0)],
                        np.float32(0))
        if weight is not None:
            term = term * np.asarray(weight)[:, j:j + 1]
        out = term if j == 0 else out + term
    return out


@pytest.mark.parametrize("k", [1, 6])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("width", [2048, 2560, 2688])
def test_row_sum_is_the_reference_at_the_cells_widths(width, dtype, k):
    """Three tiles of result rows, the middle one naming no row, the
    others with indices outside ``0 .. R`` among them, every row of the
    source at or past ``live`` NaN: plain, weighted, and (one index a
    result row) weighted with the row dots.  The sums are float32 added
    in the choices' order: the plain sum is the bits of that sum in
    numpy at every width, and the weighted one of the padded row (whose
    products the CPU's compiler may fuse into the adds) the bits the
    kernel gives the row's last 2,560 columns as a row of their own, in
    the layout that has no pad;
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
    for w, o in ((None, None), (weight, None), (weight, other))[
            :2 + (k == 1)]:
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
    if k == 1:
        assert not np.asarray(dots)[[3, 2 * tm + 5]].any()
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


def _sparse_indices(rng, tm, k, live):
    """[3 * tm, k] indices into ``0 .. live``: three tiles of result
    rows.  The first holds a row with no term, one whose one term is in
    the LAST column, one with all k, and a group of SUB_ROWS whose only
    full row is its last (the others: one term, in a middle column), a
    row with k - 1 terms (the count walked is the next that has a body:
    ``row_moves._bodies``); the second one live row and nothing else;
    the third a thin share, most rows with none, among them indices
    past the source."""
    g = row_moves.SUB_ROWS
    idx = np.full((3 * tm, k), -1, np.int64)
    idx[1, k - 1] = 5                        # row 0: no term; row 1: last
    idx[2] = rng.integers(0, live, k)        # all k
    idx[g:2 * g, k // 2] = rng.integers(0, live, g)
    idx[2 * g - 1] = rng.integers(0, live, k)
    idx[3 * g + 1, 0], idx[3 * g + 1, k - 1] = 7, 9      # first and last
    idx[4 * g + 2, 1:] = rng.integers(0, live, k - 1)    # all but the first
    idx[tm + tm // 2 + 3, 1] = 11            # a tile with one live row
    thin = rng.random((tm, k)) < 0.1
    idx[2 * tm:] = np.where(thin, rng.integers(0, live + 4, (tm, k)), -1)
    return idx


@pytest.mark.parametrize("k", [4, 6, 8])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("width", [2048, 2688, 4096])
def test_a_rows_terms_are_summed_in_their_columns_order(width, dtype, k):
    """``rows_sum`` walks a result row's named terms alone, moved to the
    first of its k slots in the order of their columns
    (``row_moves._moves``: a slot by the term's rank), and for sixteen
    rows as many as the fullest of them has.  So the float32 sum is the
    bits of the sum over all k columns with zeros where no row is named,
    plain and under weights that differ by column and by row (powers of
    two: a product is then exact, and no compiler's fused multiply-add
    rounds otherwise than numpy's two steps), and what ``row_sum_ref``
    gives; a row with no term is zeros whatever its slots held.  Rows
    the slots' budget has no room for are refused by name and take the
    reference."""
    rows, live = 200, 150
    words = row_moves.row_words(width, dtype)
    refused = row_moves.unfriendly(width, dtype, k, "interpret", rows)
    assert ("do not fit" in refused) == (
        4 * k * (row_moves.row_tile(k, 128) or 512) * words
        > row_moves._VMEM_SLOTS)
    tm = row_moves.row_tile(k, words) or 128
    rng = np.random.default_rng(width + k)
    idx = _sparse_indices(rng, tm, k, live)
    src = rng.standard_normal((rows, width), np.float32)
    src[live:] = np.nan
    src = jnp.asarray(src, dtype)
    index = jnp.asarray(idx, jnp.int32)
    exponent = (np.arange(k)[None, :] + np.arange(3 * tm)[:, None]) % 5 - 2
    weight = jnp.asarray(2.0 ** exponent, jnp.float32)
    for w in (None, weight):
        out = row_moves.row_sum(src, index, w, live=jnp.int32(live),
                                out_dtype=jnp.float32, interpret=True)[0]
        np.testing.assert_array_equal(
            out, _in_the_kernels_order(src, idx, w))
        np.testing.assert_allclose(
            out, row_moves.row_sum_ref(src, index, w, None, jnp.float32)[0],
            rtol=1e-6, atol=1e-6)
    none = ~((idx >= 0) & (idx < rows)).any(axis=1)
    assert none[0] and none[tm:tm + tm // 2].all()
    assert not np.asarray(out)[none].any()
    # in the source's own dtype too (the gather's pullback)
    same = row_moves.row_sum(src, index, live=jnp.int32(live),
                             interpret=True)[0]
    assert same.dtype == src.dtype
    np.testing.assert_array_equal(
        same.astype(jnp.float32),
        jnp.asarray(_in_the_kernels_order(src, idx)).astype(dtype).astype(
            jnp.float32))


def test_the_moves_put_a_rows_terms_first_and_count_them():
    """``_moves`` on one tile of 128 rows x 4: a named index's slot is
    its rank among its row's named ones x the tile + the row, the
    weights move with it, ``have`` counts a row's terms and ``walks``
    names the body that sums each SUB_ROWS rows, the most terms of any
    of them, which ``sum_terms`` adds up."""
    tm, k, rows, bits = 128, 4, 50, 6
    idx = np.full((tm, k), -1, np.int64)
    idx[0] = [-1, 7, 50, 9]          # 50 is past the source: no row
    idx[1] = [3, 2, 1, 0]
    idx[17] = [-1, -1, -1, 4]
    weight = np.arange(1, tm * k + 1, dtype=np.float32).reshape(tm, k)
    counts, walks, moves, have, packed = row_moves._moves(
        jnp.asarray(idx, jnp.int32), jnp.asarray(weight), rows, 1, tm, bits)
    assert int(counts[0]) == 7
    named = sorted(int(m) for m in np.asarray(moves)[:7])
    at = lambda rank, row, index: ((rank * tm + row) << bits) | index
    assert named == sorted([at(0, 0, 7), at(1, 0, 9), at(0, 1, 3),
                            at(1, 1, 2), at(2, 1, 1), at(3, 1, 0),
                            at(0, 17, 4)])
    assert (np.asarray(moves)[7:] == np.iinfo(np.int32).max).all()
    np.testing.assert_array_equal(np.asarray(have)[[0, 1, 2, 17], 0],
                                  [2, 4, 0, 1])
    np.testing.assert_array_equal(
        np.asarray(row_moves._bodies(k))[np.asarray(walks)], [4, 1] + [0] * 6)
    np.testing.assert_array_equal(np.asarray(packed)[[0, 1, 17]], [
        [weight[0, 1], weight[0, 3], 0, 0], weight[1],
        [weight[17, 3], 0, 0, 0]])
    terms, slots = row_moves.sum_terms(jnp.asarray(idx, jnp.int32), rows)
    assert (int(terms), int(slots)) == (16 * 5, tm * k)
    # past four terms the counts that have a body are the even ones and
    # k, which is tried first; every count the table can hold has one
    assert [row_moves._bodies(k) for k in (4, 6, 7, 8)] == [
        [4, 0, 1, 2, 3], [6, 0, 1, 2, 3, 4], [7, 0, 1, 2, 3, 4, 6],
        [8, 0, 1, 2, 3, 4, 6]]
    for k in range(2, 11):
        counts = np.arange(16 * (k + 1)) // 16
        held = row_moves._group_terms(
            jnp.asarray(np.arange(k)[None, :] < counts[:, None]))
        assert (np.asarray(held) >= np.arange(k + 1)).all()
        np.testing.assert_array_equal(
            np.asarray(row_moves._bodies(k))[
                np.asarray(row_moves._body_of(held, k))], held)
    five = jnp.asarray(np.where(np.arange(7) < 5, 1, -1)[None].repeat(
        16, axis=0), jnp.int32)
    assert int(row_moves.sum_terms(five, rows)[0]) == 16 * 6
    assert int(row_moves.sum_terms(five.at[3].set(1), rows)[0]) == 16 * 7
    # every choice a row: all the slots, as before
    full = jnp.asarray(np.arange(tm * k).reshape(tm, k) % rows, jnp.int32)
    assert tuple(map(int, row_moves.sum_terms(full, rows))) == (
        tm * k, tm * k)
    # a number of rows that is no whole SUB_ROWS: the last group's few
    few = jnp.asarray(idx[:20], jnp.int32)
    assert tuple(map(int, row_moves.sum_terms(few, rows))) == (16 * 5, 80)


def test_row_dots_go_with_one_index_a_row():
    src = jnp.zeros((8, 256), jnp.float32)
    with pytest.raises(ValueError, match="one index a result row"):
        row_moves.row_sum(src, jnp.zeros((16, 2), jnp.int32),
                          other=jnp.zeros((16, 256)), interpret=True)
