"""The window's arithmetic on made-up completion times."""

import pytest

from benchmark.lib import slices


def _completions(gaps, t_open=100.0):
    out, t = [t_open], t_open
    for gap in gaps:
        t += gap
        out.append(t)
    return out


def test_the_window_closes_at_the_first_completion_past_its_length():
    # tasks every 2 s; 15 s asked for: the window holds 8 whole tasks and
    # closes at 16 s, and nothing after it counts
    got = slices.throughput(_completions([2.0] * 10), 100.0, 15.0, 1000)
    assert got["tasks"] == 8 and got["window_s"] == pytest.approx(16.0)
    assert got["records_per_s"] == pytest.approx(500.0)
    # slices close at the first completion >= 3 s: four of 4 s
    assert got["slices"] == [500.0] * 4


def test_a_completion_exactly_at_the_length_closes_the_window():
    got = slices.throughput(_completions([2.0] * 10), 100.0, 14.0, 1000)
    assert got["tasks"] == 7 and got["window_s"] == pytest.approx(14.0)


def test_slices_close_at_task_completions_only():
    rel = [1.0, 2.0, 3.5, 4.0, 7.2, 9.0]
    assert slices.cut(rel) == [(3.5, 3), (7.2 - 3.5, 2)]


def test_completions_before_the_window_do_not_count():
    comps = [90.0, 100.0, 102.0, 104.0, 106.0, 108.0, 110.0, 112.0]
    got = slices.throughput(comps, 100.0, 12.0, 10)
    assert got["tasks"] == 6 and len(got["slices"]) == 3


def test_median_of_an_even_and_odd_number_of_slices():
    got = slices.throughput(_completions([3.0, 4.0, 5.0]), 100.0, 12.0, 120)
    assert got["slices"] == [40.0, 30.0, 24.0]
    assert got["median_slice_records_per_s"] == 30.0
    assert got["records_per_s"] == pytest.approx(360 / 12.0)
    got = slices.throughput(_completions([3.0, 4.0, 5.0, 6.0]), 100.0, 18.0,
                            120)
    assert got["median_slice_records_per_s"] == pytest.approx(
        (30.0 + 24.0) / 2)


def test_a_stall_moves_the_rate_and_not_the_median_of_slices():
    steady = [1.8] * 24                      # twelve slices of two tasks
    stalled = list(steady)
    stalled[9] += 1.6                        # one 1.6 s stall
    a = slices.throughput(_completions(steady), 100.0, 43.0, 1024)
    b = slices.throughput(_completions(stalled), 100.0, 44.0, 1024)
    assert len(a["slices"]) == len(b["slices"]) == 12
    assert b["median_slice_records_per_s"] == pytest.approx(
        a["median_slice_records_per_s"])
    # the end-to-end rate is all the work over all the time: it moves
    assert b["records_per_s"] == pytest.approx(
        a["records_per_s"] * (24 * 1.8) / (24 * 1.8 + 1.6))
    assert a["stall_share"] == pytest.approx(0.0, abs=1e-9)
    assert b["stall_share"] == pytest.approx(
        100 * (1 - (24 * 1.8) / (24 * 1.8 + 1.6)), rel=1e-6)


def test_a_slowdown_of_every_task_moves_both():
    a = slices.throughput(_completions([1.8] * 24), 100.0, 40.0, 1024)
    b = slices.throughput(_completions([1.9] * 24), 100.0, 40.0, 1024)
    for key in ("records_per_s", "median_slice_records_per_s"):
        assert b[key] == pytest.approx(a[key] * 1.8 / 1.9)


def test_too_few_slices_give_a_rate_and_no_median():
    got = slices.throughput(_completions([2.0] * 4), 100.0, 7.0, 10)
    assert got["records_per_s"] == pytest.approx(5.0)
    assert got["median_slice_records_per_s"] is None
    assert got["stall_share"] is None


def test_a_window_no_task_closes_is_an_error_not_a_number():
    with pytest.raises(slices.NoWholeTask):
        slices.throughput(_completions([2.0] * 3), 100.0, 7.0, 10)
