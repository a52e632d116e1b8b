"""The trace reduction on the hand-built fixture."""

import json
import os

import pytest

from benchmark.lib import xplane

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures", "tiny_trace.json")


@pytest.fixture(scope="module")
def trace():
    with open(FIXTURE) as fh:
        return json.load(fh)


def test_union_merges_nested_and_touching_intervals():
    assert xplane.union([(5, 7), (0, 4), (1, 2), (4, 5)]) == [(0, 7)]
    assert xplane.total(xplane.union([(0, 2), (5, 6)])) == 3


def test_self_time_takes_children_out_of_a_while():
    got = dict(xplane.self_times([["while.1", 100, 400],
                                  ["fusion.1", 100, 100],
                                  ["cc", 250, 200]]))
    assert got == {"while.1": 100, "fusion.1": 100, "cc": 200}


def test_busy_union_and_idle_share(trace):
    got = xplane.reduce(trace)
    # the devices' window: 100 (first op) .. 1300 (last op's end) ns
    assert got["window_s"] == pytest.approx(1200e-9)
    # chip 0 busy 100..600 and 1000..1300 = 800; chip 1 busy 500; mean 650
    assert got["busy_s"] == pytest.approx(650e-9)
    assert 1 - got["busy_s"] / got["window_s"] == pytest.approx(
        1 - 650 / 1200)
    # the host's events cover 0..2000
    assert got["host_window_s"] == pytest.approx(2000e-9)
    assert got["chips"] == 2


def test_per_kernel_time_and_mosaic_share(trace):
    got = xplane.reduce(trace)
    (seconds, calls), = got["custom_calls"].values()
    assert seconds == pytest.approx(200e-9) and calls == pytest.approx(1)
    assert got["mosaic_s"] == pytest.approx(200e-9)
    names = dict(got["device_ops"])
    assert names["fusion.2"] == pytest.approx(150e-9)   # mean of 300 and 0
    assert names["while.1"] == pytest.approx(100e-9)    # self time only


def test_exposed_collective_time(trace):
    assert xplane.reduce(trace)["collective_exposed_s"] == pytest.approx(
        100e-9)


def test_gaps_are_named_by_what_the_host_was_doing(trace):
    got = xplane.reduce(trace)["idle_gaps"]
    # inside what both the devices and the host recorded (100..1300):
    # 600..1000 is the wait for the next batch; the task's span covers it
    # too, and the innermost event names it
    assert got == [["queue.get", pytest.approx(400e-9)]]


def test_no_device_plane_reads_as_nothing():
    assert xplane.reduce({"devices": {}, "host": []}) is None


def test_per_step_numbers_use_the_steps_own_range(trace):
    got = xplane.reduce(trace)
    # chip 0: two steps over 100..1300, busy 800 of 1200; chip 1: one
    # step over 100..600, busy 500 of 500
    assert got["steps"] == pytest.approx(1.5)
    assert got["step_busy_s"] == pytest.approx(650e-9)
    assert got["step_range_s"] == pytest.approx(850e-9)


def test_short_names_keep_the_op_and_its_shape():
    hlo = ('%checkpoint.23 = bf16[128,2048,128]{2,1,0:T(8,128)(2,1)} '
           'custom-call(bf16[128,2048,128]{2,1,0} %a), '
           'custom_call_target="tpu_custom_call"')
    assert xplane.short_name(hlo) == \
        "checkpoint.23 bf16[128,2048,128] custom-call"
    assert xplane.short_name("%fusion.4 = (f32[8]{0}, f32[8]{0}) fusion(") \
        == "fusion.4 f32[8]"
    assert xplane.short_name("while.1") == "while.1"


def test_a_gap_takes_the_innermost_covering_event_or_the_largest_overlap(
        trace):
    # covered by the task, the loss fetch and the runtime's wait: innermost
    assert xplane.name_gap(trace, 1050, 1300) == "PjRtFuture::Await"
    # one event alone: its name
    assert xplane.name_gap(trace, 1900, 1990) == "timing.task_process"
    # nothing covers half of 1300..2000: the largest overlap (the next
    # task's first 100 against the last 50 of the one before)
    assert xplane.name_gap(trace, 1300, 2000) == "timing.task_process"
    assert xplane.name_gap(trace, 2100, 2200) == "none"


def test_without_a_modules_line_there_is_no_step_count(trace):
    bare = dict(trace, modules={})
    got = xplane.reduce(bare)
    assert got["steps"] == 0 and got["busy_s"] == pytest.approx(650e-9)
