"""The reduction from trace events to the device numbers, on hand-made
events and on a small XSpace in the layout the profiler writes for TPU
devices (``tests/data/two_chips.textproto``)."""
import pathlib

import pytest

from perfbench import trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _reduced(devices, host):
    return tr.Reduced(devices=devices, host=host)


def test_union_and_subtract():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 6)]) == [(0, 2), (3, 5),
                                                        (6, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.length(tr.clip([(0, 5), (8, 20)], 2, 10)) == 5


def test_self_times_exclude_nested_operations():
    evs = [("while", 0, 10), ("fusion", 1, 3), ("dot", 4, 8),
           ("copy", 12, 13)]
    assert tr.self_times(evs) == {"while": 4, "fusion": 2, "dot": 4,
                                  "copy": 1}


def test_idle_share_is_one_minus_busy_over_window_averaged():
    win = [("perfbench_window", 0, 100)]
    devs = {0: [("a", 0, 50), ("b", 40, 60)],       # busy 60
            1: [("a", 10, 30), ("c", 90, 120)]}     # busy 20 + 10
    red = _reduced(devs, win)
    assert red.busy_s == pytest.approx(45e-9)
    assert red.window_s == pytest.approx(100e-9)
    assert red.idle_share() == pytest.approx(0.55)


def test_exposed_collective_time_is_what_no_compute_covers():
    win = [("perfbench_window", 0, 100)]
    devs = {0: [("all-gather.1", 0, 30), ("fusion", 10, 20),
                ("collective-permute-done", 50, 60)],
            1: [("all-reduce", 0, 10), ("fusion", 0, 10)]}
    red = _reduced(devs, win)
    # device 0: 30 - 10 covered + 10 = 30; device 1: 0
    assert red.collective_exposed_s() == pytest.approx(15e-9)
    assert _reduced({0: [("fusion", 0, 5)]}, win).collective_exposed_s() \
        is None


def test_idle_gaps_are_named_by_the_covering_host_event():
    host = [("perfbench_window", 0, 100), ("dispatch", 28, 45),
            ("drain", 60, 99), ("inner", 61, 70)]
    devs = {0: [("x", 0, 30), ("y", 40, 60)]}
    gaps = _reduced(devs, host).idle_gaps()
    assert gaps[0] == ["drain", pytest.approx(40e-9)]
    assert gaps[1] == ["dispatch", pytest.approx(10e-9)]


def test_profile_in_the_tpu_layout():
    """The whole path from an XSpace: planes, lines, events, nesting,
    window, idle share, exposed collectives and the breakdown."""
    from jax.profiler import ProfileData
    text = (DATA / "two_chips.textproto").read_text()
    red = tr.from_profile(ProfileData.from_text_proto(text))
    assert sorted(red.devices) == [0, 1]
    assert red.window == (0, 100000)
    # chip 0 busy 40 + 40 + 5 us, chip 1 busy 20 + 30 us
    assert red.busy_s == pytest.approx((85e-6 + 50e-6) / 2)
    assert red.idle_share() == pytest.approx(1 - 67.5e-6 / 100e-6)
    # chip 0: all-gather 88-95, loop until 90: 5 us; chip 1: 30-40: 10 us
    assert red.collective_exposed_s() == pytest.approx(7.5e-6)
    ops = dict(red.top_ops())
    assert ops["fusion.1"] == pytest.approx((40e-6 + 40e-6) / 2)
    # the loop's own time excludes the dot inside it and the 2 us of the
    # all-gather that starts before it ends
    assert ops["while.2"] == pytest.approx(28e-6 / 2)
    assert ops["dot.3"] == pytest.approx(10e-6 / 2)
    gaps = red.idle_gaps()
    assert gaps[0] == ["PjitFunction(_run_jit)", pytest.approx(10e-6)]
    assert gaps[1] == ["device_get", pytest.approx(5e-6)]
