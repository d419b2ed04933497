"""The collective readers (``collective_ms``, ``collective_exposed_ms``)
on hand-made traces: collectives that other work overlaps and that it
does not, on two chips, in a window of two traced chunks, and the
chip's layout, where ops carry their HLO text (operands included), the
scan's loop spans its body, and a fusion reads a collective's result."""
import types

import pytest

from perfbench import collective_ops, harness
from perfbench import trace_reduce as tr

EVERY = 12
ITERS = 2 * EVERY            # traced_stamps (1, 3): two chunks


def _ctx(devices, traced=(1, 3)):
    red = tr.Reduced(devices=devices,
                     host=[("perfbench_window", 0, 1_000_000)])
    win = types.SimpleNamespace(traced_stamps=traced,
                                iters_per_chunk=EVERY)
    return types.SimpleNamespace(trace=red, window=win)


def _read(name, ctx):
    return harness.load_metric(name).read(ctx)


# chip 0: a halo permute half hidden under a fusion, then an all-gather
# that nothing covers; chip 1: an all-reduce wholly under a fusion and
# two overlapping permute events (counted once)
DEVICES = {
    0: [("collective-permute-start.1", 100_000, 300_000),
        ("fusion.7", 200_000, 400_000),
        ("all-gather.3", 500_000, 600_000)],
    1: [("all-reduce.2", 100_000, 200_000),
        ("fusion.7", 0, 300_000),
        ("collective-permute-start.1", 400_000, 500_000),
        ("collective-permute-done.1", 450_000, 550_000)],
}


def test_collective_time_is_the_union_of_collectives_per_iteration():
    # chip 0: 200 + 100 us; chip 1: 100 + 150 us; mean 275 us
    assert _read("collective_ms", _ctx(DEVICES)) == pytest.approx(
        0.275 / ITERS)


def test_exposed_time_leaves_out_what_other_work_covers():
    # chip 0: 100 us of the permute + the 100 us all-gather; chip 1: the
    # 150 us of permutes; mean 175 us
    assert _read("collective_exposed_ms", _ctx(DEVICES)) == pytest.approx(
        0.175 / ITERS)


def test_collectives_under_compute_are_hidden_not_absent():
    hidden = {0: [("fusion.1", 0, 500_000),
                  ("all-gather.1", 100_000, 200_000)]}
    assert _read("collective_ms", _ctx(hidden)) == pytest.approx(
        0.1 / ITERS)
    assert _read("collective_exposed_ms", _ctx(hidden)) == 0.0


@pytest.mark.parametrize("name", ["collective_ms", "collective_exposed_ms"])
def test_nothing_to_read_without_collectives_or_a_traced_window(name):
    assert _read(name, _ctx({0: [("fusion.1", 0, 500_000)]})) is None
    assert _read(name, _ctx(DEVICES, traced=None)) is None
    untraced = types.SimpleNamespace(
        trace=None, window=types.SimpleNamespace(traced_stamps=(1, 3),
                                                 iters_per_chunk=EVERY))
    assert _read(name, untraced) is None


def test_a_window_edge_clips_the_collectives():
    edge = {0: [("all-gather.1", 900_000, 1_200_000)]}
    assert _read("collective_ms", _ctx(edge)) == pytest.approx(0.1 / ITERS)
    assert _read("collective_exposed_ms", _ctx(edge)) == pytest.approx(
        0.1 / ITERS)


# the chip's layout: each op named by its HLO text, the chunk's scan a
# while op spanning its body, a permute's done waiting alone, an
# all-reduce under the fusion that runs beside it
LOOP = ("%while.5 = (s32[], f32[1024,4481]{1,0:T(8,128)}) while((s32[], "
        "f32[1024,4481]{1,0:T(8,128)}) %tuple.2), condition=%cond.1, "
        "body=%body.1")
DONE = ("%collective-permute-done.1 = s8[3564,4481]{1,0:T(8,128)(4,1)} "
        "collective-permute-done(s8[3564,4481]{1,0:T(8,128)(4,1)} "
        "%collective-permute-start.1)")
REDUCE = ("%all-reduce.2 = f32[4481]{0:T(1024)} all-reduce(f32[4481]"
          "{0:T(1024)} %fusion.9), replica_groups={{0,1,2,3}}, "
          "to_apply=%add.1")
FUSION = ("%fusion.7 = f32[1024,4481]{1,0:T(8,128)} fusion(f32[1024,4481]"
          "{1,0:T(8,128)} %param.1), kind=kLoop, calls=%fused_computation.7")
CHIP = {0: [(LOOP, 0, 1_000_000), (FUSION, 0, 400_000),
            (DONE, 400_000, 500_000), (FUSION, 500_000, 900_000),
            (REDUCE, 600_000, 700_000)]}


def test_the_scans_loop_hides_no_collective():
    ctx = _ctx(CHIP)
    assert _read("collective_ms", ctx) == pytest.approx(0.2 / ITERS)
    # the permute's 100 us are exposed, the all-reduce's are hidden;
    # counting the loop as work would read no exposure at all
    assert _read("collective_exposed_ms", ctx) == pytest.approx(
        0.1 / ITERS)
    assert ctx.trace.collective_exposed_s() == 0.0


@pytest.mark.parametrize("name,holds", [
    (LOOP, True), ("while.2", True), ("conditional.1", True),
    ("%call.4 = f32[] call(f32[] %p), to_apply=%f", True),
    (FUSION, False), ("fusion.1", False),
    ("%custom-call.3 = f32[8]{0} custom-call(f32[8]{0} %p)", False)])
def test_ops_that_hold_others_are_told_by_their_opcode(name, holds):
    assert collective_ops.holds_others(name) is holds


# the halo's consumer, as a v5e names it: a fusion whose operands are the
# permutes' results, and a shaping op reading an all-gather
HALO_READER = ("%convert_multiply_fusion.2 = f32[935,4481]{1,0:T(8,128)S(1)}"
               " fusion(s8[223,4481]{1,0:T(8,128)(4,1)S(1)} "
               "%collective-permute-done.1, s8[229,4481]{1,0:T(8,128)(4,1)"
               "S(1)} %collective-permute-done.2), kind=kLoop, "
               "calls=%fused_computation.103")
GATHER_READER = ("%bitcast.327 = f32[1024]{0:T(1024)S(1)} bitcast(f32[1024,1]"
                 "{0,1:T(1,128)S(1)} %all-gather.14)")
START = ("%collective-permute-start.3 = (s8[223,4481]{1,0:T(8,128)(4,1)S(1)},"
         " s8[223,4481]{1,0:T(8,128)(4,1)S(1)}, u32[]{:S(2)}, u32[]{:S(2)})"
         " collective-permute-start(s8[223,4481]{1,0:T(8,128)(4,1)S(1)} "
         "%broadcast_select_fusion.13), channel_id=1, "
         "source_target_pairs={{0,1},{1,2},{2,3},{3,0}}")
PSUM = ("%psum.56 = f32[4481]{0:T(1024)S(1)} all-reduce(f32[4481]{0:T(1024)"
        "S(1)} %fusion.156), channel_id=1, replica_groups={{0,1,2,3}}, "
        "to_apply=%region_12.17")


@pytest.mark.parametrize("name,collective", [
    (DONE, True), (START, True), (REDUCE, True), (PSUM, True),
    ("all-gather.3", True), ("collective-permute-done.1", True),
    ("recv-done.2", True), ("send.1", True),
    (HALO_READER, False), (GATHER_READER, False), (FUSION, False),
    (LOOP, False), ("fusion.7", False), ("sendrecv_fusion.2", False)])
def test_collectives_are_told_by_their_opcode_alone(name, collective):
    assert collective_ops.is_collective(name) is collective


def test_a_fusion_that_reads_the_halo_is_work():
    # the permute's done waits 100 us alone, then the fusion that
    # dequantizes the halo runs 300 us, its text naming the done
    chip = {0: [(LOOP, 0, 1_000_000), (DONE, 100_000, 200_000),
                (HALO_READER, 200_000, 500_000),
                (GATHER_READER, 600_000, 700_000)]}
    ctx = _ctx(chip)
    assert _read("collective_ms", ctx) == pytest.approx(0.1 / ITERS)
    assert _read("collective_exposed_ms", ctx) == pytest.approx(0.1 / ITERS)
    # a halo reader under the done hides it
    chip[0].append((HALO_READER, 150_000, 200_000))
    assert _read("collective_exposed_ms", _ctx(chip)) == pytest.approx(
        0.05 / ITERS)
