"""Device time by named scope and by host span, and idle time by host
span (``perfbench/scopes.py``), on hand-made events and on a small
XSpace in the chip's layout with op metadata
(``tests/data/scoped_chip.textproto``), and the per-layer readers built
on them."""
import pathlib
import types

import pytest

from perfbench import harness
from perfbench import scopes as sc
from perfbench import trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"
US = 1e-6
NEW_METRICS = ("eval_ms", "eval_idle_ms", "drain_idle_ms")


def _fixture():
    from jax.profiler import ProfileData
    text = (DATA / "scoped_chip.textproto").read_text()
    red = tr.from_profile(ProfileData.from_text_proto(text))
    return sc.ScopedReduced(devices=red.devices, host=red.host,
                            scoped=sc.scoped_ops(sc.read_xspace(text=text)))


@pytest.mark.parametrize("path,scope", [
    ("jit(f)/while/body/jit(netes_step)/noise/add:", "noise"),
    ("jit(f)/reward/vmap(vmap())/while/body/closed_call/cos:", "reward"),
    ("jit(f)/broadcast/jit(_uniform)/shift_right_logical:", "broadcast"),
    ("jit(f)/mixing/channel/round:", "channel"),
    ("jit(f)/broadcast_in_dim:", sc.UNSCOPED),
    ("jit(scan)/while/body/closed_call/add:", sc.UNSCOPED),
    (None, None),
])
def test_scope_is_the_innermost_scope_component(path, scope):
    assert sc.scope_of(path) == scope


def test_scoped_ops_key_each_event_by_its_own_metadata():
    red = _fixture()
    chip0 = {(s / 1e3, e / 1e3): sc for sc, s, e in red.scoped[0]}
    # two programs' fusion.1: noise at 2-12 us, eval at 90-94 us
    assert chip0[(2, 12)] == "noise" and chip0[(90, 94)] == "eval"
    # a loop with no path takes the scope its body shares ...
    assert chip0[(12, 42)] == "reward"
    # ... and stays unscoped when its body spans several
    assert chip0[(0, 50)] == sc.UNSCOPED
    assert chip0[(70, 80)] == sc.UNSCOPED
    assert chip0[(52, 56)] == sc.UNSCOPED


def test_scope_self_time_is_mean_over_chips():
    red = _fixture()
    # noise: chip 0 10 us, chip 1 20 us
    assert red.scope_self_s(["noise"]) == pytest.approx(15 * US)
    # reward: chip 0 loop 10 + fusion 10 + slice 10, chip 1 20
    assert red.scope_self_s(["reward"]) == pytest.approx(25 * US)
    assert red.scope_self_s(["mixing"]) == pytest.approx(3 * US)
    assert red.scope_self_s(["eval"]) == pytest.approx(2 * US)
    # the scan loop's own 4, fusion.9 4, the eager loop 4 and its body 6
    assert red.scope_self_s([sc.UNSCOPED]) == pytest.approx(9 * US)
    assert red.scope_self_s(["noise", "mixing"]) == pytest.approx(18 * US)
    assert red.scope_self_s(["stats", "schedule"]) is None
    total = red.scope_self_s(list(sc.SCOPES) + [sc.UNSCOPED])
    assert total == pytest.approx(red.busy_s)


def test_idle_time_under_host_spans_adds_up_to_the_idle_time():
    red = _fixture()
    # chip 0 idle in eval: 60-70, 80-90, 94-150; chip 1: 60-150
    assert sc.idle_under(red, "repro/eval") == pytest.approx(83 * US)
    # chip 0: 50-52, 56-60, 150-160, 170-200; chip 1: 40-60, 150-160,
    # 170-200
    assert sc.idle_under(red, "repro/drain") == pytest.approx(53 * US)
    assert sc.idle_under(red, "repro/chunk") == pytest.approx(10 * US)
    assert sc.idle_under(red, "repro/checkpoint") is None
    idle = red.window_s - red.busy_s
    assert idle == pytest.approx((83 + 53 + 10) * US)


def test_existing_numbers_of_the_scoped_fixture():
    red = _fixture()
    assert red.window == (0, 200000)
    assert red.busy_s == pytest.approx((68 + 40) / 2 * US)
    ops = dict(red.top_ops())
    # the two programs' fusion.1 share a name in the breakdown
    assert ops["fusion.1"] == pytest.approx((10 + 4 + 20) / 2 * US)


def test_a_trace_without_op_metadata_names_no_scope():
    text = (DATA / "two_chips.textproto").read_text()
    scoped = sc.scoped_ops(sc.read_xspace(text=text))
    assert {sc for ops in scoped.values() for sc, _, _ in ops} == {
        sc.UNSCOPED}


def test_read_xspace_reads_the_serialized_form():
    space = sc.read_xspace(text=(DATA / "scoped_chip.textproto").read_text())
    again = sc.read_xspace(space.SerializeToString())
    assert sc.scoped_ops(again) == sc.scoped_ops(space)


# -- what the program's host phases dispatched --------------------------

def test_busy_begun_under_a_host_span_counts_by_the_outermost_op():
    red = _fixture()
    # chip 0: the eager loop 70-80 and the eval fusion.1 90-94 begin in
    # repro/eval (60-150); chip 1 runs nothing there
    assert sc.busy_begun_under(red, "repro/eval") == pytest.approx(7 * US)
    # the scan loop (0-50, body included) begins in repro/chunk (0-10)
    # on chip 0, fusion.1 (0-20) on chip 1; fusion.9 (52-56) and chip
    # 1's fusion.2 (20-40) begin in repro/drain (10-60)
    assert sc.busy_begun_under(red, "repro/chunk") == pytest.approx(35 * US)
    assert sc.busy_begun_under(red, "repro/drain") == pytest.approx(12 * US)
    assert sc.busy_begun_under(red, "repro/checkpoint") is None
    begun = sum(sc.busy_begun_under(red, s) for s in
                ("repro/chunk", "repro/drain", "repro/eval"))
    assert begun == pytest.approx(red.busy_s)


def test_load_scoped_reads_a_serialized_trace(tmp_path):
    space = sc.read_xspace(text=(DATA / "scoped_chip.textproto").read_text())
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(space.SerializeToString())
    red = sc.load_scoped(str(path))
    want = _fixture()
    assert red.scoped == want.scoped and red.busy_s == want.busy_s
    assert red.scope_self_s(["noise"]) == pytest.approx(15 * US)


# -- the readers ----------------------------------------------------------

def _ctx(red, traced=(1, 3), every=12):
    win = types.SimpleNamespace(traced_stamps=traced, iters_per_chunk=every)
    return types.SimpleNamespace(trace=red, window=win)


def _read(name, ctx):
    return harness.load_metric(name).read(ctx)


def _hand_made(ops, host):
    """A plain Reduced, as the harness loads it: a 0-1000 ns window, ops
    (name, start, end) on one chip and host spans (name, start, end)."""
    return tr.Reduced(devices={0: ops},
                      host=[(tr.WINDOW, 0, 1000)] + host)


def test_eval_reader_counts_the_work_begun_under_the_eval_span():
    host = [("repro/chunk", 0, 50), ("repro/drain", 50, 300),
            ("repro/eval", 400, 600), ("repro/drain", 600, 650),
            ("repro/chunk", 700, 750), ("repro/drain", 750, 1000)]
    # the evaluation's loop 420-470 (its body inside it) and fusion.1
    # 590-610 begin inside repro/eval; the scans (60-200, 690-900) and
    # the copy in the score's drain do not
    red = _hand_made([("while.1", 60, 200), ("fusion.1", 70, 100),
                      ("while.5", 420, 470), ("fusion.3", 430, 440),
                      ("fusion.1", 590, 610), ("copy", 610, 620),
                      ("while.1", 690, 900)], host)
    # 70 ns over 2 evaluation points
    assert _read("eval_ms", _ctx(red)) == pytest.approx(35e-6)


def test_idle_readers_divide_by_the_traced_chunks():
    host = [("repro/chunk", 0, 10), ("repro/drain", 100, 200),
            ("repro/eval", 300, 700), ("repro/drain", 700, 800)]
    red = _hand_made([("fusion.2", 0, 150), ("fusion.1", 650, 750)], host)
    ctx = _ctx(red)
    # eval: idle 300-650; drain: 150-200 and 750-800
    assert _read("eval_idle_ms", ctx) == pytest.approx(175e-6)
    assert _read("drain_idle_ms", ctx) == pytest.approx(50e-6)


def test_readers_give_nothing_for_a_program_without_spans():
    """The benchmark's readers over an older program: no ``repro/``
    span, so no number and no error."""
    red = _hand_made([("fusion.1", 0, 100)], [("PjitFunction", 0, 50)])
    for name in NEW_METRICS:
        assert _read(name, _ctx(red)) is None, name


def test_readers_give_nothing_without_a_traced_window():
    red = _hand_made([("fusion.1", 0, 10)], [("repro/eval", 0, 5)])
    for name in NEW_METRICS:
        assert _read(name, _ctx(red, traced=None)) is None, name
        assert _read(name, types.SimpleNamespace(
            trace=None, window=types.SimpleNamespace(
                traced_stamps=(1, 3), iters_per_chunk=12))) is None
