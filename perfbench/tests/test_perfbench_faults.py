"""`correct` comes out false when the timed path is broken underneath.

Each test drives a whole run of the harness on the CPU at a small size
(the chip check skipped), with one fault planted in the program's timed
path, and sees the comparison with the reference fail; the unbroken
program passes, and so must not the control (the reference itself in
bfloat16 put in the program's place). Each cell runs with its own
limits, at 24 agents.
"""
import copy
import time

import jax
import jax.numpy as jnp
import pytest

from perfbench import harness
from perfbench import reference as ref_mod

SEED = 2 ** 31 + 4243


@pytest.fixture(scope="module", autouse=True)
def _restore_cache_config():
    """A run turns the persistent compilation cache on for its process;
    give the worker back the settings it had for the next test file."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    from jax.experimental.compilation_cache import compilation_cache
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module", params=["er1000.pendulum", "fc3000.pendulum"])
def cell(request):
    """Each cell at 24 agents, with its own limits."""
    c = copy.deepcopy(harness.load_cell(request.param))
    c.config["n_agents"] = 24
    if c.config["topology"]["family"] == "erdos_renyi":
        c.config["topology"]["p"] = 0.3
    return c


def _run(cell):
    jax.clear_caches()
    return harness.run(cell, SEED, 0.5, False, time.perf_counter(),
                       skip_chip_check=True)


def test_sound_program_is_correct(cell):
    result = _run(cell)
    assert result["correct"], result["checks"]
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "checks"


def test_state_returned_unchanged_is_caught(cell, monkeypatch):
    from repro.core import netes
    step = netes.netes_step

    def frozen(state, *args, **kwargs):
        out = step(state, *args, **kwargs)
        return (state,) + tuple(out[1:])

    monkeypatch.setattr(netes, "netes_step", frozen)
    assert not _run(cell)["correct"]


def test_half_batch_left_out_is_caught(cell, monkeypatch):
    from repro.train import loop
    resolve = loop.resolve_task

    def half(task):
        reward_fn, *rest = resolve(task)

        def first_half(params, key):
            r = reward_fn(params, key)
            m = r.shape[0] // 2
            return jnp.concatenate([r[:m], jnp.full((r.shape[0] - m,),
                                                    r[:m].mean())])
        return (first_half, *rest)

    monkeypatch.setattr(loop, "resolve_task", half)
    assert not _run(cell)["correct"]


class _NetesWithArgmin:
    """``jax.numpy`` as the program's NetES module sees it, with the
    broadcast's argmax turned into an argmin."""

    def __getattr__(self, name):
        return jnp.argmin if name == "argmax" else getattr(jnp, name)


def test_wrong_broadcast_row_is_caught(cell, monkeypatch):
    from repro.core import netes
    monkeypatch.setattr(netes, "jnp", _NetesWithArgmin())
    result = _run(cell)
    assert not result["correct"]
    assert result["checks"]["select_rank"]["value"] > 0.5


def test_eq3_mixing_left_out_is_caught(cell, monkeypatch):
    from repro.core import netes

    def no_mixing(adj, thetas, *args, **kwargs):
        return jnp.zeros_like(thetas)

    monkeypatch.setattr(netes, "mixing_update", no_mixing)
    assert not _run(cell)["correct"]


class _Followed:
    """A first chunk that the reference computed, in the program's
    place."""

    def __init__(self, got, chunk):
        self.metrics = {"reward_mean": [got["reward_mean"]],
                        "update_var": [got["update_var"]],
                        "broadcast": [got["broadcast"]] * chunk}
        self._after = {"theta_mean": got["theta_mean"], "row": got["row"]}

    def follow(self):
        return self._after


def _in_programs_place(cell, reference):
    got = reference.first(SEED)
    first = _Followed(got, cell.traffic["eval_every"])
    first.metrics["broadcast"] = reference.broadcast_flags(
        SEED, cell.traffic["eval_every"])
    return first


def test_bfloat16_control_is_not_correct(cell):
    """The control: the reference in bfloat16 as the program."""
    setup = harness.reference_setup(cell)
    f32 = ref_mod.Reference(setup)
    control = ref_mod.Reference(setup, dtype=jnp.bfloat16, edges=f32.edges)
    _, ok = harness.check(cell, SEED, _in_programs_place(cell, control), f32)
    assert not ok


def test_sound_reference_in_the_programs_place_is_correct(cell):
    """The same path with the float32 reference as the program: every
    number reads 0."""
    f32 = ref_mod.Reference(harness.reference_setup(cell))
    judged, ok = harness.check(cell, SEED, _in_programs_place(cell, f32),
                               f32)
    assert ok
    assert all(v["value"] in (0.0, None) for v in judged.values()), judged


def test_run_off_a_tpu_is_refused(capsys):
    code = harness.main(["--workload", "er1000.pendulum", "--seed", "1",
                         "--seconds", "1"])
    out = capsys.readouterr()
    assert code == harness.NO_CHIP_EXIT
    assert out.out == ""
