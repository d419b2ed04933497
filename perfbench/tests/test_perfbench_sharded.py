"""`correct` on the four-chip cell (`er16384-q8-x4.pendulum`), driven
through the harness on 4 forced host devices at 64 agents (ER p = 0.1):
the sharded engine over the int8 channel passes the comparison with the
plain reference, and fails it with the state returned unchanged, with
the worst row broadcast, with the exchange between chips left out, and
with the reference in bfloat16 in the program's place. One subprocess
runs all five, since the device count is fixed when JAX starts."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "er16384-q8-x4.pendulum"

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import copy
import json
import sys
import time

import jax
import jax.numpy as jnp

from perfbench import harness
from perfbench import reference as ref_mod
from repro.distributed import fleet_shard

SEED = 2 ** 31 + 4243
cell = copy.deepcopy(harness.load_cell(sys.argv[1]))
cell.config["n_agents"] = 64
cell.config["topology"]["p"] = 0.1
assert cell.chips == 4 and cell.config["shards"] == 4


def run():
    jax.clear_caches()
    fleet_shard.clear_engine_cache()
    r = harness.run(cell, SEED, 0.5, False, time.perf_counter(),
                    skip_chip_check=True)
    return {"correct": r["correct"], "checks": r["checks"],
            "platform": r["device"]["platform"],
            "devices": jax.device_count()}


out = {"sound": run()}

step = fleet_shard.ShardedNetES._step


def frozen(self, ops, operands, carry, *args):
    # the state comes back as it went in; the metrics are the step's
    return carry, step(self, ops, operands, carry, *args)[1]


fleet_shard.ShardedNetES._step = frozen
out["frozen"] = run()
fleet_shard.ShardedNetES._step = step


class ArgmaxIsArgmin:
    def __getattr__(self, name):
        return jnp.argmin if name == "argmax" else getattr(jnp, name)


fleet_shard.jnp = ArgmaxIsArgmin()
out["wrong_row"] = run()
fleet_shard.jnp = jnp


class Isolated:
    # the mixing's collectives on a chip that hears no other: the halo
    # rounds bring zeros, the payload gather holds only its own rows
    def __init__(self, ops):
        self.ops = ops

    def __getattr__(self, name):
        return getattr(self.ops, name)

    def ppermute_recv(self, x, r):
        return jnp.zeros_like(x)

    def all_gather(self, x):
        full = self.ops.all_gather(x)
        lo = self.ops.axis_index() * x.shape[0]
        rows = jnp.arange(full.shape[0])
        own = (rows >= lo) & (rows < lo + x.shape[0])
        return jnp.where(own.reshape((-1,) + (1,) * (full.ndim - 1)), full,
                         jnp.zeros_like(full))


mix = fleet_shard.ShardedNetES._mix
fleet_shard.ShardedNetES._mix = (
    lambda self, ops, *args: mix(self, Isolated(ops), *args))
out["no_exchange"] = run()
out["no_exchange"]["modes"] = sorted(
    e.plan.mode for e in fleet_shard._ENGINE_CACHE.values())
fleet_shard.ShardedNetES._mix = mix


class Followed:
    # the reference's first chunk in the program's place
    def __init__(self, ref, seed, chunk):
        got = ref.first(seed)
        self.metrics = {"reward_mean": [got["reward_mean"]],
                        "update_var": [got["update_var"]],
                        "broadcast": ref.broadcast_flags(seed, chunk)}
        self._after = {"theta_mean": got["theta_mean"], "row": got["row"]}

    def follow(self):
        return self._after


setup = harness.reference_setup(cell)
f32 = ref_mod.Reference(setup)
control = ref_mod.Reference(setup, dtype=jnp.bfloat16, edges=f32.edges)
judged, ok = harness.check(
    cell, SEED, Followed(control, SEED, cell.traffic["eval_every"]), f32)
out["control_bf16"] = {"correct": ok, "checks": judged}
print("SHARDED_CELL " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def readings():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), str(ROOT / "src")])
    res = subprocess.run([sys.executable, "-c", _SCRIPT, CELL], cwd=ROOT,
                         capture_output=True, text=True, timeout=900,
                         env=env)
    lines = [ln for ln in res.stdout.splitlines()
             if ln.startswith("SHARDED_CELL ")]
    assert lines, (res.stdout[-2000:], res.stderr[-4000:])
    return json.loads(lines[-1][len("SHARDED_CELL "):])


def test_sound_sharded_program_is_correct(readings):
    sound = readings["sound"]
    assert sound["correct"], sound["checks"]
    assert sound["platform"] == "cpu" and sound["devices"] == 4


def test_state_returned_unchanged_is_caught(readings):
    assert not readings["frozen"]["correct"]


def test_wrong_broadcast_row_is_caught(readings):
    wrong = readings["wrong_row"]
    assert not wrong["correct"]
    assert wrong["checks"]["select_rank"]["value"] > 0.5


def test_exchange_between_chips_left_out_is_caught(readings):
    """The mixing's halo rounds and payload gather bring a chip nothing
    of the others' rows: each chip mixes over its own quarter of the
    graph, and the update the harness compares departs from the
    reference's."""
    cut = readings["no_exchange"]
    assert cut["modes"] == ["halo"]
    assert not cut["correct"], cut["checks"]


def test_bfloat16_control_is_not_correct(readings):
    assert not readings["control_bf16"]["correct"]
