"""The comparison that decides `correct`.

The timed call's first scan chunk, driven from the seed through the
window's own entry point, is compared with the plain reference
(`reference.py`); so are the program's parameters after its first
iteration, read by calling the same entry (``repro.core.netes.run``)
for one iteration on the first chunk's own input state.

Where the comparison starts, and what it leaves to ranks. NetES on the
pendulum amplifies rounding: an episode's return is not continuous in
the policy's parameters (a policy that swings the pendulum up one
period later loses hundreds), and the episodes most sensitive to the
last bit are the best ones, which end near the unstable upright
balance. The fitness shaping ranks the 2N returns and the broadcast
takes their argmax, so two correct computations follow visibly
different trajectories once one update has been applied, and the best
return itself swings (PERF.md §6 gives the readings). So the numbers
compared are those of iteration 0, which depends on the seed alone, and
the broadcast decisions of the whole chunk, which depend on the random
stream alone; the broadcast's choice is judged by rank, not by value:

* ``reward_mean_gap``: |program − reference| / |reference| of iteration
  0's mean episode return — the parameters, the noise and the reward
  evaluation of all 2N perturbed agents;
* ``update_var_gap``: the same for the population variance of iteration
  0's Eq. 3 update with weight decay — the fitness shaping, each agent's
  own part of the mixing over the graph (with the int8 codec where the
  cell has one) and the decay. The part every agent shares cancels in
  a variance; ``mix_gap`` sees it;
* where iteration 0 broadcasts, every agent then holds the broadcast
  row: ``select_row_gap`` is its relative distance to the nearest of the
  reference's 2N candidates (an unperturbed θ, or a row that is no
  candidate, reads far from 0), and ``select_rank`` the share of the
  reference's candidates that returned more than that one (0 for the
  best; the worst candidate reads about 1, the best one's antithetic
  partner tenths);
* where iteration 0 does not broadcast, ``mix_gap``:
  ‖θ̄'_program − θ̄'_reference‖ / ‖θ̄'_reference − θ̄_reference‖, with θ̄
  and θ̄' the population's mean parameters before and after: the mean
  Eq. 3 update with decay, whose shared term Σᵢ aᵢⱼR̃ᵢθ̃ᵢ is the dense
  product of a fully connected cell;
* ``broadcast_mismatch``: iterations of the first chunk on which the
  broadcast fired on one side only (exact);
* ``repeated_iterations``: iterations of the first chunk whose mean
  return equals the previous iteration's to the bit (exact: fresh noise
  and episodes every iteration). A state that does not advance replays
  its random stream and is caught by these two.
"""
from __future__ import annotations

from typing import Dict, Mapping, Optional, Sequence

import numpy as np

ITERS = 3           # iterations the calibration follows (PERF.md §6)


def _rel_gap(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float64)
                               - np.asarray(want, np.float64))
                        / np.abs(np.asarray(want, np.float64))))


def _select(row: np.ndarray, candidates: np.ndarray, returns: np.ndarray
            ) -> Dict[str, float]:
    cands = np.asarray(candidates, np.float32)
    dist = (np.linalg.norm(cands - np.asarray(row, np.float32), axis=1)
            / np.linalg.norm(cands, axis=1))
    j = int(np.argmin(dist))
    returns = np.asarray(returns, np.float64)
    return {"select_row_gap": float(dist[j]),
            "select_rank": float(np.mean(returns > returns[j]))}


def numbers(program: Mapping, ref: Mapping, flags: Sequence[float]
            ) -> Dict[str, Optional[float]]:
    """The compared numbers. ``program`` holds the first chunk's metric
    arrays, ``theta_mean`` (the mean parameters before and after
    iteration 0) and ``row`` (the first agent's parameters after it);
    ``ref`` is ``Reference.first``'s output; ``flags`` the reference's
    broadcast decisions over the chunk. A number that iteration 0 does
    not reach on this seed has no value."""
    got_flags = np.asarray(program["broadcast"], np.float64)[:len(flags)]
    means = np.asarray(program["reward_mean"])
    out = {
        "reward_mean_gap": _rel_gap(program["reward_mean"][0],
                                    ref["reward_mean"]),
        "update_var_gap": _rel_gap(program["update_var"][0],
                                   ref["update_var"]),
        "select_row_gap": None, "select_rank": None, "mix_gap": None,
        "broadcast_mismatch": float(np.sum(got_flags != np.asarray(flags))),
        "repeated_iterations": float(np.sum(means[1:] == means[:-1])),
    }
    if flags[0]:
        out.update(_select(program["row"], ref["candidates"],
                           ref["returns"]))
    else:
        before, after = ref["theta_mean"]
        out["mix_gap"] = float(
            np.linalg.norm(np.asarray(program["theta_mean"][1]) - after)
            / np.linalg.norm(after - before))
    return out


def later_iterations(program: Mapping[str, np.ndarray],
                     ref: Mapping[str, Sequence[float]]) -> Dict[str, float]:
    """The iteration-level gaps taken over every iteration the reference
    ran (the calibration's look at later iterations; not compared)."""
    t = min(len(ref["reward_mean"]), len(program["reward_mean"]))
    return {f"{k}_gap": _rel_gap(np.asarray(program[k])[:t], ref[k][:t])
            for k in ("reward_mean", "update_var")}


def judge(values: Mapping[str, Optional[float]],
          limits: Mapping[str, float]) -> Dict[str, Dict]:
    """Each compared number beside its limit. A number that is not finite
    fails; one with no value (nothing to compare on this seed) passes
    and says so."""
    out = {}
    for name, limit in limits.items():
        v = values[name]
        ok = v is None or bool(np.isfinite(v) and v <= limit)
        out[name] = {"value": v, "limit": float(limit), "ok": ok}
    return out
