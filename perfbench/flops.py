"""Operations and bytes that one NetES iteration needs, from its shapes.

These count the algorithm's work (paper Algorithm 1 and Eq. 3), the same
whatever representation, kernel or precision implements it. A multiply-add
counts as 2 operations; a float32 element is 4 bytes.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, Sequence

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind``. A kind that is
    not in the table is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" (known: {sorted(table)})")
    return table[device_kind]


def mlp_macs(sizes: Sequence[int]) -> int:
    """Multiply-adds of one forward pass of the policy MLP."""
    return sum(a * b for a, b in zip(sizes[:-1], sizes[1:], strict=True))


def policy_dim(sizes: Sequence[int]) -> int:
    """Parameters of the MLP: weights and biases."""
    return sum(a * b + b for a, b in zip(sizes[:-1], sizes[1:], strict=True))


def rollout_flops(n: int, sizes: Sequence[int], steps: int) -> int:
    """Reward evaluation of one iteration: 2N antithetic episodes of
    ``steps`` policy forward passes (the task's own arithmetic is a few
    scalar operations a step and is not counted)."""
    return 2 * (2 * n) * steps * mlp_macs(sizes)


def rollout_bytes(n: int, dim: int) -> int:
    """The least reward evaluation must move: each of the 2N perturbed
    parameter vectors read once."""
    return 2 * n * dim * 4


def mixing_flops(nnz: int, dim: int) -> int:
    """Eq. 3's neighbor contraction: one multiply-add per edge and
    parameter (the self-correction and decay terms are O(N·D))."""
    return 2 * nnz * dim


def mixing_bytes(n: int, dim: int, nnz: int, fully_connected: bool) -> int:
    """Read θ and the perturbed payload, write θ' (3·N·D floats), plus
    one int32 source index per edge; a fully connected graph needs no
    index."""
    return 3 * n * dim * 4 + (0 if fully_connected else 4 * nnz)


def eval_flops(episodes: int, sizes: Sequence[int], steps: int) -> int:
    """One noise-free evaluation of the best parameters."""
    return 2 * episodes * steps * mlp_macs(sizes)


def iteration_flops(n: int, sizes: Sequence[int], steps: int, nnz: int,
                    eval_episodes: int, eval_every: int) -> float:
    """The whole step: rollout, mixing, and the evaluation amortized over
    the iterations between evaluation points."""
    dim = policy_dim(sizes)
    return (rollout_flops(n, sizes, steps) + mixing_flops(nnz, dim)
            + eval_flops(eval_episodes, sizes, steps) / eval_every)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: Dict[str, float]) -> Dict[str, float]:
    """Least time at the chip's peaks over the measured time, in %, and
    which of the two bounds it."""
    t_flops = flops / peak["bf16_flop_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return {"percent": 100.0 * max(t_flops, t_bytes) / seconds,
            "bound": bound}
