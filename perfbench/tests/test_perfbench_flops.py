"""The FLOP and byte counts and the peaks table, against hand counts."""
import pytest

from perfbench import flops


def test_policy_of_the_paper_has_4481_parameters():
    assert flops.policy_dim([3, 64, 64, 1]) == 3 * 64 + 64 + 64 * 64 + 64 + 64 + 1
    assert flops.mlp_macs([3, 64, 64, 1]) == 4352


def test_rollout_counts_at_a_small_shape():
    # N=2 agents: 4 episodes, 5 steps, MLP 3->4->1: 12 + 4 = 16 MACs
    assert flops.rollout_flops(2, [3, 4, 1], 5) == 2 * 4 * 5 * 16
    assert flops.rollout_bytes(2, 21) == 4 * 21 * 4


def test_mixing_counts_at_a_small_shape():
    # 3 agents on a path 0-1-2 with self-loops: nnz = 3 + 4 = 7
    assert flops.mixing_flops(7, 10) == 140
    assert flops.mixing_bytes(3, 10, 7, fully_connected=False) == 360 + 28
    assert flops.mixing_bytes(3, 10, 9, fully_connected=True) == 360


def test_iteration_amortizes_the_evaluation():
    per = flops.iteration_flops(2, [3, 4, 1], 5, nnz=4, eval_episodes=3,
                                eval_every=6)
    assert per == 2 * 4 * 5 * 16 + 2 * 4 * 21 + (2 * 3 * 5 * 16) / 6


def test_paper_scale_er1000_iteration():
    # the issue's count: 2*2N*200*4352 rollout + 2*nnz*D mixing
    per = flops.rollout_flops(1000, [3, 64, 64, 1], 200)
    assert per == 3_481_600_000


def test_roofline_names_its_bound():
    peak = {"bf16_flop_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_share(100.0, 1.0, 2.0, peak) == {
        "percent": 50.0, "bound": "flops"}
    assert flops.roofline_share(1.0, 40.0, 8.0, peak) == {
        "percent": 50.0, "bound": "bytes"}


def test_peaks_table_refuses_an_unknown_device():
    assert flops.peaks("TPU v5 lite")["bf16_flop_per_s"] == 197e12
    assert flops.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("cpu")
