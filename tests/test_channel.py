"""Channel algebra + end-to-end threading (DESIGN.md §11).

Property tests (via the hypothesis shim) for the codec algebra, exact
lossless/dropout(0) parity on every physical representation (static AND
scheduled), event-trigger semantics, realized-traffic counters, the
distributed step builders, bit-for-bit channel-state resume through
``checkpoint/io`` (mirroring the schedule resume test), and the
topology × channel training grid: one scan per cell, realized bytes
equal to the live-edge count, fused ≡ unfused wire paths, and sparse ER
moving a fraction of fully-connected's bytes.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from _train_rerun import assert_one_scan_per_config, rerun_after_warmup

from repro.comm import channel as cc
from repro.comm.channel import ChannelSpec
from repro.core import netes, topology, topology_repr
from repro.core.netes import NetESConfig
from repro.core.topology import TopologySpec
from repro.envs import resolve_task
from repro.train.loop import TrainConfig, build_topology, train_rl_netes

N = 12
DIM = 6
CFG = NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.5)


def _reward(params, key):
    return -jnp.sum(params ** 2, axis=-1)


def _topo(rep: str, n: int = N):
    fam = "circulant_erdos_renyi" if rep == "circulant" else "erdos_renyi"
    adj = np.asarray(getattr(topology, fam)(n, p=0.4, seed=0))
    return topology_repr.from_dense(adj, rep)


# ---------------------------------------------------------------------------
# spec parsing / validation
# ---------------------------------------------------------------------------

def test_parse_pipeline_roundtrip():
    spec = ChannelSpec.parse(
        "event_triggered(threshold=0.01)|quantize(bits=4)|"
        "dropout(p=0.1,seed=3)")
    kinds = [s.kind for s in spec.stages]
    assert kinds == ["event_triggered", "quantize", "dropout"]
    assert spec.stages[1].bits == 4
    assert spec.stages[2].p == pytest.approx(0.1)
    assert spec.stages[2].seed == 3
    assert not spec.lossless
    assert ChannelSpec.parse("lossless").lossless
    assert spec.label() == "evt0.01|q4|drop0.1"


def test_parse_rejects_bad_stages():
    with pytest.raises(ValueError):
        ChannelSpec.parse("quantize(bits=3)")
    with pytest.raises(ValueError):
        ChannelSpec.parse("warp(x=1)")
    with pytest.raises(ValueError):
        ChannelSpec.parse("dropout(p=1.5)")
    with pytest.raises(ValueError):
        ChannelSpec.parse("topk(frac=0)")
    with pytest.raises(ValueError):
        ChannelSpec.parse("dropout(p=0.1)|dropout(p=0.2)")
    with pytest.raises(ValueError):
        ChannelSpec.parse("quantize(0.5)")


def test_lossless_stage_collapses():
    assert ChannelSpec.parse("lossless|quantize(bits=8)").stages == \
        ChannelSpec.parse("quantize(bits=8)").stages


# ---------------------------------------------------------------------------
# codec algebra (property tests)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(bits=st.sampled_from([8, 4]), seed=st.integers(0, 50))
def test_quantize_error_bound(bits, seed):
    """Absmax uniform quantization: per-entry error ≤ half a step."""
    x = np.random.default_rng(seed).normal(size=(5, 32)).astype(np.float32)
    ch = cc.compile_channel(f"quantize(bits={bits})", 5)
    y = np.asarray(ch.codec(jnp.asarray(x), batched=True))
    step = np.abs(x).max(axis=1, keepdims=True) / (2 ** (bits - 1) - 1)
    assert (np.abs(x - y) <= step / 2 + 1e-6).all()


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 50))
def test_quantize_compose_tightens_monotonically(seed):
    """Composing a coarser quantizer after a finer one can only lose
    information: err(q4∘q8) ≥ err(q8), err(q1∘q4) ≥ err(q4), and the
    single-stage errors themselves are monotone in bits."""
    x = jnp.asarray(np.random.default_rng(seed).normal(
        size=(4, 64)).astype(np.float32))

    def err(y):
        return float(jnp.abs(x - y).sum())

    q = {b: cc.compile_channel(f"quantize(bits={b})", 4) for b in (8, 4, 1)}
    e8 = err(q[8].codec(x, batched=True))
    e4 = err(q[4].codec(x, batched=True))
    e1 = err(q[1].codec(x, batched=True))
    assert e8 <= e4 <= e1
    e48 = err(q[4].codec(q[8].codec(x, batched=True), batched=True))
    e14 = err(q[1].codec(q[4].codec(x, batched=True), batched=True))
    assert e48 >= e8 - 1e-5
    assert e14 >= e4 - 1e-5
    # pipeline form composes the same stages
    pipe = cc.compile_channel("quantize(bits=8)|quantize(bits=4)", 4)
    assert err(pipe.codec(x, batched=True)) == pytest.approx(e48, rel=1e-6)


@settings(max_examples=10, deadline=None)
@given(frac=st.sampled_from([0.1, 0.25, 0.5]), seed=st.integers(0, 50))
def test_topk_keeps_largest(frac, seed):
    x = np.random.default_rng(seed).normal(size=(3, 40)).astype(np.float32)
    ch = cc.compile_channel(f"topk(frac={frac})", 3)
    y = np.asarray(ch.codec(jnp.asarray(x), batched=True))
    k = int(np.ceil(frac * 40))
    for r in range(3):
        kept = np.nonzero(y[r])[0]
        assert len(kept) <= k
        thresh = np.sort(np.abs(x[r]))[-k]
        assert (np.abs(x[r][kept]) >= thresh - 1e-6).all()
        np.testing.assert_array_equal(y[r][kept], x[r][kept])


@pytest.mark.parametrize("rep", ["dense", "sparse", "circulant"])
def test_lossless_is_exact_identity_all_representations(rep):
    """netes.run with a lossless channel ≡ the channel-free path,
    bit for bit, on every physical representation."""
    topo = _topo(rep)
    s0 = netes.init_state(jax.random.PRNGKey(0), N, DIM)
    s_ref, _ = netes.run(s0, topo, _reward, CFG, num_iters=6)
    ch = cc.compile_channel("lossless", N)
    s_ch, cs, m = netes.run(s0, topo, _reward, CFG, num_iters=6,
                            channel=ch, chan_state=ch.init(s0.thetas))
    assert np.array_equal(np.asarray(s_ref.thetas), np.asarray(s_ch.thetas))
    assert np.array_equal(np.asarray(s_ref.best_theta),
                          np.asarray(s_ch.best_theta))
    # realized messages = live non-self edges (+ broadcast fan-out)
    assert float(cs.msgs) > 0


@pytest.mark.parametrize("rep", ["dense", "sparse", "circulant"])
def test_dropout_p0_is_lossless_bit_for_bit(rep):
    topo = _topo(rep)
    s0 = netes.init_state(jax.random.PRNGKey(0), N, DIM)
    s_ref, _ = netes.run(s0, topo, _reward, CFG, num_iters=6)
    ch = cc.compile_channel("dropout(p=0.0,seed=9)", N)
    s_ch, _, _ = netes.run(s0, topo, _reward, CFG, num_iters=6,
                           channel=ch, chan_state=ch.init(s0.thetas))
    assert np.array_equal(np.asarray(s_ref.thetas), np.asarray(s_ch.thetas))


def test_scheduled_lossless_parity():
    """Lossless channel threaded through a SCHEDULED run ≡ the
    channel-free scheduled run (the carry gains the channel state but
    the math is untouched)."""
    tc = TrainConfig(
        n_agents=16, iters=12,
        topology=TopologySpec(family="erdos_renyi", n_agents=16, p=0.2,
                              seed=1),
        representation="sparse", schedule="resample_er(period=4)",
        seed=0, eval_every=4, eval_episodes=2,
        netes=NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.5))
    h_ref = train_rl_netes("landscape:sphere", tc)
    h_ch = train_rl_netes("landscape:sphere",
                          dataclasses.replace(tc, channel="lossless"))
    assert h_ref["eval"] == h_ch["eval"]


def test_dense_sparse_parity_under_dropout():
    """Dropout draws per UNDIRECTED edge id (stateless PRF), so the
    same links fail regardless of representation: dense and sparse runs
    of one graph stay trajectory-equivalent under faults."""
    adj = np.asarray(topology.erdos_renyi(N, p=0.4, seed=0))
    s0 = netes.init_state(jax.random.PRNGKey(0), N, DIM)
    ch = cc.compile_channel("dropout(p=0.3,seed=7)", N)
    outs = {}
    for rep in ("dense", "sparse"):
        topo = topology_repr.from_dense(adj, rep)
        s, cs, _ = netes.run(s0, topo, _reward, CFG, num_iters=6,
                             channel=ch, chan_state=ch.init(s0.thetas))
        outs[rep] = (np.asarray(s.thetas), float(cs.msgs))
    assert outs["dense"][1] == outs["sparse"][1]        # same edges down
    np.testing.assert_allclose(outs["dense"][0], outs["sparse"][0],
                               rtol=1e-5, atol=1e-6)


def test_event_threshold_zero_sends_every_step():
    topo = _topo("dense")
    s0 = netes.init_state(jax.random.PRNGKey(0), N, DIM)
    ch = cc.compile_channel("event_triggered(threshold=0)", N)
    _, cs, m = netes.run(s0, topo, _reward, CFG, num_iters=8,
                         channel=ch, chan_state=ch.init(s0.thetas))
    np.testing.assert_array_equal(np.asarray(m["trigger_frac"]),
                                  np.ones(8, np.float32))


def test_event_trigger_holds_reference_payload():
    """A huge threshold never triggers: receivers keep the zero initial
    reference, so the mixing contribution comes from stale (zero)
    payloads — and the trigger fraction records it."""
    topo = _topo("dense")
    s0 = netes.init_state(jax.random.PRNGKey(0), N, DIM)
    ch = cc.compile_channel("event_triggered(threshold=1e9)", N)
    cs0 = ch.init(s0.thetas)
    wire, mask, cs1, info = ch.apply(cs0, topo, s0.thetas + 1.0)
    assert mask is None
    np.testing.assert_array_equal(np.asarray(wire),
                                  np.zeros_like(np.asarray(wire)))
    assert float(info["trigger_frac"]) == 0.0
    assert float(info["msgs"]) == 0.0


def test_realized_messages_counts_live_edges():
    topo = _topo("dense")
    live = int(np.asarray(topo.adj).sum() - N)       # non-self edges
    msgs = cc.realized_messages(topo, None, None)
    assert int(msgs) == live
    # dropout mask scales the count down; triggered=none keeps sources
    key = jax.random.PRNGKey(0)
    mask = cc.dropout_mask(key, topo, 0.5)
    masked = cc.realized_messages(topo, mask, None)
    assert 0 <= float(masked) < live


@pytest.mark.parametrize("rep", ["dense", "sparse", "circulant"])
def test_masked_neighbor_column_matches_masked_dense(rep):
    """neighbor_column(edge_mask=…) ≡ column of (adj ⊙ dense mask) for
    every representation — the contract the seed-replay ε-scan leans on
    (link-symmetric masks let row slices stand in for columns)."""
    topo = _topo(rep)
    key = jax.random.PRNGKey(11)
    mask = cc.dropout_mask(key, topo, 0.4)
    dense_topo = _topo("dense") if rep != "circulant" else \
        topology_repr.from_dense(np.asarray(topo.to_dense()), "dense")
    dense_mask = cc.dropout_mask(key, dense_topo, 0.4)
    masked_adj = np.asarray(dense_topo.adj) * np.asarray(dense_mask)
    for i in range(N):
        col = np.asarray(topology_repr.neighbor_column(
            topo, jnp.int32(i), edge_mask=mask))
        np.testing.assert_allclose(col, masked_adj[:, i], atol=1e-6,
                                   err_msg=f"{rep} col {i}")


def test_dropout_mask_symmetric_and_keeps_self():
    topo = _topo("dense")
    mask = np.asarray(cc.dropout_mask(jax.random.PRNGKey(3), topo, 0.5))
    np.testing.assert_array_equal(mask, mask.T)
    np.testing.assert_array_equal(np.diag(mask), np.ones(N))


def test_payload_bytes_model():
    assert cc.compile_channel(None, 4).payload_bytes(100) == 400
    assert cc.compile_channel("quantize(bits=8)", 4).payload_bytes(100) \
        == 100
    assert cc.compile_channel("quantize(bits=1)", 4).payload_bytes(100) \
        == pytest.approx(12.5)
    # topk sends value+index per kept element
    assert cc.compile_channel("topk(frac=0.25)|quantize(bits=8)",
                              4).payload_bytes(100) == pytest.approx(
        25 * (8 + 32) / 8)


# ---------------------------------------------------------------------------
# checkpoint / resume mid-stream
# ---------------------------------------------------------------------------

def test_resume_mid_channel_reproduces_uninterrupted_eval_trace(tmp_path):
    """Interrupt a channeled (and scheduled) run at an eval point,
    resume from the checkpoint: the post-resume eval trace is
    bit-for-bit identical to the uninterrupted run's — the threefry
    dropout stream, event references, and traffic counters all travel
    through checkpoint/io (mirroring the schedule resume test)."""
    tc = TrainConfig(
        n_agents=16, iters=16,
        topology=TopologySpec(family="erdos_renyi", n_agents=16, p=0.2,
                              seed=1),
        representation="sparse", schedule="resample_er(period=4)",
        channel="event_triggered(threshold=0.001)|quantize(bits=8)|"
                "dropout(p=0.2,seed=3)",
        seed=0, eval_every=4, eval_episodes=2,
        netes=NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.5))
    h_full = train_rl_netes("landscape:sphere", tc)
    ckpt = str(tmp_path / "ckpt")
    h_half = train_rl_netes("landscape:sphere", dataclasses.replace(
        tc, iters=8, checkpoint_dir=ckpt))
    h_res = train_rl_netes("landscape:sphere", dataclasses.replace(
        tc, checkpoint_dir=ckpt))
    assert h_half["eval"] == h_full["eval"][:2]
    assert h_res["eval_iter"] == h_full["eval_iter"][2:]
    assert h_res["eval"] == h_full["eval"][2:]       # bit-for-bit
    # counters resume too: totals add up to the uninterrupted run's
    total = np.float64(np.sum(h_half["msgs"]) + np.sum(h_res["msgs"]))
    assert total == pytest.approx(np.sum(h_full["msgs"]))


# ---------------------------------------------------------------------------
# distributed step builders
# ---------------------------------------------------------------------------

def _nano_cfg():
    from repro.configs import get_config
    return dataclasses.replace(
        get_config("mistral-nemo-12b-smoke"), name="chan-nano",
        num_layers=1, d_model=32, num_heads=2, num_kv_heads=2,
        head_dim=16, d_ff=64, vocab_size=64)


def test_replica_step_lossless_parity_and_lossy_runs():
    from repro.data import make_batch
    from repro.distributed import netes_dist
    from repro.models import transformer

    cfg = _nano_cfg()
    n = 6
    key = jax.random.PRNGKey(0)
    adj = np.asarray(topology.erdos_renyi(n, p=0.5, seed=0))
    topo = topology_repr.from_dense(adj, "sparse")
    p0 = transformer.init_params(key, cfg)
    params = jax.tree.map(
        lambda l: jnp.broadcast_to(l, (n,) + l.shape).copy(), p0)
    batch = make_batch(cfg, dict(seq_len=16, global_batch=n), key)
    batch = jax.tree.map(lambda x: x.reshape((n, 1) + x.shape[1:]), batch)

    ref_step = jax.jit(netes_dist.make_replica_train_step(
        cfg, CFG, n, microbatch=1, topology=topo))
    p_ref, m_ref = ref_step(params, None, batch, key)

    ch = cc.compile_channel("lossless", n)
    chan_step = jax.jit(netes_dist.make_replica_train_step(
        cfg, CFG, n, microbatch=1, topology=topo, channel=ch))
    p_ch, m_ch, cs = chan_step(params, None, batch, key, ch.init(params))
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_ch), strict=True):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert float(m_ch["loss_mean"]) == float(m_ref["loss_mean"])

    lossy = cc.compile_channel(
        "event_triggered(threshold=0.0001)|quantize(bits=8)|"
        "dropout(p=0.3,seed=2)", n)
    lossy_step = jax.jit(netes_dist.make_replica_train_step(
        cfg, CFG, n, microbatch=1, topology=topo, channel=lossy))
    cs = lossy.init(params)
    p_l, m_l, cs = lossy_step(params, None, batch, key, cs)
    assert np.isfinite(float(m_l["loss_mean"]))
    assert float(cs.msgs) >= 0
    # event reference now holds the transmitted tree
    assert jax.tree.structure(cs.last_sent) == jax.tree.structure(params)


def test_consensus_step_channel_and_event_rejection():
    from repro.data import make_batch
    from repro.distributed import netes_dist
    from repro.models import transformer

    cfg = _nano_cfg()
    n = 4
    key = jax.random.PRNGKey(0)
    adj = jnp.asarray(topology.erdos_renyi(n, p=0.6, seed=0))
    params = transformer.init_params(key, cfg)
    batch = make_batch(cfg, dict(seq_len=16, global_batch=n), key)
    batch = jax.tree.map(lambda x: x.reshape((n, 1) + x.shape[1:]), batch)

    ch = cc.compile_channel("quantize(bits=8)|dropout(p=0.2,seed=1)", n)
    step = jax.jit(netes_dist.make_consensus_train_step(
        cfg, CFG, n, channel=ch))
    p1, m, cs = step(params, adj, batch, key, ch.init(params))
    assert np.isfinite(float(m["loss_mean"]))
    # no per-edge θ traffic exists in consensus mode: the counter sees
    # only the broadcast fan-out (n messages when the event fired)
    assert float(cs.msgs) == float(m["broadcast"]) * n

    with pytest.raises(ValueError, match="event_triggered"):
        netes_dist.make_consensus_train_step(
            cfg, CFG, n,
            channel=cc.compile_channel("event_triggered(threshold=0)", n))


def test_collective_codec_rejects_stateful_stages():
    from repro.distributed import permute_mixing
    with pytest.raises(ValueError, match="stateless"):
        permute_mixing._wire_codec(
            cc.compile_channel("dropout(p=0.1)", 4))


# ---------------------------------------------------------------------------
# search integration
# ---------------------------------------------------------------------------

def test_grid_crosses_channels_and_collapses_lossless():
    from repro.search.candidates import make_grid
    grid = make_grid(8, ("erdos_renyi", "fully_connected"), (0.2,), (0,),
                     channels=(None, "lossless", "quantize(bits=8)"))
    labels = [c.label() for c in grid]
    assert labels == ["erdos_renyi:p=0.2:s=0", "erdos_renyi:p=0.2:s=0+q8",
                      "fully_connected", "fully_connected+q8"]


# ---------------------------------------------------------------------------
# the topology × channel training grid
# ---------------------------------------------------------------------------

GRID_N, GRID_P, GRID_ITERS = 64, 0.1, 8
GRID_TASK = "landscape:rastrigin@2.5"
GRID_FAMILIES = {"erdos_renyi": (GRID_P, "sparse"),
                 "fully_connected": (1.0, "dense")}
GRID_CHANNELS = {"lossless": "lossless", "drop10": "dropout(p=0.1,seed=0)",
                 "drop30": "dropout(p=0.3,seed=0)", "q8": "quantize(bits=8)",
                 "q4": "quantize(bits=4)", "q1": "quantize(bits=1)"}
DROP_P = {"drop10": 0.1, "drop30": 0.3}


def _grid_tc(family, suffix, fused=True):
    p, rep = GRID_FAMILIES[family]
    return TrainConfig(
        n_agents=GRID_N, iters=GRID_ITERS,
        topology=TopologySpec(family=family, n_agents=GRID_N, p=p, seed=0),
        representation=rep, channel=GRID_CHANNELS[suffix],
        channel_fused=fused, seed=0, eval_every=GRID_ITERS // 2,
        eval_episodes=2,
        # every iteration broadcasts, so each step's message count is
        # known: the live mixing edges plus N broadcast receipts
        netes=NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=1.0))


@pytest.fixture(scope="module")
def grid_cell(tmp_path_factory):
    """Runs (and keeps) one cell: a warm run and its rerun, the rerun's
    history, both runs' spans, and the graph's directed non-self edges."""
    done = {}

    def cell(family, suffix):
        if (family, suffix) not in done:
            tc = _grid_tc(family, suffix)
            topo = build_topology(tc)
            adj = np.asarray(topo.to_dense())
            edges = int((adj != 0).sum() - GRID_N)
            hist, warm, rerun = rerun_after_warmup(
                GRID_TASK, tc, tmp_path_factory.mktemp(f"{family}-{suffix}"))
            done[family, suffix] = dict(topo=topo, edges=edges, hist=hist,
                                        warm=warm, rerun=rerun)
        return done[family, suffix]
    return cell


@pytest.mark.parametrize("suffix", list(GRID_CHANNELS))
@pytest.mark.parametrize("family", list(GRID_FAMILIES))
def test_channel_grid_cell(grid_cell, family, suffix):
    """One scan per cell; realized messages are the live edges plus the
    broadcast receipts of every step, and the bytes are those messages
    × the channel's encoded message size."""
    c = grid_cell(family, suffix)
    assert c["topo"].kind == GRID_FAMILIES[family][1]
    assert_one_scan_per_config(c["warm"], c["rerun"])
    hist, edges = c["hist"], c["edges"]
    msgs = np.asarray(hist["msgs"], np.float64)
    assert msgs.shape == (GRID_ITERS,)
    live = msgs - GRID_N
    if suffix in DROP_P:
        # symmetric drops remove edges in pairs; about p of them go
        assert (live % 2 == 0).all() and (live <= edges).all()
        kept = live.sum() / (GRID_ITERS * edges)
        assert abs(kept - (1.0 - DROP_P[suffix])) < 0.08, kept
    else:
        np.testing.assert_array_equal(live, edges)
    channel = cc.compile_channel(GRID_CHANNELS[suffix], GRID_N)
    dim = resolve_task(GRID_TASK)[1]
    assert hist["realized_msgs"] == msgs.sum()
    assert hist["realized_wire_bytes"] == round(
        msgs.sum() * channel.payload_bytes(dim))


@pytest.mark.parametrize("suffix", ["q8", "q4", "q1"])
def test_channel_grid_fused_matches_unfused(grid_cell, suffix, tmp_path):
    """The sparse ER cells' wire path trains exactly as the fake-quant
    path does, and moves the same bytes; the fake-quant control is one
    scan per config too."""
    fused = grid_cell("erdos_renyi", suffix)["hist"]
    unfused, warm, rerun = rerun_after_warmup(
        GRID_TASK, _grid_tc("erdos_renyi", suffix, fused=False), tmp_path)
    assert_one_scan_per_config(warm, rerun)
    assert unfused["eval"] == fused["eval"]
    assert unfused["realized_wire_bytes"] == fused["realized_wire_bytes"]


def test_er_moves_under_2p_of_fc_bytes(grid_cell):
    """Summed over the lossy channels, sparse ER moves less than 2·p of
    fully-connected's realized bytes: the counters follow the graph."""
    lossy = [s for s in GRID_CHANNELS if s != "lossless"]

    def total(family):
        return sum(grid_cell(family, s)["hist"]["realized_wire_bytes"]
                   for s in lossy)

    assert total("erdos_renyi") < 2 * GRID_P * total("fully_connected")
