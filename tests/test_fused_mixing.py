"""Quantized wire path (DESIGN.md §12).

Property tests (via the hypothesis shim) for the wire codec
(``core.wire_format`` ≡ the channel's fake-quant ``_quantize``, bit for
bit), the wire neighbor sum and the fused broadcast select against their
jnp oracles (the select on both lowerings), the ``weighted_neighbor_sum``
WirePayload dispatch across representations, and end-to-end
wire-vs-fake-quant trajectory parity (static, scheduled, distributed),
and checkpoint resume through the wire path.

A wire payload is decoded once and contracted by the same code as the
fake-quant messages, and ``decode(encode(x))`` equals the fake-quant bit
for bit, so the end-to-end parity assertions are equality, not
tolerance. Tolerances appear only where an oracle computes in a
genuinely different order (the (N, K, D) einsum reference).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.comm import channel as cc
from repro.core import netes, topology, topology_repr, wire_format
from repro.core.netes import NetESConfig
from repro.core.topology import TopologySpec
from repro.kernels import netes_fused_mixing as nfm
from repro.kernels import ref
from repro.train.loop import TrainConfig, train_rl_netes

N = 12
DIM = 6
CFG = NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.5)


def _reward(params, key):
    return -jnp.sum(params ** 2, axis=-1)


def _topo(rep: str, n: int = N, p: float = 0.4):
    fam = "circulant_erdos_renyi" if rep == "circulant" else "erdos_renyi"
    adj = np.asarray(getattr(topology, fam)(n, p=p, seed=0))
    return topology_repr.from_dense(adj, rep)


# ---------------------------------------------------------------------------
# wire codec ≡ channel fake-quant (property tests)
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(bits=st.sampled_from([8, 4, 1]), n=st.sampled_from([8, 64, 257]),
       seed=st.integers(0, 50))
def test_encode_decode_matches_fake_quant_bitwise(bits, n, seed):
    """decode(encode(x)) ≡ the channel's in-place ``_quantize`` — the
    fused path reads the SAME numbers off the wire that the unfused
    path mixes, bit for bit (f32)."""
    x = jnp.asarray(np.random.default_rng(seed).normal(
        size=(n, 7)).astype(np.float32))
    wp = wire_format.encode(x, bits, True)
    assert wp.codes.dtype == jnp.int8
    assert wp.scale.shape == (n, 1)
    y = wire_format.decode_payload(wp)
    assert y.dtype == x.dtype
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(cc._quantize(x, bits, True)))


@settings(max_examples=10, deadline=None)
@given(bits=st.sampled_from([8, 4, 1]), seed=st.integers(0, 50))
def test_encode_unbatched_and_payload_pytree(bits, seed):
    """Unbatched encode (one message) uses a single global scale, and
    WirePayload round-trips as a pytree leaf-pair + static dtype."""
    x = jnp.asarray(np.random.default_rng(seed).normal(
        size=(31,)).astype(np.float32))
    wp = wire_format.encode(x, bits, False)
    assert wp.scale.shape == (1,)
    np.testing.assert_array_equal(
        np.asarray(wire_format.decode_payload(wp)),
        np.asarray(cc._quantize(x, bits, False)))
    leaves, treedef = jax.tree.flatten(wp)
    assert len(leaves) == 2
    wp2 = jax.tree.unflatten(treedef, leaves)
    assert wp2.dtype == wp.dtype
    np.testing.assert_array_equal(np.asarray(wp2.codes),
                                  np.asarray(wp.codes))


def test_slice_stack_indexes_message_axis():
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(4, 5, 3)).astype(np.float32))
    # a stacked wire: one payload per draw r along axis 1
    wp = wire_format.encode(x, 8, True)
    for r in range(5):
        sl = wire_format.slice_stack(wp, jnp.int32(r))
        np.testing.assert_array_equal(np.asarray(sl.codes),
                                      np.asarray(wp.codes[:, r]))


# ---------------------------------------------------------------------------
# wire neighbor sum and fused broadcast select vs oracles
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(n=st.sampled_from([8, 64, 257]), bits=st.sampled_from([8, 4, 1]),
       seed=st.integers(0, 50), masked=st.sampled_from([False, True]))
def test_wire_neighbor_sum_matches_oracle(n, bits, seed, masked):
    rng = np.random.default_rng(seed)
    adj = np.asarray(topology.erdos_renyi(n, p=0.3, seed=seed))
    topo = topology_repr.from_dense(adj, "sparse")
    coeff = jnp.asarray(rng.normal(size=n), jnp.float32)
    x = jnp.asarray(rng.normal(size=(n, 9)), jnp.float32)
    wp = wire_format.encode(x, bits, True)
    em = None
    if masked:
        em = cc.dropout_mask(jax.random.PRNGKey(seed), topo, 0.4)
    want = ref.wire_neighbor_sum_ref(topo.neighbor_idx,
                                     topo.neighbor_mask, coeff,
                                     wp.codes, wp.scale, em)
    got = topology_repr.weighted_neighbor_sum(topo, coeff, wp,
                                              edge_mask=em)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(bits=st.sampled_from([8, 4, 1]), seed=st.integers(0, 50),
       flag=st.sampled_from([False, True]))
def test_fused_broadcast_select_matches_oracle(bits, seed, flag):
    rng = np.random.default_rng(seed)
    th = jnp.asarray(rng.normal(size=(10, 17)), jnp.float32)
    wp = wire_format.encode(jnp.asarray(rng.normal(size=17), jnp.float32),
                            bits, False)
    do = jnp.asarray(flag)
    want = ref.broadcast_select_ref(wp.codes, wp.scale, do, th)
    for backend in ("auto", "pallas"):
        got = nfm.fused_broadcast_select(wp.codes, wp.scale, do, th,
                                         backend=backend)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=backend)


@pytest.mark.parametrize("flag", [False, True])
def test_fused_broadcast_select_ragged_tiles(flag):
    """A 2-D grid whose edge blocks overhang both axes (N=600, D=700
    over 512×512 tiles) is still exactly the oracle: Pallas masks the
    overhang, nothing is padded."""
    rng = np.random.default_rng(7)
    th = jnp.asarray(rng.normal(size=(600, 700)), jnp.float32)
    wp = wire_format.encode(jnp.asarray(rng.normal(size=700), jnp.float32),
                            8, False)
    do = jnp.asarray(flag)
    got = nfm.fused_broadcast_select(wp.codes, wp.scale, do, th,
                                     backend="pallas")
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(ref.broadcast_select_ref(wp.codes, wp.scale, do, th)))


def test_backend_resolution_by_platform(monkeypatch):
    """The platform alone picks the lowering: compiled Pallas on TPU,
    XLA elsewhere; explicit Pallas off TPU interprets."""
    monkeypatch.setattr(nfm, "_on_tpu", lambda: True)
    assert nfm._resolve_backend("auto") == "pallas"
    assert nfm._resolve_interpret(None) is False
    monkeypatch.setattr(nfm, "_on_tpu", lambda: False)
    assert nfm._resolve_backend("auto") == "xla"
    assert nfm._resolve_interpret(None) is True
    with pytest.raises(ValueError):
        nfm._resolve_backend("cuda")


# ---------------------------------------------------------------------------
# channel wire-eligibility + apply_wire
# ---------------------------------------------------------------------------

def test_wire_quantized_eligibility():
    def ch(spec):
        return cc.compile_channel(spec, N)

    assert ch("quantize(bits=8)").wire_quantized
    assert ch("quantize(bits=1)|dropout(p=0.1,seed=0)").wire_quantized
    assert ch("event_triggered(threshold=0.01)|quantize(bits=4)"
              ).wire_quantized
    assert not ch("lossless").wire_quantized
    assert not ch("dropout(p=0.1,seed=0)").wire_quantized
    assert not ch("quantize(bits=8)|quantize(bits=4)").wire_quantized
    assert not ch("quantize(bits=8)|topk(frac=0.5)").wire_quantized
    # topology gate: fused only on sparse, and only when enabled
    t_sparse, t_dense = _topo("sparse"), _topo("dense")
    q = ch("quantize(bits=8)")
    assert q.wire_fused(t_sparse) and not q.wire_fused(t_dense)
    q_off = cc.compile_channel("quantize(bits=8)", N, fused=False)
    assert not q_off.wire_fused(t_sparse)


def test_apply_wire_rejects_non_wire_channels():
    ch = cc.compile_channel("dropout(p=0.1,seed=0)", N)
    topo = _topo("sparse")
    x = jnp.zeros((N, DIM), jnp.float32)
    with pytest.raises(ValueError, match="wire"):
        ch.apply_wire(ch.init(x), topo, x)
    with pytest.raises(ValueError, match="wire"):
        ch.encode_wire(x, batched=True)


@settings(max_examples=10, deadline=None)
@given(spec=st.sampled_from(["quantize(bits=8)", "quantize(bits=4)",
                             "quantize(bits=1)",
                             "quantize(bits=8)|dropout(p=0.3,seed=2)",
                             "event_triggered(threshold=0.001)|"
                             "quantize(bits=4)"]),
       seed=st.integers(0, 50))
def test_apply_wire_decodes_to_apply(spec, seed):
    """``apply_wire`` ≡ ``apply`` with the quantize stage's fake-quant
    replaced by a wire encode: decoding its payload reproduces the
    unfused messages bit for bit, with identical mask/state/info."""
    topo = _topo("sparse")
    ch = cc.compile_channel(spec, N)
    x = jnp.asarray(np.random.default_rng(seed).normal(
        size=(N, DIM)).astype(np.float32))
    s0 = ch.init(x)
    msgs, mask, s1, info = ch.apply(s0, topo, x)
    wire, w_mask, w_s1, w_info = ch.apply_wire(s0, topo, x)
    assert isinstance(wire, wire_format.WirePayload)
    np.testing.assert_array_equal(
        np.asarray(wire_format.decode_payload(wire)), np.asarray(msgs))
    if mask is None:
        assert w_mask is None
    else:
        np.testing.assert_array_equal(np.asarray(mask),
                                      np.asarray(w_mask))
    np.testing.assert_array_equal(np.asarray(info["msgs"]),
                                  np.asarray(w_info["msgs"]))
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(w_s1), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# weighted_neighbor_sum WirePayload dispatch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rep", ["dense", "sparse", "circulant"])
@pytest.mark.parametrize("bits", [8, 4, 1])
def test_wire_dispatch_matches_decoded(rep, bits):
    """``weighted_neighbor_sum(topo, coeff, WirePayload)`` ≡ the same
    contraction on the decoded payload, bit for bit, for every
    representation (the payload is decoded once, then recursed)."""
    rng = np.random.default_rng(bits)
    topo = _topo(rep)
    coeff = jnp.asarray(rng.normal(size=N), jnp.float32)
    x = jnp.asarray(rng.normal(size=(N, DIM)), jnp.float32)
    wp = wire_format.encode(x, bits, True)
    want = topology_repr.weighted_neighbor_sum(
        topo, coeff, wire_format.decode_payload(wp))
    got = topology_repr.weighted_neighbor_sum(topo, coeff, wp)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("rep", ["dense", "sparse", "circulant"])
def test_wire_dispatch_respects_edge_mask(rep):
    topo = _topo(rep)
    rng = np.random.default_rng(7)
    coeff = jnp.asarray(rng.normal(size=N), jnp.float32)
    x = jnp.asarray(rng.normal(size=(N, DIM)), jnp.float32)
    wp = wire_format.encode(x, 8, True)
    em = cc.dropout_mask(jax.random.PRNGKey(1), topo, 0.5)
    want = topology_repr.weighted_neighbor_sum(
        topo, coeff, wire_format.decode_payload(wp), edge_mask=em)
    got = topology_repr.weighted_neighbor_sum(topo, coeff, wp,
                                              edge_mask=em)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# end-to-end: fused ≡ unfused trajectories
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", ["quantize(bits=8)",
                                  "quantize(bits=1)",
                                  "quantize(bits=4)|dropout(p=0.2,seed=3)"])
def test_netes_run_fused_matches_unfused_bitwise(spec):
    topo = _topo("sparse")
    s0 = netes.init_state(jax.random.PRNGKey(0), N, DIM)
    outs = {}
    for fused in (True, False):
        ch = cc.compile_channel(spec, N, fused=fused)
        assert ch.wire_fused(topo) == fused
        s, cs, m = netes.run(s0, topo, _reward, CFG, num_iters=8,
                             channel=ch, chan_state=ch.init(s0.thetas))
        outs[fused] = (np.asarray(s.thetas), float(cs.msgs))
    np.testing.assert_array_equal(outs[True][0], outs[False][0])
    assert outs[True][1] == outs[False][1]      # traffic counters agree


def test_scheduled_scan_fused_matches_unfused():
    """Fused wire path inside a SCHEDULED 1-scan run (graph resampling
    on device) ≡ the unfused run, eval trace bit for bit."""
    tc = TrainConfig(
        n_agents=16, iters=12,
        topology=TopologySpec(family="erdos_renyi", n_agents=16, p=0.2,
                              seed=1),
        representation="sparse", schedule="resample_er(period=4)",
        channel="quantize(bits=8)", seed=0,
        eval_every=4, eval_episodes=2,
        netes=NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.5))
    h_fused = train_rl_netes("landscape:sphere", tc)
    h_unfused = train_rl_netes(
        "landscape:sphere", dataclasses.replace(tc, channel_fused=False))
    assert h_fused["eval"] == h_unfused["eval"]
    assert np.sum(h_fused["msgs"]) == np.sum(h_unfused["msgs"])


def test_resume_mid_fused_channel_reproduces_eval_trace(tmp_path):
    """Checkpoint/resume through the wire path: the post-resume
    eval trace is bit-for-bit the uninterrupted run's (the channel
    state, schedule state, and wire dispatch all travel)."""
    tc = TrainConfig(
        n_agents=16, iters=16,
        topology=TopologySpec(family="erdos_renyi", n_agents=16, p=0.2,
                              seed=1),
        representation="sparse", schedule="resample_er(period=4)",
        channel="quantize(bits=8)|dropout(p=0.2,seed=3)",
        seed=0, eval_every=4, eval_episodes=2,
        netes=NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.5))
    h_full = train_rl_netes("landscape:sphere", tc)
    ckpt = str(tmp_path / "ckpt")
    h_half = train_rl_netes("landscape:sphere", dataclasses.replace(
        tc, iters=8, checkpoint_dir=ckpt))
    h_res = train_rl_netes("landscape:sphere", dataclasses.replace(
        tc, checkpoint_dir=ckpt))
    assert h_half["eval"] == h_full["eval"][:2]
    assert h_res["eval"] == h_full["eval"][2:]       # bit-for-bit
    total = np.float64(np.sum(h_half["msgs"]) + np.sum(h_res["msgs"]))
    assert total == pytest.approx(np.sum(h_full["msgs"]))


def test_replica_step_fused_matches_unfused():
    """Distributed replica step (stacked transformer leaves, seed-replay
    ε-scan + fused broadcast) fused ≡ unfused, every parameter leaf."""
    from repro.data import make_batch
    from repro.distributed import netes_dist
    from repro.models import transformer

    from test_channel import _nano_cfg

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

    outs = {}
    for fused in (True, False):
        ch = cc.compile_channel("quantize(bits=8)", n, fused=fused)
        step = jax.jit(netes_dist.make_replica_train_step(
            cfg, CFG, n, microbatch=1, topology=topo, channel=ch))
        p1, m, cs = step(params, None, batch, key, ch.init(params))
        outs[fused] = (p1, float(cs.msgs))
    for a, b in zip(jax.tree.leaves(outs[True][0]),
                    jax.tree.leaves(outs[False][0]), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert outs[True][1] == outs[False][1]
