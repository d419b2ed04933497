"""Model-component correctness: blocks vs references, decode vs prefill."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import make_batch
from repro.models import attention, mamba, moe, rwkv6, transformer

RNG = np.random.default_rng(7)


# (kind, causal) of AttnSpec for each mask of flash_attention_ref, and
# the pattern's width: window for sliding, chunk for chunked.
ATTN_MASKS = {"causal": ("full", True, 0), "window": ("sliding", True, 96),
              "chunk": ("chunked", True, 128), "full": ("full", False, 0)}


@pytest.mark.parametrize("b,s,h,kv,hd,mask,dtype,block", [
    *[(*shape, mask, jnp.float32, 128)
      for shape in [(1, 128, 4, 4, 32),       # MHA
                    (2, 257, 4, 2, 64),       # GQA, ragged seq
                    (1, 512, 8, 1, 32)]       # MQA
      for mask in ATTN_MASKS],
    (1, 256, 4, 2, 64, "causal", jnp.bfloat16, 128),
    (2, 200, 4, 2, 32, "window", jnp.float32, 64),  # 200 ∤ 64
])
def test_blockwise_attention_matches_naive(b, s, h, kv, hd, mask, dtype,
                                           block):
    from repro.kernels import ref
    kind, causal, width = ATTN_MASKS[mask]
    q = jnp.asarray(RNG.normal(size=(b, s, h, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, s, kv, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, s, kv, hd)), dtype)
    pos = jnp.arange(s)
    spec = attention.AttnSpec(num_heads=h, num_kv_heads=kv, head_dim=hd,
                              kind=kind, window=width)
    o_b = attention.blockwise_attention(spec, q, k, v, pos, pos,
                                        causal=causal, q_block=block,
                                        k_block=block)
    o_r = ref.flash_attention_ref(
        q, k, v, causal=causal,
        window=width if kind == "sliding" else 0,
        chunk=width if kind == "chunked" else 0)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(o_b, np.float32),
                               np.asarray(o_r, np.float32),
                               rtol=tol, atol=tol)


def test_decode_attention_matches_prefill():
    """Token-by-token decode == full-sequence attention (rolling cache)."""
    b, s, h, kv, hd = 2, 32, 4, 2, 16
    d_model = 64
    spec = attention.AttnSpec(num_heads=h, num_kv_heads=kv, head_dim=hd,
                              kind="full", rope=True)
    params = attention.attn_init(jax.random.PRNGKey(0), d_model, spec,
                                 jnp.float32)
    x = jnp.asarray(RNG.normal(size=(b, s, d_model)), jnp.float32) * 0.1
    pos = jnp.arange(s)
    full = attention.attention_block(params, spec, x, pos)

    cache = attention.init_kv_cache(b, spec, s, jnp.float32)
    outs = []
    for t in range(s):
        o, cache = attention.decode_attention(
            params, spec, x[:, t:t + 1], cache, jnp.full((b,), t))
        outs.append(o)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


def test_decode_attention_sliding_window_rolls():
    b, s, h, kv, hd, w = 1, 48, 2, 2, 8, 16
    d_model = 32
    spec = attention.AttnSpec(num_heads=h, num_kv_heads=kv, head_dim=hd,
                              kind="sliding", window=w, rope=True)
    params = attention.attn_init(jax.random.PRNGKey(1), d_model, spec,
                                 jnp.float32)
    x = jnp.asarray(RNG.normal(size=(b, s, d_model)), jnp.float32) * 0.1
    pos = jnp.arange(s)
    full = attention.attention_block(params, spec, x, pos)
    cache = attention.init_kv_cache(b, spec, s, jnp.float32)
    assert cache["k"].shape[1] == w, "cache bounded by window"
    outs = []
    for t in range(s):
        o, cache = attention.decode_attention(
            params, spec, x[:, t:t + 1], cache, jnp.full((b,), t))
        outs.append(o)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


def test_mamba_chunked_matches_unchunked_and_decode():
    spec = mamba.MambaSpec(d_model=64, d_state=8)
    p = mamba.mamba_init(jax.random.PRNGKey(0), spec, jnp.float32)
    x = jnp.asarray(RNG.normal(size=(2, 128, 64)), jnp.float32) * 0.3
    y_full = mamba.mamba_block(p, spec, x, chunk=1024)
    y_chunk = mamba.mamba_block(p, spec, x, chunk=32)
    np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_chunk),
                               rtol=1e-4, atol=1e-5)
    cache = mamba.init_mamba_cache(2, spec, jnp.float32)
    outs = []
    c = cache
    for t in range(16):
        o, c = mamba.mamba_decode(p, spec, x[:, t:t + 1], c)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(y_full[:, :16]),
                               rtol=1e-4, atol=1e-5)


def _sequential_recurrence(decay, drive):
    """h_t = decay_t ⊙ h_{t−1} + drive_t over axis 1, one step at a time."""
    dec, drv = np.asarray(decay, np.float64), np.asarray(drive, np.float64)
    h = np.zeros_like(dec[:, 0])
    out = np.empty_like(dec)
    for t in range(dec.shape[1]):
        h = dec[:, t] * h + drv[:, t]
        out[:, t] = h
    return out


@pytest.mark.parametrize("b,s,d,n", [(1, 32, 64, 8), (2, 64, 300, 16),
                                     (1, 128, 512, 4),
                                     (2, 63, 32, 8)])  # odd: uneven halving
def test_mamba_scan_matches_sequential_recurrence(b, s, d, n):
    """The associative scan ``mamba_block`` runs equals the recurrence
    stepped once per token."""
    dec = jnp.asarray(RNG.uniform(0.8, 0.999, (b, s, d, n)), jnp.float32)
    drv = jnp.asarray(RNG.normal(size=(b, s, d, n)), jnp.float32)
    np.testing.assert_allclose(np.asarray(mamba.mamba_scan_ref(dec, drv)),
                               _sequential_recurrence(dec, drv),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,s,h,n,chunk", [(1, 16, 2, 8, 8),
                                           (2, 48, 3, 16, 16),
                                           (1, 64, 4, 32, 16),
                                           (1, 40, 2, 16, 16)])  # 16 ∤ 40
def test_wkv6_chunked_matches_scan(b, s, h, n, chunk):
    r = jnp.asarray(RNG.normal(size=(b, s, h, n)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, s, h, n)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, s, h, n)), jnp.float32)
    w = jnp.asarray(RNG.uniform(0.92, 0.999, (b, s, h, n)), jnp.float32)
    u = jnp.asarray(RNG.normal(size=(h, n)), jnp.float32)
    o_c, s_c = rwkv6.wkv6_chunked(r, k, v, w, u, chunk=chunk)
    o_r, s_r = rwkv6.wkv6_scan_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_r),
                               rtol=2e-4, atol=2e-4)


def test_rwkv_block_decode_matches_prefill():
    spec = rwkv6.RWKV6Spec(d_model=64, num_heads=2)
    p = rwkv6.rwkv6_init(jax.random.PRNGKey(0), spec, jnp.float32)
    x = jnp.asarray(RNG.normal(size=(1, 24, 64)), jnp.float32) * 0.2
    full = rwkv6.rwkv6_block(p, spec, x, chunk=8)
    cache = rwkv6.init_rwkv_cache(1, spec, jnp.float32)
    outs = []
    c = cache
    for t in range(24):
        o, c = rwkv6.rwkv6_decode(p, spec, x[:, t:t + 1], c)
        outs.append(o)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("b,t,e,k,d,group", [
    (2, 128, 4, 2, 32, 64),
    (1, 100, 8, 2, 16, 100), (1, 500, 16, 6, 16, 500),
    (1, 64, 64, 8, 16, 64), (1, 257, 128, 1, 16, 257)])
def test_moe_block_matches_dense_ref_no_drops(b, t, e, k, d, group):
    """A capacity factor of E gives every expert room for all of a
    group's choices, so nothing drops."""
    spec = moe.MoESpec(num_experts=e, experts_per_token=k, d_model=d,
                       d_ff=2 * d, capacity_factor=float(e),
                       group_size=group)
    p = moe.moe_init(jax.random.PRNGKey(1), spec, jnp.float32)
    x = jnp.asarray(RNG.normal(size=(b, t, d)), jnp.float32)
    block, dense = (jax.jit(f, static_argnums=1)
                    for f in (moe.moe_block, moe.moe_ref))
    np.testing.assert_allclose(np.asarray(block(p, spec, x)),
                               np.asarray(dense(p, spec, x)),
                               rtol=2e-4, atol=2e-4)


def test_moe_capacity_drops_bounded():
    """With cf=1.0 some tokens drop; output stays finite and ≤ ref count."""
    spec = moe.MoESpec(num_experts=4, experts_per_token=1, d_model=16,
                       d_ff=32, capacity_factor=1.0, group_size=64)
    p = moe.moe_init(jax.random.PRNGKey(2), spec, jnp.float32)
    x = jnp.asarray(RNG.normal(size=(1, 64, 16)), jnp.float32)
    y = moe.moe_block(p, spec, x)
    assert np.isfinite(np.asarray(y)).all()
    # dropped tokens output exactly 0 (residual-only pass-through)
    zero_rows = (np.abs(np.asarray(y[0])).max(axis=-1) == 0.0).sum()
    assert zero_rows >= 0


def test_moe_load_balance_loss_uniform_router():
    spec = moe.MoESpec(num_experts=8, experts_per_token=2, d_model=16,
                       d_ff=32)
    p = moe.moe_init(jax.random.PRNGKey(3), spec, jnp.float32)
    p = dict(p, router=jnp.zeros_like(p["router"]))
    x = jnp.asarray(RNG.normal(size=(1, 256, 16)), jnp.float32)
    lb = moe.load_balance_loss(p, spec, x)
    # uniform probs: E · Σ f_e p_e = E · E·(1/E·1/E) = 1
    assert abs(float(lb) - 1.0) < 0.2


def test_scan_layers_equals_unrolled():
    cfg = dataclasses.replace(get_config("mistral-nemo-12b-smoke"),
                              name="scan-test", num_layers=8)
    key = jax.random.PRNGKey(0)
    batch = make_batch(cfg, dict(seq_len=64, global_batch=2), key)
    params = transformer.init_params(key, cfg)
    l_scan = transformer.loss_fn(params, cfg, batch)
    orig = transformer.stack_plan
    transformer.stack_plan = lambda c: (0, c.num_layers, 1, 0)
    try:
        params_u = transformer.init_params(key, cfg)
        l_unroll = transformer.loss_fn(params_u, cfg, batch)
    finally:
        transformer.stack_plan = orig
    assert abs(float(l_scan) - float(l_unroll)) < 1e-5


def test_chunked_xent_matches_unchunked():
    cfg = get_config("mistral-nemo-12b-smoke")
    key = jax.random.PRNGKey(0)
    params = transformer.init_params(key, cfg)
    batch = make_batch(cfg, dict(seq_len=256, global_batch=2), key)
    l_big = transformer.loss_fn(params, cfg, batch, xent_chunk=64)
    l_one = transformer.loss_fn(params, cfg, batch, xent_chunk=10 ** 9)
    assert abs(float(l_big) - float(l_one)) < 1e-4


@pytest.mark.parametrize("arch", ["whisper-tiny-smoke",
                                  "llava-next-mistral-7b-smoke"])
def test_frontend_archs_fuse_embeddings(arch):
    cfg = get_config(arch)
    key = jax.random.PRNGKey(0)
    params = transformer.init_params(key, cfg)
    batch = make_batch(cfg, dict(seq_len=64, global_batch=2), key)
    logits = transformer.forward(params, cfg, batch)
    if cfg.frontend == "vision":
        assert logits.shape[1] == 64            # patches + text
        assert batch["tokens"].shape[1] == 64 - cfg.num_patches
    else:
        assert "frames" in batch
    loss = transformer.loss_fn(params, cfg, batch)
    assert np.isfinite(float(loss))
