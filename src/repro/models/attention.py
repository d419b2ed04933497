"""Attention: GQA with RoPE; full / sliding-window / chunked-local patterns;
blockwise (memory-efficient) prefill computation and single-token decode.

The blockwise implementation is the one prefill path (compiles on any
backend, O(block²) memory); tests check it against the naive oracle
``kernels.ref.flash_attention_ref``.

Patterns (``kind``):
  * ``full``     — causal.
  * ``sliding``  — causal ∧ (i − j < window)        [gemma3 local, jamba attn]
  * ``chunked``  — causal ∧ (i//chunk == j//chunk)  [llama4 local layers]
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.context import maybe_constrain

from . import layers

NEG_INF = -1e30
_PAD_K_POS = 10 ** 9     # position of blockwise_attention's padded keys


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    num_heads: int
    num_kv_heads: int
    head_dim: int
    kind: str = "full"              # full | sliding | chunked
    window: int = 0                 # for sliding / chunked
    rope: bool = True
    rope_theta: float = 10000.0
    qk_norm: bool = False
    softmax_scale: Optional[float] = None

    @property
    def scale(self) -> float:
        return self.softmax_scale or self.head_dim ** -0.5


def attn_init(key: jax.Array, d_model: int, spec: AttnSpec, dtype,
              cross: bool = False):
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": layers.dense_init(kq, (d_model, spec.num_heads, spec.head_dim), dtype),
        "wk": layers.dense_init(kk, (d_model, spec.num_kv_heads, spec.head_dim), dtype),
        "wv": layers.dense_init(kv, (d_model, spec.num_kv_heads, spec.head_dim), dtype),
        "wo": layers.dense_init(ko, (spec.num_heads, spec.head_dim, d_model), dtype,
                                scale=1.0 / (spec.num_heads * spec.head_dim) ** 0.5),
    }
    if spec.qk_norm:
        p["q_norm"] = layers.rmsnorm_init(spec.head_dim, dtype)
        p["k_norm"] = layers.rmsnorm_init(spec.head_dim, dtype)
    return p


def _mask_bias(spec: AttnSpec, q_pos: jax.Array, k_pos: jax.Array,
               causal: bool) -> jax.Array:
    """(Sq, Sk) additive bias implementing the pattern."""
    diff = q_pos[:, None] - k_pos[None, :]
    ok = jnp.ones(diff.shape, dtype=bool)
    if causal:
        ok &= diff >= 0
    if spec.kind == "sliding":
        ok &= diff < spec.window
    elif spec.kind == "chunked":
        ok &= (q_pos[:, None] // spec.window) == (k_pos[None, :] // spec.window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _gqa_scores(q: jax.Array, k: jax.Array, scale: float) -> jax.Array:
    """q (B,Sq,Hkv,G,hd), k (B,Sk,Hkv,hd) → (B,Hkv,G,Sq,Sk) fp32."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                      preferred_element_type=jnp.float32) * scale


def blockwise_attention(spec: AttnSpec, q: jax.Array, k: jax.Array,
                        v: jax.Array, q_positions: jax.Array,
                        k_positions: jax.Array, causal: bool = True,
                        q_block: int = 512, k_block: int = 1024) -> jax.Array:
    """Memory-efficient attention: outer map over query blocks, inner scan
    over KV blocks with online softmax. Never materializes (Sq, Sk).

    q: (B, Sq, H, hd); k, v: (B, Sk, Hkv, hd). Returns (B, Sq, H, hd).
    """
    b, sq, h, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    # shard-friendliness: the q-block reshape splits S into (n_blocks,
    # block); if n_blocks < the model-axis width (16), an S-sharded q would
    # be force-gathered. Keep ≥16 query blocks for long sequences.
    if sq >= 16 * 128:
        q_block = min(q_block, sq // 16)
    q_block = min(q_block, sq)
    k_block = min(k_block, sk)
    # pad to block multiples
    sq_p = -(-sq // q_block) * q_block
    sk_p = -(-sk // k_block) * k_block
    qp = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
    qpos = jnp.pad(q_positions, (0, sq_p - sq), constant_values=-(10 ** 9))
    kpos = jnp.pad(k_positions, (0, sk_p - sk), constant_values=_PAD_K_POS)

    nq = sq_p // q_block
    nk = sk_p // k_block
    # All q blocks ride as a batch dim (dim 1 stays S-sharded under SPMD —
    # a lax.map over q blocks would serialize globally and force gathers);
    # only the KV walk is a scan, with replicated K/V slices as xs.
    qp = qp.reshape(b, nq, q_block, hkv, g, hd)
    kp = kp.reshape(b, nk, k_block, hkv, hd)
    vp = vp.reshape(b, nk, k_block, hkv, hd)
    qpos = qpos.reshape(nq, q_block)
    kpos = kpos.reshape(nk, k_block)

    def kv_step(carry, inputs):
        acc, m, l = carry
        kc, vc, kpc = inputs                      # (B,kb,Hkv,hd), …, (kb,)
        s = jnp.einsum("bnqhgd,bkhd->bhgnqk", qp, kc,
                       preferred_element_type=jnp.float32) * spec.scale
        bias = _mask_bias(spec, qpos.reshape(-1), kpc, causal)
        if sk_p > sk:
            # padded keys sit past every query, which only the causal
            # mask excludes: mask them for every pattern
            bias = jnp.where(kpc[None, :] == _PAD_K_POS, NEG_INF, bias)
        s = s + bias.reshape(nq, q_block, -1)[None, None, None]
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhgnqk,bkhd->bhgnqd", p.astype(vc.dtype), vc,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, hkv, g, nq, q_block, hd), jnp.float32)
    m0 = jnp.full((b, hkv, g, nq, q_block), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, nq, q_block), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        kv_step, (acc0, m0, l0),
        (kp.swapaxes(0, 1), vp.swapaxes(0, 1), kpos))
    out = (acc / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)
    # (B,Hkv,G,nq,qb,hd) → (B, S, H, hd)
    out = out.transpose(0, 3, 4, 1, 2, 5).reshape(b, sq_p, h, hd)
    return out[:, :sq]


def attention_block(params, spec: AttnSpec, x: jax.Array,
                    positions: jax.Array, kv_x: Optional[jax.Array] = None,
                    kv_positions: Optional[jax.Array] = None,
                    causal: bool = True) -> jax.Array:
    """Self (or cross, via kv_x) attention over a full sequence (train/prefill)."""
    b, s, _ = x.shape
    src = x if kv_x is None else kv_x
    src_pos = positions if kv_positions is None else kv_positions
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", src, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, params["wv"])
    # context parallelism: q stays sequence-sharded; K/V are all-gathered
    # (every query block needs the full key range). The optimization
    # barrier pins the projection to the S-sharded x — without it XLA
    # hoists the reshard upstream and all-gathers the (much larger)
    # residual stream instead of the GQA-narrow K/V (§Perf iteration 3).
    k, v = jax.lax.optimization_barrier((k, v))
    k = maybe_constrain(k, "kv_full")
    v = maybe_constrain(v, "kv_full")
    if spec.qk_norm:
        q = layers.rmsnorm(params["q_norm"], q)
        k = layers.rmsnorm(params["k_norm"], k)
    if spec.rope:
        q = layers.apply_rope(q, positions, spec.rope_theta)
        k = layers.apply_rope(k, src_pos, spec.rope_theta)
    out = blockwise_attention(spec, q, k, v, positions, src_pos, causal=causal)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def prefill_attention(params, spec: AttnSpec, x: jax.Array,
                      positions: jax.Array, cache: dict
                      ) -> tuple[jax.Array, dict]:
    """Full-sequence causal self-attention that ALSO writes the decode
    KV cache — exactly the slots S teacher-forced ``decode_attention``
    steps would have filled (slot = pos % L; of positions sharing a slot
    only the latest survives, so only the last L prompt positions are
    written). One O(S) forward replaces O(S) jitted decode calls; parity
    is tested in tests/test_serve_prefill.py.

    For windowed patterns the attention mask bounds the lookback, so a
    prompt longer than the L-slot ring still matches decode; FULL
    attention over a ring smaller than the prompt cannot (decode could
    only see the last L keys) — rejected rather than silently diverging
    (ServeEngine always sizes the cache ≥ prompt + new tokens)."""
    b, s, _ = x.shape
    if spec.kind == "full" and s > cache["k"].shape[1]:
        raise ValueError(
            f"prefill of a {s}-token prompt into a {cache['k'].shape[1]}"
            "-slot full-attention cache is not decode-equivalent; size "
            "the cache to at least the prompt length")
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    k, v = jax.lax.optimization_barrier((k, v))
    k = maybe_constrain(k, "kv_full")
    v = maybe_constrain(v, "kv_full")
    if spec.qk_norm:
        q = layers.rmsnorm(params["q_norm"], q)
        k = layers.rmsnorm(params["k_norm"], k)
    if spec.rope:
        q = layers.apply_rope(q, positions, spec.rope_theta)
        k = layers.apply_rope(k, positions, spec.rope_theta)
    out = blockwise_attention(spec, q, k, v, positions, positions,
                              causal=True)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])

    length = cache["k"].shape[1]
    start = max(0, s - length)
    slots = jnp.arange(start, s) % length
    ck = cache["k"].at[:, slots].set(
        k[:, start:s].astype(cache["k"].dtype))
    cv = cache["v"].at[:, slots].set(
        v[:, start:s].astype(cache["v"].dtype))
    return y, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# decode (single token against a cache)
# ---------------------------------------------------------------------------

def init_kv_cache(batch: int, spec: AttnSpec, max_len: int, dtype):
    """Cache length for windowed/chunked patterns is bounded by the window."""
    length = cache_length(spec, max_len)
    shape = (batch, length, spec.num_kv_heads, spec.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_length(spec: AttnSpec, max_len: int) -> int:
    if spec.kind in ("sliding", "chunked") and spec.window > 0:
        return min(max_len, spec.window)
    return max_len


def decode_attention(params, spec: AttnSpec, x: jax.Array, cache: dict,
                     pos: jax.Array) -> tuple[jax.Array, dict]:
    """One-token decode. x: (B, 1, D); pos: (B,) current absolute position.

    The cache is a rolling buffer of length L=cache_length: slot = pos % L.
    For ``chunked`` the mask drops entries from previous chunks.
    """
    b = x.shape[0]
    length = cache["k"].shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k_new = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v_new = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if spec.qk_norm:
        q = layers.rmsnorm(params["q_norm"], q)
        k_new = layers.rmsnorm(params["k_norm"], k_new)
    if spec.rope:
        q = layers.apply_rope(q, pos[:, None], spec.rope_theta)
        k_new = layers.apply_rope(k_new, pos[:, None], spec.rope_theta)

    slot = (pos % length).astype(jnp.int32)            # (B,)
    onehot = jax.nn.one_hot(slot, length, dtype=cache["k"].dtype)  # (B, L)
    k = cache["k"] * (1.0 - onehot[:, :, None, None]) + \
        onehot[:, :, None, None] * k_new.astype(cache["k"].dtype)
    v = cache["v"] * (1.0 - onehot[:, :, None, None]) + \
        onehot[:, :, None, None] * v_new.astype(cache["v"].dtype)

    # absolute position of every cache slot given current pos
    idx = jnp.arange(length)[None, :]                  # (1, L)
    # slots hold positions p ∈ (pos−L, pos]; slot s holds the largest p≤pos
    # with p % L == s.
    cache_pos = pos[:, None] - ((pos[:, None] - idx) % length)
    valid = cache_pos >= 0
    if spec.kind == "sliding" and spec.window > 0:
        valid &= (pos[:, None] - cache_pos) < spec.window
    elif spec.kind == "chunked" and spec.window > 0:
        valid &= (cache_pos // spec.window) == (pos[:, None] // spec.window)

    hkv = spec.num_kv_heads
    g = spec.num_heads // hkv
    qr = q.reshape(b, 1, hkv, g, spec.head_dim)
    s = jnp.einsum("bqhgd,blhd->bhgql", qr, k,
                   preferred_element_type=jnp.float32) * spec.scale
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgql,blhd->bqhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = out.reshape(b, 1, spec.num_heads, spec.head_dim).astype(x.dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, {"k": k, "v": v}
