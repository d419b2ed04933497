"""RWKV-6 "Finch" block (arXiv:2404.05892): attention-free time-mix with
data-dependent decay, multi-head matrix-valued state.

Per head h (head_dim = n), per step t:
    S_t = diag(w_t) · S_{t−1} + k_tᵀ v_t          (S: (n, n) state)
    o_t = r_t · (diag(u) · k_tᵀ v_t + S_{t−1})
with w_t = exp(−exp(decay_t)) data-dependent per channel (the Finch change
vs RWKV-5's static decay), u the "bonus" for the current token.

Prefill/train runs a chunked lax.scan carrying S (the WKV state is O(H·n²)
— independent of sequence length, hence `long_500k` eligibility); decode is
a single state update.

Token-shift (the RWKV "half-channel looks at t−1") is implemented with
jnp.pad/shift; the LoRA-style low-rank adapters produce the per-token
mix coefficients as in the Finch paper (rank 32 for w, 64 elsewhere,
reduced proportionally for small test models).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from . import layers


@dataclasses.dataclass(frozen=True)
class RWKV6Spec:
    d_model: int
    num_heads: int
    lora_rank_decay: int = 0   # 0 ⇒ max(16, d_model // 128)
    lora_rank_mix: int = 0     # 0 ⇒ max(16, d_model // 64)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    @property
    def rank_w(self) -> int:
        return self.lora_rank_decay or max(16, self.d_model // 128)

    @property
    def rank_mix(self) -> int:
        return self.lora_rank_mix or max(16, self.d_model // 64)


def _lora_init(key, d, rank, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "a": layers.dense_init(k1, (d, rank), dtype, scale=0.01),
        "b": layers.dense_init(k2, (rank, d), dtype, scale=0.01),
        "bias": jnp.zeros((d,), dtype=jnp.float32),
    }


def _lora(p, x):
    return (jnp.tanh(x @ p["a"]) @ p["b"]).astype(jnp.float32) + p["bias"]


def rwkv6_init(key: jax.Array, spec: RWKV6Spec, dtype):
    d = spec.d_model
    keys = jax.random.split(key, 10)
    return {
        # token-shift mix coefficients (static part) per r/k/v/w/g
        "mix": 0.5 * jnp.ones((5, d), dtype=dtype),
        "mix_lora": _lora_init(keys[0], d, spec.rank_mix, dtype),
        "wr": layers.dense_init(keys[1], (d, d), dtype),
        "wk": layers.dense_init(keys[2], (d, d), dtype),
        "wv": layers.dense_init(keys[3], (d, d), dtype),
        "wg": layers.dense_init(keys[4], (d, d), dtype),
        "wo": layers.dense_init(keys[5], (d, d), dtype),
        "decay_lora": _lora_init(keys[6], d, spec.rank_w, dtype),
        "decay_base": -6.0 * jnp.ones((d,), dtype=jnp.float32),
        "bonus_u": 0.5 * jnp.ones((spec.num_heads, spec.head_dim),
                                  dtype=jnp.float32),
        "ln_x": layers.layernorm_init(d, dtype),
    }


def _time_shift(x: jax.Array, last: jax.Array | None = None) -> jax.Array:
    """x shifted one step back along S; first step sees ``last`` (or zeros)."""
    if last is None:
        last = jnp.zeros_like(x[:, :1])
    return jnp.concatenate([last, x[:, :-1]], axis=1)


def _mix_inputs(params, x: jax.Array, x_prev: jax.Array):
    """Data-dependent token-shift mixing (Finch §3.1). Returns r,k,v,g,w
    pre-projection inputs, each (B,S,D)."""
    delta = x_prev - x
    base = x + delta * params["mix"][4][None, None].astype(x.dtype)
    dyn = _lora(params["mix_lora"], base).astype(x.dtype)   # (B,S,D)
    outs = []
    for i in range(5):
        mi = params["mix"][i][None, None].astype(x.dtype)
        outs.append(x + delta * (mi + dyn * 0.1))
    return outs  # xr, xk, xv, xw, xg


def wkv6_scan_ref(r, k, v, w, u, s0=None):
    """Reference WKV-6 recurrence via lax.scan over time.

    r,k,v: (B,S,H,n); w: (B,S,H,n) decay in (0,1); u: (H,n) bonus.
    Returns (out (B,S,H,n) fp32, s_final (B,H,n,n)).
    """
    b, s, h, n = r.shape
    if s0 is None:
        s0 = jnp.zeros((b, h, n, n), jnp.float32)

    def step(state, inp):
        rt, kt, vt, wt = inp                               # (B,H,n)
        kv = kt[..., :, None] * vt[..., None, :]           # (B,H,n,n)
        out = jnp.einsum("bhn,bhnm->bhm", rt,
                         u[None, :, :, None] * kv + state)
        state = wt[..., :, None] * state + kv
        return state, out

    xs = tuple(t.swapaxes(0, 1).astype(jnp.float32) for t in (r, k, v, w))
    s_fin, outs = jax.lax.scan(step, s0, xs)
    return outs.swapaxes(0, 1), s_fin


def wkv6_chunked(r, k, v, w, u, s0=None, chunk: int = 128):
    """Chunked-parallel WKV-6: within a chunk, the contribution of in-chunk
    keys is a masked matmul (parallel, MXU-friendly); the carried state
    enters through per-position cumulative decays. O(S·n²/chunk) state work
    + O(S·chunk·n) matmul work — the standard linear-attention chunking.
    """
    b, s, h, n = r.shape
    if s0 is None:
        s0 = jnp.zeros((b, h, n, n), jnp.float32)
    if s % chunk != 0:
        return wkv6_scan_ref(r, k, v, w, u, s0)
    nc = s // chunk
    rc, kc, vc, wc = (t.reshape(b, nc, chunk, h, n).swapaxes(0, 1)
                      .astype(jnp.float32) for t in (r, k, v, w))

    # causal (strict lower-triangular) mask for in-chunk interactions
    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), k=-1)

    def body(state, inp):
        rt, kt, vt, wt = inp                               # (B,C,H,n)
        logw = jnp.log(jnp.maximum(wt, 1e-38))             # (B,C,H,n)
        cum = jnp.cumsum(logw, axis=1)                     # Π_{τ≤t} w_τ (log)
        dec_in = jnp.exp(cum)                              # decay from chunk start
        # state contribution: r_t · (Π_{τ<t} w) · S_in ; Π_{τ<t} = cum/w_t
        dec_prev = jnp.exp(cum - logw)
        out_state = jnp.einsum("bchn,bhnm->bchm", rt * dec_prev, state)
        # in-chunk contribution: Σ_{j<t} r_t ⊙ (Π_{j<τ≤t−1}? w) ... exact
        # per-channel decay between j and t−1 is exp(cum_{t−1} − cum_j);
        # using cum_t − logw_t − cum_j:
        # score[b,h,t,j] over key channel n must keep per-channel decays —
        # do it as (rt·dec_prev_t) · (k_j / dec_in_j)ᵀ, valid while the
        # ratio stays finite (we clamp logw so dec_in ≥ exp(−60·chunk)… for
        # robustness normalize by per-chunk min).
        k_scaled = kt / jnp.maximum(dec_in, 1e-30)
        att = jnp.einsum("bchn,bdhn->bhcd", rt * dec_prev, k_scaled)
        att = att * tri[None, None]
        out_intra = jnp.einsum("bhcd,bdhm->bchm", att, vt)
        # bonus (current token) term
        out_bonus = (rt * kt * u[None, None]).sum(-1, keepdims=True) * vt
        out = out_state + out_intra + out_bonus
        # state update: S_out = (Π_chunk w) S_in + Σ_j (Π_{j<τ} w) k_j v_jᵀ
        dec_all = jnp.exp(cum[:, -1])                      # (B,H,n)
        k_dec = kt * jnp.exp(cum[:, -1:] - cum)            # Π_{j<τ≤C} w
        kv = jnp.einsum("bchn,bchm->bhnm", k_dec, vt)
        state = dec_all[..., None] * state + kv
        return state, out

    s_fin, outs = jax.lax.scan(body, s0, (rc, kc, vc, wc))
    out = outs.swapaxes(0, 1).reshape(b, s, h, n)
    return out, s_fin


def rwkv6_block(params, spec: RWKV6Spec, x: jax.Array,
                chunk: int = 128) -> jax.Array:
    """Time-mix block, full sequence. x: (B, S, D) → (B, S, D)."""
    b, s, d = x.shape
    h, n = spec.num_heads, spec.head_dim
    xp = _time_shift(x)
    xr, xk, xv, xw, xg = _mix_inputs(params, x, xp)
    r = (xr @ params["wr"]).reshape(b, s, h, n)
    k = (xk @ params["wk"]).reshape(b, s, h, n)
    v = (xv @ params["wv"]).reshape(b, s, h, n)
    g = jax.nn.silu(xg @ params["wg"])
    decay = params["decay_base"] + _lora(params["decay_lora"], xw)
    w = jnp.exp(-jnp.exp(decay)).reshape(b, s, h, n)       # (0,1)
    out, _ = wkv6_chunked(r, k, v, w, params["bonus_u"], chunk=chunk)
    out = out.reshape(b, s, d).astype(x.dtype)
    out = layers.layernorm(params["ln_x"], out)
    return (out * g) @ params["wo"]


def rwkv6_prefill(params, spec: RWKV6Spec, x: jax.Array, cache: dict,
                  chunk: int = 128):
    """Full-sequence time-mix block that ALSO returns the decode cache —
    the final WKV state and last block input exactly as S teacher-forced
    ``rwkv6_decode`` steps would have left them (the initial
    ``cache['x_prev']``/``cache['s']`` seed the shift and recurrence, so
    a zero-initialized cache matches ``rwkv6_block`` bit-for-bit)."""
    b, s, d = x.shape
    h, n = spec.num_heads, spec.head_dim
    xp = _time_shift(x, cache["x_prev"])
    xr, xk, xv, xw, xg = _mix_inputs(params, x, xp)
    r = (xr @ params["wr"]).reshape(b, s, h, n)
    k = (xk @ params["wk"]).reshape(b, s, h, n)
    v = (xv @ params["wv"]).reshape(b, s, h, n)
    g = jax.nn.silu(xg @ params["wg"])
    decay = params["decay_base"] + _lora(params["decay_lora"], xw)
    w = jnp.exp(-jnp.exp(decay)).reshape(b, s, h, n)
    out, s_fin = wkv6_chunked(r, k, v, w, params["bonus_u"],
                              s0=cache["s"], chunk=chunk)
    out = out.reshape(b, s, d).astype(x.dtype)
    out = layers.layernorm(params["ln_x"], out)
    y = (out * g) @ params["wo"]
    return y, {"s": s_fin, "x_prev": x[:, -1:].astype(
        cache["x_prev"].dtype)}


def init_rwkv_cache(batch: int, spec: RWKV6Spec, dtype):
    return {
        "s": jnp.zeros((batch, spec.num_heads, spec.head_dim, spec.head_dim),
                       jnp.float32),
        "x_prev": jnp.zeros((batch, 1, spec.d_model), dtype),
    }


def rwkv6_decode(params, spec: RWKV6Spec, x: jax.Array, cache: dict):
    """One-token step. x: (B, 1, D)."""
    b, _, d = x.shape
    h, n = spec.num_heads, spec.head_dim
    xp = cache["x_prev"]
    xr, xk, xv, xw, xg = _mix_inputs(params, x, xp)
    r = (xr @ params["wr"]).reshape(b, 1, h, n)
    k = (xk @ params["wk"]).reshape(b, 1, h, n)
    v = (xv @ params["wv"]).reshape(b, 1, h, n)
    g = jax.nn.silu(xg @ params["wg"])
    decay = params["decay_base"] + _lora(params["decay_lora"], xw)
    w = jnp.exp(-jnp.exp(decay)).reshape(b, 1, h, n)
    out, s_new = wkv6_scan_ref(r, k, v, w, params["bonus_u"], cache["s"])
    out = out.reshape(b, 1, d).astype(x.dtype)
    out = layers.layernorm(params["ln_x"], out)
    y = (out * g) @ params["wo"]
    return y, {"s": s_new, "x_prev": x}


# channel-mix (RWKV's FFN variant with token shift + squared relu)

def rwkv6_channel_init(key: jax.Array, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "mix_k": 0.5 * jnp.ones((d_model,), dtype=dtype),
        "mix_r": 0.5 * jnp.ones((d_model,), dtype=dtype),
        "wk": layers.dense_init(k1, (d_model, d_ff), dtype),
        "wv": layers.dense_init(k2, (d_ff, d_model), dtype),
        "wr": layers.dense_init(k3, (d_model, d_model), dtype),
    }


def rwkv6_channel(params, x: jax.Array, x_prev: jax.Array | None = None):
    xp = _time_shift(x, x_prev)
    xk = x + (xp - x) * params["mix_k"][None, None]
    xr = x + (xp - x) * params["mix_r"][None, None]
    kk = jnp.square(jax.nn.relu(xk @ params["wk"]))
    return jax.nn.sigmoid(xr @ params["wr"]) * (kk @ params["wv"])
