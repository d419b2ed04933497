"""Mamba selective-SSM block (Jamba's SSM half, arXiv:2403.19887 cites
Mamba-1 style blocks).

Prefill/train uses an associative scan over the sequence (O(S log S) depth,
O(S) work); decode is a single recurrent state update.

State-space recurrence (per channel c, state n):
    h_t = exp(Δ_t · A)  ⊙ h_{t−1} + Δ_t · B_t · x_t
    y_t = C_t · h_t + D ⊙ x_t
with input-dependent Δ, B, C (the "selective" part).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from . import layers


@dataclasses.dataclass(frozen=True)
class MambaSpec:
    d_model: int
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0  # 0 ⇒ ceil(d_model / 16)

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def rank(self) -> int:
        return self.dt_rank or -(-self.d_model // 16)


def mamba_init(key: jax.Array, spec: MambaSpec, dtype):
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    di, ds, r = spec.d_inner, spec.d_state, spec.rank
    a = jnp.tile(jnp.arange(1, ds + 1, dtype=jnp.float32)[None, :], (di, 1))
    k6a, k6b = jax.random.split(k6)
    return {
        # x/z projections kept as separate leaves: a fused (D, 2·di) weight
        # would make the x/z split slice across the model-sharded di dim
        # (resharding); separate leaves shard cleanly.
        "in_x": layers.dense_init(k6a, (spec.d_model, di), dtype),
        "in_z": layers.dense_init(k6b, (spec.d_model, di), dtype),
        "conv_w": layers.dense_init(k2, (spec.d_conv, di), dtype, scale=0.5),
        "conv_b": jnp.zeros((di,), dtype=dtype),
        "x_proj": layers.dense_init(k3, (di, r + 2 * ds), dtype),
        "dt_proj": layers.dense_init(k4, (r, di), dtype),
        "dt_bias": (jnp.log(jnp.expm1(0.01 * jnp.ones((di,))))).astype(jnp.float32),
        "A_log": jnp.log(a),                       # (di, ds) fp32
        "D": jnp.ones((di,), dtype=jnp.float32),
        "out_proj": layers.dense_init(k5, (di, spec.d_model), dtype),
    }


def _ssm_inputs(params, spec: MambaSpec, u: jax.Array):
    """x/z projections from the residual stream u: (B, S, D)."""
    return u @ params["in_x"], u @ params["in_z"]


def _selective_terms(params, spec: MambaSpec, x: jax.Array):
    """x: (B, S, di) post-conv. Returns decay (B,S,di,ds), drive (B,S,di,ds),
    C (B,S,ds)."""
    r, ds = spec.rank, spec.d_state
    proj = x @ params["x_proj"]                            # (B,S,r+2ds)
    dt = proj[..., :r] @ params["dt_proj"]                 # (B,S,di)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    b = proj[..., r:r + ds].astype(jnp.float32)            # (B,S,ds)
    c = proj[..., r + ds:].astype(jnp.float32)             # (B,S,ds)
    a = -jnp.exp(params["A_log"])                          # (di,ds)
    decay = jnp.exp(dt[..., None] * a[None, None])         # (B,S,di,ds)
    drive = dt[..., None] * b[..., None, :] * x.astype(jnp.float32)[..., None]
    return decay, drive, c


def _causal_conv(params, spec: MambaSpec, x: jax.Array) -> jax.Array:
    """Depthwise causal conv over S. x: (B, S, di)."""
    w = params["conv_w"]                                   # (K, di)
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None] for i in range(k))
    return jax.nn.silu(out + params["conv_b"])


def mamba_scan_ref(decay: jax.Array, drive: jax.Array) -> jax.Array:
    """Associative scan of h_t = decay_t ⊙ h_{t−1} + drive_t over axis 1.

    decay, drive: (B, S, di, ds) fp32 → h: (B, S, di, ds).
    """
    def combine(a, b):
        (da, xa), (db, xb) = a, b
        return da * db, xb + db * xa

    (_, h) = jax.lax.associative_scan(combine, (decay, drive), axis=1)
    return h


def mamba_block(params, spec: MambaSpec, x: jax.Array,
                chunk: int = 1024) -> jax.Array:
    """Full-sequence (train/prefill). x: (B, S, D) → (B, S, D).

    Sequences longer than ``chunk`` are processed as a sequential
    ``lax.scan`` over chunks carrying the SSM state, with a parallel
    associative scan *within* each chunk — the (B, S, di, ds) state tensor
    is never materialized for the full sequence (it would be ~34 GB/slice at
    32k prefill for jamba).
    """
    b, s, _ = x.shape
    xin, z = _ssm_inputs(params, spec, x)
    xc = _causal_conv(params, spec, xin)                   # (B,S,di)

    if s <= chunk:
        decay, drive, c = _selective_terms(params, spec, xc)
        h = mamba_scan_ref(decay, drive)                   # (B,S,di,ds)
        y = jnp.einsum("bsdn,bsn->bsd", h, c)              # (B,S,di)
    else:
        assert s % chunk == 0, f"seq {s} not divisible by chunk {chunk}"
        nc = s // chunk
        xcc = xc.reshape(b, nc, chunk, -1).swapaxes(0, 1)  # (nc,B,chunk,di)
        h0 = jnp.zeros((b, spec.d_inner, spec.d_state), jnp.float32)

        def body(h_prev, xc_chunk):
            decay, drive, c = _selective_terms(params, spec, xc_chunk)

            def combine(u, v):
                (da, xa), (db, xb) = u, v
                return da * db, xb + db * xa

            cumdec, hloc = jax.lax.associative_scan(
                combine, (decay, drive), axis=1)
            h = hloc + cumdec * h_prev[:, None]            # (B,chunk,di,ds)
            y = jnp.einsum("bsdn,bsn->bsd", h, c)
            return h[:, -1], y

        _, ys = jax.lax.scan(body, h0, xcc)                # (nc,B,chunk,di)
        y = ys.swapaxes(0, 1).reshape(b, s, -1)

    y = y + params["D"][None, None] * xc.astype(jnp.float32)
    y = y.astype(x.dtype) * jax.nn.silu(z)
    return y @ params["out_proj"]


def mamba_prefill(params, spec: MambaSpec, x: jax.Array, cache: dict):
    """Full-sequence block that ALSO returns the decode cache — the
    final SSM state and conv ring exactly as S teacher-forced
    ``mamba_decode`` steps would have left them (the ring holds the
    last ``d_conv − 1`` pre-conv inputs, zero-padded for short
    prompts). Serve prompts fit one chunk, so the direct associative
    scan suffices (``mamba_block``'s chunked path is a train/long-
    prefill concern)."""
    b, s, _ = x.shape
    xin, z = _ssm_inputs(params, spec, x)                  # (B,S,di)
    xc = _causal_conv(params, spec, xin)
    decay, drive, c = _selective_terms(params, spec, xc)
    h = mamba_scan_ref(decay, drive)                       # (B,S,di,ds)
    y = jnp.einsum("bsdn,bsn->bsd", h, c)
    y = y + params["D"][None, None] * xc.astype(jnp.float32)
    y = y.astype(x.dtype) * jax.nn.silu(z)
    out = y @ params["out_proj"]

    k = spec.d_conv - 1
    buf = jnp.concatenate(
        [jnp.zeros((b, k, spec.d_inner), cache["conv"].dtype),
         xin.astype(cache["conv"].dtype)], axis=1)[:, s:s + k]
    return out, {"h": h[:, -1], "conv": buf}


def init_mamba_cache(batch: int, spec: MambaSpec, dtype):
    return {
        "h": jnp.zeros((batch, spec.d_inner, spec.d_state), jnp.float32),
        "conv": jnp.zeros((batch, spec.d_conv - 1, spec.d_inner), dtype),
    }


def mamba_decode(params, spec: MambaSpec, x: jax.Array, cache: dict):
    """One-token step. x: (B, 1, D)."""
    xin, z = _ssm_inputs(params, spec, x)                  # (B,1,di)
    # conv over rolling buffer
    buf = jnp.concatenate([cache["conv"], xin], axis=1)    # (B,K,di)
    w = params["conv_w"]
    conv = (buf * w[None]).sum(axis=1, keepdims=True)
    xc = jax.nn.silu(conv + params["conv_b"])              # (B,1,di)
    decay, drive, c = _selective_terms(params, spec, xc)
    h = decay[:, 0] * cache["h"] + drive[:, 0]             # (B,di,ds)
    y = jnp.einsum("bdn,bn->bd", h, c[:, 0])[:, None]
    y = y + params["D"][None, None] * xc.astype(jnp.float32)
    y = y.astype(x.dtype) * jax.nn.silu(z)
    out = y @ params["out_proj"]
    return out, {"h": h, "conv": buf[:, 1:]}
