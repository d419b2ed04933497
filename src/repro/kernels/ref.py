"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function mirrors its kernel's exact math in straightforward jnp —
tests sweep shapes/dtypes and ``assert_allclose`` kernel vs oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def netes_mixing_ref(adj, w_theta, w_eps, theta, eps, *, sigma: float):
    """out_j = Σ_i a_ji R̃θ_i (θ_i − θ_j) + σ Σ_i a_ji R̃ε_i ε_i."""
    adj = adj.astype(jnp.float32)
    wt = adj * w_theta.astype(jnp.float32)[None, :]
    we = adj * w_eps.astype(jnp.float32)[None, :]
    mixed = wt @ theta.astype(jnp.float32)
    mixed += sigma * (we @ eps.astype(jnp.float32))
    mixed -= wt.sum(axis=1)[:, None] * theta.astype(jnp.float32)
    return mixed.astype(theta.dtype)


def sparse_mixing_ref(neighbor_idx, neighbor_mask, w_theta, w_eps, theta,
                      eps, *, sigma: float):
    """Neighbor-list mixing oracle — same math as ``netes_mixing_ref``
    restricted to the listed edges:

        out_j = Σ_k m_jk R̃θ_{i_jk} (θ_{i_jk} − θ_j)
                + σ Σ_k m_jk R̃ε_{i_jk} ε_{i_jk}.
    """
    idx = neighbor_idx
    mask = neighbor_mask.astype(jnp.float32)
    wt_nb = mask * jnp.take(w_theta.astype(jnp.float32), idx)   # (N, K)
    we_nb = mask * jnp.take(w_eps.astype(jnp.float32), idx)
    th_nb = jnp.take(theta.astype(jnp.float32), idx, axis=0)    # (N, K, P)
    ep_nb = jnp.take(eps.astype(jnp.float32), idx, axis=0)
    mixed = jnp.einsum("jk,jkd->jd", wt_nb, th_nb)
    mixed += sigma * jnp.einsum("jk,jkd->jd", we_nb, ep_nb)
    mixed -= wt_nb.sum(axis=1)[:, None] * theta.astype(jnp.float32)
    return mixed.astype(theta.dtype)


def wire_neighbor_sum_ref(neighbor_idx, neighbor_mask, coeff, codes,
                          scale, edge_mask=None, *, out_dtype=jnp.float32):
    """Decode-then-contract oracle for ``topology_repr.
    weighted_neighbor_sum`` on a sparse topology and a wire payload — one
    (N, K, D) gather and einsum instead of the slot loop.

        out_j = Σ_k m_jk · em_jk · coeff_{i_jk} · (codes · scale)_{i_jk}
    """
    values = codes.astype(jnp.float32) * scale                  # (N, D)
    w = neighbor_mask.astype(jnp.float32) * jnp.take(
        coeff.astype(jnp.float32), neighbor_idx)                # (N, K)
    if edge_mask is not None:
        w = w * edge_mask.astype(jnp.float32)
    v_nb = jnp.take(values, neighbor_idx, axis=0)               # (N, K, D)
    return jnp.einsum("jk,jkd->jd", w, v_nb).astype(out_dtype)


def broadcast_select_ref(codes, scale, do_broadcast, thetas):
    """Decode → broadcast → select oracle for ``netes_fused_mixing.
    fused_broadcast_select``. codes (D,), scale (1,), thetas (N, D)."""
    dec = (codes.astype(jnp.float32) * scale).astype(thetas.dtype)
    return jnp.where(do_broadcast,
                     jnp.broadcast_to(dec[None, :], thetas.shape), thetas)


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                        chunk: int = 0, scale=None):
    """Naive softmax attention. q: (B, Sq, H, hd); k, v: (B, Sk, Hkv, hd)."""
    b, sq, h, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    scale = scale or hd ** -0.5
    qr = q.reshape(b, sq, hkv, g, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qr.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    ok = jnp.ones((sq, sk), bool)
    if causal:
        ok &= qpos >= kpos
    if window:
        ok &= (qpos - kpos) < window
    if chunk:
        ok &= (qpos // chunk) == (kpos // chunk)
    s = jnp.where(ok[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def mamba_scan_ref(decay, drive):
    """h_t = decay_t ⊙ h_{t−1} + drive_t, over axis 1 (time).
    decay, drive: (B, S, D, N) fp32."""
    def step(h, inp):
        d, x = inp
        h = d * h + x
        return h, h

    dec = decay.swapaxes(0, 1)
    drv = drive.swapaxes(0, 1)
    _, hs = jax.lax.scan(step, jnp.zeros_like(decay[:, 0]), (dec, drv))
    return hs.swapaxes(0, 1)


def rwkv6_wkv_ref(r, k, v, w, u, s0=None):
    """WKV-6 recurrence (matches models.rwkv6.wkv6_scan_ref).
    r,k,v,w: (B, S, H, n); u: (H, n). Returns (out fp32, final state)."""
    b, s, h, n = r.shape
    if s0 is None:
        s0 = jnp.zeros((b, h, n, n), jnp.float32)

    def step(state, inp):
        rt, kt, vt, wt = inp
        kv = kt[..., :, None] * vt[..., None, :]
        out = jnp.einsum("bhn,bhnm->bhm", rt,
                         u[None, :, :, None] * kv + state)
        state = wt[..., :, None] * state + kv
        return state, out

    xs = tuple(t.swapaxes(0, 1).astype(jnp.float32) for t in (r, k, v, w))
    s_fin, outs = jax.lax.scan(step, s0, xs)
    return outs.swapaxes(0, 1), s_fin


def moe_topk_ref(logits, k):
    """Top-k gating: returns (normalized gate values (T, k), expert ids)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    vals, ids = jax.lax.top_k(probs, k)
    vals = vals / jnp.maximum(vals.sum(axis=-1, keepdims=True), 1e-9)
    return vals, ids


def centered_rank_ref(x):
    flat = x.reshape(-1)
    ranks = jnp.argsort(jnp.argsort(flat))
    return (ranks.astype(jnp.float32) / (flat.shape[0] - 1) - 0.5).reshape(
        x.shape)
