"""Pure-jnp oracles that tests compare the live paths against.

Each function states its contraction in straightforward jnp: the Eq. 3
mixing (dense and neighbor-list) that ``core.netes.mixing_update`` runs,
the wire-payload contraction and broadcast select of
``kernels/netes_fused_mixing``, and naive softmax attention for
``models.attention.blockwise_attention``.
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
