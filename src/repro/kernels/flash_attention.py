"""Pallas TPU kernel: blocked flash attention with GQA + sliding-window /
chunked-local masks (the prefill/train attention hot loop).

TPU mapping (VMEM tiling):
  grid = (batch·kv_heads, Sq/BLOCK_Q) — one program per query tile per
  (batch, kv-head); the inner loop walks KV tiles with online softmax.
  BLOCK_Q × head_dim and BLOCK_K × head_dim tiles are MXU-aligned
  (block sizes multiples of 128). The GQA group dim (q heads per kv head)
  rides inside the q tile: (BLOCK_Q, G·hd) reshaped — scores per group are
  (G, BLOCK_Q, BLOCK_K) fp32 in VREGs.

Window/chunk masks are applied via position arithmetic inside the kernel —
masked-out KV tiles still stream (structural skipping is a §Perf item;
see EXPERIMENTS.md).

Validated with interpret=True against ``ref.flash_attention_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, causal: bool,
                  window: int, chunk: int, block_q: int, block_k: int,
                  seq_k: int, seq_k_valid: int):
    qi = pl.program_id(1)
    q = q_ref[...][0].astype(jnp.float32)       # (block_q, G, hd)
    g, hd = q.shape[1], q.shape[2]
    q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)

    def body(ki, carry):
        acc, m, l = carry
        k_tile = k_ref[0, pl.ds(ki * block_k, block_k)].astype(
            jnp.float32)                        # (block_k, hd)
        v_tile = v_ref[0, pl.ds(ki * block_k, block_k)].astype(
            jnp.float32)                        # (block_k, hd)
        k_pos = ki * block_k + jax.lax.iota(jnp.int32, block_k)
        s = jnp.einsum("qgd,kd->gqk", q, k_tile,
                       preferred_element_type=jnp.float32) * scale
        ok = (k_pos < seq_k_valid)[None, :] * jnp.ones(
            (block_q, block_k), bool)                 # mask padded keys
        diff = q_pos[:, None] - k_pos[None, :]
        if causal:
            ok &= diff >= 0
        if window:
            ok &= diff < window
        if chunk:
            ok &= (q_pos[:, None] // chunk) == (k_pos[None, :] // chunk)
        s = jnp.where(ok[None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("gqk,kd->gqd", p, v_tile,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((g, block_q, hd), jnp.float32)
    m0 = jnp.full((g, block_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((g, block_q), jnp.float32)
    n_k = seq_k // block_k
    acc, m, l = jax.lax.fori_loop(0, n_k, body, (acc0, m0, l0))
    out = acc / jnp.maximum(l[..., None], 1e-30)        # (g, block_q, hd)
    o_ref[...] = out.swapaxes(0, 1).astype(o_ref.dtype)[None]


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "chunk", "block_q", "block_k", "interpret", "scale"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0, chunk: int = 0,
                    scale=None, block_q: int = 128, block_k: int = 128,
                    interpret: bool = True) -> jax.Array:
    """q: (B, Sq, H, hd); k, v: (B, Sk, Hkv, hd) → (B, Sq, H, hd).

    Sq/Sk padded to block multiples internally; H = G · Hkv.
    """
    b, sq, h, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    scale = scale or hd ** -0.5
    sq_p = -(-sq // block_q) * block_q
    sk_p = -(-sk // block_k) * block_k
    qp = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))

    # layout: (B, Hkv, S, [G,] hd) so each grid program sees one (b, kv-head)
    qg = qp.reshape(b, sq_p, hkv, g, hd).transpose(0, 2, 1, 3, 4)
    kg = kp.transpose(0, 2, 1, 3)
    vg = vp.transpose(0, 2, 1, 3)
    qf = qg.reshape(b * hkv, sq_p, g, hd)
    kf = kg.reshape(b * hkv, sk_p, hd)
    vf = vg.reshape(b * hkv, sk_p, hd)

    grid = (b * hkv, sq_p // block_q)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, chunk=chunk, block_q=block_q,
                          block_k=block_k, seq_k=sk_p, seq_k_valid=sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, g, hd), lambda bh, qi: (bh, qi, 0, 0)),
            pl.BlockSpec((1, sk_p, hd), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, sk_p, hd), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, g, hd),
                               lambda bh, qi: (bh, qi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, sq_p, g, hd), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(b, hkv, sq_p, g, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, sq_p, h, hd)[:, :sq]
