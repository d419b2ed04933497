"""Pallas TPU kernel: RWKV-6 WKV recurrence with data-dependent decay.

    out_t = r_t · (diag(u) · k_tᵀ v_t + S_{t−1})
    S_t   = diag(w_t) · S_{t−1} + k_tᵀ v_t

TPU mapping: grid = (B, H) — one program per (batch, head). The (n, n)
state matrix stays VMEM/VREG-resident across the sequence; each step
streams r/k/v/w rows (n,) and writes one out row. Heads are independent ⇒
grid-parallel; S is sequential (recurrence). Validated in interpret mode
against ``ref.rwkv6_wkv_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *,
                seq: int):
    n = r_ref.shape[3]
    u = u_ref[...][0]                              # (n,)

    def body(t, state):
        rt = r_ref[0, t, 0]                         # (n,)
        kt = k_ref[0, t, 0]
        vt = v_ref[0, t, 0]
        wt = w_ref[0, t, 0]
        kv = kt[:, None] * vt[None, :]                 # (n, n)
        out = rt @ (u[:, None] * kv + state)           # (n,)
        o_ref[0, t, 0] = out
        return wt[:, None] * state + kv

    s_fin = jax.lax.fori_loop(0, seq, body, jnp.zeros((n, n), jnp.float32))
    s_ref[...] = s_fin[None, None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def rwkv6_wkv(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
              u: jax.Array, *, interpret: bool = True):
    """r,k,v,w: (B, S, H, n); u: (H, n) → (out (B,S,H,n) f32,
    final state (B,H,n,n) f32)."""
    b, s, h, n = r.shape
    args = [t.astype(jnp.float32) for t in (r, k, v, w)]
    grid = (b, h)
    out, s_fin = pl.pallas_call(
        functools.partial(_wkv_kernel, seq=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, s, 1, n), lambda bi, hi: (bi, 0, hi, 0)),
            pl.BlockSpec((1, s, 1, n), lambda bi, hi: (bi, 0, hi, 0)),
            pl.BlockSpec((1, s, 1, n), lambda bi, hi: (bi, 0, hi, 0)),
            pl.BlockSpec((1, s, 1, n), lambda bi, hi: (bi, 0, hi, 0)),
            pl.BlockSpec((1, n), lambda bi, hi: (hi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, s, 1, n), lambda bi, hi: (bi, 0, hi, 0)),
            pl.BlockSpec((1, 1, n, n), lambda bi, hi: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h, n), jnp.float32),
            jax.ShapeDtypeStruct((b, h, n, n), jnp.float32),
        ],
        interpret=interpret,
    )(*args, u.astype(jnp.float32))
    return out, s_fin
