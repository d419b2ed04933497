"""Pallas TPU kernel: Mamba selective-scan recurrence.

    h_t = decay_t ⊙ h_{t−1} + drive_t        (per channel d, state n)

TPU mapping: grid = (B, d_inner/TILE_D) — one program per (batch, channel
tile). The (TILE_D, N_state) hidden state lives in VREG/VMEM across the
whole sequence; each step streams one (TILE_D, N) slab of decay/drive from
VMEM and writes one slab of h. Channel tiles are independent ⇒ the grid
parallelizes over cores; the S loop is inherently sequential (recurrence).
A production variant would double-buffer S-chunks HBM→VMEM; interpret mode
validates the math against ``ref.mamba_scan_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_D = 256


def _scan_kernel(decay_ref, drive_ref, h_ref, *, seq: int):
    td, n = decay_ref.shape[2], decay_ref.shape[3]

    def body(t, h):
        h = decay_ref[0, t] * h + drive_ref[0, t]
        h_ref[0, t] = h
        return h

    h0 = jnp.zeros((td, n), jnp.float32)
    jax.lax.fori_loop(0, seq, body, h0)


@functools.partial(jax.jit, static_argnames=("tile_d", "interpret"))
def mamba_scan(decay: jax.Array, drive: jax.Array, *, tile_d: int = TILE_D,
               interpret: bool = True) -> jax.Array:
    """decay, drive: (B, S, D, N) fp32 → h: (B, S, D, N)."""
    b, s, d, n = decay.shape
    tile_d = min(tile_d, d)
    d_pad = -(-d // tile_d) * tile_d
    dec = jnp.pad(decay, ((0, 0), (0, 0), (0, d_pad - d), (0, 0)))
    drv = jnp.pad(drive, ((0, 0), (0, 0), (0, d_pad - d), (0, 0)))

    grid = (b, d_pad // tile_d)
    h = pl.pallas_call(
        functools.partial(_scan_kernel, seq=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, s, tile_d, n), lambda bi, di: (bi, 0, di, 0)),
            pl.BlockSpec((1, s, tile_d, n), lambda bi, di: (bi, 0, di, 0)),
        ],
        out_specs=pl.BlockSpec((1, s, tile_d, n),
                               lambda bi, di: (bi, 0, di, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, d_pad, n), jnp.float32),
        interpret=interpret,
    )(dec.astype(jnp.float32), drv.astype(jnp.float32))
    return h[:, :, :d]
