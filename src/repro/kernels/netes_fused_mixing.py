"""Fused broadcast-best select over a quantized wire payload (DESIGN.md §12).

``fused_broadcast_select`` is ``where(do_broadcast, decode(codes, scale),
θ)`` in one pass: every agent adopts the int8 broadcast-best payload
without a decoded (D,) + broadcast (N, D) intermediate round trip. It has
two lowerings. ``backend="auto"`` picks by platform: the Pallas kernel,
compiled, on TPU; the XLA lowering elsewhere. An explicit
``backend="pallas"`` off TPU runs the same kernel program in interpret
mode — how the CPU tests check it against its oracle,
``ref.broadcast_select_ref``.

The neighbor contraction has no kernel here: ``topology_repr.
weighted_neighbor_sum`` decodes a wire payload once and runs the f32
path. A Pallas gather kernel with an in-kernel row gather is refused by
the TPU compiler (``dynamic_slice`` has no Mosaic lowering), so one
needs its own design and a chip-measured cell on each side.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import wire_format

# Broadcast-select block: (TILE_N, TILE_D) f32 is 1 MiB, so the θ input
# and output, double-buffered, stay well inside v5e's scoped VMEM at any
# N (a block spanning all of N is refused at N=16384, D=4481).
TILE_N = 512
TILE_D = 512

BACKENDS = ("pallas", "xla")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve_backend(backend: str) -> str:
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "xla"
    if backend not in BACKENDS:
        raise ValueError(f"unknown fused backend {backend!r}; "
                         f"available: {BACKENDS + ('auto',)}")
    return backend


def _resolve_interpret(interpret) -> bool:
    # Pallas compiles on TPU and interprets everywhere else.
    return not _on_tpu() if interpret is None else bool(interpret)


# ---------------------------------------------------------------------------
# fused broadcast-best select
# ---------------------------------------------------------------------------

def _broadcast_select_kernel(flag_ref, scale_ref, codes_ref, theta_ref,
                             out_ref):
    flag = flag_ref[0, 0]
    theta = theta_ref[...]                   # (TILE_N, TILE_D)
    dec = wire_format.decode(codes_ref[...], scale_ref[...])  # (1, TILE_D)
    out_ref[...] = jnp.where(flag != 0, dec.astype(theta.dtype), theta)


@functools.partial(jax.jit, static_argnames=("interpret", "backend"))
def fused_broadcast_select(codes: jax.Array, scale: jax.Array,
                           do_broadcast: jax.Array, thetas: jax.Array, *,
                           interpret=None,
                           backend: str = "auto") -> jax.Array:
    """``where(do_broadcast, decode(codes, scale), thetas)`` in one pass —
    every agent adopts the quantized broadcast-best payload without a
    decoded (D,) + broadcast (N, D) intermediate round-trip.

    codes (D,) int8; scale (1,) f32; do_broadcast scalar bool;
    thetas (N, D). Returns (N, D) in thetas' dtype. The Pallas grid tiles
    both axes; a ragged edge block is masked by Pallas, so nothing is
    padded or cropped around the kernel.
    """
    backend = _resolve_backend(backend)
    if backend == "xla":
        dec = wire_format.decode(codes, scale, thetas.dtype)
        return jnp.where(do_broadcast, dec[None, :], thetas)

    n, d = thetas.shape
    # a block either spans its whole axis or is (8, 128)-aligned
    tn = min(n, TILE_N)
    td = min(d, TILE_D)
    flag = do_broadcast.astype(jnp.int32).reshape(1, 1)
    return pl.pallas_call(
        _broadcast_select_kernel,
        grid=(pl.cdiv(n, tn), pl.cdiv(d, td)),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),       # flag
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),       # scale
            pl.BlockSpec((1, td), lambda i, j: (0, j)),      # codes slab
            pl.BlockSpec((tn, td), lambda i, j: (i, j)),     # θ block
        ],
        out_specs=pl.BlockSpec((tn, td), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, d), thetas.dtype),
        interpret=_resolve_interpret(interpret),
    )(flag, scale.reshape(1, 1).astype(jnp.float32), codes.reshape(1, d),
      thetas)


# ---------------------------------------------------------------------------
# static-analysis registry hook (repro.analysis — DESIGN.md §14)
# ---------------------------------------------------------------------------

def analysis_entry_points():
    """Contract-linter entry point for the broadcast select in its Pallas
    lowering, interpreted."""
    from repro.analysis.registry import EntryPoint

    def build_broadcast_select():
        d, n = 16, 8
        fn = functools.partial(fused_broadcast_select, interpret=True,
                               backend="pallas")
        args = (jnp.zeros((d,), jnp.int8), jnp.ones((1,), jnp.float32),
                jnp.array(True), jnp.ones((n, d), jnp.float32))
        return fn, args, {}

    return (
        EntryPoint(name="kernels.fused_broadcast_select",
                   build=build_broadcast_select),
    )
