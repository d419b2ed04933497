"""Encoded wire representation of quantized channel payloads.

The unfused channel path (``comm.channel.Channel.apply``) is a
*fake-quant*: it quantizes and immediately dequantizes, handing the
mixing contraction a full-width f32 payload — so the hot path writes and
re-reads N·D·4 bytes the wire never carried. This module defines the
actual on-wire form — integer codes plus a per-message decode scale —
so the contraction can read the narrow representation directly and the
decoded f32 payload (let alone the (N, K, D) gather of it) never
materializes (DESIGN.md §12).

One form covers every quantize mode the channel speaks
(``comm.channel.StageSpec(kind="quantize", bits=8|4|1)``):

* ``codes`` — int8, the payload's shape. q8 stores the rounded level in
  [−127, 127]; q4 in [−7, 7]; q1 stores sign(x) ∈ {−1, 0, 1}. Storage
  is byte-aligned on device regardless of ``bits`` (an int8 gather is
  the narrowest XLA/Pallas-addressable unit); sub-byte *wire* width is
  what ``Channel.elem_bytes`` models, exactly as before.
* ``scale`` — float32, the payload shape with message axes reduced to 1
  (broadcastable): absmax/levels for q8/q4, mean|x| for q1.

``decode`` is deliberately uniform across bits — ``codes · scale`` —
which is what makes it a *block* function: it applies unchanged to any
aligned slab of codes + scales, so a Pallas kernel can inline it per
tile (``kernels/netes_fused_mixing``) exactly as XLA runs it on the
whole array. ``comm.channel`` re-exports it as the codec's decode.

This module is import-leaf (jax only): ``core.topology_repr`` dispatches
on ``WirePayload``, ``comm.channel`` encodes into it, and the kernels
decode from it without any import cycle.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class WirePayload:
    """A quantized payload in wire form: ``value ≡ codes · scale``.

    Registered pytree: ``codes``/``scale`` trace; ``dtype`` (the payload
    dtype the decode casts back to — what the fake-quant path returns)
    rides the static aux, so contraction entry points can produce the
    caller's dtype without a side channel.
    """

    codes: Array           # int8, payload shape
    scale: Array           # float32, payload shape w/ msg axes -> 1
    dtype: Any = np.float32

    def tree_flatten(self):
        return (self.codes, self.scale), (jnp.dtype(self.dtype),)

    @classmethod
    def tree_unflatten(cls, aux, children):
        codes, scale = children
        return cls(codes=codes, scale=scale, dtype=aux[0])

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.codes.shape

    @property
    def ndim(self) -> int:
        return self.codes.ndim


jax.tree_util.register_pytree_node(
    WirePayload, WirePayload.tree_flatten, WirePayload.tree_unflatten)


def _msg_axes(x: Array, batched: bool) -> Tuple[int, ...]:
    return tuple(range(1 if batched else 0, x.ndim))


def encode(x: Array, bits: int, batched: bool) -> WirePayload:
    """Quantize ``x`` into wire form.

    Mirrors ``comm.channel._quantize`` operation-for-operation so that
    ``decode(encode(x)) == _quantize(x)`` bit-for-bit on f32 payloads
    (both compute round(x/s)·s — resp. sign(x)·scale — with the same s
    in the same dtype); bf16 payloads round once more on the final cast
    (within the documented quantization tolerance, DESIGN.md §12).
    """
    axes = _msg_axes(x, batched)
    if bits == 1:
        scale = jnp.abs(x).mean(axis=axes, keepdims=True)
        codes = jnp.sign(x)
    else:
        levels = float(2 ** (bits - 1) - 1)
        amax = jnp.abs(x).max(axis=axes, keepdims=True)
        scale = amax / levels
        codes = jnp.round(x / jnp.where(scale > 0, scale, 1.0))
    return WirePayload(codes=codes.astype(jnp.int8),
                       scale=scale.astype(jnp.float32),
                       dtype=x.dtype)


def decode(codes: Array, scale: Array,
           dtype: Optional[Any] = None) -> Array:
    """``codes · scale`` — the one decode for every quantize mode.

    A *block* function: pure jnp over any aligned (codes, scale) slabs
    with broadcastable shapes, so it inlines into a Pallas kernel body
    (per-tile) exactly as it runs under XLA (whole-array). Keep it free
    of shape introspection beyond broadcasting.
    """
    y = codes.astype(jnp.float32) * scale
    return y if dtype is None else y.astype(dtype)


def decode_payload(wp: WirePayload) -> Array:
    """Decode a whole ``WirePayload`` back to its payload dtype (the
    unfused fallback and the parity oracle's reference path)."""
    return decode(wp.codes, wp.scale, wp.dtype)


def slice_stack(wp: WirePayload, r: Array) -> WirePayload:
    """Index a stacked payload's axis 1 (``(N, R, rest…) -> (N, rest…)``)
    keeping wire form — the distributed stacked-leaf scan slices one
    (N, rest) slab per step. ``scale``'s axis 1 is size 1 (message axes
    are reduced), so it is indexed at 0."""
    return WirePayload(
        codes=jax.lax.dynamic_index_in_dim(wp.codes, r, axis=1,
                                           keepdims=False),
        scale=jax.lax.dynamic_index_in_dim(wp.scale, 0, axis=1,
                                           keepdims=False),
        dtype=wp.dtype)
