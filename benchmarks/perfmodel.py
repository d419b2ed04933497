"""Production-target distributed step model, shared by kernel_bench's
crossover table, fleet_bench's scale axis, and resilience_bench's
topology × channel grid.

Constants model a v5e-class chip (documented in DESIGN.md §3): the
distributed mixing moves each agent's D-float shard over ICI — dense as
one (N−1)·D·``elem_bytes`` all-gather, sparse as K_max routed neighbor
fetches, circulant as |±Δ| ppermute hops — then contracts locally (dense
on the MXU, sparse/circulant on the VPU, ~50× worse per flop; sparsity
wins on WIRE BYTES, not arithmetic). The all-gather is a fully-pipelined
ring schedule at near-peak link utilization; an arbitrary neighbor set
has no static schedule, so its transfers contend for links at
~1/``GATHER_CONTENTION`` of ring throughput.

Element width (DESIGN.md §11): payloads default to f32
(``elem_bytes=4``), but a lossy channel narrows them —
``comm.channel.Channel.elem_bytes`` gives the encoded width (1 byte for
quantize(8), 0.5 for quantize(4), ⅛ for sign) and an event-triggered
stage scales the EXPECTED traffic by its measured ``trigger_rate``.

**Crossover note (re-derived for sub-f32 payloads).** Comparing comm terms, sparse beats dense when
``K · contention · elem_bytes_sparse < (N−1) · elem_bytes_dense``, i.e.
K* ≈ (N−1)/3 when both sides move f32 (the ≈``SPARSE_DENSITY_CUTOFF``
heuristic). The ratio of element widths shifts it linearly: a dense f32
all-gather versus int8-quantized neighbor fetches moves the crossover to
K* ≈ 4(N−1)/3 — i.e. a quantized sparse channel wins on wire bytes at
EVERY density; conversely an int8 dense all-gather against f32 fetches
pulls it down to K* ≈ (N−1)/12. Compression and topology multiply, so
the resilience bench sweeps them jointly.

``wire_bytes`` is the regression-gated metric (DESIGN.md §8): a
deterministic function of (topology, channel) alone, comparable across
any two machines — unlike wall-times.
"""
from __future__ import annotations

ICI_BW = 9.0e10          # bytes/s per link (ring-collective effective)
GATHER_CONTENTION = 3.0  # unscheduled neighbor-fetch bandwidth derating
HOP_LAT = 2.0e-6         # s per routed transfer / permute hop
MXU_FLOPS = 2.0e14       # f32 matmul units
VPU_FLOPS = 4.0e12       # vector units (gather + fma path)
D_PROD = 1 << 20         # per-agent parameter floats at production scale


def wire_bytes(n: int, fan_in: int, kind: str, d: int = D_PROD,
               elem_bytes: float = 4.0,
               trigger_rate: float = 1.0) -> int:
    """Per-chip collective bytes of one distributed mixing step.

    ``fan_in``: K_max for sparse, |±Δ| signed-offset count for circulant,
    ignored for dense (which always moves the full (N−1)·D all-gather).
    ``elem_bytes``: encoded payload width (``Channel.elem_bytes``; 4 =
    uncompressed f32). ``trigger_rate``: expected fraction of steps a
    source actually transmits (event-triggered channels; 1 = always).
    """
    if kind == "dense":
        return int(round((n - 1) * d * elem_bytes * trigger_rate))
    return int(round(fan_in * d * elem_bytes * trigger_rate))


def modeled_step_us(n: int, fan_in: int, kind: str, d: int = D_PROD,
                    elem_bytes: float = 4.0,
                    trigger_rate: float = 1.0,
                    codec_stages: int = 0) -> float:
    """Modeled production step time (µs) — comm + decode + contraction.

    Circulant ppermute chains are statically scheduled ring rotations, so
    unlike arbitrary sparse neighbor sets they pay no contention derating
    (DESIGN.md §2). Quantized payloads shrink the bandwidth term but not
    the hop latency; event triggering scales the expected bandwidth AND
    the expected hop count (an untriggered source sends nothing).

    ``codec_stages``: number of payload-codec stages (quantize/topk) in
    the channel pipeline. A receiver decodes each message in ONE pass
    regardless of how many stages composed the encoding — the stages
    narrow what moves on the wire, but dequantization back to f32 is a
    single ``codes · scale`` sweep (2 VPU ops/element over the received
    fan-in) — so the decode term is charged once iff ``codec_stages >
    0``, never per stage.
    """
    wb = wire_bytes(n, fan_in, kind, d, elem_bytes, trigger_rate)
    recv = (n - 1) if kind == "dense" else fan_in
    decode = 0.0
    if codec_stages > 0:
        decode = 2 * recv * d * trigger_rate / VPU_FLOPS
    if kind == "dense":
        comm = HOP_LAT + wb / ICI_BW
        comp = 2 * n * d / MXU_FLOPS
    else:
        contention = 1.0 if kind == "circulant" else GATHER_CONTENTION
        comm = (fan_in * HOP_LAT * trigger_rate + wb * contention / ICI_BW)
        comp = 2 * fan_in * d / VPU_FLOPS
    return (comm + decode + comp) * 1e6
