"""The work of Mellum2-12B-A2.5B's served step on this chip, from shapes
alone: model FLOPs of one token pass, and the FLOPs and bytes of one
``decode_attn_ring`` call.

What this chip computes is its share of the stated deployment: every
layer's attention and router, the embedding and the untied head, and of
the routed experts only those it holds.  The router spreads a token's k
choices over all ``num_router_experts``, so the held experts take
k·held/E of them on average; the counts use that expectation, not the
choices a run happened to make.  A sliding layer attends at most
``sliding_window`` positions.
"""

from __future__ import annotations

from typing import Dict, Tuple

from bench.models.mellum2 import RING_KIND, dims, runs

BF16 = 2


def attention_params(cfg: Dict) -> int:
    m = dims(cfg)
    return m["d"] * (m["h"] + 2 * m["kv"]) * m["dh"] + m["h"] * m["dh"] * m["d"]


def moe_params(cfg: Dict) -> float:
    """Router, and the held experts' expected share of the k choices."""
    m = dims(cfg)
    return m["d"] * m["e"] + m["k"] * m["held"] / m["e"] * 3 * m["d"] * m["f"]


def ring_layers(cfg: Dict) -> int:
    return sum(n for kind, n in runs(cfg) if kind == RING_KIND)


def ring_attention(cfg: Dict, kv_len: int) -> Tuple[float, float]:
    """(FLOPs, bytes) one ``decode_attn_ring`` call needs for one token at
    a cache of ``kv_len`` positions: K and V of the min(kv_len, window)
    positions in the ring read once, q read and the output written, and
    the token's K and V row written, all bfloat16; QK^T and PV."""
    m = dims(cfg)
    rows = min(kv_len, m["window"])
    flops = 4.0 * rows * m["h"] * m["dh"]
    nbytes = BF16 * (2 * rows * m["kv"] * m["dh"] + 2 * m["h"] * m["dh"]
                     + 2 * m["kv"] * m["dh"])
    return flops, float(nbytes)


def token_flops(cfg: Dict, kv_len: int) -> float:
    """Model FLOPs of one token at a cache of ``kv_len`` positions: 2 per
    weight multiplied, and QK^T and PV over the positions each layer
    attends."""
    m = dims(cfg)
    flops = 0.0
    for kind, n in runs(cfg):
        seen = min(kv_len, m["window"]) if kind == RING_KIND else kv_len
        flops += n * (2.0 * (attention_params(cfg) + moe_params(cfg))
                      + 4.0 * seen * m["h"] * m["dh"])
    return flops + 2.0 * m["d"] * m["vocab"]
