"""The work each timed operation needs, from shapes alone.

These counts are what the *algorithm* needs, whatever implements it: a
program that moves more bytes than this (a merge that copies every
global per block, a kernel that loads cache blocks past ``kv_len``)
shows as a lower roofline share, never as more work.  Nothing here
reads a compiled program's cost analysis.
"""

from __future__ import annotations

from typing import Dict, Tuple

F32 = 4
BF16 = 2

# floating-point operations per element of warpPrefixStats: the xor
# butterfly sum (5 adds), v*v and its butterfly max (1 + 5), v+1 and the
# three shfl_down adds (4), four warp reductions at about one operation
# per element each (red_max, red_min, red_add, red_add width 8), and the
# seven adds of the final sum
WARP_PREFIX_STATS_FLOPS_PER_ELEMENT = 26


def cox_program(name: str, n: int, block: int) -> Tuple[float, float]:
    """(FLOPs, bytes) one launch of a COX SDK program over ``n`` elements
    needs: every input read once, every output written once."""
    if name == "vectorAdd":
        return float(n), float(3 * F32 * n)
    if name == "warpPrefixStats":
        return float(WARP_PREFIX_STATS_FLOPS_PER_ELEMENT * n), float(2 * F32 * n)
    if name == "histogram64":
        return float(n), float(F32 * n + 2 * F32 * 64)
    if name == "gridReduce":
        grid = -(-n // block)
        return float(n + grid), float(F32 * n + 2 * F32 * grid + F32)
    raise KeyError(f"no work model for COX program {name!r}")


def granite_active_params(cfg: Dict) -> int:
    """Weights one token multiplies by in a decoder step: attention
    projections, router, the top-k experts of every layer, and the tied
    output head (the embedding lookup is a gather, not a product)."""
    d = cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // h
    attn = d * (h + 2 * kv) * dh + h * dh * d
    router = d * cfg["num_local_experts"]
    experts = cfg["num_experts_per_tok"] * 3 * d * cfg["intermediate_size"]
    body = cfg["num_hidden_layers"] * (attn + router + experts)
    return body + d * cfg["vocab_size"]


def granite_token_flops(cfg: Dict, kv_len: int) -> float:
    """Model FLOPs of one token at a cache of ``kv_len`` positions:
    2 per active weight, plus QK^T and PV over the real positions."""
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    return 2.0 * granite_active_params(cfg) + layers * 4.0 * kv_len * d


def decode_attention(cfg: Dict, kv_len: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the decode attention of one token needs over all
    layers: read K and V at ``kv_len`` positions in bfloat16, read q and
    write the output."""
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // h
    flops = layers * 4.0 * kv_len * h * dh
    nbytes = layers * (2.0 * kv_len * kv * dh * BF16 + 2.0 * h * dh * BF16)
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peaks: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peaks["peak_flops"], nbytes / peaks["mem_bw"])
