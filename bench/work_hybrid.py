"""The work of granite-4.0-h-small's served step on this chip, from shapes
alone: model FLOPs of one token pass, and the FLOPs and bytes of one
``ssd_decode`` call.

What this chip computes is its share of the stated deployment: the
served layers' mixers, routers and shared experts, the embedding and the
tied head, and of the routed experts only those it holds.  The router
spreads a token's k choices over all ``num_router_experts``, so the held
experts take k·held/E of them on average; the counts use that
expectation, not the choices a run happened to make.
"""

from __future__ import annotations

from typing import Dict, Tuple

from bench.models.granite_hybrid import dims, runs

F32 = 4


def mamba_params(cfg: Dict) -> int:
    """Weights one token multiplies by in a Mamba2 mixer: the in and out
    projections and the depthwise conv."""
    m = dims(cfg)
    conv_c = m["di"] + 2 * m["n"]
    return (
        m["d"] * (2 * m["di"] + 2 * m["n"] + m["ssm_h"])
        + m["di"] * m["d"]
        + m["conv"] * conv_c
    )


def attention_params(cfg: Dict) -> int:
    m = dims(cfg)
    return m["d"] * (m["h"] + 2 * m["kv"]) * m["dh"] + m["h"] * m["dh"] * m["d"]


def moe_params(cfg: Dict) -> float:
    """Router, the held experts' expected share of the k choices, and the
    shared expert."""
    m = dims(cfg)
    held_choices = m["k"] * m["held"] / m["e"]
    return m["d"] * m["e"] + (held_choices * m["f"] + m["fs"]) * 3 * m["d"]


def ssd_decode_work(cfg: Dict, batch: int = 1) -> Tuple[float, float]:
    """(FLOPs, bytes) one ``ssd_decode`` call needs: the f32 state read
    and written once (3 FLOPs an element for h·exp(A·dt) + B ⊗ (x·dt), 2
    for C·h'), x and y in f32, and dt, A, D, B and C."""
    m = dims(cfg)
    state = batch * m["ssm_h"] * m["n"] * m["p"]
    flops = 5.0 * state + 2.0 * batch * m["ssm_h"] * m["p"]
    operands = 2 * batch * m["ssm_h"] * m["p"] + batch * m["ssm_h"] + 2 * m["ssm_h"]
    operands += 2 * batch * m["n"]
    return flops, float(F32 * (2 * state + operands))


def mamba_layers(cfg: Dict) -> int:
    return sum(n for kind, n in runs(cfg) if kind == "mamba")


def token_flops(cfg: Dict, kv_len: int) -> float:
    """Model FLOPs of one token at a cache of ``kv_len`` positions: 2 per
    weight multiplied, the SSD state update, and QK^T and PV over the
    real positions."""
    m = dims(cfg)
    flops = 0.0
    for kind, n in runs(cfg):
        if kind == "mamba":
            mixer = 2.0 * mamba_params(cfg) + ssd_decode_work(cfg)[0]
        else:
            mixer = 2.0 * attention_params(cfg) + 4.0 * kv_len * m["h"] * m["dh"]
        flops += n * (mixer + 2.0 * moe_params(cfg))
    return flops + 2.0 * m["d"] * m["vocab"]
