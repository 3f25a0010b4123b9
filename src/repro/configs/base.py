"""Model / shape configuration dataclasses and the architecture registry."""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN scaling of RoPE (``layers.yarn_frequencies``), as a published
    config's ``rope_scaling`` gives it."""
    factor: float
    original_max: int           # original_max_position_embeddings
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 0.0   # cos/sin scale; 0 = 0.1·ln(factor) + 1


@dataclasses.dataclass
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    d_head: int = 0             # default d_model // n_heads
    qkv_bias: bool = False
    act: str = "swiglu"         # swiglu | gelu
    norm: str = "rms"           # rms | ln
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    rope: bool = True           # False: no position embedding (NoPE)
    rope_theta: float = 10000.0
    yarn: Optional[Yarn] = None  # YaRN on the full-attention layers' RoPE
    # per-layer mixer of a dense/moe stack, "attention" (full) |
    # "sliding_attention" (keys i - window < j <= i) | "mamba", read for
    # the first n_layers; () = attention in every layer
    layer_types: Tuple[str, ...] = ()
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    n_shared: int = 0
    d_expert: int = 0
    d_shared: int = 0           # shared-expert width; 0 = n_shared * d_expert
    # the experts this chip holds, [expert_lo, expert_lo + experts_held),
    # of the n_experts the router chooses from; 0 = all of them
    experts_held: int = 0
    expert_lo: int = 0
    capacity_factor: float = 1.25
    # --- SSM (Mamba2/SSD) ---
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_inner: int = 0          # default 2*d_model
    conv_k: int = 4
    conv_bias: bool = False
    ssd_chunk: int = 128        # SSD chunk length (perf knob, §Perf)
    # --- hybrid (Zamba2-style shared attention) ---
    attn_every: int = 0         # apply the shared attention block every N
    # --- enc-dec ---
    enc_layers: int = 0
    # --- vlm/audio stubs ---
    n_frontend_tokens: int = 0  # precomputed patch/frame embeddings
    # --- execution ---
    window: int = 0             # the sliding_attention layers' window, and
                                # the hybrid family's shared attention's
    remat: str = "full"         # none | full
    param_dtype: object = jnp.bfloat16
    tp_pad: int = 0             # runtime: pad q-heads to this TP degree
                                # (group-aligned, masked — exact math)
    notes: str = ""

    def head_padding(self):
        """(Hp, gp, g_true): padded head count, padded group size, true
        group size.  Padding happens inside each kv group so the
        head→kv mapping is preserved exactly; padded heads are masked
        before the output projection, so results equal the true arch."""
        H, Hkv = self.n_heads, self.n_kv
        if not H or not Hkv:
            return H, 0, 0
        g = H // Hkv
        if not self.tp_pad or H % self.tp_pad == 0:
            return H, g, g
        gp = g
        while (gp * Hkv) % self.tp_pad != 0:
            gp += 1
        return gp * Hkv, gp, g

    def __post_init__(self):
        if self.d_head == 0 and self.n_heads:
            self.d_head = self.d_model // self.n_heads
        mamba = self.family in ("ssm", "hybrid") or "mamba" in self.layer_types
        if mamba and self.ssm_inner == 0:
            self.ssm_inner = 2 * self.d_model
        if mamba and self.ssm_heads == 0:
            self.ssm_heads = self.ssm_inner // self.ssm_head_dim

    def layer_runs(self) -> List[Tuple[str, int, int]]:
        """(mixer, first layer, count) of each run of same-mixer layers of
        a dense/moe stack, in order."""
        kinds = list(self.layer_types[:self.n_layers]) or \
            ["attention"] * self.n_layers
        runs: List[Tuple[str, int, int]] = []
        for i, kind in enumerate(kinds):
            if runs and runs[-1][0] == kind:
                runs[-1] = (kind, runs[-1][1], runs[-1][2] + 1)
            else:
                runs.append((kind, i, 1))
        return runs

    def held_experts(self) -> Tuple[int, int]:
        """(first expert, count) of the experts whose weights this chip
        holds."""
        return self.expert_lo, self.experts_held or self.n_experts

    def shared_width(self) -> int:
        return self.d_shared or self.n_shared * (self.d_expert or self.d_ff)

    # ------------------------------------------------------------------
    def param_count(self) -> int:
        """Total parameters N (for 6·N·D roofline accounting)."""
        d, f, V = self.d_model, self.d_ff, self.vocab
        H, Hkv, Dh = self.n_heads, self.n_kv, self.d_head
        attn = d * (H + 2 * Hkv) * Dh + H * Dh * d + \
            (H * Dh + 2 * Hkv * Dh if self.qkv_bias else 0)
        if self.act == "swiglu":
            mlp = 3 * d * f
        else:
            mlp = 2 * d * f
        mamba = self._mamba_params()
        if self.family == "moe":
            fe = self.d_expert or f
            moe = self.n_experts * 3 * d * fe + d * self.n_experts
            moe += 3 * d * self.shared_width()
            body = sum(n * ((mamba if kind == "mamba" else attn) + moe + 2 * d)
                       for kind, _, n in self.layer_runs())
        elif self.family == "ssm":
            body = self.n_layers * (mamba + d)
        elif self.family == "hybrid":
            shared = attn + mlp + 2 * d
            body = self.n_layers * (mamba + d) + shared
        elif self.family == "encdec":
            enc = self.enc_layers * (attn + mlp + 2 * d)
            dec = self.n_layers * (2 * attn + mlp + 3 * d)
            body = enc + dec
        else:  # dense / vlm
            per_layer = attn + mlp + 2 * d
            body = self.n_layers * per_layer
        emb = V * d * (1 if self.tie_embeddings else 2)
        return body + emb + d

    def _mamba_params(self) -> int:
        """One Mamba2 mixer: in/out projections, conv (and its bias),
        A_log, D, dt_bias and the gated norm."""
        d, di, N, Hs = self.d_model, self.ssm_inner, self.ssm_state, \
            self.ssm_heads
        conv = (self.conv_k + self.conv_bias) * (di + 2 * N)
        return d * (2 * di + 2 * N + Hs) + di * d + conv + 3 * Hs + di

    def active_param_count(self) -> int:
        """Activated parameters (MoE: top-k + shared only)."""
        if self.family != "moe":
            return self.param_count()
        d, V = self.d_model, self.vocab
        H, Hkv, Dh = self.n_heads, self.n_kv, self.d_head
        fe = self.d_expert or self.d_ff
        attn = d * (H + 2 * Hkv) * Dh + H * Dh * d
        mamba = self._mamba_params()
        act_moe = (self.top_k * fe + self.shared_width()) * 3 * d + \
            d * self.n_experts
        body = sum(n * ((mamba if kind == "mamba" else attn) + act_moe + 2 * d)
                   for kind, _, n in self.layer_runs())
        return body + V * d + d


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                   # train | prefill | decode


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

# architectures for which long_500k is runnable (sub-quadratic decode)
LONG_CONTEXT_OK = {"mamba2-130m", "zamba2-1.2b"}


def pattern_period(layer_types) -> int:
    """The shortest p with layer_types[i] == layer_types[i % p] for all i."""
    n = len(layer_types)
    return next(p for p in range(1, n + 1)
                if all(layer_types[i] == layer_types[i % p] for i in range(n)))


def reduced(cfg: ModelConfig) -> ModelConfig:
    """Smoke-test twin: same family/topology, tiny dims.  A layer pattern
    keeps one whole period; an expert share keeps its share of the
    experts."""
    n_experts = min(cfg.n_experts, 4) if cfg.n_experts else 0
    held = 0
    if cfg.experts_held:
        shares = cfg.n_experts // cfg.experts_held
        held = max(1, n_experts // shares)
    c = dataclasses.replace(
        cfg,
        name=cfg.name + "-smoke",
        n_layers=pattern_period(cfg.layer_types) if cfg.layer_types else 2,
        d_model=64,
        n_heads=4,
        n_kv=max(1, min(cfg.n_kv, 2)) if cfg.n_kv else 0,
        d_head=16,
        d_ff=128,
        vocab=512,
        n_experts=n_experts,
        top_k=min(cfg.top_k, 2) if cfg.top_k else 0,
        n_shared=min(cfg.n_shared, 1),
        d_expert=32 if cfg.d_expert else 0,
        d_shared=48 if cfg.d_shared else 0,
        experts_held=held,
        expert_lo=0,
        capacity_factor=8.0,  # no token drops in smoke tests
        ssm_state=16 if cfg.ssm_state else 0,
        ssm_heads=0, ssm_inner=0,
        ssm_head_dim=16,
        attn_every=2 if cfg.attn_every else 0,
        enc_layers=2 if cfg.enc_layers else 0,
        n_frontend_tokens=8 if cfg.n_frontend_tokens else 0,
        window=min(cfg.window, 16) if cfg.window else 0,
        remat="none",
        param_dtype=jnp.float32,
    )
    return c
