"""Architecture config — one dataclass covers every family in the assigned pool."""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int

    head_dim: Optional[int] = None   # default d_model // n_heads
    qkv_bias: bool = False
    swa_window: Optional[int] = None  # sliding-window attention width (None = full)
    rope_theta: float = 10000.0
    pos_embed: str = "rope"          # rope | learned | none
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    gated_mlp: bool = True           # SwiGLU vs plain GELU MLP
    act: str = "silu"
    tie_embeddings: bool = True
    max_position: int = 524288       # for learned pos-embed archs this is clamped

    # --- latent attention (MLA, DeepSeek-V2); kv_lora_rank 0 = GQA ---
    kv_lora_rank: int = 0            # width of the normed K/V latent c_kv
    qk_nope_head_dim: int = 0        # per-head q/k dims without rotary
    qk_rope_head_dim: int = 0        # rotary dims (k's are shared by all heads)
    v_head_dim: int = 0

    # --- YaRN rotary scaling (factor 0 = plain rope) ---
    rope_scaling_factor: float = 0.0
    rope_original_max: int = 0       # original_max_position_embeddings
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # --- MoE ---
    n_experts: int = 0               # experts held here: ids [0, n_experts)
    router_experts: int = 0          # router width (0 = n_experts)
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0                # per-expert hidden (fine-grained MoE)
    first_k_dense: int = 0           # DeepSeekMoE: first k layers use dense FFN
    capacity_factor: float = 1.25    # GShard path (expert axis on a mesh)
    norm_topk_prob: bool = True      # renormalize the top-k gates to sum 1
    routed_scaling_factor: float = 1.0
    moe_aux_coef: float = 0.01       # balance-loss coefficient
    moe_seq_aux: bool = False        # balance loss per row, then the mean

    # --- SSM / hybrid ---
    ssm_state: int = 0               # mamba state size N
    ssm_heads: int = 0               # number of SSM heads (hybrid)
    slstm_at: Tuple[int, ...] = ()   # xLSTM: which blocks are sLSTM
    proj_factor: float = 2.0         # xLSTM/mamba up-projection factor

    # --- enc-dec (whisper) ---
    enc_layers: int = 0
    enc_frames: int = 1500           # stubbed conv frontend output length

    # --- vlm ---
    n_vision_tokens: int = 0

    # --- numerics ---
    dtype: str = "bfloat16"          # compute dtype

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def n_router(self) -> int:
        """Router width: every expert of the layer, held here or not."""
        return self.router_experts or self.n_experts

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def is_subquadratic(self) -> bool:
        """Can this arch decode at 500k context (paper shape ``long_500k``)?"""
        if self.family in ("ssm", "hybrid"):
            return True
        return self.swa_window is not None

    @property
    def has_decoder(self) -> bool:
        return True  # every assigned arch has an autoregressive decoder

    def n_params(self) -> int:
        """Exact parameter count of this implementation (master copy)."""
        d, hd = self.d_model, self.hd
        q = self.n_heads * hd
        kv = self.n_kv_heads * hd
        att = d * (q + 2 * kv) + q * d
        if self.qkv_bias:
            att += q + 2 * kv
        if self.is_mla:
            H, r = self.n_heads, self.qk_rope_head_dim
            att = (d * H * (self.qk_nope_head_dim + r)          # wq
                   + d * (self.kv_lora_rank + r)                # wkv_a
                   + self.kv_lora_rank                          # kv_norm
                   + self.kv_lora_rank * H * (self.qk_nope_head_dim
                                              + self.v_head_dim)  # wkv_b
                   + H * self.v_head_dim * d)                   # wo
        if self.family == "moe":
            ff_moe = 3 * d * self.moe_d_ff  # gate/up/down per expert
            dense_ff = 3 * d * self.d_ff if self.d_ff else 0
            router = d * self.n_router
            shared = self.n_shared_experts * 3 * d * self.moe_d_ff
            moe_layer = att + self.n_experts * ff_moe + router + shared + 2 * d
            dense_layer = att + dense_ff + 2 * d
            body = (self.n_layers - self.first_k_dense) * moe_layer + self.first_k_dense * dense_layer
        elif self.family == "ssm":  # xLSTM: blocks counted in xlstm.py helper
            from repro.models.xlstm import xlstm_param_count
            body = xlstm_param_count(self)
        elif self.family == "hybrid":
            from repro.models.ssm import hymba_param_count
            body = hymba_param_count(self)
        elif self.family == "encdec":
            ff = (3 if self.gated_mlp else 2) * d * self.d_ff
            enc_layer = att + ff + 2 * d
            dec_layer = att + att + ff + 3 * d  # + cross-attention
            body = self.enc_layers * enc_layer + self.n_layers * dec_layer
        else:  # dense / vlm backbone
            ff = (3 if self.gated_mlp else 2) * d * self.d_ff
            body = self.n_layers * (att + ff + 2 * d)
        emb = self.vocab_size * d
        head = 0 if self.tie_embeddings else self.vocab_size * d
        pos = 0
        if self.pos_embed == "learned":
            pos = min(self.max_position, 32768) * d
            if self.family == "encdec":
                pos += self.enc_frames * d
        return int(body + emb + head + pos + d)  # + final norm

    def reduced(self) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests."""
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            n_layers=min(self.n_layers, 2 if not self.slstm_at else 4),
            d_model=64,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads < self.n_heads else 4,
            d_ff=128 if self.d_ff else 0,
            head_dim=16,
            vocab_size=256,
            n_experts=min(self.n_experts, 4) or 0,
            router_experts=min(self.router_experts, 8),
            n_shared_experts=min(self.n_shared_experts, 1),
            top_k=min(self.top_k, 2) if self.top_k else 0,
            moe_d_ff=32 if self.moe_d_ff else 0,
            first_k_dense=min(self.first_k_dense, 1),
            ssm_state=min(self.ssm_state, 8) if self.ssm_state else 0,
            ssm_heads=min(self.ssm_heads, 2) if self.ssm_heads else 0,
            slstm_at=tuple(i for i in self.slstm_at if i < 4)[:2],
            enc_layers=min(self.enc_layers, 2),
            enc_frames=32 if self.family == "encdec" else self.enc_frames,
            n_vision_tokens=8 if self.n_vision_tokens else 0,
            swa_window=min(self.swa_window, 32) if self.swa_window else None,
            kv_lora_rank=min(self.kv_lora_rank, 32),
            qk_nope_head_dim=min(self.qk_nope_head_dim, 16),
            qk_rope_head_dim=min(self.qk_rope_head_dim, 8),
            v_head_dim=min(self.v_head_dim, 8),
            rope_original_max=min(self.rope_original_max, 64),
            max_position=8192,
            dtype="float32",
        )
