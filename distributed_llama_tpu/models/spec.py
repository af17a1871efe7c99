"""Model hyperparameter spec.

TPU-native analogue of TransformerSpec (ref: src/transformer.hpp:82-104).
Values and enum encodings are file-compatible with the reference `.m` header
(ref: src/transformer.hpp:42-80).
"""

from __future__ import annotations

import dataclasses
import enum

from ..quants.types import FloatType


class ArchType(enum.IntEnum):
    """ref: src/transformer.hpp:71-75 (values double as legacy file magics)."""

    LLAMA = 0xABCD00
    GROK1 = 0xABCD01
    MIXTRAL = 0xABCD02
    # latent attention (one normed latent + one shared rope key a token)
    # over a leading dense layer and sigmoid-bias-routed experts with a
    # shared expert; not a reference-engine architecture
    SARVAM_MLA = 0xABCD03


class HiddenAct(enum.IntEnum):
    """ref: src/transformer.hpp:77-80."""

    GELU = 0
    SILU = 1


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    arch: ArchType
    dim: int
    hidden_dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    vocab_size: int
    seq_len: int
    hidden_act: HiddenAct = HiddenAct.SILU
    rope_theta: float = 10000.0
    n_experts: int = 0
    n_active_experts: int = 0
    weights_float_type: FloatType = FloatType.F32
    version: int = 0
    # -- SARVAM_MLA only (all zero elsewhere; written to the header only for
    # that architecture, so every other file stays byte-identical) ---------
    kv_lora_rank: int = 0          # r: width of the normed latent c~
    qk_nope_head_dim: int = 0      # d_n: per-head query/key part without rope
    qk_rope_head_dim: int = 0      # d_r: the rotated part; ONE key for all heads
    v_head_dim: int = 0            # d_v
    n_dense_layers: int = 0        # leading layers whose FFN is dense
    dense_hidden_dim: int = 0      # their width (hidden_dim is an expert's)
    n_shared_experts: int = 0      # experts every token passes, added unweighted
    n_routed_experts: int = 0      # router outputs; n_experts of them are HELD
    expert_offset: int = 0         # first held expert's index under the router
    routed_scaling: float = 1.0    # factor on the normalised top-k weights
    rms_eps: float = 0.0           # 0: ops/norms.RMS_EPS
    rope_factor: float = 1.0       # deepseek yarn; 1: plain rope
    rope_orig_len: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0

    @property
    def is_mla(self) -> bool:
        return self.arch == ArchType.SARVAM_MLA

    @property
    def head_size(self) -> int:
        if self.is_mla:  # a query head: [q_n ; q_r]
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        # ref: src/transformer.cpp:248
        return self.dim // self.n_heads

    @property
    def cache_head_size(self) -> int:
        """Width of one K-leaf row of the cache: a key head, or the normed
        latent and the rotated rope key side by side."""
        if self.is_mla:
            return self.kv_lora_rank + self.qk_rope_head_dim
        return self.head_size

    @property
    def cache_v_head_size(self) -> int:
        """Width of a V-leaf row; 0 where the cache has no V leaf (the
        latent is key and value at once)."""
        return 0 if self.is_mla else self.head_size

    @property
    def cache_values_per_token(self) -> int:
        """Cache VALUES a token holds over all layers (times the cache
        dtype's item size: bytes)."""
        return self.n_layers * self.n_kv_heads * (
            self.cache_head_size + self.cache_v_head_size)

    @property
    def norm_eps(self) -> float:
        from ..ops.norms import RMS_EPS

        return self.rms_eps or RMS_EPS

    @property
    def router_width(self) -> int:
        """Outputs of the router: the experts routed over, of which
        n_experts (from expert_offset on) are held here."""
        return self.n_routed_experts or self.n_experts

    def is_dense_layer(self, l: int) -> bool:
        return not self.is_moe or l < self.n_dense_layers

    @property
    def attn_softmax_scale(self) -> float:
        """1/sqrt(head) times yarn's attention factor squared (DeepSeek-V2:
        mscale_all_dim enters the softmax scale, not the cos/sin table)."""
        from ..ops.rope import yarn_mscale

        return (self.head_size ** -0.5
                * yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) ** 2)

    @property
    def kv_dim(self) -> int:
        # ref: src/transformer.cpp:249
        return (self.dim * self.n_kv_heads) // self.n_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    def validate(self) -> None:
        assert self.dim % self.n_heads == 0
        assert (self.dim * self.n_kv_heads) % self.n_heads == 0
        if self.is_mla:
            assert self.n_kv_heads == 1, "the latent cache has one head"
            assert min(self.kv_lora_rank, self.qk_nope_head_dim,
                       self.qk_rope_head_dim, self.v_head_dim) > 0
            assert self.qk_rope_head_dim % 2 == 0
            assert self.n_dense_layers == 0 or self.dense_hidden_dim > 0
            assert (self.expert_offset + self.n_experts
                    <= self.router_width), "held experts outside the router"
            assert self.n_active_experts <= self.router_width
            return
        if self.arch in (ArchType.GROK1, ArchType.MIXTRAL):
            # MoE archs without experts would fail deep inside the forward
            # (missing moe_router); reject at spec level instead
            assert self.is_moe, f"{self.arch.name} requires n_experts > 0"
        if self.is_moe:
            assert 0 < self.n_active_experts <= self.n_experts
