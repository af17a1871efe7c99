"""Model hyperparameter spec.

TPU-native analogue of TransformerSpec (ref: src/transformer.hpp:82-104).
Values and enum encodings are file-compatible with the reference `.m` header
(ref: src/transformer.hpp:42-80).
"""

from __future__ import annotations

import dataclasses
import enum
import math

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
    # gated-delta-rule layers (a per-slot recurrent state and a short
    # convolution) beside full-attention layers without rotation, the
    # norms on each sublayer's OUTPUT; not a reference-engine architecture
    OLMO_HYBRID = 0xABCD04
    # state-space (Mamba-2) layers beside full-attention layers without
    # rotation, softmax-routed experts and a shared expert in EVERY layer
    # under a pre-norm block, four published multipliers (embedding,
    # residual, attention, logits); not a reference-engine architecture
    GRANITE_HYBRID = 0xABCD05
    # Kimi Delta Attention layers (the gated delta rule with a decay that is
    # a VECTOR over a head's key channels, low-rank decay and gate
    # projections) beside latent-attention layers WITHOUT positions, a
    # leading dense layer, then sigmoid-bias-routed experts and a shared
    # expert in every layer; not a reference-engine architecture
    KIMI_LINEAR = 0xABCD06
    # selective-scan (Mamba-1) layers, whose decay is a number a (channel,
    # state index) pair and whose step is a data-dependent low-rank
    # projection with norms on dt, B and C, beside full-attention layers
    # WITHOUT positions that share ONE KV head, a dense SwiGLU in every
    # layer under a pre-norm block; not a reference-engine architecture
    JAMBA = 0xABCD07


class LayerKind(enum.IntEnum):
    """What a layer's mixer is, and so what a slot remembers in it: K/V
    rows (ATTENTION), one latent row (LATENT), or a recurrent state and
    the convolution's tail (DELTA: the gated delta rule; SSM: a state-space
    mixer, Mamba-2's or, where the spec's ssm_dt_rank says so, Mamba-1's
    selective scan). The per-layer description every cache maker, loader
    plan, forward and byte ledger reads."""

    ATTENTION = 0
    LATENT = 1
    DELTA = 2
    SSM = 3

    @property
    def has_state(self) -> bool:
        """A slot holds a state and a tail here, not rows of a cache."""
        return self in (LayerKind.DELTA, LayerKind.SSM)


# What assumes ROWS of a cache and cannot hold a recurrent state yet. Each is
# refused at start-up with its message (apps/dllama.main before a model is
# loaded; Engine, PrefixCache and Scheduler for a library caller), as
# SARVAM_MLA is refused under tp. CHANGES.md (PR 34) says what lifts each.
STATE_REFUSALS = {
    "prefix_cache": "--prefix-cache: a cached prefix of this model is a "
                    "snapshot of every state layer's state at a block "
                    "boundary, and the arena holds rows of a cache only",
    "speculation": "--draft / --lookup-decode: a verify step takes a "
                   "rejected draft back by position, and a recurrent state "
                   "cannot be taken back",
    "kv_transfer": "--kv-transfer: a block frame carries rows of a cache; "
                   "a recurrent state has no frame",
    "parallel": "--tp / --pp / --sp / --ep / --nnodes > 1: the recurrent "
                "state and its kernels run on one shard (dp replicas are "
                "the way to more chips)",
    "session": "--session: a session file holds the rows of a cache up to "
               "a position, not a recurrent state",
}


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
    # -- OLMO_HYBRID only (header keys of their own, as above) -------------
    mixers: tuple = ()             # LayerKind a layer, from the header;
    #                                () where every layer is the same kind
    lin_heads: int = 0             # key and value heads of a DELTA layer
    lin_k_head_dim: int = 0        # d_k: q and k of a head
    lin_v_head_dim: int = 0        # d_v: v, gate and output of a head
    lin_conv_width: int = 0        # taps of the causal depthwise convolution
    lin_beta_scale: int = 1        # 2: beta in (0, 2), negative eigenvalues
    lin_decay_dim: int = 1         # channels of a head's decay: 1, a scalar
    #                                a token, or d_k, one a key channel (the
    #                                KDA mixer, with its low-rank decay and
    #                                gate projections); KIMI_LINEAR's header
    # -- GRANITE_HYBRID only (header keys of their own) --------------------
    ssm_heads: int = 0             # H: heads of an SSM layer
    ssm_head_dim: int = 0          # P: d_inner = H x P
    ssm_d_state: int = 0           # N: a head's state is (P, N) float32
    ssm_groups: int = 0            # G: B and C are shared by H / G heads
    ssm_conv_width: int = 0        # taps of the causal depthwise convolution
    ssm_conv_bias: int = 0         # 1: the convolution adds a bias a channel
    ssm_dt_rank: int = 0           # R > 0: the SELECTIVE scan (Mamba-1): the
    #                                step is a rank-R projection of the
    #                                convolved x, the decay a number a
    #                                (state index, channel) pair, every
    #                                channel a head of its own (H = d_inner,
    #                                P = 1) and the state a slot (N, d_inner);
    #                                JAMBA's header (0: Mamba-2, a scalar
    #                                decay a head)
    # published multipliers, data and not `if arch ==` in forward; 1 (or,
    # for the softmax scale, 0) leaves the program's text as it was
    embedding_scale: float = 1.0   # x0 = scale x E[token]
    residual_scale: float = 1.0    # on each sublayer's output, before the add
    attn_scale: float = 0.0        # softmax scale; 0: head_size ** -0.5
    logit_scale: float = 1.0       # on the head's output

    @property
    def layer_kinds(self) -> tuple:
        """LayerKind of every layer: data where the header carries it, the
        architecture's one kind everywhere else."""
        if self.mixers:
            return tuple(LayerKind(m) for m in self.mixers)
        kind = (LayerKind.LATENT if self.arch == ArchType.SARVAM_MLA
                else LayerKind.ATTENTION)
        return (kind,) * self.n_layers

    @property
    def cache_index(self) -> tuple:
        """Layer -> index of its leaves among the leaves of its kind
        (KVCache holds one leaf set a KIND: rows for the layers that
        attend, state and tail for the DELTA layers)."""
        seen: dict = {}
        out = []
        for kind in self.layer_kinds:
            out.append(seen.get(kind.has_state, 0))
            seen[kind.has_state] = out[-1] + 1
        return tuple(out)

    @property
    def n_cache_layers(self) -> int:
        """Layers that keep ROWS of a cache (all but the state layers)."""
        return sum(not k.has_state for k in self.layer_kinds)

    @property
    def n_state_layers(self) -> int:
        return self.n_layers - self.n_cache_layers

    @property
    def has_state(self) -> bool:
        return self.n_state_layers > 0

    def refusal(self, what: str) -> str | None:
        """Why this model cannot run with `what` (a key of STATE_REFUSALS)
        asked for; None where it can."""
        if not self.has_state:
            return None
        return (f"{self.arch.name} keeps a recurrent state a slot and does "
                f"not run with {STATE_REFUSALS[what]}")

    def refuse(self, what: str) -> None:
        """ValueError with that message, for a library caller."""
        why = self.refusal(what)
        if why:
            raise ValueError(why)

    @property
    def post_norm(self) -> bool:
        """Norms on each sublayer's output (h = x + norm(mixer(x))) and
        q, k normed at full width, instead of a norm on its input."""
        return self.arch == ArchType.OLMO_HYBRID

    @property
    def lin_conv_dim(self) -> int:
        """Channels of a DELTA layer's convolution: [q ; k ; v]."""
        return self.lin_heads * (2 * self.lin_k_head_dim
                                 + self.lin_v_head_dim)

    @property
    def ssm_inner(self) -> int:
        """d_inner: the width of an SSM layer's x, gate and output."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_selective(self) -> bool:
        """An SSM layer is Mamba-1's selective scan and its kernels
        (ops/pallas_selective_scan.py), not Mamba-2's (ops/pallas_ssd.py)."""
        return self.ssm_dt_rank > 0

    @property
    def ssm_conv_dim(self) -> int:
        """Channels of an SSM layer's convolution: [x ; B ; C], or x alone
        where B and C are read from the CONVOLVED x (the selective scan)."""
        if self.ssm_selective:
            return self.ssm_inner
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_d_state

    def state_leaves(self, kind: LayerKind) -> tuple:
        """What a slot keeps in ONE layer of a kind that has a state: the
        float32 state's shape a slot, and the (rows, channels) of the
        convolution's tail. The one statement KVCache.create and the byte
        ledgers (state_bytes_per_slot, so /stats and the profiler) read."""
        if kind == LayerKind.DELTA:
            return ((self.lin_heads, self.lin_k_head_dim,
                     self.lin_v_head_dim),
                    (max(self.lin_conv_width - 1, 0), self.lin_conv_dim))
        assert kind == LayerKind.SSM, kind
        # the selective scan keeps the state index outermost, so that the
        # channels fill the lanes
        state = ((self.ssm_d_state, self.ssm_inner) if self.ssm_selective
                 else (self.ssm_heads, self.ssm_head_dim, self.ssm_d_state))
        return (state, (max(self.ssm_conv_width - 1, 0), self.ssm_conv_dim))

    def state_bytes_per_slot(self, cache_itemsize: int) -> int:
        """Bytes a slot holds over all state layers, whatever its context:
        the float32 state and the convolution's tail, which is kept in the
        cache dtype but never narrower than bf16 (the ONE statement of that
        rule: KVCache.create asks `tail_itemsize`)."""
        total = 0
        for kind in self.layer_kinds:
            if kind.has_state:
                state, tail = self.state_leaves(kind)
                total += (math.prod(state) * 4 + math.prod(tail)
                          * self.tail_itemsize(cache_itemsize))
        return total

    @staticmethod
    def tail_itemsize(cache_itemsize: int) -> int:
        return max(cache_itemsize, 2)

    @property
    def is_mla(self) -> bool:
        """The layers that attend keep ONE latent row a token (heads of
        d_n + d_r, a cache row of r + d_r, no V leaf); a state layer may
        stand beside them. Read off the layers' kinds, not the
        architecture's name."""
        return LayerKind.LATENT in self.layer_kinds

    @property
    def lin_vector_decay(self) -> bool:
        """A DELTA layer's decay is a vector over the key channels: the KDA
        mixer and its kernels (ops/pallas_kda.py), not the scalar rule's."""
        return self.lin_decay_dim > 1

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
        """Cache VALUES a token holds over the layers that HAVE a cache
        (times the cache dtype's item size: bytes)."""
        return self.n_cache_layers * self.n_kv_heads * (
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
        if self.is_moe:
            # the held experts, all the router's or a share of a wider one
            # (whatever the architecture)
            assert (self.expert_offset + self.n_experts
                    <= self.router_width), "held experts outside the router"
            assert 0 < self.n_active_experts <= self.router_width
        if self.is_mla:
            assert self.n_kv_heads == 1, "the latent cache has one head"
            assert min(self.kv_lora_rank, self.qk_nope_head_dim,
                       self.qk_rope_head_dim, self.v_head_dim) > 0
            assert self.qk_rope_head_dim % 2 == 0
            assert self.n_dense_layers == 0 or self.dense_hidden_dim > 0
        if self.mixers:
            assert len(self.mixers) == self.n_layers, "one kind a layer"
        kinds = set(self.layer_kinds)
        # a model's attending layers are all of one kind: latent rows or
        # K / V rows (one K-leaf width a model, cache_head_size)
        assert not (self.is_mla and LayerKind.ATTENTION in kinds), kinds
        if LayerKind.DELTA in kinds:
            assert min(self.lin_heads, self.lin_k_head_dim,
                       self.lin_v_head_dim) > 0
            assert self.lin_conv_width >= 2
            assert self.lin_beta_scale in (1, 2)
            assert self.lin_decay_dim in (1, self.lin_k_head_dim)
        if LayerKind.SSM in kinds:
            assert min(self.ssm_heads, self.ssm_head_dim, self.ssm_d_state,
                       self.ssm_groups) > 0
            assert self.ssm_heads % self.ssm_groups == 0
            assert self.ssm_conv_width >= 2
            assert self.ssm_conv_bias in (0, 1)
            if self.ssm_selective:
                assert self.ssm_head_dim == 1 and self.ssm_groups == 1, (
                    "the selective scan: every channel a head of its own")
                assert self.ssm_dt_rank % 32 == 0, "whole Q80 blocks of r"
        if self.arch in (ArchType.GROK1, ArchType.MIXTRAL):
            # MoE archs without experts would fail deep inside the forward
            # (missing moe_router); reject at spec level instead
            assert self.is_moe, f"{self.arch.name} requires n_experts > 0"
