"""What a model's tensors ARE, declared once.

One ordered list of entries a group of tensors: a mixer by its LayerKind,
the dense FFN, the MoE FFN, a layer's norms, the model's two ends. An entry
says the tensor's name in the `.m` file, its shape as a function of the
spec, how it is stored, what LEAF of the params pytree it becomes, and how
that leaf splits over a tp mesh. The file plan (io/model_file.
model_tensor_plan), the loader (models/loader.load_params_streamed, and
models/params.load_params through it), the single-shard fusing (models/
params.fuse_layer_weights) and the sharding (parallel/sharding) walk this
table and hold no list of names of their own. A new mixer adds its entries
here, its forward in models/transformer.py and its kernel; an architecture
whose mixers exist adds nothing.

File order (ref: src/transformer.cpp:623-683): tok_emb; a layer: its
mixer's tensors, its FFN's (w1 w2 w3, or the router and the HELD experts'
up gate down, expert by expert), its norms; rms_final; wcls. Shapes are
(d, n) = (out rows, in columns) for matmul weights.
"""

from __future__ import annotations

import enum
import itertools
from typing import Callable, Iterator, NamedTuple

from ..quants.types import FloatType
from .spec import ArchType, LayerKind, ModelSpec


class Leaf(enum.Enum):
    """What a file tensor becomes in the params pytree."""

    WEIGHT = enum.auto()   # a matmul weight: Q40 or dense, by the load mode
    F32 = enum.auto()      # a float32 leaf (stored as float32 too)
    COMPUTE = enum.auto()  # a dense leaf of the compute dtype
    ROWS = enum.auto()     # a row block of the dense leaf `into`, which is
    #                        its members concatenated in file order (thin
    #                        projections: a few rows, no Q40 row matmul)
    HALVES = enum.auto()   # splits per head into the two dense leaves of
    #                        `into` (models/params.split_wkvb)
    EXPERT = enum.auto()   # one expert's member of the (E, ...) stack `into`


class Tensor(NamedTuple):
    name: str                                 # in the file, after `layers.N.`
    shape: Callable[[ModelSpec], tuple]
    leaf: Leaf = Leaf.WEIGHT
    into: str | tuple | None = None           # the leaf, where not `name`
    fuse: str | None = None   # the leaf its group concatenates into on ONE
    #                           shard (3 calls sharing an input become 1)
    split: str | None = None  # over tp: "row" (output dim, RowMatmulSlice),
    #                           "col" (input dim, ColMatmulSlice; ref:
    #                           src/transformer.cpp:14-76), None: replicated
    when: Callable[[ModelSpec], bool] | None = None   # absent otherwise
    stored_f32: bool = False                  # F32 leaves always are

    def ftype(self, spec: ModelSpec) -> FloatType:
        if self.stored_f32 or self.leaf is Leaf.F32:
            return FloatType.F32
        return spec.weights_float_type

    @property
    def leaves(self) -> tuple:
        """The leaf names this tensor ends in (without single-shard fusing)."""
        if self.into is None:
            return (self.name,)
        return self.into if isinstance(self.into, tuple) else (self.into,)


def _vec(width: Callable[[ModelSpec], int], name: str, **kw) -> Tensor:
    return Tensor(name, lambda s: (width(s),), Leaf.F32, **kw)


def _dim(s: ModelSpec) -> int:
    return s.dim


def _lin_k(s: ModelSpec) -> int:
    return s.lin_heads * s.lin_k_head_dim


def _lin_v(s: ModelSpec) -> int:
    return s.lin_heads * s.lin_v_head_dim


def _dense_hidden(s: ModelSpec) -> int:
    """A dense layer's FFN width: its own where the spec has one (the
    leading dense layers of a model whose hidden_dim is an expert's)."""
    return s.dense_hidden_dim or s.hidden_dim


def _shared_hidden(s: ModelSpec) -> int:
    return s.n_shared_experts * s.hidden_dim


def _shared(s: ModelSpec) -> bool:
    return s.n_shared_experts > 0


_QKV = dict(fuse="wqkv", split="row")

# -- mixers, by LayerKind ----------------------------------------------------

# K / V rows a token; rms_q and rms_k (the full projected width) where the
# block norms its sublayers' outputs
ATTENTION = (
    Tensor("wq", lambda s: (s.dim, s.dim), **_QKV),
    Tensor("wk", lambda s: (s.kv_dim, s.dim), **_QKV),
    Tensor("wv", lambda s: (s.kv_dim, s.dim), **_QKV),
    Tensor("wo", lambda s: (s.dim, s.dim), split="col"),
    _vec(_dim, "rms_q", when=lambda s: s.post_norm),
    _vec(lambda s: s.kv_dim, "rms_k", when=lambda s: s.post_norm),
)

# one latent row a token: wq (H x (d_n + d_r) rows; alone, no wk / wv to
# fuse with), wkva (the latent's r rows, then the rope key's d_r), wkvb
# (per head d_n key rows then d_v value rows, over the latent: absorbed
# attention's two per-head operands), wo (over H x d_v)
LATENT = (
    Tensor("wq", lambda s: (s.n_heads * s.head_size, s.dim), split="row"),
    Tensor("wkva", lambda s: (s.kv_lora_rank + s.qk_rope_head_dim, s.dim)),
    Tensor("wkvb", lambda s: (s.n_heads * (s.qk_nope_head_dim
                                           + s.v_head_dim), s.kv_lora_rank),
           Leaf.HALVES, into=("w_uk", "w_uv")),
    Tensor("wo", lambda s: (s.dim, s.n_heads * s.v_head_dim), split="col"),
)

# what both gated-delta-rule mixers hold: q k (H x d_k rows), v (H x d_v),
# and after the projections, float32: conv_w (taps x [q ; k ; v] channels:
# the convolutions side by side, tap j weighs the row `taps - 1 - j` tokens
# back), a_log (H), dt_bias (a decay channel) and rms_o (d_v, the gated
# output norm of a head)
_DELTA_QKV = (
    Tensor("wq", lambda s: (_lin_k(s), s.dim), **_QKV),
    Tensor("wk", lambda s: (_lin_k(s), s.dim), **_QKV),
    Tensor("wv", lambda s: (_lin_v(s), s.dim), **_QKV),
)
_DELTA_OUT = (
    Tensor("wo", lambda s: (s.dim, _lin_v(s)), split="col"),
    Tensor("conv_w", lambda s: (s.lin_conv_width, s.lin_conv_dim), Leaf.F32),
    _vec(lambda s: s.lin_heads, "a_log"),
    _vec(lambda s: s.lin_heads * s.lin_decay_dim, "dt_bias"),
    _vec(lambda s: s.lin_v_head_dim, "rms_o"),
)
# a scalar decay a head: the output gate wg (H x d_v), the decay's and
# beta's H rows each as ONE thin leaf
DELTA = (
    *_DELTA_QKV,
    Tensor("wg", lambda s: (_lin_v(s), s.dim)),
    Tensor("wa", lambda s: (s.lin_heads, s.dim), Leaf.ROWS, into="w_ab"),
    Tensor("wb", lambda s: (s.lin_heads, s.dim), Leaf.ROWS, into="w_ab"),
    *_DELTA_OUT,
)
# a decay a key channel (KDA): the decay's low-rank pair wf_a (d_k rows
# over the stream) and wf_b (H x d_k rows over those d_k), wbeta (H rows),
# the output gate's pair wg_a (d_v rows) and wg_b (H x d_v rows over
# them); the three thin projections of the stream are one leaf, the second
# halves (d_k columns: four Q40 blocks a row) a dense leaf each
DELTA_VECTOR = (
    *_DELTA_QKV,
    Tensor("wf_a", lambda s: (s.lin_k_head_dim, s.dim), Leaf.ROWS,
           into="w_fgb"),
    Tensor("wf_b", lambda s: (_lin_k(s), s.lin_k_head_dim),
           Leaf.COMPUTE),
    Tensor("wbeta", lambda s: (s.lin_heads, s.dim), Leaf.ROWS, into="w_fgb"),
    Tensor("wg_a", lambda s: (s.lin_v_head_dim, s.dim), Leaf.ROWS,
           into="w_fgb"),
    Tensor("wg_b", lambda s: (_lin_v(s), s.lin_v_head_dim),
           Leaf.COMPUTE),
    *_DELTA_OUT,
)

# a Mamba-2 state-space mixer: the input projection in leaves whose rows
# tile, wz (the gate; d_inner rows), wx (d_inner), wbc (B's G x N rows,
# then C's) and wdt (H rows), wo (over d_inner), then float32: conv_w (taps
# x [x ; B ; C] channels), conv_b (a channel), a_log, dt_bias and ssm_d (H:
# the decay, the step's bias and the skip) and rms_o (d_inner)
SSM = (
    Tensor("wz", lambda s: (s.ssm_inner, s.dim), fuse="wzx"),
    Tensor("wx", lambda s: (s.ssm_inner, s.dim), fuse="wzx"),
    Tensor("wbc", lambda s: (2 * s.ssm_groups * s.ssm_d_state, s.dim),
           Leaf.ROWS, into="w_bcdt"),
    Tensor("wdt", lambda s: (s.ssm_heads, s.dim), Leaf.ROWS, into="w_bcdt"),
    Tensor("wo", lambda s: (s.dim, s.ssm_inner), split="col"),
    Tensor("conv_w", lambda s: (s.ssm_conv_width, s.ssm_conv_dim), Leaf.F32),
    _vec(lambda s: s.ssm_conv_dim, "conv_b", when=lambda s: s.ssm_conv_bias),
    _vec(lambda s: s.ssm_heads, "a_log"),
    _vec(lambda s: s.ssm_heads, "dt_bias"),
    _vec(lambda s: s.ssm_heads, "ssm_d"),
    _vec(lambda s: s.ssm_inner, "rms_o"),
)

# a Mamba-1 mixer (the selective scan; ssm_dt_rank = R > 0): ONE input
# projection as its halves wz (the gate) and wx, fused on one shard as
# Mamba-2's are; wxp, x_proj, R + 2N rows over the CONVOLVED x ([r ; B ;
# C]), and wdt, dt_proj, d_inner rows over those R: thin (192 rows; an
# R-wide contraction, five Q40 blocks a row), a dense leaf each as KDA's
# low-rank pairs are; wo; then float32: conv_w (taps x d_inner: x ALONE),
# conv_b, a_log (N x d_inner: the state index outermost, so that the
# channels fill the lanes of the scan), dt_bias and ssm_d (a channel) and
# the three inner norms rms_dt (R), rms_b and rms_c (N). NO norm before wo.
SSM_SELECTIVE = (
    Tensor("wz", lambda s: (s.ssm_inner, s.dim), fuse="wzx"),
    Tensor("wx", lambda s: (s.ssm_inner, s.dim), fuse="wzx"),
    Tensor("wxp", lambda s: (s.ssm_dt_rank + 2 * s.ssm_d_state, s.ssm_inner),
           Leaf.COMPUTE),
    Tensor("wdt", lambda s: (s.ssm_inner, s.ssm_dt_rank), Leaf.COMPUTE),
    Tensor("wo", lambda s: (s.dim, s.ssm_inner), split="col"),
    Tensor("conv_w", lambda s: (s.ssm_conv_width, s.ssm_conv_dim), Leaf.F32),
    _vec(lambda s: s.ssm_conv_dim, "conv_b", when=lambda s: s.ssm_conv_bias),
    Tensor("a_log", lambda s: (s.ssm_d_state, s.ssm_inner), Leaf.F32),
    _vec(lambda s: s.ssm_inner, "dt_bias"),
    _vec(lambda s: s.ssm_inner, "ssm_d"),
    _vec(lambda s: s.ssm_dt_rank, "rms_dt"),
    _vec(lambda s: s.ssm_d_state, "rms_b"),
    _vec(lambda s: s.ssm_d_state, "rms_c"),
)

# -- the FFN -------------------------------------------------------------------

DENSE_FFN = (
    Tensor("w1", lambda s: (_dense_hidden(s), s.dim), fuse="w13",
           split="row"),
    Tensor("w2", lambda s: (s.dim, _dense_hidden(s)), split="col"),
    Tensor("w3", lambda s: (_dense_hidden(s), s.dim), fuse="w13",
           split="row"),
)

# the router over router_width experts, of which n_experts are held here;
# its bias (float32, used for the choice only) goes with latent attention;
# the shared expert's sh_w1 (gate) sh_w2 (down) sh_w3 (up) at
# n_shared_experts x hidden_dim
MOE_FFN = (
    Tensor("moe_router", lambda s: (s.router_width, s.dim), Leaf.COMPUTE),
    _vec(lambda s: s.router_width, "moe_bias", when=lambda s: s.is_mla),
    Tensor("up", lambda s: (s.hidden_dim, s.dim), Leaf.EXPERT,
           into="moe_up", split="row"),
    Tensor("gate", lambda s: (s.hidden_dim, s.dim), Leaf.EXPERT,
           into="moe_gate", split="row"),
    Tensor("down", lambda s: (s.dim, s.hidden_dim), Leaf.EXPERT,
           into="moe_down", split="col"),
    Tensor("sh_w1", lambda s: (_shared_hidden(s), s.dim), when=_shared),
    Tensor("sh_w2", lambda s: (s.dim, _shared_hidden(s)), when=_shared),
    Tensor("sh_w3", lambda s: (_shared_hidden(s), s.dim), when=_shared),
)

# -- norms and ends ------------------------------------------------------------

NORMS = (
    _vec(_dim, "rms_att"),
    _vec(_dim, "rms_ffn"),
    _vec(_dim, "rms_moe", when=lambda s: s.arch == ArchType.GROK1),
    _vec(_dim, "rms_ffn2", when=lambda s: s.arch == ArchType.GROK1),
)
# the latent's norm, after the block's (as the files have it)
LATENT_NORMS = (_vec(lambda s: s.kv_lora_rank, "rms_kv"),)

HEAD = (Tensor("tok_emb", lambda s: (s.vocab_size, s.dim), Leaf.COMPUTE,
               stored_f32=True),)
# wcls: vocab-sharded logits (the reference computes them on the root)
TAIL = (_vec(_dim, "rms_final"),
        Tensor("wcls", lambda s: (s.vocab_size, s.dim), split="row"))

_MIXERS = {LayerKind.ATTENTION: ATTENTION, LayerKind.LATENT: LATENT,
           LayerKind.DELTA: DELTA, LayerKind.SSM: SSM}
_GROUPS = (HEAD, *_MIXERS.values(), DELTA_VECTOR, SSM_SELECTIVE, DENSE_FFN,
           MOE_FFN, NORMS, LATENT_NORMS, TAIL)


def layer_tensors(spec: ModelSpec, l: int) -> list[Tensor]:
    """Layer l's entries in file order: the mixer of its kind, its FFN,
    the norms."""
    kind = spec.layer_kinds[l]
    mixer = (DELTA_VECTOR if kind == LayerKind.DELTA and spec.lin_vector_decay
             else SSM_SELECTIVE if kind == LayerKind.SSM and spec.ssm_selective
             else _MIXERS[kind])
    ffn = DENSE_FFN if spec.is_dense_layer(l) else MOE_FFN
    tail = LATENT_NORMS if kind == LayerKind.LATENT else ()
    return [t for t in (*mixer, *ffn, *NORMS, *tail)
            if t.when is None or t.when(spec)]


def model_tensors(spec: ModelSpec) -> Iterator[tuple[str, int | None, Tensor]]:
    """(name in the file, layer or None, entry) for every tensor, in file
    order; the held experts' entries expert by expert."""
    for t in HEAD:
        yield t.name, None, t
    for l in range(spec.n_layers):
        p = f"layers.{l}."
        for expert, run in itertools.groupby(
                layer_tensors(spec, l), lambda t: t.leaf is Leaf.EXPERT):
            if expert:
                run = list(run)
                for e in range(spec.n_experts):
                    for t in run:
                        yield f"{p}experts.{e}.{t.name}", l, t
            else:
                for t in run:
                    yield p + t.name, l, t
    for t in TAIL:
        yield t.name, None, t


def group_members(spec: ModelSpec, l: int, leaf: str) -> list[Tensor]:
    """Layer l's tensors that one leaf is made of by concatenation: the
    ROWS of a thin leaf, or a single-shard fusion group."""
    return [t for t in layer_tensors(spec, l) if leaf in (t.into, t.fuse)]


def _derive() -> tuple[dict, dict]:
    """Every leaf's split over tp, and fused leaf -> its members' names in
    row order, read off the groups (a leaf declared twice must agree)."""
    splits: dict = {}
    fused: dict = {}
    for t in itertools.chain(*_GROUPS):
        for leaf in (*t.leaves, *((t.fuse,) if t.fuse else ())):
            assert splits.setdefault(leaf, t.split) == t.split, leaf
        if t.fuse and t.name not in fused.setdefault(t.fuse, []):
            fused[t.fuse].append(t.name)
    return splits, fused


# a leaf that is not declared is a KeyError in LEAF_SPLIT
LEAF_SPLIT, FUSION_GROUPS = _derive()
