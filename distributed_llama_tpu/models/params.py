"""Model parameters as a JAX pytree.

Structure: {"tok_emb", "rms_final", "wcls", "layers": [<per-layer dict>, ...]}
— each layer's weights are standalone device arrays (no stacked (L, ...)
axis). The forward pass statically unrolls over `layers` (the TPU analogue
of the reference's flat per-layer task list, ref: src/llama2-tasks.cpp:
249-275); standalone buffers feed the fused Q40 kernel in place, with no
per-step slice/copy, and per-layer loading never materializes a stacked
host copy (important for the 70B path — each tensor moves host -> device
individually, models/loader.py).

Two storage modes:
  * dense  — weights dequantized to `dtype` (bf16 on TPU) at load
  * q40    — weights kept as packed QuantizedTensor in HBM (4.5 bits/weight),
             dequantized inside the consuming matmul (ref keeps Q40 in RAM
             and fuses dequant into the kernel: src/funcs.cpp:286-385)

Unsliced tensors (embeddings, norms, wcls, MoE router) mirror the reference's
root-only tensors (ref: src/transformer.cpp:639-673) by being replicated
across the mesh.
"""

from __future__ import annotations

import itertools

import jax.numpy as jnp
import numpy as np

from ..io.model_file import HostTensor, model_tensor_plan, to_q40_host
from ..quants.jax_codec import QuantizedTensor
from ..quants.types import FloatType
from .spec import ModelSpec
from .tensors import FUSION_GROUPS, group_members


def split_wkvb(spec: ModelSpec, wkvb: np.ndarray):
    """The latent's up-projection (H x (d_n + d_v), r), dequantized, as the
    two operands of absorbed attention: W_uk (H, d_n, r), which folds into
    the query, and W_uv (H, d_v, r), which unfolds the attended latent.
    Kept dense in the compute dtype: they are batched per-head
    contractions, not Q40 row matmuls, and the packed wkvb is dropped."""
    per = wkvb.reshape(spec.n_heads,
                       spec.qk_nope_head_dim + spec.v_head_dim,
                       spec.kv_lora_rank)
    return (np.ascontiguousarray(per[:, :spec.qk_nope_head_dim]),
            np.ascontiguousarray(per[:, spec.qk_nope_head_dim:]))


def load_params(
    spec: ModelSpec,
    tensors: dict[str, HostTensor],
    mode: str = "dense",
    dtype=jnp.float32,
) -> dict:
    """Build the params pytree from a dict of file tensors: the streamed
    loader's placement (models/loader.py), fed in plan order, on the default
    device and with no leaf fused."""
    from .loader import load_params_streamed

    feed = (tensors[name] for name, _, _ in model_tensor_plan(spec))
    return load_params_streamed(spec, None, mode=mode, dtype=dtype,
                                fuse=False, tensors=feed)[0]


def _concat_weights(ws: list):
    """Concatenate matmul weights along the output dim (device-side)."""
    if isinstance(ws[0], QuantizedTensor):
        return QuantizedTensor(
            jnp.concatenate([w.packed for w in ws], axis=0),
            jnp.concatenate([w.scales for w in ws], axis=0),
        )
    return jnp.concatenate(ws, axis=0)


def fuse_layer_weights(params: dict) -> dict:
    """Fuse each single-shard group (models/tensors.FUSION_GROUPS: QKV ->
    wqkv, w1|w3 -> w13, an SSM layer's gate and x -> wzx) along the output
    dim, IN PLACE, in the layers that hold all its members.

    Single-shard (tp == 1) fast path: decode is DMA-latency-bound per kernel
    call, so 3 calls sharing one input become 1 call with a 3x deeper grid
    (measured win on v5e). Not applied under tensor parallelism: the fused
    output dim would shard across the q|k|v segment boundaries, breaking the
    reference's RowMatmulSlice semantics (ref: src/transformer.cpp:14-46).
    Mutates the layer dicts so the superseded per-projection device buffers
    are actually freed even while the caller still holds the params dict
    (at 7B Q40 they are ~2.5 GB of HBM)."""
    for lw in params["layers"]:
        for group, members in FUSION_GROUPS.items():
            if all(m in lw for m in members):  # a latent wq has no wk / wv
                lw[group] = _concat_weights([lw.pop(m) for m in members])
    return params


def _split_rows(w, cuts: list[int]) -> list:
    """Split a matmul weight back along the output dim at `cuts`."""
    if isinstance(w, QuantizedTensor):
        return [QuantizedTensor(w.packed[a:b], w.scales[a:b])
                for a, b in zip([0] + cuts, cuts + [w.packed.shape[0]])]
    return [w[a:b] for a, b in zip([0] + cuts, cuts + [w.shape[0]])]


def unfuse_layer_weights(params: dict, spec: ModelSpec) -> dict:
    """Inverse of fuse_layer_weights (exact row slices at the members'
    declared row counts), for engines built at tp > 1 from a params dict
    another (tp == 1) engine already fused — fuse mutates in place, and a
    row split of the fused [q|k|v] output dim does not align with the
    projection boundaries, which the fully-manual pp region (unlike GSPMD,
    whose sharding never changes semantics) would silently miscompute.
    No-op when nothing is fused."""
    if not any(g in lw for lw in params["layers"] for g in FUSION_GROUPS):
        return params
    params = dict(params)
    params["layers"] = [dict(lw) for lw in params["layers"]]
    for l, lw in enumerate(params["layers"]):
        for group in FUSION_GROUPS:
            if group in lw:
                members = group_members(spec, l, group)
                cuts = list(itertools.accumulate(
                    t.shape(spec)[0] for t in members[:-1]))
                for t, w in zip(members, _split_rows(lw.pop(group), cuts)):
                    lw[t.name] = w
    return params


def kv_replication(spec: ModelSpec, tp: int) -> int:
    """Replication factor r for tp > n_kv_heads, validating the config.

    Relaxes the reference's hard `nSlices <= nKvHeads` constraint
    (ref: src/transformer.cpp:254-257) — the planned extension the reference
    could not do (SURVEY.md §7 step 4): GQA models with few kv heads (e.g.
    70B's 8) can now shard over more chips (tp=16) by replicating each kv
    head's projections and cache r = tp/n_kv_heads times as tp "virtual"
    heads (virtual head j holds real head j//r). Query heads stay
    contiguously sharded — shard s's H/tp query heads all belong to virtual
    head s, so attention remains head-local like the reference's
    MultiHeadAttSlice. Aggregate kv projection + cache memory grows r-fold,
    but PER-DEVICE cache stays one head's worth — the same as at
    tp = n_kv_heads — while per-device weights and FLOPs keep shrinking.
    """
    kvh = spec.n_kv_heads
    assert tp % kvh == 0, (
        f"tp={tp} must be a multiple of n_kv_heads={kvh} to replicate")
    assert spec.n_heads % tp == 0, (
        f"tp={tp} must divide n_heads={spec.n_heads}")
    return tp // kvh


def _repeat_head_rows(a, kvh: int, r: int):
    """Repeat row-blocks of axis 0 (grouped per kv head) r times, so virtual
    head j = real head j // r. Works for dense (kv_dim, n), Q40 packed
    (kv_dim, m) and scales (kv_dim, nb)."""
    per = a.shape[0] // kvh
    rep = jnp.repeat(jnp.asarray(a).reshape(kvh, per, *a.shape[1:]), r, axis=0)
    return rep.reshape(kvh * r * per, *a.shape[1:])


def replicate_kv_heads(params: dict, spec: ModelSpec, tp: int) -> dict:
    """Expand wk/wv to tp virtual heads (see kv_replication). Non-mutating
    (fresh layer dicts, like repack_col_weights — callers may keep using
    the original pytree); idempotent (already-expanded leaves are detected
    by their row count, so loader-expanded params pass through)."""
    r = kv_replication(spec, tp)
    if r == 1:
        return params
    kvh = spec.n_kv_heads
    params = dict(params)
    params["layers"] = [dict(lw) for lw in params["layers"]]
    for lw in params["layers"]:
        for key in ("wk", "wv"):
            w = lw.get(key)
            if w is None:
                continue  # fused wqkv exists only on the tp==1 path
            if isinstance(w, QuantizedTensor):
                if w.packed.shape[0] == spec.kv_dim * r:
                    continue
                assert w.packed.shape[0] == spec.kv_dim, w.packed.shape
                lw[key] = QuantizedTensor(
                    _repeat_head_rows(w.packed, kvh, r),
                    _repeat_head_rows(w.scales, kvh, r))
            else:
                if w.shape[0] == spec.kv_dim * r:
                    continue
                assert w.shape[0] == spec.kv_dim, w.shape
                lw[key] = _repeat_head_rows(w, kvh, r)
    return params


def random_tensors(spec: ModelSpec, seed: int = 0, scale: float = 0.02) -> dict[str, HostTensor]:
    """Synthetic host tensors for tests/benchmarks (numpy RNG, not xorshift —
    speed matters at 8B scale)."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape, ftype in model_tensor_plan(spec):
        x = (rng.standard_normal(shape, dtype=np.float32) * scale)
        if ftype == FloatType.Q40:
            out[name] = to_q40_host(x)
            out[name].name = name
        else:
            out[name] = HostTensor(name, FloatType.F32, shape, data=x)
    return out
