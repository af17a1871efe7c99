"""Model parameters as a JAX pytree.

Structure: {"tok_emb", "rms_final", "wcls", "layers": [<per-layer dict>, ...]}
— each layer's weights are standalone device arrays (no stacked (L, ...)
axis). The forward pass statically unrolls over `layers` (the TPU analogue
of the reference's flat per-layer task list, ref: src/llama2-tasks.cpp:
249-275); standalone buffers feed the fused Q40 kernel in place, with no
per-step slice/copy, and per-layer loading never materializes a stacked
host copy (important for the 70B path — each tensor moves host -> device
individually via the `put` hook).

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

from typing import Callable

import jax.numpy as jnp
import numpy as np

from ..io.model_file import HostTensor, model_tensor_plan
from ..quants.jax_codec import QuantizedTensor
from ..quants.numpy_codec import quantize_q40
from ..quants.types import FloatType
from .spec import ArchType, LayerKind, ModelSpec


# a KDA layer's thin projections of the stream, in the (file) order of their
# one dense leaf `w_fgb`: the decay's first half, the step's rows, the gate's
KDA_THIN_ROWS = ("wf_a", "wbeta", "wg_a")


def _to_q40_host(x: np.ndarray) -> HostTensor:
    scales, packed = quantize_q40(x.reshape(-1, x.shape[-1]))
    t = HostTensor("", FloatType.Q40, x.shape, scales=scales, packed=packed)
    return t


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
    put: Callable | None = None,
) -> dict:
    """Build the params pytree from file tensors.

    `put` optionally maps (name, np array | host QuantizedTensor) -> device
    array — the hook a sharded streaming loader uses for direct multi-chip
    placement; defaults to plain jnp.asarray.
    """
    assert mode in ("dense", "q40")
    dev = put or (lambda name, x: x if isinstance(x, QuantizedTensor) else jnp.asarray(x))

    def weight(t: HostTensor, name: str):
        """One matmul weight in the requested storage mode."""
        if mode == "q40":
            if t.ftype != FloatType.Q40:
                t = _to_q40_host(t.to_f32())
            return dev(name, QuantizedTensor.from_numpy(t.scales, t.packed))
        return dev(name, t.to_f32().astype(dtype))

    def moe_weight(ts: list[HostTensor], name: str):
        """Stacked (E, ...) expert weight (experts stay stacked so decode can
        dynamic-gather the active ones)."""
        if mode == "q40":
            qs = [t if t.ftype == FloatType.Q40 else _to_q40_host(t.to_f32()) for t in ts]
            packed = np.stack([q.packed for q in qs])
            scales = np.stack([q.scales for q in qs])
            return dev(name, QuantizedTensor.from_numpy(scales, packed))
        dense = np.stack([t.to_f32() for t in ts]).astype(dtype)
        return dev(name, dense)

    p: dict = {}
    p["tok_emb"] = dev("tok_emb", tensors["tok_emb"].to_f32().astype(dtype))
    layers = []
    for l in range(spec.n_layers):
        lw: dict = {}
        lw["rms_att"] = dev(f"layers.{l}.rms_att", tensors[f"layers.{l}.rms_att"].to_f32())
        lw["rms_ffn"] = dev(f"layers.{l}.rms_ffn", tensors[f"layers.{l}.rms_ffn"].to_f32())
        if spec.arch == ArchType.GROK1:
            lw["rms_moe"] = dev(f"layers.{l}.rms_moe", tensors[f"layers.{l}.rms_moe"].to_f32())
            lw["rms_ffn2"] = dev(f"layers.{l}.rms_ffn2", tensors[f"layers.{l}.rms_ffn2"].to_f32())
        if spec.layer_kinds[l] == LayerKind.DELTA:
            kda = spec.lin_vector_decay
            for w in ("wq", "wk", "wv", "wo") + (() if kda else ("wg",)):
                lw[w] = weight(tensors[f"layers.{l}.{w}"], f"layers.{l}.{w}")
            # the thin projections, dense on the device, ONE leaf: decay
            # and beta rows; for KDA the decay's and the gate's first
            # halves with the step's rows, and their second halves (d_k
            # columns: four Q40 blocks a row) a leaf each
            thin, rows = (("w_fgb", KDA_THIN_ROWS) if kda
                          else ("w_ab", ("wa", "wb")))
            lw[thin] = dev(f"layers.{l}.{thin}", np.concatenate(
                [tensors[f"layers.{l}.{w}"].to_f32()
                 for w in rows]).astype(dtype))
            for w in ("wf_b", "wg_b") if kda else ():
                lw[w] = dev(f"layers.{l}.{w}",
                            tensors[f"layers.{l}.{w}"].to_f32().astype(dtype))
            for w in ("conv_w", "a_log", "dt_bias", "rms_o"):
                lw[w] = dev(f"layers.{l}.{w}",
                            tensors[f"layers.{l}.{w}"].to_f32())
        elif spec.layer_kinds[l] == LayerKind.SSM:
            for w in ("wz", "wx", "wo"):
                lw[w] = weight(tensors[f"layers.{l}.{w}"], f"layers.{l}.{w}")
            # B | C and dt rows: thin projections, one dense leaf
            lw["w_bcdt"] = dev(f"layers.{l}.w_bcdt", np.concatenate(
                [tensors[f"layers.{l}.{w}"].to_f32()
                 for w in ("wbc", "wdt")]).astype(dtype))
            for w in ("conv_w", "conv_b", "a_log", "dt_bias", "ssm_d",
                      "rms_o"):
                if f"layers.{l}.{w}" in tensors:    # conv_b: with a bias
                    lw[w] = dev(f"layers.{l}.{w}",
                                tensors[f"layers.{l}.{w}"].to_f32())
        elif spec.layer_kinds[l] == LayerKind.LATENT:
            lw["rms_kv"] = dev(f"layers.{l}.rms_kv",
                               tensors[f"layers.{l}.rms_kv"].to_f32())
            for w in ("wq", "wkva", "wo"):
                lw[w] = weight(tensors[f"layers.{l}.{w}"], f"layers.{l}.{w}")
            w_uk, w_uv = split_wkvb(
                spec, tensors[f"layers.{l}.wkvb"].to_f32())
            lw["w_uk"] = dev(f"layers.{l}.w_uk", w_uk.astype(dtype))
            lw["w_uv"] = dev(f"layers.{l}.w_uv", w_uv.astype(dtype))
        else:
            for w in ("wq", "wk", "wv", "wo"):
                lw[w] = weight(tensors[f"layers.{l}.{w}"],
                               f"layers.{l}.{w}")
            if spec.post_norm:
                for w in ("rms_q", "rms_k"):
                    lw[w] = dev(f"layers.{l}.{w}",
                                tensors[f"layers.{l}.{w}"].to_f32())
        if not spec.is_dense_layer(l):
            if spec.is_mla:
                lw["moe_bias"] = dev(f"layers.{l}.moe_bias",
                                     tensors[f"layers.{l}.moe_bias"].to_f32())
            if spec.n_shared_experts:
                for w in ("sh_w1", "sh_w2", "sh_w3"):
                    lw[w] = weight(tensors[f"layers.{l}.{w}"],
                                   f"layers.{l}.{w}")
            lw["moe_router"] = dev(
                f"layers.{l}.moe_router",
                tensors[f"layers.{l}.moe_router"].to_f32().astype(dtype))
            for w in ("up", "gate", "down"):
                ts = [tensors[f"layers.{l}.experts.{e}.{w}"] for e in range(spec.n_experts)]
                lw[f"moe_{w}"] = moe_weight(ts, f"layers.{l}.moe_{w}")
        else:
            for w in ("w1", "w2", "w3"):
                lw[w] = weight(tensors[f"layers.{l}.{w}"], f"layers.{l}.{w}")
        layers.append(lw)
    p["layers"] = layers
    p["rms_final"] = dev("rms_final", tensors["rms_final"].to_f32())
    p["wcls"] = weight(tensors["wcls"], "wcls")
    return p


def _concat_weights(ws: list):
    """Concatenate matmul weights along the output dim (device-side)."""
    if isinstance(ws[0], QuantizedTensor):
        return QuantizedTensor(
            jnp.concatenate([w.packed for w in ws], axis=0),
            jnp.concatenate([w.scales for w in ws], axis=0),
        )
    return jnp.concatenate(ws, axis=0)


def fuse_layer_weights(params: dict) -> dict:
    """Fuse QKV -> wqkv and w1|w3 -> w13 along the output dim, IN PLACE.

    Single-shard (tp == 1) fast path: decode is DMA-latency-bound per kernel
    call, so 3 calls sharing one input become 1 call with a 3x deeper grid
    (measured win on v5e). Not applied under tensor parallelism: the fused
    output dim would shard across the q|k|v segment boundaries, breaking the
    reference's RowMatmulSlice semantics (ref: src/transformer.cpp:14-46).
    Mutates the layer dicts so the superseded per-projection device buffers
    are actually freed even while the caller still holds the params dict
    (at 7B Q40 they are ~2.5 GB of HBM)."""
    for lw in params["layers"]:
        if "wq" in lw and "wk" in lw:  # SARVAM_MLA has wq and no wk/wv
            lw["wqkv"] = _concat_weights([lw.pop("wq"), lw.pop("wk"), lw.pop("wv")])
        if "w1" in lw:
            lw["w13"] = _concat_weights([lw.pop("w1"), lw.pop("w3")])
        if "wz" in lw:     # an SSM layer's gate and x: one call, 2x the grid
            lw["wzx"] = _concat_weights([lw.pop("wz"), lw.pop("wx")])
    return params


def _split_rows(w, cuts: list[int]) -> list:
    """Split a matmul weight back along the output dim at `cuts`."""
    if isinstance(w, QuantizedTensor):
        return [QuantizedTensor(w.packed[a:b], w.scales[a:b])
                for a, b in zip([0] + cuts, cuts + [w.packed.shape[0]])]
    return [w[a:b] for a, b in zip([0] + cuts, cuts + [w.shape[0]])]


def unfuse_layer_weights(params: dict, spec: ModelSpec) -> dict:
    """Inverse of fuse_layer_weights (exact row slices), for engines built
    at tp > 1 from a params dict another (tp == 1) engine already fused —
    fuse mutates in place, and a row split of the fused [q|k|v] output dim
    does not align with the projection boundaries, which the fully-manual
    pp region (unlike GSPMD, whose sharding never changes semantics) would
    silently miscompute. No-op when nothing is fused."""
    if not any("wqkv" in lw or "w13" in lw for lw in params["layers"]):
        return params
    d, kv, h = spec.dim, spec.kv_dim, spec.hidden_dim
    params = dict(params)
    params["layers"] = [dict(lw) for lw in params["layers"]]
    for lw in params["layers"]:
        if "wqkv" in lw:
            lw["wq"], lw["wk"], lw["wv"] = _split_rows(
                lw.pop("wqkv"), [d, d + kv])
        if "w13" in lw:
            lw["w1"], lw["w3"] = _split_rows(lw.pop("w13"), [h])
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
            out[name] = _to_q40_host(x)
            out[name].name = name
            out[name].shape = shape
        else:
            out[name] = HostTensor(name, FloatType.F32, shape, data=x)
    return out
