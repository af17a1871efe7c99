"""Compile the serving step programs for a DESCRIBED v5e:2x2 topology — no
chip attached, nothing runs (on-chip-measurement guide, section 2).

    JAX_PLATFORMS=cpu python tools/rehearse_chip_compile.py            # 7B, 32 layers
    JAX_PLATFORMS=cpu python tools/rehearse_chip_compile.py --layers 2

What this shows that interpret-mode tests cannot: whether the TPU compiler
accepts every kernel inside the WHOLE decode and slot-prefill programs the
engine would mint (one chip, and tp=4 with the q80 and the exact reduce),
what each takes per device (`memory_analysis`), and which kernels and
collectives the compiler put in. A compile that passes is not a chip run:
it says nothing about results or times.

The engine builds its mesh from `jax.devices()` and places real arrays,
which a described device cannot hold — so the helpers below rebuild the
params pytree the way `Engine.__init__` does (fuse at tp == 1; repack col
weights + wrap row weights at tp > 1; `param_pspecs` shardings) over
`jax.eval_shape`d leaves, and jit the same `forward()` call
`Engine.slot_decode_step` / `slot_prefill_chunk` wrap. `Engine` picks the
kernel path from `jax.default_backend()`, which is `cpu` here: callers
pass use_pallas=True. tests/test_chip_compile.py keeps a few of these
compiles (cut to 2 layers) as tier-1 tests.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from distributed_llama_tpu.models.spec import (ArchType, HiddenAct,  # noqa: E402
                                               LayerKind, ModelSpec)
from distributed_llama_tpu.quants.jax_codec import QuantizedTensor  # noqa: E402

LLAMA2_7B = ModelSpec(arch=ArchType.LLAMA, dim=4096, hidden_dim=11008,
                      n_layers=32, n_heads=32, n_kv_heads=32,
                      vocab_size=32000, seq_len=2048,
                      hidden_act=HiddenAct.SILU)
MISTRAL_7B = ModelSpec(arch=ArchType.LLAMA, dim=4096, hidden_dim=14336,
                       n_layers=32, n_heads=32, n_kv_heads=8,
                       vocab_size=32000, seq_len=4096,
                       hidden_act=HiddenAct.SILU, rope_theta=1e6)
MIXTRAL_8X7B = ModelSpec(arch=ArchType.MIXTRAL, dim=4096, hidden_dim=14336,
                         n_layers=32, n_heads=32, n_kv_heads=8,
                         vocab_size=32000, seq_len=2048,
                         hidden_act=HiddenAct.SILU, n_experts=8,
                         n_active_experts=2, rope_theta=1e6)
# sarvam-105b as benchmark/configs/sarvam-105b-ep8.json serves it: one
# chip's share of 8 (16 of 128 routed experts, an eighth of the vocabulary)
SARVAM_105B_EP8 = ModelSpec(
    arch=ArchType.SARVAM_MLA, dim=4096, hidden_dim=2048, n_layers=32,
    n_heads=64, n_kv_heads=1, vocab_size=32768, seq_len=8192,
    hidden_act=HiddenAct.SILU, n_experts=16, n_active_experts=8,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, n_dense_layers=1, dense_hidden_dim=16384,
    n_shared_experts=1, n_routed_experts=128, routed_scaling=2.5,
    rms_eps=1e-6, rope_factor=40.0, rope_orig_len=4096,
    rope_mscale_all_dim=1.0)
# Olmo-Hybrid-7B as benchmark/configs/olmo-hybrid-7b.json serves it: whole
# on one chip, (DELTA x 3, ATTENTION) x 8
OLMO_HYBRID_7B = ModelSpec(
    arch=ArchType.OLMO_HYBRID, dim=3840, hidden_dim=11008, n_layers=32,
    n_heads=30, n_kv_heads=30, vocab_size=100352, seq_len=8192,
    hidden_act=HiddenAct.SILU, rope_theta=0.0, rms_eps=1e-6,
    mixers=((int(LayerKind.DELTA),) * 3
            + (int(LayerKind.ATTENTION),)) * 8, lin_heads=30, lin_k_head_dim=96,
    lin_v_head_dim=192, lin_conv_width=4, lin_beta_scale=2)


# granite-4.0-h-small as benchmark/configs/granite-4.0-h-small-ep2.json
# serves it: one chip's share of 2 (36 of 72 routed experts, half the
# vocabulary), (SSM x 5, ATTENTION, SSM x 4) x 4
GRANITE_4_H_SMALL_EP2 = ModelSpec(
    arch=ArchType.GRANITE_HYBRID, dim=4096, hidden_dim=768, n_layers=40,
    n_heads=32, n_kv_heads=8, vocab_size=50176, seq_len=8192,
    hidden_act=HiddenAct.SILU, rope_theta=0.0, rms_eps=1e-5, n_experts=36,
    n_active_experts=10, n_shared_experts=2, n_routed_experts=72,
    mixers=((int(LayerKind.SSM),) * 5 + (int(LayerKind.ATTENTION),)
            + (int(LayerKind.SSM),) * 4) * 4,
    ssm_heads=128, ssm_head_dim=64, ssm_d_state=128, ssm_groups=1,
    ssm_conv_width=4, ssm_conv_bias=1, embedding_scale=12.0,
    residual_scale=0.22, attn_scale=0.0078125, logit_scale=0.0625)


# Kimi-Linear-48B-A3B as benchmark/configs/kimi-linear-48b-a3b-ep4.json
# serves it: one chip's share of 4 (64 of 256 routed experts, a quarter of
# the vocabulary), (KDA x 3, LATENT) x 6 and the tail KDA x 2, LATENT
KIMI_LINEAR_48B_EP4 = ModelSpec(
    arch=ArchType.KIMI_LINEAR, dim=2304, hidden_dim=1024, n_layers=27,
    n_heads=32, n_kv_heads=1, vocab_size=40960, seq_len=8192,
    hidden_act=HiddenAct.SILU, rope_theta=0.0, rms_eps=1e-5, n_experts=64,
    n_active_experts=8, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, n_dense_layers=1,
    dense_hidden_dim=9216, n_shared_experts=1, n_routed_experts=256,
    routed_scaling=2.446,
    mixers=((int(LayerKind.DELTA),) * 3 + (int(LayerKind.LATENT),)) * 6
    + (int(LayerKind.DELTA),) * 2 + (int(LayerKind.LATENT),),
    lin_heads=32, lin_k_head_dim=128, lin_v_head_dim=128, lin_conv_width=4,
    lin_beta_scale=1, lin_decay_dim=128)


# AI21-Jamba2-3B as benchmark/configs/jamba2-3b.json serves it: whole on one
# chip, 8,192 positions a slot, selective scans (Mamba-1) with layers 7
# and 21 attending through 20 query heads on ONE KV head
JAMBA2_3B = ModelSpec(
    arch=ArchType.JAMBA, dim=2560, hidden_dim=8192, n_layers=28, n_heads=20,
    n_kv_heads=1, vocab_size=65536, seq_len=8192,
    hidden_act=HiddenAct.SILU, rope_theta=0.0, rms_eps=1e-6,
    mixers=tuple(int(LayerKind.ATTENTION if l % 14 == 7 else LayerKind.SSM)
                 for l in range(28)),
    ssm_heads=5120, ssm_head_dim=1, ssm_d_state=16, ssm_groups=1,
    ssm_conv_width=4, ssm_conv_bias=1, ssm_dt_rank=160)


def hybrid_layers(spec: ModelSpec, periods: int, period: int = 4) -> ModelSpec:
    """The first `periods` periods of a hybrid's layer pattern."""
    n = period * periods
    return dataclasses.replace(spec, n_layers=n, mixers=spec.mixers[:n])


def describe_topology():
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def q40_struct(*shape: int) -> QuantizedTensor:
    """Abstract Q40 weight of logical shape (..., d, n) in device layout."""
    nb = shape[-1] // 32
    return QuantizedTensor(
        jax.ShapeDtypeStruct((*shape[:-1], 16 * nb), jnp.uint8),
        jax.ShapeDtypeStruct((*shape[:-1], nb), jnp.uint16))


def _zeros_q40(*shape: int) -> QuantizedTensor:
    s = q40_struct(*shape)
    return QuantizedTensor(jnp.zeros(s.packed.shape, jnp.uint8),
                           jnp.zeros(s.scales.shape, jnp.uint16))


def _loaded_params(spec: ModelSpec, dtype) -> dict:
    """The pytree models/loader hands the engine (q40 mode), of zeros —
    only ever built under jax.eval_shape."""
    d, h, kv = spec.dim, spec.hidden_dim, spec.kv_dim
    layers = []
    for l in range(spec.n_layers):
        lw = {"rms_att": jnp.ones((d,), jnp.float32),
              "rms_ffn": jnp.ones((d,), jnp.float32)}
        if spec.layer_kinds[l] == LayerKind.DELTA:
            nh, dk, dv = (spec.lin_heads, spec.lin_k_head_dim,
                          spec.lin_v_head_dim)
            kda = spec.lin_vector_decay
            lw.update(
                wq=_zeros_q40(nh * dk, d), wk=_zeros_q40(nh * dk, d),
                wv=_zeros_q40(nh * dv, d), wo=_zeros_q40(d, nh * dv),
                conv_w=jnp.zeros((spec.lin_conv_width, spec.lin_conv_dim),
                                 jnp.float32),
                a_log=jnp.zeros((nh,), jnp.float32),
                dt_bias=jnp.zeros((nh * spec.lin_decay_dim,), jnp.float32),
                rms_o=jnp.ones((dv,), jnp.float32))
            if kda:     # the thin projections of models/params.load_params
                lw.update(w_fgb=jnp.zeros((dk + nh + dv, d), dtype),
                          wf_b=jnp.zeros((nh * dk, dk), dtype),
                          wg_b=jnp.zeros((nh * dv, dv), dtype))
            else:
                lw.update(wg=_zeros_q40(nh * dv, d),
                          w_ab=jnp.zeros((2 * nh, d), dtype))
        elif spec.layer_kinds[l] == LayerKind.SSM and spec.ssm_selective:
            inner, n, r = spec.ssm_inner, spec.ssm_d_state, spec.ssm_dt_rank
            lw.update(
                wz=_zeros_q40(inner, d), wx=_zeros_q40(inner, d),
                wo=_zeros_q40(d, inner),
                wxp=jnp.zeros((r + 2 * n, inner), dtype),
                wdt=jnp.zeros((inner, r), dtype),
                conv_w=jnp.zeros((spec.ssm_conv_width, inner), jnp.float32),
                conv_b=jnp.zeros((inner,), jnp.float32),
                a_log=jnp.zeros((n, inner), jnp.float32),
                dt_bias=jnp.zeros((inner,), jnp.float32),
                ssm_d=jnp.ones((inner,), jnp.float32),
                rms_dt=jnp.ones((r,), jnp.float32),
                rms_b=jnp.ones((n,), jnp.float32),
                rms_c=jnp.ones((n,), jnp.float32))
        elif spec.layer_kinds[l] == LayerKind.SSM:
            nh, inner = spec.ssm_heads, spec.ssm_inner
            lw.update(
                wz=_zeros_q40(inner, d), wx=_zeros_q40(inner, d),
                wo=_zeros_q40(d, inner),
                w_bcdt=jnp.zeros((spec.ssm_conv_dim - inner + nh, d), dtype),
                conv_w=jnp.zeros((spec.ssm_conv_width, spec.ssm_conv_dim),
                                 jnp.float32),
                conv_b=jnp.zeros((spec.ssm_conv_dim,), jnp.float32),
                a_log=jnp.zeros((nh,), jnp.float32),
                dt_bias=jnp.zeros((nh,), jnp.float32),
                ssm_d=jnp.ones((nh,), jnp.float32),
                rms_o=jnp.ones((inner,), jnp.float32))
        elif spec.is_mla:
            nh, r = spec.n_heads, spec.kv_lora_rank
            lw.update(
                rms_kv=jnp.ones((r,), jnp.float32),
                wq=_zeros_q40(nh * spec.head_size, d),
                wkva=_zeros_q40(r + spec.qk_rope_head_dim, d),
                w_uk=jnp.zeros((nh, spec.qk_nope_head_dim, r), dtype),
                w_uv=jnp.zeros((nh, spec.v_head_dim, r), dtype),
                wo=_zeros_q40(d, nh * spec.v_head_dim))
        else:
            lw.update(wq=_zeros_q40(d, d), wk=_zeros_q40(kv, d),
                      wv=_zeros_q40(kv, d), wo=_zeros_q40(d, d))
            if spec.post_norm:
                lw.update(rms_q=jnp.ones((d,), jnp.float32),
                          rms_k=jnp.ones((kv,), jnp.float32))
        if spec.is_mla and spec.is_dense_layer(l):
            hd = spec.dense_hidden_dim
            lw.update(w1=_zeros_q40(hd, d), w2=_zeros_q40(d, hd),
                      w3=_zeros_q40(hd, d))
        elif spec.is_moe:
            e = spec.n_experts
            if spec.is_mla:
                lw.update(moe_bias=jnp.zeros((spec.router_width,),
                                             jnp.float32))
            if spec.n_shared_experts:
                sh = spec.n_shared_experts * h
                lw.update(sh_w1=_zeros_q40(sh, d), sh_w2=_zeros_q40(d, sh),
                          sh_w3=_zeros_q40(sh, d))
            lw.update(moe_router=jnp.zeros((spec.router_width, d), dtype),
                      moe_up=_zeros_q40(e, h, d),
                      moe_gate=_zeros_q40(e, h, d),
                      moe_down=_zeros_q40(e, d, h))
        else:
            lw.update(w1=_zeros_q40(h, d), w2=_zeros_q40(d, h),
                      w3=_zeros_q40(h, d))
        layers.append(lw)
    return {"tok_emb": jnp.zeros((spec.vocab_size, d), dtype),
            "layers": layers, "rms_final": jnp.ones((d,), jnp.float32),
            "wcls": _zeros_q40(spec.vocab_size, d)}


def abstract_step(spec: ModelSpec, devices, *, tp: int = 1, batch: int,
                  t: int, seq_len: int, q80: bool = False,
                  dtype=jnp.bfloat16, slot_map: bool | None = None,
                  summary: bool | None = None):
    """(jitted step, abstract args) for the slot program of shape (B, T):
    T == 1 is `slot_decode_step`, T > 1 `slot_prefill_chunk_T`. `devices`
    are described devices; tp > 1 lays them out as the engine's mesh.
    slot_map: whether the chunk's rows follow a slot map (an argument
    `slots` after the cache); None decides as `Engine.__init__` does, from
    the layer kinds and the mesh. Without a mesh both programs end, as the
    engine's do (Engine._with_summary), with the sampling summary of their
    logits: the last argument holds its temperatures, its vocabulary and
    whether the step computes it, the last output is its packed leaf
    (summary=False: the program without, to compare with)."""
    from distributed_llama_tpu.models.params import fuse_layer_weights
    from distributed_llama_tpu.models.transformer import (KVCache, forward,
                                                          takes_slot_map)
    from distributed_llama_tpu.ops.sharded_vocab import (step_summary,
                                                         vocab_shard_axes)
    from distributed_llama_tpu.parallel.mesh import make_mesh
    from distributed_llama_tpu.parallel.sharding import (
        cache_pspec, check_tp_constraints, param_pspecs, repack_col_weights,
        wrap_row_weights)

    mesh = vocab_axes = None
    if tp > 1:
        mesh = make_mesh(tp=tp, devices=devices[:tp])
        vocab_axes = vocab_shard_axes(mesh, spec.vocab_size)
        check_tp_constraints(spec, tp, q40=True)

    def build():
        params = _loaded_params(spec, dtype)
        if tp == 1:
            return fuse_layer_weights(params)
        return wrap_row_weights(repack_col_weights(params, tp))

    params = jax.eval_shape(build)
    if tp == 1:
        one = SingleDeviceSharding(devices[0])
        place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)
        params, cache_sh, rep = place(params), one, one
    else:
        specs = param_pspecs(params, vocab_axes or None)
        params = jax.tree_util.tree_map(
            lambda s, ps: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, ps)),
            params, specs)
        cache_sh = NamedSharding(mesh, cache_pspec())
        rep = NamedSharding(mesh, P())
    cache = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=cache_sh),
        jax.eval_shape(lambda: KVCache.create(spec, batch, seq_len, dtype)))
    tokens = jax.ShapeDtypeStruct((batch, t), jnp.int32, sharding=rep)
    pos = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=rep)
    common = dict(  # Engine._forward_kwargs with the kernels on
        activation_q80=q80, compute_dtype=dtype, use_pallas=True,
        tp_mesh=mesh, tp_reduce="q80" if q80 else "exact",
        vocab_mesh=mesh if vocab_axes else None,
        vocab_axes=vocab_axes or ("tp",),
        expert_counts=spec.is_moe)  # Engine._counts_experts

    sample = ()             # Engine._sample_operands
    if mesh is None if summary is None else summary:
        sample = (jax.ShapeDtypeStruct((batch + 2,), jnp.float32,
                                       sharding=rep),)

    def with_summary(out, sample):      # Engine._with_summary
        return (*out, step_summary(out[0], *sample)) if sample else out

    if t == 1:
        def slot_decode_step(params, tokens, pos0, cache, *sample):
            return with_summary(
                forward(params, spec, tokens, pos0, cache, **common), sample)

        return (jax.jit(slot_decode_step, donate_argnums=(3,)),
                (params, tokens, pos, cache, *sample))

    if slot_map is None:    # Engine._chunk_slot_map
        slot_map = takes_slot_map(spec, meshed=tp > 1)

    def slot_prefill_chunk(params, tokens, pos0, logit_index, cache, *rest):
        slots, sample = (rest[0], rest[1:]) if slot_map else (None, rest)
        return with_summary(
            forward(params, spec, tokens, pos0, cache,
                    logit_index=logit_index, **common, slots=slots), sample)

    return (jax.jit(slot_prefill_chunk, donate_argnums=(4,)),
            (params, tokens, pos, pos, cache, *((pos,) if slot_map else ()),
             *sample))


def cache_shaped_copies(compiled_text: str, leaf_shape) -> list[str]:
    """The `copy` instructions of a compiled module whose result has the
    (per-device) cache leaf's shape: XLA re-laying a whole K or V cache
    around an update of a few rows (PERF.md section 6, PR 27)."""
    import re

    dims = re.escape(f"[{','.join(map(str, leaf_shape))}]")
    return [ln.strip() for ln in compiled_text.splitlines()
            if re.search(rf"= \w+{dims}\S* copy\(", ln)]


def expert_sized_results(compiled_text: str, spec: ModelSpec) -> list[str]:
    """The instructions of a compiled module, those inside fusions too, whose
    result is ONE EXPERT'S packed Q40 matrix — an expert sliced out of its
    stacked (E, d, m) leaf into HBM before a kernel may read it, which cost
    36 % of `mixtral-8x7b-12l`'s device time (PERF.md section 6, PR 31) —
    or a whole packed stack. Not counted: parameters, a loop's or a
    branch's name for one of its operands (`get-tuple-element`, which moves
    nothing: the grouped experts' waves carry the stacks into their loop by
    reference, models/transformer._grouped_experts), and the compiler's own
    prefetches into VMEM (`S(1)` results of copy-start/-done and of the
    `ConcatBitcast` custom call of a sliced prefetch), which read a weight
    once in place of the kernel's read — a stack, or a dense weight of an
    expert's shape (sarvam's shared expert). The scales are not judged
    here: a stack of them whose block count is not whole lane tiles
    (`moe_down`) is re-laid once a layer, as a dense w2's are."""
    import re

    e, h, d = spec.n_experts, spec.hidden_dim, spec.dim
    shapes = {f"[{h},{d // 2}]", f"[{d},{h // 2}]",
              f"[{e},{h},{d // 2}]", f"[{e},{d},{h // 2}]"}
    prefetch = ("custom-call", "copy-start", "copy-done")
    found = []
    for ln in compiled_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?\S+ = u8(\[[\d,]*\])(\S*) ([\w-]+)\(", ln)
        if m and m.group(1) in shapes and m.group(3) not in (
                "parameter", "get-tuple-element") and not (
                m.group(3) in prefetch and "S(1)" in m.group(2)):
            found.append(ln.strip())
    return found


def compile_report(fn, args) -> dict:
    """Compile for the described devices; what the compiler put in."""
    import re

    from distributed_llama_tpu.runtime.profiler import kernel_call_sites

    t0 = time.time()
    lowered = fn.lower(*args)
    compiled = lowered.compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    return {
        "compile_s": round(time.time() - t0, 1),
        "tpu_custom_calls": text.count('custom_call_target="tpu_custom_call"'),
        "kernels": kernel_call_sites(lowered.as_text()),
        "collectives": {op: len(re.findall(rf"= \S+ {op}(?:-start)?\(", text))
                        for op in ("all-reduce", "all-gather", "all-to-all",
                                   "collective-permute")},
        "argument_gib": round(mem.argument_size_in_bytes / 2**30, 2),
        "temp_gib": round(mem.temp_size_in_bytes / 2**30, 2),
        "alias_gib": round(mem.alias_size_in_bytes / 2**30, 2)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--layers", type=int, default=32)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=32)
    ap.add_argument("--seq-len", type=int, default=1024)
    args = ap.parse_args()
    import dataclasses

    # never read back without a chip: keep these compiles out of the cache
    jax.config.update("jax_enable_compilation_cache", False)
    devices = describe_topology().devices
    spec = dataclasses.replace(LLAMA2_7B, n_layers=args.layers)
    for tp, q80 in ((1, False), (4, True), (4, False)):
        for t in (1, args.chunk):
            fn, a = abstract_step(spec, devices, tp=tp, batch=args.batch,
                                  t=t, seq_len=args.seq_len, q80=q80)
            rep = compile_report(fn, a)
            print(f"llama2-7b L={args.layers} tp={tp} "
                  f"reduce={'q80' if q80 else 'exact'} B={args.batch} T={t}: "
                  f"{rep}", flush=True)


if __name__ == "__main__":
    main()
