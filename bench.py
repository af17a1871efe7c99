"""Benchmark: Llama-2-7B Q40 decode ms/token on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} — plus a
"variants" list of additional measured rows (prefill throughput, 8k-fill
long-context decode with bf16 and fp8 caches, prompt-lookup speculative
decode, Mixtral-shaped MoE decode) taken in the same run so every
capability axis has on-chip perf evidence.

Every row names the device it ran on (`platform`, `device_kind`,
`device_count` as JAX reports them) and device metrics (MFU, effective HBM
bandwidth) use the peaks of THAT device kind (`DEVICE_PEAKS`; an accelerator
of unknown kind is an error, a CPU run reports them as null — a CPU number
is never written under a device metric's name). Each completed row is
flushed to stderr as it is measured; a mid-run failure or SIGTERM still
prints the final JSON line with every row completed so far plus an "error"
field, and exits NON-ZERO (`BENCH_SIMULATE_OUTAGE` is the test hook).
`vs_baseline` is the speedup over the reference's best published
single-node number for the benched model: Llama-2-7B = 101.81 ms/token
(30-vCPU GCP c3d, ref README.md:88), Llama-3-8B = 564.31 ms/token
(RasPi 5, ref README.md:61), Llama-2-13B = 184.19 ms/token (GCP c3d,
ref README.md:89). The reference publishes no MoE or long-context numbers
(SURVEY.md §6), so those rows carry vs_baseline: null.

Weights are synthetic Q40 blocks generated at the packed-byte level (random
nibbles + small f16 scales) — decode speed does not depend on weight values,
and this avoids materializing 28 GB of f32 on the host. The decode path is
the production one: Engine.decode_greedy_device (fully on-device lax.scan,
fused argmax, donated KV cache).

Env knobs: BENCH_MODEL=7b|8b|13b|moe|grok|70bt|tiny (8b = Llama-3-8B
GQA/128k-vocab, judged against the reference's best 1-node 8B number; 13b
vs its 13B GCP row; moe/grok = the production-width MoE configs below;
70bt = Llama-2-70B widths truncated to 4 layers — the per-layer cost of
the north-star shape on one chip), BENCH_TOKENS=<n decode steps>,
BENCH_SEQ/BENCH_FILL for long-context variants, BENCH_CACHE=f8 for the fp8
KV cache, BENCH_VARIANTS=0 to skip the extra rows, BENCH_SERVE=1 to add
the continuous-batching Poisson-arrival serving row (_serve_row;
BENCH_SERVE_REQUESTS/_BATCH/_BUDGETS size the trace), BENCH_PREFIX=1 to
add the radix prefix-cache shared-system-prompt row (_prefix_row;
BENCH_PREFIX_REQUESTS/_BATCH/_SYS/_BLOCK/_TOKENS size it), BENCH_CHAOS=1
to add the fault-injection resilience row (_chaos_row), BENCH_ROUTER=1 to
add the 2-replica failover-router row (_router_row; cache-aware vs
round-robin placement + one injected replica kill —
BENCH_ROUTER_REQUESTS/_BATCH/_GROUPS/_SYS/_BLOCK/_BLOCKS/_TOKENS/
_KILL_AFTER size it) plus the PROCESS-mode row (_router_procs_row; two
real replica worker OS processes, one SIGKILLed mid-trace —
respawn-to-routable ms, availability %, zero unstreamed failures, token
parity; BENCH_PROCS_REQUESTS/_TOKENS/_KILL_AFTER/_STEP_MS/
_SPAWN_TIMEOUT size it; BENCH_ROUTER_PROCS=0 skips it, =only runs just
it), and BENCH_AUTOTUNE=1 to add the closed batch-knee-loop row
(_autotune_row: tools/autotune.py calibration -> auto-sized batch ->
SLO-aware adaptive chunk admission, A/B'd against static settings on
goodput-at-SLO with greedy token parity and zero post-warmup compiles;
BENCH_AUTOTUNE_REQUESTS/_TOKENS/_BATCHES/_STATIC/_SLO_TTFT_MS/
_SLO_ITL_MS/_IAT/_LONG size it), BENCH_KVX=1 to add the cross-replica KV
block transfer row (_kvx_row: cold-replica fills OFF vs ON on a
shared-prefix trace — TTFT p50, fill hit rate, wire bytes reconciled —
plus the disaggregated prefill/decode A/B;
BENCH_KVX_FAMILIES/_SYS/_BLOCK/_TOKENS/_IAT/_LONG/_STREAMS size it),
BENCH_FLEET=1 to add the fleet-brain chaos row (_fleet_row: two tenants
through a 10x Poisson spike + one worker SIGKILL under the autoscaling
FleetController — victim p99 TTFT at SLO, replicas visibly scaling,
zero unstreamed failures;
BENCH_FLEET_REQUESTS/_VICTIM/_TOKENS/_STEP_MS/_SLO_MS/_IAT/
_SPAWN_TIMEOUT size it), and
BENCH_VOCAB=1 to add the
vocab-sharding A/B row (_vocab_row: sharded vs replicated embedding+head
on one mixed greedy/sampled trace over a tp mesh — greedy parity
asserted, per-chip embedding+wcls bytes and head+sample ms per variant,
zero frozen-ledger compiles; BENCH_VOCAB_TP/_BATCH/_REQUESTS/_TOKENS/
_STEPS size it), BENCH_SPEC=1 to add the REAL-draft
speculative-decoding row (_spec_row: truncated-depth self-draft vs
prompt-lookup vs plain greedy on a fixed-seed NON-repetitive eval with
the measured accept rate ON the row, plus a Poisson serving A/B with
per-slot drafts under --freeze-compiles semantics;
BENCH_SPEC_TOKENS/_DEPTH/_DRAFT_LEN/_REQUESTS/_BATCH/_TAIL size it).
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llama_tpu.models.spec import ArchType, HiddenAct, ModelSpec
from distributed_llama_tpu.quants.jax_codec import QuantizedTensor
from distributed_llama_tpu.runtime.engine import Engine
from distributed_llama_tpu.runtime.trace import TRACER

BASELINE_MS_PER_TOKEN = 101.81  # ref README.md:88 — Llama 2 7B, 1x GCP c3d-highcpu-30
BASELINE_8B_MS_PER_TOKEN = 564.31  # ref README.md:61 — Llama 3 8B, best 1-node (RasPi 5)
BASELINE_13B_MS_PER_TOKEN = 184.19  # ref README.md:89 — Llama 2 13B, 1x GCP c3d-highcpu-30

LLAMA2_7B = ModelSpec(
    arch=ArchType.LLAMA, dim=4096, hidden_dim=11008, n_layers=32,
    n_heads=32, n_kv_heads=32, vocab_size=32000, seq_len=2048,
    hidden_act=HiddenAct.SILU)

LLAMA2_13B = ModelSpec(  # 7.2 GB packed Q40 — fits one 16 GB chip
    arch=ArchType.LLAMA, dim=5120, hidden_dim=13824, n_layers=40,
    n_heads=40, n_kv_heads=40, vocab_size=32000, seq_len=2048,
    hidden_act=HiddenAct.SILU)

LLAMA3_8B = ModelSpec(  # GQA + 128k vocab (BASELINE.json config 2)
    arch=ArchType.LLAMA, dim=4096, hidden_dim=14336, n_layers=32,
    n_heads=32, n_kv_heads=8, vocab_size=128256, seq_len=2048,
    hidden_act=HiddenAct.SILU, rope_theta=500000.0)

TINY = ModelSpec(
    arch=ArchType.LLAMA, dim=256, hidden_dim=704, n_layers=4,
    n_heads=8, n_kv_heads=8, vocab_size=512, seq_len=256,
    hidden_act=HiddenAct.SILU)

MIXTRAL_MOE = ModelSpec(  # Mixtral 8x7B production dims, truncated to 4
    # layers so synth + host-to-device transfer stays bounded (~3.3 GB packed); decode
    # cost is per-layer linear, so ms/token/layer and the active-expert
    # effective bandwidth extrapolate to the full 32-layer model
    arch=ArchType.MIXTRAL, dim=4096, hidden_dim=14336, n_layers=4,
    n_heads=32, n_kv_heads=8, vocab_size=32000, seq_len=2048,
    hidden_act=HiddenAct.SILU, rope_theta=1000000.0,
    n_experts=8, n_active_experts=2)

LLAMA2_70B_TRUNC = ModelSpec(  # Llama-2-70B PRODUCTION widths (dim 8192,
    # hidden 28672, GQA 64/8 — the north-star model), truncated to 4
    # layers (~2.4 GB packed + embeddings): measures the per-layer decode
    # cost of the 70B SHAPE on real silicon, so the v5e-16 projection
    # (README) rests on a measured per-layer number, not the 7B's
    arch=ArchType.LLAMA, dim=8192, hidden_dim=28672, n_layers=4,
    n_heads=64, n_kv_heads=8, vocab_size=32000, seq_len=2048,
    hidden_act=HiddenAct.SILU)

GROK1_TRUNC = ModelSpec(  # Grok-1 PRODUCTION widths (dim 6144, 8 experts
    # of hidden 32768, GQA 48/8, 131k vocab, GELU, the 4-norm block —
    # ref: convert-grok-1.py:59-70 / grok1-tasks.cpp), truncated to 2
    # layers: one full-width layer is 2.72 GB packed Q40, so 2 layers +
    # embeddings (~7.6 GB) saturate a 16 GB chip while ms/token/layer
    # extrapolates to the full 64-layer model (VERDICT r4 #5)
    arch=ArchType.GROK1, dim=6144, hidden_dim=32768, n_layers=2,
    n_heads=48, n_kv_heads=8, vocab_size=131072, seq_len=2048,
    hidden_act=HiddenAct.GELU, rope_theta=10000.0,
    n_experts=8, n_active_experts=2)


def _rand_q40(rng: np.random.Generator, *shape: int) -> QuantizedTensor:
    """Random Q40 weight of logical shape (..., n): packed nibbles + scales
    sized so dequantized values land in a healthy ~N(0, 0.02) range.
    Generated directly in the device layout (..., 16*nb) flattened; scales
    as uint16 f16-bits as on device (quants/jax_codec.py)."""
    nb = shape[-1] // 32
    packed = rng.integers(0, 256, (*shape[:-1], 16 * nb), dtype=np.uint8)
    scales = (rng.random((*shape[:-1], nb), dtype=np.float32) * 0.004 + 0.001)
    sdt = os.environ.get("BENCH_SCALES", "u16")
    if sdt == "f32":
        return QuantizedTensor(jnp.asarray(packed), jnp.asarray(scales))
    return QuantizedTensor(jnp.asarray(packed),
                           jnp.asarray(scales.astype(np.float16).view(np.uint16)))


def synth_q40_params(spec: ModelSpec, seed: int = 0, dtype=jnp.bfloat16) -> dict:
    rng = np.random.default_rng(seed)
    d, h = spec.dim, spec.hidden_dim
    kv = spec.kv_dim
    layers = []
    for _ in range(spec.n_layers):
        lw = {
            "rms_att": jnp.ones((d,), jnp.float32),
            "rms_ffn": jnp.ones((d,), jnp.float32),
            "wq": _rand_q40(rng, d, d),
            "wk": _rand_q40(rng, kv, d),
            "wv": _rand_q40(rng, kv, d),
            "wo": _rand_q40(rng, d, d),
        }
        if spec.arch == ArchType.GROK1:  # the 4-norm Grok block
            lw["rms_moe"] = jnp.ones((d,), jnp.float32)
            lw["rms_ffn2"] = jnp.ones((d,), jnp.float32)
        if spec.is_moe:
            lw["moe_router"] = jnp.asarray(
                rng.standard_normal((spec.n_experts, d), dtype=np.float32)
                * 0.02, dtype)
            lw["moe_up"] = _rand_q40(rng, spec.n_experts, h, d)
            lw["moe_gate"] = _rand_q40(rng, spec.n_experts, h, d)
            lw["moe_down"] = _rand_q40(rng, spec.n_experts, d, h)
        else:
            lw["w1"] = _rand_q40(rng, h, d)
            lw["w2"] = _rand_q40(rng, d, h)
            lw["w3"] = _rand_q40(rng, h, d)
        layers.append(lw)
    return {
        "tok_emb": jnp.asarray(
            rng.standard_normal((spec.vocab_size, d), dtype=np.float32) * 0.02, dtype),
        "layers": layers,
        "rms_final": jnp.ones((d,), jnp.float32),
        "wcls": _rand_q40(rng, spec.vocab_size, d),
    }


# per-chip peaks keyed by `device_kind` (Google Cloud documentation, "TPU
# v5e": 197 TFLOP/s bf16, 819 GB/s HBM). A device that is not in the table
# is an error, not a default.
DEVICE_PEAKS = {"TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbs": 819.0}}


def _device_fields() -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "device_kind": d[0].device_kind,
            "device_count": len(d)}


def _device_peaks() -> dict | None:
    """Peaks of the device the bench runs on; None on the CPU backend (its
    rows carry no device metric); KeyError for an unknown accelerator."""
    d = jax.devices()[0]
    if d.platform == "cpu":
        return None
    if d.device_kind not in DEVICE_PEAKS:
        raise KeyError(f"no peaks recorded for device kind "
                       f"{d.device_kind!r} — add it to bench.DEVICE_PEAKS "
                       "with its source")
    return DEVICE_PEAKS[d.device_kind]


def _ffn_vals_per_layer(spec: ModelSpec) -> int:
    """Q40 values one decode step reads from a layer's FFN: dense = w1/w2/w3;
    MoE = the K active experts' up/gate/down (the gather path reads only the
    active experts' bytes — models/transformer._moe_ffn)."""
    d, h = spec.dim, spec.hidden_dim
    if spec.is_moe:
        return spec.n_active_experts * 3 * h * d
    return 3 * h * d


def _decode_read_bytes(spec: ModelSpec, avg_fill: float = 0.0,
                       cache_itemsize: int = 2) -> int:
    """HBM bytes one decode step must read: every layer weight + wcls in
    packed Q40 form (0.5 B/weight + f16-bit scales on device), one embedding
    row, norms, the f32 MoE router when present, plus the K/V cache rows
    attention reads at the average fill depth. The roofline denominator for
    effective-bandwidth."""
    d, kv, v = spec.dim, spec.kv_dim, spec.vocab_size
    per_layer_vals = d * d * 2 + kv * d * 2 + _ffn_vals_per_layer(spec)
    total_vals = per_layer_vals * spec.n_layers + v * d  # + wcls
    packed = total_vals // 2               # device layout: 16 B per 32 nibbles
    scale_w = 4 if os.environ.get("BENCH_SCALES") == "f32" else 2
    scales = total_vals // 32 * scale_w    # uint16 f16-bit (or A/B f32) scales
    router = (spec.n_experts * d * 2 * spec.n_layers) if spec.is_moe else 0
    cache = int(avg_fill) * 2 * kv * spec.n_layers * cache_itemsize  # k + v
    return (packed + scales + router + cache
            + d * 4 * (2 * spec.n_layers + 1) + d * 2)


def _decode_flops(spec: ModelSpec) -> int:
    """MACs*2 per decoded token (active matmul weights touched once each)."""
    d, kv, v = spec.dim, spec.kv_dim, spec.vocab_size
    per_layer = d * d * 2 + kv * d * 2 + _ffn_vals_per_layer(spec)
    return 2 * (per_layer * spec.n_layers + v * d)


def _measure_decode(engine, n_tokens: int, fill: int, repeats: int) -> float:
    """Best-of-N decode timing; returns ms/token."""
    dt = None
    for _ in range(repeats):
        engine.pos = fill
        _, d = engine.decode_greedy_device(first_token=1, n_tokens=n_tokens)
        dt = d if dt is None else min(dt, d)
        if TRACER.enabled:
            # the on-device loop has no per-step host boundary, so the
            # timeline sample is the run's MEAN ms/token at this batch
            # composition — one sample per measured run, comparable with
            # the scheduler rows' per-iteration records
            TRACER.step(decode_rows=engine.batch, prefill_rows=0, chunk=0,
                        queue_depth=0, wall_ms=d / n_tokens * 1e3)
    return dt / n_tokens * 1e3


# hbm-block plumbing (ISSUE-10 satellite): row functions that build an
# engine note it here; _with_step_timeline attaches the ledger next to
# step_timeline on every emitted row. A box, not a parameter, because
# the engines live deep inside the row functions.
_HBM_BOX: dict = {}


def _note_hbm(engine, prefix_cache=None) -> None:
    """Record the hbm ledger (runtime/profiler.hbm_ledger) of the row's
    engine — called while the engine's arrays are still live."""
    from distributed_llama_tpu.runtime.profiler import hbm_ledger

    try:
        _HBM_BOX["hbm"] = hbm_ledger(engine, prefix_cache)
    except Exception as e:  # noqa: BLE001 — a ledger bug must never
        _HBM_BOX["hbm"] = {"error": f"{type(e).__name__}: {e}"}  # kill a
        # measured row


def _with_step_timeline(row_fn, *args, **kwargs) -> dict:
    """Run one bench row with the flight recorder on and attach the
    per-batch-composition step-ms summary (the ISSUE-9 satellite: every
    row carries the raw measurement ROADMAP item 1's knee search mines).
    Rows that drive the slot scheduler get real per-iteration
    compositions; rows measuring the on-device decode loop get per-run
    mean samples (see _measure_decode); the cluster control-plane row
    records its heartbeat round trips under the dec0_pre0_c0
    composition (its "step" is one PING→PONG). The recorder is reset
    per row so compositions from different models/batches never mix."""
    TRACER.reset()
    # decode_every huge: the serving rows only need STEP records here —
    # span events would grow the ring without changing the block
    TRACER.configure(capacity=4096, decode_every=1 << 30)
    _HBM_BOX.pop("hbm", None)
    try:
        row = row_fn(*args, **kwargs)
    finally:
        timeline = TRACER.steps.summary_json()
        TRACER.reset()
    row["step_timeline"] = timeline
    # the hbm ledger the row noted while its engine was live (empty for
    # rows without one — the cluster control-plane row; the procs row
    # merges WORKER-side ledgers itself)
    row.setdefault("hbm", _HBM_BOX.pop("hbm", {}))
    return row


def _decode_row(metric: str, spec: ModelSpec, ms_per_token: float, *,
                fill: int = 0, n_tokens: int = 0, cache_itemsize: int = 2,
                base: float | None = None) -> dict:
    tok_s = 1000.0 / ms_per_token
    peaks = _device_peaks()
    eff_bw_gbs = (_decode_read_bytes(spec, avg_fill=fill + n_tokens / 2,
                                     cache_itemsize=cache_itemsize)
                  / (ms_per_token / 1e3) / 1e9)
    mfu = (_decode_flops(spec) * tok_s / (peaks["bf16_tflops"] * 1e12)
           if peaks else None)
    return {
        "metric": metric,
        "value": round(ms_per_token, 3),
        "unit": "ms/token",
        "vs_baseline": round(base / ms_per_token, 2) if base else None,
        "tokens_per_sec_per_chip": round(tok_s, 2),
        # device metrics: null on the CPU backend ("not measured")
        "effective_hbm_gbs": round(eff_bw_gbs, 1) if peaks else None,
        "hbm_peak_share": (round(eff_bw_gbs / peaks["hbm_gbs"], 4)
                           if peaks else None),
        "mfu": round(mfu, 4) if peaks else None,
    }


def _measure_prefill(engine, n_prompt: int, repeats: int) -> float:
    """Time a whole-prompt chunked prefill from a fresh session; returns
    tok/s (first run compiles and is excluded)."""
    import time

    rng = np.random.default_rng(7)
    prompt = rng.integers(
        1, engine.spec.vocab_size, n_prompt).astype(np.int64).tolist()
    best = None
    for i in range(repeats + 1):
        engine.reset()
        t0 = time.perf_counter()
        logits = engine.prefill(prompt)
        jax.block_until_ready(logits)
        dt = time.perf_counter() - t0
        if i > 0:
            best = dt if best is None else min(best, dt)
    engine.reset()
    return n_prompt / best


def _lookup_row(engine, repeats: int) -> dict:
    """Prompt-lookup speculative decode on the 7B engine: host-loop wall
    of a 128-token plain greedy run vs the same run through
    `generate_lookup` with the draft miner's history primed with the
    model's own (deterministic, fixed-seed) continuation — the full-
    acceptance regime repetitive text reaches, measured with the real
    mechanism live (mining, verify forwards, acceptance). Reported
    fields: end-to-end speedup, tokens/forward, and the cost of a
    width-8 verify forward relative to a single-token step. Acceptance is
    content-dependent; this row is the mechanism's ceiling, not a corpus
    average.

    Parity note: in bf16 the t = 1 and t = 1+k forwards tile differently,
    and an argmax near-tie can flip a token (both streams are the model's
    own argmaxes; exact-parity is asserted by the f32 suite,
    tests/test_speculative.py). The timed prime is therefore the lookup
    stream's own FIXED POINT — re-primed until it reproduces itself — so
    the row measures full acceptance; `parity_prefix` records how far the
    plain stream agreed."""
    import time

    from distributed_llama_tpu.sampler import Sampler

    n, draft_len = 128, 7
    prompt = [1, 17, 93, 5]
    greedy = Sampler(engine.spec.vocab_size, temperature=0.0, topp=0.9,
                     seed=1)

    best_plain, plain_tokens = None, None
    for i in range(repeats + 1):  # run 0 compiles — excluded
        engine.reset()
        t0 = time.perf_counter()
        r = engine.generate(prompt, max_tokens=n, sampler=greedy)
        dt = time.perf_counter() - t0
        if i > 0:
            best_plain = dt if best_plain is None else min(best_plain, dt)
        plain_tokens = r.tokens

    stream = plain_tokens
    for _ in range(4):  # fixed-point prime (converges in 1-2 passes)
        engine.reset()
        lk = engine.generate_lookup(prompt, n, draft_len=draft_len,
                                    history=prompt + stream).tokens
        if lk == stream:
            break
        stream = lk

    primed = prompt + stream
    best_lk, lk_tokens = None, None
    for i in range(repeats + 1):
        engine.reset()
        t0 = time.perf_counter()
        r = engine.generate_lookup(prompt, n, draft_len=draft_len,
                                   history=primed)
        dt = time.perf_counter() - t0
        if i > 0:
            best_lk = dt if best_lk is None else min(best_lk, dt)
        lk_tokens = r.tokens
    forwards, toks = engine.last_accept_stats
    agree = next((i for i, (a, b) in enumerate(zip(plain_tokens, lk_tokens))
                  if a != b), len(lk_tokens))
    engine.reset()

    spec_rec = getattr(engine, "last_spec",
                       {"drafted": 0, "accepted": 0})
    row = {
        "metric": "llama2_7b_q40_lookup_decode_hostloop_speedup_max_accept",
        "value": round(best_plain / best_lk, 2), "unit": "x",
        "vs_baseline": None,
        "tokens_per_forward": round(toks / forwards, 2),
        # honest accept reporting (VERDICT #6): the measured rate and
        # the regime label ride the row — this trace is REPETITIVE BY
        # CONSTRUCTION (fixed-point primed history = the mechanism's
        # ceiling); the non-repetitive regime is BENCH_SPEC's _spec_row
        "accept_rate": round(spec_rec["accepted"]
                             / max(spec_rec["drafted"], 1), 3),
        "eval_label": "repetitive_primed",
        "verify8_cost_vs_step": round((best_lk / forwards)
                                      / (best_plain / n), 2),
        "parity_prefix": round(agree / n, 3),
    }
    if toks / forwards <= 1.2:
        # a degenerate synth stream can defeat even the primed miner; the
        # row degrades with a warning rather than aborting later rows
        row["warning"] = "low acceptance despite primed history"
    return row


def _batch_row(params, spec: ModelSpec, repeats: int, b: int = 8) -> dict:
    """Batched decode aggregate throughput on ONE chip: decode is
    weight-read-bound at batch=1, so b rows amortize the same weight read
    across b tokens — the single-chip serving-throughput headline the
    batched API endpoint rides on. Measured through the ON-DEVICE batched
    loop (generate_batch_device — one dispatch for the whole run), so the
    row measures the amortization and not the host loop's round trip per
    step."""
    import gc
    import time

    eng = Engine(spec, params, compute_dtype=jnp.bfloat16,
                 cache_dtype=jnp.bfloat16, max_seq_len=512, batch=b)
    n = 96
    prompts = [[1, 17 + i, 93, 5 + i] for i in range(b)]
    best = None
    for i in range(repeats + 1):  # run 0 compiles — excluded
        eng.reset()
        t0 = time.perf_counter()
        outs = eng.generate_batch_device(
            prompts, n, temperature=0.8, topp=0.9, seed=9)
        dt = time.perf_counter() - t0
        if i > 0:
            best = dt if best is None else min(best, dt)
    toks = sum(len(o) for o in outs)
    agg_tok_s = toks / best
    del eng
    gc.collect()
    return {
        "metric": f"llama2_7b_q40_batch{b}_device_decode_agg_tok_per_s_1chip",
        "value": round(agg_tok_s, 1), "unit": "tok/s",
        "vs_baseline": None,
        "ms_per_step": round(best / (toks / b) * 1e3, 3),
        "batch": b,
    }


def _batch_lookup_row(params, spec: ModelSpec, repeats: int,
                      b: int = 8) -> dict:
    """Batched SPECULATIVE decode (VERDICT r4 #7): b rows amortize one
    weight read per verify forward AND each row confirms multiple draft
    tokens per forward — the two serving multipliers compose. Same
    max-acceptance regime as _lookup_row (per-row histories primed with
    each row's own fixed-point continuation); the host loop pays one
    dispatch per forward, but multi-token accepts mean ~1/k the
    forwards of the plain batch loop."""
    import gc
    import time

    eng = Engine(spec, params, compute_dtype=jnp.bfloat16,
                 cache_dtype=jnp.bfloat16, max_seq_len=512, batch=b)
    n, draft_len = 96, 7
    prompts = [[1, 17 + i, 93, 5 + i] for i in range(b)]

    # per-row fixed-point prime (the _lookup_row discipline, batched)
    streams = eng.generate_batch_lookup(prompts, n, draft_len=draft_len)
    for _ in range(4):
        eng.reset()
        nxt = eng.generate_batch_lookup(
            prompts, n, draft_len=draft_len,
            histories=[p + s for p, s in zip(prompts, streams)])
        if nxt == streams:
            break
        streams = nxt
    primed = [p + s for p, s in zip(prompts, streams)]

    best = None
    outs = None
    for i in range(repeats + 1):  # run 0 warms remaining widths
        eng.reset()
        t0 = time.perf_counter()
        outs = eng.generate_batch_lookup(prompts, n, draft_len=draft_len,
                                         histories=primed)
        dt = time.perf_counter() - t0
        if i > 0:
            best = dt if best is None else min(best, dt)
    forwards, toks = eng.last_accept_stats
    agg_tok_s = sum(len(o) for o in outs) / best
    del eng
    gc.collect()
    return {
        "metric": (f"llama2_7b_q40_batch{b}_lookup_decode_agg_tok_per_s_"
                   "1chip_max_accept"),
        "value": round(agg_tok_s, 1), "unit": "tok/s",
        "vs_baseline": None,
        "tokens_per_forward_all_rows": round(toks / forwards, 2),
        # VERDICT #6 labeling: fixed-point primed == repetitive by
        # construction (see _lookup_row; _spec_row is the other regime)
        "eval_label": "repetitive_primed",
        "batch": b,
    }


def _serve_row(params, spec: ModelSpec, prefix: str, b: int = 8) -> dict:
    """Continuous batching vs static batching under a Poisson arrival
    trace (the ISSUE-2 serving metric). One fixed-seed synthetic trace of
    mixed-length requests arrives at ~system capacity; it is served twice:

      * STATIC — the old /v1/batch/completions regime: requests group into
        full batches of `b` in arrival order, a batch starts only when its
        LAST member has arrived and the previous batch drained, and every
        slot is held until the batch's slowest row finishes its budget
        (per-row budgets retire rows via stop_flags; the host-loop
        generate_batch_stream is the production static path).
      * CONTINUOUS — the slot scheduler (runtime/scheduler.py): requests
        join the running decode batch on arrival, chunked prefill
        interleaves with decode, finished rows free their slot instantly.

    Both are host-loop paths over the same engine weights, so the ratio
    isolates the SCHEDULING win (slot reuse + no wait-for-full-batch), not
    dispatch differences. Batch durations for the static fold are measured
    wall-clock; arrivals are folded analytically so the static number
    never pays sleep jitter. Reported: continuous aggregate tok/s (the
    headline), the static number and ratio, and the scheduler's TTFT/ITL
    percentiles + occupancy from runtime/stats.ServeStats.

    Env knobs: BENCH_SERVE_REQUESTS (default 24), BENCH_SERVE_BATCH
    (default 8), BENCH_SERVE_BUDGETS (comma list, default 16,32,64,96).
    Prompt lengths cycle {8, 16, 32} so the static path's right-padded
    prefill keeps a bounded compile-key set, like the scheduler's fixed
    chunk."""
    import gc
    import time

    from distributed_llama_tpu.runtime.scheduler import Scheduler
    from distributed_llama_tpu.sampler import Sampler

    b = int(os.environ.get("BENCH_SERVE_BATCH", str(b)))
    n_req = max(int(os.environ.get("BENCH_SERVE_REQUESTS", "24")), b)
    budgets_pool = [int(x) for x in os.environ.get(
        "BENCH_SERVE_BUDGETS", "16,32,64,96").split(",")]
    seq = min(512, spec.seq_len)
    cdt = jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16

    rng = np.random.default_rng(0)
    lens = [(8, 16, 32)[i % 3] for i in range(n_req)]
    prompts = [rng.integers(1, spec.vocab_size, n).astype(np.int64).tolist()
               for n in lens]
    budgets = [budgets_pool[int(i)] for i in
               rng.integers(0, len(budgets_pool), n_req)]

    eng = Engine(spec, params, compute_dtype=cdt, cache_dtype=cdt,
                 max_seq_len=seq, batch=b)
    _note_hbm(eng)

    def greedy():
        return Sampler(spec.vocab_size, temperature=0.0, topp=0.9, seed=7)

    def run_static_batch(batch_prompts, batch_budgets):
        """One wait-for-full-batch run with per-row budget retirement;
        returns (tokens, seconds)."""
        n_rows = len(batch_prompts)
        rows = batch_prompts + [[1]] * (eng.batch - n_rows)
        stop_flags = np.zeros(eng.batch, bool)
        stop_flags[n_rows:] = True
        counts = [0] * n_rows
        eng.reset()
        t0 = time.perf_counter()
        for step in eng.generate_batch_stream(rows, max(batch_budgets),
                                              greedy(),
                                              stop_flags=stop_flags):
            for i in range(n_rows):
                if step[i] is not None:
                    counts[i] += 1
                    if counts[i] >= batch_budgets[i]:
                        stop_flags[i] = True
        return sum(counts), time.perf_counter() - t0

    # warm every compile key off the clock: static bpre widths {8,16,32} +
    # bvec, and the scheduler's slot_prefill_chunk_32 + slot_decode_step
    for n in (8, 16, 32):
        wp = rng.integers(1, spec.vocab_size, n).astype(np.int64).tolist()
        run_static_batch([wp] * min(2, b), [2] * min(2, b))
    sched = Scheduler(eng, chunk=32)
    warm = sched.submit(prompts[0], 2, greedy())
    while not warm.finished.is_set():
        sched.step()

    # static fold: batches of b in arrival order; batch k starts at
    # max(previous end, last member's arrival)
    d_static = []
    toks_static = 0
    for i in range(0, n_req, b):
        t, d = run_static_batch(prompts[i:i + b], budgets[i:i + b])
        toks_static += t
        d_static.append(d)

    # offered load = 3x the STATIC path's measured capacity — the
    # saturated ("heavy traffic") regime where aggregate throughput, not
    # arrival rate, is the binding constraint. Under lighter load both
    # systems simply track arrivals and the comparison collapses to
    # latency (where continuous wins on TTFT but the tok/s ratio is ~1);
    # saturation is what exposes static batching's idle-slot waste.
    mean_iat = sum(d_static) / n_req / 3.0
    arrivals = np.cumsum(rng.exponential(mean_iat, n_req))
    end = 0.0
    for k, d in enumerate(d_static):
        last_arrival = arrivals[min((k + 1) * b, n_req) - 1]
        end = max(end, last_arrival) + d
    static_tok_s = toks_static / end

    # continuous run on the same trace, real wall clock
    sched = Scheduler(eng, chunk=32)
    sched.start()
    try:
        live = []
        t0 = time.perf_counter()
        for arr, p, k in zip(arrivals, prompts, budgets):
            dt = t0 + arr - time.perf_counter()
            if dt > 0:
                time.sleep(dt)
            live.append(sched.submit(p, k, greedy()))
        for r in live:
            assert r.finished.wait(600), "scheduler stalled"
        t_cont = time.perf_counter() - t0
    finally:
        sched.close()
    toks_cont = sum(r.stats.n_out for r in live)
    cont_tok_s = toks_cont / t_cont
    s = sched.stats.summary()

    del eng
    gc.collect()
    return {
        "metric": f"{prefix}_continuous_batch{b}_poisson_agg_tok_per_s_1chip",
        "value": round(cont_tok_s, 1), "unit": "tok/s", "vs_baseline": None,
        "static_agg_tok_per_s": round(static_tok_s, 1),
        "vs_static_batch": round(cont_tok_s / static_tok_s, 2),
        "requests": n_req, "batch": b,
        "tokens": toks_cont,
        "ttft_p50_ms": s["ttft_p50_ms"], "ttft_p99_ms": s["ttft_p99_ms"],
        "itl_p50_ms": s["itl_p50_ms"], "itl_p99_ms": s["itl_p99_ms"],
        "mean_slot_occupancy": s["mean_slot_occupancy"],
        "max_queue_depth": s["max_queue_depth"],
    }


def _prefix_row(params, spec: ModelSpec, prefix: str, b: int = 4) -> dict:
    """Radix prefix cache under a shared-system-prompt workload (the
    ISSUE-4 metric): replay a fixed-seed Poisson arrival trace whose
    prompts share a common system prefix — the dominant production
    chat/RAG shape — through the slot scheduler twice, cache OFF then
    ON (runtime/prefix_cache.py), and report:

      * prefill tokens served from cache (the headline %, acceptance
        bar >= 50 on this workload),
      * greedy TOKEN PARITY between the runs (seeded K/V is bitwise the
        cold prefill's K/V, so outputs must be identical),
      * TTFT p50 delta — the latency a returning client actually gains
        when its system prompt + history seed instead of prefilling,
      * the modeled wire/HBM tradeoff (netstats.estimate_prefix_reuse).

    The FIRST request runs alone before the measured replay (cache ON
    and OFF both, for symmetry): a shared system prompt is warm long
    before any steady-state window, and publishing happens at
    prefill-finish, so the replayed requests all see a warm tree.

    Env knobs: BENCH_PREFIX_REQUESTS (default 16), BENCH_PREFIX_BATCH
    (default 4), BENCH_PREFIX_SYS (shared prefix tokens, default 48),
    BENCH_PREFIX_BLOCK (block_len, default 16 — the shared prefix is a
    whole number of blocks so the whole-blocks-only lookup covers it),
    BENCH_PREFIX_TOKENS (per-request decode budget, default 8)."""
    import gc
    import time

    from distributed_llama_tpu.runtime.netstats import estimate_prefix_reuse
    from distributed_llama_tpu.runtime.prefix_cache import PrefixCache
    from distributed_llama_tpu.runtime.scheduler import Scheduler
    from distributed_llama_tpu.sampler import Sampler

    b = int(os.environ.get("BENCH_PREFIX_BATCH", str(b)))
    n_req = max(int(os.environ.get("BENCH_PREFIX_REQUESTS", "16")), 2)
    sys_len = int(os.environ.get("BENCH_PREFIX_SYS", "48"))
    bl = int(os.environ.get("BENCH_PREFIX_BLOCK", "16"))
    budget = int(os.environ.get("BENCH_PREFIX_TOKENS", "8"))
    seq = min(512, spec.seq_len)
    cdt = jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16

    rng = np.random.default_rng(0)
    shared = rng.integers(1, spec.vocab_size, sys_len).astype(
        np.int64).tolist()
    tails = [rng.integers(1, spec.vocab_size, (8, 12, 16)[i % 3]).astype(
        np.int64).tolist() for i in range(n_req)]
    prompts = [shared + t for t in tails]
    arrivals = np.cumsum(rng.exponential(0.04, n_req - 1))

    eng = Engine(spec, params, compute_dtype=cdt, cache_dtype=cdt,
                 max_seq_len=seq, batch=b)

    def greedy():
        return Sampler(spec.vocab_size, temperature=0.0, topp=0.9, seed=7)

    def run_trace(pc):
        """One full serve of the trace; returns (per-request token lists,
        replayed-requests TTFT p50 ms)."""
        sched = Scheduler(eng, chunk=bl, prefix_cache=pc)
        sched.warmup()  # compile keys (incl. seed/publish) off the clock
        prime = sched.submit(prompts[0], budget, greedy())
        while not prime.finished.is_set():
            sched.step()
        sched.start()
        live = []
        try:
            t0 = time.perf_counter()
            for arr, p in zip(arrivals, prompts[1:]):
                dt = t0 + arr - time.perf_counter()
                if dt > 0:
                    time.sleep(dt)
                live.append(sched.submit(p, budget, greedy()))
            for r in live:
                assert r.finished.wait(600), "scheduler stalled"
        finally:
            sched.close()
        outs = [list(prime.tokens(timeout=5.0))]
        outs += [list(r.tokens(timeout=5.0)) for r in live]
        ttfts = sorted(r.stats.ttft_ms for r in live)
        return outs, ttfts[len(ttfts) // 2]

    outs_off, ttft_off = run_trace(None)
    pc = PrefixCache(eng, num_blocks=max(2 * b * seq // bl,
                                         sys_len // bl + 8), block_len=bl)
    _note_hbm(eng, pc)  # the cache-ON shape: slots + the real arena
    outs_on, ttft_on = run_trace(pc)

    s = pc.stats.summary()
    # hbm_copy uses the REAL copied volume: every hit gathers the full
    # fixed seed width (seq // bl blocks), not just the matched tokens —
    # the single-compilation-key tradeoff estimate_prefix_reuse documents
    reuse = estimate_prefix_reuse(spec, eng.mesh,
                                  tokens_saved=s["tokens_saved"],
                                  tokens_copied=s["hits"] * (seq // bl) * bl,
                                  cache_bytes=jnp.dtype(cdt).itemsize)
    del eng
    gc.collect()
    return {
        "metric": f"{prefix}_prefix_cache_block{bl}_prefill_saved_pct",
        "value": round(100.0 * (s["prefill_saved_frac"] or 0.0), 2),
        "unit": "%", "vs_baseline": None,
        "requests": n_req, "batch": b,
        "shared_prefix_tokens": sys_len, "block_len": bl,
        "token_parity": outs_on == outs_off,
        "hit_rate": s["hit_rate"],
        "tokens_saved": s["tokens_saved"],
        "blocks_published": s["blocks_published"],
        "evictions": s["evictions"],
        "ttft_p50_ms_off": round(ttft_off, 3),
        "ttft_p50_ms_on": round(ttft_on, 3),
        "ttft_p50_delta_ms": round(ttft_off - ttft_on, 3),
        **reuse,
    }


def _autotune_row(params, spec: ModelSpec, prefix: str) -> dict:
    """The closed batch-knee loop, measured end to end (the ISSUE-11
    metric): calibrate → auto-size → self-tune, A/B'd against hand-tuned
    static settings on ONE fixed-seed Poisson trace.

      1. CALIBRATE — tools/autotune.calibrate() sweeps the serving step
         shapes across BENCH_AUTOTUNE_BATCHES (reusing this run's
         synthesized weights) and fits the knee; the artifact rides the
         row under "calibration".
      2. AUTO-SIZE — runtime/profiler.resolve_auto_shape picks
         --serve-batch from the calibrated knee capped by HBM headroom
         (null on CPU: the knee stands alone), exactly what
         `--serve-batch auto --autotune AUTOTUNE.json` does at startup.
      3. SELF-TUNE — the trace is served by the auto-sized scheduler
         with the SLO-aware adaptive chunk policy armed
         (--slo-ttft-ms/--slo-itl-ms) and --freeze-compiles semantics
         enforced (COMPILES.freeze during the run), vs every static
         (batch, chunk) combo in BENCH_AUTOTUNE_STATIC.

    The trace interleaves short decode-heavy requests with LONG prompts
    (the chunked-prefill interference shape): a wide static chunk blows
    running streams' ITL whenever a long prompt admits, a narrow one
    starves TTFT — the adaptive ladder is the tradeoff knob. Reported
    per policy: goodput-at-SLO (tokens of SLO-meeting requests / wall —
    dlprof's goodput definition), SLO fraction, TTFT/ITL p50/p99, and
    aggregate tok/s. Acceptance bars ride the row: `beats_all_static`
    (goodput-at-SLO >= every swept static), `token_parity` (greedy
    outputs bit-identical across ALL policies — slot scheduling and
    chunk boundaries must not change tokens), and
    `compiles_after_warmup == 0` across the adaptive run (the width
    ladder is warmed up front; the sentinel proves it).

    Env knobs: BENCH_AUTOTUNE_REQUESTS (default 24),
    BENCH_AUTOTUNE_TOKENS (short-request budget, default 16),
    BENCH_AUTOTUNE_BATCHES (calibration sweep, default "2,4,8,16,32"),
    BENCH_AUTOTUNE_STATIC (static B:C combos, default
    "2:32,4:32,8:8,8:32" — 8 is the hand-picked production batch this
    loop was built to beat), BENCH_AUTOTUNE_SLO_TTFT_MS /
    _SLO_ITL_MS (defaults 1000/80 — CPU-tiny scale),
    BENCH_AUTOTUNE_REPEATS (best-of-N serves per policy, default 2),
    BENCH_AUTOTUNE_IAT (mean arrival gap s, default 0.02 — saturates
    every swept static so goodput, not arrivals, is the binding
    constraint, the _serve_row discipline),
    BENCH_AUTOTUNE_LONG (long-prompt tokens, default 96)."""
    import gc
    import time

    from distributed_llama_tpu.runtime.profiler import (COMPILES,
                                                        resolve_auto_shape)
    from distributed_llama_tpu.runtime.scheduler import Scheduler
    from distributed_llama_tpu.sampler import Sampler

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import autotune as autotune_mod

    n_req = max(int(os.environ.get("BENCH_AUTOTUNE_REQUESTS", "24")), 4)
    budget = int(os.environ.get("BENCH_AUTOTUNE_TOKENS", "16"))
    cal_batches = [int(x) for x in os.environ.get(
        "BENCH_AUTOTUNE_BATCHES", "2,4,8,16,32").split(",")]
    statics = [tuple(int(v) for v in s.split(":")) for s in os.environ.get(
        "BENCH_AUTOTUNE_STATIC", "2:32,4:32,8:8,8:32").split(",")]
    slo_ttft = float(os.environ.get("BENCH_AUTOTUNE_SLO_TTFT_MS", "1000"))
    slo_itl = float(os.environ.get("BENCH_AUTOTUNE_SLO_ITL_MS", "80"))
    mean_iat = float(os.environ.get("BENCH_AUTOTUNE_IAT", "0.02"))
    long_len = int(os.environ.get("BENCH_AUTOTUNE_LONG", "96"))
    chunk_max = 32
    seq = min(256, spec.seq_len)
    cdt = jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16

    # 1. CALIBRATE (quiet: the sweep's own step timelines are internal)
    artifact = autotune_mod.calibrate(
        model=os.environ.get("BENCH_MODEL", "tiny"), batches=cal_batches,
        chunk=chunk_max, steps=16, seq=seq, spec=spec, params=params,
        log=lambda *a, **k: None)
    # calibrate() drives its own recorder sessions; re-arm the row's
    # (dropping the sweep's compositions — the A/B serves below are the
    # row's step_timeline)
    TRACER.reset()
    TRACER.configure(capacity=4096, decode_every=1 << 30)

    # 2. AUTO-SIZE from the artifact, the way --serve-batch auto does
    template = Engine(spec, params, compute_dtype=cdt, cache_dtype=cdt,
                      max_seq_len=seq, batch=1)
    autosize = resolve_auto_shape(template, serve_batch="auto",
                                  autotune=artifact, slo_itl_ms=slo_itl)
    del template
    gc.collect()
    b_auto = autosize["serve_batch"]

    # the fixed-seed trace: every 3rd request a long prompt, the rest
    # short decode-heavy streams (arrivals saturate the smallest static)
    rng = np.random.default_rng(0)
    lens = [long_len if i % 3 == 2 else (6, 10)[i % 2]
            for i in range(n_req)]
    budgets = [max(budget // 2, 4) if i % 3 == 2 else budget
               for i in range(n_req)]
    prompts = [rng.integers(1, spec.vocab_size, n).astype(np.int64).tolist()
               for n in lens]
    arrivals = np.cumsum(rng.exponential(mean_iat, n_req))

    def greedy():
        return Sampler(spec.vocab_size, temperature=0.0, topp=0.9, seed=7)

    repeats = max(int(os.environ.get("BENCH_AUTOTUNE_REPEATS", "2")), 1)

    def run_policy(b: int, chunk: int, adaptive: bool) -> dict:
        """Serve the trace `repeats` times under one policy and keep the
        best-of-N goodput (the bench's jitter discipline — every policy
        gets the same treatment, so the A/B compares policies, not CPU
        scheduling luck). Token outputs must be IDENTICAL across the
        repeats (asserted) — timing never changes greedy tokens."""
        eng = Engine(spec, params, compute_dtype=cdt, cache_dtype=cdt,
                     max_seq_len=seq, batch=b)
        best = None
        for rep in range(repeats):
            # fresh scheduler per repeat over the SAME engine: the
            # compile keys are warm after the first, and slot reuse
            # needs no cache reset (overwrite-before-attend)
            sched = Scheduler(eng, chunk=chunk,
                              slo_ttft_ms=slo_ttft if adaptive else None,
                              slo_itl_ms=slo_itl if adaptive else None)
            sched.warmup()
            if adaptive and rep == 0:
                _note_hbm(eng)  # the auto-sized shape is the row's ledger
            sched.start()
            live = []
            try:
                t0 = time.perf_counter()
                for arr, p, k in zip(arrivals, prompts, budgets):
                    dt = t0 + arr - time.perf_counter()
                    if dt > 0:
                        time.sleep(dt)
                    live.append(sched.submit(p, k, greedy()))
                for r in live:
                    assert r.finished.wait(600), "scheduler stalled"
                wall = time.perf_counter() - t0
            finally:
                admission = (sched.admission.summary()
                             if sched.admission is not None else None)
                sched.close()
            outs = [list(r.tokens(timeout=5.0)) for r in live]
            recs = [r.stats for r in live]
            ok = [r for r in recs
                  if (r.ttft_ms is not None and r.ttft_ms <= slo_ttft
                      and (r.itl_ms is None or r.itl_ms <= slo_itl))]
            ttfts = sorted(r.ttft_ms for r in recs
                           if r.ttft_ms is not None)
            itls = sorted(r.itl_ms for r in recs if r.itl_ms is not None)
            pct = lambda xs, p: (round(xs[min(len(xs) - 1,  # noqa: E731
                                              round(p * (len(xs) - 1)))],
                                       3) if xs else None)
            run = {
                "batch": b, "chunk": chunk, "adaptive": adaptive,
                "goodput_tok_s": round(sum(r.n_out for r in ok) / wall, 2),
                "agg_tok_s": round(sum(r.n_out for r in recs) / wall, 2),
                "slo_fraction": round(len(ok) / len(recs), 4),
                "ttft_p50_ms": pct(ttfts, 0.5),
                "ttft_p99_ms": pct(ttfts, 0.99),
                "itl_p50_ms": pct(itls, 0.5), "itl_p99_ms": pct(itls, 0.99),
                "wall_s": round(wall, 2),
                **({"admission": admission} if admission else {}),
                "outs": outs,
            }
            if best is not None:
                assert run["outs"] == best["outs"], \
                    "greedy outputs changed between repeats"
            if best is None or (run["goodput_tok_s"]
                                > best["goodput_tok_s"]):
                best = run
        del eng
        gc.collect()
        return best

    static_runs = [run_policy(b, c, adaptive=False) for b, c in statics]

    # 3. SELF-TUNE under the recompile sentinel's freeze: the adaptive
    # run must mint ZERO post-warmup keys (the ladder warmed them all)
    before = COMPILES.after_warmup
    prev_freeze = COMPILES.freeze
    COMPILES.freeze = True
    try:
        adaptive_run = run_policy(b_auto, chunk_max, adaptive=True)
    finally:
        COMPILES.freeze = prev_freeze
    compiles_after_warmup = COMPILES.after_warmup - before

    parity = all(run["outs"] == static_runs[0]["outs"]
                 for run in static_runs[1:] + [adaptive_run])
    for run in static_runs + [adaptive_run]:
        run.pop("outs")
    best_static = max(static_runs, key=lambda r: r["goodput_tok_s"])
    return {
        "metric": f"{prefix}_autotune_adaptive_goodput_tok_per_s_at_slo",
        "value": adaptive_run["goodput_tok_s"], "unit": "tok/s",
        "vs_baseline": None,
        "slo_ttft_ms": slo_ttft, "slo_itl_ms": slo_itl,
        "requests": n_req, "long_prompt_tokens": long_len,
        "serve_batch_auto": b_auto,
        "autosize": autosize,
        "calibration": {"batches": cal_batches,
                        "decode_curve": artifact["decode_curve"],
                        "prefill_ms_by_width":
                            artifact["prefill_ms_by_width"],
                        "knee": artifact["knee"],
                        "recommendation": artifact["recommendation"]},
        "adaptive": adaptive_run,
        "static": static_runs,
        "best_static": {k: best_static[k] for k in
                        ("batch", "chunk", "goodput_tok_s")},
        "vs_best_static": round(adaptive_run["goodput_tok_s"]
                                / best_static["goodput_tok_s"], 2)
        if best_static["goodput_tok_s"] else None,
        "beats_all_static": all(
            adaptive_run["goodput_tok_s"] >= r["goodput_tok_s"]
            for r in static_runs),
        "token_parity": parity,
        "compiles_after_warmup": compiles_after_warmup,
        "freeze_compiles": True,
    }


def _spec_row(prefix: str) -> dict:
    """REAL-draft speculative decoding (the ISSUE-13 metric): the
    zero-extra-weights truncated-depth self-draft (runtime/draft.py) vs
    prompt-lookup vs plain greedy, measured on a fixed-seed
    NON-REPETITIVE eval — the regime VERDICT #6 said the committed
    lookup rows never covered (their max-accept numbers were best-case
    by construction; this row carries the measured accept rate and a
    repetitiveness label ON the row so the regime is never implicit
    again).

    The model is synthetic with LAYER-DECAYED weights: the first
    `depth` layers carry scale `base`, deeper layers scale `tail` —
    the structural regime where a truncated-depth prefix predicts the
    full model (trained checkpoints approximate this late-layer
    redundancy; the accept rate REPORTED is what this construction
    measures, not a trained-model claim). The eval prompt is random
    tokens over a 2048 vocab and the greedy continuation is verified
    aperiodic (`repeated_3gram_frac`, `label`): prompt-lookup's own
    tokens/forward on the same stream is the honest control — on
    non-repetitive text it proposes nothing.

    Three single-stream passes (plain / lookup / self-draft, best-of-N
    wall each, bit-identical streams asserted) + one Poisson serving
    A/B: the same fixed arrival trace through the slot scheduler with
    per-slot drafts OFF then ON (token parity per request), with the
    compile ledger FROZEN after the draft-on warmup — the acceptance
    bars ride the row: `token_parity`, `value` > 1.5 (single-stream
    speedup), serving ratio > 1, `compiles_after_warmup` == 0.

    Env knobs: BENCH_SPEC_TOKENS (96), BENCH_SPEC_DEPTH (1),
    BENCH_SPEC_DRAFT_LEN (8), BENCH_SPEC_REQUESTS (12),
    BENCH_SPEC_BATCH (4), BENCH_SPEC_TAIL (0.05), BENCH_SPEC_REPEATS
    (= BENCH_REPEATS)."""
    import gc
    import time

    from distributed_llama_tpu.io import HostTensor
    from distributed_llama_tpu.io.model_file import model_tensor_plan
    from distributed_llama_tpu.models.params import load_params
    from distributed_llama_tpu.quants import FloatType
    from distributed_llama_tpu.runtime.draft import DraftModel, build_draft
    from distributed_llama_tpu.runtime.profiler import COMPILES
    from distributed_llama_tpu.runtime.scheduler import Scheduler
    from distributed_llama_tpu.sampler import Sampler

    n = int(os.environ.get("BENCH_SPEC_TOKENS", "96"))
    depth = int(os.environ.get("BENCH_SPEC_DEPTH", "1"))
    draft_len = int(os.environ.get("BENCH_SPEC_DRAFT_LEN", "8"))
    n_req = max(int(os.environ.get("BENCH_SPEC_REQUESTS", "12")), 4)
    b = int(os.environ.get("BENCH_SPEC_BATCH", "4"))
    tail = float(os.environ.get("BENCH_SPEC_TAIL", "0.05"))
    repeats = max(int(os.environ.get(
        "BENCH_SPEC_REPEATS", os.environ.get("BENCH_REPEATS", "2"))), 1)

    spec = ModelSpec(
        arch=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=8,
        n_heads=8, n_kv_heads=4, vocab_size=512, seq_len=512,
        hidden_act=HiddenAct.SILU, weights_float_type=FloatType.F32)
    rng = np.random.default_rng(0)
    host = {}
    for name, shape, _ft in model_tensor_plan(spec):
        if "rms" in name:
            x = 1.0 + rng.standard_normal(shape).astype(np.float32) * 0.02
        else:
            s = 0.35
            if name.startswith("layers."):
                if int(name.split(".")[1]) >= depth:
                    s = tail
            x = rng.standard_normal(shape).astype(np.float32) * s
        host[name] = HostTensor(name, FloatType.F32, shape, data=x)
    params = load_params(spec, host, mode="dense", dtype=jnp.float32)

    def engine(batch=1):
        return Engine(spec, params, compute_dtype=jnp.float32,
                      cache_dtype=jnp.float32, batch=batch,
                      prefill_chunk=64)

    def greedy():
        return Sampler(spec.vocab_size, temperature=0.0, topp=0.9, seed=7)

    prompt = np.random.default_rng(123).integers(
        3, spec.vocab_size, 48).tolist()

    # -- single-stream ladder: plain / lookup / self-draft ----------------
    def timed(fn):
        best, toks = None, None
        for i in range(repeats + 1):  # run 0 compiles — excluded
            t0 = time.perf_counter()
            toks = fn()
            dt = time.perf_counter() - t0
            if i > 0:
                best = dt if best is None else min(best, dt)
        return best, toks

    eng_p = engine()

    def run_plain():
        eng_p.reset()
        return eng_p.generate(prompt, n, greedy()).tokens

    best_plain, plain_toks = timed(run_plain)

    eng_l = engine()

    def run_lookup():
        eng_l.reset()
        return eng_l.generate_lookup(prompt, n, draft_len=draft_len).tokens

    best_lk, lk_toks = timed(run_lookup)
    lk_fwd, lk_n = eng_l.last_accept_stats
    lk_spec = dict(eng_l.last_spec)

    eng_d = engine()
    draft = DraftModel.self_draft(eng_d, depth)

    def run_draft():
        eng_d.reset()
        return eng_d.generate_draft(prompt, n, draft=draft,
                                    draft_len=draft_len).tokens

    best_dr, dr_toks = timed(run_draft)
    dr_fwd, dr_n = eng_d.last_accept_stats
    dr_spec = dict(eng_d.last_spec)

    single_parity = plain_toks == lk_toks == dr_toks
    # repetitiveness label from the PLAIN stream's own n-gram statistics
    # (the honest regime marker — a 3-gram that recurs is exactly what
    # prompt-lookup mines)
    t_arr = np.asarray(plain_toks)
    seen: set = set()
    hits = 0
    for i in range(len(t_arr) - 2):
        g = tuple(t_arr[i:i + 3])
        hits += g in seen
        seen.add(g)
    rep_frac = hits / max(len(t_arr) - 2, 1)
    label = "repetitive" if rep_frac > 0.2 else "non_repetitive"

    # -- Poisson serving A/B: per-slot drafts OFF vs ON -------------------
    rng2 = np.random.default_rng(5)
    lens = [(8, 16, 32)[i % 3] for i in range(n_req)]
    prompts = [rng2.integers(3, spec.vocab_size, ln).tolist()
               for ln in lens]
    budget = 24
    # saturated offered load: ~3x the plain path's single-stream capacity
    mean_iat = (best_plain / n) * budget / max(b, 1) / 3.0
    arrivals = np.cumsum(rng2.exponential(mean_iat, n_req))

    def serve(drafting: bool):
        eng = engine(batch=b)
        sched = Scheduler(
            eng, chunk=16,
            draft_factory=(lambda e: build_draft(e, f"self:{depth}"))
            if drafting else None,
            draft_len=draft_len if drafting else 0,
            draft_vocab=spec.vocab_size)
        sched.warmup()
        frozen = before = None
        if drafting:
            # the sentinel proof: the whole speculative serve runs with
            # the ledger FROZEN — one unplanned key would abort the row
            before = COMPILES.after_warmup
            frozen, COMPILES.freeze = COMPILES.freeze, True
        try:
            sched.start()
            live = []
            t0 = time.perf_counter()
            for arr, p in zip(arrivals, prompts):
                dt = t0 + arr - time.perf_counter()
                if dt > 0:
                    time.sleep(dt)
                live.append(sched.submit(p, budget, greedy()))
            for r in live:
                assert r.finished.wait(600), "scheduler stalled"
            wall = time.perf_counter() - t0
        finally:
            if drafting:
                COMPILES.freeze = frozen
            sched.close()
        outs = []
        for r in live:
            toks = []
            for t in r.tokens(timeout=5):
                toks.append(t)
            outs.append(toks)
        extra = {}
        if drafting:
            extra = {"spec": sched.stats.spec.summary(),
                     "compiles_after_warmup": COMPILES.after_warmup
                     - before}
        del sched, eng
        gc.collect()
        return {"agg_tok_per_s": round(
            sum(len(o) for o in outs) / wall, 1), "outs": outs, **extra}

    off = serve(False)
    on = serve(True)
    serve_parity = off["outs"] == on["outs"]
    off.pop("outs")
    on.pop("outs")

    del eng_p, eng_l, eng_d, draft, params
    gc.collect()
    return {
        "metric": f"{prefix}_selfdraft_speculative_speedup_nonrepetitive",
        "value": round(best_plain / best_dr, 2), "unit": "x",
        "vs_baseline": None,
        "eval_label": label,
        "repeated_3gram_frac": round(rep_frac, 3),
        "tokens": n, "draft_depth": depth, "draft_len": draft_len,
        "token_parity": bool(single_parity and serve_parity),
        "selfdraft": {
            "tok_per_s": round(n / best_dr, 1),
            "tokens_per_forward": round(dr_n / dr_fwd, 2),
            "accept_rate": round(dr_spec["accepted"]
                                 / max(dr_spec["drafted"], 1), 3),
            "drafted": dr_spec["drafted"],
            "accepted": dr_spec["accepted"],
        },
        "prompt_lookup": {
            "tok_per_s": round(n / best_lk, 1),
            "speedup_vs_plain": round(best_plain / best_lk, 2),
            "tokens_per_forward": round(lk_n / lk_fwd, 2),
            "accept_rate": round(lk_spec["accepted"]
                                 / max(lk_spec["drafted"], 1), 3)
            if lk_spec["drafted"] else None,
            "drafted": lk_spec["drafted"],
        },
        "plain_tok_per_s": round(n / best_plain, 1),
        "serving_ab": {
            "requests": n_req, "batch": b, "budget": budget,
            "draft_off": off, "draft_on": on,
            "agg_speedup": round(on["agg_tok_per_s"]
                                 / off["agg_tok_per_s"], 2),
        },
        "compiles_after_warmup": on.get("compiles_after_warmup"),
    }


def _chaos_row(params, spec: ModelSpec, prefix: str, b: int = 4) -> dict:
    """Serving resilience under injected faults (the ISSUE-3 metric):
    replay a fixed-seed Poisson arrival trace through the SUPERVISED
    scheduler (runtime/resilience.EngineSupervisor) with deterministic
    step crashes injected mid-trace (runtime/faults.py), and report what a
    client fleet actually experiences:

      * availability %      — fraction of wall time /readyz would be 200
                              (polled at 5 ms)
      * recovered vs failed — requests that got a structured error frame
                              and succeeded on ONE client retry, vs ones
                              that did not
      * recovery p50 ms     — failure detected -> ready again
                              (SupervisorStats.recovery_ms)

    Env knobs: BENCH_CHAOS_REQUESTS (default 24), BENCH_CHAOS_BATCH
    (default 4), BENCH_CHAOS_CRASHES (default 2 — spaced across the
    trace: each next crash arms only after the previous recovery)."""
    import gc
    import threading
    import time

    from distributed_llama_tpu.runtime.faults import FAULTS
    from distributed_llama_tpu.runtime.resilience import EngineSupervisor
    from distributed_llama_tpu.runtime.scheduler import RequestError
    from distributed_llama_tpu.sampler import Sampler

    b = int(os.environ.get("BENCH_CHAOS_BATCH", str(b)))
    n_req = max(int(os.environ.get("BENCH_CHAOS_REQUESTS", "24")), 2)
    n_crashes = int(os.environ.get("BENCH_CHAOS_CRASHES", "2"))
    seq = min(512, spec.seq_len)
    cdt = jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16

    rng = np.random.default_rng(0)
    lens = [(8, 16, 32)[i % 3] for i in range(n_req)]
    prompts = [rng.integers(1, spec.vocab_size, n).astype(np.int64).tolist()
               for n in lens]
    budgets = [int(x) for x in rng.integers(8, 33, n_req)]
    arrivals = np.cumsum(rng.exponential(0.05, n_req))

    def factory():
        return Engine(spec, params, compute_dtype=cdt, cache_dtype=cdt,
                      max_seq_len=seq, batch=b)

    sup = EngineSupervisor(factory, chunk=32, stall_timeout=60.0,
                           backoff_base=0.05, breaker_threshold=10_000)
    _note_hbm(sup.engine, sup.prefix_cache)

    def greedy():
        return Sampler(spec.vocab_size, temperature=0.0, topp=0.9, seed=7)

    # availability sampler: what /readyz would answer, at 5 ms resolution
    ready_samples: list[bool] = []
    sampling = threading.Event()
    sampling.set()

    def sample_ready():
        while sampling.is_set():
            ready_samples.append(sup.ready)
            time.sleep(0.005)

    # crash scheduler: arm the next step crash only after the previous
    # recovery completed, so crashes SPACE OUT across the trace instead of
    # burning the breaker on back-to-back failures
    def inject_crashes():
        for k in range(n_crashes):
            while sup.sup_stats.recoveries < k and sampling.is_set():
                time.sleep(0.01)
            if not sampling.is_set():
                return
            FAULTS.arm("step_raise", after=5)  # a few steps of grace

    results = {"ok_first": 0, "recovered": 0, "unrecovered": 0}
    res_lock = threading.Lock()

    def run_request(prompt, budget):
        # one client-side retry: a structured error frame (RequestError)
        # or an unready rejection waits for /readyz then resubmits once
        for attempt in range(2):
            try:
                while not sup.ready:
                    time.sleep(0.02)
                req = sup.submit(prompt, budget, greedy())
                n = sum(1 for _ in req.tokens(timeout=120.0))
                with res_lock:
                    results["ok_first" if attempt == 0
                            else "recovered"] += 1
                return n
            except RequestError:
                if attempt == 1:
                    with res_lock:
                        results["unrecovered"] += 1
            except Exception:  # noqa: BLE001 — unready race on submit
                if attempt == 1:
                    with res_lock:
                        results["unrecovered"] += 1
        return 0

    threads: list[threading.Thread] = []
    tokens_out = [0] * n_req

    def client(i):
        tokens_out[i] = run_request(prompts[i], budgets[i])

    t0 = time.perf_counter()
    samp = threading.Thread(target=sample_ready, daemon=True)
    samp.start()
    inj = threading.Thread(target=inject_crashes, daemon=True)
    inj.start()
    try:
        for i in range(n_req):
            dt = t0 + arrivals[i] - time.perf_counter()
            if dt > 0:
                time.sleep(dt)
            t = threading.Thread(target=client, args=(i,), daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=240.0)
    finally:
        sampling.clear()
        FAULTS.clear()
    wall = time.perf_counter() - t0
    samp.join(timeout=2.0)
    availability = (100.0 * sum(ready_samples) / len(ready_samples)
                    if ready_samples else 0.0)
    rec = sorted(sup.sup_stats.recovery_ms)
    rec_p50 = rec[len(rec) // 2] if rec else None
    summary = sup.summary()
    sup.close()
    del sup
    gc.collect()
    return {
        "metric": f"{prefix}_chaos_batch{b}_availability_pct",
        "value": round(availability, 2), "unit": "%", "vs_baseline": None,
        "requests": n_req,
        "crashes_injected": summary["resilience"]["crashes"],
        "ok_first_attempt": results["ok_first"],
        "recovered_by_retry": results["recovered"],
        "unrecovered": results["unrecovered"],
        "requests_failed_frames": summary["requests_failed"],
        "recoveries": summary["resilience"]["recoveries"],
        "recovery_p50_ms": round(rec_p50, 1) if rec_p50 is not None else None,
        "tokens_out": int(sum(tokens_out)),
        "wall_s": round(wall, 2),
    }


def _router_row(params, spec: ModelSpec, prefix: str, b: int = 2) -> dict:
    """Multi-replica serving tier (the ISSUE-6 metric): a shared-prefix
    Poisson trace — prompts drawn from BENCH_ROUTER_GROUPS distinct
    system-prompt families — served by TWO replicas twice:

      * ROUND_ROBIN — the "2x independent servers" regime: requests
        alternate replicas blindly, so every prefix family must warm on
        BOTH replicas before it ever hits;
      * CACHE_AWARE — the router's SGLang-style placement: each family
        concentrates on the replica whose radix tree already holds it,
        so a family pays exactly ONE cold prefill tier-wide.

    The placement A/B runs CLOSED-LOOP (one request in flight at a time):
    with a fixed seed the placement sequence — and therefore the
    hit/miss count — is fully DETERMINISTIC, so the reported gap
    measures the policy, never CPU timing luck. The chaos pass then
    re-serves the trace OPEN-LOOP (Poisson arrivals, work genuinely in
    flight) on cache_aware with ONE replica killed mid-trace
    (replica_raise, count-deterministic) to measure what clients
    experience during the failure: availability % (router readiness at
    5 ms), ZERO failed not-yet-streamed requests (retried on the
    survivor), structured frames for mid-stream ones, and greedy token
    parity with the crash-free runs.

    Env knobs: BENCH_ROUTER_REQUESTS (default 16), BENCH_ROUTER_BATCH
    (per-replica slots, default 2), BENCH_ROUTER_GROUPS (default 4),
    BENCH_ROUTER_SYS (shared tokens per family, default 48),
    BENCH_ROUTER_BLOCK (block_len, default 16), BENCH_ROUTER_BLOCKS
    (arena blocks per replica, default ample for every family),
    BENCH_ROUTER_TOKENS (decode budget, default 8),
    BENCH_ROUTER_KILL_AFTER (replica 0 steps before the kill, default 5).
    """
    import gc
    import threading
    import time

    from distributed_llama_tpu.runtime.faults import FAULTS
    from distributed_llama_tpu.runtime.router import Router
    from distributed_llama_tpu.runtime.scheduler import RequestError
    from distributed_llama_tpu.sampler import Sampler

    b = int(os.environ.get("BENCH_ROUTER_BATCH", str(b)))
    n_req = max(int(os.environ.get("BENCH_ROUTER_REQUESTS", "16")), 4)
    groups = max(int(os.environ.get("BENCH_ROUTER_GROUPS", "4")), 1)
    sys_len = int(os.environ.get("BENCH_ROUTER_SYS", "48"))
    bl = int(os.environ.get("BENCH_ROUTER_BLOCK", "16"))
    budget = int(os.environ.get("BENCH_ROUTER_TOKENS", "8"))
    kill_after = int(os.environ.get("BENCH_ROUTER_KILL_AFTER", "5"))
    blocks = int(os.environ.get(
        "BENCH_ROUTER_BLOCKS",
        str(2 * groups * (sys_len // bl + 1) + 8)))
    seq = min(512, spec.seq_len)
    cdt = jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16

    rng = np.random.default_rng(0)
    families = [rng.integers(1, spec.vocab_size, sys_len).astype(
        np.int64).tolist() for _ in range(groups)]
    gidx = rng.integers(0, groups, n_req)
    tails = [rng.integers(1, spec.vocab_size, (8, 12, 16)[i % 3]).astype(
        np.int64).tolist() for i in range(n_req)]
    prompts = [families[int(gidx[i])] + tails[i] for i in range(n_req)]
    arrivals = np.cumsum(rng.exponential(0.04, n_req))

    def factory():
        return Engine(spec, params, compute_dtype=cdt, cache_dtype=cdt,
                      max_seq_len=seq, batch=b)

    def greedy():
        return Sampler(spec.vocab_size, temperature=0.0, topp=0.9, seed=7)

    def run_trace(policy: str, kill: bool, closed_loop: bool) -> dict:
        FAULTS.clear()
        router = Router(factory, replicas=2, policy=policy, retry_budget=1,
                        chunk=bl, stall_timeout=60.0, backoff_base=0.05,
                        breaker_threshold=10_000, circuit_threshold=10_000,
                        prefix_blocks=blocks, prefix_block_len=bl)
        h0 = router.replicas[0]
        _note_hbm(h0.sup.engine, h0.sup.prefix_cache)  # one replica's
        # exact shape (siblings are identical and SHARE the weights)
        outs: dict = {}
        errs: dict = {}
        ready_samples: list = []
        sampling = threading.Event()
        sampling.set()

        def sample_ready():
            while sampling.is_set():
                ready_samples.append(router.ready)
                time.sleep(0.005)

        def client(i):
            got: list = []
            try:
                req = router.submit(prompts[i], budget, greedy())
                for t in req.tokens(timeout=300.0):
                    got.append(t)
                outs[i] = (got, req.retries)
            except RequestError as e:
                errs[i] = (len(got), e)
            except Exception as e:  # noqa: BLE001 — no-replica rejection
                errs[i] = (len(got), e)

        if kill:
            FAULTS.arm("replica_raise", key="r0", after=kill_after)
        samp = threading.Thread(target=sample_ready, daemon=True)
        samp.start()
        threads = []
        t0 = time.perf_counter()
        try:
            for i in range(n_req):
                if closed_loop:
                    # placement A/B: one request at a time — with both
                    # replicas idle at every pick, the placement (and so
                    # the hit count) is a pure, deterministic function
                    # of the policy
                    client(i)
                    continue
                dt = t0 + arrivals[i] - time.perf_counter()
                if dt > 0:
                    time.sleep(dt)
                t = threading.Thread(target=client, args=(i,), daemon=True)
                t.start()
                threads.append(t)
            for t in threads:
                t.join(timeout=300.0)
        finally:
            sampling.clear()
            FAULTS.clear()
        wall = time.perf_counter() - t0
        samp.join(timeout=2.0)
        # prefix-cache counters across EVERY generation of both replicas
        # (a killed replica's pre-crash stats live in its supervisor's
        # dead-generation list, not the rebuilt tree's fresh zeros)
        all_stats = []
        for h in router.replicas:
            all_stats.append(h.sup.stats)
            all_stats.extend(h.sup._dead_stats)
        lookups = sum(s.prefix.lookups for s in all_stats if s.prefix)
        hits = sum(s.prefix.hits for s in all_stats if s.prefix)
        saved = sum(s.prefix.tokens_saved for s in all_stats if s.prefix)
        prefilled = sum(s.prefix.tokens_prefilled for s in all_stats
                        if s.prefix)
        summary = router.summary()
        crashes = sum(r["resilience"]["crashes"]
                      for r in summary["replicas"])
        out = {
            "hit_rate_pct": round(100.0 * hits / lookups, 2) if lookups
            else 0.0,
            "prefill_saved_pct": round(
                100.0 * saved / (saved + prefilled), 2)
            if saved + prefilled else 0.0,
            "agg_tok_per_s": round(
                sum(len(o) for o, _ in outs.values()) / wall, 1),
            "ttft_p50_ms": summary["ttft_p50_ms"],
            "availability_pct": round(
                100.0 * sum(ready_samples) / len(ready_samples), 2)
            if ready_samples else None,
            "completed": len(outs),
            "unstreamed_failures": sum(1 for n, _ in errs.values()
                                       if n == 0),
            "midstream_failures": sum(1 for n, _ in errs.values()
                                      if n > 0),
            "retries": router.stats.retries,
            "failovers_ok": router.stats.failovers_ok,
            "crashes_injected": crashes,
            "outs": {i: o for i, (o, _) in outs.items()},
        }
        router.close()
        del router
        gc.collect()
        return out

    # three serves of the SAME trace: the placement A/B runs crash-free
    # (the hit-rate gap must measure the POLICY, not which run ate the
    # kill), then the chaos pass re-runs cache-aware with one replica
    # killed mid-trace for the availability/failover numbers
    rr = run_trace("round_robin", kill=False, closed_loop=True)
    ca = run_trace("cache_aware", kill=False, closed_loop=True)
    chaos = run_trace("cache_aware", kill=True, closed_loop=False)
    # greedy parity: every request COMPLETED in a run must match the
    # round-robin run token-for-token (failover replays are
    # bit-identical; mid-stream kills errored structurally and are
    # excluded by construction)
    parity = all(run["outs"][i] == rr["outs"][i]
                 for run in (ca, chaos) for i in run["outs"]
                 if i in rr["outs"])
    for run in (rr, ca, chaos):
        run.pop("outs")
    return {
        "metric": f"{prefix}_router_2rep_cache_aware_hit_rate_pct",
        "value": ca["hit_rate_pct"], "unit": "%", "vs_baseline": None,
        "requests": n_req, "replicas": 2, "batch_per_replica": b,
        "prefix_families": groups, "family_tokens": sys_len,
        "block_len": bl, "arena_blocks_per_replica": blocks,
        "token_parity": parity,
        "round_robin": rr, "cache_aware": ca, "cache_aware_chaos": chaos,
        "hit_rate_gain_pct": round(
            ca["hit_rate_pct"] - rr["hit_rate_pct"], 2),
    }


_CPU_WORKER_ENV = {"JAX_PLATFORMS": "cpu"}


def _label_cpu_workers(row: dict) -> dict:
    """Rows whose measured work ran in CPU child processes, whatever the
    parent's backend: labelled as such, never as the parent's device."""
    row.update(platform="cpu", device_kind="cpu", device_count=None)
    return row


def _router_procs_row(prefix: str) -> dict:
    """Process-isolated replica tier (the ISSUE-7 metric): spawn TWO real
    replica worker OS processes (runtime/replica_worker.py — each its own
    single-process CPU-JAX interpreter over deterministic synthetic
    weights, served through the framed replica protocol), drive an
    open-loop Poisson trace through the failover router, and deliver a
    REAL ``SIGKILL -9`` to one worker mid-trace. Reported:

      * kill_to_routable_ms / respawn_p50_ms — death -> the respawned
        worker is warmed and routable again (the supervised-respawn
        bound the chaos tests pin);
      * availability_pct — router readiness sampled at 5 ms: the sibling
        replica must keep the SERVICE ready through the whole outage;
      * unstreamed_failures — requests that failed with zero tokens
        streamed: must be 0 (the connection EOF is a structured
        retryable frame, failed over to the sibling within the retry
        budget); mid-stream casualties get the structured non-retryable
        frame and are counted separately, never silently replayed;
      * token_parity — every completed serve of the same prompt (either
        replica, pre- or post-kill, failover replays, the respawned
        process) produced IDENTICAL greedy tokens. Compared pairwise
        across completions, so the bar is backend-independent: both
        workers hold bit-identical params by construction (same
        spec/seed), and the respawned one reloads exactly them.

    Workers pace decode via a worker-side ``slow_step`` fault so the kill
    provably lands while streams are in flight. Env knobs:
    BENCH_PROCS_REQUESTS (default 10), BENCH_PROCS_TOKENS (decode budget,
    default 6), BENCH_PROCS_KILL_AFTER (requests submitted before the
    kill, default half the trace), BENCH_PROCS_STEP_MS (decode pacing,
    default 40), BENCH_PROCS_SPAWN_TIMEOUT (startup/respawn bound,
    default 300 s — includes the worker's jax import + tiny-model
    compile on a cold XLA cache)."""
    import gc
    import signal as _signal
    import tempfile
    import threading
    import time as _time

    from distributed_llama_tpu.runtime.replica_worker import WorkerProc
    from distributed_llama_tpu.runtime.router import (RemoteReplicaHandle,
                                                      Router)
    from distributed_llama_tpu.runtime.scheduler import RequestError
    from distributed_llama_tpu.sampler import Sampler

    n_req = max(int(os.environ.get("BENCH_PROCS_REQUESTS", "10")), 4)
    budget = int(os.environ.get("BENCH_PROCS_TOKENS", "6"))
    kill_after = int(os.environ.get("BENCH_PROCS_KILL_AFTER",
                                    str(n_req // 2)))
    step_ms = int(os.environ.get("BENCH_PROCS_STEP_MS", "40"))
    spawn_timeout = float(os.environ.get("BENCH_PROCS_SPAWN_TIMEOUT",
                                         "300"))

    spec_fields = dict(dim=64, hidden_dim=128, n_layers=2, n_heads=4,
                       n_kv_heads=2, vocab_size=128, seq_len=128)
    cfg = {"test_spec": spec_fields, "seed": 11, "scale": 0.05,
           "compute_dtype": "f32", "batch": 2,
           # the survivor absorbs the whole trace during the outage —
           # its admission queue must hold every not-yet-served request
           "serve": {"stall_timeout": 60.0, "max_queue": n_req},
           # worker-side flight recorder: each worker's step timeline
           # rides its stats reply (span events are off the hot path —
           # decode_every huge keeps the ring step-dominated)
           "trace": {"capacity": 2048, "decode_every": 1 << 30}}
    # workers are CPU JAX (the parent holds the chip, and a chip belongs
    # to one process): this row measures host-side plumbing and says so —
    # _label_cpu_workers stamps it. Workers place their compile cache with
    # the same helper as every process (utils/compile_cache.py), so worker
    # 1 and every respawn reuse worker 0's compiles
    wenv = dict(_CPU_WORKER_ENV)
    workdir = tempfile.mkdtemp(prefix="dllama-bench-procs-")

    def mk(i):
        proc = WorkerProc(i, dict(cfg, fault_key=f"r{i}"), workdir=workdir,
                          env=wenv,
                          faults=f"slow_step:times=0;ms={step_ms}")
        return RemoteReplicaHandle(i, proc=proc, poll_interval=0.1,
                                   spawn_backoff_base=0.05,
                                   spawn_timeout=spawn_timeout,
                                   respawn_timeout=spawn_timeout)

    # spawn the two worker processes CONCURRENTLY (handle construction
    # blocks on the port handshake — import + weight build + warmup):
    # the row measures kill-to-routable, not cold-start serialization
    handles: list = [None, None]
    builders = [threading.Thread(target=lambda i=i: handles.__setitem__(
        i, mk(i))) for i in (0, 1)]
    for t in builders:
        t.start()
    for t in builders:
        t.join()
    if any(h is None for h in handles):
        for h in handles:
            if h is not None:
                h.close()  # don't orphan the sibling that DID come up
        raise RuntimeError("replica worker spawn failed (see workdir logs)")

    rng = np.random.default_rng(3)
    # each distinct prompt appears (at least) twice in the trace — the
    # parity bar compares completions of the same prompt pairwise
    distinct = [rng.integers(1, spec_fields["vocab_size"],
                             12 + 4 * (i % 3)).astype(np.int64).tolist()
                for i in range(max(n_req // 2, 1))]
    prompts = [distinct[i % len(distinct)] for i in range(n_req)]
    arrivals = np.cumsum(rng.exponential(0.08, n_req))

    def greedy():
        return Sampler(spec_fields["vocab_size"], temperature=0.0,
                       topp=0.9, seed=5)

    router = Router(None, policy="round_robin", retry_budget=1,
                    handle_factories=[lambda: handles[0],
                                      lambda: handles[1]])
    h0 = router.replicas[0]
    outs: dict = {}
    errs: dict = {}
    ready_samples: list = []
    sampling = threading.Event()
    sampling.set()

    def sample_ready():
        while sampling.is_set():
            ready_samples.append(router.ready)
            _time.sleep(0.005)

    def client(i):
        got: list = []
        try:
            req = router.submit(prompts[i], budget, greedy())
            for t in req.tokens(timeout=300.0):
                got.append(t)
            outs[i] = got
        except RequestError as e:
            errs[i] = (len(got), e)
        except Exception as e:  # noqa: BLE001 — no-replica rejection
            errs[i] = (len(got), e)

    kill_to_routable_ms = None
    try:
        samp = threading.Thread(target=sample_ready, daemon=True)
        samp.start()
        threads = []
        t_kill = None
        t0 = _time.perf_counter()
        for i in range(n_req):
            dt = t0 + arrivals[i] - _time.perf_counter()
            if dt > 0:
                _time.sleep(dt)
            t = threading.Thread(target=client, args=(i,), daemon=True)
            t.start()
            threads.append(t)
            if i + 1 == kill_after:
                t_kill = _time.perf_counter()
                os.kill(h0._proc.proc.pid, _signal.SIGKILL)
        for t in threads:
            t.join(timeout=300.0)
        # supervised respawn: keep sampling readiness until the killed
        # replica is routable again (the acceptance bound)
        end = _time.perf_counter() + spawn_timeout
        while _time.perf_counter() < end and not h0.ready:
            _time.sleep(0.01)
        if h0.ready and t_kill is not None:
            kill_to_routable_ms = (_time.perf_counter() - t_kill) * 1e3
        # the respawned process SERVES: one more lap of the trace's first
        # two prompts so round_robin provably lands one on each replica
        for i in (0, 1):
            req = router.submit(prompts[i], budget, greedy())
            outs[n_req + i] = list(req.tokens(timeout=300.0))
            prompts.append(prompts[i])
    finally:
        sampling.clear()
        proc_stats = h0.proc_stats.summary()
        stats = router.stats
        # worker-local step timelines (steps never cross the boundary;
        # the stats reply carries each worker's summary) — keyed per
        # replica so two workers' compositions never merge
        step_timeline = {}
        hbm = {}
        for h in handles:
            s = (h.client.stats_summary() or {}) if h is not None else {}
            for k, v in (s.get("step_timeline") or {}).items():
                step_timeline[f"r{h.id}_{k}"] = v
            # per-WORKER hbm ledgers off the same stats reply (each
            # process owns its weights — no shared-buffer caveat here)
            if s.get("hbm"):
                hbm[f"r{h.id}"] = s["hbm"]
        router.close()
        gc.collect()

    by_prompt: dict = {}
    for i, toks in outs.items():
        by_prompt.setdefault(tuple(prompts[i]), []).append(toks)
    parity = all(all(o == serves[0] for o in serves)
                 for serves in by_prompt.values())
    return {
        "metric": f"{prefix}_router_procs_sigkill_respawn_ms",
        "value": (None if kill_to_routable_ms is None
                  else round(kill_to_routable_ms, 1)),
        "unit": "ms", "vs_baseline": None,
        "hbm": hbm,  # per-WORKER ledgers, rK-keyed (this row is emitted
        # outside _with_step_timeline — it builds its own blocks)
        "mode": "process", "replicas": 2, "requests": n_req,
        "decode_step_ms": step_ms,
        "kill_to_routable_ms": (None if kill_to_routable_ms is None
                                else round(kill_to_routable_ms, 1)),
        "respawn_p50_ms": proc_stats["respawn_p50_ms"],
        "respawns": proc_stats["respawns"],
        "exit_classes": proc_stats["exit_classes"],
        "availability_pct": round(
            100.0 * sum(ready_samples) / len(ready_samples), 2)
        if ready_samples else None,
        "completed": len(outs),
        "unstreamed_failures": sum(1 for n, _ in errs.values() if n == 0),
        "midstream_failures": sum(1 for n, _ in errs.values() if n > 0),
        "retries": stats.retries,
        "failovers_ok": stats.failovers_ok,
        "token_parity": parity,
        "step_timeline": step_timeline,
        # the acceptance bars ride the row
        "within_bound": (kill_to_routable_ms is not None
                         and kill_to_routable_ms / 1e3 < spawn_timeout),
        "spawn_timeout_s": spawn_timeout,
    }


def _fleet_row(prefix: str) -> dict:
    """Fleet-brain chaos row (the ISSUE-18 metric): TWO tenants drive a
    process-replica tier through a 10x Poisson load spike with one
    replica SIGKILLed mid-spike, under the FleetController
    (runtime/fleet.py). The victim tenant (high priority, weight 4)
    sends the SAME slow trickle before and during the spike; the hog
    tenant (low priority, weight 1, token-budgeted) floods 10x arrivals
    only during the spike. Reported bars:

      * victim_p99_ttft_ms — the victim's spike-phase p99 TTFT must
        stay at SLO (BENCH_FLEET_SLO_MS, default 2000): weighted-fair
        queueing means the hog's overage buys the hog latency, not the
        victim;
      * victim_p99_ratio — spike p99 over baseline p99 (reported; the
        fairness story in one number);
      * scale_ups >= 1 — the controller VISIBLY grew the replica set
        under the spike (pressure EWMA over threshold), HBM-capped;
      * unstreamed_failures == 0 — the SIGKILL mid-spike failed over
        every not-yet-streamed request; nothing was silently lost.

    Env knobs: BENCH_FLEET_REQUESTS (hog spike requests, default 16),
    BENCH_FLEET_VICTIM (victim requests per phase, default 6),
    BENCH_FLEET_TOKENS (decode budget, default 6), BENCH_FLEET_STEP_MS
    (worker decode pacing, default 40), BENCH_FLEET_SLO_MS (victim p99
    TTFT bar, default 2000), BENCH_FLEET_IAT (victim inter-arrival s,
    default 0.5; the hog floods at IAT/10), BENCH_FLEET_SPAWN_TIMEOUT
    (startup/scale-up bound, default 300 s)."""
    import gc
    import signal as _signal
    import tempfile
    import threading
    import time as _time

    from distributed_llama_tpu.runtime.fleet import (FleetConfig,
                                                     FleetController)
    from distributed_llama_tpu.runtime.replica_worker import WorkerProc
    from distributed_llama_tpu.runtime.router import (RemoteReplicaHandle,
                                                      Router)
    from distributed_llama_tpu.runtime.scheduler import RequestError
    from distributed_llama_tpu.sampler import Sampler

    n_hog = max(int(os.environ.get("BENCH_FLEET_REQUESTS", "16")), 4)
    n_victim = max(int(os.environ.get("BENCH_FLEET_VICTIM", "6")), 3)
    budget = int(os.environ.get("BENCH_FLEET_TOKENS", "6"))
    step_ms = int(os.environ.get("BENCH_FLEET_STEP_MS", "40"))
    slo_ms = float(os.environ.get("BENCH_FLEET_SLO_MS", "2000"))
    iat = float(os.environ.get("BENCH_FLEET_IAT", "0.5"))
    spawn_timeout = float(os.environ.get("BENCH_FLEET_SPAWN_TIMEOUT",
                                         "300"))

    spec_fields = dict(dim=64, hidden_dim=128, n_layers=2, n_heads=4,
                       n_kv_heads=2, vocab_size=128, seq_len=128)
    cfg = {"test_spec": spec_fields, "seed": 11, "scale": 0.05,
           "compute_dtype": "f32", "batch": 2,
           # the whole spike may queue on two replicas while the third
           # spawns; weighted-fair ordering happens IN this queue
           "serve": {"stall_timeout": 60.0,
                     "max_queue": n_hog + 2 * n_victim,
                     # hog sustains 50 tok/s; the victim is unlimited —
                     # over budget, the hog is served only when no
                     # in-budget tenant waits
                     "tenant_budgets": "hog=1:50,victim=4"},
           "trace": {"capacity": 2048, "decode_every": 1 << 30}}
    wenv = dict(_CPU_WORKER_ENV)
    workdir = tempfile.mkdtemp(prefix="dllama-bench-fleet-")

    def mk(i):
        proc = WorkerProc(i, dict(cfg, fault_key=f"r{i}"), workdir=workdir,
                          env=wenv,
                          faults=f"slow_step:times=0;ms={step_ms}")
        return RemoteReplicaHandle(i, proc=proc, poll_interval=0.1,
                                   spawn_backoff_base=0.05,
                                   spawn_timeout=spawn_timeout,
                                   respawn_timeout=spawn_timeout)

    handles: list = [None, None]
    builders = [threading.Thread(target=lambda i=i: handles.__setitem__(
        i, mk(i))) for i in (0, 1)]
    for t in builders:
        t.start()
    for t in builders:
        t.join()
    if any(h is None for h in handles):
        for h in handles:
            if h is not None:
                h.close()
        raise RuntimeError("replica worker spawn failed (see workdir logs)")

    router = Router(None, policy="round_robin", retry_budget=1,
                    handle_factories=[lambda: handles[0],
                                      lambda: handles[1]])
    # arm the scale-up path: the controller spawns r2.. through this
    router._spawn_factory = lambda rid, tier: mk(rid)
    fleet = FleetController(
        router, config=FleetConfig(min_replicas=2, max_replicas=3,
                                   poll=0.1, up_pressure=0.6,
                                   up_after=2, down_after=10_000,
                                   cooldown_ticks=2))
    h0 = router.replicas[0]
    rng = np.random.default_rng(7)
    prompt_of: dict = {}
    ttfts: dict = {}    # label -> ms
    errs: dict = {}

    def greedy():
        return Sampler(spec_fields["vocab_size"], temperature=0.0,
                       topp=0.9, seed=5)

    def client(label, tenant, priority, prompt):
        got: list = []
        t0 = _time.perf_counter()
        try:
            req = router.submit(prompt, budget, greedy(),
                                tenant=tenant, priority=priority)
            for t in req.tokens(timeout=300.0):
                if not got:
                    ttfts[label] = (_time.perf_counter() - t0) * 1e3
                got.append(t)
            prompt_of[label] = (tuple(prompt), tuple(got))
        except (RequestError, Exception) as e:  # noqa: BLE001
            errs[label] = (len(got), e)

    def run_phase(phase, victim_iat, hog_n, hog_iat, kill_at=None):
        threads = []
        v_arr = np.cumsum(rng.exponential(victim_iat, n_victim))
        h_arr = (np.cumsum(rng.exponential(hog_iat, hog_n))
                 if hog_n else np.array([]))
        events = sorted(
            [(t, "victim", i) for i, t in enumerate(v_arr)]
            + [(t, "hog", i) for i, t in enumerate(h_arr)])
        t0 = _time.perf_counter()
        for k, (at, who, i) in enumerate(events):
            dt = t0 + at - _time.perf_counter()
            if dt > 0:
                _time.sleep(dt)
            n_tok = 12 + 4 * (i % 3)
            prompt = rng.integers(1, spec_fields["vocab_size"],
                                  n_tok).astype(np.int64).tolist()
            pr = "high" if who == "victim" else "low"
            th = threading.Thread(target=client,
                                  args=(f"{phase}:{who}:{i}", who, pr,
                                        prompt), daemon=True)
            th.start()
            threads.append(th)
            if kill_at is not None and k + 1 == kill_at:
                os.kill(h0._proc.proc.pid, _signal.SIGKILL)
        for th in threads:
            th.join(timeout=300.0)

    try:
        # baseline: the victim alone, controller running but unprovoked
        fleet.start()
        run_phase("base", iat, 0, 0.0)
        base = sorted(v for k, v in ttfts.items() if k.startswith("base:"))
        # spike: hog floods at 10x the victim's rate; SIGKILL replica 0
        # a third of the way in — the controller must absorb BOTH
        run_phase("spike", iat, n_hog, iat / 10.0,
                  kill_at=max((n_hog + n_victim) // 3, 2))
        # let in-flight scale decisions land before reading the summary
        deadline = _time.perf_counter() + spawn_timeout
        while (_time.perf_counter() < deadline
               and router.scaling is not None):
            _time.sleep(0.05)
    finally:
        fleet_summary = fleet.summary()
        fleet.close()
        stats = router.stats
        router.close()
        gc.collect()

    spike = sorted(v for k, v in ttfts.items() if k.startswith("spike:")
                   and ":victim:" in k)
    base_p99 = base[int(0.99 * (len(base) - 1))] if base else None
    victim_p99 = spike[int(0.99 * (len(spike) - 1))] if spike else None
    # per-tenant view from the CLIENT side (the WFQ ledger itself lives
    # in the workers, where the queueing happens): completions + spike
    # p99 per tenant — the hog's queueing delay vs the victim's
    tenant_view = {}
    for who in ("victim", "hog"):
        lat = sorted(v for k, v in ttfts.items()
                     if k.startswith("spike:") and f":{who}:" in k)
        tenant_view[who] = {
            "completed": sum(1 for k in ttfts if f":{who}:" in k),
            "spike_p99_ttft_ms": (round(lat[int(0.99 * (len(lat) - 1))], 1)
                                  if lat else None),
        }
    # greedy parity across every completion of the same prompt length
    # is not meaningful here (prompts are unique); the parity bar lives
    # in the router/procs rows — this row pins fairness + scaling
    unstreamed = sum(1 for n, _ in errs.values() if n == 0)
    return {
        "metric": f"{prefix}_fleet_spike_victim_p99_ttft_ms",
        "value": (None if victim_p99 is None else round(victim_p99, 1)),
        "unit": "ms", "vs_baseline": None,
        "mode": "process", "boot_replicas": 2,
        "hog_requests": n_hog, "victim_requests_per_phase": n_victim,
        "decode_step_ms": step_ms, "slo_ms": slo_ms,
        "victim_base_p99_ttft_ms": (None if base_p99 is None
                                    else round(base_p99, 1)),
        "victim_p99_ratio": (None if not (base_p99 and victim_p99)
                             else round(victim_p99 / base_p99, 2)),
        "victim_within_slo": (victim_p99 is not None
                              and victim_p99 <= slo_ms),
        "scale_ups": fleet_summary.get("scale_ups", 0),
        "scale_blocked_hbm": fleet_summary.get("scale_blocked_hbm", 0),
        "actual_replicas_end": fleet_summary.get("actual_replicas"),
        "tenants": tenant_view,
        "completed": len(ttfts),
        "unstreamed_failures": unstreamed,
        "midstream_failures": sum(1 for n, _ in errs.values() if n > 0),
        "retries": stats.retries, "failovers_ok": stats.failovers_ok,
        # the acceptance bars ride the row
        "within_bound": (victim_p99 is not None and victim_p99 <= slo_ms
                         and unstreamed == 0
                         and fleet_summary.get("scale_ups", 0) >= 1),
    }


def _cluster_chaos_row(prefix: str) -> dict:
    """Cluster worker-loss detection latency (the ISSUE-5 metric): spawn
    REAL two-OS-process control-plane clusters (parallel/cluster_harness
    .py — no model/mesh, pure root<->worker star) and measure
    death-of-worker -> root's structured ClusterPeerLost, wall clock,
    for the two failure shapes:

      * detect_eof_ms   — worker os._exit mid-phase (socket EOF: the
                          fast path), p50 over BENCH_CLUSTER_REPEATS runs
      * detect_stall_ms — worker reader wedged via the recv_stall fault
                          (socket stays open; only heartbeat silence can
                          see it): must land within worker_timeout + one
                          recv granularity, never hang

    Env knobs: BENCH_CLUSTER_REPEATS (default 3), BENCH_CLUSTER_TIMEOUT
    (--worker-timeout, default 2.0), BENCH_CLUSTER_HB (default 0.2)."""
    import time as _time

    from distributed_llama_tpu.testing import free_port

    repeats = int(os.environ.get("BENCH_CLUSTER_REPEATS", "3"))
    w_timeout = float(os.environ.get("BENCH_CLUSTER_TIMEOUT", "2.0"))
    hb = float(os.environ.get("BENCH_CLUSTER_HB", "0.2"))
    harness = "distributed_llama_tpu.parallel.cluster_harness"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # the harness never inits a backend
    env.pop("DLLAMA_FAULTS", None)

    def launch_pair(phases, worker_extra=(), faults=""):
        """ONE home for the harness launch/parse/reap protocol (fault
        and clean runs both ride it — a CLI/framing change must not be
        made twice). Returns (root events, worker events); a worker
        whose reader is wedged by a fault never exits on its own and is
        reaped before its communicate."""
        port = free_port()
        wenv = dict(env)
        if faults:
            wenv["DLLAMA_FAULTS"] = faults
        common = ["--heartbeat-interval", str(hb),
                  "--worker-timeout", str(w_timeout)]
        root = subprocess.Popen(
            [sys.executable, "-m", harness, "root", "--port", str(port),
             "--phases", phases, *common],
            env=env, text=True, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL)
        worker = subprocess.Popen(
            [sys.executable, "-m", harness, "worker", "--port", str(port),
             "--rank", "1", *common, *worker_extra],
            env=wenv, text=True, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL)
        try:
            r_out, _ = root.communicate(timeout=w_timeout + 90)
            if worker.poll() is None:  # wedged reader never exits on its
                worker.kill()          # own — reap it immediately
            w_out, _ = worker.communicate(timeout=10)
            return ([json.loads(ln) for ln in r_out.splitlines()
                     if ln.startswith("{")],
                    [json.loads(ln) for ln in w_out.splitlines()
                     if ln.startswith("{")])
        finally:
            for p in (root, worker):
                if p.poll() is None:
                    p.kill()
                    p.communicate(timeout=10)

    def run_pair(worker_extra, faults=""):
        r_ev, w_ev = launch_pair("formation:0.1,decode:60",
                                 worker_extra, faults)
        lost = next(e for e in r_ev if e["event"] == "cluster_peer_lost")
        return lost, w_ev

    def clean_pair(phases: str):
        """One CLEAN run (no faults, normal shutdown): the wire-ledger
        source. Returns (root complete.stats, worker shutdown.stats,
        [tick phase names])."""
        r_ev, w_ev = launch_pair(phases)
        return (next(e for e in r_ev if e["event"] == "complete")["stats"],
                next(e for e in w_ev if e["event"] == "shutdown")["stats"],
                [e["phase"] for e in w_ev if e["event"] == "tick"])

    eof_ms = []
    for _ in range(repeats):
        lost, w_ev = run_pair(["--die-after", "0.5"])
        died = next(e for e in w_ev if e["event"] == "dying")
        eof_ms.append((lost["t_wall"] - died["t_wall"]) * 1e3)
    # one stall run: detection latency ~= worker_timeout by construction,
    # measured from the worker's LAST frame (the root's own accounting).
    # Monotonic clock for the local interval — an NTP step mid-run would
    # corrupt a wall-clock difference (the cross-process t_wall deltas
    # above are the one place wall clock is unavoidable)
    t0 = _time.perf_counter()
    lost, _ = run_pair([], faults="recv_stall:after=2;times=0")
    stall_wall_s = _time.perf_counter() - t0
    eof_ms.sort()

    # the measured wire plane (dlwire): one clean run's ledger from both
    # ends, reconciled EXACTLY against frame-size arithmetic — the
    # protocol frames (phase ticks) have deterministic sizes, so drift
    # here is 0 by construction or the ledger is broken
    from distributed_llama_tpu.parallel.multihost import (_HEADER_LEN,
                                                          frame_bytes)
    from distributed_llama_tpu.runtime.netstats import reconcile_wire
    phases = "formation:0.1,tick_a:0.3,tick_b:0.3"
    root_stats, worker_stats, ticks = clean_pair(phases)
    w_peer0 = ((worker_stats.get("wire") or {}).get("peers") or {}
               ).get("0") or {}
    measured_run_rx = ((w_peer0.get("rx") or {}).get("RUN")
                       or {"bytes": 0})["bytes"]
    modeled_run_rx = sum(frame_bytes(_HEADER_LEN, len(name.encode()))
                         for name in ticks)
    reconcile = reconcile_wire(measured_run_rx, modeled_run_rx,
                               unit="bytes")
    # the row's step_timeline: the control plane's "step" is one
    # heartbeat round trip — every RTT sample from the clean run's
    # ledger feeds the dec0/pre0/c0 composition (decode-curve consumers
    # ignore dec=0 rows by construction; dlprof's wire report reads it)
    wire = root_stats.get("wire") or {}
    for peer_rec in (wire.get("peers") or {}).values():
        for rtt in (peer_rec.get("rtt_ms") or {}).get("recent", ()):
            TRACER.step(decode_rows=0, prefill_rows=0, chunk=0,
                        queue_depth=0, wall_ms=rtt)
    return {
        "metric": f"{prefix}_cluster_detect_eof_ms",
        "value": round(eof_ms[len(eof_ms) // 2], 1), "unit": "ms",
        "vs_baseline": None,
        "repeats": repeats,
        "detect_eof_ms_all": [round(v, 1) for v in eof_ms],
        "detect_stall_last_seen_s": lost["last_seen_s"],
        "stall_run_wall_s": round(stall_wall_s, 2),
        "worker_timeout_s": w_timeout,
        "heartbeat_interval_s": hb,
        "stall_reason": lost["reason"],
        # the acceptance bar rides the row: detection is bounded
        "within_bound": (eof_ms[-1] / 1e3 < w_timeout
                         and lost["last_seen_s"] < w_timeout + 1.0),
        # the measured cluster wire plane (root + worker ledgers of the
        # clean run) and the exact control-plane reconciliation
        "wire": {"root": wire, "worker": worker_stats.get("wire") or {},
                 "reconcile": reconcile},
    }


def _variant_rows(engine, params, spec: ModelSpec, repeats: int, emit) -> None:
    """Extra measured rows for the default 7b run: prefill throughput,
    8k-fill long-context decode (bf16 and fp8 caches — the documented fp8
    attention tax as a measured artifact), and the lookup-decode row.
    Each row is passed to `emit` the moment it is measured."""
    import gc

    n_pre = 2048
    # prefill runs are short (~0.4 s): extra repeats are nearly free and
    # tighten the best-of-N
    tok_s = _measure_prefill(engine, n_pre, max(repeats, 4))
    emit({
        "metric": "llama2_7b_q40_prefill_2048_tok_per_s",
        "value": round(tok_s, 1), "unit": "tok/s", "vs_baseline": None,
        "step_timeline": {}})

    spec8k = dataclasses.replace(spec, seq_len=8192)
    for cdt, name in ((jnp.bfloat16, "bf16"), (jnp.float8_e4m3fn, "f8")):
        eng = Engine(spec8k, params, compute_dtype=jnp.bfloat16,
                     cache_dtype=cdt, max_seq_len=8192)
        emit(_with_step_timeline(
            lambda eng=eng, cdt=cdt, name=name: _decode_row(
                f"llama2_7b_q40_decode_8kfill_{name}_cache_ms_per_token",
                spec8k, _measure_decode(eng, 256, 7680, repeats),
                fill=7680, n_tokens=256,
                cache_itemsize=jnp.dtype(cdt).itemsize)))
        del eng
        gc.collect()

    emit(_with_step_timeline(_shardmap_row, engine, params, spec, repeats))
    emit(_with_step_timeline(_lookup_row, engine, repeats))
    # batched decode needs its own engine (batch is a build-time shape);
    # the 7b weights are shared, the extra KV cache is 512-seq x 8 rows
    emit(_with_step_timeline(_batch_row, params, spec, repeats))
    emit(_with_step_timeline(_batch_lookup_row, params, spec, repeats))


def _shardmap_row(engine, params, spec: ModelSpec, repeats: int) -> dict:
    """The multi-chip kernel path ON SILICON (VERDICT r4 #1): a 1-device
    Mesh(('tp',)) engine with force_mesh_kernels=True runs every Q40 matmul
    and the flash attention as Pallas kernels INSIDE shard_map manual
    regions — the exact lowering (Mosaic under manual partitioning) that
    every multi-chip perf claim rides on, previously executed only in
    interpret mode off-chip. Measured INTERLEAVED against the direct-kernel
    engine (same-process alternation, best-of-N per variant) and reported as a parity ratio."""
    from distributed_llama_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(tp=1, devices=jax.devices()[:1])
    eng_sm = Engine(spec, params, mesh, compute_dtype=jnp.bfloat16,
                    cache_dtype=jnp.bfloat16, max_seq_len=spec.seq_len,
                    force_mesh_kernels=True)
    n = 128
    best_direct = best_sm = None
    for _ in range(max(repeats, 3)):
        ms_d = _measure_decode(engine, n, 0, 1)
        ms_s = _measure_decode(eng_sm, n, 0, 1)
        best_direct = ms_d if best_direct is None else min(best_direct, ms_d)
        best_sm = ms_s if best_sm is None else min(best_sm, ms_s)
    row = _decode_row("llama2_7b_q40_decode_shardmap_1dev_ms_per_token",
                      spec, best_sm, n_tokens=n)
    row["direct_ms_per_token"] = round(best_direct, 3)
    row["vs_direct_kernel"] = round(best_sm / best_direct, 3)
    del eng_sm
    import gc

    gc.collect()  # engines hold reference cycles; free the HBM now
    return row


def _moe_row(repeats: int) -> dict:
    """Mixtral-shaped MoE decode (the expert-gather path,
    ops/pallas_q40.q40_expert_matmul). Runs with the chip to itself —
    callers must drop the 7b engine/params first (a resident 3.9 GB
    neighbor measured ~25% off the standalone bandwidth)."""
    import gc

    moe_params = synth_q40_params(MIXTRAL_MOE)
    eng = Engine(MIXTRAL_MOE, moe_params, compute_dtype=jnp.bfloat16,
                 cache_dtype=jnp.bfloat16)
    msm = _measure_decode(eng, 256, 0, repeats)
    row = _decode_row("mixtral_moe_q40_decode_ms_per_token_1chip",
                      MIXTRAL_MOE, msm, n_tokens=256)
    # per-layer cost extrapolates to full-depth Mixtral/Grok (decode cost is
    # layer-linear; wcls/embedding amortize further at 32 layers)
    row["ms_per_token_per_layer"] = round(msm / MIXTRAL_MOE.n_layers, 4)
    del eng, moe_params
    gc.collect()
    return row


def _grok_row(repeats: int) -> dict:
    """Grok-1 decode at PRODUCTION widths (VERDICT r4 #5): the 4-norm GELU
    MoE block at dim 6144 / hidden 32768 / 131k vocab, 2 layers resident
    (7.6 GB — a full-width layer is 2.72 GB packed). Needs the chip alone
    like _moe_row; the per-layer column extrapolates to all 64 layers."""
    import gc

    params = synth_q40_params(GROK1_TRUNC)
    eng = Engine(GROK1_TRUNC, params, compute_dtype=jnp.bfloat16,
                 cache_dtype=jnp.bfloat16)
    msg = _measure_decode(eng, 128, 0, repeats)
    row = _decode_row("grok1_fullwidth_q40_decode_ms_per_token_1chip",
                      GROK1_TRUNC, msg, n_tokens=128)
    row["ms_per_token_per_layer"] = round(msg / GROK1_TRUNC.n_layers, 4)
    row["full_depth_64l_ms_per_token_extrapolated"] = round(
        msg / GROK1_TRUNC.n_layers * 64, 2)
    del eng, params
    gc.collect()
    return row


def _kvx_row(params, spec: ModelSpec, prefix: str) -> dict:
    """Cross-replica KV block transfer row (the ISSUE-14 metric,
    runtime/kv_transfer.py), two passes:

    1. COLD-REPLICA FILL A/B — a shared-prefix Poisson-paced trace of
       family pairs served by a 2-replica router (in-process
       ReplicaServers behind connect-mode handles: every frame crosses
       a REAL socket) under round-robin placement, so each family's
       second request lands on the replica that has NEVER seen it.
       Transfer OFF: the cold replica re-prefills the family prefix.
       Transfer ON: it fetches the donor's published blocks
       (RMSG_BLOCK_*) and prefills only the tail. Reported: cold-request
       TTFT p50 OFF vs ON (acceptance: >= 30% better ON), fill hit
       rate, measured BLOCK_DATA wire bytes RECONCILED against the
       frame-size arithmetic (25% bar; exact by construction), greedy
       TOKEN PARITY between the runs, and zero post-warmup compiles
       with the ledger FROZEN through the ON serve.

    2. DISAGGREGATED PREFILL/DECODE A/B — a decode-heavy stream with
       long prompts arriving concurrently, served by (a) ONE unified
       mixed replica and (b) a prefill-tier + decode-tier pair (equal
       decode capacity). Reported: the decode stream's ITL p99 + the
       long prompts' TTFT p50 under both shapes, parity + zero
       failures asserted (the perf delta is the finding, CPU timing is
       not asserted).

    Env knobs: BENCH_KVX_FAMILIES (6), BENCH_KVX_SYS (64),
    BENCH_KVX_BLOCK (16), BENCH_KVX_TOKENS (8), BENCH_KVX_IAT (0.02),
    BENCH_KVX_LONG (96), BENCH_KVX_STREAMS (4)."""
    import gc
    import time

    from distributed_llama_tpu.parallel.multihost import frame_bytes
    from distributed_llama_tpu.runtime import kv_transfer as kvx
    from distributed_llama_tpu.runtime.engine import Engine as _Eng
    from distributed_llama_tpu.runtime.netstats import (
        estimate_block_transfer, reconcile_wire)
    from distributed_llama_tpu.runtime.profiler import COMPILES
    from distributed_llama_tpu.runtime.replica_worker import ReplicaServer
    from distributed_llama_tpu.runtime.resilience import EngineSupervisor
    from distributed_llama_tpu.runtime.router import (RemoteReplicaHandle,
                                                      Router)
    from distributed_llama_tpu.sampler import Sampler

    n_fam = int(os.environ.get("BENCH_KVX_FAMILIES", "6"))
    sys_len = int(os.environ.get("BENCH_KVX_SYS", "64"))
    bl = int(os.environ.get("BENCH_KVX_BLOCK", "16"))
    budget = int(os.environ.get("BENCH_KVX_TOKENS", "8"))
    iat = float(os.environ.get("BENCH_KVX_IAT", "0.02"))
    long_len = int(os.environ.get("BENCH_KVX_LONG", "96"))
    n_streams = int(os.environ.get("BENCH_KVX_STREAMS", "4"))
    seq = min(512, spec.seq_len)
    cdt = jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16
    b = 2

    def sup_factory(key=None):
        def make_engine():
            return _Eng(spec, params, batch=b, compute_dtype=cdt,
                        cache_dtype=cdt, max_seq_len=seq)
        # chunk = block_len, like the prefix row: the A/B measures
        # chunked prefill vs block fills — a chunk wider than the whole
        # prompt would hide the prefill cost inside one fixed-width
        # forward and measure nothing
        return lambda: EngineSupervisor(
            make_engine, chunk=bl,
            prefix_blocks=max(2 * b * seq // bl, 64),
            prefix_block_len=bl, kv_transfer=True, stall_timeout=60.0,
            fault_key=key)

    def cluster(tiers, *, transfer, policy="round_robin"):
        servers = [ReplicaServer(sup_factory(f"r{i}"),
                                 kv_transfer=transfer, tier=t)
                   for i, t in enumerate(tiers)]
        ports = [s.start() for s in servers]
        handles = [RemoteReplicaHandle(i, address=("127.0.0.1", p),
                                       block_len=bl, poll_interval=0.1)
                   for i, p in enumerate(ports)]
        router = Router(None, policy=policy,
                        handle_factories=[(lambda h=h: h)
                                          for h in handles],
                        kv_transfer=transfer, fill_min_tokens=bl)
        return servers, handles, router

    def greedy():
        return Sampler(spec.vocab_size, temperature=0.0, topp=0.9,
                       seed=7)

    rng = np.random.default_rng(0)
    fams = [rng.integers(1, spec.vocab_size, sys_len).astype(
        np.int64).tolist() for _ in range(n_fam)]
    tails = [rng.integers(1, spec.vocab_size, 4 + (i % 3) * 4).astype(
        np.int64).tolist() for i in range(n_fam)]
    gaps = rng.exponential(iat, n_fam)

    def run_fill_trace(transfer):
        """Pairs per family: the warm request places on r0 (round robin)
        and publishes; the cold one places on r1. Returns (tokens,
        cold-TTFT list, servers) — servers still open for ledger reads."""
        servers, _handles, router = cluster(("mixed", "mixed"),
                                            transfer=transfer)
        if transfer:
            COMPILES.freeze = True  # acceptance: the ON serve mints
            # zero post-warmup keys (a violation fails requests loudly)
        outs, cold_ttft = [], []
        try:
            for i, (fam, tail) in enumerate(zip(fams, tails)):
                time.sleep(min(gaps[i], 0.2))
                prompt = fam + tail
                warm = router.submit(prompt, budget, greedy())
                outs.append(list(warm.tokens(timeout=120)))
                cold = router.submit(prompt, budget, greedy())
                outs.append(list(cold.tokens(timeout=120)))
                assert cold.replica_id != warm.replica_id
                cold_ttft.append(cold.stats.ttft_ms)
        finally:
            COMPILES.freeze = False
        summary = router.summary()
        router.close()
        return outs, sorted(cold_ttft), servers, summary

    warm_compiles = COMPILES.after_warmup
    outs_off, ttft_off, servers_off, _ = run_fill_trace(False)
    for s in servers_off:
        s.shutdown()
    outs_on, ttft_on, servers_on, summ_on = run_fill_trace(True)
    frozen_delta = COMPILES.after_warmup - warm_compiles

    # the measured block-frame ledger vs the exact frame arithmetic
    agg = summ_on["kv_transfer"]
    measured_data = sum(
        srv.kvx_stats.wire.peer_bytes(peer, "BLOCK_DATA", "rx")
        for srv in servers_on for peer in (0, 1))
    per_block = kvx.block_payload_bytes(
        spec.n_layers, spec.n_kv_heads, bl, spec.head_size, cdt)
    modeled_data = agg["blocks_filled"] * frame_bytes(1, per_block)
    rec = reconcile_wire(measured_data, modeled_data)
    est = estimate_block_transfer(
        spec, tokens=agg["blocks_filled"] * bl, block_len=bl,
        cache_bytes=jnp.dtype(cdt).itemsize)
    for s in servers_on:
        s.shutdown()

    # -- pass 2: disaggregated prefill/decode A/B ------------------------
    longs = [rng.integers(1, spec.vocab_size, long_len).astype(
        np.int64).tolist() for _ in range(n_streams)]
    shorts = [rng.integers(1, spec.vocab_size, 8).astype(
        np.int64).tolist() for _ in range(n_streams)]

    def run_disagg(tiers, transfer):
        servers, _h, router = cluster(tiers, transfer=transfer)
        outs, itls, ttfts = [], [], []
        try:
            import threading as _th
            results = {}

            def serve(tag, prompt, toks):
                r = router.submit(prompt, toks, greedy())
                results[tag] = (list(r.tokens(timeout=180)), r.stats)

            threads = []
            for i in range(n_streams):
                threads.append(_th.Thread(
                    target=serve, args=(f"s{i}", shorts[i], 24)))
                threads.append(_th.Thread(
                    target=serve, args=(f"l{i}", longs[i], 4)))
            for t in threads:
                t.start()
                time.sleep(iat)
            for t in threads:
                t.join(timeout=240)
            for i in range(n_streams):
                toks, st = results[f"s{i}"]
                outs.append(toks)
                if st.itl_ms is not None:
                    itls.append(st.itl_ms)
                toks_l, st_l = results[f"l{i}"]
                outs.append(toks_l)
                ttfts.append(st_l.ttft_ms)
        finally:
            router.close()
            for s in servers:
                s.shutdown()
        itls.sort()
        ttfts.sort()
        return outs, {
            "itl_p99_ms": round(itls[-1], 3) if itls else None,
            "itl_p50_ms": round(itls[len(itls) // 2], 3)
            if itls else None,
            "long_ttft_p50_ms": round(ttfts[len(ttfts) // 2], 3)
            if ttfts else None,
        }

    outs_uni, uni = run_disagg(("mixed",), transfer=False)
    outs_dis, dis = run_disagg(("prefill", "decode"), transfer=True)

    gc.collect()
    ttft_off_p50 = ttft_off[len(ttft_off) // 2]
    ttft_on_p50 = ttft_on[len(ttft_on) // 2]
    gain = (ttft_off_p50 - ttft_on_p50) / ttft_off_p50 \
        if ttft_off_p50 else 0.0
    return {
        "metric": f"{prefix}_kv_transfer_cold_ttft_gain_pct",
        "value": round(100.0 * gain, 2),
        "unit": "%", "vs_baseline": None,
        "families": n_fam, "shared_prefix_tokens": sys_len,
        "block_len": bl,
        "token_parity": outs_on == outs_off,
        "token_parity_disagg": outs_dis == outs_uni,
        "cold_ttft_p50_ms_off": round(ttft_off_p50, 3),
        "cold_ttft_p50_ms_on": round(ttft_on_p50, 3),
        "fill_hit_rate": (round(agg["fills_ok"]
                                / agg["fills_requested"], 4)
                          if agg["fills_requested"] else None),
        "fills_ok": agg["fills_ok"],
        "fill_fallbacks": agg["fill_fallbacks"],
        "tokens_filled": agg["tokens_filled"],
        "blocks_filled": agg["blocks_filled"],
        "bytes_rx": agg["bytes_rx"],
        "compiles_after_warmup": frozen_delta,
        "unified": uni, "disaggregated": dis,
        "kv_transfer": {**agg, "reconcile": rec},
        "wire_model": est,
        "reconcile": rec,
    }


def _vocab_child() -> None:
    """Child body of the BENCH_VOCAB row (own process: the vocab A/B
    needs a tp mesh, and the virtual-device XLA flag is parse-once per
    process). Serves the SAME mixed greedy/sampled trace through a real
    Scheduler on a tp mesh twice — vocab-sharded vs replicated head —
    asserting greedy token parity, then times the head+sample path of
    one decode step per variant and reads both HBM ledgers. Prints ONE
    JSON line on stdout."""
    import gc
    import time

    from distributed_llama_tpu.parallel.mesh import make_mesh
    from distributed_llama_tpu.runtime.profiler import COMPILES, hbm_ledger
    from distributed_llama_tpu.runtime.scheduler import Scheduler
    from distributed_llama_tpu.sampler import Sampler

    tp = int(os.environ.get("BENCH_VOCAB_TP", "2"))
    b = int(os.environ.get("BENCH_VOCAB_BATCH", "2"))
    n_req = max(int(os.environ.get("BENCH_VOCAB_REQUESTS", "8")), 2)
    budget = int(os.environ.get("BENCH_VOCAB_TOKENS", "8"))
    steps = int(os.environ.get("BENCH_VOCAB_STEPS", "30"))
    spec = TINY
    params = synth_q40_params(spec)

    def serve(shard: bool):
        mesh = make_mesh(tp=tp, dp=1)
        eng = Engine(spec, dict(params), mesh, batch=b,
                     compute_dtype=jnp.bfloat16, cache_dtype=jnp.bfloat16,
                     max_seq_len=spec.seq_len, shard_vocab=shard)
        sched = Scheduler(eng, chunk=32)
        sched.warmup()
        COMPILES.reset()
        eng.mark_compile_warm()  # frozen-ledger bar: serving the trace
        COMPILES.freeze = True   # must mint ZERO new keys per variant
        outs = []
        try:
            reqs = []
            for i in range(n_req):
                # even requests greedy (parity bar), odd sampled at a
                # fixed seed (the sharded candidate path must serve them)
                temp = 0.0 if i % 2 == 0 else 0.8
                smp = Sampler(spec.vocab_size, temp, 0.9, seed=1234 + i,
                              backend="python")
                reqs.append(sched.submit(
                    [1 + i % 7, 5, 9 + i % 3, 2], budget, smp))
            while sched.has_work():
                sched.step()
            outs = [list(r.tokens()) for r in reqs]
            # parity bar = GREEDY rows only (even indices): sampled rows
            # are distribution-exact but their candidate probabilities
            # are the DEVICE softmax — a 1-ulp difference vs the host
            # softmax near a crossing could legitimately flip a sampled
            # token, and the design never promises sampled bit-parity
            greedy_outs = outs[0::2]
            frozen_delta = COMPILES.after_warmup
            # head+sample wall: one gated decode dispatch + the host
            # sample path (full (B, V) fetch vs sharded summaries)
            gate = np.full((b,), eng.seq_len, np.int32)
            tokz = np.zeros((b, 1), np.int32)
            view_vocab = spec.vocab_size
            smp_t = Sampler(spec.vocab_size, 0.0, 0.9, seed=7,
                            backend="python")
            best = None
            for _ in range(max(steps, 3)):
                t0 = time.perf_counter()
                lg = eng.slot_decode_step(tokz, gate)
                view = eng.sample_view(lg, None, view_vocab)
                view.sample(smp_t, 0)
                dt = (time.perf_counter() - t0) * 1e3
                best = dt if best is None else min(best, dt)
            led = hbm_ledger(eng, device_stats=False)
        finally:
            COMPILES.freeze = False
            sched.close()
        stats = dict(getattr(eng, "vocab_sample_stats", {}))
        del eng, sched
        gc.collect()
        return greedy_outs, outs, best, led, frozen_delta, stats

    g_on, outs_on, head_on, led_on, froz_on, st_on = serve(True)
    g_off, outs_off, head_off, led_off, froz_off, _ = serve(False)
    print(json.dumps({
        "tp": tp, "batch": b, "requests": n_req,
        "token_parity": g_on == g_off,
        "sampled_parity": outs_on == outs_off,  # informational: holds
        # unless device/host softmax rounding flips a draw
        "head_sample_ms_sharded": round(head_on, 3),
        "head_sample_ms_replicated": round(head_off, 3),
        "vocab_bytes_per_chip_sharded": led_on["vocab_bytes"],
        "vocab_bytes_per_chip_replicated": led_off["vocab_bytes"],
        "logits_ws_bytes_sharded": led_on["logits_workspace_bytes"],
        "logits_ws_bytes_replicated": led_off["logits_workspace_bytes"],
        "compiles_after_warmup_sharded": froz_on,
        "compiles_after_warmup_replicated": froz_off,
        "sampled_via_candidates": st_on.get("sharded", 0),
        "sampled_fallbacks": st_on.get("fallback", 0),
    }))


def _vocab_row(prefix: str) -> dict:
    """BENCH_VOCAB=1: the vocab-sharding A/B (ISSUE-15) — sharded vs
    replicated embedding+head on the same mixed greedy/sampled trace,
    greedy tokens asserted IDENTICAL, per-chip embedding+wcls bytes and
    the head+sample ms on the row, zero frozen-ledger compiles per
    variant. Runs in a child process: the tp mesh needs virtual CPU
    devices, and XLA parses that flag once per process."""
    env = dict(os.environ)
    env["BENCH_VOCAB_CHILD"] = "1"
    env["JAX_PLATFORMS"] = "cpu"  # virtual devices; the parent may hold a chip
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = " ".join(flags)
    r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       capture_output=True, text=True, timeout=900,
                       env=env, cwd=os.path.dirname(os.path.abspath(__file__)))
    if r.returncode != 0:
        return {"metric": f"{prefix}_vocab_shard_head_sample_ms",
                "value": None, "unit": "ms",
                "error": (r.stderr or r.stdout)[-400:]}
    child = json.loads(r.stdout.strip().splitlines()[-1])
    assert child["token_parity"], "vocab-sharded greedy tokens diverged"
    row = {
        "metric": f"{prefix}_vocab_shard_head_sample_ms",
        "value": child["head_sample_ms_sharded"], "unit": "ms",
        "vs_baseline": None,
        "vs_replicated": (round(child["head_sample_ms_sharded"]
                                / child["head_sample_ms_replicated"], 3)
                          if child["head_sample_ms_replicated"] else None),
    }
    row.update(child)
    return row


def main() -> None:
    if os.environ.get("BENCH_VOCAB_CHILD"):
        _vocab_child()
        return
    model = os.environ.get("BENCH_MODEL", "7b")
    # 512-token decode: the per-dispatch cost amortizes and attention runs
    # at realistic steady-state fill
    n_tokens = int(os.environ.get("BENCH_TOKENS", "512"))
    specs = {"7b": LLAMA2_7B, "8b": LLAMA3_8B, "13b": LLAMA2_13B,
             "moe": MIXTRAL_MOE, "grok": GROK1_TRUNC,
             "70bt": LLAMA2_70B_TRUNC, "tiny": TINY}
    if model not in specs:
        sys.exit(f"error: unknown BENCH_MODEL {model!r} "
                 f"(one of {', '.join(specs)})")
    spec = specs[model]
    # long-context variants: BENCH_SEQ widens the cache, BENCH_FILL starts
    # decode at a deep fill (the flash kernel reads ~fill bytes of cache)
    seq = int(os.environ.get("BENCH_SEQ", str(min(spec.seq_len, 2048))))
    fill = int(os.environ.get("BENCH_FILL", "0"))
    assert 0 <= fill < seq - 1, f"BENCH_FILL={fill} must be < BENCH_SEQ-1={seq - 1}"
    if seq != spec.seq_len:
        spec = dataclasses.replace(spec, seq_len=seq)
    cache_dtype = (jnp.float8_e4m3fn if os.environ.get("BENCH_CACHE") == "f8"
                   else jnp.bfloat16)
    # decode must fit the KV cache: decode_greedy_device has no per-step
    # overflow guard, so steps past seq_len would silently measure garbage
    n_tokens = min(n_tokens, seq - fill - 1)

    metric = {"7b": "llama2_7b_q40_decode_ms_per_token_1chip",
              "8b": "llama3_8b_q40_decode_ms_per_token_1chip",
              "13b": "llama2_13b_q40_decode_ms_per_token_1chip",
              "moe": "mixtral_moe_q40_decode_ms_per_token_1chip",
              "grok": "grok1_fullwidth_q40_decode_ms_per_token_1chip",
              "70bt": "llama2_70b_width_q40_decode_ms_per_token_1chip"}.get(
        model, "tiny_llama_q40_decode_ms_per_token")
    base = {"7b": BASELINE_MS_PER_TOKEN,
            "8b": BASELINE_8B_MS_PER_TOKEN,
            "13b": BASELINE_13B_MS_PER_TOKEN,
            "tiny": BASELINE_MS_PER_TOKEN}.get(model)  # no published MoE row

    # the JSON line exists (value: null) before any jax work: every failure
    # past this point still prints it, annotated, instead of a traceback
    out: dict = {"metric": metric, "value": None, "unit": "ms/token",
                 "vs_baseline": None}
    def emit(row: dict) -> None:
        for k, v in _device_fields().items():
            row.setdefault(k, v)  # CPU-worker rows keep their own label
        out.setdefault("variants", []).append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)

    # a driver-side `timeout` delivers SIGTERM: flush whatever was measured
    # as the one stdout JSON line instead of dying silently (the variant
    # ladder is long, and the already-measured rows are worth keeping) —
    # and exit non-zero: a cut run is not a complete one
    import signal

    def _flush_and_exit(signum, frame):
        out.setdefault("error", "terminated (driver timeout?) — "
                                "partial rows kept")
        print(json.dumps(out), flush=True)
        sys.exit(1)

    try:
        signal.signal(signal.SIGTERM, _flush_and_exit)
    except (ValueError, OSError):  # non-main thread / exotic platform
        pass

    try:
        from distributed_llama_tpu.utils.compile_cache import \
            ensure_compile_cache

        ensure_compile_cache()
        out.update(_device_fields())
        params = synth_q40_params(spec)
        engine = Engine(
            spec, params,
            compute_dtype=jnp.bfloat16, cache_dtype=cache_dtype,
            max_seq_len=seq)

        repeats = int(os.environ.get("BENCH_REPEATS", "2"))

        def _main():
            row = _decode_row(
                metric, spec, _measure_decode(engine, n_tokens, fill,
                                              repeats),
                fill=fill, n_tokens=n_tokens,
                cache_itemsize=jnp.dtype(cache_dtype).itemsize, base=base)
            _note_hbm(engine)
            return row

        main_row = _with_step_timeline(_main)
        ms_per_token = main_row["value"]
        out.update(main_row)
        if model in ("moe", "grok", "70bt"):
            # truncated-depth configs: the per-layer cost is the number
            # that extrapolates to full depth (includes the shared
            # wcls/embedding read spread over the resident layers — the
            # true per-layer weight read is slightly lower; full-depth
            # runs amortize the head further)
            out["ms_per_token_per_layer"] = round(
                ms_per_token / spec.n_layers, 4)
        print(json.dumps(out), file=sys.stderr, flush=True)
        if os.environ.get("BENCH_SIMULATE_OUTAGE"):  # test hook
            raise RuntimeError("simulated mid-run outage")

        if os.environ.get("BENCH_SERVE", "0") != "0":
            # continuous-batching serving row (runtime/scheduler.py) —
            # behind a flag so the default bench ladder stays fast; the
            # driver opts in with BENCH_SERVE=1 for the serving A/B
            emit(_with_step_timeline(_serve_row, params, spec,
                                     prefix=metric.split("_decode")[0]))

        if os.environ.get("BENCH_AUTOTUNE", "0") != "0":
            # the closed batch-knee loop (tools/autotune.py +
            # runtime/profiler.resolve_auto_shape + the SLO-aware
            # adaptive scheduler): calibrate, auto-size, then A/B the
            # self-tuned policy against every swept static setting on
            # goodput-at-SLO with greedy token parity and zero
            # post-warmup compiles asserted on the row
            emit(_with_step_timeline(_autotune_row, params, spec,
                                     prefix=metric.split("_decode")[0]))

        if os.environ.get("BENCH_PREFIX", "0") != "0":
            # radix prefix-cache row (runtime/prefix_cache.py): the
            # shared-system-prompt trace served cache OFF vs ON —
            # prefill tokens saved %, TTFT delta, greedy token parity
            emit(_with_step_timeline(_prefix_row, params, spec,
                                     prefix=metric.split("_decode")[0]))

        if os.environ.get("BENCH_ROUTER", "0") != "0":
            # multi-replica router row (runtime/router.py): the shared-
            # prefix trace at 2 replicas, cache-aware vs round-robin
            # placement, with one replica killed mid-trace — hit-rate
            # gain, availability %, zero-unstreamed-failure count
            # BENCH_ROUTER_PROCS selects the tier(s): "1" (default) =
            # thread row + process row, "0" = thread row only, "only" =
            # process row only (the smoke tests pick one each)
            procs_knob = os.environ.get("BENCH_ROUTER_PROCS", "1")
            if procs_knob != "only":
                emit(_with_step_timeline(
                    _router_row, params, spec,
                    prefix=metric.split("_decode")[0]))
            if procs_knob != "0":
                # process-mode row (runtime/replica_worker.py): two real
                # worker OS processes, one SIGKILLed mid-trace —
                # respawn-to-routable latency, availability %, zero
                # unstreamed failures, token parity
                emit(_label_cpu_workers(_router_procs_row(
                    prefix=metric.split("_decode")[0])))

        if os.environ.get("BENCH_FLEET", "0") != "0":
            # fleet-brain chaos row (runtime/fleet.py, ISSUE-18): two
            # tenants through a 10x Poisson spike + one SIGKILL under
            # the autoscaling controller — victim p99 TTFT at SLO,
            # replicas visibly scaling, zero unstreamed failures
            emit(_label_cpu_workers(_fleet_row(
                prefix=metric.split("_decode")[0])))

        if os.environ.get("BENCH_KVX", "0") != "0":
            # cross-replica KV block transfer row (runtime/
            # kv_transfer.py): the shared-prefix trace with cold-replica
            # fills OFF vs ON (TTFT p50, fill hit rate, measured block
            # frames reconciled against the frame arithmetic, greedy
            # parity, zero frozen-ledger compiles) plus the
            # disaggregated prefill/decode A/B against a unified tier
            emit(_with_step_timeline(_kvx_row, params, spec,
                                     prefix=metric.split("_decode")[0]))

        if os.environ.get("BENCH_VOCAB", "0") != "0":
            # vocab-sharding A/B row (ops/sharded_vocab.py, ISSUE-15):
            # sharded vs replicated embedding+head on the same trace,
            # greedy parity asserted, per-chip vocab bytes + head ms
            # (child process: the tp mesh needs virtual devices)
            emit(_label_cpu_workers(_vocab_row(
                prefix=metric.split("_decode")[0])))

        if os.environ.get("BENCH_SPEC", "0") != "0":
            # real-draft speculative decoding row (runtime/draft.py):
            # self-draft vs prompt-lookup vs plain greedy on a
            # fixed-seed NON-repetitive eval (measured accept rate +
            # repetitiveness label on the row — the VERDICT #6
            # reporting debt), plus the per-slot Poisson serving A/B
            # with the compile ledger frozen
            emit(_with_step_timeline(_spec_row,
                                     prefix=metric.split("_decode")[0]))

        if os.environ.get("BENCH_CHAOS", "0") != "0":
            # resilience row (runtime/resilience.py): the Poisson trace
            # replayed with injected mid-trace crashes — availability %,
            # recovered-request counts, recovery p50
            emit(_with_step_timeline(_chaos_row, params, spec,
                                     prefix=metric.split("_decode")[0]))
            # cluster row (parallel/multihost.py): two-process control-
            # plane chaos — worker death/stall -> structured detection
            # latency, bounded by --worker-timeout — plus the measured
            # wire plane (dlwire): a clean run's per-peer byte/RTT
            # ledger as the row's `wire` block, heartbeat round trips
            # as its step_timeline, and the exact frame-arithmetic
            # reconciliation
            emit(_with_step_timeline(
                _cluster_chaos_row, prefix=metric.split("_decode")[0]))

        # extra capability rows, measured in the same run (driver default
        # config only — explicit BENCH_* overrides mean a targeted A/B)
        defaults = (model == "7b" and fill == 0 and seq == 2048
                    and cache_dtype == jnp.bfloat16)
        if defaults and os.environ.get("BENCH_VARIANTS", "1") != "0":
            import gc

            _variant_rows(engine, params, spec, repeats, emit)
            del engine, params  # free the 7b weights before the MoE rows
            gc.collect()
            emit(_with_step_timeline(_moe_row, repeats))
            emit(_with_step_timeline(_grok_row, repeats))
    except Exception as e:  # partial rows survive a mid-run failure: the
        # JSON line still prints, annotated — and the exit code says the
        # run is not complete (SIGTERM likewise, via _flush_and_exit)
        out["error"] = f"{type(e).__name__}: {e}"[:400]
        print(json.dumps(out), flush=True)
        sys.exit(1)

    print(json.dumps(out))


if __name__ == "__main__":
    main()
