"""dllama CLI — inference / generate / chat modes.

TPU-native equivalent of the reference CLI (ref: src/apps/dllama/dllama.cpp):

  inference  prompt completion with a per-token benchmark line and end-of-run
             averages (ref: dllama.cpp:43-91)
  generate   plain streaming completion (ref: dllama.cpp:96-131)
  chat       interactive chat with the Llama-2 [INST]/<<SYS>> template
             (ref: dllama.cpp:133-178)
  api        OpenAI-compatible HTTP server (ref: src/apps/dllama-api)
  worker     join a multi-host cluster as a non-root process
             (ref: dllama.cpp:180-193). Single-host multi-device needs no
             workers — use --tp N. Across hosts, start workers with
             `dllama worker --nnodes N --node-rank r --coordinator h:p`
             and the root with the same --nnodes/--coordinator plus any
             mode; the mesh then spans every host's devices and workers
             follow the broadcast protocol (parallel/multihost.py)

Flag surface mirrors AppArgs::parse (ref: src/app.cpp:19-93) plus TPU mesh
flags. --weights-float-type / --buffer-float-type keep the reference
semantics: the former must match the model file, the latter selects the Q80
activation round-trip.
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np


def _int_or_auto(v: str):
    """argparse type for --serve-batch/--prefix-blocks: a plain int, or
    the literal 'auto' — resolved at engine build from the default
    batch knee capped by HBM-ledger headroom (runtime/profiler.
    resolve_auto_shape; docs/serving.md "Auto-sizing")."""
    s = v.strip().lower()
    if s == "auto":
        return "auto"
    try:
        return int(s)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected an integer or 'auto', got {v!r}")


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="dllama",
        description="TPU-native distributed-llama: run Llama/Mixtral/Grok-1 "
                    "inference from reference-format .m/.t files.")
    p.add_argument("mode", choices=["inference", "generate", "chat", "api", "worker"])
    p.add_argument("--model", help="path to .m model file")
    p.add_argument("--tokenizer", help="path to .t tokenizer file")
    p.add_argument("--prompt", default=None)
    p.add_argument("--steps", type=int, default=0,
                   help="max tokens to generate (0 = until seq_len, ref app.cpp:117-119)")
    p.add_argument("--temperature", type=float, default=0.8)  # ref: app.cpp:31
    p.add_argument("--topp", type=float, default=0.9)         # ref: app.cpp:32
    p.add_argument("--seed", type=int, default=None,
                   help="sampler seed (default: time, ref app.cpp:88-91)")
    p.add_argument("--weights-float-type", default=None,
                   choices=["f32", "f16", "q40", "q80"],
                   help="must match the model file (ref: app.cpp:47-48)")
    p.add_argument("--buffer-float-type", default="q80", choices=["f32", "q80"],
                   help="activation exchange dtype (q80 reproduces the "
                        "reference's quantized wire buffers, ref: app.cpp:49-50). "
                        "NOT honored with --pp > 1: pipeline stages reduce "
                        "with GSPMD-exact collectives (the quantized "
                        "exchange cannot nest inside the manual-pp region), "
                        "so q80 is ignored there and f32 exact collectives "
                        "run instead")
    p.add_argument("--nthreads", type=int, default=None,
                   help="accepted for reference CLI parity; XLA manages "
                        "device parallelism (ref: app.cpp:84)")
    p.add_argument("--workers", nargs="*", default=None,
                   help="n/a on TPU; use --tp (ref: app.cpp:51-74)")
    p.add_argument("--port", type=int, default=9990)
    p.add_argument("--host", default="0.0.0.0")
    # TPU-native flags
    p.add_argument("--tp", type=int, default=1, help="tensor-parallel mesh size")
    p.add_argument("--dp", type=int, default=1, help="data-parallel mesh size")
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel mesh size (ring-attention prefill)")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel mesh size (MoE models: each device "
                        "holds n_experts/ep experts)")
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline-parallel mesh size (each device holds "
                        "n_layers/pp layers and their KV cache). Contract "
                        "exclusions: --session is refused (stage-stacked "
                        "pp caches are not host-fetchable) and "
                        "--buffer-float-type q80 is ignored in favor of "
                        "exact f32 collectives")
    p.add_argument("--shard-vocab", default="auto",
                   choices=["auto", "on", "off"],
                   help="row-split the embedding table and logits head "
                        "over the vocab dim (ops/sharded_vocab.py): the "
                        "replicated 533 MB/chip table at 70B widths "
                        "becomes vocab/tp per chip, and serving never "
                        "materializes full logits (sharded argmax + "
                        "candidate top-k/top-p, greedy bit-identical, "
                        "sampled distribution-exact). auto = on whenever "
                        "the mesh's tp axes divide the vocab; off keeps "
                        "the replicated parity oracle")
    p.add_argument("--max-seq-len", type=int, default=None)
    p.add_argument("--compute-dtype", default="bf16", choices=["bf16", "f32"])
    p.add_argument("--cache-dtype", default="bf16",
                   choices=["bf16", "f32", "f8"],
                   help="KV-cache element type; f8 (e4m3) halves cache "
                        "memory — 2x context per device (net-new vs the "
                        "reference's f32-only cache) — at decode-rate "
                        "PARITY with bf16: the flash kernel upcasts f8 "
                        "blocks via in-register bit reassembly "
                        "(ops/pallas_attention._f8_bits_to; measured 7B "
                        "decode at 7680-deep fill 18.9 vs 18.8 ms/token, "
                        "r5 A/B — r4's 2.3x astype stall is gone)")
    p.add_argument("--pallas", action="store_true", default=None,
                   help="force the fused Pallas kernels on (default: on for "
                        "TPU backends, including multi-device meshes via "
                        "shard_map; off on CPU where Mosaic can't compile)")
    p.add_argument("--no-pallas", dest="pallas", action="store_false",
                   help="force the XLA dequant path instead of the Pallas "
                        "kernels")
    p.add_argument("--system-prompt", default=None, help="chat mode system prompt")
    p.add_argument("--session", default=None, metavar="FILE",
                   help="chat/api modes: persist the KV-cache session to "
                        "FILE (chat: after every turn; api: on shutdown) "
                        "and resume from it on start — a conversation "
                        "survives process restarts without re-prefilling "
                        "its history (net-new: the reference has no "
                        "session persistence, SURVEY.md §5.4)")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="write a jax.profiler trace of the generation to DIR "
                        "(view with tensorboard/xprof; net-new — the "
                        "reference has no profiler hooks, SURVEY.md §5.1)")
    p.add_argument("--device-sampling", action="store_true",
                   help="run the whole sampled decode loop on device (one "
                        "lax.while_loop that exits at eos; temperature/"
                        "top-p + reference-parity xorshift on the TPU — no "
                        "host round-trip per token). Composes with --dp: "
                        "batch row i gets its own device RNG stream seeded "
                        "seed+i (same prompt, distinct samples). "
                        "Output streams after the loop. Net-new: the "
                        "reference samples on CPU every token")
    p.add_argument("--lookup-decode", type=int, default=0, metavar="K",
                   help="speculative decoding: draft up to K tokens per "
                        "step from the context's own n-grams and verify "
                        "them in ONE forward (prompt lookup — decode is "
                        "weight-read-bound on TPU, so confirmed draft "
                        "tokens are nearly free). At --temperature 0 the "
                        "token stream is exactly the greedy stream; at "
                        "temperature > 0 tokens are accepted/resampled "
                        "rejection-style, distribution-exact vs the host "
                        "sampler (different RNG stream). Net-new: the "
                        "reference is strictly 1 token/forward")
    p.add_argument("--draft", default=None, metavar="self:D|model:PATH",
                   help="REAL-draft speculative decoding (runtime/draft"
                        ".py): 'self:D' runs the model's own first D "
                        "layers + logits head as a zero-extra-weights "
                        "draft (reuses the loaded buffers, keeps a small "
                        "D-layer KV cache); 'model:PATH' loads a "
                        "separate draft .m (same tokenizer) onto the "
                        "same machinery. Greedy output is BIT-IDENTICAL "
                        "to the plain stream (drafts only batch the "
                        "confirmation — and unlike --lookup-decode they "
                        "pay on ARBITRARY text, not just repetitive "
                        "text); temperature > 0 uses general rejection "
                        "resampling (min(1, p/q) accept against the "
                        "draft's real distribution), distribution-exact. "
                        "In api mode with --serve-batch, every slot "
                        "drafts per row through one fixed-width verify "
                        "forward, and the SLO admission policy degrades "
                        "to no-speculation when inter-token latency "
                        "endangers --slo-itl-ms. Mutually exclusive "
                        "with --lookup-decode")
    p.add_argument("--draft-len", type=int, default=None, metavar="K",
                   help="with --draft: tokens proposed per draft forward "
                        "(default 7). The verify width is 1 + K and is "
                        "compiled once; larger K amortizes more per "
                        "accept but wastes more draft work when the "
                        "draft diverges (watch dllama_spec_accept_rate, "
                        "docs/serving.md)")
    p.add_argument("--serve-batch", type=_int_or_auto, default=0,
                   metavar="B|auto",
                   help="api mode: run the continuous-batching scheduler "
                        "with B KV slots (runtime/scheduler.py, docs/"
                        "serving.md) — /v1/completions and /v1/chat/"
                        "completions join and leave the running decode "
                        "batch per step, and POST /v1/batch/completions "
                        "borrows the same engine. Decode is weight-read-"
                        "bound — B live slots amortize one weight read per "
                        "step for near-Bx aggregate tok/s; only the B-row "
                        "KV cache is new memory. 'auto' sizes B at startup "
                        "from a conservative default batch knee capped by "
                        "HBM-ledger headroom "
                        "— the decision is logged and exported on /stats "
                        "(docs/serving.md 'Auto-sizing'). Single-process "
                        "engines only; --tp composes (the vocab-sharded "
                        "serving path), other mesh axes and --nnodes are "
                        "refused. Net-new: the reference serves batch=1")
    p.add_argument("--serve-chunk", type=int, default=0, metavar="C",
                   help="api mode: prefill chunk width for the continuous-"
                        "batching scheduler (tail chunks pad to C, so C is "
                        "the ONLY prefill compilation key; 0 = the "
                        "engine's prefill chunk, capped to the context). "
                        "Smaller C bounds the inter-token stall admission "
                        "adds to running requests; larger C prefills new "
                        "prompts in fewer steps (docs/serving.md). With "
                        "--slo-ttft-ms/--slo-itl-ms this is the WIDEST "
                        "rung of the adaptive width ladder")
    # SLO-aware self-tuning admission (api mode, with --serve-batch;
    # runtime/scheduler.AdmissionPolicy, docs/serving.md "Auto-sizing and
    # SLO-aware admission"): either flag arms the policy
    p.add_argument("--slo-ttft-ms", type=float, default=None, metavar="MS",
                   help="api mode, with --serve-batch: time-to-first-token "
                        "target. The admission policy widens the chunked-"
                        "prefill width (toward --serve-chunk) when the "
                        "live TTFT EWMA endangers this bound and inter-"
                        "token latency has headroom — new prompts finish "
                        "prefilling in fewer iterations")
    p.add_argument("--slo-itl-ms", type=float, default=None, metavar="MS",
                   help="api mode, with --serve-batch: inter-token-latency "
                        "target. Every scheduler iteration with prefill "
                        "work stretches running streams' token gap by one "
                        "chunk forward; the admission policy shrinks the "
                        "chunk width one warmed rung at a time when the "
                        "live step-time EWMA approaches this bound, and "
                        "widens again when decode rows idle. Host-side "
                        "only: the width ladder is warmed up front, so "
                        "--freeze-compiles stays green while it adapts")
    # prefix-cache flags (api mode; runtime/prefix_cache.py,
    # docs/serving.md "Prefix caching")
    p.add_argument("--prefix-cache", action="store_true",
                   help="api mode, with --serve-batch: radix prefix cache "
                        "— cross-request KV reuse (runtime/prefix_cache"
                        ".py). Admissions seed the longest cached token "
                        "prefix (shared system prompts, few-shot "
                        "templates, chat history) from an on-device "
                        "block arena and prefill only the suffix; "
                        "finished prompts publish their blocks back. "
                        "GET /stats gains a prefix_cache hit-rate/"
                        "tokens-saved block. Net-new: the reference "
                        "recomputes every prompt from scratch")
    p.add_argument("--prefix-blocks", type=_int_or_auto, default=0,
                   metavar="N|auto",
                   help="prefix-cache arena size in blocks (0 = the "
                        "2 x serve-batch x context default; 'auto' = that "
                        "target capped by measured HBM headroom — the "
                        "arena never eats the slots' room; decision on "
                        "/stats like --serve-batch auto). Arena bytes = "
                        "N x 2 x layers x kv_heads x block_len x "
                        "head_size x cache dtype — budget it against the "
                        "B-row KV cache (docs/serving.md)")
    p.add_argument("--prefix-block-len", type=int, default=None,
                   metavar="L",
                   help="prefix-cache block granularity in tokens "
                        "(default 32): reuse is whole-blocks-only, so "
                        "smaller L matches more of a shared prefix but "
                        "spends more index/publish work per token "
                        "(docs/serving.md)")
    # serving-resilience flags (api mode; runtime/resilience.py,
    # docs/operations.md)
    p.add_argument("--queue-depth", type=int, default=0, metavar="N",
                   help="api mode: bound the scheduler admission queue at "
                        "N waiting requests — overload returns HTTP 429 + "
                        "Retry-After instead of queueing unboundedly "
                        "(0 = 4x --serve-batch)")
    p.add_argument("--request-deadline", type=float, default=0.0,
                   metavar="SECS",
                   help="api mode: per-request end-to-end budget; a "
                        "request past it (queued or mid-decode) fails "
                        "fast with a structured 'deadline' error frame "
                        "(0 = off)")
    p.add_argument("--stall-timeout", type=float, default=0.0,
                   metavar="SECS",
                   help="api mode: watchdog bound on one scheduler step — "
                        "a step stalled longer (a silent stall raises "
                        "nothing) marks the engine unhealthy and "
                        "triggers recovery (0 = default 10; must exceed "
                        "the worst-case step, compiles are warmed off "
                        "the clock)")
    p.add_argument("--drain-timeout", type=float, default=30.0,
                   metavar="SECS",
                   help="api mode: graceful-drain budget on SIGTERM — "
                        "admissions stop immediately, in-flight requests "
                        "get this long to finish before being failed "
                        "with structured shutdown frames")
    # multi-replica serving-tier flags (api mode; runtime/router.py,
    # docs/operations.md "Multi-replica operations")
    p.add_argument("--replicas", type=int, default=1, metavar="N",
                   help="api mode, with --serve-batch: run N supervised "
                        "engine replicas behind a cache-aware failover "
                        "router (runtime/router.py) — weights SHARED, "
                        "each replica its own KV cache + prefix arena. "
                        "A crashed/stalled/broken replica is invisible "
                        "to clients: not-yet-streamed requests retry on "
                        "a healthy sibling (token-identical for greedy), "
                        "/readyz stays ready while any replica serves, "
                        "and replicas drain/restart one at a time "
                        "(POST /admin/drain_replica) with zero failed "
                        "requests")
    p.add_argument("--retry-budget", type=int, default=None, metavar="K",
                   help="api mode, with --replicas: automatic failover "
                        "resubmits per request (default 1). Only "
                        "requests that have not streamed a token are "
                        "retried; mid-stream failures surface a "
                        "structured non-retryable error frame instead")
    p.add_argument("--route-policy", default=None,
                   choices=["cache_aware", "least_loaded", "round_robin"],
                   help="api mode, with --replicas: placement policy "
                        "(default cache_aware — route to the replica "
                        "whose radix tree caches the longest prompt "
                        "prefix, fall back to least-loaded; the SGLang "
                        "cache-aware routing idea). Session affinity "
                        "(body `session`/`user` field) applies under "
                        "every policy")
    # process-isolated replica flags (api mode; runtime/replica_worker.py,
    # docs/operations.md "Process-isolated replicas")
    p.add_argument("--replica-procs", type=int, default=0, metavar="N",
                   help="api mode, with --serve-batch: run N replicas as "
                        "supervised OS PROCESSES (each its own "
                        "interpreter + weights, served over the framed "
                        "replica protocol) instead of threads — the real "
                        "fault boundary: a segfault, OOM kill, or "
                        "SIGKILL costs ONE replica, the router fails "
                        "not-yet-streamed requests over to a sibling "
                        "(token-identical for greedy), and the process "
                        "supervisor respawns the dead worker under "
                        "backoff with exit-code classification. "
                        "Mutually exclusive with --replicas")
    p.add_argument("--replica-hosts", default=None, metavar="H:P,...",
                   help="api mode, with --serve-batch: comma-separated "
                        "host:port list of PRE-STARTED replica workers "
                        "(python -m distributed_llama_tpu.runtime."
                        "replica_worker on each host) — the cross-host "
                        "tier. No spawn supervision: each worker's "
                        "lifetime belongs to its host's operator. "
                        "Mutually exclusive with --replica-procs")
    # KV block transfer + prefill/decode disaggregation (runtime/
    # kv_transfer.py, docs/serving.md "KV block transfer")
    p.add_argument("--kv-transfer", action="store_true",
                   help="api mode, with --prefix-cache and a replica "
                        "tier: let replicas SHIP published KV blocks to "
                        "each other (RMSG_BLOCK_* over the framed "
                        "codec) — a replica placed cold on a prefix a "
                        "sibling caches FETCHES the blocks and seeds "
                        "them instead of re-prefilling (greedy outputs "
                        "bit-identical, transfer failures degrade to a "
                        "plain re-prefill). Also the carrier of --tier "
                        "disaggregation. Block frames ride the dlwire "
                        "ledger (dllama_kv_transfer_* on /metrics)")
    p.add_argument("--tier", default=None, metavar="T[,T...]",
                   help="api mode, with --kv-transfer: per-replica "
                        "disaggregation roles (prefill|decode|mixed; "
                        "one value applies to all, or a comma list "
                        "matching the replica count). prefill-tier "
                        "replicas run ONLY prompt prefills (big "
                        "chunks, no decode occupancy) and stream their "
                        "blocks to decode-tier replicas, which admit "
                        "already-seeded — the vLLM-lineage split that "
                        "kills prefill/decode interference. The router "
                        "falls back to the unified mixed path when no "
                        "prefill replica is routable. Not with "
                        "--replica-hosts (set `tier` in each worker's "
                        "own config; the router learns it from the "
                        "health PONG)")
    # fleet brain (runtime/fleet.py, docs/operations.md "Overload and
    # autoscaling"): load-adaptive replica autoscaling, SLO-aware
    # overload shedding, multi-tenant weighted fairness
    p.add_argument("--min-replicas", type=int, default=0, metavar="N",
                   help="api mode, with a replica tier: floor of the "
                        "fleet controller's autoscaling window (default: "
                        "the boot replica count — autoscaling off). The "
                        "controller drains + reaps sustained-idle "
                        "replicas down to this floor, folding their "
                        "lifetime counters into the router totals")
    p.add_argument("--max-replicas", type=int, default=0, metavar="N",
                   help="api mode, with a replica tier: ceiling of the "
                        "autoscaling window (default: the boot count — "
                        "autoscaling off). Under sustained queue growth "
                        "the controller spawns replicas up to N, hard-"
                        "capped by the HBM ledger's slots_addable "
                        "headroom; fresh replicas warm their caches "
                        "from siblings via --kv-transfer fills before "
                        "taking traffic")
    p.add_argument("--tenant-budgets", default=None,
                   metavar="NAME=W[:TPS],...",
                   help="api mode, with --serve-batch: per-tenant "
                        "weighted-fair queueing + token budgets. Each "
                        "entry names a tenant with fair-share weight W "
                        "and optional sustained tokens/sec budget (e.g. "
                        "'gold=4:2000,free=1:100'). Tenants come from "
                        "the request body `tenant` field or X-Tenant "
                        "header (unknown tenants get weight 1, no "
                        "budget); an over-budget tenant is served only "
                        "when no in-budget tenant waits, so a hog's "
                        "overage can never move a victim's p99")
    p.add_argument("--admin-token", default=None, metavar="TOKEN",
                   help="api mode: bearer token accepted on /admin/* as "
                        "an alternative to the loopback-only default "
                        "(constant-time compare) — required for "
                        "operating a remote-replica tier from off-box")
    # flight recorder (runtime/trace.py, docs/observability.md): request
    # spans + step timeline into a bounded ring, exported by
    # GET /metrics (Prometheus) and GET /admin/trace (JSONL)
    p.add_argument("--trace", action="store_true",
                   help="api mode: enable the flight recorder — per-"
                        "request lifecycle spans and the per-iteration "
                        "step timeline (each step record with its number, "
                        "start and ms per scheduler span: sched.admit, "
                        "sched.dispatch.*, sched.wait, sched.sample_emit, "
                        "sched.publish), in a fixed-capacity ring served "
                        "by /admin/trace and the dllama_step_ms /metrics "
                        "family. Host-side; disabled it is a no-op "
                        "(docs/observability.md quantifies the well-"
                        "under-2%% enabled overhead)")
    p.add_argument("--trace-buffer", type=int, default=None, metavar="N",
                   help="ring capacity in events (default 8192; oldest "
                        "events fall off first)")
    p.add_argument("--trace-dir", default=None, metavar="DIR",
                   help="also persist events as rotating JSONL files "
                        "under DIR (16 MB x 8 files per process; replica "
                        "workers write worker-rK/ subdirs)")
    p.add_argument("--trace-sample", type=float, default=None, metavar="R",
                   help="fraction of request SPANS persisted to "
                        "--trace-dir (deterministic per trace id; the "
                        "in-memory ring and /metrics always see "
                        "everything). Default 1.0")
    p.add_argument("--trace-decode-every", type=int, default=None,
                   metavar="N",
                   help="decode progress event cadence in tokens "
                        "(default 8) — bounds how much ring one long "
                        "stream can occupy")
    # device-tier observability (runtime/profiler.py,
    # docs/observability.md "Device tier"): compile ledger + recompile
    # sentinel, HBM ledger, on-demand capture, sampled attribution
    p.add_argument("--freeze-compiles", action="store_true",
                   help="api mode (needs --serve-batch): after warmup "
                        "compiles the serving set, any NEW compile key "
                        "is refused with a structured error instead of "
                        "compiled — the runtime twin of dlgrind's "
                        "static fingerprint gate. Covers everything "
                        "minted post-warmup, including the batch "
                        "endpoint's whole-batch executables (warm those "
                        "shapes first or leave the freeze off; "
                        "docs/operations.md 'Recompile storms')")
    p.add_argument("--profile-dir", default=None, metavar="DIR",
                   help="where POST /admin/profile captures land (a "
                        "jax.profiler trace with the Python tracer off and "
                        "the scheduler's spans as TraceAnnotations, with "
                        "or without --trace) "
                        "(default: a fresh temp dir per capture; replica "
                        "workers write worker-rK/ subdirs)")
    # multi-host cluster flags (the reference's root + worker nodes,
    # ref: src/app.cpp:51-74; here one jax.distributed SPMD cluster)
    p.add_argument("--nnodes", type=int, default=1,
                   help="number of host processes in the cluster (rank 0 is "
                        "the root; others run `dllama worker`)")
    p.add_argument("--node-rank", type=int, default=0,
                   help="this process's rank (0..nnodes-1)")
    p.add_argument("--coordinator", default=None,
                   help="jax.distributed coordinator address host:port, "
                        "reachable from every node (required with --nnodes)")
    p.add_argument("--push-weights", action="store_true",
                   help="cluster weight distribution: rank 0 streams the "
                        ".m and broadcasts each tensor's bytes, so workers "
                        "need NO local model file (the reference root's "
                        "per-worker TCP weight push, transformer.cpp:562-"
                        "591). Pass on EVERY process; workers may omit "
                        "--model")
    # cluster control-plane resilience flags (parallel/multihost.py,
    # docs/operations.md "Cluster failure modes"). The root's
    # --heartbeat-interval / --worker-timeout are authoritative: workers
    # adopt them from the HELLO ack, so only the root's values matter
    p.add_argument("--connect-timeout", type=float, default=30.0,
                   metavar="SECS",
                   help="cluster formation budget: workers retry the "
                        "root's control port with exponential backoff "
                        "until this deadline, and the root waits this "
                        "long for every worker's versioned HELLO — past "
                        "it, a structured formation error (exit 44), "
                        "never a silent hang")
    p.add_argument("--heartbeat-interval", type=float, default=2.0,
                   metavar="SECS",
                   help="root->worker MSG_PING cadence on the control "
                        "channel (workers answer MSG_PONG; both sides "
                        "time out silent peers)")
    p.add_argument("--worker-timeout", type=float, default=10.0,
                   metavar="SECS",
                   help="peer-loss detection bound: a node silent on the "
                        "control channel this long (dead, wedged, or "
                        "partitioned) is declared lost with a structured "
                        "ClusterPeerLost diagnostic (exit 43) instead of "
                        "hanging a collective forever; must comfortably "
                        "exceed --heartbeat-interval")
    return p


def build_engine(args):
    """model file -> (engine, tokenizer, sampler). Mirrors App::run wiring
    (ref: src/app.cpp:103-132)."""
    import jax.numpy as jnp

    from ..io.model_file import content_fingerprint, read_spec
    from ..models.loader import load_params_streamed
    from ..quants.types import FloatType
    from ..runtime.engine import Engine
    from ..sampler import Sampler
    from ..tokenizer import Tokenizer

    multihost = jax.process_count() > 1
    push = getattr(args, "push_weights", False)
    # root-push mode: only rank 0 needs the .m — workers receive spec +
    # weights over the broadcast protocol (parallel/multihost.py)
    pushed_worker = push and multihost and jax.process_index() > 0
    if (not args.model and not pushed_worker) or not args.tokenizer:
        sys.exit("error: --model and --tokenizer are required "
                 "(--model optional for --push-weights workers)")

    wft = None
    if args.weights_float_type:
        wft = FloatType[args.weights_float_type.upper()]

    if multihost:
        # spec broadcast runs on EVERY multihost startup (push or not) so
        # the collective sequence is flag-independent — a --push-weights
        # mismatch then reaches check_config as a symmetric error instead
        # of deadlocking in mismatched collectives (bcast_spec docstring)
        from ..parallel.multihost import bcast_spec
        if jax.process_index() == 0:
            spec = read_spec(args.model, weights_float_type=wft)
            model_fp = content_fingerprint(args.model)
            bcast_spec(spec, model_fp, push=push)
        else:
            rspec, rfp, _ = bcast_spec(None)
            if pushed_worker:
                spec, model_fp = rspec, rfp
            else:
                spec = read_spec(args.model, weights_float_type=wft)
                model_fp = content_fingerprint(args.model)
    else:
        spec = read_spec(args.model, weights_float_type=wft)
        # sampled content hash of the weights file — folded into the
        # KV-session fingerprint always, and into the cluster config check
        # when multihost
        model_fp = content_fingerprint(args.model)
    print(f"⏩ {args.model or '<pushed>'}: arch={spec.arch.name} "
          f"dim={spec.dim} layers={spec.n_layers} "
          f"heads={spec.n_heads}/{spec.n_kv_heads} seq={spec.seq_len}")

    mode = "q40" if spec.weights_float_type == FloatType.Q40 else "dense"
    cdt = jnp.bfloat16 if args.compute_dtype == "bf16" else jnp.float32
    kdt = {"bf16": jnp.bfloat16, "f32": jnp.float32,
           "f8": jnp.float8_e4m3fn}[args.cache_dtype]
    if multihost:
        # every process must agree on the mesh/dtype flags (the reference
        # memcpys its spec struct over the socket and hopes — we verify).
        # The MODEL and TOKENIZER files are fingerprinted too: hosts loading
        # different .m/.t files would desync eos step counts and hang the
        # cluster in a mismatched collective instead of erroring (ADVICE r2).
        # The model hash samples file size + start/middle/end chunks, so
        # same-architecture different-weight builds (fine-tunes, requants)
        # are caught without reading a 40 GB file
        import dataclasses
        import zlib

        from ..parallel.multihost import check_config
        spec_fp = zlib.crc32(repr(dataclasses.astuple(spec)).encode())
        with open(args.tokenizer, "rb") as f:
            tok_fp = zlib.crc32(f.read())
        check_config([spec_fp, model_fp, tok_fp,
                      args.tp, args.dp, args.sp, args.ep, args.pp,
                      int(args.buffer_float_type == "q80"),
                      int(args.compute_dtype == "bf16"),
                      ["bf16", "f32", "f8"].index(args.cache_dtype),
                      # a seq-len or kernel-path mismatch would compile
                      # different step programs / loop bounds per process ->
                      # a cross-host collective hang, not an error
                      args.max_seq_len if args.max_seq_len is not None else -1,
                      2 if args.pallas is None else int(args.pallas),
                      # API-mode sampling uses each process's OWN sampler
                      # flags (MSG_RUN headers carry them, MSG_API doesn't)
                      # — a mismatch would silently diverge token streams
                      int(np.float32(args.temperature).view(np.int32)),
                      int(np.float32(args.topp).view(np.int32)),
                      # API-mode speculation likewise uses each process's
                      # own --lookup-decode: a mismatch would diverge the
                      # verify-forward widths and hang a collective
                      args.lookup_decode,
                      # weight-push changes the LOAD phase's broadcast
                      # sequence; reachable because bcast_spec above runs
                      # flag-independently
                      int(push)])

    mesh = None
    if (args.tp > 1 or args.dp > 1 or args.sp > 1 or args.ep > 1
            or args.pp > 1 or multihost):
        from ..parallel.mesh import make_mesh
        # multihost with all-default axes: tp spans every device cluster-wide
        tp = None if (multihost and args.tp == 1) else args.tp
        mesh = make_mesh(tp=tp, dp=args.dp, sp=args.sp, ep=args.ep,
                         pp=args.pp)

    q80 = args.buffer_float_type == "q80"
    if q80 and args.pp > 1:
        # pipeline stages reduce with GSPMD-exact collectives; the quantized
        # exchange cannot nest inside the manual-pp region
        print("⏩ --pp uses exact collectives; ignoring --buffer-float-type q80")
        q80 = False

    # streamed sharded load: one tensor resident at a time, each shard
    # placed straight onto its device (ref weight push: transformer.cpp:562-621)
    t0 = time.perf_counter()
    tensor_src = None
    if getattr(args, "push_weights", False) and multihost:
        # rank 0 streams its file into the broadcast; workers consume the
        # identical tensor stream with no local .m
        from ..parallel.multihost import bcast_model_tensors
        tensor_src = bcast_model_tensors(spec, args.model or None)
    # ONE resolution of the --shard-vocab tri-state, shared by the loader
    # and the engine: they MUST agree — the loader places tok_emb/wcls in
    # the layout the engine keeps, so a drift here would silently
    # reintroduce the load-time reshard (a transient replicated 524
    # MB/chip table at 70B widths)
    shard_vocab = {"auto": None, "on": True, "off": False}[
        getattr(args, "shard_vocab", "auto")]
    params, lstats = load_params_streamed(
        spec, args.model, mesh, mode=mode, dtype=cdt, q80_collectives=q80,
        tensors=tensor_src, shard_vocab=shard_vocab)
    print(f"⏩ loaded {lstats.total_bytes / 1e9:.2f} GB in "
          f"{time.perf_counter()-t0:.1f}s (peak host "
          f"{lstats.peak_host_bytes / 1e6:.0f} MB)")
    engine = Engine(
        spec, params, mesh,
        batch=max(args.dp, 1),
        max_seq_len=args.max_seq_len,
        compute_dtype=cdt, cache_dtype=kdt,
        activation_q80=(q80 and mode == "q40"),
        q80_collectives=q80,
        use_pallas=args.pallas,  # None -> engine default (on for TPU)
        # folded into the KV-session fingerprint: a session saved from a
        # same-shape different-weight model must be refused (ADVICE r3)
        model_fingerprint=model_fp,
        # vocab sharding: None (auto) enables whenever the mesh's tp
        # axes divide the vocab; resolved ONCE above, shared with the
        # loader placement
        shard_vocab=shard_vocab,
    )

    tokenizer = Tokenizer.from_file(args.tokenizer)
    seed = args.seed if args.seed is not None else int(time.time())
    if multihost:
        # one sampler stream cluster-wide: every process reproduces the
        # root's sampling decisions locally (no per-token control traffic,
        # unlike the reference's per-step pos broadcast, tasks.cpp:165-182)
        from ..parallel.multihost import broadcast_seed
        seed = broadcast_seed(seed)
    sampler = Sampler(tokenizer.vocab_size, args.temperature, args.topp, seed)
    return engine, tokenizer, sampler


class FrontDoorTemplate:
    """The slice of the Engine surface a PROCESS-TIER api front end
    actually reads (shape validation at startup; the handlers use the
    router's remote shape shim per request). Built by
    ``build_front_template`` WITHOUT loading weights: the workers own the
    model — loading it in the parent too would hold N+1 copies locally,
    and force a pure --replica-hosts router box to hold one at all."""

    def __init__(self, spec, max_seq_len=None):
        self.spec = spec
        self.seq_len = min(max_seq_len or spec.seq_len, spec.seq_len)


def build_front_template(args):
    """model file -> (shape template, tokenizer, sampler) for the
    process-replica front door (api --replica-procs/--replica-hosts):
    reads only the spec header of the .m — no weight load, no Engine, no
    KV cache. Tokenizing, routing, retry policy, and shape validation
    are everything the parent does; the worker processes own the model
    (runtime/replica_worker.build_supervisor_factory)."""
    from ..io.model_file import read_spec
    from ..quants.types import FloatType
    from ..sampler import Sampler
    from ..tokenizer import Tokenizer

    if not args.model or not args.tokenizer:
        sys.exit("error: --model and --tokenizer are required")
    wft = (FloatType[args.weights_float_type.upper()]
           if args.weights_float_type else None)
    spec = read_spec(args.model, weights_float_type=wft)
    print(f"⏩ {args.model}: arch={spec.arch.name} dim={spec.dim} "
          f"layers={spec.n_layers} heads={spec.n_heads}/{spec.n_kv_heads} "
          f"seq={spec.seq_len} (front door: spec only, workers own the "
          "weights)")
    tokenizer = Tokenizer.from_file(args.tokenizer)
    seed = args.seed if args.seed is not None else int(time.time())
    sampler = Sampler(tokenizer.vocab_size, args.temperature, args.topp,
                      seed)
    return FrontDoorTemplate(spec, args.max_seq_len), tokenizer, sampler


def check_session_flags(args) -> None:
    """--session needs a host-fetchable, stage-flat KV cache:
    save_session fetches it to the host — impossible for a multi-process
    mesh (non-addressable shards) and unsupported for stage-stacked pp
    caches. Shared by the chat CLI and the API server so the constraint
    cannot diverge; fails before any engine work."""
    if getattr(args, "session", None) and (args.nnodes > 1 or args.pp > 1):
        sys.exit("error: --session does not compose with --nnodes or --pp")


def _steps(args, engine) -> int:
    s = args.steps if args.steps > 0 else engine.seq_len
    return min(s, engine.seq_len)  # clamp like ref: app.cpp:117-119


def _safe_print(piece: str) -> None:
    """Print only printable pieces (ref: safePrintf, src/tokenizer.cpp:18-36)."""
    out = "".join(c for c in piece if c.isprintable() or c in "\n\t ")
    print(out, end="", flush=True)


def _announce_run(tokens: list[int], max_tokens: int, reset: bool = False,
                  sampler=None, lookup: int = 0) -> None:
    """Root side of the multi-host protocol: tell worker processes to enter
    the same generate() call (no-op single-process). lookup > 0 replays a
    speculative run — deterministic draft mining keeps the verify shapes
    in lock-step. With the flight recorder on, the run rides one minted
    trace id (header slot) so the workers' span events (shipped back via
    MSG_TRACE) land on the root's timeline under it."""
    if jax.process_count() > 1:
        from ..parallel import multihost as mh
        from ..runtime.trace import TRACER

        tid = 0
        if TRACER.enabled:
            tid = TRACER.new_id()
            link = mh.get_link()
            if link is not None:
                link.trace_tid = tid
            TRACER.event("cluster_tick", tid, phase="run", role="root",
                         rank=0, n_prompt=len(tokens))
        mh.set_phase("run")
        mh.send_run(tokens, max_tokens,
                    sampler.rng_state if sampler else 0,
                    sampler.temperature if sampler else 0.0,
                    sampler.topp if sampler else 0.0, reset,
                    lookup=lookup, trace_tid=tid)


import contextlib


@contextlib.contextmanager
def _maybe_profile(args, trace_dir=None):
    """jax.profiler trace of the generation when --profile DIR is given (or
    an explicit dir — the benchmark mode's per-step T capture)."""
    target = trace_dir or args.profile
    if not target:
        yield
        return
    import jax.profiler
    with jax.profiler.trace(target):
        yield
    if args.profile:
        print(f"📈 profiler trace written to {args.profile}")


def _stream_pieces(tokenizer, prev_token: int, toks: list[int]) -> None:
    """Print a token list as decoded text (single place for the piece loop)."""
    for tok in toks:
        _safe_print(tokenizer.decode_piece(prev_token, tok).decode(
            "utf-8", errors="replace"))
        prev_token = tok
    print()


def cmd_generate(args, benchmark: bool) -> None:
    if args.device_sampling and args.nnodes > 1:
        sys.exit("error: --device-sampling does not compose with "
                 "--nnodes (the worker protocol drives generate())")
    if args.lookup_decode:
        if args.device_sampling:
            sys.exit("error: --lookup-decode is host-loop decoding; it "
                     "does not compose with --device-sampling")
        if args.dp > 1 and args.temperature != 0:
            sys.exit("error: --lookup-decode with --dp is greedy-only "
                     "(Engine.generate_batch_lookup); set --temperature 0")
        if args.dp > 1 and args.nnodes > 1:
            # the worker protocol's lookup replay is single-row
            # (cmd_worker -> generate_lookup); a batched root would run a
            # different forward program and hang the cluster
            sys.exit("error: --lookup-decode with --dp does not compose "
                     "with --nnodes")
    engine, tokenizer, sampler = build_engine(args)
    prompt = args.prompt or "Hello"
    tokens = tokenizer.encode(prompt)
    print(f"💡 prompt tokens: {len(tokens)}")

    if engine.batch > 1:
        # dp throughput mode: the batch rows generate independently (here the
        # same prompt replicated); row 0 streams to stdout
        t0 = time.perf_counter()
        if args.lookup_decode:
            # batched speculation (round 5): per-row drafts, one verify
            # forward per step, exact per-row greedy parity
            _announce_run(tokens, _steps(args, engine), sampler=sampler,
                          lookup=args.lookup_decode)
            outs = engine.generate_batch_lookup(
                [tokens] * engine.batch, _steps(args, engine),
                eos_id=tokenizer.stop_token_ids(),
                draft_len=args.lookup_decode,
                vocab_size=tokenizer.vocab_size)
        elif args.device_sampling:
            with _maybe_profile(args):
                outs = engine.generate_batch_device(
                    [tokens] * engine.batch, _steps(args, engine),
                    temperature=args.temperature, topp=args.topp,
                    seed=sampler.rng_state,
                    eos_id=tokenizer.stop_token_ids(),
                    vocab_size=tokenizer.vocab_size)
        else:
            _announce_run(tokens, _steps(args, engine), sampler=sampler)
            outs = engine.generate_batch([tokens] * engine.batch,
                                         _steps(args, engine), sampler,
                                         eos_id=tokenizer.stop_token_ids())
        dt = time.perf_counter() - t0
        _stream_pieces(tokenizer, tokens[-1], outs[0])
        if benchmark:
            n = sum(len(o) for o in outs)
            print(f"Generated tokens:    {n} ({engine.batch} sequences)")
            print(f"Avg tokens / second: {n / max(dt, 1e-9):.2f}")
        return

    if args.device_sampling:
        t0 = time.perf_counter()
        with _maybe_profile(args):
            out = engine.generate_device(
                tokens, _steps(args, engine),
                temperature=args.temperature, topp=args.topp,
                seed=sampler.rng_state,
                eos_id=tokenizer.stop_token_ids(),
                vocab_size=tokenizer.vocab_size)
        dt = time.perf_counter() - t0
        _stream_pieces(tokenizer, tokens[-1], out)
        if benchmark:
            # honest accounting: this first call's wall time includes the
            # loop's jit compile — don't fake a per-token rate
            print(f"Generated tokens:    {len(out)} (on-device loop, "
                  f"{engine.last_device_steps} device steps)")
            print(f"Wall time:           {dt:.2f} s "
                  "(includes one-time loop compile)")
        return

    prev = [tokens[-1]]

    def on_token(tok: int) -> None:
        _safe_print(tokenizer.decode_piece(prev[0], tok).decode("utf-8", errors="replace"))
        prev[0] = tok

    if args.draft:
        # real-draft speculation (runtime/draft.py): greedy is
        # bit-identical to the plain stream, sampled is
        # distribution-exact via general rejection resampling
        from ..runtime.draft import build_draft
        try:
            draft = build_draft(engine, args.draft)
        except ValueError as e:
            sys.exit(f"error: {e}")
        dl = args.draft_len or 7
        t0 = time.perf_counter()
        with _maybe_profile(args):
            if args.temperature > 0:
                res = engine.generate_draft_sampled(
                    tokens, _steps(args, engine), draft=draft,
                    temperature=float(np.float32(args.temperature)),
                    topp=float(np.float32(args.topp)),
                    seed=sampler.rng_state,
                    eos_id=tokenizer.stop_token_ids(), draft_len=dl,
                    on_token=on_token, vocab_size=tokenizer.vocab_size)
            else:
                res = engine.generate_draft(
                    tokens, _steps(args, engine), draft=draft,
                    eos_id=tokenizer.stop_token_ids(), draft_len=dl,
                    on_token=on_token, vocab_size=tokenizer.vocab_size)
        dt = time.perf_counter() - t0
        print()
        if benchmark:
            fwd, n = engine.last_accept_stats
            print(f"Generated tokens:    {n} in {fwd} forwards "
                  f"({n / max(fwd, 1):.2f} tokens/forward, "
                  f"draft {args.draft})")
            print(f"Wall time:           {dt:.2f} s (includes draft + "
                  "verify compiles)")
        return

    if args.lookup_decode:
        _announce_run(tokens, _steps(args, engine), sampler=sampler,
                      lookup=args.lookup_decode)
        t0 = time.perf_counter()
        with _maybe_profile(args):
            if args.temperature > 0:
                # sampled speculation: distribution-exact via rejection
                # resampling (Engine.generate_lookup_sampled) — NOT
                # xorshift-stream-parity with the plain sampled loop.
                # temperature/topp go through the same float32 roundtrip
                # the cluster header applies: a worker seeing
                # 0.69999998807 where the root used 0.7 could flip one
                # accept decision, diverge the verify widths, and hang a
                # cross-host collective
                res = engine.generate_lookup_sampled(
                    tokens, _steps(args, engine),
                    temperature=float(np.float32(args.temperature)),
                    topp=float(np.float32(args.topp)),
                    seed=sampler.rng_state,
                    eos_id=tokenizer.stop_token_ids(),
                    draft_len=args.lookup_decode, on_token=on_token,
                    vocab_size=tokenizer.vocab_size)
            else:
                res = engine.generate_lookup(
                    tokens, _steps(args, engine),
                    eos_id=tokenizer.stop_token_ids(),
                    draft_len=args.lookup_decode, on_token=on_token,
                    vocab_size=tokenizer.vocab_size)
        dt = time.perf_counter() - t0
        print()
        if benchmark:
            fwd, n = engine.last_accept_stats
            print(f"Generated tokens:    {n} in {fwd} forwards "
                  f"({n / max(fwd, 1):.2f} tokens/forward)")
            print(f"Wall time:           {dt:.2f} s (includes compiles for "
                  "each distinct verify length)")
        return

    _announce_run(tokens, _steps(args, engine), sampler=sampler)
    # benchmark mode on a single-process multi-device mesh: capture a trace
    # so T is the MEASURED per-step collective time from the device
    # timeline (netstats.per_step_op_ms), not a repeated microbench
    # constant — the reference's T column is genuinely per-token
    # (ref: src/apps/dllama/dllama.cpp:74-79)
    trace_dir = args.profile
    auto_trace = (benchmark and trace_dir is None and engine.mesh is not None
                  and engine.mesh.size > 1 and jax.process_count() == 1)
    if auto_trace:
        import tempfile
        trace_dir = tempfile.mkdtemp(prefix="dllama-trace-")
    try:
        with _maybe_profile(args, trace_dir):
            res = engine.generate(tokens, _steps(args, engine), sampler,
                                  eos_id=tokenizer.stop_token_ids(),
                                  on_token=on_token)
        print()
        if benchmark:
            _print_benchmark(args, engine, res, trace_dir=trace_dir)
    finally:
        if auto_trace:  # parsed above; traces are tens of MB per run
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)


def _print_benchmark(args, engine, res, trace_dir=None) -> None:
    """Per-token G/I/T/S lines + averages (ref: dllama.cpp:47-48,74-91);
    S = modeled per-device collective kB, T = measured per-step collective
    time from the trace (falling back to the all-reduce microbench scaled
    to the per-layer reduce count — netstats.py)."""
    wire = engine.wire_estimate()
    # the first stats step is the whole prefill: its fallback T follows the
    # schedule prefill actually ran (GPipe ppermute hops on pp meshes —
    # engine.measure_prefill_transfer_ms), not the per-token decode model
    n_prompt = max(engine.pos - (len(res.tokens) - 1), 1)
    if jax.process_count() > 1:
        # workers join the IDENTICAL microbench sequence: n_prompt rides
        # the header so their measure_prefill_transfer_ms runs the same
        # per-segment collectives (incl. pp ppermute) as ours — the root
        # measuring a collective the workers skip deadlocks the mesh
        # (ADVICE r5 high; regression: tests/test_multihost.py
        # test_two_process_benchmark_completes)
        from ..parallel import multihost as mh
        mh.set_phase("bench")
        mh.send_xfer_bench(n_prompt)
    t_ms = engine.measure_transfer_ms()
    t_pre_ms = engine.measure_prefill_transfer_ms(n_prompt)
    t_steps: list[float] = []
    if trace_dir:
        from ..runtime.netstats import per_step_op_ms

        # the engine names its jitted wrappers by role (decode_step /
        # prefill_chunk_N / prefill_seg — engine._compiled_step), so decode
        # executions are matched exactly instead of tail-aligning every
        # module named 'run' (ADVICE r3: extra executions in the window
        # shifted T onto the wrong steps). A count mismatch means the
        # window caught unrelated executions — fall back to the microbench.
        dec_t = per_step_op_ms(trace_dir, module_hint="decode_step")
        pre_t = per_step_op_ms(trace_dir, module_hint="prefill")
        n_dec = len(res.stats.steps) - 1
        if len(dec_t) == n_dec and (dec_t or pre_t):
            t_steps = [sum(pre_t)] + dec_t  # n_dec == 0: prefill-only run
        elif dec_t or pre_t:
            print(f"⏩ trace module count mismatch (decode {len(dec_t)} vs "
                  f"{n_dec} steps); using the microbench T estimate")
    for i, s in enumerate(res.stats.steps):
        tv = (t_steps[i] if i < len(t_steps)
              else (t_pre_ms if i == 0 else t_ms))
        print(f"🔶 G {s.generation_ms:7.2f} ms I {s.device_ms:7.2f} ms "
              f"T {tv:6.2f} ms H {s.host_ms:5.2f} ms "
              f"S {wire.sent_kb_per_token:7.1f} kB")
    avg = res.stats.averages()
    n = len(res.tokens)
    print(f"Generated tokens:    {n}")
    print(f"Avg tokens / second: {1000.0 / max(avg.generation_ms, 1e-9):.2f}")
    print(f"Avg generation time: {avg.generation_ms:.2f} ms")
    print(f"Avg inference time:  {avg.device_ms:.2f} ms")
    if len(t_steps) > 1:
        t_avg = sum(t_steps[1:]) / len(t_steps[1:])
        print(f"Avg transfer:        {t_avg:.2f} ms/token measured "
              f"(trace; microbench estimate {t_ms:.2f} ms), "
              f"{wire.sent_kb_per_token:.1f} kB/token/device")
    else:
        print(f"Avg transfer (est):  {t_ms:.2f} ms, "
              f"{wire.sent_kb_per_token:.1f} kB/token/device")
    for kname, kb in wire.breakdown.items():
        print(f"  {kname}: {kb:.1f} kB")
    print(f"Avg sampling time:   {avg.host_ms:.2f} ms")


def cmd_chat(args) -> None:
    """Interactive chat with the Llama-2 template (ref: dllama.cpp:133-178)."""
    import os

    if args.lookup_decode and args.nnodes > 1:
        # same loud guard as generate mode — a silently ignored flag is
        # worse than an error
        sys.exit("error: --lookup-decode does not compose with --nnodes")
    check_session_flags(args)
    engine, tokenizer, sampler = build_engine(args)
    chat_draft = None
    if args.draft:
        from ..runtime.draft import build_draft
        try:
            chat_draft = build_draft(engine, args.draft)
        except ValueError as e:
            sys.exit(f"error: {e}")
    convo: list[int] = []  # whole-conversation tokens: the draft miner's
    # n-gram source (chat history is full of quotable n-grams) AND the
    # real draft's catch-up stream (token at position i = convo[i])
    resumed = False
    if args.session and os.path.exists(args.session):
        convo = engine.load_session(args.session)
        resumed = True
        print(f"💾 resumed session from {args.session} "
              f"({engine.pos} cached positions)")
    system = args.system_prompt
    if system is None and not resumed:
        try:
            system = input("💻 System prompt (optional): ")
        except EOFError:
            system = ""
    first = not resumed
    while True:
        try:
            user = input("\n👱 User\n> ")
        except EOFError:
            break
        if not user:
            continue
        if first and system:
            text = f"[INST] <<SYS>>\n{system}\n<</SYS>>\n\n{user} [/INST]"
        else:
            text = f"[INST] {user} [/INST]"
        first = False
        tokens = tokenizer.encode(text, add_bos=True)
        print("\n🤖 Assistant")
        prev = [tokens[-1]]
        stops = tokenizer.stop_token_ids()

        def on_token(tok: int) -> None:
            if tok not in stops:
                _safe_print(tokenizer.decode_piece(prev[0], tok).decode("utf-8", errors="replace"))
            prev[0] = tok

        # the prompt itself must also fit before any generation can start
        remaining = engine.seq_len - engine.pos - len(tokens)
        if remaining <= 1:
            print("(context window full)")
            break
        budget = min(_steps(args, engine), remaining)
        convo.extend(tokens)
        if chat_draft is not None:
            # real-draft turns: the draft's own forward proposes — the
            # chat history is its catch-up stream, not an n-gram mine
            dl = args.draft_len or 7
            if args.temperature > 0:
                res = engine.generate_draft_sampled(
                    tokens, budget, draft=chat_draft,
                    temperature=args.temperature, topp=args.topp,
                    seed=sampler.rng_state, eos_id=stops, draft_len=dl,
                    on_token=on_token, vocab_size=tokenizer.vocab_size,
                    history=convo)
                sampler.set_seed(sampler.rng_state + len(res.tokens) + 1)
            else:
                res = engine.generate_draft(
                    tokens, budget, draft=chat_draft, eos_id=stops,
                    draft_len=dl, on_token=on_token,
                    vocab_size=tokenizer.vocab_size, history=convo)
            convo.extend(res.tokens)
        elif args.lookup_decode:
            # chat turns speculate, mining drafts from the WHOLE
            # conversation so far — prior turns are the richest n-gram
            # source. Greedy turns are token-stream-exact; sampled turns
            # are distribution-exact (rejection resampling)
            if args.temperature > 0:
                res = engine.generate_lookup_sampled(
                    tokens, budget, temperature=args.temperature,
                    topp=args.topp, seed=sampler.rng_state, eos_id=stops,
                    draft_len=args.lookup_decode, on_token=on_token,
                    vocab_size=tokenizer.vocab_size, history=convo)
                # advance the shared seed so the next turn draws fresh
                sampler.set_seed(sampler.rng_state + len(res.tokens) + 1)
            else:
                res = engine.generate_lookup(tokens, budget, eos_id=stops,
                                             draft_len=args.lookup_decode,
                                             on_token=on_token,
                                             vocab_size=tokenizer.vocab_size,
                                             history=convo)
            convo.extend(res.tokens)
        else:
            _announce_run(tokens, budget, sampler=sampler)
            res = engine.generate(tokens, budget, sampler,
                                  eos_id=stops, on_token=on_token)
            convo.extend(res.tokens)
        print()
        if args.session:
            # token history rides along so a resumed process keeps mining
            # speculative drafts from pre-restart turns
            engine.save_session(args.session, tokens=convo)


def cmd_worker(args) -> None:
    """Worker process: hold this host's weight shards, lock-step the root's
    runs (ref: src/apps/dllama/dllama.cpp:180-193, Worker::work
    tasks.cpp:230-256 — the TaskLoop pass per `pos` trigger becomes a full
    generate() per broadcast run; per-token sync is unnecessary because the
    sampler stream is deterministic and logits are replicated)."""
    from ..parallel import multihost as mh
    from ..runtime.trace import TRACER

    if getattr(args, "trace", False):
        # worker-side flight recorder (dlwire): ring only — span events
        # ship ROOT-ward over MSG_TRACE after each run, so the root's
        # /admin/trace (or trace sink) is the one merged timeline; a
        # local sink would just split the story across hosts
        TRACER.configure(
            capacity=getattr(args, "trace_buffer", None) or 8192,
            enabled=True)
    engine, tokenizer, sampler = build_engine(args)
    stops = tokenizer.stop_token_ids()
    api_state = None
    print(f"⏳ worker rank {jax.process_index()} of {jax.process_count()} "
          "ready")
    while True:
        mh.set_phase("idle")
        # supervised wait: a root that dies or wedges surfaces as a
        # structured ClusterPeerLost within --worker-timeout (the link's
        # receiver thread also hard-exits via the installed handler when
        # this thread is itself wedged in a collective) — never the
        # reference's unbounded socket read
        msg = mh.recv_msg()
        if msg.kind == mh.MSG_SHUTDOWN:
            print("🔌 root shut down — exiting")
            return
        if msg.kind == mh.MSG_RUN:
            mh.set_phase("run")
            tid = msg.trace_tid
            t_run = time.perf_counter()
            if TRACER.enabled and tid:
                # adopt the root's id: advance the local mint counter
                # past it so this worker's own scheduler-door mints
                # (MSG_API replays) can never collide with a run tid
                TRACER.reserve(tid)
                link = mh.get_link()
                if link is not None:
                    link.trace_tid = tid  # a mid-run casualty links here
                TRACER.event("cluster_tick", tid, phase="run",
                             role="worker", rank=jax.process_index(),
                             n_prompt=len(msg.tokens or ()))
            if msg.reset:
                engine.reset()
            if msg.lookup:
                # speculative replay: drafts mine the replicated token
                # stream, so every process computes the same verify widths
                # (send_run's lock-step contract); the sampled mode's
                # rejection draws come from the header seed — identical
                # numpy streams on every process
                if msg.temperature > 0:
                    engine.generate_lookup_sampled(
                        msg.tokens, msg.max_tokens,
                        temperature=msg.temperature, topp=msg.topp,
                        seed=msg.seed, eos_id=stops,
                        draft_len=msg.lookup,
                        vocab_size=tokenizer.vocab_size)
                else:
                    engine.generate_lookup(msg.tokens, msg.max_tokens,
                                           eos_id=stops,
                                           draft_len=msg.lookup,
                                           vocab_size=tokenizer.vocab_size)
            else:
                # sample with the ROOT's params and rng state from the
                # header — immune to any sampler-flag mismatch between
                # the processes
                from ..sampler import Sampler
                run_sampler = Sampler(tokenizer.vocab_size, msg.temperature,
                                      msg.topp, msg.seed)
                if engine.batch > 1:
                    engine.generate_batch([msg.tokens] * engine.batch,
                                          msg.max_tokens, run_sampler,
                                          eos_id=stops)
                else:
                    engine.generate(msg.tokens, msg.max_tokens, run_sampler,
                                    eos_id=stops)
            if TRACER.enabled and tid:
                TRACER.event("cluster_tick", tid, phase="run_done",
                             role="worker", rank=jax.process_index(),
                             ms=round((time.perf_counter() - t_run) * 1e3,
                                      3))
                # one ship per run (tids are per-run unique — no delta
                # bookkeeping needed): best-effort, the root's casualty
                # path covers a worker that dies before shipping
                lk = mh.get_link()
                if lk is not None and hasattr(lk, "ship_trace"):
                    lk.ship_trace(TRACER.export_span(tid))
        elif msg.kind == mh.MSG_API:
            mh.set_phase("api")
            # replay the root's API request end-to-end from the raw body —
            # prompt build, sampling, stop scan are all deterministic
            import json

            from .api_server import ApiState, PromptTooLong, _completion_chunks
            if api_state is None:
                api_state = ApiState(engine, tokenizer, sampler,
                                     lookup_decode=args.lookup_decode)
            try:
                for _ in _completion_chunks(api_state, json.loads(msg.body)):
                    pass
            except (PromptTooLong, json.JSONDecodeError, KeyError,
                    TypeError) as e:
                # deterministic request errors: the root raised the SAME
                # error at the same point, so state stays in lock-step
                print(f"⚠️  request failed: {type(e).__name__}: {e}")
            except Exception as e:  # noqa: BLE001 — worker-LOCAL failure
                # (OOM, I/O) the root never hit: engine/session state has
                # diverged from the root's. Resync to a known state — fresh
                # cache, empty session — so subsequent requests line their
                # collectives up again (the sampler state was restored by
                # _completion_chunks' finally) (ADVICE r2)
                print(f"⚠️  request failed locally ({type(e).__name__}: {e})"
                      " — resyncing engine state")
                api_state.cached_tokens = []
                engine.reset()
        elif msg.kind == mh.MSG_XFER_BENCH:
            # the EXACT sequence the root runs in _print_benchmark —
            # decode microbench THEN the prefill-schedule microbench for
            # the header's n_prompt (ADVICE r5 high: the old handler
            # stopped after measure_transfer_ms, so the root's prefill
            # collectives had no worker counterpart and --benchmark hung
            # the cluster)
            mh.set_phase("bench")
            engine.measure_transfer_ms()
            engine.measure_prefill_transfer_ms(max(msg.max_tokens, 1))
            mh.set_phase("idle")


def _refuse_for_state(args) -> None:
    """A model that keeps a recurrent state a slot, asked to run with
    something that holds rows of a cache only: exit at start-up with the
    one message (models/spec.STATE_REFUSALS), from the file's header,
    before anything is loaded."""
    import os

    model = getattr(args, "model", None)
    if not model or not os.path.exists(model):
        return          # the loader says what is wrong with the path
    from ..io.model_file import read_spec

    try:
        spec = read_spec(model)
    except (ValueError, KeyError, OSError):
        return          # not a header this reader knows: the loader's error
    asked = {
        "prefix_cache": getattr(args, "prefix_cache", False),
        "kv_transfer": getattr(args, "kv_transfer", False),
        "speculation": args.draft or args.lookup_decode,
        "parallel": max(args.tp, args.pp, args.sp, args.ep, args.nnodes) > 1,
        "session": args.session,
    }
    for what, on in asked.items():
        why = on and spec.refusal(what)
        if why:
            sys.exit("error: " + why)


def main(argv: list[str] | None = None) -> None:
    args = build_argparser().parse_args(argv)
    if args.workers:
        sys.exit("error: --workers is not applicable on TPU — the reference's "
                 "TCP root/worker star is one SPMD program here; use --tp N "
                 "for one host's devices, or --nnodes/--coordinator + "
                 "`dllama worker` processes for a multi-host cluster")
    # pp contract holes closed at PARSE time, before any engine or cluster
    # work: a flag combination that cannot work must not cost a model load
    # (or, worse, be silently ignored for a whole run)
    if args.draft_len is not None and not args.draft:
        sys.exit("error: --draft-len has no effect without --draft "
                 "(self:<depth> or model:<path>)")
    if args.draft_len is not None and args.draft_len < 1:
        sys.exit("error: --draft-len must be >= 1")
    if args.draft:
        if args.lookup_decode:
            sys.exit("error: --draft and --lookup-decode both pick the "
                     "draft source — use one (the real draft pays on "
                     "arbitrary text; prompt lookup only on repetitive)")
        from ..runtime.draft import parse_draft_spec
        try:
            kind, arg = parse_draft_spec(args.draft)
        except ValueError as e:
            sys.exit(f"error: {e}")
        if kind == "model":
            import os as _os
            if not _os.path.exists(arg):
                sys.exit(f"error: --draft model:{arg}: no such file")
        if args.nnodes > 1:
            sys.exit("error: --draft does not compose with --nnodes "
                     "(the worker protocol has no draft replay)")
        if args.pp > 1:
            sys.exit("error: --draft does not compose with --pp "
                     "(stage-stacked layers cannot be depth-sliced)")
        if args.dp > 1:
            sys.exit("error: --draft is single-sequence outside api "
                     "mode and per-slot inside it; it does not compose "
                     "with --dp")
        if args.device_sampling:
            sys.exit("error: --draft is host-loop decoding; it does "
                     "not compose with --device-sampling")
    _refuse_for_state(args)
    if (getattr(args, "shard_vocab", "auto") == "on" and args.tp <= 1
            and args.nnodes <= 1):
        # dead-flag discipline: an explicit "on" needs a tp mesh to split
        # over (auto simply stays off); multihost defaults tp to the
        # cluster width, so only the unambiguous single-node case refuses
        sys.exit("error: --shard-vocab on needs a tensor-parallel mesh "
                 "(--tp > 1) to split the vocab over; 'auto' enables it "
                 "whenever the mesh allows")
    if args.session and args.pp > 1:
        sys.exit("error: --session does not compose with --pp > 1 — "
                 "save_session fetches the KV cache to the host, and "
                 "stage-stacked pipeline caches are not host-fetchable "
                 "(see docs/parallelism.md)")
    if args.session and args.nnodes > 1:
        sys.exit("error: --session does not compose with --nnodes > 1 — "
                 "a multi-process mesh's cache shards are not addressable "
                 "from one host")
    if args.nnodes > 1:
        if not args.coordinator:
            sys.exit("error: --nnodes > 1 requires --coordinator host:port")
        if args.mode == "worker" and args.node_rank == 0:
            sys.exit("error: rank 0 is the root — run a non-worker mode")
        if args.mode != "worker" and args.node_rank != 0:
            sys.exit("error: non-root ranks must run `dllama worker`")
        if args.heartbeat_interval <= 0 or args.worker_timeout <= 0:
            sys.exit("error: --heartbeat-interval and --worker-timeout "
                     "must be positive")
        if args.worker_timeout < 2 * args.heartbeat_interval:
            # healthy workers only produce frames in response to PINGs: a
            # detection bound under ~2 pings declares live nodes dead on
            # one delayed heartbeat — a self-destructing config, refused
            # up front like the other flag-contract holes above
            sys.exit(f"error: --worker-timeout {args.worker_timeout:g} "
                     "must be at least 2x --heartbeat-interval "
                     f"({args.heartbeat_interval:g}) — a node is only "
                     "expected to produce a frame per heartbeat, so a "
                     "tighter bound declares healthy peers lost "
                     "(recommended: 3-5x)")
        from ..parallel import multihost as mh
        try:
            mh.init_multihost(args.coordinator, args.nnodes, args.node_rank,
                              connect_timeout=args.connect_timeout,
                              heartbeat_interval=args.heartbeat_interval,
                              worker_timeout=args.worker_timeout)
        except mh.ClusterProtocolError as e:
            print(f"🔴 cluster formation failed: {e}", flush=True)
            sys.exit(mh.EXIT_FORMATION)
        # peer loss during ANY later phase (weight load, a generate()'s
        # collectives, idle) -> one structured diagnostic line + exit 43,
        # fired from the link's detection thread — the only thread
        # guaranteed not to be wedged inside the very collective the dead
        # peer just orphaned
        mh.install_peer_lost_exit()
        mh.set_phase("load")
    elif args.mode == "worker":
        sys.exit("error: worker mode needs a cluster — pass --nnodes N "
                 "--node-rank r --coordinator host:port (single-host "
                 "multi-device runs need no workers: use --tp N)")
    if not (args.mode == "api" and (args.replica_procs
                                    or args.replica_hosts)):
        # every process that compiles keeps its cache where the launcher
        # (or the fixed in-checkout default) says; the process tiers'
        # front door compiles nothing and must not touch jax config
        from ..utils.compile_cache import ensure_compile_cache

        ensure_compile_cache()
    clean = True
    try:
        if args.mode == "worker":
            cmd_worker(args)
        elif args.mode == "inference":
            cmd_generate(args, benchmark=True)
        elif args.mode == "generate":
            cmd_generate(args, benchmark=False)
        elif args.mode == "chat":
            cmd_chat(args)
        elif args.mode == "api":
            from .api_server import serve
            serve(args)
    except BaseException as e:
        clean = False
        if args.nnodes > 1:
            from ..parallel.multihost import (EXIT_PEER_LOST,
                                              ClusterPeerLost)
            if isinstance(e, ClusterPeerLost):
                # surfaced on the driving thread (a send/recv raced the
                # detection threads' callback): same structured exit
                import json
                print("🔴 cluster: " + json.dumps(e.summary()), flush=True)
                sys.exit(EXIT_PEER_LOST)
        raise
    finally:
        if args.nnodes > 1:
            from ..parallel import multihost as mh
            if args.mode != "worker" and clean:
                # clean exit: the SHUTDOWN frame reaches workers wherever
                # they are (the control channel is out-of-band — no
                # collective pairing needed). After a mid-run crash the
                # heartbeat EOF tells them instead, within
                # --worker-timeout, so no broadcast is required (or safe)
                mh.send_shutdown()
            mh.close_link()


if __name__ == "__main__":
    main()
