"""Real-draft speculative decoding: a pluggable draft forward with its
own small KV cache, verified by the target engine's existing
rejection-resampling machinery.

Prompt-lookup speculation (runtime/speculative.py) only pays on
repetitive text — its drafts come from the context's own n-grams. This
module generalizes the win to ARBITRARY text by drafting from a
real model:

  * **Self-draft (zero extra weights)** — the primary mode: the target
    model's own truncated-depth prefix (the first ``d`` layers plus the
    shared final norm + logits head) runs as the draft. It reuses the
    already-loaded weight buffers (a python-level slice of
    ``params["layers"]`` — no copy, no extra HBM) and keeps its own
    small ``d``-layer KV cache. Late layers of trained transformers
    refine rather than overturn the residual stream, so the prefix's
    argmax agrees with the full model's often enough to pay — and when
    it doesn't, verification makes wrong drafts cost only their (cheap)
    draft forwards, never a wrong token.
  * **Model draft** — a separate TinyLlama-class ``.m``
    (``--draft model:PATH``) rides the SAME machinery: a
    :class:`DraftModel` over its own spec/params with depth = its full
    layer count. The tokenizer (and so the vocab) must match the
    target's.

Cost model: one draft proposal is ONE dispatched program (a
``lax.scan`` of k greedy steps through d layers — k·d/L of a full
forward, and exactly one host round trip however large k is), and one
verify forward confirms accepted-prefix + 1 like the lookup path. Decode
is weight-read-bound on TPU, and where the host round trip per dispatch
dominates instead, both regimes amortize: the draft reads d/L of the weights, the verify
reads them once for up to k+1 tokens.

Correctness never depends on the draft: greedy emission is always the
TARGET's argmax over the verify logits (bit-identical to the plain
greedy stream — drafts only batch the confirmation), and sampled
emission goes through :func:`speculative.accept_or_resample_q`, which is
marginal-exact for any proposal distribution. A stale or unseeded draft
cache can only lower the accept rate.

Every draft executable is minted through the TARGET engine's compile
ledger (``Engine._mint``), so the recompile sentinel and
``--freeze-compiles`` cover the draft path, and the key set is bounded
by construction: one prefill width, one scan shape, one single-token
step. ``Scheduler.warmup()`` compiles all of them before the sentinel
arms. Docs: docs/serving.md "Speculative decoding".
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..models.transformer import KVCache, forward


def parse_draft_spec(s: str) -> tuple[str, str]:
    """``--draft`` argument -> ("self", depth-string) | ("model", path).
    Raises ValueError with a CLI-ready message on anything else (the
    dead-flag discipline: a bad draft spec is a parse-time error, never
    a silently ignored flag or a mid-serve crash)."""
    kind, _, arg = str(s).partition(":")
    if kind == "self":
        if not arg.isdigit() or int(arg) < 1:
            raise ValueError(
                f"--draft self:<depth> needs a positive layer count, got "
                f"{s!r}")
        return "self", arg
    if kind == "model":
        if not arg:
            raise ValueError("--draft model:<path> needs a .m path")
        return "model", arg
    raise ValueError(
        f"--draft {s!r} is not 'self:<depth>' or 'model:<path>'")


# -- traced bodies -----------------------------------------------------------
# Module-level so analysis/entrypoints.py fingerprints the SAME programs
# the engine jits (the slot_seed_prefix discipline): a drifting dtype or
# arity here would retrace per call and show up in dlgrind's DLG204 gate.


def draft_scan_tokens(params, spec, tok0, pos, cache, *, k, n_vocab,
                      fwd_kwargs):
    """k greedy autoregressive draft steps in ONE program: feed tok0 at
    per-row positions ``pos``, argmax (over the tokenizer vocab — the
    host Sampler's truncation, sampler.py:69), feed that, k times.
    Returns ((B, k) int32 draft tokens, updated draft cache). Gated rows
    pass pos == seq_len: every write drops out of bounds (the engine's
    standard OOB gating) and their tokens are garbage the caller
    ignores. Rows near the context edge rely on the same drop-mode
    scatter; their late tokens are never accepted (the verify caps at
    the row's headroom)."""

    def body(carry, _):
        tok, p, cache = carry
        logits, cache = forward(params, spec, tok, p, cache, **fwd_kwargs)
        nxt = jnp.argmax(logits[:, :n_vocab].astype(jnp.float32),
                         axis=-1).astype(jnp.int32)
        return (nxt[:, None], p + 1, cache), nxt

    (_, _, cache), toks = lax.scan(body, (tok0, pos, cache), None, length=k)
    return toks.T, cache  # (B, k)


def draft_prefill_cache(params, spec, tok, pos, cache, *, fwd_kwargs):
    """One (B, C) draft prefill chunk at per-row offsets; returns ONLY
    the updated cache — the logits head is dead code XLA eliminates, so
    a draft catch-up chunk never pays the wcls matmul. Gating and tail
    padding follow slot_prefill_chunk's invariants exactly (pad writes
    land beyond the real frontier and are overwritten before the draft
    attends them)."""
    _, cache = forward(params, spec, tok, pos, cache,
                       logit_index=jnp.zeros((tok.shape[0],), jnp.int32),
                       **fwd_kwargs)
    return cache


def batched_verify(params, spec, tok, pos, cache, *, n_vocab, fwd_kwargs):
    """The fixed-width slot verify forward: (B, 1+K) tokens at per-row
    positions with per-position logits, argmaxed ON DEVICE over the
    tokenizer vocab (fetching (B, T, V) floats per step is the D2H cost
    generate_batch_lookup already measured prohibitive; (B, T) int32 is
    bytes). Returns (greedy (B, 1+K) int32, position-0 logits (B, V) f32
    — the plain-decode logits non-speculating rows sample from, one
    fetch for both row classes, updated cache)."""
    logits, cache = forward(params, spec, tok, pos, cache,
                            logits_for_all=True, **fwd_kwargs)
    greedy = jnp.argmax(logits[..., :n_vocab].astype(jnp.float32),
                        axis=-1).astype(jnp.int32)
    return greedy, logits[:, 0], cache


# -- the draft model ---------------------------------------------------------


class DraftModel:
    """One draft forward (spec + params + its own KV cache shape) bound
    to a target :class:`runtime.engine.Engine`.

    The target engine supplies the batch/seq-len/cache-dtype shapes, the
    forward configuration, and — crucially — the compile ledger: every
    draft executable is minted via ``engine._mint`` under ``("sdraft_*",
    depth-label, ...)`` keys, so the recompile sentinel, the compile
    /stats block, and ``--freeze-compiles`` cover the draft path with no
    extra wiring. The draft's KV cache is the CALLER's state (the
    scheduler keeps one batched cache; a single-stream generation keeps
    its own): this object is immutable after construction and safely
    shared."""

    def __init__(self, engine, spec, params, *, label: str):
        if spec.vocab_size != engine.spec.vocab_size:
            raise ValueError(
                f"draft vocab {spec.vocab_size} != target vocab "
                f"{engine.spec.vocab_size} — draft and target must share "
                "the tokenizer (draft proposals are target token ids)")
        assert engine._pp == 1, "drafting does not support --pp"
        self.engine = engine
        self.spec = spec
        self.params = params
        self.label = label

    # -- constructors ------------------------------------------------------

    @classmethod
    def self_draft(cls, engine, depth: int) -> "DraftModel":
        """The zero-extra-weights mode: the target's first ``depth``
        layers + the shared embedding/final-norm/logits-head buffers.
        ``params["layers"]`` is a python slice of the target's list —
        the SAME device buffers, no copy."""
        depth = int(depth)
        if not 1 <= depth < engine.spec.n_layers:
            raise ValueError(
                f"--draft self:{depth}: depth must be in "
                f"1..{engine.spec.n_layers - 1} (the target has "
                f"{engine.spec.n_layers} layers; a full-depth 'draft' "
                "would just run the model twice)")
        spec = dataclasses.replace(engine.spec, n_layers=depth)
        params = dict(engine.params)
        params["layers"] = list(engine.params["layers"][:depth])
        return cls(engine, spec, params, label=f"self{depth}")

    @classmethod
    def from_file(cls, engine, path: str) -> "DraftModel":
        """A separate draft ``.m`` (TinyLlama-class): its own spec and
        weights, depth = its full layer count, same verify machinery.
        Loaded unsharded — model drafts require a mesh-less target (the
        self-draft inherits the target's sharding; a foreign checkpoint
        does not)."""
        if engine.mesh is not None:
            raise ValueError(
                "--draft model:PATH needs a mesh-less target engine "
                "(use --draft self:<depth>, which shares the target's "
                "sharded buffers)")
        from ..io.model_file import read_spec
        from ..models.loader import load_params_streamed
        from ..quants.types import FloatType

        spec = read_spec(path)
        mode = "q40" if spec.weights_float_type == FloatType.Q40 else "dense"
        params, _ = load_params_streamed(spec, path, None, mode=mode,
                                         dtype=engine.compute_dtype)
        return cls(engine, spec, params, label="model")

    # -- compiled draft programs ------------------------------------------

    def _kwargs(self) -> dict:
        # self-draft: the target's exact forward config (its params ARE
        # target buffers, sharding included). Model drafts loaded
        # unsharded keep the dtype/kernel knobs but no mesh.
        kw = self.engine._forward_kwargs()
        if self.label == "model":
            # vocab_mesh too: a file-loaded draft's tok_emb/wcls are
            # replicated single-device arrays — inheriting the target's
            # vocab sharding would reshard the whole draft embedding
            # through the sharded-gather shard_map on every dispatch
            kw.update(tp_mesh=None, sp_cache_mesh=None, pp_mesh=None,
                      vocab_mesh=None)
        return kw

    def new_cache(self) -> KVCache:
        """A fresh draft KV cache: depth layers x the TARGET's
        (batch, seq_len) shape in the target's cache dtype — d/L of the
        main cache's bytes. Built through a minted jitted maker (sharded
        placement on mesh engines, like Engine._new_cache)."""
        eng = self.engine
        key = ("sdraft_cache", self.label)
        if key not in eng._steps:
            spec, b, s, dt = self.spec, eng.batch, eng.seq_len, eng.cache_dtype
            mk = jax.jit(lambda: KVCache.create(spec, b, s, dt))
            if eng._cache_sharding is not None and self.label != "model":
                sh = KVCache((eng._cache_sharding,) * spec.n_layers,
                             (eng._cache_sharding,) * spec.n_layers)
                mk = jax.jit(lambda: KVCache.create(spec, b, s, dt),
                             out_shardings=sh)
            eng._mint(key, mk)
        return eng._steps[key]()

    def prefill_chunk(self, cache: KVCache, tok: np.ndarray,
                      pos: np.ndarray) -> KVCache:
        """One (B, C) draft prefill / catch-up chunk (gated rows pass
        pos == seq_len). C is part of the compile key; the scheduler
        always uses ONE fixed width (its widest rung), so this stays a
        single executable per draft."""
        eng = self.engine
        b, c = tok.shape
        key = ("sdraft_prefill", self.label, c)
        if key not in eng._steps:
            kw = self._kwargs()
            spec = self.spec

            def run(params, tok, pos, cache):
                return draft_prefill_cache(params, spec, tok, pos, cache,
                                           fwd_kwargs=kw)

            run.__name__ = f"draft_prefill_{self.label}_{c}"
            eng._mint(key, jax.jit(run, donate_argnums=(3,)))
        tokd, posd = self._put(tok, pos)
        return eng._steps[key](self.params, tokd, posd, cache)

    def propose(self, cache: KVCache, tok: np.ndarray, pos: np.ndarray,
                k: int, *, n_vocab: int) -> tuple[np.ndarray, KVCache]:
        """Greedy draft proposal: ONE dispatched scan of k draft steps.
        tok (B,) int32 is each row's last emitted token, fed at pos (B,)
        (== the target's next write position — the draft and target walk
        the same absolute positions). Returns ((B, k) np tokens, updated
        cache)."""
        eng = self.engine
        key = ("sdraft_scan", self.label, int(k), int(n_vocab))
        if key not in eng._steps:
            kw = self._kwargs()
            spec = self.spec

            def run(params, tok0, pos, cache, k=int(k), nv=int(n_vocab)):
                return draft_scan_tokens(params, spec, tok0, pos, cache,
                                         k=k, n_vocab=nv, fwd_kwargs=kw)

            run.__name__ = f"draft_scan_{self.label}_{k}"
            eng._mint(key, jax.jit(run, donate_argnums=(3,)))
        tokd, posd = self._put(np.asarray(tok, np.int32)[:, None], pos)
        toks, cache = eng._steps[key](self.params, tokd, posd, cache)
        return np.asarray(toks), cache

    def step_logits(self, cache: KVCache, tok: np.ndarray,
                    pos: np.ndarray) -> tuple[np.ndarray, KVCache]:
        """One single-token draft forward returning the full (B, V)
        logits — the SAMPLED draft loop's building block (the host draws
        each proposal from the draft's own distribution, so the next
        input is data-dependent and the loop cannot fuse into a scan).
        One compile key."""
        eng = self.engine
        key = ("sdraft_step", self.label)
        if key not in eng._steps:
            kw = self._kwargs()
            spec = self.spec

            def run(params, tok, pos, cache):
                return forward(params, spec, tok, pos, cache, **kw)

            run.__name__ = f"draft_step_{self.label}"
            eng._mint(key, jax.jit(run, donate_argnums=(3,)))
        tokd, posd = self._put(tok, pos)
        logits, cache = eng._steps[key](self.params, tokd, posd, cache)
        return np.asarray(logits), cache

    def _put(self, tok: np.ndarray, pos: np.ndarray):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.mesh import DP_AXIS

        eng = self.engine
        tokd = jnp.asarray(tok, jnp.int32)
        posd = jnp.asarray(pos, jnp.int32)
        if eng._token_sharding is not None and self.label != "model":
            tokd = jax.device_put(tokd, eng._token_sharding)
            posd = jax.device_put(
                posd, NamedSharding(eng.mesh, P(DP_AXIS)))
        return tokd, posd


def build_draft(engine, spec_str: str) -> DraftModel:
    """``--draft`` string -> DraftModel over ``engine`` (the factory the
    supervisor calls per generation: a rebuilt engine gets a fresh
    DraftModel over ITS buffers)."""
    kind, arg = parse_draft_spec(spec_str)
    if kind == "self":
        return DraftModel.self_draft(engine, int(arg))
    return DraftModel.from_file(engine, arg)
