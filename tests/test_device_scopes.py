"""Every op of the two served step programs under one name of the closed
vocabulary models/scopes.DEVICE_SCOPES: the eight architectures' tiny models
are compiled (on this CPU) and the compiled module's text read.

Counted are the instructions that compute or move data: not parameters,
constants, tuples, get-tuple-element or bitcast, nor a broadcast or an iota
(values from nothing), nor a scalar result (a reducer's body). Of those
that name a line of the program (`op_name="jit(...)/..."`) every one is to
carry a scope; the CPU compiler's own instructions (no op_name, or a
parameter's name on a relayout of it) no scope can reach, and they stay a
small share of the whole.
"""

import re

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.models.params import load_params, random_tensors
from distributed_llama_tpu.models.scopes import DEVICE_SCOPES
from distributed_llama_tpu.models.spec import ArchType
from distributed_llama_tpu.runtime.engine import Engine
from distributed_llama_tpu.testing import (tiny_granite_spec, tiny_hybrid_spec,
                                           tiny_jamba_spec, tiny_kimi_spec,
                                           tiny_mla_spec, tiny_spec)

B, CHUNK = 4, 8
SPECS = {
    "LLAMA": tiny_spec,
    "MIXTRAL": lambda: tiny_spec(arch=ArchType.MIXTRAL, n_experts=4,
                                 n_active_experts=2),
    "GROK1": lambda: tiny_spec(arch=ArchType.GROK1, n_experts=4,
                               n_active_experts=2),
    "SARVAM_MLA": tiny_mla_spec,
    "OLMO_HYBRID": tiny_hybrid_spec,
    "GRANITE_HYBRID": tiny_granite_spec,
    "KIMI_LINEAR": tiny_kimi_spec,
    "JAMBA": tiny_jamba_spec,
}
# what each architecture's programs must show, beside the dense names
OWN = {"MIXTRAL": {"moe_router", "moe_routed"},
       "GROK1": {"moe_router", "moe_routed"},
       "SARVAM_MLA": {"mla_absorb", "moe_router", "moe_routed", "moe_shared"},
       "OLMO_HYBRID": {"gdn_proj", "gdn_conv", "gdn_rule", "gdn_out"},
       "GRANITE_HYBRID": {"ssm_proj", "ssm_conv", "ssm_scan", "ssm_out",
                          "moe_router", "moe_routed", "moe_shared"},
       "JAMBA": {"ssm_proj", "ssm_conv", "ssm_dt", "ssm_scan", "ssm_out"},
       "KIMI_LINEAR": {"kda_proj", "kda_conv", "kda_rule", "kda_out",
                       "mla_absorb", "moe_router", "moe_routed",
                       "moe_shared"}}
DENSE = {"embed", "attn_proj", "attn_cache", "attn_core", "attn_out", "ffn",
         "block_tail", "head", "act_q80"}
TRIVIAL = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
           "broadcast", "iota"}
# `%name = <non-scalar shape> opcode(`: a scalar's shape is `f32[]`
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (?!\w+\[\] )\S+ ([a-z][a-z\-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module")
def compiled_text():
    made = {}

    def text(arch: str, program: str) -> str:
        if arch not in made:
            spec = SPECS[arch]()
            params = load_params(
                spec, random_tensors(spec, seed=1, scale=0.05), mode="q40",
                dtype=jnp.float32)
            eng = Engine(spec, params, batch=B, compute_dtype=jnp.float32,
                         cache_dtype=jnp.float32, activation_q80=True,
                         use_pallas=False)
            i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
            pos = np.full((B,), eng.seq_len, np.int32)
            one, chunk = np.zeros((B, 1)), np.zeros((B, CHUNK))
            eng.slot_decode_step(one, pos)             # mint both programs
            eng.slot_prefill_chunk(chunk, pos, np.zeros(B))
            # as the engine calls them: the chunk's slot map where it
            # takes one, the summary's operand (PR 53)
            served = (*((eng._identity_map,) if eng._chunk_slot_map else ()),
                      *eng._sample_operands(None, None))
            made[arch] = {
                "decode": eng._steps["slot_decode"].lower(
                    eng.params, i32(one), i32(pos), eng.cache, *served[-1:]),
                "prefill": eng._steps["slot_prefill", CHUNK].lower(
                    eng.params, i32(chunk), i32(pos), i32(np.zeros(B)),
                    eng.cache, *served)}
        return made[arch][program].compile().as_text()

    return text


@pytest.mark.limit_s(120)
@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("arch", list(SPECS))
def test_every_op_of_a_served_step_program_is_under_a_device_scope(
        compiled_text, arch, program):
    counted = scoped = own = own_scoped = 0
    found = set()
    for line in compiled_text(arch, program).split("\n"):
        m = INSTRUCTION.match(line)
        if not m or m.group(1) in TRIVIAL:
            continue
        counted += 1
        name = OP_NAME.search(line)
        parts = name.group(1).split("/") if name else []
        names = [p for p in parts if p in DEVICE_SCOPES]
        found.update(names)
        scoped += bool(names)
        if parts and parts[0].startswith("jit("):   # a line of the program
            own += 1
            own_scoped += bool(names)
    assert counted > 300 and own > 0.9 * counted
    assert own_scoped >= 0.99 * own, (own_scoped, own)
    assert scoped >= 0.93 * counted, (scoped, counted)
    assert found == DENSE | OWN.get(arch, set())


@pytest.mark.limit_s(30)
def test_the_vocabulary_is_closed_and_no_name_holds_a_layer_index():
    assert len(DEVICE_SCOPES) == len(set(DEVICE_SCOPES)) == 26
    assert all(re.fullmatch(r"[a-z]+(_[a-z0-9]+)*", s) and
               not re.search(r"\d+$", s.replace("q80", ""))
               for s in DEVICE_SCOPES)
    # the names the step programs' sources use are the tuple's
    import inspect

    from distributed_llama_tpu.models import transformer
    from distributed_llama_tpu.ops import matmul
    used = set(re.findall(r'named_scope\("([^"]+)"\)',
                          inspect.getsource(transformer)
                          + inspect.getsource(matmul)))
    assert used == set(DEVICE_SCOPES)
