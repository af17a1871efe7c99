"""What a layer's tensors are is declared once (models/tensors.py), and the
file plan, both loaders, the fusing and the sharding read that declaration.

The digests below were taken on the tree BEFORE the declaration existed
(four file plans, two loaders, five lists of names; the parent of PR 51):
every spec's plan, every tiny file's bytes and every leaf of every params
pytree (path, shape, dtype, sharding, bytes) are what they were. A change
that moves one of them moves a model file's hash or a served program's
operand, and says so here first.
"""

import glob
import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.io.model_file import (content_fingerprint,
                                                 model_tensor_plan, read_model)
from distributed_llama_tpu.models import ArchType
from distributed_llama_tpu.models.loader import load_params_streamed
from distributed_llama_tpu.models.params import load_params
from distributed_llama_tpu.parallel import make_mesh
from distributed_llama_tpu.quants.types import FloatType
from distributed_llama_tpu.testing import (tiny_granite_spec,
                                           tiny_hybrid_spec, tiny_jamba_spec,
                                           tiny_kimi_spec, tiny_mla_spec,
                                           tiny_spec, write_synthetic_model)

from test_model_forward import make_spec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
PACKAGE = os.path.join(REPO, "distributed_llama_tpu")

TINY = {
    "llama": tiny_spec,
    "mixtral": lambda: make_spec(ArchType.MIXTRAL, hidden_dim=128,
                                 weights_float_type=FloatType.Q40),
    # a float32 file: every matmul weight is quantised at load in q40 mode
    "grok1": lambda: make_spec(ArchType.GROK1, hidden_dim=128),
    "sarvam_mla": tiny_mla_spec,
    "olmo_hybrid": tiny_hybrid_spec,
    "granite_hybrid": tiny_granite_spec,
    "kimi_linear": tiny_kimi_spec,
    "jamba": tiny_jamba_spec,       # PR 52: taken on the tree that added it
}
# for the meshes only: wide enough that a column split over 4 keeps whole
# Q40 blocks (2 kv heads, so tp=4 replicates them)
WIDE = {"llama-wide": lambda: tiny_spec(dim=128)}
SPECS = {**TINY, **WIDE}
CONFIGS = sorted(os.path.basename(p)[:-5] for p in
                 glob.glob(os.path.join(BENCH, "configs", "*.json")))
SEED = 51


def _bench_spec(name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import workmodel

    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        config = json.load(f)
    return workmodel.for_config(config).spec(config)


def plan_digest(spec) -> str:
    h = hashlib.sha256()
    for name, shape, ftype in model_tensor_plan(spec):
        h.update(f"{name}|{tuple(shape)}|{int(ftype)}\n".encode())
    return h.hexdigest()[:16]


def tree_digest(params) -> str:
    """Every leaf's path, shape, dtype, placement and bytes."""
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        spec = getattr(leaf.sharding, "spec", None)
        h.update(f"{jax.tree_util.keystr(path)}|{leaf.shape}|{leaf.dtype}|"
                 f"{spec}\n".encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()[:16]


# -- (a) the plan, for the eight tiny specs and the seven configurations ---

PLAN = {
    "llama": "7faf3c30223224a7",
    "mixtral": "53ea4a111e78c263",
    "grok1": "e9328b3e361df3df",
    "sarvam_mla": "a3ed22a9618f002b",
    "olmo_hybrid": "ffa219fbfa0923de",
    "granite_hybrid": "1a3b5a22c9542aaf",
    "kimi_linear": "07f9b34584691917",
    "jamba": "371d75fc34ab8ea9",
    "jamba2-3b": "961b63c311d8e692",
    "granite-4.0-h-small-ep2": "05fe47507df5d413",
    "kimi-linear-48b-a3b-ep4": "baf2da44967312bf",
    "mistral-7b": "2170aa8622708951",
    "mixtral-8x7b-12l": "0601ddb5cf23ed30",
    "olmo-hybrid-7b": "a9361408dab80388",
    "sarvam-105b-ep8": "cacef017ee997aab",
}


@pytest.mark.parametrize("name", [*TINY, *CONFIGS])
def test_plan_is_the_parents(name):
    spec = TINY[name]() if name in TINY else _bench_spec(name)
    assert plan_digest(spec) == PLAN[name]


# -- (b) the file's bytes ---------------------------------------------------

FILE = {
    "llama": 1471395468,
    "mixtral": 2803120070,
    "grok1": 3192714448,
    "sarvam_mla": 2268728784,
    "olmo_hybrid": 1186703354,
    "granite_hybrid": 1167398874,
    "kimi_linear": 1662979427,
    "jamba": 2845572666,
}


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    """name -> path of the spec's synthetic file, written once a module."""
    root = tmp_path_factory.mktemp("tensor_table")
    made: dict = {}

    def get(name):
        if name not in made:
            made[name] = str(root / f"{name}.m")
            write_synthetic_model(made[name], SPECS[name](), SEED)
        return made[name]

    return get


@pytest.mark.parametrize("name", list(TINY))
def test_file_bytes_are_the_parents(files, name):
    assert content_fingerprint(files(name)) == FILE[name]


# -- (c) the params pytree, leaf by leaf ------------------------------------

# (loader, mode, fuse): the dict feed in both modes, the file read with and
# without the single-shard fusion groups; bfloat16, so that every cast to
# the compute dtype is in the bytes
LOADS = [("bulk", "dense", False), ("bulk", "q40", False),
         ("streamed", "dense", False), ("streamed", "q40", False),
         ("streamed", "dense", True), ("streamed", "q40", True)]

TREE = {
    "llama/bulk/dense/plain": "8741d76efdff9d37",
    "llama/bulk/q40/plain": "44a23a61627d65ec",
    "llama/streamed/dense/plain": "8741d76efdff9d37",
    "llama/streamed/q40/plain": "44a23a61627d65ec",
    "llama/streamed/dense/fused": "9ca0c83256d03d55",
    "llama/streamed/q40/fused": "5e7b4ef3b2e16d6c",
    "mixtral/bulk/dense/plain": "bd4325ac14c5903b",
    "mixtral/bulk/q40/plain": "73a304cb96c870c2",
    "mixtral/streamed/dense/plain": "bd4325ac14c5903b",
    "mixtral/streamed/q40/plain": "73a304cb96c870c2",
    "mixtral/streamed/dense/fused": "d323d12208e6f3ea",
    "mixtral/streamed/q40/fused": "935e4e87dc1bbdad",
    "grok1/bulk/dense/plain": "7425a3998703f6ad",
    "grok1/bulk/q40/plain": "504d1826a6c0b101",
    "grok1/streamed/dense/plain": "7425a3998703f6ad",
    "grok1/streamed/q40/plain": "504d1826a6c0b101",
    "grok1/streamed/dense/fused": "07b1d2e7486b30a9",
    "grok1/streamed/q40/fused": "a032c4dbb9da117f",
    "sarvam_mla/bulk/dense/plain": "6d75e7e9a857a122",
    "sarvam_mla/bulk/q40/plain": "7b863a18c68d5e04",
    "sarvam_mla/streamed/dense/plain": "6d75e7e9a857a122",
    "sarvam_mla/streamed/q40/plain": "7b863a18c68d5e04",
    "sarvam_mla/streamed/dense/fused": "555a44dd2aad1199",
    "sarvam_mla/streamed/q40/fused": "395bc74f5d5f2a5f",
    "olmo_hybrid/bulk/dense/plain": "b8f9c6530bed0ff0",
    "olmo_hybrid/bulk/q40/plain": "dc58788ebf370984",
    "olmo_hybrid/streamed/dense/plain": "b8f9c6530bed0ff0",
    "olmo_hybrid/streamed/q40/plain": "dc58788ebf370984",
    "olmo_hybrid/streamed/dense/fused": "abca0b1c35283948",
    "olmo_hybrid/streamed/q40/fused": "7e79a8236bb9d487",
    "granite_hybrid/bulk/dense/plain": "7fd8ef37f8f262b9",
    "granite_hybrid/bulk/q40/plain": "fae150bd6c717921",
    "granite_hybrid/streamed/dense/plain": "7fd8ef37f8f262b9",
    "granite_hybrid/streamed/q40/plain": "fae150bd6c717921",
    "granite_hybrid/streamed/dense/fused": "1ff5f43a4b90014b",
    "granite_hybrid/streamed/q40/fused": "45cde2d9e8f31b20",
    "kimi_linear/bulk/dense/plain": "80b5b2c0a1f4f396",
    "kimi_linear/bulk/q40/plain": "561b04dc1a703735",
    "kimi_linear/streamed/dense/plain": "80b5b2c0a1f4f396",
    "kimi_linear/streamed/q40/plain": "561b04dc1a703735",
    "kimi_linear/streamed/dense/fused": "0c43f0a9341ffea8",
    "kimi_linear/streamed/q40/fused": "f7d42c97c4c45166",
    "jamba/bulk/dense/plain": "2b6b9c9a572f6e47",
    "jamba/bulk/q40/plain": "6215afb5dd241b41",
    "jamba/streamed/dense/plain": "2b6b9c9a572f6e47",
    "jamba/streamed/q40/plain": "6215afb5dd241b41",
    "jamba/streamed/dense/fused": "06dc4f503de97738",
    "jamba/streamed/q40/fused": "24ac62055f8b031b",
}


def _load(files, name, loader, mode, fuse, mesh=None, **kw):
    spec = SPECS[name]()
    if loader == "bulk":
        _, tensors = read_model(files(name), spec=spec)
        return load_params(spec, tensors, mode=mode, dtype=jnp.bfloat16)
    params, _ = load_params_streamed(spec, files(name), mesh, mode=mode,
                                     dtype=jnp.bfloat16, fuse=fuse, **kw)
    return params


@pytest.mark.parametrize("loader,mode,fuse", LOADS)
@pytest.mark.parametrize("name", list(TINY))
def test_params_are_the_parents(files, name, loader, mode, fuse):
    got = tree_digest(_load(files, name, loader, mode, fuse))
    assert got == TREE[f"{name}/{loader}/{mode}/{'fused' if fuse else 'plain'}"]


@pytest.mark.parametrize("mode", ["dense", "q40"])
@pytest.mark.parametrize("name", list(TINY))
def test_the_two_feeds_give_the_same_bytes(name, mode):
    """The dict feed (load_params) and the file read without fusing give
    every leaf the same bytes: the goldens say so, and one loader can
    serve both callers."""
    assert (TREE[f"{name}/bulk/{mode}/plain"]
            == TREE[f"{name}/streamed/{mode}/plain"])


# the placements only a mesh has: row and column splits, the q80 column
# stacks, kv heads replicated past their count, experts over ep, stages
# over pp (8 virtual devices, tests/conftest.py)
MESHES = {
    "llama/tp2": ("llama", dict(tp=2), {}),
    "llama/tp2-q80": ("llama", dict(tp=2), dict(q80_collectives=True)),
    "llama-wide/tp4-kvrep": ("llama-wide", dict(tp=4), {}),
    "llama/pp2-tp2": ("llama", dict(pp=2, tp=2), {}),
    "llama/tp2-vocab-replicated": ("llama", dict(tp=2),
                                   dict(shard_vocab=False)),
    "mixtral/tp2": ("mixtral", dict(tp=2), {}),
    "mixtral/ep2-tp2": ("mixtral", dict(ep=2, tp=2), {}),
    "mixtral/pp2-ep2-tp2": ("mixtral", dict(pp=2, ep=2, tp=2), {}),
    "grok1/tp2-q80": ("grok1", dict(tp=2), dict(q80_collectives=True)),
}

MESH_TREE = {
    "llama/tp2/dense": "c9559ec84d270513",
    "llama/tp2/q40": "d14dc8d2a952ffaa",
    "llama/tp2-q80/dense": "b306b6084af3ed5f",
    "llama/tp2-q80/q40": "360cc1315f925597",
    "llama-wide/tp4-kvrep/dense": "d2eb49d9faae6b12",
    "llama-wide/tp4-kvrep/q40": "32b4ff778b1db525",
    "llama/pp2-tp2/dense": "77b79abfccdbd851",
    "llama/pp2-tp2/q40": "e3197026c94b86d8",
    "llama/tp2-vocab-replicated/dense": "c2b703e3cf507ea3",
    "llama/tp2-vocab-replicated/q40": "62c8f067bcddacd5",
    "mixtral/tp2/dense": "6edee13f3b193877",
    "mixtral/tp2/q40": "be83d015016e6ec8",
    "mixtral/ep2-tp2/dense": "6693c336d8b5f0c7",
    "mixtral/ep2-tp2/q40": "43b07a6e432e6989",
    "mixtral/pp2-ep2-tp2/dense": "6e6f0d3269076719",
    "mixtral/pp2-ep2-tp2/q40": "bd8ac979f1fc361d",
    "grok1/tp2-q80/dense": "bf36f96d68067bac",
    "grok1/tp2-q80/q40": "055e7a266bd77a28",
}


@pytest.mark.parametrize("mode", ["dense", "q40"])
@pytest.mark.parametrize("case", list(MESHES))
def test_placed_params_are_the_parents(files, case, mode):
    name, axes, kw = MESHES[case]
    mesh = make_mesh(**axes)
    got = tree_digest(_load(files, name, "streamed", mode, None, mesh, **kw))
    assert got == MESH_TREE[f"{case}/{mode}"]


# -- the fusion groups, stated once ------------------------------------------

@pytest.mark.parametrize("mode", ["dense", "q40"])
@pytest.mark.parametrize("name", list(TINY))
def test_fusing_after_the_load_is_fusing_in_the_load(files, name, mode):
    """fuse_layer_weights on a loaded pytree gives the leaves the streamed
    loader builds fused, and unfuse_layer_weights takes them apart again
    at the members' declared row counts. (The parent cut every wqkv at
    dim | kv_dim and every w13 at hidden_dim, which is wrong for a DELTA
    layer's heads and a leading dense layer's width, and left wzx fused:
    engines that unfuse run under tp, which those layers refuse.)"""
    from distributed_llama_tpu.models.params import (fuse_layer_weights,
                                                     unfuse_layer_weights)

    fused = fuse_layer_weights(_load(files, name, "bulk", mode, False))
    assert tree_digest(fused) == TREE[f"{name}/streamed/{mode}/fused"]
    plain = unfuse_layer_weights(fused, TINY[name]())
    assert tree_digest(plain) == TREE[f"{name}/bulk/{mode}/plain"]


# -- the declaration stays single ------------------------------------------------

# the leaves that stood under an architecture's comment in the parent's
# table of splits, and the file tensors they are made of
DECLARED_ONCE = (
    "rms_kv", "moe_bias", "wkva", "w_uk", "w_uv", "sh_w1", "sh_w2", "sh_w3",
    "wg", "w_ab", "conv_w", "a_log", "dt_bias", "rms_o", "rms_q", "rms_k",
    "w_fgb", "wf_b", "wg_b", "wz", "wx", "wzx", "w_bcdt", "conv_b", "ssm_d",
    "wkvb", "wa", "wb", "wbc", "wdt", "wf_a", "wbeta", "wg_a", "wxp",
    "rms_dt", "rms_b", "rms_c")
WALKERS = ("io/model_file.py", "models/loader.py", "models/params.py",
           "parallel/sharding.py")


@pytest.mark.parametrize("rel", WALKERS)
def test_walkers_name_no_tensor_of_their_own(rel):
    """The file plan, the loader, the fusing and the sharding read
    models/tensors.py: none of them quotes a tensor or a leaf that a mixer
    added (the older leaves' transformations, kv replication's wk / wv and
    the vocab split of tok_emb / wcls, are not this guard's business)."""
    with open(os.path.join(PACKAGE, rel)) as f:
        src = re.sub(r"\bopen\([^)]*\)", "", f.read())   # open(path, "wb")
    quoted = set(re.findall(r"""["'](\w+)["']""", src))
    assert not quoted & set(DECLARED_ONCE), sorted(quoted
                                                   & set(DECLARED_ONCE))


def test_the_table_declares_them_all():
    """Every one of those names IS in the table, as a file tensor or as a
    leaf with its split, and a leaf that is not declared still raises."""
    from distributed_llama_tpu.models import tensors
    from distributed_llama_tpu.parallel.sharding import leaf_pspec

    files = {t.name for g in tensors._GROUPS for t in g}
    assert set(DECLARED_ONCE) <= files | set(tensors.LEAF_SPLIT)
    assert tensors.FUSION_GROUPS == {"wqkv": ["wq", "wk", "wv"],
                                     "w13": ["w1", "w3"],
                                     "wzx": ["wz", "wx"]}
    with pytest.raises(KeyError):
        leaf_pspec("w_new", 2)


def test_one_plan_one_loader():
    """model_tensor_plan is the only plan; load_params has no branch on a
    layer's kind and no `put=`; loader.py imports nothing private from
    parallel/sharding.py."""
    import inspect

    from distributed_llama_tpu.models import loader, params

    with open(os.path.join(PACKAGE, "io/model_file.py")) as f:
        assert set(re.findall(r"\w*_tensor_plan", f.read())) == {
            "model_tensor_plan"}
    body = inspect.getsource(params.load_params)
    assert "LayerKind" not in body and "put" not in inspect.signature(
        params.load_params).parameters
    assert list(inspect.signature(params.load_params).parameters) == [
        "spec", "tensors", "mode", "dtype"]
    assert not re.search(r"sharding import[^\n]*\b_", inspect.getsource(loader))
