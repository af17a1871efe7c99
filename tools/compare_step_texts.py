"""Is the text the TPU's compiler makes for the served step programs the
same on two trees? For a DESCRIBED v5e (no chip attached, nothing runs):

    JAX_PLATFORMS=cpu python tools/compare_step_texts.py write <tree> <dir>
    JAX_PLATFORMS=cpu python tools/compare_step_texts.py diff <dir_a> <dir_b>

`write` compiles, from the checkout at <tree>, both slot step programs of
the benchmark's configurations (six since PR 48, seven since PR 52) AS
THAT TREE'S ENGINE SERVES THEM (published widths, depth cut to a few layers
or one period, B=8 and chunks of 32, jamba2-3b's B=16 and chunks of 16, the
Q80 round trip on; the chunk with a slot map where the tree's own rule gives
it one) and keeps each program's text with its `metadata={...}` taken out. One process a tree: a process imports one
`distributed_llama_tpu`. `diff` compares two such directories program by
program: the text past its tables of source locations with every Pallas
kernel's serialized body taken out, and the bodies themselves, decoded and
printed without locations (a body's bytes carry the file names and line
numbers of the kernel's source, which differ between any two trees).
"""

from __future__ import annotations

import base64
import dataclasses
import glob
import os
import re
import sys


def write(tree: str, out: str) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [tree, os.path.join(tree, "tools")]
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    import distributed_llama_tpu
    import rehearse_chip_compile as r

    assert os.path.samefile(
        os.path.dirname(os.path.dirname(distributed_llama_tpu.__file__)), tree)
    devices = r.describe_topology().devices
    cut = dataclasses.replace
    configs = {
        "mistral-7b": (cut(r.MISTRAL_7B, n_layers=2), 4096),
        "mixtral-8x7b-12l": (cut(r.MIXTRAL_8X7B, n_layers=2), 4096),
        "sarvam-105b-ep8": (cut(r.SARVAM_105B_EP8, n_layers=3), 8192),
        "olmo-hybrid-7b": (r.hybrid_layers(r.OLMO_HYBRID_7B, 1), 8192),
        "granite-4.0-h-small-ep2": (
            r.hybrid_layers(r.GRANITE_4_H_SMALL_EP2, 1, 10), 8192)}
    if hasattr(r, "KIMI_LINEAR_48B_EP4"):     # a tree from PR 48 on
        configs["kimi-linear-48b-a3b-ep4"] = (
            r.hybrid_layers(r.KIMI_LINEAR_48B_EP4, 1), 8192)
    if hasattr(r, "JAMBA2_3B"):               # a tree from PR 52 on
        configs["jamba2-3b"] = (          # m m a m, 16 slots, chunks of 16
            cut(r.JAMBA2_3B, n_layers=4, mixers=(3, 3, 0, 3)), 8192, 16, 16)
    os.makedirs(out, exist_ok=True)
    for name, (spec, seq_len, *served) in configs.items():
        batch, chunk = served or (8, 32)
        for t in (1, chunk):
            fn, args = r.abstract_step(spec, devices, batch=batch, t=t,
                                       seq_len=seq_len, q80=True)
            text = fn.lower(*args).compile().as_text()
            text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
            key = f"{name}.{'decode' if t == 1 else f'chunk{chunk}'}"
            with open(os.path.join(out, key + ".txt"), "w") as f:
                f.write(text)
            print(key, len(text), "bytes", flush=True)


def kernel_bodies(text: str) -> list[str]:
    """The Pallas kernels of a compiled module, in order, as MLIR assembly
    without source locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jaxlib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    with ctx:
        return [ir.Module.parse(base64.b64decode(b)).operation.get_asm(
            enable_debug_info=False)
            for b in re.findall(r'"body":"([^"]*)"', text)]


def diff(dir_a: str, dir_b: str) -> bool:
    same = True
    for path in sorted(glob.glob(os.path.join(dir_a, "*.txt"))):
        with open(path) as f:
            a = f.read()
        with open(os.path.join(dir_b, os.path.basename(path))) as f:
            b = f.read()
        bare = lambda t: re.sub(  # noqa: E731
            r'"body":"[^"]*"', '"body":""', t[t.index("\n%"):])
        ka, kb = kernel_bodies(a), kernel_bodies(b)
        names = sorted({(re.search(r"@(\w+)", k) or [0, "?"])[1] for k in ka})
        ok = bare(a) == bare(b) and ka == kb
        same &= ok
        print(f"{os.path.basename(path)[:-4]}: text {bare(a) == bare(b)}, "
              f"{len(ka)} kernel bodies {ka == kb} {names}", flush=True)
    print("ALL EQUAL" if same else "DIFFERENT")
    return same


if __name__ == "__main__":
    if sys.argv[1] == "write":
        write(os.path.abspath(sys.argv[2]), sys.argv[3])
    else:
        sys.exit(0 if diff(sys.argv[2], sys.argv[3]) else 1)
