"""What the two plain references share: reading a `.m` file, the Q40 block
decode, RMS norm, rotary embeddings and causal attention.

Plain `jax.numpy`, float32, matmuls at "highest" precision (on a TPU a
float32 matmul otherwise runs in bf16 passes). No kernels, no cache, no
batching, nothing imported from the program under test: the file layout and
the block decode are written out here again on purpose.

`.m` layout (the C++ reference engine's format): int32 magic 0xA00ABCD,
int32 header size, (int32 key, int32 value) pairs, then tensors in a fixed
order — tok_emb (f32); per layer wq wk wv wo, then w1 w2 w3 (dense) or
moe_router and per expert up gate down (MoE), then rms_att rms_ffn (f32);
rms_final (f32); wcls. A Q40 block is 18 bytes for 32 values: an f16 scale,
then 16 bytes whose LOW nibbles are values 0..15 and HIGH nibbles values
16..31; value = (nibble - 8) * scale.
"""

from __future__ import annotations

import struct

import jax
import jax.numpy as jnp
import numpy as np

MAGIC = 0xA00ABCD
KEYS = {1: "arch", 2: "dim", 3: "hidden_dim", 4: "n_layers", 5: "n_heads",
        6: "n_kv_heads", 7: "n_experts", 8: "n_active_experts",
        9: "vocab_size", 10: "seq_len", 11: "hidden_act", 12: "rope_theta",
        13: "weights_float_type", 0: "version"}
Q40, F32 = 2, 0   # weights_float_type codes of the format
RMS_EPS = 1e-5


def highest(fn):
    """Run fn with float32 matmuls at full precision."""
    def wrapped(*a, **k):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **k)
    return wrapped


class ModelFile:
    """Header fields and tensor offsets of one `.m` file; `tensor(name)`
    decodes one tensor to float32 (d, n), nothing else stays resident."""

    def __init__(self, path: str):
        self.path = path
        with open(path, "rb") as f:
            magic, size = struct.unpack("<ii", f.read(8))
            if magic != MAGIC:
                raise ValueError(f"{path}: not a KV-header .m file")
            body = f.read(size - 8)
        self.h = {KEYS[k]: v for k, v in
                  struct.iter_unpack("<ii", body)}
        h = self.h
        if h["weights_float_type"] != Q40:
            raise ValueError("the references read Q40 files only")
        self.kv_dim = h["dim"] * h["n_kv_heads"] // h["n_heads"]
        self.offsets: dict[str, tuple[int, tuple, int]] = {}
        off = size
        for name, shape, kind in self._plan():
            n = int(np.prod(shape))
            nbytes = n * 4 if kind == F32 else n // 32 * 18
            self.offsets[name] = (off, shape, kind)
            off += nbytes
        self.end = off

    def _plan(self):
        h, d, kv = self.h, self.h["dim"], self.kv_dim
        hid, ne = h["hidden_dim"], h.get("n_experts", 0)
        yield "tok_emb", (h["vocab_size"], d), F32
        for l in range(h["n_layers"]):
            p = f"layers.{l}."
            yield p + "wq", (d, d), Q40
            yield p + "wk", (kv, d), Q40
            yield p + "wv", (kv, d), Q40
            yield p + "wo", (d, d), Q40
            if ne:
                yield p + "moe_router", (ne, d), Q40
                for e in range(ne):
                    yield p + f"experts.{e}.up", (hid, d), Q40
                    yield p + f"experts.{e}.gate", (hid, d), Q40
                    yield p + f"experts.{e}.down", (d, hid), Q40
            else:
                yield p + "w1", (hid, d), Q40
                yield p + "w2", (d, hid), Q40
                yield p + "w3", (hid, d), Q40
            yield p + "rms_att", (d,), F32
            yield p + "rms_ffn", (d,), F32
        yield "rms_final", (d,), F32
        yield "wcls", (h["vocab_size"], d), Q40

    def raw(self, name: str) -> tuple[np.ndarray, tuple, int]:
        off, shape, kind = self.offsets[name]
        n = int(np.prod(shape))
        count = n * 4 if kind == F32 else n // 32 * 18
        return np.fromfile(self.path, np.uint8, count, offset=off), shape, kind

    def tensor(self, name: str) -> jnp.ndarray:
        buf, shape, kind = self.raw(name)
        if kind == F32:
            return jnp.asarray(buf.view(np.float32).reshape(shape))
        return decode_q40(jnp.asarray(buf), shape)

    def rows(self, name: str, idx: np.ndarray) -> jnp.ndarray:
        """Rows `idx` of an f32 tensor (the embedding of a few tokens)."""
        off, shape, kind = self.offsets[name]
        assert kind == F32
        table = np.memmap(self.path, np.float32, "r", off, shape)
        return jnp.asarray(np.asarray(table[np.asarray(idx)]))


@jax.jit
def _decode_blocks(raw: jnp.ndarray) -> jnp.ndarray:
    blocks = raw.reshape(-1, 18)
    scale = jax.lax.bitcast_convert_type(
        blocks[:, :2].reshape(-1, 2), jnp.float16).astype(jnp.float32)
    q = blocks[:, 2:].astype(jnp.int32)
    lo = (q & 0xF) - 8
    hi = (q >> 4) - 8
    vals = jnp.concatenate([lo, hi], axis=1).astype(jnp.float32)
    return vals * scale.reshape(-1, 1)


def decode_q40(raw: jnp.ndarray, shape: tuple) -> jnp.ndarray:
    """18-byte blocks -> float32 values of `shape`."""
    return _decode_blocks(raw).reshape(shape)


def rms_norm(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + RMS_EPS))


def rope_angles(t: int, head: int, theta: float):
    j = jnp.arange(head // 2, dtype=jnp.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] / theta ** (2.0 * j / head)
    return jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]   # (T, 1, hs/2)


def rope_interleaved(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Pairs (2j, 2j+1) of each head rotate together — the original Llama
    formulation, which the `.m` format's LLAMA architecture uses (weights
    converted from a half-split checkpoint are permuted to match)."""
    t, h, hs = x.shape
    c, s = rope_angles(t, hs, theta)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * c - x1 * s, x0 * s + x1 * c], -1).reshape(t, h, hs)


def rope_half_split(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Element j of a head rotates with element j + hs/2 — the published
    Mixtral (and Hugging Face) formulation."""
    t, h, hs = x.shape
    c, s = rope_angles(t, hs, theta)
    x0, x1 = x[..., : hs // 2], x[..., hs // 2:]
    return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c], -1)


def causal_attention(q, k, v) -> jnp.ndarray:
    """q (T, H, hs), k and v (T, Hkv, hs): grouped-query causal softmax
    attention over the whole sequence; returns (T, H * hs)."""
    t, h, hs = q.shape
    group = h // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(hs))
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, -1), v)
    return out.reshape(t, h * hs)


def attention_block(mf: ModelFile, l: int, x: jnp.ndarray, rope) -> jnp.ndarray:
    """x + Wo . attention(rope(Wq h), rope(Wk h), Wv h), h = rms_norm(x)."""
    p = f"layers.{l}."
    hd, heads, kvh = mf.h["dim"] // mf.h["n_heads"], mf.h["n_heads"], \
        mf.h["n_kv_heads"]
    theta = float(mf.h["rope_theta"])
    t = x.shape[0]
    hn = rms_norm(x, mf.tensor(p + "rms_att"))
    q = (hn @ mf.tensor(p + "wq").T).reshape(t, heads, hd)
    k = (hn @ mf.tensor(p + "wk").T).reshape(t, kvh, hd)
    v = (hn @ mf.tensor(p + "wv").T).reshape(t, kvh, hd)
    att = causal_attention(rope(q, theta), rope(k, theta), v)
    return x + att @ mf.tensor(p + "wo").T


def head(mf: ModelFile, x: jnp.ndarray) -> jnp.ndarray:
    return rms_norm(x, mf.tensor("rms_final")) @ mf.tensor("wcls").T
