"""Qwen3 (``model_type`` "qwen3"): dense grouped-query attention with an
RMSNorm on every q and k head, rotary embedding, a SwiGLU MLP and tied
embeddings; rank-r LoRA on the served ``lora`` group's targets.

Shapes come from the configuration file (its published keys, and the
served ``lora`` group); a float32 model moves 4 bytes an element."""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from perfkit import reference, weights


@dataclasses.dataclass(frozen=True)
class Shape:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    lora_rank: int
    lora_targets: tuple[str, ...]
    itemsize: int = 4

    @classmethod
    def from_config(cls, cfg: dict) -> "Shape":
        m = cfg
        lora = cfg["serving"]["lora"]
        return cls(layers=m["num_hidden_layers"], d_model=m["hidden_size"],
                   heads=m["num_attention_heads"],
                   kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
                   d_ff=m["intermediate_size"], vocab=m["vocab_size"],
                   lora_rank=lora["rank"], lora_targets=tuple(lora["targets"]),
                   itemsize=4 if cfg["serving"]["dtype"] == "float32" else 2)

    def proj_dims(self) -> dict[str, tuple[int, int]]:
        d, hd = self.d_model, self.head_dim
        return {"q": (d, self.heads * hd), "k": (d, self.kv_heads * hd),
                "v": (d, self.kv_heads * hd), "o": (self.heads * hd, d)}


# ------------------------------------------------------------ the program
def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file: the program's
    architecture entry with the file's sizes, q/k RMSNorm on and a SwiGLU
    MLP, as Qwen3's block has them."""
    from repro import configs
    from repro.configs.base import LoRAConfig

    m, sv = cfg, cfg["serving"]
    lora = sv["lora"]
    return dataclasses.replace(
        configs.get(sv["arch"]),
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        tie_embeddings=bool(m["tie_word_embeddings"]), qk_norm=True,
        activation="silu",
        lora=LoRAConfig(rank=lora["rank"], alpha=float(lora["alpha"]),
                        targets=tuple(lora["targets"]),
                        max_adapters=lora["max_adapters"]))


def kv_bytes_per_token(s: Shape) -> int:
    return s.layers * 2 * s.kv_heads * s.head_dim * s.itemsize


def adapter_bytes(s: Shape) -> int:
    return s.layers * s.itemsize * sum(
        din * s.lora_rank + s.lora_rank * dout
        for t, (din, dout) in s.proj_dims().items() if t in s.lora_targets)


# ------------------------------------------------------------ the weights
@functools.partial(jax.jit, static_argnums=(1, 2))
def make_params(key: jax.Array, s: Shape, dtype=jnp.float32) -> dict:
    """The engine's parameter tree: RMSNorm gains (held as ``scale``, applied
    as ``1 + scale``), q/k RMSNorm per head, tied embeddings."""
    L, d, hd = s.layers, s.d_model, s.head_dim
    k = iter(jax.random.split(key, 16))
    return {
        "embed": (jax.random.normal(next(k), (s.vocab, d), jnp.float32)
                  * weights.EMBED_STD).astype(dtype),
        "final_norm": weights.norm(next(k), (d,), dtype),
        "layers": {
            "norm1": weights.norm(next(k), (L, d), dtype),
            "norm2": weights.norm(next(k), (L, d), dtype),
            "mixer": {
                "wq": weights.dense(next(k), (L, d, s.heads * hd), dtype),
                "wk": weights.dense(next(k), (L, d, s.kv_heads * hd), dtype),
                "wv": weights.dense(next(k), (L, d, s.kv_heads * hd), dtype),
                "wo": weights.dense(next(k), (L, s.heads * hd, d), dtype),
                "q_norm": weights.norm(next(k), (L, hd), dtype),
                "k_norm": weights.norm(next(k), (L, hd), dtype),
            },
            "ffn": {
                "w_gate": weights.dense(next(k), (L, d, s.d_ff), dtype),
                "w_up": weights.dense(next(k), (L, d, s.d_ff), dtype),
                "w_down": weights.dense(next(k), (L, s.d_ff, d), dtype),
            },
        },
    }


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def make_adapters(key: jax.Array, s: Shape, n: int, dtype=jnp.float32) -> dict:
    """``n`` adapters: {target: (A (n, L, d_in, r), B (n, L, r, d_out))}."""
    out = {}
    for i, (t, (din, dout)) in enumerate(s.proj_dims().items()):
        if t not in s.lora_targets:
            continue
        ka, kb = jax.random.split(jax.random.fold_in(key, i))
        a = jax.random.normal(ka, (n, s.layers, din, s.lora_rank),
                              jnp.float32) / din ** 0.5
        b = jax.random.normal(kb, (n, s.layers, s.lora_rank, dout),
                              jnp.float32) * weights.LORA_B_STD
        out[t] = (a.astype(dtype), b.astype(dtype))
    return out


# --------------------------------------------------------- the reference
@dataclasses.dataclass(frozen=True)
class Consts:
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    eps: float
    lora_scale: float
    targets: tuple[str, ...]

    @classmethod
    def from_config(cls, cfg: dict) -> "Consts":
        m, lora = cfg, cfg["serving"]["lora"]
        return cls(heads=m["num_attention_heads"],
                   kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
                   rope_theta=float(m["rope_theta"]), eps=float(m["rms_norm_eps"]),
                   lora_scale=lora["alpha"] / lora["rank"],
                   targets=tuple(lora["targets"]))


@functools.partial(jax.jit, static_argnames=("c", "precision"))
def logits_at(params: dict, adapter: dict, tokens: jax.Array,
              at: jax.Array, lora_from: jax.Array, *, c: Consts,
              precision: str) -> jax.Array:
    """Qwen3's forward pass. Per layer: RMSNorm, q/k/v projections each plus
    its LoRA delta ``(x A) B * alpha / rank``, RMSNorm of every q and k head,
    rotary embedding, causal grouped-query softmax attention, the output
    projection plus its LoRA delta, the residual, RMSNorm, a SwiGLU MLP and
    the residual; then the final RMSNorm and the tied embedding as the LM
    head."""
    dt, prec, mm = reference.numerics(precision)
    cast = lambda t: jax.tree.map(lambda a: a.astype(dt), t)
    params, adapter = cast(params), cast(adapter)
    T = tokens.shape[0]
    H, Hk, D = c.heads, c.kv_heads, c.head_dim
    G = H // Hk
    causal = jnp.tril(jnp.ones((T, T), bool))
    on = (jnp.arange(T) >= lora_from).astype(dt)[:, None]

    def proj(h, w, name, ad):
        y = mm(h, w)
        if name in c.targets:
            a, b = ad[name]
            y = y + (mm(mm(h, a), b) * c.lora_scale).astype(dt) * on
        return y

    def layer(x, xs):
        lp, ad = xs
        mx = lp["mixer"]
        h = reference.rms(x, lp["norm1"], c.eps, dt)
        q = proj(h, mx["wq"], "q", ad).reshape(T, H, D)
        k = proj(h, mx["wk"], "k", ad).reshape(T, Hk, D)
        v = proj(h, mx["wv"], "v", ad).reshape(T, Hk, D)
        q = reference.rope(reference.rms(q, mx["q_norm"], c.eps, dt), c.rope_theta)
        k = reference.rope(reference.rms(k, mx["k_norm"], c.eps, dt), c.rope_theta)
        qg = q.reshape(T, Hk, G, D)
        s = jnp.einsum("tkgd,ukd->kgtu", qg, k, precision=prec)
        s = s.astype(jnp.float32) / np.sqrt(D)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(dt)
        o = jnp.einsum("kgtu,ukd->tkgd", p, v, precision=prec).reshape(T, H * D)
        x = x + proj(o, mx["wo"], "o", ad)
        h2 = reference.rms(x, lp["norm2"], c.eps, dt)
        f = lp["ffn"]
        x = x + mm(jax.nn.silu(mm(h2, f["w_gate"])) * mm(h2, f["w_up"]),
                   f["w_down"])
        return x, None

    x = jnp.take(params["embed"], tokens, axis=0)
    # adapters are stored (L, d_in, r) per target: scan them with the layers
    per_layer = {t: adapter[t] for t in adapter}
    x, _ = jax.lax.scan(layer, x, (params["layers"], per_layer))
    h = reference.rms(jnp.take(x, at, axis=0), params["final_norm"], c.eps, dt)
    return jnp.matmul(h, params["embed"].T, precision=prec).astype(jnp.float32)


# ------------------------------------------------------- the work counts
def token_flops(s: Shape, position: int, emits: bool) -> int:
    """Model FLOPs of one computed token at absolute ``position`` (it
    attends ``position + 1`` keys): projections, LoRA, MLP and attention in
    every layer, and the LM head when the token's logits are used."""
    per_layer = 0
    for t, (din, dout) in s.proj_dims().items():
        per_layer += 2 * din * dout
        if t in s.lora_targets:
            per_layer += 2 * din * s.lora_rank + 2 * s.lora_rank * dout
    per_layer += 3 * 2 * s.d_model * s.d_ff
    per_layer += 4 * s.heads * s.head_dim * (position + 1)
    head = 2 * s.d_model * s.vocab if emits else 0
    return s.layers * per_layer + head


def span_flops(s: Shape, start: int, n: int, emits_last: bool) -> int:
    """FLOPs of ``n`` tokens computed at positions ``start .. start+n-1``
    (closed form of summing :func:`token_flops`)."""
    if n <= 0:
        return 0
    f0 = token_flops(s, 0, False)  # the position-independent part
    attn = 4 * s.heads * s.head_dim * s.layers
    # sum over positions p of (p + 1) = n*start + n(n+1)/2
    ctx = n * start + n * (n + 1) // 2
    head = 2 * s.d_model * s.vocab if emits_last else 0
    return n * (f0 - attn) + attn * ctx + head


def ragged_extend_need(s: Shape, rows: Iterable[tuple[int, int]]) -> tuple[int, int]:
    """(FLOPs, bytes) one ``ragged_extend`` call needs in one layer.

    ``rows`` are (start, n): a row's n real query tokens at positions
    ``start ..``; each attends every earlier position and itself. Bytes: the
    real queries in and out once, and each row's keys and values once."""
    flops = nbytes = 0
    for start, n in rows:
        if n <= 0:
            continue
        ctx = n * start + n * (n + 1) // 2
        flops += 4 * s.heads * s.head_dim * ctx
        nbytes += 2 * n * s.heads * s.head_dim * s.itemsize
        nbytes += 2 * (start + n) * s.kv_heads * s.head_dim * s.itemsize
    return flops, nbytes


def fused_sgmv_need(s: Shape, target: str, tokens: int,
                    adapters: int) -> tuple[int, int]:
    """(FLOPs, bytes) one ``fused_sgmv`` call needs: ``tokens`` real rows
    times the base weight plus each row's adapter, reading the weight once,
    each distinct adapter once, and the real rows in and out once."""
    if tokens <= 0:
        return 0, 0
    din, dout = s.proj_dims()[target]
    r = s.lora_rank
    flops = 2 * tokens * din * dout + 2 * tokens * (din * r + r * dout)
    nbytes = (din * dout + tokens * (din + dout)
              + adapters * (din * r + r * dout)) * s.itemsize
    return flops, nbytes
