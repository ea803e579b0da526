"""A model module for a grouped-query attention block without q/k RMSNorm
(``model_type`` "llama", with tied embeddings as Llama-3.2-1B publishes
them). The harness's tests copy it into a checkout as
``bench/models/llama.py`` to add a second architecture by new files alone.

Apart from the q/k norms the block is Qwen3's, so the shape, the adapters
and the work counts come from the sibling ``qwen3.py``; the program
config, the weight tree and the reference are this module's own."""

from __future__ import annotations

import dataclasses
import functools
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from perfkit import reference, spec, weights

_qwen3 = spec.model_module({"model_type": "qwen3"},
                           pathlib.Path(__file__).resolve().parents[1])
Shape, Consts = _qwen3.Shape, _qwen3.Consts
kv_bytes_per_token, adapter_bytes = _qwen3.kv_bytes_per_token, _qwen3.adapter_bytes
make_adapters, span_flops = _qwen3.make_adapters, _qwen3.span_flops
ragged_extend_need, fused_sgmv_need = _qwen3.ragged_extend_need, _qwen3.fused_sgmv_need


def program_config(cfg: dict):
    """The program's ModelConfig, served by its path without q/k norms."""
    return dataclasses.replace(_qwen3.program_config(cfg), qk_norm=False)


@functools.partial(jax.jit, static_argnums=(1, 2))
def make_params(key: jax.Array, s: Shape, dtype=jnp.float32) -> dict:
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
            },
            "ffn": {
                "w_gate": weights.dense(next(k), (L, d, s.d_ff), dtype),
                "w_up": weights.dense(next(k), (L, d, s.d_ff), dtype),
                "w_down": weights.dense(next(k), (L, s.d_ff, d), dtype),
            },
        },
    }


@functools.partial(jax.jit, static_argnames=("c", "precision"))
def logits_at(params: dict, adapter: dict, tokens: jax.Array,
              at: jax.Array, lora_from: jax.Array, *, c: Consts,
              precision: str) -> jax.Array:
    """Per layer: RMSNorm, q/k/v projections with LoRA, rotary embedding,
    causal grouped-query attention, the output projection with LoRA, the
    residual, RMSNorm, SwiGLU and the residual; the tied LM head."""
    dt, prec, mm = reference.numerics(precision)
    params, adapter = jax.tree.map(lambda a: a.astype(dt), (params, adapter))
    T = tokens.shape[0]
    H, Hk, D = c.heads, c.kv_heads, c.head_dim
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
        mx, f = lp["mixer"], lp["ffn"]
        h = reference.rms(x, lp["norm1"], c.eps, dt)
        q = reference.rope(proj(h, mx["wq"], "q", ad).reshape(T, H, D), c.rope_theta)
        k = reference.rope(proj(h, mx["wk"], "k", ad).reshape(T, Hk, D), c.rope_theta)
        v = proj(h, mx["wv"], "v", ad).reshape(T, Hk, D)
        s = jnp.einsum("tkgd,ukd->kgtu", q.reshape(T, Hk, H // Hk, D), k, precision=prec)
        s = jnp.where(causal[None, None], s.astype(jnp.float32) / np.sqrt(D), -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(dt)
        o = jnp.einsum("kgtu,ukd->tkgd", p, v, precision=prec).reshape(T, H * D)
        x = x + proj(o, mx["wo"], "o", ad)
        h2 = reference.rms(x, lp["norm2"], c.eps, dt)
        return x + mm(jax.nn.silu(mm(h2, f["w_gate"])) * mm(h2, f["w_up"]),
                      f["w_down"]), None

    x, _ = jax.lax.scan(layer, jnp.take(params["embed"], tokens, axis=0),
                        (params["layers"], adapter))
    h = reference.rms(jnp.take(x, at, axis=0), params["final_norm"], c.eps, dt)
    return jnp.matmul(h, params["embed"].T, precision=prec).astype(jnp.float32)
