"""The plain reference: Qwen3's forward pass in straightforward
``jax.numpy``, one sequence at a time, written from the published
architecture and imported from nothing of the program.

Per layer: RMSNorm, q/k/v projections each plus its LoRA delta
``(x A) B * alpha / rank``, RMSNorm of every q and k head, rotary embedding
(rotate-half, ``rope_theta``), causal grouped-query softmax attention, the
output projection plus its LoRA delta, the residual, RMSNorm, a SwiGLU MLP and
the residual; then the final RMSNorm and the tied embedding as the LM head.
An RMSNorm gain is ``1 + scale`` (see weights.py).

``precision="highest"`` is the oracle (float32, every matmul at full
precision). The lower precisions are controls, each the step below a stated
precision: ``"bf16"`` keeps weights and activations in bfloat16 with
matmuls at the default precision, the way a bf16 server runs; ``"fp8"``
also rounds every matmul operand to float8 (e4m3, one scale per tensor), the
way an fp8 server runs. Norms and softmax are taken in float32 and cast
back, as such servers do."""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


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


def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor, back in x's type."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32))) / 448.0 + 1e-30
    q = (x.astype(jnp.float32) / s).astype(jnp.float8_e4m3fn)
    return (q.astype(jnp.float32) * s).astype(x.dtype)


def _rms(x, scale, eps, dtype):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def _rope(x, theta):
    # x: (T, H, D); rotate-half convention
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., : D // 2], x32[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("c", "precision"))
def logits_at(params: dict, adapter: dict, tokens: jax.Array,
              at: jax.Array, lora_from: jax.Array, *, c: Consts,
              precision: str) -> jax.Array:
    """Logits (len(at), vocab) in float32 at positions ``at`` of the causal
    forward over ``tokens`` (trailing padding cannot reach them). Positions
    before ``lora_from`` (a prompt's adapter-independent opening) are
    computed without the adapter."""
    if precision == "highest":
        dt, prec = jnp.float32, jax.lax.Precision.HIGHEST
    elif precision in ("bf16", "fp8"):
        dt, prec = jnp.bfloat16, jax.lax.Precision.DEFAULT
    else:
        raise ValueError(precision)
    cast = lambda t: jax.tree.map(lambda a: a.astype(dt), t)
    params, adapter = cast(params), cast(adapter)
    if precision == "fp8":
        mm = lambda a, b: jnp.matmul(_fp8(a), _fp8(b), precision=prec)
    else:
        mm = functools.partial(jnp.matmul, precision=prec)
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
        h = _rms(x, lp["norm1"], c.eps, dt)
        q = proj(h, mx["wq"], "q", ad).reshape(T, H, D)
        k = proj(h, mx["wk"], "k", ad).reshape(T, Hk, D)
        v = proj(h, mx["wv"], "v", ad).reshape(T, Hk, D)
        q = _rope(_rms(q, mx["q_norm"], c.eps, dt), c.rope_theta)
        k = _rope(_rms(k, mx["k_norm"], c.eps, dt), c.rope_theta)
        qg = q.reshape(T, Hk, G, D)
        s = jnp.einsum("tkgd,ukd->kgtu", qg, k, precision=prec)
        s = s.astype(jnp.float32) / np.sqrt(D)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(dt)
        o = jnp.einsum("kgtu,ukd->tkgd", p, v, precision=prec).reshape(T, H * D)
        x = x + proj(o, mx["wo"], "o", ad)
        h2 = _rms(x, lp["norm2"], c.eps, dt)
        f = lp["ffn"]
        x = x + mm(jax.nn.silu(mm(h2, f["w_gate"])) * mm(h2, f["w_up"]),
                   f["w_down"])
        return x, None

    x = jnp.take(params["embed"], tokens, axis=0)
    # adapters are stored (L, d_in, r) per target: scan them with the layers
    per_layer = {t: adapter[t] for t in adapter}
    x, _ = jax.lax.scan(layer, x, (params["layers"], per_layer))
    h = _rms(jnp.take(x, at, axis=0), params["final_norm"], c.eps, dt)
    return jnp.matmul(h, params["embed"].T, precision=prec).astype(jnp.float32)


def served_gaps(ref: np.ndarray, served: np.ndarray) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best at its position."""
    return ref.max(axis=-1) - ref[np.arange(len(served)), served]
