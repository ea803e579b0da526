"""Weights drawn by the benchmark from ``--seed``, on the device, in one
jitted call each, in the type they are served in.

The benchmark makes the weights and hands them to the program, so the plain
reference can take them from the benchmark and nothing from the program.
The layout is the serving engine's parameter tree (checked against the
engine's own shapes before it is installed); the values follow the
published architecture: RMSNorm gains, q/k RMSNorm per head, tied
embeddings. The program keeps an RMSNorm gain as ``1 + scale``, so the tree
holds ``scale`` and the reference applies ``1 + scale``."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .work import Shape

# weight scales: a dense matrix draws N(0, 2 / (d_in + d_out)); embeddings
# N(0, 0.02^2); norm gains 1 + N(0, 0.1^2); adapters A ~ N(0, 1/d_in) and
# B ~ N(0, LORA_B_STD^2), strong enough that serving the wrong adapter moves
# the logits by far more than rounding does
EMBED_STD = 0.02
NORM_STD = 0.1
LORA_B_STD = 0.05


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (the driver's seeds exceed 32
    bits)."""
    s = np.random.SeedSequence(seed % (1 << 64)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(s[0])), int(s[1]))


def _dense(key, shape, dtype):
    d_in, d_out = shape[-2], shape[-1]
    return (jax.random.normal(key, shape, jnp.float32)
            * (2.0 / (d_in + d_out)) ** 0.5).astype(dtype)


def _norm(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * NORM_STD).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def make_params(key: jax.Array, s: Shape, dtype=jnp.float32) -> dict:
    L, d, hd = s.layers, s.d_model, s.head_dim
    k = iter(jax.random.split(key, 16))
    return {
        "embed": (jax.random.normal(next(k), (s.vocab, d), jnp.float32)
                  * EMBED_STD).astype(dtype),
        "final_norm": _norm(next(k), (d,), dtype),
        "layers": {
            "norm1": _norm(next(k), (L, d), dtype),
            "norm2": _norm(next(k), (L, d), dtype),
            "mixer": {
                "wq": _dense(next(k), (L, d, s.heads * hd), dtype),
                "wk": _dense(next(k), (L, d, s.kv_heads * hd), dtype),
                "wv": _dense(next(k), (L, d, s.kv_heads * hd), dtype),
                "wo": _dense(next(k), (L, s.heads * hd, d), dtype),
                "q_norm": _norm(next(k), (L, hd), dtype),
                "k_norm": _norm(next(k), (L, hd), dtype),
            },
            "ffn": {
                "w_gate": _dense(next(k), (L, d, s.d_ff), dtype),
                "w_up": _dense(next(k), (L, d, s.d_ff), dtype),
                "w_down": _dense(next(k), (L, s.d_ff, d), dtype),
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
                              jnp.float32) * LORA_B_STD
        out[t] = (a.astype(dtype), b.astype(dtype))
    return out


def check_layout(ours, theirs) -> None:
    """Raise unless two parameter trees agree leaf for leaf in structure,
    shape and dtype."""
    a = jax.tree_util.tree_structure(ours)
    b = jax.tree_util.tree_structure(theirs)
    if a != b:
        raise ValueError(f"parameter tree differs from the engine's:\n{a}\n{b}")
    for x, y in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        if x.shape != y.shape or x.dtype != y.dtype:
            raise ValueError(f"leaf {x.shape}/{x.dtype} vs {y.shape}/{y.dtype}")
