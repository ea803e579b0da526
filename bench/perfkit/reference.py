"""What every model's plain reference shares (``bench/models/``): its
numerics, written in straightforward ``jax.numpy`` and imported from nothing
of the program.

``precision="highest"`` is the oracle (float32, every matmul at full
precision). The lower precisions are controls, each the step below a stated
precision: ``"bf16"`` keeps weights and activations in bfloat16 with
matmuls at the default precision, the way a bf16 server runs; ``"fp8"``
also rounds every matmul operand to float8 (e4m3, one scale per tensor), the
way an fp8 server runs. Norms and softmax are taken in float32 and cast
back, as such servers do. An RMSNorm gain is ``1 + scale`` (see
weights.py)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def numerics(precision: str):
    """(dtype, matmul precision, matmul) of a reference precision."""
    if precision == "highest":
        dt, prec = jnp.float32, jax.lax.Precision.HIGHEST
    elif precision in ("bf16", "fp8"):
        dt, prec = jnp.bfloat16, jax.lax.Precision.DEFAULT
    else:
        raise ValueError(precision)
    if precision == "fp8":
        mm = lambda a, b: jnp.matmul(fp8(a), fp8(b), precision=prec)
    else:
        mm = functools.partial(jnp.matmul, precision=prec)
    return dt, prec, mm


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor, back in x's type."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32))) / 448.0 + 1e-30
    q = (x.astype(jnp.float32) / s).astype(jnp.float8_e4m3fn)
    return (q.astype(jnp.float32) * s).astype(x.dtype)


def rms(x, scale, eps, dtype):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def rope(x, theta):
    # x: (T, H, D); rotate-half convention
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., : D // 2], x32[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1).astype(x.dtype)


def served_gaps(ref: np.ndarray, served: np.ndarray) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best at its position."""
    return ref.max(axis=-1) - ref[np.arange(len(served)), served]
