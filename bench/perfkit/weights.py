"""What every model's weights share: drawn by the benchmark from ``--seed``,
on the device, in one jitted call each (a model module's ``make_params`` and
``make_adapters``), in the type they are served in.

The benchmark makes the weights and hands them to the program, so the plain
reference can take them from the benchmark and nothing from the program.
The layout is the serving engine's parameter tree (checked against the
engine's own shapes by :func:`check_layout` before it is installed). The
program keeps an RMSNorm gain as ``1 + scale``, so a tree holds ``scale``
and the reference applies ``1 + scale``."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

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


def dense(key, shape, dtype):
    d_in, d_out = shape[-2], shape[-1]
    return (jax.random.normal(key, shape, jnp.float32)
            * (2.0 / (d_in + d_out)) ** 0.5).astype(dtype)


def norm(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * NORM_STD).astype(dtype)


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
