"""Ragged extend attention — the serving hot loop's kernel.

The engine's one-true-step (`models.attention.gqa_cached`) attends a batch of
fresh suffix chunks against the dense KV cache: row ``b`` holds
``true_lens[b]`` live query tokens (mixed batches pack decode riders with
``true_lens == 1`` next to prefill chunks; bucket padding brings every row to
the same ``S``) whose absolute positions start at ``start[b]``, and the keys
are cache positions ``0 .. start[b] + true_lens[b] - 1``. This kernel computes
exactly that — causal flash attention with a per-row key frontier — directly
on the engine's native layouts (``q (B, S, Hq, D)``, cache ``(B, T, Hkv, D)``,
no transposes).

Blocks hold every head: ``(1, bq, Hq, D)`` for q/o and ``(1, bk, Hkv, D)`` for
K/V, so the last two block dims equal the array's — the shape Mosaic accepts
(a head block of 1 in the second-minor position is refused). The kernel loops
over heads inside; each KV block is fetched once for all query heads.

Trimming (README.md §Kernels): ``start`` and ``true_lens`` are scalar-
prefetched. KV blocks past a row's frontier — beyond
``ceil((start+true_lens)/block_k)`` or above the causal diagonal of its
query block — clamp their index map to the last live block, and Pallas skips
the DMA when consecutive grid steps map to the same block; query blocks past
``ceil(true_lens/block_q)`` clamp the same way. Compute for trimmed blocks is
``pl.when``-guarded, so a decode rider in a padded bucket costs one q block ×
its live KV prefix, not ``S/bq × T/bk`` rectangles. Rows with
``true_lens[b] == 0`` (inactive slots) emit exact zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NEG_INF = -1e30


def _ragged_extend_kernel(
    start_ref,  # scalar prefetch: (B,) int32 — first absolute q position
    lens_ref,  # scalar prefetch: (B,) int32 — live q tokens per row
    q_ref,  # (1, bq, H, D)
    k_ref,  # (1, bk, Hkv, D)
    v_ref,  # (1, bk, Hkv, D)
    o_ref,  # (1, bq, H, D)
    acc_ref,  # (H, bq, D) f32
    m_ref,  # (H, bq, 1) f32
    l_ref,  # (H, bq, 1) f32
    *,
    block_q: int,
    block_k: int,
    kv_blocks: int,
):
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    start = start_ref[b]
    n_new = lens_ref[b]
    limit = start + n_new  # first invalid absolute key position
    H = q_ref.shape[2]
    G = H // k_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # skip: padded q blocks, kv blocks past the row frontier, and kv blocks
    # fully above this q block's causal diagonal
    active = (
        (i * block_q < n_new)
        & (j * block_k < limit)
        & (j * block_k <= start + i * block_q + block_q - 1)
    )

    @pl.when(active)
    def _attend():
        D = q_ref.shape[-1]
        q_pos = (
            start
            + i * block_q
            + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        )
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1
        )
        # the q_pos < limit bound fully masks padded query rows, so they
        # emit exact zeros rather than attending the row's live prefix
        mask = (k_pos <= q_pos) & (k_pos < limit) & (q_pos < limit)
        # zero V rows past the frontier: when T is not a block multiple the
        # out-of-bounds tail reads back garbage and 0·garbage is not 0
        v_live = k_pos.T < limit
        for h in range(H):
            q = q_ref[0, :, h, :].astype(jnp.float32)  # (bq, D)
            k = k_ref[0, :, h // G, :].astype(jnp.float32)  # (bk, D)
            v = v_ref[0, :, h // G, :].astype(jnp.float32)  # (bk, D)
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) / jnp.sqrt(
                jnp.float32(D)
            )
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[h]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            # zero masked probabilities so fully-masked rows keep l == 0
            p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p, jnp.where(v_live, v, 0.0),
                preferred_element_type=jnp.float32,
            )
            m_ref[h] = m_cur

    @pl.when(j == kv_blocks - 1)
    def _finalize():
        # rows that never accumulated (padding / inactive) come out zero
        for h in range(H):
            o_ref[0, :, h, :] = (
                acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
            ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "interpret")
)
def ragged_extend(
    q: Array,  # (B, S, Hq, D) — padded suffix chunks
    k: Array,  # (B, T, Hkv, D) — dense KV cache (new rows already written)
    v: Array,  # (B, T, Hkv, D)
    start: Array,  # (B,) int32 — cache length before this chunk
    true_lens: Array,  # (B,) int32 — live tokens in each row (may be 0)
    *,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> Array:
    """Causal suffix attention against the cache with per-row trimming.

    Row ``b``'s query token ``s`` (for ``s < true_lens[b]``) attends cache
    positions ``0 .. start[b] + s``. Padded query positions — including whole
    rows with ``true_lens[b] == 0`` — return exact zeros.
    """
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    bq = min(block_q, S)
    bk = min(block_k, T)
    kv_blocks = pl.cdiv(T, bk)
    grid = (B, pl.cdiv(S, bq), kv_blocks)

    def _q_map(b, i, j, st, ln):
        live = jnp.maximum((ln[b] + bq - 1) // bq, 1)
        return (b, jnp.minimum(i, live - 1), 0, 0)

    def _kv_map(b, i, j, st, ln):
        q_live = jnp.maximum((ln[b] + bq - 1) // bq, 1)
        i_eff = jnp.minimum(i, q_live - 1)
        # last block any query of this row may see: min(row frontier,
        # this q block's causal diagonal)
        frontier = jnp.maximum((st[b] + ln[b] + bk - 1) // bk, 1)
        diag = (st[b] + i_eff * bq + bq - 1) // bk + 1
        live = jnp.maximum(jnp.minimum(frontier, diag), 1)
        return (b, jnp.minimum(j, live - 1), 0, 0)

    out = pl.pallas_call(
        functools.partial(
            _ragged_extend_kernel, block_q=bq, block_k=bk, kv_blocks=kv_blocks
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, H, D), _q_map),
                pl.BlockSpec((1, bk, Hkv, D), _kv_map),
                pl.BlockSpec((1, bk, Hkv, D), _kv_map),
            ],
            out_specs=pl.BlockSpec(
                (1, bq, H, D), lambda b, i, j, st, ln: (b, i, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((H, bq, D), jnp.float32),
                pltpu.VMEM((H, bq, 1), jnp.float32),
                pltpu.VMEM((H, bq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, S, H, D), q.dtype),
        interpret=interpret,
    )(start.astype(jnp.int32), true_lens.astype(jnp.int32), q, k, v)
    return out
