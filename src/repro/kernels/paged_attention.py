"""Paged decode attention as a Pallas TPU kernel.

vLLM's CUDA paged attention gathers KV pages with per-warp loads. The TPU
adaptation (README.md §Kernels) keeps the KV pool as dense
``(num_pages, page_size, Hkv, D)`` arrays in HBM and streams one page per
grid step into VMEM, with the page indirection performed by the **scalar-
prefetched block table inside the BlockSpec index map** — the TPU-idiomatic
replacement for pointer-chasing. Softmax is computed online (flash-style
running max / sum in VMEM scratch) across the page-grid dimension, which is
sequential on TPU, so the accumulator carries across pages of one sequence.

Each page block holds every KV head, ``(1, page_size, Hkv, D)``, and the q/o
blocks every query head, ``(1, H, D)``: the last two block dims equal the
array's, the shape Mosaic accepts (a head block of 1 in the second-minor
position is refused). The kernel loops over KV heads inside, attending each
head's ``G = H // Hkv`` query rows, so a page is fetched once per step.

Length trimming: the prefetched ``lens`` clamp the page index map to each
sequence's last live page — Pallas skips the DMA when consecutive grid steps
map to the same block, so pages past ``ceil(lens[b]/page_size)`` cost no
bandwidth — and the accumulate is ``pl.when``-guarded on the same bound so
they cost no MXU work either. Rows with ``lens[b] == 0`` (inactive slots in
a row-masked mixed batch) produce exact zeros: masked probabilities are
zeroed before they reach the accumulator, so ``l`` stays 0 and the finalize
guard divides 0/1, not the historical ``exp(NEG_INF - NEG_INF) = 1`` path
that silently emitted ``mean(V)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NEG_INF = -1e30


def _paged_attn_kernel(
    tables_ref,  # scalar prefetch: (B, pages_per_seq) int32
    lens_ref,  # scalar prefetch: (B,) int32
    q_ref,  # (1, H, D)
    k_ref,  # (1, page, Hkv, D)
    v_ref,  # (1, page, Hkv, D)
    o_ref,  # (1, H, D)
    acc_ref,  # VMEM scratch (Hkv, G, D) f32
    m_ref,  # VMEM scratch (Hkv, G, 1) f32
    l_ref,  # VMEM scratch (Hkv, G, 1) f32
    *,
    page_size: int,
    pages_per_seq: int,
):
    b = pl.program_id(0)
    p = pl.program_id(1)
    Hkv, G = acc_ref.shape[0], acc_ref.shape[1]

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Pages at or past the sequence length are skipped outright (their DMA
    # was already suppressed by the clamped index map below).
    @pl.when(p * page_size < lens_ref[b])
    def _attend():
        D = q_ref.shape[-1]
        # mask tokens beyond the sequence length
        token_idx = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1
        )
        valid = token_idx < lens_ref[b]
        for h in range(Hkv):
            q = q_ref[0, h * G:(h + 1) * G, :].astype(jnp.float32)  # (G, D)
            k = k_ref[0, :, h, :].astype(jnp.float32)  # (page, D)
            v = v_ref[0, :, h, :].astype(jnp.float32)  # (page, D)
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) / jnp.sqrt(
                jnp.float32(D)
            )  # (G, page)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[h]  # (G, 1)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            # zero masked probabilities explicitly: when every score in the
            # page is NEG_INF, exp(s - m) is 1, not 0 — without this, a row
            # whose length is 0 averages V instead of emitting zeros
            p_ij = jnp.where(valid, jnp.exp(s - m_cur), 0.0)  # (G, page)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p_ij, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p_ij, v, preferred_element_type=jnp.float32
            )
            m_ref[h] = m_cur

    @pl.when(p == pages_per_seq - 1)
    def _finalize():
        for h in range(Hkv):
            o_ref[0, h * G:(h + 1) * G, :] = (
                acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
            ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(
    q: Array,  # (B, H, D)
    k_pages: Array,  # (P, page_size, Hkv, D)
    v_pages: Array,  # (P, page_size, Hkv, D)
    block_tables: Array,  # (B, pages_per_seq) int32
    lengths: Array,  # (B,) int32
    *,
    interpret: bool = False,
) -> Array:
    B, H, D = q.shape
    P, page_size, Hkv, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]
    G = H // Hkv
    grid = (B, pages_per_seq)

    def _kv_map(b, p, t, l):
        # clamp to the last live page: steps past it revisit the same block,
        # and a revisited block is not re-fetched
        live = jnp.maximum((l[b] + page_size - 1) // page_size, 1)
        return (t[b, jnp.minimum(p, live - 1)], 0, 0, 0)

    return pl.pallas_call(
        functools.partial(
            _paged_attn_kernel, page_size=page_size, pages_per_seq=pages_per_seq
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, H, D), lambda b, p, t, l: (b, 0, 0)),
                pl.BlockSpec((1, page_size, Hkv, D), _kv_map),
                pl.BlockSpec((1, page_size, Hkv, D), _kv_map),
            ],
            out_specs=pl.BlockSpec((1, H, D), lambda b, p, t, l: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Hkv, G, D), jnp.float32),
                pltpu.VMEM((Hkv, G, 1), jnp.float32),
                pltpu.VMEM((Hkv, G, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
    )(block_tables, lengths, q, k_pages, v_pages)
