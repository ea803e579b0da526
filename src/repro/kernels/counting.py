"""Analytic HBM-traffic and FLOP counters for the Pallas kernels.

Interpret mode cannot measure DMA, so the regression harness does not *time*
the bandwidth wins — it *counts* them, by replaying each kernel's exact grid
order and BlockSpec index maps in plain Python and tallying a block fetch
whenever the mapped block index differs from the previous grid step (Pallas
skips the copy when consecutive steps map to the same block — the mechanism
both the length-trimmed clamps and the revisit semantics rely on). The same
walk marks which steps execute compute (the ``pl.when`` guards), giving
analytic FLOPs. ``benchmarks/kernels_bench.py`` emits these counts per shape
and CI asserts the trimmed grids move strictly fewer bytes than their
rectangular/full-grid baselines; see README.md §Kernels.

Everything here is host-side integer arithmetic on static shapes — no jax.
"""

from __future__ import annotations

from typing import Sequence

from .flash_prefill import _tri_schedule


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


class _FetchCounter:
    """Counts block fetches: one per grid step whose index differs from the
    previous step's (consecutive equal indices ⇒ the DMA is skipped)."""

    def __init__(self) -> None:
        self.fetches = 0
        self._prev: object = None

    def visit(self, index: object) -> None:
        if index != self._prev:
            self.fetches += 1
            self._prev = index

    def reset(self) -> None:
        """Forget the resident block (kernel boundary: VMEM does not
        persist across launches)."""
        self._prev = None


def flash_prefill_counts(
    B: int,
    H: int,
    Hkv: int,
    S: int,
    D: int,
    *,
    block_q: int = 128,
    block_k: int = 128,
    itemsize: int = 4,
    true_lens: Sequence[int] | None = None,
    variant: str = "block_skip",
) -> dict:
    """Counted traffic for ``flash_prefill`` / ``flash_prefill_ragged``.

    ``variant="rect"`` replays the historical rectangular grid (above-diagonal
    blocks fetched, compute ``pl.when``-skipped) as the baseline;
    ``"block_skip"`` replays the triangular flattened schedule. Passing
    ``true_lens`` replays the ragged index-map clamps on top.
    """
    G = H // Hkv
    bq = min(block_q, S)
    bk = min(block_k, S)
    qb, kb = _cdiv(S, bq), _cdiv(S, bk)
    if variant == "block_skip":
        rows, cols, _ = _tri_schedule(qb, kb, bq, bk)
        sched = list(zip(rows.tolist(), cols.tolist()))
    elif variant == "rect":
        sched = [(i, j) for i in range(qb) for j in range(kb)]
    else:
        raise ValueError(f"unknown variant {variant!r}")
    lens = list(true_lens) if true_lens is not None else [S] * B
    kv_ctr, q_ctr = _FetchCounter(), _FetchCounter()
    flops = 0
    for b in range(B):
        live_q = max(_cdiv(lens[b], bq), 1)
        live_k = max(_cdiv(lens[b], bk), 1)
        for h in range(H):
            for i, j in sched:
                i_eff = min(i, live_q - 1) if true_lens is not None else i
                j_eff = min(j, live_k - 1) if true_lens is not None else j
                q_ctr.visit((b, h, i_eff))
                kv_ctr.visit((b, h // G, j_eff))
                active = j * bk <= i * bq + bq - 1  # causal intersection
                if true_lens is not None:
                    active = active and i * bq < lens[b] and j * bk < lens[b]
                if active:
                    flops += 4 * bq * bk * D
    kv_bytes = kv_ctr.fetches * bk * D * itemsize * 2  # K and V
    q_bytes = q_ctr.fetches * bq * D * itemsize
    return {
        "grid_steps": B * H * len(sched),
        "kv_block_fetches": kv_ctr.fetches,
        "kv_bytes": kv_bytes,
        "q_bytes": q_bytes,
        "hbm_bytes": kv_bytes + 2 * q_bytes,  # q in, o out
        "flops": flops,
    }


def paged_attention_counts(
    B: int,
    H: int,
    Hkv: int,
    D: int,
    page_size: int,
    pages_per_seq: int,
    lengths: Sequence[int],
    *,
    itemsize: int = 4,
    trimmed: bool = True,
) -> dict:
    """Counted traffic for ``paged_attention``.

    ``trimmed=False`` replays the historical full-grid fetch (every page of
    every sequence streamed, tokens masked after the fact).
    """
    kv_ctr = _FetchCounter()
    flops = 0
    for b in range(B):
        live = max(_cdiv(lengths[b], page_size), 1)
        for p in range(pages_per_seq):
            p_eff = min(p, live - 1) if trimmed else p
            kv_ctr.visit((b, p_eff))  # one page block holds every KV head
            if not trimmed or p * page_size < lengths[b]:
                flops += 4 * H * page_size * D
    kv_bytes = kv_ctr.fetches * page_size * Hkv * D * itemsize * 2
    q_bytes = B * H * D * itemsize
    return {
        "grid_steps": B * pages_per_seq,
        "kv_block_fetches": kv_ctr.fetches,
        "kv_bytes": kv_bytes,
        "q_bytes": q_bytes,
        "hbm_bytes": kv_bytes + 2 * q_bytes,
        "flops": flops,
    }


def ragged_extend_counts(
    B: int,
    H: int,
    Hkv: int,
    S: int,
    T: int,
    D: int,
    start: Sequence[int],
    true_lens: Sequence[int],
    *,
    block_q: int = 128,
    block_k: int = 128,
    itemsize: int = 4,
    trimmed: bool = True,
) -> dict:
    """Counted traffic for ``ragged_extend``.

    ``trimmed=False`` replays the dense baseline (every q block attends every
    cache block of the padded rectangle, masking after the fetch) — what the
    jnp ``sdpa`` path pays.
    """
    bq = min(block_q, S)
    bk = min(block_k, T)
    qb, kb = _cdiv(S, bq), _cdiv(T, bk)
    kv_ctr, q_ctr = _FetchCounter(), _FetchCounter()
    flops = 0
    for b in range(B):
        live_q = max(_cdiv(true_lens[b], bq), 1)
        frontier = max(_cdiv(start[b] + true_lens[b], bk), 1)
        for i in range(qb):
            i_eff = min(i, live_q - 1) if trimmed else i
            diag = (start[b] + i_eff * bq + bq - 1) // bk + 1
            live_k = max(min(frontier, diag), 1)
            for j in range(kb):
                j_eff = min(j, live_k - 1) if trimmed else j
                # q blocks hold every query head, KV blocks every KV head
                q_ctr.visit((b, i_eff))
                kv_ctr.visit((b, j_eff))
                active = (
                    i * bq < true_lens[b]
                    and j * bk < start[b] + true_lens[b]
                    and j * bk <= start[b] + i * bq + bq - 1
                )
                if not trimmed or active:
                    flops += 4 * H * bq * bk * D
    kv_bytes = kv_ctr.fetches * bk * Hkv * D * itemsize * 2
    q_bytes = q_ctr.fetches * bq * H * D * itemsize
    return {
        "grid_steps": B * qb * kb,
        "kv_block_fetches": kv_ctr.fetches,
        "kv_bytes": kv_bytes,
        "q_bytes": q_bytes,
        "hbm_bytes": kv_bytes + 2 * q_bytes,
        "flops": flops,
    }


def sgmv_counts(
    B: int,
    S: int,
    d_in: int,
    d_out: int,
    r: int,
    *,
    block_s: int = 128,
    block_o: int = 128,
    itemsize: int = 4,
    fused: bool = True,
) -> dict:
    """Counted activation traffic for the LoRA projection.

    ``fused=True`` replays ``fused_sgmv`` (one kernel: the x tile is fetched
    once per token tile and read once per (token, out) block);
    ``fused=False`` replays the unfused pair — base matmul kernel plus the
    shrink/expand ``sgmv`` kernel — each streaming the x tile again.
    """
    bs = min(block_s, S)
    bo = min(block_o, d_out)
    sb, ob = _cdiv(S, bs), _cdiv(d_out, bo)
    kernels = 1 if fused else 2  # fused vs (base matmul, sgmv)
    x_ctr = _FetchCounter()
    for _ in range(kernels):
        x_ctr.reset()
        for b in range(B):
            for s in range(sb):
                for o in range(ob):
                    x_ctr.visit((b, s))
    x_bytes = x_ctr.fetches * bs * d_in * itemsize
    flops = 2 * B * S * d_in * d_out + 2 * B * S * r * (d_in + d_out)
    return {
        "grid_steps": kernels * B * sb * ob,
        "x_tile_fetches": x_ctr.fetches,
        "x_passes_per_block": x_ctr.fetches / (B * sb),
        "x_bytes": x_bytes,
        "kernel_launches": kernels,
        "flops": flops,
    }
