"""Bucketed, jit-cached, multi-request batch prefill.

The TTFT hot path. The eager seed path ran ``model.extend`` once per
admitted request with the request's *exact* suffix length — every distinct
prompt length was a fresh XLA compile, and each call dispatched unfused ops
over all batch slots to advance one row. This module replaces it with:

* **length buckets** — each suffix chunk is padded to a small set of
  power-of-two lengths, so XLA compiles at most ``len(buckets)`` distinct
  shapes no matter how many prompt lengths the trace contains;
* **request coalescing** — every request admitted in the same engine step
  rides in ONE batched ``extend`` call, with per-row ``adapter_ids``
  batching heterogeneous LoRAs through the SGMV path;
* **chunking** — suffixes longer than ``chunk`` are fed ``chunk`` tokens per
  engine step, interleaved with decode, so a 2k-token prompt cannot hold the
  decode loop hostage (chunked prefill a la Sarathi/InfiniLoRA's
  prefill–decode disaggregation, on a single engine).

Correctness relies on the models' row-masked extend (``true_lens``): pad
positions neither write KV/recurrent state nor advance ``len``, and each
row's next-token logits are gathered at its own last *real* position.

:func:`assemble_batch` additionally accepts decode rows (``true_lens == 1``)
so the Sarathi-style mixed scheduler (serving/scheduler.py) can pack prefill
chunks and decode tokens into ONE batched ``extend`` per engine step.

Adapter-id contract: ``adapter_ids`` is per ROW per dispatch. A NEGATIVE id
marks a base-model row — the SGMV delta is masked to zero, which is how the
engine computes a request's declared adapter-independent shared prefix
(cross-adapter KV sharing). A chunk therefore may never straddle the shared
boundary; the engine clamps chunks to land on it (``_clamp_shared_chunks``).
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def make_buckets(min_bucket: int, chunk: int) -> tuple[int, ...]:
    """Power-of-two bucket lengths from ``min_bucket`` up to ``chunk``
    (``chunk`` itself is always the last bucket)."""
    if min_bucket < 1 or chunk < 1:
        raise ValueError("min_bucket and chunk must be >= 1")
    if min_bucket > chunk:
        min_bucket = chunk
    buckets = []
    b = max(1, min_bucket)
    while b < chunk:
        buckets.append(b)
        b *= 2
    buckets.append(chunk)
    return tuple(sorted(set(buckets)))


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= n (n <= 0 maps to the smallest bucket)."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"suffix chunk of {n} tokens exceeds the largest "
                     f"bucket {buckets[-1]} — chunk before bucketing")


def assemble_batch(
    n_slots: int,
    bucket: int,
    prefill_chunks: Mapping[int, Sequence[int]],
    decode_tokens: Mapping[int, int] | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Assemble one (possibly mixed) row-masked batch.

    ``prefill_chunks`` maps slot -> that row's suffix token slice for this
    step; ``decode_tokens`` maps slot -> the row's last generated token —
    decode rows ride in the same batch as 1-token rows (``true_lens == 1``),
    which is what makes Sarathi-style mixed scheduling a pure batch-assembly
    concern: the row-masked ``extend`` already handles heterogeneous per-row
    lengths. Returns (tokens (B, bucket) int32, true_lens (B,), row_mask (B,)).
    """
    tokens = np.zeros((n_slots, bucket), np.int32)
    true_lens = np.zeros((n_slots,), np.int32)
    row_mask = np.zeros((n_slots,), bool)
    for slot, toks in prefill_chunks.items():
        c = len(toks)
        if c > bucket:
            raise ValueError(f"chunk of {c} tokens exceeds bucket {bucket}")
        tokens[slot, :c] = toks
        true_lens[slot] = c
        row_mask[slot] = True
    for slot, tok in (decode_tokens or {}).items():
        if row_mask[slot]:
            raise ValueError(f"slot {slot} is both prefilling and decoding")
        tokens[slot, 0] = tok
        true_lens[slot] = 1
        row_mask[slot] = True
    return tokens, true_lens, row_mask


@dataclasses.dataclass
class PrefillStats:
    calls: int = 0          # batched prefill dispatches
    rows: int = 0           # request-chunks processed across all calls
    tokens: int = 0         # real (unpadded) suffix tokens processed
    pad_tokens: int = 0     # bucket-padding overhead tokens

    @property
    def mean_batch(self) -> float:
        return self.rows / self.calls if self.calls else 0.0


class BatchPrefill:
    """Per-bucket jit cache around row-masked ``model.extend``.

    One compiled executable per bucket length serves every (suffix-length,
    active-row-set) combination: tokens are padded to the bucket, rows not
    prefilling this step are masked out, and the merged cache keeps their
    contents bit-identical.
    """

    def __init__(self, model, buckets: Sequence[int]):
        self.model = model
        self.buckets = tuple(sorted(buckets))
        self._fns: dict[int, object] = {}
        self.stats = PrefillStats()

    # ------------------------------------------------------------- bucketing
    def bucket_for(self, n: int) -> int:
        return bucket_for(n, self.buckets)

    # --------------------------------------------------------- compile probe
    @property
    def compile_count(self) -> int:
        """Number of distinct lowered shapes across all bucket functions
        (jit tracing-cache probe) — the test invariant is
        ``compile_count <= len(buckets)``."""
        total = 0
        for fn in self._fns.values():
            size = getattr(fn, "_cache_size", None)
            total += size() if callable(size) else 1
        return total

    # ------------------------------------------------------------- execution
    def step_fn(self, bucket: int):
        """The jitted mixed step for ``bucket`` (built on first use): the
        program every prefill chunk and mixed batch of that length runs."""
        if bucket not in self._fns:
            if bucket not in self.buckets:
                raise ValueError(f"tokens padded to {bucket}, not a "
                                 f"configured bucket {self.buckets}")
            self._fns[bucket] = self._build(bucket)
        return self._fns[bucket]

    def _build(self, bucket: int):
        model = self.model

        def step(params, lora, cache, tokens, start, true_lens, row_mask,
                 adapter_ids):
            logits, new_cache = model.extend(
                params, cache, tokens, start, lora=lora,
                adapter_ids=adapter_ids, all_logits=True, true_lens=true_lens,
            )
            B = tokens.shape[0]
            # keyed row-merge: 'len' is (B,), every other cache leaf is
            # layer-stacked (L, B, …) — never guess the batch axis by shape
            # (L == B would silently merge along layers)
            merged = {}
            for key, new in new_cache.items():
                if key == "len":
                    m = row_mask
                else:
                    m = row_mask.reshape((1, B) + (1,) * (new.ndim - 2))
                merged[key] = jnp.where(m, new, cache[key])
            # each row's next-token logits live at its own last real position
            idx = jnp.maximum(true_lens - 1, 0)
            last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0, :]
            return last, merged

        return jax.jit(step)

    def __call__(self, params, lora, cache, tokens, start, true_lens,
                 row_mask, adapter_ids, stat_mask=None):
        """Run one coalesced (possibly mixed) chunk.

        tokens: (B, bucket) int32 — pad with any token id beyond true_lens
        start: (B,) current cache lengths; true_lens: (B,) real chunk tokens
        (0 for rows riding along); row_mask: (B,) bool participating rows.
        ``stat_mask`` (B,) bool restricts PrefillStats accounting to the
        actual prefill chunk rows — mixed batches carry decode rider rows
        (true_lens == 1) that must not inflate avg_prefill_batch or count
        their bucket padding as prefill overhead. Defaults to ``row_mask``.
        Returns (per-row last-real-token logits (B, V), merged cache).
        """
        bucket = int(tokens.shape[1])
        fn = self.step_fn(bucket)
        sm = row_mask if stat_mask is None else stat_mask
        sm = np.asarray(sm)
        real = int(np.asarray(true_lens)[sm].sum())
        nrows = int(sm.sum())
        self.stats.calls += 1
        self.stats.rows += nrows
        self.stats.tokens += real
        self.stats.pad_tokens += nrows * bucket - real
        return fn(params, lora, cache, tokens, start, true_lens, row_mask,
                  adapter_ids)
