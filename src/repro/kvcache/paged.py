"""Physical paged KV store: two-tier (device + host) block arrays.

The control plane (``repro.core.BlockPool``) hands out integer block ids;
this module maps them to rows of physical arrays:

* HBM tier  — jnp arrays ``(L, n_hbm_blocks, block_size, Hkv, D)`` (k and v)
* host tier — numpy arrays ``(L, n_host_blocks, block_size, Hkv, D)``

Running queries use *dense* per-sequence caches (the model's native layout);
the pool is touched at admission (gather prefix blocks → dense) and at
commit (scatter the new suffix → blocks), mirroring the paper's running-KV /
history-KV split (Fig. 14). Swap ops copy rows between tiers (host↔device
transfers — what PCIe does on the paper's platform).

Every copy runs through four jitted programs of a fixed block count
(scatter, gather, swap-out's take, swap-in's put). A copy of n blocks is
split by the binary decomposition of n into pieces of 2^k blocks, largest
first, one program call per piece; the slot, the dense start and the block
ids are traced operands built on the host. So each program compiles once
per power of two up to its largest copy (:meth:`PagedKVPool.warm` does that
at construction), never once per block count, and no padded row is moved.
The written buffers are donated: the pool's for scatter and swap-in, the
dense leaves' for gather.

MLA archs store (latent ‖ k_rope) in the k array with Hkv=1 and
D = kv_lora_rank + rope_dim (v array unused; the dense side keeps latent and
k_rope apart and the programs split or join them); SSM archs use
``state_cache.StateCache`` instead.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Array = jax.Array


@dataclasses.dataclass
class KVPoolSpec:
    num_layers: int
    block_size: int  # tokens per block
    kv_heads: int
    head_dim: int
    dtype: jnp.dtype = jnp.float32
    use_v: bool = True  # False for MLA (latent-only)

    @property
    def bytes_per_token(self) -> int:
        per = self.num_layers * self.kv_heads * self.head_dim
        per *= 2 if self.use_v else 1
        return per * jnp.dtype(self.dtype).itemsize

    @property
    def block_bytes(self) -> int:
        return self.bytes_per_token * self.block_size

    @property
    def dense_keys(self) -> tuple[str, str]:
        """The dense running cache's leaves the pool copies to and from:
        ``(L, B, T, Hkv, D)`` k and v, or MLA's ``(L, B, T, rank)`` latent
        and ``(L, B, T, rope)`` k_rope."""
        return ("k", "v") if self.use_v else ("latent", "krope")


def pieces(n: int) -> list[tuple[int, int]]:
    """``(offset, size)`` in blocks of each piece of an n-block copy: the
    binary decomposition of n, largest first."""
    out, off = [], 0
    for k in reversed(range(n.bit_length())):
        if n >> k & 1:
            out.append((off, 1 << k))
            off += 1 << k
    return out


def piece_sizes(limit: int) -> list[int]:
    """Every piece size a copy of at most ``limit`` blocks can use."""
    return [1 << k for k in range(limit.bit_length())]


# ---------------------------------------------------------------- programs
# ``hbm`` is ``(k,)`` or ``(k, v)``; ``dense`` the two leaves of
# KVPoolSpec.dense_keys. The piece size is ``ids.shape[0]``; every other
# operand's shape is fixed by the engine, so the size alone keys a program.


def _dense_rows(dense, slot, start, ntok):
    """Dense rows ``[start, start + ntok)`` of ``slot`` as (L, ntok, H, D)."""
    a, b = dense
    if a.ndim == 5:  # k, v
        return tuple(
            lax.dynamic_slice(x, (0, slot, start, 0, 0),
                              (x.shape[0], 1, ntok) + x.shape[3:])[:, 0]
            for x in (a, b))
    # MLA: pool row == concat(latent, krope) as one pseudo-head
    lat, rope = (lax.dynamic_slice(x, (0, slot, start, 0),
                                   (x.shape[0], 1, ntok, x.shape[3]))[:, 0]
                 for x in (a, b))
    return (jnp.concatenate([lat, rope], axis=-1)[:, :, None, :],)


def _put_dense(dense, slot, start, rows):
    """Write (L, ntok, H, D) rows into ``dense`` at ``(slot, start)``."""
    a, b = dense
    if a.ndim == 5:
        return tuple(
            lax.dynamic_update_slice(x, r[:, None].astype(x.dtype),
                                     (0, slot, start, 0, 0))
            for x, r in zip(dense, rows))
    r = a.shape[3]
    k = rows[0][:, :, 0]
    return tuple(
        lax.dynamic_update_slice(x, part[:, None].astype(x.dtype),
                                 (0, slot, start, 0))
        for x, part in ((a, k[..., :r]), (b, k[..., r:])))


def _scatter(hbm, dense, ids, slot, start):
    """Dense rows of ``slot`` from ``start`` → pool blocks ``ids``."""
    n = ids.shape[0]
    L, _, bs, H, D = hbm[0].shape
    rows = _dense_rows(dense, slot, start, n * bs)
    return tuple(x.at[:, ids].set(r.reshape(L, n, bs, H, D).astype(x.dtype))
                 for x, r in zip(hbm, rows))


def _gather(dense, hbm, ids, slot, start):
    """Pool blocks ``ids`` → dense rows of ``slot`` from ``start``."""
    rows = []
    for x in hbm:
        L, _, bs, H, D = x.shape
        rows.append(jnp.take(x, ids, axis=1).reshape(L, -1, H, D))
    return _put_dense(dense, slot, start, rows)


def _take(hbm, ids):
    return tuple(jnp.take(x, ids, axis=1) for x in hbm)


def _put(hbm, rows, ids):
    return tuple(x.at[:, ids].set(r.astype(x.dtype)) for x, r in zip(hbm, rows))


def _ids(block_ids) -> np.ndarray:
    return np.asarray(block_ids, np.int32).reshape(-1)


def _check_span(dense, start: int, n_tokens: int) -> None:
    # lax.dynamic_slice would clamp an overhanging start silently
    if not 0 <= start <= dense[0].shape[2] - n_tokens:
        raise ValueError(f"rows [{start}, {start + n_tokens}) do not fit a "
                         f"dense cache of {dense[0].shape[2]} tokens")


def _layout(arrays) -> tuple:
    return tuple((a.shape, str(a.dtype)) for a in arrays)


@functools.cache
def _programs(kind: str, layout: tuple) -> tuple:
    """The jitted programs of one operand layout: scatter and gather for
    ``kind == "dense"``, take and put for ``"swap"``. Pools of one layout
    share them, so an engine built twice compiles once; a layout's own jit
    objects make ``jit_cache_size`` count that layout's programs alone."""
    fns = (_scatter, _gather) if kind == "dense" else (_take, _put)
    donate = (0, 0) if kind == "dense" else ((), 0)
    # partial: a function object of this layout's own, as jit caches by it
    return tuple(jax.jit(functools.partial(f), donate_argnums=d)
                 for f, d in zip(fns, donate))


class PagedKVPool:
    """Two-tier physical KV block store."""

    def __init__(self, spec: KVPoolSpec, n_hbm_blocks: int, n_host_blocks: int):
        self.spec = spec
        s = spec
        shape_hbm = (s.num_layers, n_hbm_blocks, s.block_size, s.kv_heads, s.head_dim)
        shape_host = (s.num_layers, n_host_blocks, s.block_size, s.kv_heads, s.head_dim)
        self.k_hbm = jnp.zeros(shape_hbm, s.dtype)
        self.v_hbm = jnp.zeros(shape_hbm, s.dtype) if s.use_v else None
        self.k_host = np.zeros(shape_host, s.dtype)
        self.v_host = np.zeros(shape_host, s.dtype) if s.use_v else None
        self.n_hbm_blocks = n_hbm_blocks
        self.n_host_blocks = n_host_blocks
        self.swap_in_bytes = 0
        self.swap_out_bytes = 0
        self._take_fn, self._put_fn = _programs(
            "swap", (_layout(self._hbm), n_host_blocks))
        self._dense_layouts: set[tuple] = set()

    @property
    def _hbm(self) -> tuple[Array, ...]:
        return (self.k_hbm,) if self.v_hbm is None else (self.k_hbm, self.v_hbm)

    def _set_hbm(self, hbm: tuple[Array, ...]) -> None:
        self.k_hbm = hbm[0]
        if self.v_hbm is not None:
            self.v_hbm = hbm[1]

    @property
    def _host(self) -> tuple[np.ndarray, ...]:
        return (self.k_host,) if self.v_host is None else (self.k_host, self.v_host)

    def _dense_programs(self, dense) -> tuple:
        key = (_layout(self._hbm), _layout(dense))
        self._dense_layouts.add(key)
        return _programs("dense", key)

    # ------------------------------------------------------ dense <-> pool
    def scatter(self, block_ids: Sequence[int], dense: tuple[Array, Array],
                slot: int, start: int) -> None:
        """Dense rows ``[start, start + n·block_size)`` of ``slot`` → HBM
        blocks ``block_ids`` (n of them). ``dense`` is read, not consumed."""
        ids = _ids(block_ids)
        bs = self.spec.block_size
        _check_span(dense, start, len(ids) * bs)
        scatter_fn, _ = self._dense_programs(dense)
        for off, size in pieces(len(ids)):
            self._set_hbm(scatter_fn(
                self._hbm, dense, ids[off:off + size], np.int32(slot),
                np.int32(start + off * bs)))

    def gather(self, block_ids: Sequence[int], dense: tuple[Array, Array],
               slot: int, start: int) -> tuple[Array, Array]:
        """HBM blocks → dense rows of ``slot`` from ``start``. ``dense`` is
        donated: use the returned leaves in its place."""
        ids = _ids(block_ids)
        bs = self.spec.block_size
        _check_span(dense, start, len(ids) * bs)
        _, gather_fn = self._dense_programs(dense)
        for off, size in pieces(len(ids)):
            dense = gather_fn(
                dense, self._hbm, ids[off:off + size], np.int32(slot),
                np.int32(start + off * bs))
        return dense

    # --------------------------------------------------------------- swaps
    def swap_out(self, hbm_blocks: Sequence[int], host_blocks: Sequence[int]) -> None:
        """Copy HBM rows to host rows (device→host transfer). Every piece
        is enqueued before the first one is waited for."""
        src, dst = _ids(hbm_blocks), _ids(host_blocks)
        parts = pieces(len(src))
        taken = [self._take_fn(self._hbm, src[off:off + size])
                 for off, size in parts]
        for (off, size), rows in zip(parts, taken):
            for host, r in zip(self._host, rows):
                r = np.asarray(r)
                host[:, dst[off:off + size]] = r
                self.swap_out_bytes += r.nbytes

    def swap_in(self, host_blocks: Sequence[int], hbm_blocks: Sequence[int]) -> None:
        """Copy host rows to HBM rows (host→device transfer)."""
        src, dst = _ids(host_blocks), _ids(hbm_blocks)
        for off, size in pieces(len(src)):
            # on the device before the call, as warm()'s rows are: a numpy
            # operand would key a second program of the same shape
            rows = tuple(jax.device_put(h[:, src[off:off + size]])
                         for h in self._host)
            self._set_hbm(self._put_fn(self._hbm, rows, dst[off:off + size]))
            self.swap_in_bytes += sum(r.nbytes for r in rows)

    # ------------------------------------------------------------ programs
    def copy_limits(self, dense_blocks: int) -> tuple[int, int]:
        """Largest copy in blocks: between the dense cache and the pool (a
        slot holds ``dense_blocks``), and between the tiers (a swapped node
        fits in both)."""
        return (min(dense_blocks, self.n_hbm_blocks),
                min(self.n_hbm_blocks, self.n_host_blocks))

    def warm(self, dense: tuple[Array, Array]) -> tuple[Array, Array]:
        """Compile every piece size of the four programs now, on the pool's
        and the dense cache's own buffers, which must still be all zeros:
        each call copies zeros onto zeros. ``dense`` is donated: use the
        returned leaves in its place."""
        dense_max, swap_max = self.copy_limits(
            dense[0].shape[2] // self.spec.block_size)
        scatter_fn, gather_fn = self._dense_programs(dense)
        zero = np.int32(0)
        for size in piece_sizes(dense_max):
            ids = np.arange(size, dtype=np.int32)
            self._set_hbm(scatter_fn(self._hbm, dense, ids, zero, zero))
            dense = gather_fn(dense, self._hbm, ids, zero, zero)
        for size in piece_sizes(swap_max):
            ids = np.arange(size, dtype=np.int32)
            rows = self._take_fn(self._hbm, ids)
            self._set_hbm(self._put_fn(self._hbm, rows, ids))
        return dense

    def compile_count(self) -> int:
        """Programs compiled for this pool's layouts by the four copy
        programs (shared with any pool of the same layouts)."""
        from repro.core import jit_cache_size

        fns = [self._take_fn, self._put_fn]
        for key in self._dense_layouts:
            fns.extend(_programs("dense", key))
        return sum(jit_cache_size(f) for f in fns)
