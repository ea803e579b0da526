"""The KV pool's piecewise copies move exactly the rows a plain numpy copy
moves: scatter (dense → pool), swap-out (pool → host), swap-in (host →
pool) and gather (pool → dense), for both pool layouts."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kvcache import KVPoolSpec, PagedKVPool
from repro.kvcache.paged import pieces, piece_sizes

L, BS, SLOTS = 2, 2, 3
N_HBM, N_HOST = 48, 56
T = N_HBM * BS + 8  # a slot holds every pool block past a nonzero start
RANK, ROPE = 6, 2  # MLA: the pool row is latent ‖ k_rope


def _layout(mla: bool):
    if mla:
        spec = KVPoolSpec(L, BS, kv_heads=1, head_dim=RANK + ROPE, use_v=False)
        dense = [(L, SLOTS, T, RANK), (L, SLOTS, T, ROPE)]
    else:
        spec = KVPoolSpec(L, BS, kv_heads=2, head_dim=4)
        dense = [(L, SLOTS, T, 2, 4)] * 2
    return spec, dense


def _pool_rows(dense, slot, start, n):
    """The pool rows of dense tokens [start, start + n·BS) of ``slot``."""
    rows = [d[:, slot, start:start + n * BS] for d in dense]
    if rows[0].ndim == 3:  # MLA: join latent and k_rope into one head
        rows = [np.concatenate(rows, axis=-1)[:, :, None, :]]
    return [r.reshape(L, n, BS, *r.shape[2:]) for r in rows]


def _as_dense(rows, dense_like):
    """Pool rows (L, n, BS, H, D) back in the dense leaves' layout."""
    flat = [r.reshape(L, -1, *r.shape[3:]) for r in rows]
    if dense_like[0].ndim == 4:
        return [flat[0][:, :, 0, :RANK], flat[0][:, :, 0, RANK:]]
    return flat


def _host(pool):
    return [pool.k_host] + ([pool.v_host] if pool.v_host is not None else [])


def _hbm(pool):
    return [pool.k_hbm] + ([pool.v_hbm] if pool.v_hbm is not None else [])


@pytest.mark.parametrize("mla", [False, True], ids=["gqa", "mla"])
@pytest.mark.parametrize("start", [0, 5], ids=["start0", "start5"])
@pytest.mark.parametrize("n", [1, 2, 3, 7, 16, 37, N_HBM])
def test_piecewise_copies_are_exact(n, start, mla):
    spec, dense_shapes = _layout(mla)
    rng = np.random.default_rng(1000 * n + 10 * start + mla)
    pool = PagedKVPool(spec, N_HBM, N_HOST)
    hbm0 = [rng.standard_normal(x.shape).astype(np.float32) for x in _hbm(pool)]
    pool.k_hbm = jnp.asarray(hbm0[0])
    if pool.v_hbm is not None:
        pool.v_hbm = jnp.asarray(hbm0[1])
    for h in _host(pool):
        h[...] = rng.standard_normal(h.shape)
    dense_np = [rng.standard_normal(s).astype(np.float32) for s in dense_shapes]
    dense = tuple(jnp.asarray(d) for d in dense_np)

    # scatter: dense rows of slot 1 from `start` -> n distinct pool blocks
    ids = rng.permutation(N_HBM)[:n]
    pool.scatter(ids.tolist(), dense, 1, start)
    want_hbm = [x.copy() for x in hbm0]
    for w, r in zip(want_hbm, _pool_rows(dense_np, 1, start, n)):
        w[:, ids] = r
    for got, w in zip(_hbm(pool), want_hbm):
        np.testing.assert_array_equal(np.asarray(got), w)

    # swap-out: those blocks -> n host blocks
    host_ids = rng.permutation(N_HOST)[:n]
    want_host = [h.copy() for h in _host(pool)]
    for w, x in zip(want_host, want_hbm):
        w[:, host_ids] = x[:, ids]
    pool.swap_out(ids.tolist(), host_ids.tolist())
    for got, w in zip(_host(pool), want_host):
        np.testing.assert_array_equal(got, w)

    # swap-in: n other host blocks -> n other pool blocks
    src = rng.permutation(N_HOST)[:n]
    dst = rng.permutation(N_HBM)[:n]
    for w, h in zip(want_hbm, want_host):
        w[:, dst] = h[:, src]
    pool.swap_in(src.tolist(), dst.tolist())
    for got, w in zip(_hbm(pool), want_hbm):
        np.testing.assert_array_equal(np.asarray(got), w)

    # gather: n pool blocks -> dense rows of slot 2 from `start`
    gids = rng.permutation(N_HBM)[:n]
    want_dense = [d.copy() for d in dense_np]
    for w, r in zip(want_dense, _as_dense([x[:, gids] for x in want_hbm],
                                          dense_np)):
        w[:, 2, start:start + n * BS] = r
    dense = pool.gather(gids.tolist(), dense, 2, start)
    for got, w in zip(dense, want_dense):
        np.testing.assert_array_equal(np.asarray(got), w)

    # the donated pool buffers were replaced, not lost: it takes more writes
    pool.scatter(ids.tolist(), dense, 2, start)
    for w, r in zip(want_hbm, _pool_rows(want_dense, 2, start, n)):
        w[:, ids] = r
    for got, w in zip(_hbm(pool), want_hbm):
        assert not got.is_deleted()
        np.testing.assert_array_equal(np.asarray(got), w)
    assert pool.swap_out_bytes == pool.swap_in_bytes == n * spec.block_bytes


@pytest.mark.parametrize("n", [0, 1, 6, 37, 128, 255])
def test_pieces_are_the_binary_decomposition(n):
    parts = pieces(n)
    assert [s for _, s in parts] == sorted(
        (1 << k for k in range(n.bit_length()) if n >> k & 1), reverse=True)
    assert [o for o, _ in parts] == [sum(s for _, s in parts[:i])
                                     for i in range(len(parts))]
    assert {s for _, s in parts} <= set(piece_sizes(n))


def test_warm_compiles_each_piece_size_once():
    spec, dense_shapes = _layout(False)
    pool = PagedKVPool(spec, N_HBM, N_HOST)
    dense = tuple(jnp.zeros(s, jnp.float32) for s in dense_shapes)
    dense = pool.warm(dense)
    to_dense, swaps = pool.copy_limits(T // BS)
    bound = 2 * len(piece_sizes(to_dense)) + 2 * len(piece_sizes(swaps))
    assert pool.compile_count() == bound
    for n in range(1, N_HBM + 1):  # every block count, no new program
        ids = list(range(n))
        pool.scatter(ids, dense, 0, 0)
        dense = pool.gather(ids, dense, 1, 3)
        pool.swap_out(ids, ids)
        pool.swap_in(ids, ids)
    assert pool.compile_count() == bound
    assert not np.asarray(pool.k_hbm).any()  # warming wrote zeros


def test_overhanging_dense_span_is_refused():
    spec, dense_shapes = _layout(False)
    pool = PagedKVPool(spec, N_HBM, N_HOST)
    dense = tuple(jnp.zeros(s, jnp.float32) for s in dense_shapes)
    with pytest.raises(ValueError):
        pool.scatter(list(range(5)), dense, 0, T - 4 * BS)
    with pytest.raises(ValueError):
        pool.gather(list(range(5)), dense, 0, T - 4 * BS)
