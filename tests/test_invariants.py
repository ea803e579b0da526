"""libra-check runtime sanitizer tests.

Three layers:
1. detection — deliberately corrupt a live manager in every way the
   sanitizer claims to catch, and assert the sweep raises a structured
   PoolInvariantError naming that invariant;
2. gating — REPRO_SANITIZE / ManagerConfig(sanitize=...) wire the sweep
   into every mutating public op (and the config flag beats the env);
3. a seeded-random lifecycle fuzz with sanitize=True + exact byte
   accounting that runs even where hypothesis is unavailable (the
   hypothesis fuzz in test_core_property.py covers the same ops deeper).

Plus the jit-cache compile-count regression for the bucketed prefill
engine (a 32-request mixed trace must stay within #buckets + #phases
distinct compiled programs).
"""

import random

import pytest

from repro.core import (
    ManagerConfig,
    CacheManager,
    NodeKind,
    PoolInvariantError,
    Residency,
    Tier,
    check_pool_invariants,
    jit_cache_size,
    make_fastlibra,
    sanitize_enabled,
)

KVB = 64
BS = 4
BLOCK_BYTES = KVB * BS


def _mgr(**kw):
    mgr, sw = make_fastlibra(
        hbm_bytes=kw.pop("hbm_blocks", 24) * BLOCK_BYTES,
        host_bytes=128 * BLOCK_BYTES,
        kv_bytes_per_token=KVB,
        block_size=BS,
        **kw,
    )
    for lid in "ab":
        mgr.register_lora(lid, BLOCK_BYTES, now=0.0)
    return mgr, sw


def _one_query(mgr, lid="a", toks=tuple(range(12)), qid="q0", now=1.0):
    lk = mgr.lookup(lid, toks, now)
    adm = mgr.admit(lk, now)
    assert not adm.queued
    blocks = mgr.allocate_running(qid, len(toks), now)
    assert blocks is not None
    mgr.commit(qid, lk, toks, now)
    mgr.unpin(adm.pinned)
    return lk


def _expect(mgr, fragment):
    with pytest.raises(PoolInvariantError) as ei:
        check_pool_invariants(mgr)
    assert any(fragment in v for v in ei.value.violations), ei.value.violations
    return ei.value


# ------------------------------------------------------------- detection
def test_clean_manager_passes():
    mgr, _ = _mgr()
    _one_query(mgr)
    check_pool_invariants(mgr)  # must not raise


def test_detects_pool_partition_corruption():
    mgr, _ = _mgr()
    mgr.pool._allocated[Tier.HBM].add(10_000)
    err = _expect(mgr, "pool-partition")
    assert err.dump  # tree dump attached for forensics


def test_detects_leaked_block():
    mgr, _ = _mgr()
    _one_query(mgr)
    kv = next(mgr.tree.iter_nodes({NodeKind.KV}))
    kv.hbm_blocks.pop()  # node forgets a block it still has allocated
    _expect(mgr, "allocated-but-unowned")


def test_detects_block_aliasing():
    mgr, _ = _mgr()
    _one_query(mgr)
    kv = next(mgr.tree.iter_nodes({NodeKind.KV}))
    kv.hbm_blocks.append(kv.hbm_blocks[0])  # same block owned twice
    _expect(mgr, "block-aliasing")


def test_detects_validity_chain_break():
    mgr, _ = _mgr()
    _one_query(mgr)
    lora = mgr.tree.lora_node("a")
    lora.tier = Residency.HOST  # HBM KV child now hangs under a host parent
    _expect(mgr, "validity-chain")


def test_detects_tier_residency_mismatch():
    mgr, _ = _mgr()
    _one_query(mgr)
    kv = next(mgr.tree.iter_nodes({NodeKind.KV}))
    kv.tier = None  # dropped tier while still owning blocks
    _expect(mgr, "tier-residency")


def test_detects_byte_accounting_drift():
    mgr, _ = _mgr()
    _one_query(mgr)
    kv = next(mgr.tree.iter_nodes({NodeKind.KV}))
    # move a block out of the tree without releasing it in the pool: the
    # breakdown shrinks but the pool's used count does not
    kv.hbm_blocks.pop()
    kv.num_blocks -= 1
    _expect(mgr, "byte-accounting")


def test_detects_radix_key_mismatch():
    mgr, _ = _mgr()
    _one_query(mgr)
    lora = mgr.tree.lora_node("a")
    (key, child), = list(lora.children.items())
    del lora.children[key]
    lora.children[(99, 99, 99, 99)] = child  # key no longer the edge prefix
    _expect(mgr, "radix-structure")


def test_detects_negative_refcount():
    mgr, _ = _mgr()
    _one_query(mgr)
    next(mgr.tree.iter_nodes()).ref_count = -1
    _expect(mgr, "pin-bookkeeping")


def test_detects_running_block_mismatch():
    mgr, _ = _mgr()
    lk = mgr.lookup("a", tuple(range(8)), 1.0)
    adm = mgr.admit(lk, 1.0)
    mgr.allocate_running("open", 8, 1.0)
    mgr._running["open"].pop()  # lose a running block without accounting
    mgr.kv_pool.release(Tier.HBM, [])  # no-op, keeps pool consistent
    try:
        _expect(mgr, "pin-bookkeeping")
    finally:
        mgr._sanitize = False  # cleanup below would re-raise otherwise
        mgr.abort_running("open")
        mgr.unpin(adm.pinned)


def test_detects_partial_state_snapshot():
    mgr, _ = _mgr(state_bytes=2 * BLOCK_BYTES)
    # adapters start on HOST; admit "a" so the snapshot's ancestry is HBM
    adm = mgr.admit(mgr.lookup_state("a", (), 0.5), 0.5)
    node = mgr.commit_state("a", tuple(range(6)), now=1.0)
    assert node is not None and node.num_blocks == mgr.config.state_blocks
    stolen = node.hbm_blocks.pop()  # snapshots are indivisible
    try:
        _expect(mgr, "hollow-state")
    finally:
        node.hbm_blocks.append(stolen)


def test_detects_lora_registry_break():
    mgr, _ = _mgr()
    mgr.tree._lora_nodes["ghost"] = mgr.tree.lora_node("a")
    _expect(mgr, "lora-registry")


# ------------------------------------------- shared-prefix trunk (I-shared)
def _one_shared_query(mgr, lid="a", toks=tuple(range(12)), shared=8,
                      qid="s0", now=1.0):
    """Full lifecycle of a query whose first ``shared`` tokens are declared
    adapter-independent — commits a trunk span + an adapter fork."""
    lk = mgr.lookup(lid, toks, now, shared_prefix_len=shared)
    adm = mgr.admit(lk, now)
    assert not adm.queued
    assert mgr.allocate_running(qid, len(toks) + 4, now) is not None
    mgr.commit(qid, lk, toks + tuple(range(500, 504)), now)
    mgr.unpin(adm.pinned)
    return lk


def _trunk_and_fork(mgr):
    shared = [n for n in mgr.tree.shared_nodes()]
    assert shared, "no trunk node committed"
    trunk = shared[0]
    forks = [c for c in trunk.children.values() if c.lora_id is not None]
    assert forks, "no adapter fork under the trunk"
    return trunk, forks[0]


def test_shared_query_passes_and_splits_bytes():
    mgr, _ = _mgr(sanitize=True)
    _one_shared_query(mgr, lid="a", qid="s0")
    _one_shared_query(mgr, lid="b", qid="s1", now=2.0)
    trunk, fork = _trunk_and_fork(mgr)
    assert trunk.lora_id is None and fork.lora_id in ("a", "b")
    bd = mgr.hbm_breakdown()
    assert bd["shared_kv_bytes"] == len(trunk.hbm_blocks) * BLOCK_BYTES > 0
    check_pool_invariants(mgr)  # must not raise


def test_detects_trunk_with_sharing_disabled():
    mgr, _ = _mgr()
    _one_shared_query(mgr)
    mgr.config.share_prefix_kv = False  # trunk now structurally illegal
    _expect(mgr, "share_prefix_kv disabled")


def test_detects_non_kv_trunk_node():
    mgr, _ = _mgr()
    _one_shared_query(mgr)
    trunk, _ = _trunk_and_fork(mgr)
    trunk.kind = NodeKind.STATE  # lora_id=None must imply KV kind
    _expect(mgr, "trunk is KV-only")


def test_detects_state_fork_off_trunk():
    mgr, _ = _mgr()
    _one_shared_query(mgr)
    _, fork = _trunk_and_fork(mgr)
    fork.kind = NodeKind.STATE
    _expect(mgr, "forks off the shared trunk")


def test_detects_trunk_under_non_trunk_parent():
    mgr, _ = _mgr()
    _one_shared_query(mgr)
    trunk, _ = _trunk_and_fork(mgr)
    trunk.parent = mgr.tree.lora_node("a")
    _expect(mgr, "under non-trunk parent")


def test_detects_fork_with_detached_shared_parent():
    mgr, _ = _mgr()
    _one_shared_query(mgr)
    trunk, _ = _trunk_and_fork(mgr)
    trunk.parent = None  # trunk unhooked from the root: forks dangle
    _expect(mgr, "detached shared parent")


def test_detects_fork_key_mismatch():
    mgr, _ = _mgr()
    _one_shared_query(mgr)
    trunk, fork = _trunk_and_fork(mgr)
    key = mgr.tree._child_key(trunk, fork.lora_id, fork.tokens)
    del trunk.children[key]
    trunk.children[("ghost", (9, 9, 9, 9))] = fork
    _expect(mgr, "not reachable from its shared parent")


def test_detects_shared_byte_split_drift():
    mgr, _ = _mgr()
    _one_shared_query(mgr)
    orig = mgr.hbm_breakdown()

    def skewed():
        bd = dict(orig)
        bd["shared_kv_bytes"] += BLOCK_BYTES  # misclassified bytes
        bd["history_kv_bytes"] -= BLOCK_BYTES
        return bd

    mgr.hbm_breakdown = skewed
    _expect(mgr, "shared-prefix: hbm_breakdown shared_kv_bytes")


def test_detects_nan_score():
    mgr, _ = _mgr()
    _one_query(mgr)
    mgr.scorer.score = lambda node, now: float("nan")
    _expect(mgr, "scorer-consistency")


# ---------------------------------------------------------------- gating
def test_sanitize_config_flag_hooks_every_mutating_op():
    mgr, _ = _mgr(sanitize=True)
    _one_query(mgr)  # clean ops pass with the sweep armed
    mgr.pool._allocated[Tier.HBM].add(10_000)
    with pytest.raises(PoolInvariantError):
        mgr.lookup("a", (1, 2, 3, 4), 2.0)  # corruption caught at next op


def test_sanitize_env_gate(monkeypatch):
    monkeypatch.delenv("REPRO_SANITIZE", raising=False)
    assert not sanitize_enabled()
    monkeypatch.setenv("REPRO_SANITIZE", "0")
    assert not sanitize_enabled()
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    assert sanitize_enabled()
    mgr = CacheManager(
        ManagerConfig(block_size=BS, kv_bytes_per_token=KVB),
        24 * BLOCK_BYTES, 128 * BLOCK_BYTES,
    )
    assert mgr._sanitize  # env picked up at construction
    # explicit config beats the env
    off = CacheManager(
        ManagerConfig(block_size=BS, kv_bytes_per_token=KVB, sanitize=False),
        24 * BLOCK_BYTES, 128 * BLOCK_BYTES,
    )
    assert not off._sanitize


def test_swapper_tick_runs_sanitize_sweep():
    mgr, sw = _mgr(sanitize=True, hbm_blocks=16)
    _one_query(mgr)
    mgr.pool._allocated[Tier.HBM].add(10_000)
    with pytest.raises(PoolInvariantError):
        sw.tick(5.0)


def test_sanitizer_is_pure_reads():
    """Enabling the sanitizer must not change pool behavior: the same op
    sequence yields an identical tree/pool state with it on and off."""

    def run(sanitize):
        mgr, sw = _mgr(sanitize=sanitize)
        for i in range(6):
            _one_query(mgr, lid="ab"[i % 2],
                       toks=tuple(range(i, i + 12)), qid=f"q{i}",
                       now=1.0 + i)
            sw.tick(2.0 + i)
        return (
            sorted((n.kind.value, n.tokens, n.tier and n.tier.value,
                    tuple(n.hbm_blocks), tuple(n.host_blocks))
                   for n in mgr.tree.iter_nodes()),
            mgr.pool.stats().hbm_used,
        )

    assert run(False) == run(True)


# ------------------------------------------------- seeded lifecycle fuzz
def test_seeded_fuzz_sanitized_exact_accounting():
    """Deterministic mini-fuzz of the full open-query lifecycle with the
    per-op sweep armed and byte accounting checked exactly after every op.
    Runs everywhere (no hypothesis dependency); the hypothesis fuzz in
    test_core_property.py explores the same op space adaptively."""
    rng = random.Random(0xF457)
    for trial in range(8):
        hbm_blocks = rng.randrange(10, 33)
        state = rng.random() < 0.5
        mgr, sw = make_fastlibra(
            hbm_bytes=hbm_blocks * BLOCK_BYTES,
            host_bytes=128 * BLOCK_BYTES,
            kv_bytes_per_token=KVB,
            block_size=BS,
            state_bytes=2 * BLOCK_BYTES if state else 0,
            sanitize=True,
        )
        for lid in "abc":
            mgr.register_lora(lid, BLOCK_BYTES, now=0.0)
        now, open_qs, qid = 1.0, [], 0
        for _ in range(120):
            now += 0.05
            op = rng.randrange(6)
            if op <= 1:  # begin
                lid = rng.choice("abc")
                toks = tuple(rng.randrange(8) for _ in range(rng.randrange(24)))
                if state and lid == "c":
                    lk = mgr.lookup_state(lid, toks, now)
                else:
                    # shared spans interleave with plain per-adapter queries
                    lk = mgr.lookup(lid, toks, now,
                                    shared_prefix_len=rng.choice(
                                        (0, 0, 4, 8, 12)))
                adm = mgr.admit(lk, now)
                if adm.queued:
                    mgr.drain_ops()
                else:
                    name = f"f{qid}"
                    qid += 1
                    need = len(toks) - lk.match.matched_tokens + rng.randrange(1, 12)
                    if mgr.allocate_running(name, need, now) is None:
                        mgr.abort_running(name)
                        mgr.unpin(adm.pinned)
                    else:
                        open_qs.append((name, lk, adm.pinned, toks, need))
            elif op == 2 and open_qs:  # grow
                name = open_qs[rng.randrange(len(open_qs))][0]
                mgr.allocate_running(name, rng.randrange(1, 8), now)
            elif op == 3 and open_qs:  # commit
                name, lk, pinned, toks, need = open_qs.pop(
                    rng.randrange(len(open_qs)))
                full = toks + tuple(range(1000, 1000 + need))
                mgr.commit(name, lk, full, now)
                mgr.unpin(pinned)
            elif op == 4 and open_qs:  # abort
                name, lk, pinned, *_ = open_qs.pop(rng.randrange(len(open_qs)))
                mgr.abort_running(name)
                mgr.unpin(pinned)
            elif op == 5 and state:  # snapshot boundary
                toks = tuple(rng.randrange(8) for _ in range(rng.randrange(1, 16)))
                mgr.commit_state("c", toks, now)
            else:  # swapper sweep
                sw.observe_batch_size(rng.uniform(0.0, 16.0))
                sw.tick(now)
                mgr.drain_ops()
            # exact accounting after EVERY op (the per-op sweep already ran
            # inside the mutating call; this pins breakdown == pool)
            bd = mgr.hbm_breakdown()
            used = (bd["lora_bytes"] + bd["history_kv_bytes"]
                    + bd["shared_kv_bytes"]
                    + bd["state_snapshot_bytes"] + bd["running_kv_bytes"])
            assert used == mgr.pool.stats().hbm_used * mgr.config.block_bytes
        for name, lk, pinned, toks, need in open_qs:
            mgr.abort_running(name)
            mgr.unpin(pinned)
        mgr.check_invariants()
        assert all(n.ref_count == 0 for n in mgr.tree.iter_nodes())


# -------------------------------------------------- compile-count probe
def test_jit_cache_size_duck_typing():
    assert jit_cache_size(lambda x: x) == 0  # plain callables count as 0

    jax = pytest.importorskip("jax")
    fn = jax.jit(lambda x: x + 1)
    assert jit_cache_size(fn) == 0
    fn(jax.numpy.ones((2,)))
    fn(jax.numpy.ones((2,)))  # same shape: no retrace
    assert jit_cache_size(fn) == 1
    fn(jax.numpy.ones((3,)))  # new shape: one more program
    assert jit_cache_size(fn) == 2


@pytest.mark.slow
def test_compile_count_bounded_on_mixed_trace():
    """A 32-request mixed trace (varied prompt lengths, interleaved decode)
    must compile at most #buckets prefill programs + 1 per fixed-shape
    phase entry point + one KV-pool copy program per piece size — per-value
    recompiles (e.g. a Python scalar sneaking into a jit signature, or a
    copy whose shape follows the block count) blow past this bound
    immediately."""
    jax = pytest.importorskip("jax")
    from repro import configs
    from repro.serving import EngineConfig, Request, ServingEngine
    from repro.kvcache.paged import piece_sizes

    cfg = configs.reduced(configs.get("qwen3-0.6b"))
    ecfg = EngineConfig(
        hbm_bytes=8 << 20, host_bytes=32 << 20, block_size=4,
        max_batch_slots=4, max_seq_len=96,
    )
    eng = ServingEngine(cfg, ecfg, key=jax.random.PRNGKey(7))
    for i in range(3):
        eng.register_adapter(f"lora-{i}")
    rng = random.Random(7)
    for i in range(32):
        plen = rng.randrange(6, 40)  # many distinct lengths, few buckets
        prompt = tuple(rng.randrange(10, 200) for _ in range(plen))
        eng.submit(Request(f"cc{i}", f"lora-{i % 3}", prompt,
                           max_new_tokens=rng.randrange(2, 5)))
    report = eng.run(max_steps=50_000)
    assert report.n_finished == 32
    counts = eng.compile_counts()
    n_buckets = len(eng.prefill.buckets)
    n_phases = 2  # prefill + decode entry points
    # scatter and gather: pieces up to a slot's blocks; swap-out's take and
    # swap-in's put: pieces up to a node both tiers hold
    to_dense, swaps = eng.kv_pool.copy_limits(ecfg.max_seq_len // 4)
    n_copies = 2 * len(piece_sizes(to_dense)) + 2 * len(piece_sizes(swaps))
    assert counts["prefill"] <= n_buckets, counts
    assert counts["decode"] <= 1, counts
    assert counts["kv_copy"] <= n_copies, counts
    assert sum(counts.values()) <= n_buckets + n_phases + n_copies, (
        counts, eng.prefill.buckets)
