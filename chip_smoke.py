"""Smoke test of the serving engine on one TPU chip.

    python chip_smoke.py [--seed N]

Run from the root of a checkout, in one process that owns the chip. It
serves qwen3-0.6b at its published widths (28 layers, d_model 1024, 16/8
heads, head_dim 128, vocab 151936, rank-32 LoRA on q/k/v/o; random weights
from ``--seed``) through ``ServingEngine`` with the Pallas kernels, built by
the same code as ``python -m repro.launch.serve --full-config``. Phases:

1. serve — 4 adapters, 16 requests of a few hundred tokens in two rounds;
   the second repeats the first's prompts, and half the prompts open with a
   system prompt declared as an adapter-independent shared prefix. Every
   request must finish with in-vocabulary tokens, the prefix cache must hit,
   and every adapter must start cold on the host tier and be loaded.
2. kernels — ``ragged_extend``, ``paged_attention`` and ``fused_sgmv`` at the
   engine's shapes against their ``kernels/ref.py`` oracles (computed at
   ``highest`` matmul precision): ``max|kernel - ref| / max|ref|`` must stay
   within ``KERNEL_TOL``.
3. mixed step — the engine's compiled mixed-step program must contain Mosaic
   kernels (``tpu_custom_call``), and one mixed step's last-position logits
   under ``kernel_backend="pallas"`` must match ``"jnp"`` within
   ``LOGITS_TOL`` of the logits' scale, both at ``highest`` precision.

Without a TPU it exits non-zero before building anything. The times it
prints are smoke readings, not benchmarks. The last line of its output is
``{"ok": true, "device": {...}}``; any failed check raises instead.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402

ARCH = "qwen3-0.6b"
N_ADAPTERS = 4
MAX_NEW = 16
SYSTEM_LEN = 128  # shared system prompt, a multiple of the pool block size
KERNEL_TOL = 2e-2  # kernel at the engine's precision vs a "highest" oracle
LOGITS_TOL = 1e-3  # pallas vs jnp logits, both at "highest" precision


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def make_traffic(rng, vocab: int):
    """Two rounds of (adapter, prompt, shared_prefix_len); round two
    repeats round one's prompts."""
    system = rng.integers(0, vocab, SYSTEM_LEN).tolist()
    first = []
    for i in range(8):
        user = rng.integers(0, vocab, int(rng.integers(160, 320))).tolist()
        shared = SYSTEM_LEN if i % 2 == 0 else 0
        prompt = (system + user) if shared else user
        first.append((f"lora-{i % N_ADAPTERS}", tuple(prompt), shared))
    return [first, list(first)]


def phase_serve(engine, rng) -> None:
    from repro.serving import Request

    vocab = engine.model_cfg.vocab_size
    rounds = make_traffic(rng, vocab)
    # every adapter starts on the host tier: serving must load each one it
    # uses (on admission, or earlier from the swapper's prefetch)
    check(not engine.adapters.resident, "adapters resident before serving")
    reports = []
    for n, traffic in enumerate(rounds):
        for i, (adapter, prompt, shared) in enumerate(traffic):
            engine.submit(Request(f"r{n}-{i}", adapter, prompt, MAX_NEW,
                                  shared_prefix_len=shared))
        t0 = time.perf_counter()
        rep = engine.run()
        log(f"serve round {n}: wall {time.perf_counter() - t0:.3f} s "
            f"(smoke reading; round 0 includes compiles)")
        check(rep.n_unfinished == 0 and rep.n_aborted == 0,
              f"round {n}: {rep.n_unfinished} unfinished, "
              f"{rep.n_aborted} aborted")
        if n == 0:
            used = {adapter for adapter, _, _ in traffic}
            loaded = set(engine.adapters.resident)
            check(used <= loaded,
                  f"adapters {sorted(used - loaded)} never loaded")
        reports.append(rep)
    rep = reports[-1]
    n_sent = sum(len(t) for t in rounds)
    check(rep.n_finished == n_sent == len(engine.finished),
          f"{rep.n_finished} of {n_sent} requests finished")
    for r in engine.finished:
        check(len(r.generated) == MAX_NEW, f"{r.request_id}: "
              f"{len(r.generated)} tokens, want {MAX_NEW}")
        check(all(0 <= t < vocab for t in r.generated),
              f"{r.request_id}: token outside [0, {vocab})")
    cold = sum(1 for r in engine.finished if r.lora_coldstart > 0)
    pool = engine.kv_pool
    log(f"serve: {rep.n_finished} finished, kv_hit_rate {rep.kv_hit_rate}, "
        f"lora_hit_rate {rep.lora_hit_rate}, adapters cold-started on the "
        f"host tier and loaded: {sorted(engine.adapters.resident)}, "
        f"admissions that waited on a load: {cold}, "
        f"swap bytes in {pool.swap_in_bytes} "
        f"out {pool.swap_out_bytes}, avg_ttft {rep.avg_ttft} s, "
        f"avg_tpot {rep.avg_tpot} s (smoke readings)")
    check(rep.kv_hit_rate > 0, "prefix cache never hit")


def _rel_err(got, want) -> float:
    import jax.numpy as jnp

    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def phase_kernels(engine, key) -> None:
    """Each kernel at the engine's shapes against its oracle."""
    import jax.numpy as jnp

    from repro.kernels import fused_sgmv, paged_attention, ragged_extend, ref

    cfg, ecfg = engine.model_cfg, engine.cfg
    B, T = ecfg.max_batch_slots, ecfg.max_seq_len
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    S = engine.prefill.buckets[-1]
    ks = jax.random.split(key, 8)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32)
    lens = jnp.asarray([S, 1, S // 2 + 1, 0][:B], jnp.int32)
    start = jnp.asarray([0, T - 1, T // 3, 7][:B], jnp.int32)
    page = 128
    pages = T // page
    tables = jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)
    dec_lens = jnp.asarray([T, T // 2 + 1, 1, 0][:B], jnp.int32)
    lora = engine.adapters.slots["q"]
    a, b = lora[0][0], lora[1][0]  # layer 0: (slots, d_in, r), (slots, r, d_out)
    x = jax.random.normal(ks[3], (B, S, cfg.d_model), jnp.float32)
    w = engine.params["layers"]["mixer"]["wq"][0]
    ids = jnp.asarray([0, 1, -1, 2][:B], jnp.int32)
    cases = {
        "ragged_extend": (
            lambda: ragged_extend(q, k, v, start, lens),
            lambda: ref.ragged_extend_ref(q, k, v, start, lens)),
        "paged_attention": (
            lambda: paged_attention(
                q[:, 0], k.reshape(B * pages, page, Hkv, D),
                v.reshape(B * pages, page, Hkv, D), tables, dec_lens),
            lambda: ref.paged_attention_ref(
                q[:, 0], k.reshape(B * pages, page, Hkv, D),
                v.reshape(B * pages, page, Hkv, D), tables, dec_lens)),
        "fused_sgmv": (
            lambda: fused_sgmv(x, w, a, b, ids, scale=engine.model.lora_scale),
            lambda: ref.fused_sgmv_ref(x, w, a, b, ids,
                                       scale=engine.model.lora_scale)),
    }
    for name, (kernel, oracle) in cases.items():
        got = kernel()
        with jax.default_matmul_precision("highest"):
            want = oracle()
        err = _rel_err(got, want)
        log(f"kernel {name}: max|kernel - ref| / max|ref| = {err} "
            f"(tolerance {KERNEL_TOL})")
        check(err <= KERNEL_TOL, f"{name} error {err} > {KERNEL_TOL}")


def phase_mixed_step(engine, key) -> None:
    """The engine's compiled mixed step runs Mosaic kernels, and its logits
    match the jnp backend's."""
    import jax.numpy as jnp
    import numpy as np

    from repro.models import build_model
    from repro.serving.prefill import BatchPrefill

    ecfg = engine.cfg
    B, T = ecfg.max_batch_slots, ecfg.max_seq_len
    bucket = engine.prefill.buckets[-1]
    vocab = engine.model_cfg.vocab_size
    # a mixed batch over the cache the serve phase left: a full prefill
    # chunk, a decode rider, a partial chunk of base-model rows, an idle row
    true_lens = np.asarray([bucket, 1, bucket // 2 + 3, 0][:B], np.int32)
    for i in range(3):
        engine.adapters.load(f"lora-{i}")
    slot = engine.adapters.slot_of
    ids = jnp.asarray([slot("lora-0"), slot("lora-1"), -1,
                       slot("lora-2")][:B], jnp.int32)
    start = jnp.minimum(engine.cache["len"], T - bucket)
    tokens = jax.random.randint(key, (B, bucket), 0, vocab, jnp.int32)
    args = (engine.params, engine.adapters.slots, engine.cache, tokens, start,
            jnp.asarray(true_lens), jnp.asarray(true_lens > 0), ids)

    t0 = time.perf_counter()
    text = engine.prefill.step_fn(bucket).lower(*args).compile().as_text()
    n_kernels = text.count("tpu_custom_call")
    log(f"mixed step (bucket {bucket}): {n_kernels} tpu_custom_call sites, "
        f"compile {time.perf_counter() - t0:.3f} s")
    check(n_kernels > 0, "mixed-step program has no Mosaic kernel")

    live = true_lens > 0
    logits = {}
    for backend in ("pallas", "jnp"):
        model = build_model(dataclasses.replace(
            engine.model_cfg, kernel_backend=backend), dtype=jnp.float32)
        step = BatchPrefill(model, engine.prefill.buckets).step_fn(bucket)
        t0 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            last, _ = step(*args)
        logits[backend] = np.asarray(last)[live]
        log(f"mixed step {backend} at highest precision: "
            f"{time.perf_counter() - t0:.3f} s incl. compile")
    scale = float(np.max(np.abs(logits["jnp"])))
    diff = float(np.max(np.abs(logits["pallas"] - logits["jnp"])))
    log(f"mixed step logits: max|pallas - jnp| = {diff}, max|jnp| = {scale}, "
        f"ratio {diff / scale} (tolerance {LOGITS_TOL})")
    check(np.all(np.isfinite(logits["pallas"])), "non-finite logits")
    check(diff <= LOGITS_TOL * scale,
          f"pallas vs jnp logits differ by {diff / scale} of their scale")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found — JAX's first device is "
              f"{dev.platform!r}; this smoke test needs one TPU chip",
              file=sys.stderr)
        return 2
    import numpy as np

    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import build_engine

    log(f"device: {dev.platform} {dev.device_kind}, {len(jax.devices())} "
        f"device(s); compile cache {enable_compile_cache()}")
    t_start = time.perf_counter()
    engine = build_engine(configs.get(ARCH), full=True, n_adapters=N_ADAPTERS,
                          seed=args.seed, kernel_backend="pallas")
    ecfg = engine.cfg
    log(f"engine: {ARCH}, hbm pool {ecfg.hbm_bytes} B, host pool "
        f"{ecfg.host_bytes} B, {ecfg.max_batch_slots} slots x "
        f"{ecfg.max_seq_len} tokens, built in "
        f"{time.perf_counter() - t_start:.3f} s")
    key = jax.random.PRNGKey(args.seed)
    phase_serve(engine, np.random.default_rng(args.seed))
    phase_kernels(engine, jax.random.fold_in(key, 1))
    phase_mixed_step(engine, jax.random.fold_in(key, 2))
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}, wall "
        f"{time.perf_counter() - t_start:.3f} s (smoke reading)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
