"""Serving launcher: run the multi-LoRA engine on one device.

The engine drives a single device and shards nothing. Without
``--full-config`` it serves the reduced config (the CPU path: kernels run in
interpret mode); with it, the architecture's published widths, with pools
and ``max_seq_len`` sized from the model by :func:`engine_config` — the
one-chip TPU path, where the kernels compile with Mosaic.
:func:`build_engine` is the construction code ``chip_smoke.py`` shares.
Exits non-zero when any request is left unfinished or aborted.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --variant fastlibra --requests 16
"""

from __future__ import annotations

import argparse
import random
import sys

import jax
import jax.numpy as jnp

from repro import configs
from repro.distributed import RequestJournal
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving import EngineConfig, Request, ServingEngine
from repro.serving.engine import kv_pool_spec

# engine shape per mode: batch slots, dense-cache length, pool block size
FULL_SHAPE = dict(max_batch_slots=4, max_seq_len=1024, block_size=16)
REDUCED_SHAPE = dict(max_batch_slots=4, max_seq_len=128, block_size=4)


def pool_sizes(*, kv_bytes_per_token: int, adapter_bytes: int,
               n_adapters: int, max_batch_slots: int, max_seq_len: int,
               block_size: int) -> tuple[int, int]:
    """(hbm_bytes, host_bytes) of the two pool tiers, in whole blocks.

    HBM holds every batch slot's running KV at ``max_seq_len``, as much again
    for cached prefix history, and every adapter at once. The host tier
    holds every registered adapter plus one running set of demoted KV."""
    block_bytes = kv_bytes_per_token * block_size
    running = max_batch_slots * -(-max_seq_len // block_size)
    adapters = n_adapters * -(-adapter_bytes // block_bytes)
    return (2 * running + adapters) * block_bytes, (running + adapters) * block_bytes


def adapter_nbytes(cfg) -> int:
    """Bytes of one adapter as the engine stores it (float32), from shapes
    alone."""
    model = build_model(cfg, dtype=jnp.float32)
    one = jax.eval_shape(lambda: model.init_lora(jax.random.PRNGKey(0), 1))
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(one))


def engine_config(cfg, *, full: bool, n_adapters: int,
                  **overrides) -> EngineConfig:
    """EngineConfig with pools sized for ``cfg`` and ``n_adapters``."""
    shape = FULL_SHAPE if full else REDUCED_SHAPE
    hbm, host = pool_sizes(
        kv_bytes_per_token=kv_pool_spec(cfg, shape["block_size"]).bytes_per_token,
        adapter_bytes=adapter_nbytes(cfg), n_adapters=n_adapters, **shape)
    return EngineConfig(hbm_bytes=hbm, host_bytes=host, **shape, **overrides)


def build_engine(cfg, *, full: bool, n_adapters: int, seed: int = 0,
                 **overrides) -> ServingEngine:
    """The engine with ``n_adapters`` adapters ``lora-<i>`` registered, all
    weights drawn from ``seed``."""
    key = jax.random.PRNGKey(seed)
    engine = ServingEngine(
        cfg, engine_config(cfg, full=full, n_adapters=n_adapters, **overrides),
        key=key)
    for i in range(n_adapters):
        engine.register_adapter(f"lora-{i}",
                                key=jax.random.fold_in(key, 1 + i))
    return engine


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=configs.ARCH_IDS)
    ap.add_argument("--variant", default="fastlibra")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--adapters", type=int, default=4)
    ap.add_argument("--full-config", action="store_true",
                    help="serve the published widths (one TPU chip)")
    ap.add_argument("--journal", default="", metavar="PATH",
                    help="append-only request journal; a later run given "
                         "the same path replays what this one left in flight")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default="", metavar="PATH",
                    help="arm libra-trace and dump Chrome trace-event JSON "
                         "here (load in Perfetto; see README §Observability)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = configs.get(args.arch)
    if not args.full_config:
        cfg = configs.reduced(cfg)
    # --trace-out arms the tracer explicitly; otherwise the EngineConfig
    # default picks up REPRO_TRACE=1
    ekw = {"trace": True} if args.trace_out else {}
    engine = build_engine(cfg, full=args.full_config, n_adapters=args.adapters,
                          seed=args.seed, variant=args.variant, **ekw)
    journal = RequestJournal(args.journal) if args.journal else None

    # crash recovery: re-enqueue whatever a previous process left in flight
    for ev in journal.replay() if journal else ():
        engine.submit(Request(ev["rid"] + "-replayed", ev["adapter"],
                              tuple(ev["prompt"]), ev["max_new"]))
        print(f"replayed in-flight request {ev['rid']}")

    rng = random.Random(args.seed)
    for i in range(args.requests):
        rid = f"req-{i}"
        adapter = f"lora-{rng.randrange(args.adapters)}"
        prompt = tuple(rng.randrange(10, 200) for _ in range(rng.randint(6, 14)))
        if journal:
            journal.record_submit(rid, adapter, prompt, 6)
        engine.submit(Request(rid, adapter, prompt, max_new_tokens=6))
    report = engine.run()
    if journal:
        for r in engine.finished:
            journal.record_finish(r.request_id)
    print("report:", report.row())
    if args.trace_out:
        engine.export_trace(args.trace_out)
        print(f"trace: wrote {args.trace_out} "
              f"(summarize: python -m repro.obs.report {args.trace_out})")
    if report.n_unfinished or report.n_aborted:
        print(f"serve: FAILED — {report.n_unfinished} unfinished, "
              f"{report.n_aborted} aborted", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
