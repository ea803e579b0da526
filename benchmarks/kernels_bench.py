"""Per-kernel regression harness: parity, counted HBM traffic, roofline.

Each shape emits an ``…/interpret`` row (Pallas interpret mode, the kernel
body running in Python; available everywhere) and, only when a TPU is
attached, a ``…/compiled`` row (the Mosaic-compiled kernel timed on that
chip). Timing in interpret mode validates plumbing, not speed — off the chip
the performance claims are *counted*, not timed: the ``derived`` column
carries analytic bytes/FLOPs from ``repro.kernels.counting`` (replay of the
exact trimmed grids and index-map clamps) plus the roofline terms from
``benchmarks.roofline.kernel_roofline``. ``--check`` turns the harness into a
gate: parity vs the jnp oracles, trimmed grids strictly cheaper than their
rectangular/full baselines, fused SGMV exactly one pass over the activation
tile, zero-length paged rows exactly zero. See README.md §Kernels.

Usage:
    PYTHONPATH=src python -m benchmarks.kernels_bench [--quick] [--check]
        [--csv PATH]
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp

from repro.kernels import (
    counting,
    flash_prefill,
    flash_prefill_ragged,
    fused_sgmv,
    paged_attention,
    ragged_extend,
    ref,
    sgmv,
)

from .common import CsvOut, fmt_fields
from .roofline import TARGET_DEVICE_KIND, kernel_roofline

ON_TPU = jax.default_backend() == "tpu"
# measured times are priced against the chip that ran them; counts alone
# against the chip the kernels are written for
DEVICE_KIND = jax.devices()[0].device_kind if ON_TPU else TARGET_DEVICE_KIND


def _time(fn, *args, reps: int = 3, **kw) -> float:
    """Mean µs/call after a warmup call (compile excluded)."""
    jax.block_until_ready(fn(*args, **kw))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args, **kw))
    return (time.perf_counter() - t0) / reps * 1e6


def _err(a, b) -> float:
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))


def _roofline_tag(counts: dict, t_us: float | None = None) -> str:
    rl = kernel_roofline(counts["flops"], counts.get("hbm_bytes",
                                                     counts.get("x_bytes", 0)),
                         measured_us=t_us if ON_TPU else None,
                         device_kind=DEVICE_KIND)
    fields = ["bound_us:.2f", "dom=dominant", "ceiling_frac=ceiling_fraction:.3f"]
    if "achieved_fraction" in rl:
        fields.append("achieved_frac=achieved_fraction:.3f")
    return fmt_fields(rl, fields)


def _emit_pair(out: CsvOut, name: str, kernel_fn, ref_fn, args, kw,
               derived: str) -> tuple[float, float | None]:
    """Emit the interpret row, and on a TPU the compiled row, for one shape.

    Returns (parity_err, compiled_us); ``compiled_us`` is None off the chip,
    where no compiled kernel runs and nothing is timed under that name.
    """
    t_int = _time(kernel_fn, *args, interpret=True, **kw)
    got = kernel_fn(*args, interpret=True, **kw)
    want = ref_fn(*args)
    err = _err(got, want)
    out.emit(f"{name}/interpret", t_int, f"err={err:.2e};{derived}")
    if not ON_TPU:
        return err, None
    t_cmp = _time(kernel_fn, *args, interpret=False, **kw)
    out.emit(f"{name}/compiled", t_cmp,
             f"impl=pallas;device={DEVICE_KIND};{derived}")
    return err, t_cmp


# Achieved-vs-roofline floors (fraction of the counted roofline bound the
# compiled kernel must reach). Only meaningful against real Mosaic timings,
# so --floors is a no-op off-TPU. Conservative on purpose: they catch
# regressions that fall off a cliff (lost block-skip, serialized grid), not
# single-digit-percent drift.
FLOORS = {
    "fused_sgmv": 0.20,
    "flash_prefill": 0.30,
    "flash_prefill_ragged": 0.20,
    "paged_attention": 0.10,
    "ragged_extend": 0.20,
}


class Checks:
    def __init__(self, floors: bool = False) -> None:
        self.failures: list[str] = []
        self.floors = floors and ON_TPU

    def expect(self, ok: bool, msg: str) -> None:
        if not ok:
            self.failures.append(msg)
            print(f"CHECK FAIL: {msg}", file=sys.stderr)

    def floor(self, kernel: str, counts: dict,
              compiled_us: float | None) -> None:
        if not self.floors:  # off the chip there is no compiled time
            return
        rl = kernel_roofline(counts["flops"], counts["hbm_bytes"],
                             measured_us=compiled_us, device_kind=DEVICE_KIND)
        got = rl["achieved_fraction"]
        self.expect(got >= FLOORS[kernel],
                    f"{kernel}: achieved roofline fraction {got:.3f} below "
                    f"floor {FLOORS[kernel]}")


def bench_sgmv(out: CsvOut, checks: Checks, quick: bool) -> None:
    key = jax.random.PRNGKey(0)
    # (label, B, S, d_in, r, d_out, n_slots) — decode batch + prefill tile
    shapes = [("decode", 8, 1, 512, 32, 512, 8)]
    if not quick:
        shapes.append(("prefill", 4, 256, 256, 16, 512, 8))
    for label, B, S, d, r, o, N in shapes:
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], (B, S, d), jnp.float32)
        w = jax.random.normal(ks[1], (d, o), jnp.float32) * 0.05
        a = jax.random.normal(ks[2], (N, d, r), jnp.float32) * 0.05
        b = jax.random.normal(ks[3], (N, r, o), jnp.float32) * 0.05
        ids = jax.random.randint(ks[4], (B,), -1, N)  # incl. base-model rows
        cf = counting.sgmv_counts(B, S, d, o, r, fused=True)
        cu = counting.sgmv_counts(B, S, d, o, r, fused=False)
        derived = (f"B={B};S={S};d={d};r={r};o={o};"
                   f"x_passes={cf['x_passes_per_block']:.1f};"
                   f"unfused_x_passes={cu['x_passes_per_block']:.1f};"
                   f"launches={cf['kernel_launches']};" + _roofline_tag(cf))
        err, t_cmp = _emit_pair(out, f"kernels/fused_sgmv/{label}", fused_sgmv,
                                ref.fused_sgmv_ref, (x, w, a, b, ids), {},
                                derived)
        checks.expect(err < 1e-4, f"fused_sgmv/{label} parity err={err:.2e}")
        checks.floor("fused_sgmv", {**cf, "hbm_bytes": cf["x_bytes"]}, t_cmp)
        checks.expect(cf["x_passes_per_block"] == 1.0,
                      f"fused_sgmv/{label} x_passes={cf['x_passes_per_block']}"
                      " (want exactly 1 pass over the activation tile)")
        checks.expect(cu["x_passes_per_block"] == 2.0,
                      f"unfused sgmv/{label} baseline x_passes="
                      f"{cu['x_passes_per_block']} (want 2)")
        # unfused pair (legacy path) for the timing comparison row
        t_unf = _time(sgmv, x, a, b, ids, interpret=True)
        out.emit(f"kernels/sgmv/{label}/interpret", t_unf,
                 f"delta_only=1;pair_of=fused_sgmv/{label}")


def bench_flash(out: CsvOut, checks: Checks, quick: bool) -> None:
    key = jax.random.PRNGKey(1)
    # (label, S, block) — incl. a long-causal shape where block-skip pays
    shapes = [("S256", 256, 64)]
    if not quick:
        shapes.append(("long_S1024", 1024, 128))
    B, H, Hkv, D = 1, 4, 2, 64
    for label, S, blk in shapes:
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
        tri = counting.flash_prefill_counts(B, H, Hkv, S, D, block_q=blk,
                                            block_k=blk, variant="block_skip")
        rect = counting.flash_prefill_counts(B, H, Hkv, S, D, block_q=blk,
                                             block_k=blk, variant="rect")
        ratio = tri["kv_bytes"] / rect["kv_bytes"]
        derived = (f"S={S};blk={blk};kv_bytes={tri['kv_bytes']};"
                   f"rect_kv_bytes={rect['kv_bytes']};kv_ratio={ratio:.3f};"
                   + _roofline_tag(tri))
        err, t_cmp = _emit_pair(out, f"kernels/flash_prefill/{label}",
                                flash_prefill, ref.flash_prefill_ref, (q, k, v),
                                dict(block_q=blk, block_k=blk), derived)
        checks.expect(err < 1e-4, f"flash_prefill/{label} parity err={err:.2e}")
        checks.floor("flash_prefill", tri, t_cmp)
        checks.expect(tri["kv_bytes"] < rect["kv_bytes"],
                      f"flash_prefill/{label}: block-skip kv_bytes "
                      f"{tri['kv_bytes']} not < rect {rect['kv_bytes']}")
        checks.expect(tri["flops"] == rect["flops"],
                      f"flash_prefill/{label}: schedules disagree on flops")


def bench_flash_ragged(out: CsvOut, checks: Checks, quick: bool) -> None:
    key = jax.random.PRNGKey(2)
    B, H, Hkv, D, blk = 4, 4, 2, 64, 64
    S = 256 if quick else 512
    lens = [S, (S * 5) // 8, blk // 2, 0]  # bucket: full, partial, tiny, empty
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    tl = jnp.array(lens, jnp.int32)
    rag = counting.flash_prefill_counts(B, H, Hkv, S, D, block_q=blk,
                                        block_k=blk, true_lens=lens)
    full = counting.flash_prefill_counts(B, H, Hkv, S, D, block_q=blk,
                                         block_k=blk, variant="block_skip")
    ratio = rag["kv_bytes"] / full["kv_bytes"]
    derived = (f"S={S};lens={'/'.join(map(str, lens))};"
               f"kv_bytes={rag['kv_bytes']};full_kv_bytes={full['kv_bytes']};"
               f"kv_ratio={ratio:.3f};" + _roofline_tag(rag))
    err, t_cmp = _emit_pair(out, "kernels/flash_prefill_ragged/bucket",
                            flash_prefill_ragged, ref.flash_prefill_ragged_ref,
                            (q, k, v, tl), dict(block_q=blk, block_k=blk),
                            derived)
    checks.expect(err < 1e-4, f"flash_prefill_ragged parity err={err:.2e}")
    checks.floor("flash_prefill_ragged", rag, t_cmp)
    checks.expect(rag["kv_bytes"] < full["kv_bytes"],
                  f"flash_prefill_ragged: trimmed kv_bytes {rag['kv_bytes']} "
                  f"not < full-length {full['kv_bytes']}")
    zero_rows = flash_prefill_ragged(q, k, v, tl, block_q=blk, block_k=blk,
                                     interpret=True)[3]
    checks.expect(float(jnp.max(jnp.abs(zero_rows))) == 0.0,
                  "flash_prefill_ragged: len-0 row not exactly zero")


def bench_paged(out: CsvOut, checks: Checks, quick: bool) -> None:
    key = jax.random.PRNGKey(3)
    B, H, Hkv, D, ps, pages = 4, 8, 2, 64, 16, 4 if quick else 16
    n_pages = B * pages * 2
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    kp = jax.random.normal(ks[1], (n_pages, ps, Hkv, D), jnp.float32)
    vp = jax.random.normal(ks[2], (n_pages, ps, Hkv, D), jnp.float32)
    tables = jax.random.permutation(ks[3], n_pages)[: B * pages]
    tables = tables.reshape(B, pages).astype(jnp.int32)
    T = ps * pages
    lens = [T, T // 2 + 3, 7, 0]  # incl. non-multiple of page_size and empty
    ln = jnp.array(lens, jnp.int32)
    trim = counting.paged_attention_counts(B, H, Hkv, D, ps, pages, lens,
                                           trimmed=True)
    full = counting.paged_attention_counts(B, H, Hkv, D, ps, pages, lens,
                                           trimmed=False)
    ratio = trim["kv_bytes"] / full["kv_bytes"]
    derived = (f"B={B};pages={pages}x{ps};lens={'/'.join(map(str, lens))};"
               f"kv_bytes={trim['kv_bytes']};full_kv_bytes={full['kv_bytes']};"
               f"kv_ratio={ratio:.3f};" + _roofline_tag(trim))
    err, t_cmp = _emit_pair(out, "kernels/paged_attention/decode",
                            paged_attention, ref.paged_attention_ref,
                            (q, kp, vp, tables, ln), {}, derived)
    checks.expect(err < 1e-4, f"paged_attention parity err={err:.2e}")
    checks.floor("paged_attention", trim, t_cmp)
    checks.expect(trim["kv_bytes"] < full["kv_bytes"],
                  f"paged_attention: trimmed kv_bytes {trim['kv_bytes']} "
                  f"not < full-grid {full['kv_bytes']}")
    zero_row = paged_attention(q, kp, vp, tables, ln, interpret=True)[3]
    checks.expect(float(jnp.max(jnp.abs(zero_row))) == 0.0,
                  "paged_attention: len-0 row not exactly zero "
                  "(historical bug: softmax of all-masked row gave mean(V))")


def bench_ragged_extend(out: CsvOut, checks: Checks, quick: bool) -> None:
    key = jax.random.PRNGKey(4)
    B, H, Hkv, D, blk = 4, 4, 2, 64, 64
    S = 128 if quick else 256  # new-token bucket
    T = 512 if quick else 1024  # padded cache rectangle
    starts = [0, T // 4, T - S, 5]
    lens = [S, S // 2 + 1, S, 0]
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32)
    st = jnp.array(starts, jnp.int32)
    tl = jnp.array(lens, jnp.int32)
    trim = counting.ragged_extend_counts(B, H, Hkv, S, T, D, starts, lens,
                                         block_q=blk, block_k=blk, trimmed=True)
    dense = counting.ragged_extend_counts(B, H, Hkv, S, T, D, starts, lens,
                                          block_q=blk, block_k=blk,
                                          trimmed=False)
    ratio = trim["kv_bytes"] / dense["kv_bytes"]
    derived = (f"S={S};T={T};starts={'/'.join(map(str, starts))};"
               f"lens={'/'.join(map(str, lens))};kv_bytes={trim['kv_bytes']};"
               f"dense_kv_bytes={dense['kv_bytes']};kv_ratio={ratio:.3f};"
               + _roofline_tag(trim))
    err, t_cmp = _emit_pair(out, "kernels/ragged_extend/bucket", ragged_extend,
                            ref.ragged_extend_ref, (q, k, v, st, tl),
                            dict(block_q=blk, block_k=blk), derived)
    checks.expect(err < 1e-4, f"ragged_extend parity err={err:.2e}")
    checks.floor("ragged_extend", trim, t_cmp)
    checks.expect(trim["kv_bytes"] < dense["kv_bytes"],
                  f"ragged_extend: trimmed kv_bytes {trim['kv_bytes']} "
                  f"not < dense rectangle {dense['kv_bytes']}")


def run(out: CsvOut, *, quick: bool | None = None,
        checks: Checks | None = None) -> Checks:
    """benchmarks.run adapter; also the --check engine."""
    import os

    if quick is None:
        quick = os.environ.get("BENCH_QUICK", "0") == "1"
    if checks is None:
        checks = Checks()
    bench_sgmv(out, checks, quick)
    bench_flash(out, checks, quick)
    bench_flash_ragged(out, checks, quick)
    bench_paged(out, checks, quick)
    bench_ragged_extend(out, checks, quick)
    return checks


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--quick", action="store_true",
                   help="small shapes only (CI interpret-mode budget)")
    p.add_argument("--check", action="store_true",
                   help="assert parity + counted-traffic invariants; "
                        "exit nonzero on any failure")
    p.add_argument("--floors", action="store_true",
                   help="additionally pin achieved-vs-roofline floors "
                        "(needs a TPU; no-op on CPU, where no compiled "
                        "kernel is timed)")
    p.add_argument("--csv", default="",
                   help="also write the rows to this path")
    args = p.parse_args(argv)
    out = CsvOut()
    print("name,us_per_call,derived")
    checks = run(out, quick=args.quick, checks=Checks(floors=args.floors))
    if args.csv:
        out.write_csv(args.csv)
        print(f"# wrote {len(out.rows)} rows to {args.csv}", file=sys.stderr)
    if args.check:
        if checks.failures:
            print(f"# {len(checks.failures)} check(s) FAILED", file=sys.stderr)
            return 1
        print("# all kernel checks passed", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
