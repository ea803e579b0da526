"""Reductions the per-layer metric readers share. Each reader in
``bench/metrics/`` is a few lines over these; a reader that finds nothing to
read returns None and the metric is left out of the result line."""

from __future__ import annotations

from typing import Optional

from . import peaks, spec, window


def pct(x: Optional[float]) -> Optional[float]:
    return None if x is None else 100.0 * x


def ms(x: Optional[float]) -> Optional[float]:
    return None if x is None else 1e3 * x


def p95(vals) -> Optional[float]:
    return window.percentile(vals, 0.95)


def share(num: float, den: float) -> Optional[float]:
    return None if den <= 0 else num / den


def step_flops(run) -> int:
    """Model FLOPs of every token the window's dispatches computed."""
    s = run.shape
    return sum(run.model.span_flops(s, start, n, emits)
               for c in run.calls for start, n, emits, _ in c.rows)


def step_mfu(run) -> Optional[float]:
    if run.trace is None or not run.calls or run.device_kind not in peaks.PEAKS:
        return None
    peak = peaks.peaks(run.device_kind)["bf16_flops"]
    return pct(step_flops(run) / (run.trace.window_s * peak))


def idle_share(run) -> Optional[float]:
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return pct(1.0 - run.trace.busy_s / run.trace.window_s)


def kernel_roofline(run, kernel: str, least_s) -> Optional[float]:
    """Least time of the needed work (``least_s(run)``) over the kernel's
    summed device time; None where the trace holds none of it (the model's
    path runs no such kernel) or the device has no published peaks. A
    kernel that ran on a model whose module counts no ``<kernel>_need`` is
    a fault of the module, and raises."""
    if run.trace is None or run.device_kind not in peaks.PEAKS:
        return None
    t = run.trace.kernel_s(kernel)
    if t <= 0:
        return None
    if not hasattr(run.model, f"{kernel}_need"):
        raise spec.SpecError(f"{kernel} ran for {t} s, but the model module "
                             f"{run.model.__name__} has no {kernel}_need")
    least = least_s(run)
    return pct(least / t) if least > 0 else None


def ragged_extend_least_s(run) -> float:
    s, kind = run.shape, run.device_kind
    total = 0.0
    for c in run.calls:
        if c.kind != "mixed":
            continue
        f, b = run.model.ragged_extend_need(s, [(st, n) for st, n, _, _ in c.rows])
        total += s.layers * peaks.least_time(f, b, kind)
    return total


def fused_sgmv_least_s(run) -> float:
    s, kind = run.shape, run.device_kind
    total = 0.0
    for c in run.calls:
        tokens = sum(n for _, n, _, _ in c.rows)
        adapters = len({a for _, _, _, a in c.rows})
        for t in s.lora_targets:
            f, b = run.model.fused_sgmv_need(s, t, tokens, adapters)
            total += s.layers * peaks.least_time(f, b, kind)
    return total
