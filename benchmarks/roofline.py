"""Roofline benchmark: reads the dry-run artifacts (results/dryrun/*.json)
and emits the three roofline terms per (arch × shape × mesh) cell.

Hardware constants come from ``PEAKS``, keyed by ``jax.Device.device_kind``;
a device kind missing from the table is an error, never a default.
"""

from __future__ import annotations

import json
import pathlib

from .common import CsvOut

RESULTS = pathlib.Path(__file__).resolve().parent.parent / "results" / "dryrun"

# Published per-chip peaks. Source: Google Cloud documentation, "TPU v5e"
# (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 819 GB/s of HBM
# bandwidth, 1,600 Gbit/s of inter-chip interconnect per chip.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 1600e9 / 8},
}

# The chip the kernels and the dry-run's 16x16 production meshes are built
# for. Counted (shape-derived) roofline terms are priced against it when no
# chip is attached; a measured time is always priced against the chip that
# ran it.
TARGET_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; unknown devices are an error."""
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def kernel_roofline(flops: float, hbm_bytes: float,
                    measured_us: float | None = None, *,
                    device_kind: str) -> dict:
    """Single-chip roofline terms for one kernel invocation.

    ``flops``/``hbm_bytes`` come from ``repro.kernels.counting`` — analytic
    replay of the trimmed grids, not a profiler. ``ceiling_fraction`` is the
    best MXU utilization the counted traffic admits (t_comp / bound ≤ 1);
    ``achieved_fraction`` (when a measured TPU time is supplied) is
    bound / measured — how close the run came to its own roofline. Both are
    priced against ``device_kind``'s peaks: the chip that measured, or
    ``TARGET_DEVICE_KIND`` for counts alone. See README.md §Kernels.
    """
    pk = peaks(device_kind)
    t_comp = flops / pk["flops"]
    t_mem = hbm_bytes / pk["hbm_bw"]
    bound = max(t_comp, t_mem, 1e-15)
    out = {
        "t_compute": t_comp,
        "t_memory": t_mem,
        "bound_us": bound * 1e6,
        "dominant": "compute" if t_comp >= t_mem else "memory",
        "ceiling_fraction": t_comp / bound,
    }
    if measured_us is not None and measured_us > 0:
        out["achieved_fraction"] = min(1.0, bound * 1e6 / measured_us)
    return out


def roofline_terms(rec: dict) -> dict:
    """Roofline terms of one dry-run record, priced against the chip its
    production mesh describes (``TARGET_DEVICE_KIND``)."""
    pk = peaks(TARGET_DEVICE_KIND)
    chips = rec["num_devices"]
    t_comp = rec["flops"] / (chips * pk["flops"])
    # memory term: prefer the structural entry-only estimate (TPU-realistic);
    # XLA-CPU cost_analysis bytes count unfused elementwise chains a TPU
    # would fuse (recorded for reference as bytes_accessed).
    mem_bytes_dev = rec.get(
        "bytes_entry_per_device", rec["bytes_accessed"] / chips
    )
    t_mem = mem_bytes_dev / pk["hbm_bw"]
    # collective_bytes is per-device traffic (parsed from the partitioned
    # HLO), so the term is bytes/dev over the chip's interconnect bandwidth
    t_coll = rec["collective_bytes"] / pk["ici_bw"]
    dom = max(
        ("compute", t_comp), ("memory", t_mem), ("collective", t_coll),
        key=lambda kv: kv[1],
    )[0]
    out = {
        "t_compute": t_comp,
        "t_memory": t_mem,
        "t_collective": t_coll,
        "dominant": dom,
        "bound": max(t_comp, t_mem, t_coll),
    }
    if rec.get("model_flops"):
        out["useful_flops_ratio"] = rec["model_flops"] / max(1.0, rec["flops"])
        # roofline fraction: useful work / (what the dominant term costs)
        out["roofline_fraction"] = (
            rec["model_flops"] / (chips * pk["flops"])
        ) / max(1e-12, out["bound"])
    return out


def load_records() -> list[dict]:
    if not RESULTS.exists():
        raise FileNotFoundError(f"{RESULTS} (run launch/dryrun.py first)")
    recs = []
    for f in sorted(RESULTS.glob("*.json")):
        rec = json.loads(f.read_text())
        rec["_file"] = f.name
        recs.append(rec)
    if not recs:
        raise FileNotFoundError(f"{RESULTS} is empty (run launch/dryrun.py)")
    return recs


MITIGATION = {
    "compute": "raise MXU utilization: larger fused matmul tiles / bf16 IO",
    "memory": "cut HBM traffic: blockwise attention (q_chunk), int8 KV, "
              "remat policy 'dots'",
    "collective": "re-shard to shrink cross-device traffic: fewer all-gathers "
                  "(fsdp prefetch), hierarchical pod-axis reduce, int8 grads",
}


def markdown_table(records: list[dict]) -> str:
    """Curated §Roofline table for EXPERIMENTS.md."""
    lines = [
        "| arch | shape | mesh | t_comp s | t_mem s | t_coll s | dominant | "
        "useful | roofline-frac | note |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for rec in records:
        tag = f"×{rec['opts']}" if rec.get("opts") else ""
        if rec.get("status") != "ok":
            lines.append(
                f"| {rec['arch']} | {rec['shape']} | {rec['mesh']}{tag} | - | - | - "
                f"| - | - | - | {rec['status'][:60]} |"
            )
            continue
        t = roofline_terms(rec)
        lines.append(
            f"| {rec['arch']} | {rec['shape']} | {rec['mesh']}{tag} "
            f"| {t['t_compute']:.3f} | {t['t_memory']:.3f} "
            f"| {t['t_collective']:.3f} | {t['dominant']} "
            f"| {t.get('useful_flops_ratio', 0):.3f} "
            f"| {t.get('roofline_fraction', 0):.4f} "
            f"| {MITIGATION[t['dominant']][:48]} |"
        )
    return "\n".join(lines)


def main() -> None:
    print(markdown_table(load_records()))


def run(out: CsvOut) -> None:
    for rec in load_records():
        if rec.get("status") != "ok":
            out.emit(
                f"roofline/{rec['arch']}/{rec['shape']}/{rec['mesh']}",
                0.0,
                f"status={rec.get('status')}",
            )
            continue
        terms = roofline_terms(rec)
        out.emit(
            f"roofline/{rec['arch']}/{rec['shape']}/{rec['mesh']}",
            terms["bound"] * 1e6,
            f"dom={terms['dominant']};comp_s={terms['t_compute']:.2e};"
            f"mem_s={terms['t_memory']:.2e};coll_s={terms['t_collective']:.2e};"
            f"useful={terms.get('useful_flops_ratio', 0):.3f};"
            f"roofline_frac={terms.get('roofline_fraction', 0):.3f}",
        )


if __name__ == "__main__":
    main()
