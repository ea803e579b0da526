"""The harness loop end to end on the CPU at a tiny size: a clean run is
correct, a run whose served tokens are altered where they are produced is
not, and a new configuration, mix and per-layer metric are found by name
from files of their own."""

import copy
import json
import shutil

import numpy as np
import pytest

from perfkit import driver, spec

CELL = "qwen3-0.6b.batch-unique"


def tiny(cfg: dict) -> dict:
    cfg = copy.deepcopy(cfg)
    cfg.update({"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
                "head_dim": 16, "intermediate_size": 128, "vocab_size": 512,
                "num_hidden_layers": 2})
    cfg["serving"]["lora"].update({"rank": 4, "alpha": 4, "max_adapters": 8})
    cfg["serving"]["engine"].update({"max_seq_len": 256, "block_size": 16,
                                     "kernel_backend": "jnp", "prefill_chunk": 16})
    cfg["serving"]["engine"]["pool"] = {"hbm_tokens": 512, "hbm_adapters": 8,
                                        "host_tokens": 2048, "host_adapters": 8}
    # on the CPU the engine computes in float32 throughout: a served token's
    # logit sits within rounding of the oracle's best
    cfg["limits"].update({"max_logit_gap": 1e-3, "min_served_tokens_compared": 8,
                          "control_precision": "fp8"})
    return cfg


def tiny_mix(mix: dict) -> dict:
    mix = copy.deepcopy(mix)
    if mix.get("kind") == "closed":
        mix.update({"clients": 6, "max_context": 256, "warmup_s": 1.0,
                    "prompt_tokens": {"median": 40, "sigma": 0.5, "clip": [16, 96]},
                    "reply_tokens": {"median": 8, "sigma": 0.3, "clip": [6, 12]}})
        return mix
    mix.update({"adapters": 8, "adapters_registered": 8, "max_context": 256,
                "session_rate_per_s": 4.0, "arrival_block_s": 1.0, "warmup_s": 1.0,
                "template_tokens": 8, "turns": [2, 3],
                "user_tokens": {"median": 12, "sigma": 0.5, "clip": [4, 24]},
                "reply_tokens": {"median": 6, "sigma": 0.3, "clip": [4, 8]},
                "think_s": {"mean": 0.2}})
    return mix


def run(cell=CELL, root=spec.ROOT, trace=False, cfg=None, mix=None, control=False):
    bench = spec.load_benchmark(root)
    c = spec.find_cell(bench, cell)
    cfg = cfg or tiny(spec.find_config(bench, c["config"], root))
    mix = mix or tiny_mix(spec.load_mix(c["traffic"], root / "bench"))
    return driver.run_cell(cell, 20260417 + 2**32, 1.5, trace, root=root,
                           allow_cpu=True, cfg_override=cfg, mix_override=mix,
                           compile_cache=False, control=control)


def test_clean_run_is_correct_and_the_control_is_not():
    """The program served in float32 passes; the fp8 reference put in its
    place (its first token at each served position) and judged by the same
    comparison comes out not correct."""
    r = run(control=True)
    assert r["correct"] is True, r["checks"]
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"itl_p95_ms", "tokens_per_s", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["platform"] == "cpu"
    assert r["checks"]["served_tokens_compared"]["value"] >= 8
    ctl = r["diag"]["control"]
    assert ctl["correct"] is False
    assert ctl["checks"]["served_tokens_compared"] == r["checks"]["served_tokens_compared"]
    assert ctl["checks"]["max_logit_gap"]["value"] > ctl["checks"]["max_logit_gap"]["limit"]


def test_altered_token_is_caught(monkeypatch):
    """Break the timed path underneath: every mixed step puts a fixed token
    first, as a sampling bug would. The run must come out not correct."""
    from repro.serving import prefill

    real = prefill.BatchPrefill.__call__

    def altered(self, *a, **kw):
        logits, cache = real(self, *a, **kw)
        return logits.at[:, 7].add(1e4), cache

    monkeypatch.setattr(prefill.BatchPrefill, "__call__", altered)
    r = run()
    assert r["correct"] is False
    chk = r["checks"]["max_logit_gap"]
    assert chk["value"] > chk["limit"]


def test_no_tpu_is_refused():
    with pytest.raises(driver.NoAccelerator):
        driver.require_accelerator(1)


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """Adding a cell takes new files and new entries only: copy the
    benchmark, add a configuration, a mix and a per-layer metric beside the
    existing ones, and the harness runs the new cell and reports the new
    metric without any existing file changing."""
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = tiny(spec.find_config(bench, "qwen3-0.6b", spec.ROOT))
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tiny-chat.json").write_text(json.dumps(
        {"base": "batch-unique", **tiny_mix({"kind": "closed"})}))
    (root / "bench/metrics/requests_due.py").write_text(
        "def read(run):\n"
        "    return float(sum(1 for o in run.obs.values() if run.w0 <= o.due < run.w1))\n")
    bench["configs"].append({"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.tiny-chat", "config": "tiny",
                               "traffic": "tiny-chat", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "requests_due", "unit": "requests", "better": "higher",
                               "source": "host_clock", "layer": "load generator",
                               "moves": "itl_p95_ms", "workloads": ["tiny.tiny-chat"]})
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if "workloads" in m]  # every current one names its cells
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    r = run(cell="tiny.tiny-chat", root=root, trace=True, cfg=cfg,
            mix=spec.load_mix("tiny-chat", root / "bench"))
    assert r["metrics"]["requests_due"]["value"] == r["attempted"] > 0
    assert {p: p.read_bytes() for p in before} == before


def test_comparison_sides_with_a_cold_engine():
    """Second witness for the reuse path: a follow-up turn served from the
    cache is compared with the same prompt served by a fresh engine that
    has nothing cached. On the CPU both run in float32, so the comparison
    must read zero exactly when the two engines serve the same tokens, and
    more than zero when they do not. (A turn whose history ends on a block
    boundary reuses a block holding the KV of a token the engine never ran;
    PERF.md, Open questions.)"""
    from repro.serving import Request

    bench = spec.load_benchmark()
    cfg = tiny(spec.find_config(bench, "qwen3-0.6b"))
    qwen3 = spec.model_module(cfg)
    c = qwen3.Consts.from_config(cfg)
    rng = np.random.default_rng(3)

    def serve(engine, prompt, new, rid):
        r = Request(rid, "lora-1", tuple(prompt), new)
        engine.submit(r)
        while r.phase.name != "FINISHED":
            engine.step()
        return tuple(r.output_tokens)

    warm, _, params, adapters = driver.build(qwen3, cfg, 5, 2)
    cases = []
    for plen in (24, 25):  # with 8 new tokens: 32 (a block boundary) and 33
        first = [int(x) for x in rng.integers(0, 512, plen)]
        reply = serve(warm, first, 8, f"a{plen}")
        second = list(first) + list(reply) + [int(x) for x in rng.integers(0, 512, 20)]
        cases.append((second, serve(warm, second, 12, f"b{plen}")))
    cold, _, _, _ = driver.build(qwen3, cfg, 5, 2)
    for i, (prompt, served) in enumerate(cases):
        fresh = serve(cold, prompt, 12, f"c{i}")
        gap = driver.compare(qwen3, params, adapters, c, [(1, tuple(prompt), served, 0)],
                             256)[0]
        assert (gap.max() == 0.0) == (served == fresh), (i, gap.max())


def test_session_knobs_and_every_reader_run_through_the_harness(tmp_path):
    """A session cell that turns on every knob of the generator (bursts, a
    moving hot set, a system prompt served without the adapter, a class
    with a priority and a deadline) runs through the engine and is correct,
    and every per-layer reader in ``bench/metrics/`` and every end-to-end
    metric of ``driver.END_TO_END`` reads it. Single-turn sessions: a later
    turn could reuse a block the engine left stale (PERF.md, Open
    questions)."""
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = spec.load_benchmark()
    cfg = tiny(spec.find_config(bench, "qwen3-0.6b"))
    mix = tiny_mix(spec.load_mix("chat-lora32"))
    mix.update({"turns": [1, 1], "adapters_registered": 8,
                "system_prompt": {"tokens": 24, "shared": True},
                "adapter_rotation": {"every_s": 0.5, "shift": 3},
                "rate_modulation": {"sigma": 0.6, "interval_s": 0.5},
                "classes": [{"share": 0.4, "priority": 1, "ttft_deadline_s": 0.5}]})
    cell = "qwen3-0.6b.sessions"
    readers = sorted(p.stem for p in (root / "bench/metrics").glob("*.py"))
    bench["workloads"] = [{"name": cell, "config": "qwen3-0.6b", "traffic": "chat-lora32",
                           "chips": 1, "why": "test"}]
    bench["end_to_end"] = [{"name": n, "unit": "x", "better": "lower", "bound": 0.1,
                            "source": "host_clock"} for n in [*driver.END_TO_END, "setup_s"]]
    bench["per_layer"] = [{"name": n, "unit": "x", "better": "lower", "source": "host_clock",
                           "layer": "test", "moves": "setup_s"} for n in readers]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    traced = run(cell=cell, root=root, trace=True, cfg=cfg, mix=mix)
    assert traced["correct"] is True, traced["checks"]
    for name in ("arrival_lag_p95_ms", "queue_wait_p95_ms", "prompt_cached_share",
                 "adapter_miss_share", "window_compile_s"):
        assert isinstance(traced["metrics"][name]["value"], float), name
    assert set(traced["metrics"]) <= set(readers)
    plain = run(cell=cell, root=root, cfg=cfg, mix=mix)
    assert set(plain["metrics"]) == {*driver.END_TO_END, "setup_s"}
    assert plain["correct"] is True, plain["checks"]
