"""Model modules (``bench/models/<model_type>.py``): Qwen3's module gives the
weights, reference logits and work counts recorded before its functions
moved there out of ``perfkit/``; a configuration finds its module by its
published ``model_type``, and a missing module is named; a second
architecture runs from new files alone; a module without a kernel's
``<kernel>_need`` for a kernel that ran is named as a fault."""

import json
import pathlib
import shutil
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfkit import readers, spec, weights
from test_perfkit_harness import run, tiny, tiny_mix

GOLDEN = pathlib.Path(__file__).parent / "data" / "qwen3-golden.json"
SEED = 20260417 + 2**32
# rows of one dispatch: (start, real tokens, emits logits)
ROWS = ([(0, 512, True)],
        [(37, 16, False), (100, 1, True), (700, 1, True)],
        [(0, 128, True), (1500, 1, True), (2000, 48, True)])


def record(m, cfg: dict) -> dict:
    """What the golden file holds, read through model module ``m``: every
    weight leaf's sum and first values at the tiny size, the reference's
    logits at three positions, and the work counts at published widths."""
    s = m.Shape.from_config(tiny(cfg))
    kp, ka = jax.random.split(weights.seed_key(SEED))
    params, adapters = m.make_params(kp, s), m.make_adapters(ka, s, 3)
    leaves = {}
    for name, tree in (("params", params), ("adapters", adapters)):
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            x = np.asarray(leaf, np.float64)
            leaves[name + jax.tree_util.keystr(path)] = [float(x.sum()),
                                                         x.ravel()[:4].tolist()]
    toks = np.random.default_rng(SEED).integers(0, s.vocab, 48)
    logits = m.logits_at(params, {t: (a[1], b[1]) for t, (a, b) in adapters.items()},
                         jnp.asarray(toks, jnp.int32), jnp.asarray([5, 20, 47], jnp.int32),
                         jnp.int32(10), c=m.Consts.from_config(tiny(cfg)),
                         precision="highest")
    full = m.Shape.from_config(cfg)
    counts = []
    for rows in ROWS:
        tokens = sum(n for _, n, _ in rows)
        counts.append({
            "span_flops": [m.span_flops(full, st, n, e) for st, n, e in rows],
            "ragged_extend_need": list(m.ragged_extend_need(
                full, [(st, n) for st, n, _ in rows])),
            "fused_sgmv_need": {t: list(m.fused_sgmv_need(full, t, tokens, len(rows)))
                                for t in full.lora_targets}})
    return json.loads(json.dumps({
        "leaves": leaves, "logits": np.asarray(logits).tolist(), "counts": counts,
        "kv_bytes_per_token": m.kv_bytes_per_token(full),
        "adapter_bytes": m.adapter_bytes(full)}))


def qwen3_cfg() -> dict:
    return spec.find_config(spec.load_benchmark(), "qwen3-0.6b")


def test_qwen3_module_matches_what_perfkit_gave_before_the_move():
    """Same seed, bit-identical weights and exact work counts; the
    reference's logits within 1e-6."""
    got, want = record(spec.model_module(qwen3_cfg()), qwen3_cfg()), json.loads(
        GOLDEN.read_text())
    assert got["leaves"] == want["leaves"]
    assert got["counts"] == want["counts"]
    assert (got["kv_bytes_per_token"], got["adapter_bytes"]) == (
        want["kv_bytes_per_token"], want["adapter_bytes"])
    np.testing.assert_allclose(got["logits"], want["logits"], rtol=0, atol=1e-6)


def test_unknown_model_type_names_the_path_it_looked_for():
    cfg = dict(qwen3_cfg(), model_type="no_such_block")
    with pytest.raises(spec.SpecError, match=r"bench/models/no_such_block\.py"):
        spec.model_module(cfg)


@pytest.mark.parametrize("kernel, least_s", [
    ("ragged_extend", readers.ragged_extend_least_s),
    ("fused_sgmv", readers.fused_sgmv_least_s)])
def test_roofline_is_left_out_where_the_model_has_no_need_for_the_kernel(
        kernel, least_s):
    """A latent-attention model runs no ``ragged_extend``: the trace holds
    none of it and the reader returns None, never 0. A module that leaves
    out a kernel's ``<kernel>_need`` while the trace shows the kernel ran
    is a fault, and the reader raises rather than drop the roofline."""
    qwen3 = spec.model_module(qwen3_cfg())
    s = qwen3.Shape.from_config(qwen3_cfg())
    kernel_s = {kernel: 0.5}
    trace = types.SimpleNamespace(kernel_s=lambda name: kernel_s.get(name, 0.0))
    calls = [types.SimpleNamespace(kind="mixed", rows=[(0, 64, True, "lora-0")])]
    run = types.SimpleNamespace(trace=trace, device_kind="TPU v5 lite", calls=calls,
                                shape=s, model=qwen3)
    assert readers.kernel_roofline(run, kernel, least_s) > 0
    run.model = types.ModuleType("needless")
    run.model.__dict__.update({k: v for k, v in vars(qwen3).items()
                               if k not in (f"{kernel}_need", "__name__")})
    with pytest.raises(spec.SpecError, match=f"needless has no {kernel}_need"):
        readers.kernel_roofline(run, kernel, least_s)
    kernel_s.clear()
    assert readers.kernel_roofline(run, kernel, least_s) is None


def test_a_second_block_runs_from_new_files_alone(tmp_path):
    """A block without q/k RMSNorm (``data/llama.py``) joins a copy of the
    benchmark as a model module, a configuration naming its
    ``model_type``, a mix, a cell and a per-layer metric, all new files and
    entries: the engine serves it through the program's ``qk_norm=False``
    path (the weight tree is checked against the engine's), its own
    reference finds it correct and its fp8 control not, and no existing
    file changes."""
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}
    shutil.copy(GOLDEN.with_name("llama.py"), root / "bench/models/llama.py")
    cfg = tiny(qwen3_cfg())
    cfg["model_type"] = "llama"
    cfg["serving"]["arch"] = "llama-7b"
    (root / "bench/configs/tiny-llama.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tiny-batch.json").write_text(json.dumps(
        {"base": "batch-unique", **tiny_mix({"kind": "closed"})}))
    (root / "bench/metrics/window_gflops.py").write_text(
        "from perfkit import readers\n\n\n"
        "def read(run):\n"
        "    return readers.step_flops(run) / 1e9 if run.calls else None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = "tiny-llama.tiny-batch"
    bench["configs"].append({"name": "tiny-llama", "source": "test",
                             "file": "bench/configs/tiny-llama.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": "tiny-llama",
                               "traffic": "tiny-batch", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    bench["per_layer"].append({"name": "window_gflops", "unit": "GFLOP",
                               "better": "higher", "source": "program_counter",
                               "layer": "model step", "moves": "tokens_per_s",
                               "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    llama = spec.model_module(cfg, root / "bench")
    mixer = jax.eval_shape(llama.make_params, jax.random.PRNGKey(0),
                           llama.Shape.from_config(cfg))["layers"]["mixer"]
    assert set(mixer) == {"wq", "wk", "wv", "wo"}
    mix = spec.load_mix("tiny-batch", root / "bench")
    plain = run(cell=cell, root=root, cfg=cfg, mix=mix, control=True)
    assert plain["correct"] is True, plain["checks"]
    assert set(plain["metrics"]) == {"itl_p95_ms", "tokens_per_s", "setup_s"}
    assert plain["diag"]["control"]["correct"] is False
    traced = run(cell=cell, root=root, trace=True, cfg=cfg, mix=mix)
    assert traced["correct"] is True, traced["checks"]
    assert traced["metrics"]["window_gflops"]["value"] > 0
    assert {p: p.read_bytes() for p in before} == before
