"""Entry-point plumbing: pool sizing and exit codes of ``launch/serve.py``,
the compile-cache helper, ``chip_smoke.py``'s chip guard, and the roofline
peaks table."""

import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import configs
from repro.core import make_fastlibra
from repro.launch import compile_cache, serve
from repro.serving import ServingEngine, summarize
from repro.serving.engine import kv_pool_spec

REPO = pathlib.Path(__file__).resolve().parents[1]


# ------------------------------------------------------------ pool sizing
def test_pool_sizes_arithmetic():
    # 1000 B/token at 16 tokens/block: 16000 B blocks; 2 slots x ceil(100/16)
    # = 14 running blocks; 4 adapters x ceil(35000/16000) = 12 blocks
    hbm, host = serve.pool_sizes(
        kv_bytes_per_token=1000, adapter_bytes=35_000, n_adapters=4,
        max_batch_slots=2, max_seq_len=100, block_size=16)
    assert hbm == (2 * 14 + 12) * 16_000
    assert host == (14 + 12) * 16_000


def test_full_config_pools_hold_every_adapter():
    """At published widths the pools are sized in GB from the model: the
    host tier registers every adapter and the HBM tier holds all of them
    beside a full batch of running KV (a fixed 8 MB / 64 MB pool could not
    hold even one f32 rank-32 qwen3-0.6b adapter)."""
    cfg = configs.get("qwen3-0.6b")
    n = 4
    ecfg = serve.engine_config(cfg, full=True, n_adapters=n)
    assert ecfg.max_seq_len >= 1024
    assert 1 << 30 < ecfg.hbm_bytes < 4 << 30
    adapter = serve.adapter_nbytes(cfg)
    assert 30 << 20 < adapter < 40 << 20  # ~37 MB at f32, rank 32
    spec = kv_pool_spec(cfg, ecfg.block_size)
    manager, _ = make_fastlibra(
        ecfg.hbm_bytes, ecfg.host_bytes,
        kv_bytes_per_token=spec.bytes_per_token, block_size=ecfg.block_size)
    for i in range(n):  # raises PoolExhausted if the host tier is short
        manager.register_lora(f"lora-{i}", adapter)
    running = ecfg.max_batch_slots * ecfg.max_seq_len * spec.bytes_per_token
    assert ecfg.hbm_bytes >= n * adapter + running


# ------------------------------------------------------------ serve exits
@pytest.mark.parametrize("unfinished,aborted,rc", [
    (0, 0, 0),
    (2, 0, 1),
    (0, 1, 1),
])
def test_serve_exit_code_follows_report(monkeypatch, unfinished, aborted, rc):
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(
        ServingEngine, "run",
        lambda self: summarize([], 1.0, n_unfinished=unfinished,
                               n_aborted=aborted))
    assert serve.main(["--requests", "2", "--adapters", "2"]) == rc


# ---------------------------------------------------------- compile cache
def test_compile_cache_defaults_into_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = pathlib.Path(compile_cache.compile_cache_dir())
    assert path == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


# ------------------------------------------------------------- chip guard
def test_chip_smoke_refuses_cpu():
    """Without a TPU the smoke test exits non-zero, names the missing chip,
    and stops before it builds anything or prints a result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert proc.stdout == ""


# --------------------------------------------------------- roofline peaks
def test_roofline_peaks_keyed_by_device_kind():
    from benchmarks import roofline

    v5e = roofline.peaks("TPU v5 lite")
    assert (v5e["flops"], v5e["hbm_bw"]) == (197e12, 819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("cpu")
    rl = roofline.kernel_roofline(197e12, 819e9, device_kind="TPU v5 lite")
    assert rl["bound_us"] == pytest.approx(1e6)


def test_kernels_bench_emits_no_compiled_row_off_chip():
    from benchmarks import kernels_bench
    from benchmarks.common import CsvOut

    assert not kernels_bench.ON_TPU
    out = CsvOut()
    x = np.ones((2, 3), np.float32)
    err, t_cmp = kernels_bench._emit_pair(
        out, "kernels/identity", lambda a, interpret: a, lambda a: a,
        (x,), {}, "")
    assert (err, t_cmp) == (0.0, None)
    assert [row[0] for row in out.rows] == ["kernels/identity/interpret"]
