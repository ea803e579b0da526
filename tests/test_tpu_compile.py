"""Ahead-of-time Mosaic compiles of the serving kernels for a TPU v5e.

Nothing here runs on a chip: each case lowers a kernel (or the engine's
mixed step) for a described ``v5e:2x2`` topology and compiles it with the
TPU compiler, which refuses what the chip would refuse — block shapes that
break the (8, 128) tiling, too much VMEM, programs that do not fit. Shapes
are qwen3-0.6b's published widths in float32, the engine's dtype.

Under ``JAX_PLATFORMS=cpu`` the kernel wrappers would pick interpret mode
(``repro.kernels.ops._auto_interpret``) and a compile would lower the
*interpreted* kernel, passing for nothing; the ``compile_v5e`` fixture
steers them to Mosaic and every case asserts a ``tpu_custom_call`` in the
compiled program. The topology is described inside a fixture, never at
import, and all such compiles live in this one file.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.kernels import ops
from repro.models import build_model
from repro.serving.prefill import BatchPrefill, make_buckets

QWEN3 = configs.get("qwen3-0.6b")
H, HKV, D = QWEN3.num_heads, QWEN3.num_kv_heads, QWEN3.resolved_head_dim
D_MODEL, RANK = QWEN3.d_model, QWEN3.lora.rank
SLOTS = QWEN3.lora.max_adapters
B, T = 8, 1024  # engine batch slots, cache length
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep the cache off around these."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def compile_v5e(one_chip, no_persistent_cache, monkeypatch):
    """Returns ``compile(fn, *args)``: jit-compiles ``fn`` for one v5e chip
    from shape trees and returns the compiled program's text, after checking
    that a Mosaic kernel is in it."""
    monkeypatch.setattr(ops, "_auto_interpret", lambda: False)

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
            tree)

    def compile_(fn, *args):
        text = jax.jit(fn).lower(*on_chip(args)).compile().as_text()
        assert "tpu_custom_call" in text
        return text

    return compile_


def sds(*shape, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("S", [1, 8, 64])
def test_ragged_extend_compiles(compile_v5e, S):
    compile_v5e(ops.ragged_extend, sds(B, S, H, D), sds(B, T, HKV, D),
                sds(B, T, HKV, D), sds(B, dtype=I32), sds(B, dtype=I32))


def test_paged_attention_compiles(compile_v5e):
    page = 128
    pages = B * T // page
    compile_v5e(ops.paged_attention, sds(B, H, D), sds(pages, page, HKV, D),
                sds(pages, page, HKV, D), sds(B, T // page, dtype=I32),
                sds(B, dtype=I32))


@pytest.mark.parametrize("S", [1, 64])
@pytest.mark.parametrize("proj", ["q", "o"])
def test_fused_sgmv_compiles(compile_v5e, proj, S):
    d_in, d_out = (D_MODEL, H * D) if proj == "q" else (H * D, D_MODEL)
    compile_v5e(ops.fused_sgmv, sds(B, S, d_in), sds(d_in, d_out),
                sds(SLOTS, d_in, RANK), sds(SLOTS, RANK, d_out),
                sds(B, dtype=I32))


def test_flash_prefill_compiles(compile_v5e):
    S = 512
    compile_v5e(ops.flash_prefill, sds(1, H, S, D), sds(1, HKV, S, D),
                sds(1, HKV, S, D))


def test_engine_mixed_step_compiles(compile_v5e):
    """The BatchPrefill step at its largest bucket, as the engine builds it:
    real widths, depth cut to 2 layers (depth changes no kernel's shape)."""
    cfg = dataclasses.replace(QWEN3, num_layers=2)
    model = build_model(cfg, dtype=F32, kernel_backend="pallas")
    buckets = make_buckets(8, 64)
    step = BatchPrefill(model, buckets).step_fn(buckets[-1])
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(model.init_params, key)
    lora = jax.eval_shape(lambda k: model.init_lora(k, SLOTS), key)
    cache = jax.eval_shape(lambda: model.init_cache(B, T))
    S = buckets[-1]
    text = compile_v5e(step, params, lora, cache, sds(B, S, dtype=I32),
                       sds(B, dtype=I32), sds(B, dtype=I32),
                       sds(B, dtype=jnp.bool_), sds(B, dtype=I32))
    # attention (ragged_extend) and the LoRA projections (fused_sgmv)
    assert text.count("tpu_custom_call") >= 2
