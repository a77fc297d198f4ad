"""Compile the served path for a TPU v5e that is described, not attached.

The TPU compiler is installed alongside JAX, so these tests catch what only
the chip's compiler refuses (unaligned kernel slices, too much fast memory,
a step that does not fit the chip) without a chip.  Nothing runs: a compile
that passes says nothing about results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
so every test worker must collect the same tests and only the worker given
this file loads it.  Keep all such compiles in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.kernel import decode_attention
from repro.kernels.flash_attention.kernel import flash_attention
from repro.models import model as M
from repro.training.steps import make_prefill_step, make_serve_step

HBM_BYTES = 16 * 1024 ** 3          # one v5e chip
PROMPT_LEN, CACHE_LEN = 16, 256     # the shapes chip_smoke.py serves


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host, with JAX's persistent
    compilation cache off: a compile for a described chip is written to the
    cache but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _step_args(cfg, step, one_chip):
    params = _on(one_chip, M.abstract_params(cfg))
    if step == "prefill":
        tokens = jax.ShapeDtypeStruct((1, PROMPT_LEN), jnp.int32,
                                      sharding=one_chip)
        return make_prefill_step(cfg, CACHE_LEN), (params,
                                                   {"tokens": tokens})
    caches = _on(one_chip, M.abstract_caches(cfg, 1, CACHE_LEN))
    token = jax.ShapeDtypeStruct((1, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    return make_serve_step(cfg), (params, caches, token, pos)


@pytest.mark.parametrize("step", ["prefill", "serve"])
def test_qwen2_serving_step_compiles_for_v5e(step, one_chip):
    """The engine's prefill and serve programs at the published qwen2-0.5b
    width fit one chip."""
    cfg = get_config("qwen2-0.5b")
    fn, args = _step_args(cfg, step, one_chip)
    mem = jax.jit(fn).lower(*args).compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 4 * cfg.param_count() * 0.99
    assert used < HBM_BYTES, f"{step} step needs {used} bytes"


def _decode_args(one_chip):
    cfg = get_config("qwen2-0.5b")
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    s = jax.ShapeDtypeStruct
    return (s((1, h, d), jnp.float32, sharding=one_chip),
            s((1, CACHE_LEN, kv, d), jnp.float32, sharding=one_chip),
            s((1, CACHE_LEN, kv, d), jnp.float32, sharding=one_chip),
            s((1, CACHE_LEN), jnp.int32, sharding=one_chip),
            s((), jnp.int32, sharding=one_chip))


def _flash_args(one_chip):
    cfg = get_config("qwen2-0.5b")
    shape = (1, cfg.n_heads, 512, cfg.resolved_head_dim)
    return tuple(jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
                 for _ in range(3))


@pytest.mark.parametrize("kernel,make_args", [
    (decode_attention, _decode_args),
    (flash_attention, _flash_args),
], ids=["decode_attention", "flash_attention"])
def test_attention_kernel_compiles_for_v5e_at_qwen2_width(kernel, make_args,
                                                         one_chip):
    compiled = kernel.lower(*make_args(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_phi3_stage_serve_step_donates_its_cache_on_v5e(one_chip):
    """The engine's serve step for a 16-layer Phi-3-mini stage (published
    widths, 2047-token window, the 2048-slot ring) fits one chip and writes
    into the cache it is given: the whole cache is aliased to the output,
    so a queue of steps holds one cache, not one per step, and no second
    buffer of its size is made inside the step (caches scanned in and out
    of the layer loop as xs/ys took one, 0.8 GB, and a copy per step)."""
    from dataclasses import replace

    from repro.serving.engine import jit_serving_steps

    cfg = replace(get_config("phi3-mini-3.8b"), n_layers=16, stages=())
    caches = _on(one_chip, M.abstract_caches(cfg, 1, 2048))
    _, serve = jit_serving_steps(cfg, 2048)
    token = jax.ShapeDtypeStruct((1, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    mem = serve.lower(_on(one_chip, M.abstract_params(cfg)), caches, token,
                      pos).compile().memory_analysis()
    cache_bytes = sum(c.size * c.dtype.itemsize
                      for c in jax.tree.leaves(caches))
    assert mem.alias_size_in_bytes >= cache_bytes > 0.8e9
    assert mem.temp_size_in_bytes < 0.01 * cache_bytes
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, f"serve step needs {used} bytes"
