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
import re
from dataclasses import replace

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


# The served configurations and the cache length each cell serves.
SERVED = {
    "phi3-mini-16l": (replace(get_config("phi3-mini-3.8b"), n_layers=16,
                              stages=()), 2048),
    "qwen2-0.5b": (get_config("qwen2-0.5b"), 512),
}


@pytest.fixture(scope="module")
def served_step(one_chip):
    """The engine's compiled serve step and its abstract caches, by name of
    a served configuration, compiled once for the module."""
    from repro.serving.engine import jit_serving_steps

    compiled = {}

    def get(name):
        if name not in compiled:
            cfg, cache_len = SERVED[name]
            caches = _on(one_chip, M.abstract_caches(cfg, 1, cache_len))
            _, serve = jit_serving_steps(cfg, cache_len)
            token = jax.ShapeDtypeStruct((1, 1), jnp.int32, sharding=one_chip)
            pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
            compiled[name] = serve.lower(
                _on(one_chip, M.abstract_params(cfg)), caches, token,
                pos).compile(), caches
        return compiled[name]

    return get


def test_phi3_stage_serve_step_donates_its_cache_on_v5e(served_step):
    """The engine's serve step for a 16-layer Phi-3-mini stage (published
    widths, 2047-token window, the 2048-slot ring) fits one chip and writes
    into the cache it is given: the whole cache is aliased to the output,
    so a queue of steps holds one cache, not one per step, and no second
    buffer of its size is made inside the step (caches scanned in and out
    of the layer loop as xs/ys took one, 0.8 GB, and a copy per step)."""
    compiled, caches = served_step("phi3-mini-16l")
    mem = compiled.memory_analysis()
    cache_bytes = sum(c.size * c.dtype.itemsize
                      for c in jax.tree.leaves(caches))
    assert mem.alias_size_in_bytes >= cache_bytes > 0.8e9
    assert mem.temp_size_in_bytes < 0.01 * cache_bytes
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, f"serve step needs {used} bytes"


def _materialised(hlo: str):
    """(dtype, dims) of every instruction of the optimised HLO that is not
    inside a fused computation, so whose result is a buffer of its own."""
    fused = set(re.findall(r" fusion\(.*?calls=(%[\w.\-]+)", hlo))
    comp, out = None, []
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        inst = re.match(r"\s+(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]", line)
        if inst and comp not in fused:
            out.append((inst.group(1),
                        [int(n) for n in inst.group(2).split(",") if n]))
    return out


@pytest.mark.parametrize("name", list(SERVED))
def test_serve_step_moves_one_kv_row_a_layer_on_v5e(name, served_step):
    """A decode step writes each attention layer's new K/V row into the
    stacked cache and reads the layer's K/V where it lies.  So no buffer
    the size of one layer's K, in any axis order, is made: taking the
    slice out, two layout copies of it and the write-back made five whole
    passes a layer for K and five for V.  And the bytes the step accesses
    stay within a tenth of what it needs: one layer's weights and one
    layer's K/V (XLA counts the layer scan's body once, and a dynamic
    slice fused into its consumer as the slice), plus the head and the
    final norm.  Those passes put the 16-layer Phi-3 stage's step at
    1.36e9 bytes, against the 0.90e9 it needs."""
    compiled, caches = served_step(name)
    cfg, cache_len = SERVED[name]
    layer_kv = sorted(n for n in (cache_len, cfg.n_kv_heads,
                                  cfg.resolved_head_dim) if n > 1)
    whole_layer = [(dt, dims) for dt, dims in _materialised(compiled.as_text())
                   if sorted(n for n in dims if n > 1) == layer_kv]
    assert not whole_layer, whole_layer

    params = M.abstract_params(cfg)
    nbytes = lambda tree: sum(a.size * a.dtype.itemsize
                              for a in jax.tree.leaves(tree))
    head = params["embed" if cfg.tie_embeddings else "lm_head"]
    needed = (nbytes([params[k] for k in params if k.startswith("dec")])
              / cfg.n_layers + nbytes(caches) / cfg.n_layers
              + nbytes([head, params["final_norm"]]))
    accessed = compiled.cost_analysis()["bytes accessed"]
    assert accessed < 1.1 * needed, (accessed, needed)
