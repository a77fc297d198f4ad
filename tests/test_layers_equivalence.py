"""Mixer-level equivalence properties: each recurrent decode form must match
its parallel training form (the core correctness invariant of every cache)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.configs import get_smoke_config
from repro.models.layers import mamba as Mb
from repro.models.layers import mla as L
from repro.models.layers import xlstm as X
from repro.models.layers import attention as A
from repro.models.layers.common import LayerCtx


def _ctx(cfg, start, t=1):
    """The context of positions start..start+t-1."""
    return LayerCtx(cfg, jnp.arange(start, start + t))


def test_mlstm_parallel_equals_recurrent():
    cfg = get_smoke_config("xlstm-1.3b")
    p = X.mlstm_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (2, 11, cfg.d_model))
    y_par, _ = X.mlstm_apply(p, x, _ctx(cfg, 0, 11))
    cache = X.init_mlstm_cache(2, 0, cfg, jnp.float32)
    outs = []
    for t in range(11):
        y, cache = X.mlstm_apply(p, x[:, t:t + 1], _ctx(cfg, t), cache=cache)
        outs.append(y)
    y_rec = jnp.concatenate(outs, 1)
    assert_allclose(np.asarray(y_par), np.asarray(y_rec), atol=2e-5,
                    rtol=2e-4)


def test_slstm_scan_equals_step():
    cfg = get_smoke_config("xlstm-1.3b")
    p = X.slstm_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (2, 9, cfg.d_model))
    y_scan, _ = X.slstm_apply(p, x, _ctx(cfg, 0, 9))
    cache = X.init_slstm_cache(2, 0, cfg, jnp.float32)
    outs = []
    for t in range(9):
        y, cache = X.slstm_apply(p, x[:, t:t + 1], _ctx(cfg, t), cache=cache)
        outs.append(y)
    assert_allclose(np.asarray(y_scan), np.asarray(jnp.concatenate(outs, 1)),
                    atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("t", [5, 17, 40])
def test_mamba_chunked_scan_equals_step(t, monkeypatch):
    """Chunked associative scan == sequential recurrence, incl. chunk pads."""
    monkeypatch.setattr(Mb, "CHUNK", 16)
    cfg = get_smoke_config("jamba-1.5-large-398b")
    p = Mb.mamba_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, t, cfg.d_model))
    y_par, _ = Mb.mamba_apply(p, x, _ctx(cfg, 0, t))
    cache = Mb.init_mamba_cache(2, 0, cfg, jnp.float32)
    outs = []
    for i in range(t):
        y, cache = Mb.mamba_apply(p, x[:, i:i + 1], _ctx(cfg, i), cache=cache)
        outs.append(y)
    assert_allclose(np.asarray(y_par), np.asarray(jnp.concatenate(outs, 1)),
                    atol=3e-5, rtol=3e-4)


@pytest.mark.parametrize("qlora", [0, 48])
def test_mla_absorbed_decode_equals_naive(qlora):
    from dataclasses import replace
    cfg = get_smoke_config("deepseek-v3-671b")
    cfg = replace(cfg, mla=replace(cfg.mla, q_lora_rank=qlora))
    p = L.mla_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, cfg.d_model))
    y_naive, _ = L.mla_apply(p, x, _ctx(cfg, 0, 9))
    cache = L.init_mla_cache(2, 16, cfg, jnp.float32)
    outs = []
    for t in range(9):
        y, cache = L.mla_apply(p, x[:, t:t + 1], _ctx(cfg, t), cache=cache)
        outs.append(y)
    assert_allclose(np.asarray(y_naive), np.asarray(jnp.concatenate(outs, 1)),
                    atol=2e-5, rtol=2e-4)


def test_mla_cache_is_compressed():
    """The MLA serving win: cache stores rank-R latents, not H*D keys."""
    cfg = get_smoke_config("deepseek-v3-671b")
    cache = L.init_mla_cache(1, 64, cfg, jnp.float32)
    mla_bytes = sum(np.prod(v.shape) for k, v in cache.items()
                    if k != "positions")
    full_kv_bytes = 2 * 64 * cfg.n_heads * cfg.resolved_head_dim
    assert mla_bytes < 0.35 * full_kv_bytes


def test_gqa_attention_window_equals_full_when_window_large():
    cfg = get_smoke_config("qwen2-0.5b")
    p = A.attn_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    pos = jnp.arange(8)
    y_full, _ = A.attn_apply(p, x, LayerCtx(cfg, pos, window=0))
    y_win, _ = A.attn_apply(p, x, LayerCtx(cfg, pos, window=100))
    assert_allclose(np.asarray(y_full), np.asarray(y_win), atol=1e-6)


@pytest.mark.parametrize("chunk,t", [(4, 16), (8, 11), (5, 17)])
def test_mlstm_chunked_equals_naive(chunk, t):
    """Chunkwise-parallel mLSTM (§Perf) is exactly the naive T x T form
    (same stabiliser semantics), including ragged final chunks."""
    from dataclasses import replace
    cfg = get_smoke_config("xlstm-1.3b")
    p = X.mlstm_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (2, t, cfg.d_model))
    y_naive, _ = X.mlstm_apply(p, x, _ctx(cfg, 0, t))
    y_chunk, _ = X.mlstm_apply(p, x, _ctx(replace(cfg, mlstm_chunk=chunk), 0,
                                          t))
    assert_allclose(np.asarray(y_naive), np.asarray(y_chunk), atol=2e-5,
                    rtol=2e-4)
