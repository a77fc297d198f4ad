"""prefill + decode_step must agree with the full-sequence forward for every
architecture (MoE capacity pinned high so no tokens drop — capacity-based
dispatch is not strictly causal under drops, which is expected)."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.configs import ARCH_IDS, get_smoke_config
from repro.models import model as M

T = 12
TOL = 2e-4


def _uncap(cfg):
    if cfg.moe is not None:
        cfg = replace(cfg, moe=replace(cfg.moe, capacity_factor=8.0))
    return cfg


def _batches(cfg, key):
    tokens = jax.random.randint(key, (2, T + 1), 0, cfg.vocab_size)
    full = {"tokens": tokens}
    pre = {"tokens": tokens[:, :T]}
    if cfg.modality_embed_dim:
        n_mod = cfg.n_modality_tokens or T
        emb = jax.random.normal(jax.random.PRNGKey(9),
                                (2, n_mod, cfg.modality_embed_dim))
        full["modality_emb"] = emb
        pre["modality_emb"] = emb
    return full, pre, tokens


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_then_decode_matches_forward(arch):
    cfg = _uncap(get_smoke_config(arch))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    full, pre, tokens = _batches(cfg, jax.random.PRNGKey(1))

    # positions are offset by any prepended image tokens (decoder-only VLM)
    pos_off = 0
    if cfg.modality_embed_dim and not cfg.is_encoder_decoder:
        pos_off = full["modality_emb"].shape[1]

    full_logits, _ = M.forward(params, cfg, full)
    pre_logits, caches = M.prefill(params, cfg, pre, cache_len=64)
    err_pre = float(jnp.abs(
        pre_logits[:, 0] - full_logits[:, pos_off + T - 1]).max())
    assert err_pre < TOL, f"prefill mismatch {err_pre}"

    dec_logits, caches = M.decode_step(
        params, cfg, caches, tokens[:, T:T + 1], jnp.int32(T + pos_off))
    err_dec = float(jnp.abs(
        dec_logits[:, 0] - full_logits[:, pos_off + T]).max())
    assert err_dec < TOL, f"decode mismatch {err_dec}"


def _twice(cfg):
    """The config with each stage's pattern repeated twice, so a decode
    step scans two layers of each kind."""
    stages = tuple(replace(st, repeats=2) for st in cfg.stages)
    return replace(cfg, stages=stages,
                   n_layers=sum(st.n_layers for st in stages))


@pytest.mark.parametrize("arch,widen", [
    ("smollm-135m", None),
    ("qwen2-0.5b", None),
    # attention (its K/V row written into the stacked cache) beside mamba
    # (its state taken out and written back) in one layer scan
    ("jamba-1.5-large-398b", _twice),
], ids=["smollm-135m", "qwen2-0.5b", "attn-and-mamba"])
def test_multi_step_decode_chain(arch, widen):
    """Three consecutive decode steps track the full forward."""
    cfg = _uncap(get_smoke_config(arch))
    if widen is not None:
        cfg = widen(cfg)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, T + 3), 0,
                                cfg.vocab_size)
    full_logits, _ = M.forward(params, cfg, {"tokens": tokens})
    _, caches = M.prefill(params, cfg, {"tokens": tokens[:, :T]},
                          cache_len=32)
    for i in range(3):
        dec_logits, caches = M.decode_step(
            params, cfg, caches, tokens[:, T + i:T + i + 1], jnp.int32(T + i))
        err = float(jnp.abs(dec_logits[:, 0] - full_logits[:, T + i]).max())
        assert err < TOL, f"step {i}: {err}"


@pytest.mark.parametrize("prompt,steps", [
    (16, 4),     # the prompt overruns the 8-slot ring; decode writes on
    (4, 12),     # the prompt fits; decode's own writes run past slot 7
], ids=["prefill-wraps", "decode-wraps"])
def test_sliding_window_decode_matches_windowed_forward(prompt, steps):
    """Rotating cache + window masks == full-seq sliding-window attention."""
    cfg = _uncap(get_smoke_config("smollm-135m"))
    cfg = replace(cfg, sliding_window=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 21), 0,
                                cfg.vocab_size)
    full_logits, _ = M.forward(params, cfg, {"tokens": tokens})
    # cache_len == window -> rotating writes
    _, caches = M.prefill(params, cfg, {"tokens": tokens[:, :prompt]},
                          cache_len=8)
    for i in range(prompt, prompt + steps):
        dec_logits, caches = M.decode_step(
            params, cfg, caches, tokens[:, i:i + 1], jnp.int32(i))
        err = float(jnp.abs(dec_logits[:, 0] - full_logits[:, i]).max())
        assert err < TOL, f"windowed step at {i}: {err}"


def _windowed(cfg):
    return replace(cfg, sliding_window=8)


def _mlstm_chunked(cfg):
    """mLSTM in chunks of 5: a 12-token prompt ends in a padded chunk."""
    return replace(cfg, mlstm_chunk=5)


K = 5    # prompt of the decoded side: K prefilled, T - K decoded


@pytest.mark.parametrize("arch,widen,t,k,cache_len", [
    *[(a, None, T, K, 64) for a in ARCH_IDS],
    # 16 positions through an 8-slot ring: a prefill that wraps it, against
    # 4 prefilled and 12 decoded one slot at a time; 19 leave the ring
    # rotated (position 11 in slot 3), as a long prompt leaves Phi-3's
    ("smollm-135m", _windowed, 16, 4, 8),
    ("deepseek-v2-236b", _windowed, 16, 4, 8),
    ("smollm-135m", _windowed, 19, 4, 8),
    ("xlstm-1.3b", _mlstm_chunked, T, K, 64),
], ids=[*ARCH_IDS, "attn-ring", "mla-ring", "attn-ring-rotated",
        "mlstm-chunked"])
def test_prefill_cache_equals_decoded_cache(arch, widen, t, k, cache_len):
    """A prompt's prefill leaves the caches that prefilling its first k
    tokens and decoding the rest leaves: each mixer's parallel pass writes
    the same cache as its decode steps, slots and state alike."""
    cfg = _uncap(get_smoke_config(arch))
    if widen is not None:
        cfg = widen(cfg)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, t), 0,
                                cfg.vocab_size)
    extra, pos_off = {}, 0
    if cfg.modality_embed_dim:
        n_mod = cfg.n_modality_tokens or T
        extra["modality_emb"] = jax.random.normal(
            jax.random.PRNGKey(9), (2, n_mod, cfg.modality_embed_dim))
        if not cfg.is_encoder_decoder:
            pos_off = n_mod

    _, whole = M.prefill(params, cfg, {"tokens": tokens, **extra}, cache_len)
    _, stepped = M.prefill(params, cfg, {"tokens": tokens[:, :k], **extra},
                           cache_len)
    for i in range(k, t):
        _, stepped = M.decode_step(params, cfg, stepped, tokens[:, i:i + 1],
                                   jnp.int32(pos_off + i))

    got = jax.tree_util.tree_leaves_with_path(whole)
    want = jax.tree_util.tree_leaves(stepped)
    assert len(got) == len(want)
    for (path, a), b in zip(got, want):
        name = jax.tree_util.keystr(path)
        assert a.shape == b.shape and a.dtype == b.dtype, name
        if name.endswith("['positions']"):
            assert (a == b).all(), name
        else:
            assert_allclose(np.asarray(a), np.asarray(b), atol=TOL,
                            rtol=TOL, err_msg=name)
