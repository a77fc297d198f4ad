"""Per-architecture smoke tests (deliverable f): reduced variant of each
assigned family, one forward + one train step on CPU, asserting output
shapes and no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.configs.shapes import InputShape
from repro.data import train_batches
from repro.models import model as M
from repro.training import make_train_step
from repro.training.optimizer import AdamWConfig, init_opt_state

SHAPE = InputShape("smoke", seq_len=16, global_batch=2, kind="train")


def _batch(cfg):
    return next(iter(train_batches(cfg, SHAPE)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_no_nans(arch):
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v) for k, v in _batch(cfg).items()}
    logits, aux = M.forward(params, cfg, batch)
    t_total = batch["tokens"].shape[1]
    if cfg.modality_embed_dim and not cfg.is_encoder_decoder:
        t_total += batch["modality_emb"].shape[1]
    assert logits.shape == (2, t_total, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits).any())
    assert not bool(jnp.isnan(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_train_step(arch):
    cfg = get_smoke_config(arch)
    opt = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = init_opt_state(opt, params)
    step = jax.jit(make_train_step(cfg, opt))
    batch = _batch(cfg)
    params2, opt_state2, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert float(metrics["grad_norm"]) > 0.0
    # at least the embedding moved
    delta = float(jnp.abs(params2["embed"] - params["embed"]).max())
    assert delta > 0.0
    # no NaNs anywhere in the updated tree
    for leaf in jax.tree.leaves(params2):
        assert not bool(jnp.isnan(leaf).any())


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The full (dry-run) configs carry the exact assigned dimensions."""
    cfg = get_config(arch)
    expected = {
        "smollm-135m": (30, 576, 9, 3, 1536, 49152),
        "deepseek-v3-671b": (61, 7168, 128, 128, None, 129280),
        "deepseek-7b": (30, 4096, 32, 32, 11008, 102400),
        "phi3-mini-3.8b": (32, 3072, 32, 32, 8192, 32064),
        "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206),
        "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
        "qwen2-0.5b": (24, 896, 14, 2, 4864, 151936),
        "deepseek-v2-236b": (60, 5120, 128, 128, None, 102400),
        "llava-next-34b": (60, 7168, 56, 8, 20480, 64000),
        "xlstm-1.3b": (48, 2048, 4, 4, 0, 50304),
    }[arch]
    nl, d, h, kv, ff, vocab = expected
    assert cfg.n_layers == nl
    assert cfg.d_model == d
    assert cfg.n_heads == h
    assert cfg.n_kv_heads == kv
    if ff is not None:
        assert cfg.d_ff == ff
    assert cfg.vocab_size == vocab
    assert cfg.source  # every config cites its source


@pytest.mark.parametrize("arch", ["deepseek-v3-671b", "deepseek-v2-236b",
                                  "jamba-1.5-large-398b"])
def test_moe_expert_counts(arch):
    cfg = get_config(arch)
    m = cfg.moe
    expected = {
        "deepseek-v3-671b": (256, 8, 1),
        "deepseek-v2-236b": (160, 6, 2),
        "jamba-1.5-large-398b": (16, 2, 0),
    }[arch]
    assert (m.n_experts, m.top_k, m.n_shared) == expected


def test_param_counts_roughly_match_names():
    """Total parameter count lands near the model-name scale."""
    tol = {
        "smollm-135m": (135e6, 0.35),
        "deepseek-7b": (7e9, 0.35),
        "phi3-mini-3.8b": (3.8e9, 0.35),
        "qwen2-0.5b": (0.5e9, 0.4),
        "deepseek-v3-671b": (671e9, 0.25),
        "deepseek-v2-236b": (236e9, 0.25),
        "jamba-1.5-large-398b": (398e9, 0.3),
        "xlstm-1.3b": (1.3e9, 0.45),
        "llava-next-34b": (34e9, 0.35),
    }
    for arch, (target, frac) in tol.items():
        n = get_config(arch).param_count()
        assert abs(n - target) / target < frac, (arch, n, target)


def test_phi3_mini_carries_its_published_values():
    """Phi-3-mini-4k's config.json: MHA with 32 KV heads of 96, RoPE 1e4,
    RMSNorm eps 1e-5, no q/k/v bias, an untied head and a 2047-token
    sliding window; the long_500k shape still swaps in the long-context
    window."""
    from repro.configs.shapes import SHAPES
    from repro.launch.build import adapt_config

    cfg = get_config("phi3-mini-3.8b")
    assert (cfg.n_kv_heads, cfg.resolved_head_dim) == (32, 96)
    assert cfg.sliding_window == 2047
    assert (cfg.rope_theta, cfg.norm_eps) == (1e4, 1e-5)
    assert not cfg.qkv_bias and not cfg.tie_embeddings
    long = adapt_config(cfg, SHAPES["long_500k"])
    assert long.sliding_window == cfg.long_context_window
    assert adapt_config(cfg, SHAPES["decode_32k"]).sliding_window == 2047
