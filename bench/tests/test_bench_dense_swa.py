"""The sliding-window family: its operation and byte counts, and the
program's prefill and decode through a rotating cache against its plain
reference, at a smoke width on the CPU."""
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops
from bench import harness as H
from bench.families import dense_swa as S
from bench.layer_metrics._steps import share

DATA = Path(__file__).resolve().parent / "data"
M = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
     "head_dim": 2, "d_ff": 16, "vocab_size": 10, "qkv_bias": True,
     "tie_embeddings": True, "dtype": "float32"}
KV_POS = flops.kv_bytes_per_position(M)      # 64 bytes, both layers
ATTN = 2 * 4 * 4 * 2                         # FLOPs of one query-key pair


# --------------------------------------------------------------------- #
# Costs                                                                  #
# --------------------------------------------------------------------- #
def test_prefill_counts_only_the_pairs_in_the_window():
    m = dict(M, sliding_window=3)
    # t = 5: queries see 1, 2, 3, 3, 3 keys -> 12 pairs, not 15
    assert S._pairs(5, 3) == 12
    full = flops.prefill(M, 5)
    c = S.prefill_cost(m, 5)
    assert full.flops - c.flops == (15 - 12) * ATTN
    # the K/V of the 3 positions the window keeps is written, not all 5
    assert full.bytes - c.bytes == 2 * KV_POS


def test_decode_reads_only_the_window():
    m = dict(M, sliding_window=3)
    c, full = S.decode_cost(m, 9), flops.decode(M, 9)
    assert full.flops - c.flops == (10 - 3) * ATTN
    assert full.bytes - c.bytes == (10 - 3) * KV_POS
    assert S.decode_cost(m, 9) == S.decode_cost(m, 1000)


@pytest.mark.parametrize("w,t", [(0, 1), (0, 8), (1, 1), (7, 7), (8, 7),
                                 (100, 8)])
def test_costs_are_flops_where_the_window_holds_the_length(w, t):
    m = dict(M, sliding_window=w)
    assert S.prefill_cost(m, t) == flops.prefill(M, t)
    assert S.decode_cost(m, t - 1) == flops.decode(M, t - 1)


def test_a_trace_at_the_roofline_reads_100_and_no_more():
    m = dict(M, sliding_window=3)
    pk = json.loads((H.BENCH / "peaks.json").read_text())["TPU v5 lite"]
    positions = list(range(40, 51))
    least = sum(flops.least_seconds(S.decode_cost(m, p),
                                    pk["bf16_flops_per_s"],
                                    pk["hbm_bytes_per_s"])
                for p in positions)
    trace = types.SimpleNamespace(
        program=lambda name: (len(positions), least))
    window = types.SimpleNamespace(traced={"decode": positions,
                                           "partial": False})
    run = types.SimpleNamespace(trace=trace, window=window, peaks=pk,
                                model=m)
    assert share(run, "serve_step", "decode", S.decode_cost,
                 roofline=True) == pytest.approx(100.0)
    # the full-causal count would read the same trace above 100%
    assert share(run, "serve_step", "decode", flops.decode,
                 roofline=True) > 100.0


# --------------------------------------------------------------------- #
# Program against reference                                              #
# --------------------------------------------------------------------- #
PROMPT, STEPS, CACHE_LEN = 40, 12, 16
# Both sides compute in float32 and differ only in the order of their
# sums: ~3e-6 on logits up to ~4 in size.  A bfloat16 reference lies ~7e-2
# away (checked below), so 1e-4 passes the one and fails the other.
LOGIT_TOL = 1e-4


@pytest.fixture(scope="module")
def smoke():
    """A smoke Phi-3 (MHA, head 24, window 15), its weights drawn by the
    family, and a batch of two 40-token prompts: longer than the 16-slot
    cache, so prefill takes the rotating write."""
    config = json.loads((DATA / "smoke-swa.json").read_text())
    cfg = H.program_config(config)
    assert (cfg.sliding_window, cfg.n_kv_heads, cfg.resolved_head_dim) == \
        (15, 4, 24)
    weights = H.make_weights(cfg, S, 7)
    prompts = jax.random.randint(jax.random.PRNGKey(3), (2, PROMPT), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    return cfg, config["model"], weights, prompts


def _reference(weights, m, tokens, dtype=jnp.float32):
    """[B, L, V] logits of the reference at every position."""
    b, L = tokens.shape
    rows = jnp.repeat(jnp.arange(b), L)
    cols = jnp.tile(jnp.arange(L), b)
    out = S.logits_at(weights, m, tokens, rows, cols, dtype=dtype)
    return np.asarray(out).reshape(b, L, -1)


def _served(cfg, weights, prompts):
    """Tokens of the engine's own jitted steps (the serve step donates its
    cache), and the caches each serve step was given."""
    from repro.serving.engine import jit_serving_steps

    prefill, serve = jit_serving_steps(cfg, CACHE_LEN)
    nxt, caches = prefill(weights, {"tokens": prompts})
    last, out, given = nxt[:, None], [nxt[:, None]], []
    for i in range(STEPS - 1):
        given.append(caches)
        last, caches = serve(weights, caches, last,
                             jnp.asarray(PROMPT + i, jnp.int32))
        out.append(last)
    return np.asarray(jnp.concatenate(out, axis=1)), given


def test_served_tokens_are_the_references_choice(smoke):
    cfg, m, weights, prompts = smoke
    served, given = _served(cfg, weights, prompts)
    tokens = jnp.concatenate([prompts, jnp.asarray(served[:, :-1])], 1)
    ref = _reference(weights, m, tokens)[:, PROMPT - 1:]
    v = cfg.vocab_size
    picked = np.take_along_axis(ref[..., :v], served[..., None], -1)[..., 0]
    assert (ref[..., :v].max(-1) - picked).max() <= LOGIT_TOL
    assert all(leaf.is_deleted() for c in given
               for leaf in jax.tree.leaves(c))


def test_decode_logits_through_the_ring_match_the_reference(smoke):
    """Prefill 40 tokens into 16 slots, then 12 decode steps that each
    overwrite the oldest slot: every step's logits are the full forward's
    under the window, and a bfloat16 reference is not."""
    from repro.models import model as Mod

    cfg, m, weights, prompts = smoke
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, PROMPT + STEPS),
                                0, cfg.vocab_size, dtype=jnp.int32)
    tokens = tokens.at[:, :PROMPT].set(prompts)
    prefill = jax.jit(lambda w, t: Mod.prefill(w, cfg, {"tokens": t},
                                               CACHE_LEN))
    step = jax.jit(lambda w, c, t, p: Mod.decode_step(w, cfg, c, t, p),
                   donate_argnums=1)
    logits, caches = prefill(weights, tokens[:, :PROMPT])
    got = [logits[:, -1]]
    for i in range(STEPS - 1):
        p = PROMPT + i
        logits, caches = step(weights, caches, tokens[:, p:p + 1],
                              jnp.asarray(p, jnp.int32))
        got.append(logits[:, -1])
    v = cfg.vocab_size
    got = np.asarray(jnp.stack(got, 1))[..., :v]
    ref = _reference(weights, m, tokens)[:, PROMPT - 1:-1, :v]
    assert np.abs(got - ref).max() <= LOGIT_TOL
    low = _reference(weights, m, tokens, jnp.bfloat16)[:, PROMPT - 1:-1, :v]
    assert np.abs(low - ref).max() > LOGIT_TOL


def test_the_window_changes_the_reference(smoke):
    """The reference's mask is live at these lengths: without the window
    its logits past position 15 differ."""
    cfg, m, weights, prompts = smoke
    windowed = _reference(weights, m, prompts)
    full = _reference(weights, dict(m, sliding_window=0), prompts)
    assert np.abs(windowed[:, :15] - full[:, :15]).max() <= LOGIT_TOL
    assert np.abs(windowed[:, 20:] - full[:, 20:]).max() > 1e-2


def test_blocks_of_queries_change_nothing(smoke, monkeypatch):
    cfg, m, weights, prompts = smoke
    whole = _reference(weights, m, prompts)
    monkeypatch.setattr(S, "QUERY_BLOCK", 7)      # 40 = 5 blocks + 5
    assert np.abs(_reference(weights, m, prompts) - whole).max() <= 1e-5


def test_program_config_keeps_the_window():
    config = json.loads((H.ROOT / "bench" / "configs"
                         / "phi3-mini-16l.json").read_text())
    cfg = H.program_config(config)
    assert config["name"] == "phi3-mini-16l"
    assert config["correct"]["max_logit_gap"]["limit"] > 0
    assert cfg.sliding_window == config["model"]["sliding_window"] == 2047
    assert config["deployment"]["cache_len"] == 2048
    with pytest.raises(ValueError):
        H.program_config(dict(config, overrides=dict(
            config["overrides"], n_layers=8)))
