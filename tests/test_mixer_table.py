"""The mixer contract: a mixer kind is one entry of ``blocks.MIXERS``, and
the slot rule every position-tracked cache follows is one function."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.configs import get_smoke_config
from repro.models import blocks as B
from repro.models import model as M
from repro.models.config import LayerDef, StageDef
from repro.models.layers.common import Mixer, dense_init, write_positions

T = 12
TOL = 2e-4


def _toy(stacked: bool) -> Mixer:
    """A running mean of its input, projected: its cache is the running
    sum, kept in the stage's stacked cache or in the layer's own slice."""

    def init(key, cfg, dtype):
        return {"w": dense_init(key, cfg.d_model, cfg.d_model, dtype=dtype)}

    def cache_init(batch, cache_len, cfg, dtype):
        return {"sum": jnp.zeros((batch, cfg.d_model), dtype)}

    def apply(params, x, ctx, cache=None, layer=0):
        run = jnp.cumsum(x, axis=1)
        if cache is not None:
            prior = cache["sum"][layer] if stacked else cache["sum"]
            run = run + prior[:, None]
            last = run[:, -1]
            cache = {"sum": (cache["sum"].at[layer].set(last) if stacked
                             else last)}
        mean = run / (ctx.positions + 1)[:, None].astype(run.dtype)
        return jnp.einsum("btd,de->bte", mean, params["w"]), cache

    return Mixer(init=init, axes=lambda cfg: {"w": ("embed", "embed2")},
                 cache_init=cache_init,
                 cache_axes=lambda: {"sum": ("batch", "embed")},
                 apply=apply, stacked=stacked)


@pytest.mark.parametrize("stacked", [False, True], ids=["own", "stacked"])
def test_a_mixer_added_to_the_table_alone_runs_everywhere(stacked,
                                                          monkeypatch):
    """A new mixer kind needs its table entry and nothing else: params,
    caches and their axes, forward, prefill and decode all take it, beside
    attention in one layer scan, and prefill plus decode match forward."""
    monkeypatch.setitem(B.MIXERS, "toy", _toy(stacked))
    cfg = get_smoke_config("smollm-135m")
    cfg = replace(cfg, n_layers=4, stages=(StageDef(
        (LayerDef("toy", "dense"), LayerDef("attn", "dense")), 2),))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    caches = M.init_caches(cfg, 2, 32)
    for tree, axes in ((params, M.params_axes(cfg)),
                       (caches, M.caches_axes(cfg))):
        ranks = jax.tree.map(lambda a, ax: a.ndim == len(ax), tree, axes)
        assert all(jax.tree.leaves(ranks))
    assert caches["dec0"]["p0"]["self"]["sum"].shape == (2, 2, cfg.d_model)

    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, T + 1), 0,
                                cfg.vocab_size)
    full, _ = M.forward(params, cfg, {"tokens": tokens})
    pre, caches = M.prefill(params, cfg, {"tokens": tokens[:, :T]}, 32)
    dec, _ = M.decode_step(params, cfg, caches, tokens[:, T:], jnp.int32(T))
    assert float(jnp.abs(pre[:, 0] - full[:, T - 1]).max()) < TOL
    assert float(jnp.abs(dec[:, 0] - full[:, T]).max()) < TOL


# (window, first position, count) written into 8 slots
WRITES = [
    (0, 0, 5), (0, 3, 5),          # contiguous, from slot 0 and on from 3
    (0, 0, 12),                    # longer than the cache: the last 8
    (8, 5, 1), (8, 13, 1),         # one position on the ring
    (8, 0, 4), (8, 10, 5),         # a run that does not wrap
    (8, 6, 4),                     # a run that wraps: slots 6, 7, 0, 1
    (8, 0, 8), (8, 3, 8),          # the whole ring, rotated or not
    (8, 0, 19),                    # more than the ring: the last 8, rotated
]


@pytest.mark.parametrize("stacked", [False, True], ids=["own", "stacked"])
@pytest.mark.parametrize("window,p0,n", WRITES)
def test_write_positions_matches_one_position_at_a_time(window, p0, n,
                                                        stacked):
    """The slot rule for a run of positions is the decode rule applied one
    position at a time (slot p mod S on a ring, else p), keeping the last
    S positions of a longer run; the other slots and layers are kept."""
    s, b = 8, 2
    rng = np.random.default_rng(p0 * 100 + n)
    positions = np.arange(p0, p0 + n)
    vals = rng.normal(size=(b, n, 3)).astype(np.float32)
    buf = rng.normal(size=(b, s, 3)).astype(np.float32)
    pmap = rng.integers(-1, 50, size=(b, s)).astype(np.int32)

    want_buf, want_map = buf.copy(), pmap.copy()
    for j in range(max(0, n - s), n):
        p = positions[j]
        slot = p % s if window else (p if n <= s else j - (n - s))
        want_buf[:, slot], want_map[:, slot] = vals[:, j], p

    if stacked:     # as attention's: three layers, slots on the last axis
        layers = np.stack([-buf, buf, 2 * buf]).swapaxes(-1, -2)
        cache = {"x": layers, "positions": np.stack([pmap] * 3)}
        got = write_positions(cache, {"x": vals.swapaxes(-1, -2)},
                              positions, window, -1, layer=1)
        want_x = layers.copy()
        want_x[1] = want_buf.swapaxes(-1, -2)
        want_pos = np.stack([pmap, want_map, pmap])
    else:           # as MLA's: the layer's own, slots on axis 1
        got = write_positions({"x": buf, "positions": pmap}, {"x": vals},
                              positions, window, 1)
        want_x, want_pos = want_buf, want_map
    assert_allclose(np.asarray(got["x"]), want_x)
    np.testing.assert_array_equal(np.asarray(got["positions"]), want_pos)


@pytest.mark.parametrize("stacked", [False, True], ids=["own", "stacked"])
@pytest.mark.parametrize("window,n", [(0, 5), (0, 12), (8, 4), (8, 19)])
def test_write_positions_fresh_empties_the_slots_past_the_run(window, n,
                                                              stacked):
    """A run that starts the sequence (``fresh``) leaves the slots it does
    not reach empty, whatever the cache held: -1 in the map, zeros."""
    s, b = 8, 2
    rng = np.random.default_rng(n)
    positions = np.arange(n)
    vals = rng.normal(size=(b, n, 3)).astype(np.float32)
    buf = rng.normal(size=(b, s, 3)).astype(np.float32)
    pmap = rng.integers(-1, 50, size=(b, s)).astype(np.int32)

    want_buf, want_map = np.zeros_like(buf), np.full_like(pmap, -1)
    for j in range(max(0, n - s), n):
        slot = j % s if window else j - max(0, n - s)
        want_buf[:, slot], want_map[:, slot] = vals[:, j], j

    if stacked:
        cache = {"x": np.stack([buf, buf]), "positions": np.stack([pmap] * 2)}
        got = write_positions(cache, {"x": vals}, positions, window, 1,
                              layer=1, fresh=True)
        want_x, want_pos = np.stack([buf, want_buf]), np.stack([pmap,
                                                                want_map])
    else:
        got = write_positions({"x": buf, "positions": pmap}, {"x": vals},
                              positions, window, 1, fresh=True)
        want_x, want_pos = want_buf, want_map
    assert_allclose(np.asarray(got["x"]), want_x)
    np.testing.assert_array_equal(np.asarray(got["positions"]), want_pos)
