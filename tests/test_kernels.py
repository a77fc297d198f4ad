"""Kernel sweeps: shapes x dtypes, assert_allclose against the jnp oracles.

The kernels default to compiling for the TPU; every call here asks for
interpret mode, which executes the Pallas kernel bodies on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.kernels.decode_attention.kernel import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.halo_conv2d.ops import halo_conv_block
from repro.kernels.halo_conv2d.ref import conv_block_ref

TOLS = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def tol(dtype):
    return TOLS[jnp.bfloat16] if dtype == jnp.bfloat16 else TOLS[jnp.float32]


# --------------------------------------------------------------------------- #
# halo conv                                                                   #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("hw,ch,n_layers,tiles", [
    ((16, 16), 8, 1, (2, 2)),
    ((16, 16), 8, 3, (2, 2)),
    ((8, 24), 4, 2, (2, 4)),
    ((32, 32), 16, 2, (4, 4)),
    ((16, 16), 8, 2, (1, 1)),
])
def test_halo_conv_matches_ref(hw, ch, n_layers, tiles):
    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (2, *hw, ch))
    ws = tuple(0.2 * jax.random.normal(jax.random.PRNGKey(i + 1),
                                       (3, 3, ch, ch))
               for i in range(n_layers))
    y = halo_conv_block(x, ws, tiles=tiles, interpret=True)
    yr = conv_block_ref(x, list(ws))
    assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4, rtol=1e-4)


def test_halo_conv_tiling_invariance():
    """The paper's property: results identical across core configurations."""
    k = jax.random.PRNGKey(3)
    x = jax.random.normal(k, (1, 16, 16, 8))
    ws = tuple(0.2 * jax.random.normal(jax.random.PRNGKey(i), (3, 3, 8, 8))
               for i in range(2))
    y1 = halo_conv_block(x, ws, tiles=(1, 2), interpret=True)   # "2-core"
    y2 = halo_conv_block(x, ws, tiles=(2, 2), interpret=True)   # "4-core"
    assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5, rtol=1e-5)


# --------------------------------------------------------------------------- #
# flash attention                                                             #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,d,bq,bk", [
    (128, 64, 64, 64),
    (256, 32, 128, 64),
    (256, 128, 64, 128),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 96), (False, 0)])
def test_flash_attention_sweep(dtype, t, d, bq, bk, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (2, 2, t, d)
    q = jax.random.normal(ks[0], shape, dtype)
    k = jax.random.normal(ks[1], shape, dtype)
    v = jax.random.normal(ks[2], shape, dtype)
    y = flash_attention(q, k, v, causal=causal, window=window, bq=bq, bk=bk,
                        interpret=True)
    yr = attention_ref(q, k, v, causal=causal, window=window)
    assert_allclose(np.asarray(y, np.float32), np.asarray(yr, np.float32),
                    atol=tol(dtype), rtol=tol(dtype))


# --------------------------------------------------------------------------- #
# decode attention                                                            #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h,kv,s,block_s", [
    (8, 2, 256, 64),       # GQA 4:1
    (4, 4, 128, 128),      # MHA
    (16, 1, 512, 128),     # MQA
])
def test_decode_attention_sweep(dtype, h, kv, s, block_s):
    d = 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, h, d), dtype)
    kc = jax.random.normal(ks[1], (2, s, kv, d), dtype)
    vc = jax.random.normal(ks[2], (2, s, kv, d), dtype)
    fill = int(0.75 * s)
    positions = jnp.where(jnp.arange(s) < fill, jnp.arange(s),
                          -1)[None].repeat(2, 0)
    pos = jnp.int32(fill - 1)
    y = decode_attention(q, kc, vc, positions, pos, block_s=block_s,
                         interpret=True)
    yr = decode_attention_ref(q, kc, vc, positions, pos)
    assert_allclose(np.asarray(y, np.float32), np.asarray(yr, np.float32),
                    atol=tol(dtype), rtol=tol(dtype))


def test_decode_attention_rotating_window():
    """Rotating (mod-S) cache slots with a sliding window mask."""
    d, h, kv, s = 32, 4, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, h, d))
    kc = jax.random.normal(ks[1], (1, s, kv, d))
    vc = jax.random.normal(ks[2], (1, s, kv, d))
    # cache holds positions 200-327 at slots (p % 128)
    pos_abs = jnp.arange(200, 200 + s)
    slots = pos_abs % s
    positions = jnp.zeros((1, s), jnp.int32).at[0, slots].set(pos_abs)
    pos = jnp.int32(327)
    y = decode_attention(q, kc, vc, positions, pos, window=100, block_s=64,
                         interpret=True)
    yr = decode_attention_ref(q, kc, vc, positions, pos, window=100)
    assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------------- #
# model-layout wrappers: kernel or explicit oracle, never a silent fallback   #
# --------------------------------------------------------------------------- #


def _mha(t):
    from repro.kernels.flash_attention.ops import mha_attention
    q, k, v = (jax.random.normal(kk, (1, t, 2, 32))
               for kk in jax.random.split(jax.random.PRNGKey(4), 3))
    return lambda **kw: mha_attention(q, k, v, **kw)


def _cached_decode(s):
    from repro.kernels.decode_attention.ops import cached_decode_attention
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 1, 4, 32))
    kc = jax.random.normal(ks[1], (1, s, 2, 32))
    vc = jax.random.normal(ks[2], (1, s, 2, 32))
    positions = jnp.arange(s, dtype=jnp.int32)[None]
    return lambda **kw: cached_decode_attention(q, kc, vc, positions,
                                                jnp.int32(s - 1), **kw)


@pytest.mark.parametrize("make,good,bad", [(_mha, 128, 200),
                                           (_cached_decode, 256, 200)],
                         ids=["mha_attention", "cached_decode_attention"])
def test_wrapper_kernel_matches_oracle_and_refuses_untileable(make, good, bad):
    call = make(good)
    assert_allclose(np.asarray(call(interpret=True)),
                    np.asarray(call(use_pallas=False)), atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError, match="use_pallas=False"):
        make(bad)(interpret=True)
    make(bad)(use_pallas=False)          # the oracle takes any length


# --------------------------------------------------------------------------- #
# sLSTM scan (recurrent-matrix-resident kernel, §Perf pair 2)                 #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("dtype,b,t,h,dh,block_t", [
    (jnp.float32, 2, 32, 2, 16, 8),
    (jnp.float32, 1, 40, 1, 32, 16),     # ragged: 40 % 16 != 0
    (jnp.float32, 3, 16, 4, 8, 16),      # single block
    (jnp.bfloat16, 2, 24, 2, 16, 8),
])
def test_slstm_scan_sweep(dtype, b, t, h, dh, block_t):
    from repro.kernels.slstm_scan.kernel import slstm_scan
    from repro.kernels.slstm_scan.ref import slstm_scan_ref
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    wx = (0.5 * jax.random.normal(k[0], (b, t, 4, h, dh))).astype(dtype)
    r = (dh ** -0.5 * jax.random.normal(k[1], (4, h, dh, dh))).astype(dtype)
    bias = (0.1 * jax.random.normal(k[2], (4, h, dh))).astype(jnp.float32)
    got = slstm_scan(wx, r, bias, block_t=block_t, interpret=True)
    want = slstm_scan_ref(wx, r, bias)
    assert got.shape == want.shape == (b, t, h, dh)
    assert_allclose(np.asarray(got), np.asarray(want), atol=tol(dtype),
                    rtol=tol(dtype))


def test_slstm_kernel_matches_model_layer():
    """The kernel reproduces the model's sLSTM hidden states end-to-end
    (wx built from the layer's own input projection)."""
    from repro.configs import get_smoke_config
    from repro.kernels.slstm_scan.kernel import slstm_scan
    from repro.models.layers import xlstm as X
    cfg = get_smoke_config("xlstm-1.3b")
    p = X.slstm_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (2, 12, cfg.d_model))
    wx = jnp.einsum("btd,dghk->btghk", x, p["w"])
    hs = slstm_scan(wx, p["r"], p["b"], block_t=4, interpret=True)
    # reference: the model's own scan (hidden states pre-groupnorm)
    b_, t_ = x.shape[:2]
    hh = cfg.n_heads
    dh = cfg.d_model // hh
    state = (jnp.zeros((b_, hh, dh)), jnp.zeros((b_, hh, dh)),
             jnp.ones((b_, hh, dh)), jnp.zeros((b_, hh, dh)))
    outs = []
    for i in range(t_):
        state = X._slstm_step(p, state, wx[:, i])
        outs.append(state[0])
    want = jnp.stack(outs, axis=1)
    assert_allclose(np.asarray(hs), np.asarray(want), atol=2e-5, rtol=2e-4)
