"""Plain float32 reference of a dense decoder with sliding-window attention.

Phi-3-mini (arXiv:2404.14219; microsoft/Phi-3-mini-4k-instruct) is
``dense_gqa``'s decoder with one change: a query at position q sees the
keys k with ``q - w < k <= q``, ``w`` the configuration's
``sliding_window`` (0: every earlier key).  The layer equations, the weight
tree and the draw of the weights are ``dense_gqa``'s; this module takes its
norm, its rotary embedding and its draw, and writes the attention anew.

Attention is computed one block of queries at a time against every key, so
the scores held at once are ``[B, heads, QUERY_BLOCK, L]``: for one
3711-token sample at 32 heads, 0.24 GB in float32.  Like ``dense_gqa`` it
imports nothing of the program under test.

The costs count the work the window leaves: a query attends to at most
``w`` keys, a decode step reads at most ``w - 1`` cached positions and
writes one, and a prompt's K/V is written for the ``w`` positions a ring
keeps.  With ``w`` at least the length they are ``bench.flops``'s.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import flops
from bench.families.dense_gqa import _rms, _rope, init_leaf  # noqa: F401
from bench.flops import Cost

QUERY_BLOCK = 512


def _window(m: dict, n: int) -> int:
    """Keys a query sees at most, among ``n`` positions."""
    w = int(m.get("sliding_window", 0))
    return min(w, n) if w > 0 else n


def _pairs(t: int, w: int) -> int:
    """Query-key pairs of a ``t``-token prompt: the sum of min(q + 1, w)."""
    full = min(t, w)
    return full * (full + 1) // 2 + (t - full) * w


def _left_out(m: dict, pairs: int, positions: int) -> Cost:
    """What ``bench.flops`` counts that the window leaves out: attention
    over ``pairs`` query-key pairs and the K/V of ``positions``."""
    attn = m["n_layers"] * 4 * m["n_heads"] * m["head_dim"] * pairs
    return Cost(float(attn), float(positions * flops.kv_bytes_per_position(m)))


def prefill_cost(m: dict, t: int) -> Cost:
    """A ``t``-token prompt, as ``bench.flops.prefill`` counts it, but
    attention only over the pairs in the window and K/V written only for
    the last ``w`` positions, those a ring keeps."""
    full, w = flops.prefill(m, t), _window(m, t)
    cut = _left_out(m, t * (t + 1) // 2 - _pairs(t, w), t - w)
    return Cost(full.flops - cut.flops, full.bytes - cut.bytes)


def decode_cost(m: dict, pos: int) -> Cost:
    """One token at position ``pos`` (0-based), as ``bench.flops.decode``
    counts it, but attending to itself and at most the ``w - 1``
    positions before it, and reading only their K/V."""
    full, n = flops.decode(m, pos), _window(m, pos + 1)
    cut = _left_out(m, pos + 1 - n, pos + 1 - n)
    return Cost(full.flops - cut.flops, full.bytes - cut.bytes)


def _attend(q, k, v, w: int, dtype):
    """q [B, L, KV, G, hd] (rotated), k, v [B, L, KV, hd] -> [B, L, KV, G, hd].

    Causal within the window, one block of up to ``QUERY_BLOCK`` queries
    at a time; padded query rows past ``L`` are computed and dropped."""
    b, L, kv_, g, hd = q.shape
    blk = min(QUERY_BLOCK, L)
    nb = -(-L // blk)
    q = jnp.pad(q, [(0, 0), (0, nb * blk - L), (0, 0), (0, 0), (0, 0)])
    blocks = q.reshape(b, nb, blk, kv_, g, hd).swapaxes(0, 1)
    k_pos = jnp.arange(L)

    def one(args):
        i, qb = args
        q_pos = i * blk + jnp.arange(blk)
        s = jnp.einsum("blkgd,bskd->bkgls", qb, k).astype(jnp.float32)
        s = s * (hd ** -0.5)
        seen = k_pos[None, :] <= q_pos[:, None]
        if w > 0:
            seen &= k_pos[None, :] > q_pos[:, None] - w
        s = jnp.where(seen, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(dtype)
        return jnp.einsum("bkgls,bskd->blkgd", p, v)

    out = jax.lax.map(one, (jnp.arange(nb), blocks))      # [nb, B, blk, ...]
    return out.swapaxes(0, 1).reshape(b, nb * blk, kv_, g, hd)[:, :L]


def _layer(m: dict, dtype, x, p):
    b, L, _ = x.shape
    h_, kv_, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    c = lambda a: a.astype(dtype)                      # noqa: E731
    att = p["mixer"]
    h = _rms(x, p["norm1"]["scale"], m["norm_eps"])
    q = jnp.einsum("bld,dhk->blhk", h, c(att["wq"]))
    k = jnp.einsum("bld,dhk->blhk", h, c(att["wk"]))
    v = jnp.einsum("bld,dhk->blhk", h, c(att["wv"]))
    if m["qkv_bias"]:
        q, k, v = q + c(att["bq"]), k + c(att["bk"]), v + c(att["bv"])
    pos = jnp.arange(L)
    q, k = _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"])
    q = q.reshape(b, L, kv_, h_ // kv_, hd)
    o = _attend(q, k, v, int(m.get("sliding_window", 0)), dtype)
    x = x + jnp.einsum("ble,ed->bld", o.reshape(b, L, h_ * hd), c(att["wo"]))
    f = p["ffn"]
    h = _rms(x, p["norm2"]["scale"], m["norm_eps"])
    g = jnp.einsum("bld,df->blf", h, c(f["wg"]))
    u = jnp.einsum("bld,df->blf", h, c(f["wu"]))
    x = x + jnp.einsum("blf,fd->bld", jax.nn.silu(g) * u, c(f["wd"]))
    return x, None


def logits_at(weights, m: dict, tokens, rows, cols, dtype=jnp.float32):
    """Logits over the vocabulary at ``tokens[rows[i], cols[i]]``.

    tokens [B, L] int32; rows, cols [N] int32.  Causal, so right padding
    past a row's real length changes nothing before it.  Returns [N, V]
    float32.  ``dtype`` is the arithmetic type: float32 is the reference
    (run it under ``jax.default_matmul_precision("highest")``), bfloat16 the
    lower-precision control.
    """
    x = weights["embed"][tokens].astype(dtype)
    x, _ = jax.lax.scan(lambda x, p: _layer(m, dtype, x, p), x,
                        weights["dec0"]["p0"])
    x = _rms(x, weights["final_norm"]["scale"], m["norm_eps"])
    x = x[rows, cols]                                   # [N, d]
    v = m["vocab_size"]
    if m["tie_embeddings"]:
        head = weights["embed"][:v].astype(dtype)       # [V, d]
        out = jnp.einsum("nd,vd->nv", x, head)
    else:
        head = weights["lm_head"][:, :v].astype(dtype)  # [d, V]
        out = jnp.einsum("nd,dv->nv", x, head)
    return out.astype(jnp.float32)
