"""Shared neural-net layers: norms, RoPE, chunked (flash-style) attention.

All functions are single-worker: the local-SGD worker dimension is added
by ``jax.vmap`` in the training step. Sharding is expressed through
logical-axis constraints (``constrain``) resolved by the active
:class:`~repro.sharding.layout.MeshLayout`.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding.layout import MeshLayout

NEG_INF = -1e30


def constrain(x, lay: MeshLayout | None, *axes: str | None):
    """Logical-axis sharding constraint (no-op when no layout is active).

    Shape-aware: rules that do not divide the concrete dim are dropped
    (see MeshLayout.spec), so e.g. kv_heads=1 never fights a 16-way axis.
    """
    if lay is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, lay.sharding(*axes, dims=tuple(x.shape)))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x, scale, *, eps: float = 1e-6, plus_one: bool = False):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(var + eps)
    s = scale.astype(jnp.float32)
    if plus_one:
        s = 1.0 + s
    return (xf * s).astype(dtype)


def layer_norm(x, scale, bias, *, eps: float = 1e-5):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings (llama-style half rotation)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, *, theta: float):
    """x: (..., S, H, D); positions: (..., S) int32."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                             # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * inv   # (..., S, d/2)
    cos = jnp.cos(ang)[..., None, :]                       # (..., S, 1, d/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(num_pos: int, dim: int):
    pos = jnp.arange(num_pos, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, dim, 2, dtype=jnp.float32) * (-math.log(10000.0) / dim))
    pe = jnp.zeros((num_pos, dim), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


# ---------------------------------------------------------------------------
# Attention — chunked flash-style (no S^2 materialization, skips masked blocks)
# ---------------------------------------------------------------------------

def _softcap(s, cap: float):
    if cap and cap > 0:
        s = jnp.tanh(s / cap) * cap
    return s


def _pick_block(seq: int, want: int) -> int:
    b = min(want, seq)
    while seq % b:
        b -= 1
    return max(b, 1)


def chunked_attention(q, k, v, *, causal: bool = True, window: int = 0,
                      q_offset: int = 0, softcap: float = 0.0, scale: float = 0.0,
                      block_q: int = 512, block_k: int = 512,
                      differentiable: bool = True,
                      lay: MeshLayout | None = None):
    """Flash-style attention with GQA.

    q: (B, Sq, H, D); k, v: (B, Sk, KH, D) with H % KH == 0.
    Streams over KV blocks with an online softmax, visiting only blocks
    inside the causal/window band, so compute is proportional to the
    *unmasked* area (no 2x causal-mask waste — this matters for the
    roofline).

    Two equivalent schedules:
    * ``differentiable=True`` (training): the q-block loop is unrolled in
      Python so each block's KV range is static — required because
      reverse-mode AD cannot differentiate dynamic-bound loops.
    * ``differentiable=False`` (prefill): ``lax.map`` over q blocks with a
      dynamic-bound ``fori_loop`` — compact HLO for 32k/500k sequences.

    ``q_offset``: static absolute position of q[0] relative to k[0].
    """
    B, Sq, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    Sk = k.shape[1]
    scale = scale or 1.0 / math.sqrt(D)

    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Sk, block_k)
    nq, nk = Sq // bq, Sk // bk

    qb = q.reshape(B, nq, bq, KH, G, D)
    kb = k.reshape(B, nk, bk, KH, D)
    vb = v.reshape(B, nk, bk, KH, D)

    k_pos = jnp.arange(Sk).reshape(nk, bk)

    def bounds(i: int):
        hi = min((q_offset + (i + 1) * bq - 1) // bk + 1, nk) if causal else nk
        lo = max((q_offset + i * bq - window + 1) // bk, 0) if (window and causal) else 0
        return lo, hi

    def make_body(q_i, q_pos):
        def body(j, carry):
            m, l, acc = carry
            k_j = jax.lax.dynamic_index_in_dim(kb, j, axis=1, keepdims=False)
            v_j = jax.lax.dynamic_index_in_dim(vb, j, axis=1, keepdims=False)
            kp = jax.lax.dynamic_index_in_dim(k_pos, j, axis=0, keepdims=False)
            s = jnp.einsum("bqhgd,bkhd->bqhgk", q_i, k_j,
                           preferred_element_type=jnp.float32) * scale
            s = _softcap(s, softcap)
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask &= kp[None, :] <= q_pos[:, None]
            if window:
                mask &= q_pos[:, None] - kp[None, :] < window
            s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p, v_j.astype(jnp.float32),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new
        return body

    def init_carry():
        return (jnp.full((B, bq, KH, G), NEG_INF, jnp.float32),
                jnp.zeros((B, bq, KH, G), jnp.float32),
                jnp.zeros((B, bq, KH, G, D), jnp.float32))

    def finish(m, l, acc):
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(B, bq, H, D)

    if differentiable or nq == 1:
        outs = []
        for i in range(nq):
            lo, hi = bounds(i)
            q_pos = q_offset + i * bq + jnp.arange(bq)
            body = make_body(qb[:, i], q_pos)
            # static bounds: lowered as a scan -> reverse-differentiable
            def scan_body(carry, j):
                return body(j, carry), None
            carry, _ = jax.lax.scan(scan_body, init_carry(),
                                    jnp.arange(lo, hi))
            outs.append(finish(*carry))
        out = jnp.stack(outs, axis=1)                     # (B, nq, bq, H, D)
    else:
        def one_q_block(args):
            i, q_i = args                                 # traced block index
            q_pos = q_offset + i * bq + jnp.arange(bq)
            if causal:
                hi = jnp.minimum((q_offset + (i + 1) * bq - 1) // bk + 1, nk)
            else:
                hi = nk
            lo = (jnp.maximum((q_offset + i * bq - window + 1) // bk, 0)
                  if (window and causal) else 0)
            body = make_body(q_i, q_pos)
            carry = jax.lax.fori_loop(lo, hi, body, init_carry())
            return finish(*carry)

        qb_t = jnp.moveaxis(qb, 1, 0)                    # (nq, B, bq, KH, G, D)
        out = jax.lax.map(one_q_block, (jnp.arange(nq), qb_t))
        out = jnp.moveaxis(out, 0, 1)                    # (B, nq, bq, H, D)
    out = out.reshape(B, Sq, H, D).astype(q.dtype)
    return constrain(out, lay, "batch", "seq", "heads", None)


def cache_write(buf, new, write):
    """Write one token's k/v rows into a sequence-major cache buffer.

    ``buf``: (B, S, ...); ``new``: (B, 1, ...); ``write``: () or (B,)
    int — the target position along axis 1.  The scalar form is the
    classic single-counter decode; the vector form is what continuous
    batching needs (every resident sequence sits at its own position),
    implemented as a batch-vmapped dynamic_update_slice so each row gets
    its own start index.
    """
    new = new.astype(buf.dtype)
    w = jnp.asarray(write)
    if w.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(buf, new, w, axis=1)
    upd = lambda c, n, s: jax.lax.dynamic_update_slice_in_dim(c, n, s, axis=0)
    return jax.vmap(upd)(buf, new, w)


def decode_attention(q, k_cache, v_cache, *, cache_len, window: int = 0,
                     softcap: float = 0.0, scale: float = 0.0,
                     lay: MeshLayout | None = None):
    """Single-token attention over a (possibly seq-sharded) KV cache.

    q: (B, 1, H, D); caches: (B, S, KH, D); cache_len: () or (B,) int —
    number of valid cache entries (the new token's k/v must already be
    written at position cache_len-1).
    Softmax runs over the cache sequence dim; if that dim is sharded
    (long-context layout) GSPMD inserts the distributed-attention
    all-reduces automatically.
    """
    B, _, H, D = q.shape
    KH = k_cache.shape[2]
    G = H // KH
    S = k_cache.shape[1]
    scale = scale or 1.0 / math.sqrt(D)

    qh = q.reshape(B, KH, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qh, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = _softcap(s, softcap)
    pos = jnp.arange(S)
    clen = jnp.asarray(cache_len)
    clen = clen[:, None] if clen.ndim else clen
    valid = pos[None, :] < jnp.broadcast_to(clen, (B, 1))
    if window:
        valid &= pos[None, :] >= jnp.broadcast_to(clen, (B, 1)) - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    out = out / p.sum(axis=-1)[..., None]
    out = out.reshape(B, 1, H, D).astype(q.dtype)
    return constrain(out, lay, "batch", None, "heads", None)


def reference_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                        scale: float = 0.0):
    """O(S^2) oracle used by tests to validate chunked_attention."""
    B, Sq, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    scale = scale or 1.0 / math.sqrt(D)
    qh = q.reshape(B, Sq, KH, G, D)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qh, k,
                   preferred_element_type=jnp.float32) * scale
    s = _softcap(s, softcap)
    Sk = k.shape[1]
    qpos = jnp.arange(Sq) + (Sk - Sq)
    kpos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= qpos[:, None] - kpos[None, :] < window
    s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhgk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

def swiglu(gate, up):
    return jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up


def geglu(gate, up):
    return jax.nn.gelu(gate.astype(jnp.float32), approximate=True).astype(gate.dtype) * up
