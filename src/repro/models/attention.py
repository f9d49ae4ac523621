"""GQA attention: training/prefill (chunked flash-style) and cached decode.

Memory discipline: full-sequence attention never materializes the
``(B, H, S, S)`` score tensor.  For ``seq > PLAIN_THRESHOLD`` we run an
online-softmax over KV chunks (lax.scan) nested inside a q-chunk map
(lax.map), so the transient per chip is ``O(B * q_chunk * H * kv_chunk)``.
This is the pure-jnp flash pattern — on TPU the same tiling would live in a
Pallas kernel; here the model code stays backend-portable.

Sliding-window attention is mask-based in training/prefill and
ring-buffer-based in decode (the cache holds only ``window`` entries), which
is what makes ``long_500k`` decode memory-feasible for dense architectures.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import apply_rope
from repro.models.module import dense_param, split_tree

PLAIN_THRESHOLD = 2048
Q_CHUNK = 512
KV_CHUNK = 1024

NEG_INF = -1e30


def attention_init(key, d_model, n_heads, n_kv_heads, head_dim, dtype,
                   cross: bool = False, attn_tp: str = "heads"):
    kq, kk, kv, ko = jax.random.split(key, 4)
    if attn_tp == "head_dim":
        # TP over the head_dim: q/k contractions become model-partial sums
        # (GSPMD inserts the all-reduce); used when n_heads % model != 0
        h_ax, d_ax = None, "tp"
    else:
        h_ax, d_ax = "tp", None
    return split_tree(
        {
            "wq": dense_param(kq, (d_model, n_heads, head_dim), ("fsdp", h_ax, d_ax), dtype),
            "wk": dense_param(kk, (d_model, n_kv_heads, head_dim), ("fsdp", h_ax, d_ax), dtype),
            "wv": dense_param(kv, (d_model, n_kv_heads, head_dim), ("fsdp", h_ax, d_ax), dtype),
            "wo": dense_param(ko, (n_heads, head_dim, d_model), (h_ax, d_ax, "fsdp"), dtype),
        }
    )


def _mask(qpos, kpos, causal: bool, window: int | None):
    """(..., Sq, Sk) additive mask from absolute positions.

    Negative ``kpos`` marks padding keys (always masked) — used when a
    sequence is padded up to the flash chunk size."""
    rel = qpos[..., :, None] - kpos[..., None, :]
    ok = kpos[..., None, :] >= 0
    if causal:
        ok &= rel >= 0
    if window is not None:
        ok &= rel < window
    return jnp.where(ok, 0.0, NEG_INF)


def _plain_attention(q, k, v, qpos, kpos, causal, window):
    """q: (B,Sq,Hkv,G,D); k,v: (B,Sk,Hkv,D) -> (B,Sq,Hkv,G,D)."""
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", q, k).astype(jnp.float32) * scale
    logits = logits + _mask(qpos, kpos, causal, window)[:, None, None]
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)


def _chunk_q(x, nq, q_chunk):
    """(B, Sq, ...) -> (nq, B, q_chunk, ...)."""
    b = x.shape[0]
    return x.reshape((b, nq, q_chunk) + x.shape[2:]).swapaxes(0, 1)


def _flash_forward_pass(qs, qps, ks, vs, kps, causal, window, scale):
    """Returns (out (nq, B, qc, Hkv, G, D), lse (nq, B, Hkv, G, qc))."""
    nq, b, q_chunk, hkv, g, d = qs.shape

    def one_q_block(args):
        qb, qp = args

        def kv_step(carry, kv_blk):
            m, l, acc = carry
            kb, vb, kp = kv_blk
            logits = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb).astype(jnp.float32) * scale
            logits = logits + _mask(qp, kp, causal, window)[:, None, None]
            m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(qb.dtype), vb
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, q_chunk), NEG_INF, dtype=jnp.float32)
        l0 = jnp.zeros((b, hkv, g, q_chunk), dtype=jnp.float32)
        a0 = jnp.zeros((b, hkv, g, q_chunk, d), dtype=jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (ks, vs, kps))
        out = (acc / jnp.maximum(l[..., None], 1e-30)).astype(qb.dtype)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))  # (B, Hkv, G, qc)
        return out.transpose(0, 3, 1, 2, 4), lse

    return jax.lax.map(one_q_block, (qs, qps))


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_attention(q, k, v, qpos, kpos, causal, window, q_chunk=Q_CHUNK, kv_chunk=KV_CHUNK):
    """Online-softmax attention, chunked over q and kv, O(chunk^2) transients.

    The backward pass is hand-written (flash-attention style: recompute
    per-chunk probabilities from the saved log-sum-exp) — autodiff through the
    online-softmax scan would otherwise save the fp32 accumulator history,
    an O(S^2 / kv_chunk * D) buffer that dominates training memory.
    """
    out, _ = _flash_fwd_res(q, k, v, qpos, kpos, causal, window, q_chunk, kv_chunk)
    return out


def _flash_fwd_res(q, k, v, qpos, kpos, causal, window, q_chunk, kv_chunk):
    b, sq, hkv, g, d = q.shape
    sk = k.shape[1]
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, sk)
    assert sq % q_chunk == 0 and sk % kv_chunk == 0, (sq, sk, q_chunk, kv_chunk)
    nq, nk = sq // q_chunk, sk // kv_chunk
    scale = d**-0.5
    qs = _chunk_q(q, nq, q_chunk)
    qps = _chunk_q(qpos, nq, q_chunk)
    ks = _chunk_q(k, nk, kv_chunk)
    vs = _chunk_q(v, nk, kv_chunk)
    kps = _chunk_q(kpos, nk, kv_chunk)
    outs, lses = _flash_forward_pass(qs, qps, ks, vs, kps, causal, window, scale)
    out = outs.swapaxes(0, 1).reshape(b, sq, hkv, g, d)
    lse = lses  # (nq, B, Hkv, G, qc)
    return out, lse


def _flash_fwd(q, k, v, qpos, kpos, causal, window, q_chunk, kv_chunk):
    out, lse = _flash_fwd_res(q, k, v, qpos, kpos, causal, window, q_chunk, kv_chunk)
    return out, (q, k, v, qpos, kpos, out, lse)


def _flash_bwd(causal, window, q_chunk, kv_chunk, res, dout):
    q, k, v, qpos, kpos, out, lse = res
    b, sq, hkv, g, d = q.shape
    sk = k.shape[1]
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, sk)
    nq, nk = sq // q_chunk, sk // kv_chunk
    scale = d**-0.5

    # delta_i = sum_d dout_i * out_i  (B, Sq, Hkv, G)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    qs = _chunk_q(q, nq, q_chunk)
    qps = _chunk_q(qpos, nq, q_chunk)
    dos = _chunk_q(dout, nq, q_chunk)
    deltas = _chunk_q(delta, nq, q_chunk)  # (nq, B, qc, Hkv, G)
    ks = _chunk_q(k, nk, kv_chunk)
    vs = _chunk_q(v, nk, kv_chunk)
    kps = _chunk_q(kpos, nk, kv_chunk)

    def probs(qb, qp, kb, kp, lse_b):
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb).astype(jnp.float32) * scale
        logits = logits + _mask(qp, kp, causal, window)[:, None, None]
        return jnp.exp(logits - lse_b[..., None])  # (B, Hkv, G, qc, kc)

    # pass 1: dq — outer map over q chunks, inner scan over kv chunks
    def dq_block(args):
        qb, qp, do_b, dl_b, lse_b = args
        do_t = do_b.transpose(0, 2, 3, 1, 4)  # (B, Hkv, G, qc, D)

        def kv_step(dq_acc, kv_blk):
            kb, vb, kp = kv_blk
            p = probs(qb, qp, kb, kp, lse_b)
            dp = jnp.einsum("bhgqd,bkhd->bhgqk", do_t.astype(jnp.float32),
                            vb.astype(jnp.float32))
            ds = p * (dp - dl_b.transpose(0, 2, 3, 1)[..., None])
            dq_acc = dq_acc + scale * jnp.einsum(
                "bhgqk,bkhd->bqhgd", ds.astype(qb.dtype), kb
            ).astype(jnp.float32)
            return dq_acc, None

        dq0 = jnp.zeros(qb.shape, jnp.float32)
        dq_b, _ = jax.lax.scan(kv_step, dq0, (ks, vs, kps))
        return dq_b.astype(qb.dtype)

    dq = jax.lax.map(dq_block, (qs, qps, dos, deltas, lse))
    dq = dq.swapaxes(0, 1).reshape(b, sq, hkv, g, d)

    # pass 2: dk, dv — outer map over kv chunks, inner scan over q chunks
    def dkv_block(args):
        kb, vb, kp = args

        def q_step(carry, q_blk):
            dk_acc, dv_acc = carry
            qb, qp, do_b, dl_b, lse_b = q_blk
            p = probs(qb, qp, kb, kp, lse_b)  # (B, Hkv, G, qc, kc)
            do_t = do_b.transpose(0, 2, 3, 1, 4).astype(jnp.float32)
            dv_acc = dv_acc + jnp.einsum("bhgqk,bhgqd->bkhd", p, do_t)
            dp = jnp.einsum("bhgqd,bkhd->bhgqk", do_t, vb.astype(jnp.float32))
            ds = p * (dp - dl_b.transpose(0, 2, 3, 1)[..., None])
            dk_acc = dk_acc + scale * jnp.einsum(
                "bhgqk,bqhgd->bkhd", ds, qb.astype(jnp.float32)
            )
            return (dk_acc, dv_acc), None

        z = jnp.zeros(kb.shape, jnp.float32)
        (dk_b, dv_b), _ = jax.lax.scan(q_step, (z, z), (qs, qps, dos, deltas, lse))
        return dk_b.astype(kb.dtype), dv_b.astype(vb.dtype)

    dk, dv = jax.lax.map(dkv_block, (ks, vs, kps))
    dk = dk.swapaxes(0, 1).reshape(b, sk, hkv, d)
    dv = dv.swapaxes(0, 1).reshape(b, sk, hkv, d)

    f0 = lambda x: np.zeros(x.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, f0(qpos), f0(kpos)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def multihead_attention(
    params,
    x,
    positions,
    *,
    n_heads: int,
    n_kv_heads: int,
    rope_theta: float | None,
    causal: bool = True,
    window: int | None = None,
    kv_override: jax.Array | None = None,
    kv_positions: jax.Array | None = None,
):
    """Self- or cross-attention over a full sequence (train / prefill).

    Returns (output (B,S,Dm), k, v) — k/v returned so prefill can seed a cache.
    """
    g = n_heads // n_kv_heads
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    kv_src = x if kv_override is None else kv_override
    k = jnp.einsum("bsd,dhk->bshk", kv_src, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_src, params["wv"])
    kpos = positions if kv_positions is None else kv_positions
    if rope_theta is not None:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, kpos, rope_theta)
    b, sq = q.shape[0], q.shape[1]
    qg = q.reshape(b, sq, n_kv_heads, g, q.shape[-1])
    if max(sq, k.shape[1]) <= PLAIN_THRESHOLD:
        out = _plain_attention(qg, k, v, positions, kpos, causal, window)
    else:
        # pad q/kv lengths up to the flash chunk sizes; padded keys carry
        # kpos = -1 (always masked), padded query rows are sliced off
        sk = k.shape[1]
        pq = (-sq) % min(Q_CHUNK, sq)
        pk = (-sk) % min(KV_CHUNK, sk)
        if pq or pk:
            qg_p = jnp.pad(qg, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
            qpos_p = jnp.pad(positions, ((0, 0), (0, pq)))
            k_p = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
            v_p = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
            kpos_p = jnp.pad(kpos, ((0, 0), (0, pk)), constant_values=-1)
            out = _flash_attention(qg_p, k_p, v_p, qpos_p, kpos_p, causal, window)
            out = out[:, :sq]
        else:
            out = _flash_attention(qg, k, v, positions, kpos, causal, window)
    out = out.reshape(b, sq, n_heads, q.shape[-1])
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), k, v


@dataclasses.dataclass(frozen=True)
class KVCache:
    """Ring-buffer KV cache.  ``k``/``v``: (B, C, Hkv, D); ``length``: tokens
    already decoded (absolute position of the next token)."""

    k: jax.Array
    v: jax.Array
    length: jax.Array  # scalar int32

    @property
    def capacity(self) -> int:
        return self.k.shape[1]


jax.tree_util.register_dataclass(KVCache)


def init_cache(batch, capacity, n_kv_heads, head_dim, dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, capacity, n_kv_heads, head_dim), dtype=dtype),
        v=jnp.zeros((batch, capacity, n_kv_heads, head_dim), dtype=dtype),
        length=jnp.zeros((), dtype=jnp.int32),
    )


def decode_attention(
    params,
    x,
    cache: KVCache,
    *,
    n_heads: int,
    n_kv_heads: int,
    rope_theta: float | None,
    window: int | None = None,
    cross: bool = False,
):
    """One-token attention against a cache.

    x: (B, 1, Dm).  For self-attention the new token's K/V are written into
    the ring buffer at ``length % capacity``.  For cross-attention the cache
    holds the (fixed) encoder K/V and nothing is written.
    Returns (output (B,1,Dm), new_cache).
    """
    b = x.shape[0]
    g = n_heads // n_kv_heads
    pos = cache.length
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if rope_theta is not None:
        q = apply_rope(q, jnp.full((b, 1), pos, dtype=jnp.int32), rope_theta)

    if cross:
        k_all, v_all = cache.k, cache.v
        kpos = jnp.arange(cache.capacity, dtype=jnp.int32)
        valid = jnp.ones((cache.capacity,), dtype=bool)
        new_cache = cache
    else:
        k_new = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
        v_new = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
        if rope_theta is not None:
            k_new = apply_rope(k_new, jnp.full((b, 1), pos, dtype=jnp.int32), rope_theta)
        slot = (pos % cache.capacity).astype(jnp.int32)
        k_all = jax.lax.dynamic_update_slice(cache.k, k_new.astype(cache.k.dtype), (0, slot, 0, 0))
        v_all = jax.lax.dynamic_update_slice(cache.v, v_new.astype(cache.v.dtype), (0, slot, 0, 0))
        # absolute position held by each ring slot: the largest p <= pos with
        # p === slot (mod C); slots never written yet come out negative.
        idx = jnp.arange(cache.capacity, dtype=jnp.int32)
        kpos = pos - ((pos - idx) % cache.capacity)
        valid = kpos >= 0
        if window is not None:
            valid &= (pos - kpos) < window
        new_cache = KVCache(k=k_all, v=v_all, length=pos + 1)

    scale = q.shape[-1] ** -0.5
    qg = q.reshape(b, 1, n_kv_heads, g, q.shape[-1])
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_all).astype(jnp.float32) * scale
    logits = jnp.where(valid[None, None, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v_all)
    out = out.reshape(b, 1, n_heads, q.shape[-1])
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), new_cache
