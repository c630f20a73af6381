"""Blocked (flash-style) attention in pure JAX with a custom VJP.

Never materializes the (S, T) score matrix: nested ``lax.scan`` over
(q-block, kv-block) tiles with online softmax, f32 accumulators, and a
flash-style backward (one recompute of the tile probabilities, dq carried as
an f32 buffer).  This is simultaneously

  * the memory-feasible attention path for long-sequence cells
    (prefill_32k / train_4k), and
  * the pure-jnp oracle structure mirrored by ``kernels/flash_attention``.

Layout: q (B, S, H, E); k (B, T, K, E), v (B, T, K, Ev) with H = G·K
(GQA); v's width may differ from q's and k's (latent attention).  The mask is
positional: causal with optional sliding window, with ``q_offset`` giving the
absolute position of query row 0.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _mask(qpos, kpos, causal, window):
    ok = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        ok = kpos[None, :] <= qpos[:, None]
    if window:
        ok = ok & (kpos[None, :] > qpos[:, None] - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _fwd(q, k, v, causal, window, q_offset, bq, bk):
    B, S, H, E = q.shape
    T, K = k.shape[1], k.shape[2]
    G, Ev = H // K, v.shape[-1]
    scale = E ** -0.5
    nq, nk = S // bq, T // bk
    qb = q.reshape(B, nq, bq, K, G, E)
    kb = k.reshape(B, nk, bk, K, E)
    vb = v.reshape(B, nk, bk, K, Ev)

    def q_step(_, qi_idx):
        qi, iq = qi_idx
        qpos = q_offset + iq * bq + jnp.arange(bq)

        def kv_step(carry, kv_idx):
            m, l, acc = carry
            kj, vj, jk = kv_idx
            kpos = jk * bk + jnp.arange(bk)
            s = jnp.einsum("bqkge,btke->bkgqt", qi, kj).astype(jnp.float32)
            s = s * scale + _mask(qpos, kpos, causal, window)[None, None, None]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = corr * l + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqt,btke->bkgqe", p.astype(qi.dtype), vj)
            acc_new = corr[..., None] * acc + pv.astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, K, G, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, bq), jnp.float32)
        a0 = jnp.zeros((B, K, G, bq, Ev), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4),
             jnp.arange(nk)))
        o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return None, (o, lse)

    _, (ob, lseb) = jax.lax.scan(
        q_step, None, (qb.transpose(1, 0, 2, 3, 4, 5), jnp.arange(nq)))
    # ob: (nq, B, K, G, bq, Ev) -> (B, S, H, Ev)
    out = ob.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, H, Ev)
    return out, lseb   # lse kept in block layout (nq,B,K,G,bq) for the bwd


def _bwd_impl(q, k, v, out, lse, dout, causal, window, q_offset, bq, bk):
    B, S, H, E = q.shape
    T, K = k.shape[1], k.shape[2]
    G, Ev = H // K, v.shape[-1]
    scale = E ** -0.5
    nq, nk = S // bq, T // bk
    qb = q.reshape(B, nq, bq, K, G, E).transpose(1, 0, 2, 3, 4, 5)
    dob = dout.reshape(B, nq, bq, K, G, Ev).transpose(1, 0, 2, 3, 4, 5)
    ob = out.reshape(B, nq, bq, K, G, Ev).transpose(1, 0, 2, 3, 4, 5)
    kb = k.reshape(B, nk, bk, K, E).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, bk, K, Ev).transpose(1, 0, 2, 3, 4)
    # D_i = rowsum(dout * out)
    Db = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32), axis=-1)
    # Db: (nq, B, bq, K, G); lse: (nq, B, K, G, bq)
    Db = Db.transpose(0, 1, 3, 4, 2)                     # (nq,B,K,G,bq)

    def kv_step(dq_acc, kv_idx):
        kj, vj, jk = kv_idx
        kpos = jk * bk + jnp.arange(bk)

        def q_step(carry, q_idx):
            dk_j, dv_j = carry
            qi, doi, lsei, Di, iq = q_idx
            qpos = q_offset + iq * bq + jnp.arange(bq)
            s = jnp.einsum("bqkge,btke->bkgqt", qi, kj).astype(jnp.float32)
            s = s * scale + _mask(qpos, kpos, causal, window)[None, None, None]
            p = jnp.exp(s - lsei[..., None])                       # (B,K,G,q,t)
            dp = jnp.einsum("bqkge,btke->bkgqt", doi, vj).astype(jnp.float32)
            ds = p * (dp - Di[..., None]) * scale
            dqi = jnp.einsum("bkgqt,btke->bqkge", ds.astype(qi.dtype), kj)
            dk_j = dk_j + jnp.einsum("bkgqt,bqkge->btke",
                                     ds.astype(qi.dtype), qi).astype(jnp.float32)
            dv_j = dv_j + jnp.einsum("bkgqt,bqkge->btke",
                                     p.astype(doi.dtype), doi).astype(jnp.float32)
            return (dk_j, dv_j), dqi

        (dk_j, dv_j), dqs = jax.lax.scan(
            q_step, (jnp.zeros((B, bk, K, E), jnp.float32),
                     jnp.zeros((B, bk, K, Ev), jnp.float32)),
            (qb, dob, lse, Db, jnp.arange(nq)))
        dq_acc = dq_acc + dqs.astype(jnp.float32)
        return dq_acc, (dk_j, dv_j)

    dq0 = jnp.zeros((nq, B, bq, K, G, E), jnp.float32)
    dq, (dk, dv) = jax.lax.scan(kv_step, dq0, (kb, vb, jnp.arange(nk)))
    dq = dq.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, E).astype(q.dtype)
    dk = dk.transpose(1, 0, 2, 3, 4).reshape(B, T, K, E).astype(k.dtype)
    dv = dv.transpose(1, 0, 2, 3, 4).reshape(B, T, K, Ev).astype(v.dtype)
    return dq, dk, dv


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=True, window=0, q_offset=0,
                    block_q=512, block_k=1024):
    """q: (B,S,H,E); k: (B,T,K,E), v: (B,T,K,Ev) -> (B,S,H,Ev)."""
    out, _ = _fwd(q, k, v, causal, window, q_offset,
                  min(block_q, q.shape[1]), min(block_k, k.shape[1]))
    return out


def _vjp_fwd(q, k, v, causal, window, q_offset, block_q, block_k):
    bq = min(block_q, q.shape[1])
    bk = min(block_k, k.shape[1])
    out, lse = _fwd(q, k, v, causal, window, q_offset, bq, bk)
    return out, (q, k, v, out, lse)


def _vjp_bwd(causal, window, q_offset, block_q, block_k, res, dout):
    q, k, v, out, lse = res
    bq = min(block_q, q.shape[1])
    bk = min(block_k, k.shape[1])
    return _bwd_impl(q, k, v, out, lse, dout, causal, window, q_offset, bq, bk)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)
