"""Context / sequence parallelism (SP) for long-context decode.

For long_500k cells the KV cache shards over the "data" axis on the
*sequence* dim (each of the 16 data shards holds 32k of the 512k context).
One decode step computes a local partial softmax per shard and combines with
the global log-sum-exp trick:

    m = pmax(m_i);  l = psum(l_i·e^{m_i−m});  o = psum(o_i·e^{m_i−m}) / l

— one scalar-sized psum pair per layer instead of gathering 512k of KV.
Used by the jamba long_500k cell (its 9 attention layers); mamba needs no SP
(O(1) state) and mixtral's SWA ring cache is window-bounded.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def sp_attention_local(q, k_local, v_local, pos_local, cur_pos):
    """Partial attention of one shard. q (B,H,hd); k/v (B,T_l,KV,hd);
    pos_local (B,T_l) global positions; cur_pos (B,).
    Returns (o (B,H,hd), m (B,H), l (B,H))."""
    b, h, hd = q.shape
    kv = k_local.shape[2]
    g = h // kv
    qg = q.reshape(b, kv, g, hd)
    logits = jnp.einsum("bkgd,btkd->bkgt", qg, k_local) / jnp.sqrt(hd)
    logits = logits.astype(jnp.float32)
    valid = pos_local <= cur_pos[:, None]
    logits = jnp.where(valid[:, None, None, :], logits, -jnp.inf)
    m = jnp.max(logits, axis=-1)                            # (B,KV,G)
    e = jnp.exp(logits - m[..., None])
    e = jnp.where(jnp.isfinite(logits), e, 0.0)
    l = jnp.sum(e, axis=-1)
    o = jnp.einsum("bkgt,btkd->bkgd", e.astype(v_local.dtype), v_local)
    return (o.reshape(b, h, hd), m.reshape(b, h), l.reshape(b, h))


def sp_combine(o, m, l, axis: str):
    """Global log-sum-exp combine across the SP axis."""
    m_glob = jax.lax.pmax(m, axis)
    corr = jnp.exp(m - m_glob)
    l_glob = jax.lax.psum(l * corr, axis)
    o_glob = jax.lax.psum(o * corr[..., None].astype(o.dtype), axis)
    return o_glob / jnp.maximum(l_glob, 1e-20)[..., None].astype(o.dtype)


def sp_decode_attention(mesh, axis: str, q, k_sh, v_sh, pos_sh, cur_pos):
    """shard_map wrapper: q (B,H,hd) replicated; k/v (B,T,KV,hd) sharded on
    T over `axis`; pos (B,T) sharded likewise. Returns (B,H,hd)."""
    def inner(q, k, v, p, cp):
        o, m, l = sp_attention_local(q, k, v, p, cp)
        return sp_combine(o, m, l, axis)

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(), P(None, axis, None, None), P(None, axis, None, None),
                  P(None, axis), P()),
        out_specs=P(), check_vma=False)(q, k_sh, v_sh, pos_sh, cur_pos)
