"""GQA attention: train/prefill (XLA or Pallas-flash) + seq-sharded decode.

Decode follows the paper's segment/merge pattern (DESIGN.md §5): the KV cache
sequence dim is range-partitioned across the ``model`` axis (each device owns
one contiguous chunk — a "segment"); every device computes partial attention
over its chunk and the partials are merged with a logsumexp-weighted psum —
the same structure as sorting per-range sub-streams and concatenating, applied
to the softmax monoid instead of the sort monoid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..distributed.sharding import ShardCtx
from .layers import apply_rope, dense_init
from jax import shard_map


def init_attn(key, cfg: ModelConfig, dtype):
    D = cfg.d_model
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], D, H * hd, dtype),
        "wk": dense_init(ks[1], D, KV * hd, dtype),
        "wv": dense_init(ks[2], D, KV * hd, dtype),
        "wo": dense_init(ks[3], H * hd, D, dtype, scale=(H * hd) ** -0.5),
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
        p["bo"] = jnp.zeros((D,), dtype)
    return p


def use_context_parallel(cfg: ModelConfig, ctx: ShardCtx) -> bool:
    """GQA with kv_heads not divisible by tp: head-sharding forces an 8x2
    split of the (KV, G) dims that the partitioner thrashes against the
    T-sharded backward (measured: 24.5 GiB full re-replications per layer,
    §Perf cell A).  Instead shard attention over the SEQUENCE (context
    parallelism): T-sharded q/flash internals, tp-replicated attention
    weights (FSDP keeps them sharded over data), and one tiny K/V
    all-gather (K/V are kv_heads*hd wide — 12x smaller than the residual
    for command-r).

    Only active under SP (train/prefill): decode keeps head-TP weights —
    the seq-sharded decode path gathers the tiny q instead, and replicated
    weights would make decode gather full wq/wo per layer (measured 332 GB
    for nemotron decode).  Checkpoints are layout-agnostic, so train and
    serve can differ."""
    return (
        cfg.num_kv_heads % max(ctx.tp_size, 1) != 0
        and ctx.sp
        and ctx.tp_size > 1
    )


def spec_attn(cfg: ModelConfig, ctx: ShardCtx):
    if use_context_parallel(cfg, ctx):
        s = {
            "wq": P(ctx.fsdp, None),
            "wk": P(ctx.fsdp, None),
            "wv": P(ctx.fsdp, None),
            "wo": P(None, ctx.fsdp),
        }
        if cfg.use_bias:
            s |= {"bq": P(None), "bk": P(None), "bv": P(None), "bo": P(None)}
        return s
    s = {
        "wq": P(ctx.fsdp, ctx.tp),
        "wk": P(ctx.fsdp, ctx.tp),
        "wv": P(ctx.fsdp, ctx.tp),
        "wo": P(ctx.tp, ctx.fsdp),
    }
    if cfg.use_bias:
        s |= {"bq": P(ctx.tp), "bk": P(ctx.tp), "bv": P(ctx.tp),
              "bo": P(None)}
    return s


def _project_qkv(params, cfg: ModelConfig, x, positions):
    B, T, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.use_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, T, H, hd)
    k = k.reshape(B, T, KV, hd)
    v = v.reshape(B, T, KV, hd)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, causal: bool) -> jax.Array:
    """XLA attention: q (B,T,H,hd), k/v (B,S,KV,hd), fp32 softmax.

    Dispatches to the chunked flash path (custom_vjp, no T x S residuals)
    for long sequences — the quadratic path materializes (B,KV,G,T,S) fp32
    probs that the SPMD partitioner re-replicates in backward (measured
    24.5 GiB/layer at 104B/4k — EXPERIMENTS.md §Perf cell A)."""
    T, S = q.shape[1], k.shape[1]
    if T * S >= 2048 * 2048:
        return _sdpa_flash(q, k, v, causal)
    B, H, hd = q.shape[0], q.shape[2], q.shape[3]
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, T, KV, G, hd).astype(jnp.float32) * hd**-0.5
    logits = jnp.einsum("btkgd,bskd->bkgts", qg, k.astype(jnp.float32))
    if causal:
        mask = jnp.tril(jnp.ones((T, S), bool), k=S - T)
        logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)  # store probs bf16
    out = jnp.einsum("bkgts,bskd->btkgd", w, v)
    return out.reshape(B, T, H, hd).astype(q.dtype)


# -- chunked flash attention (pure-jnp twin of kernels/flash_attention) ------

_FLASH_CHUNK = 1024


def _flash_logits(qg, kc, causal, s0, T, Sc):
    # qg (B,KV,G,T,hd) fp32-scaled; kc (B,KV,Sc,hd)
    s = jnp.einsum("bkgtd,bksd->bkgts", qg, kc.astype(jnp.float32))
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (T, Sc), 0)
        cols = s0 + jax.lax.broadcasted_iota(jnp.int32, (T, Sc), 1)
        s = jnp.where(rows >= cols, s, -1e30)
    return s


def _flash_fwd(q, k, v, causal):
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    C = min(_FLASH_CHUNK, S)
    nc = S // C
    qg = (q.reshape(B, T, KV, G, hd).transpose(0, 2, 3, 1, 4)
          .astype(jnp.float32) * hd**-0.5)  # (B,KV,G,T,hd)
    kc = k.transpose(0, 2, 1, 3).reshape(B, KV, nc, C, hd)
    vc = v.transpose(0, 2, 1, 3).reshape(B, KV, nc, C, hd)

    def step(carry, inp):
        m, l, acc = carry
        kci, vci, s0 = inp
        s = _flash_logits(qg, kci, causal, s0, T, C)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgts,bksd->bkgtd", p, vci.astype(jnp.float32)
        )
        return (m_new, l, acc), None

    m0 = jnp.full((B, KV, G, T), -1e30, jnp.float32)
    l0 = jnp.zeros((B, KV, G, T), jnp.float32)
    a0 = jnp.zeros((B, KV, G, T, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (kc.transpose(2, 0, 1, 3, 4), vc.transpose(2, 0, 1, 3, 4),
         jnp.arange(nc) * C),
    )
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B,KV,G,T,hd)
    out_b = out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, hd).astype(q.dtype)
    return out_b, (q, k, v, out_b, lse)


def _flash_bwd(causal, res, dout):
    q, k, v, out, lse = res
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    C = min(_FLASH_CHUNK, S)
    nc = S // C
    qg = (q.reshape(B, T, KV, G, hd).transpose(0, 2, 3, 1, 4)
          .astype(jnp.float32) * hd**-0.5)
    do = (dout.reshape(B, T, KV, G, hd).transpose(0, 2, 3, 1, 4)
          .astype(jnp.float32))  # (B,KV,G,T,hd)
    og = (out.reshape(B, T, KV, G, hd).transpose(0, 2, 3, 1, 4)
          .astype(jnp.float32))
    delta = jnp.sum(do * og, axis=-1)  # (B,KV,G,T)
    kc = k.transpose(0, 2, 1, 3).reshape(B, KV, nc, C, hd)
    vc = v.transpose(0, 2, 1, 3).reshape(B, KV, nc, C, hd)

    def step(dq, inp):
        kci, vci, s0 = inp
        s = _flash_logits(qg, kci, causal, s0, T, C)
        p = jnp.exp(s - lse[..., None])  # (B,KV,G,T,C)
        dv = jnp.einsum("bkgts,bkgtd->bksd", p, do)
        dp = jnp.einsum("bkgtd,bksd->bkgts", do, vci.astype(jnp.float32))
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bkgts,bksd->bkgtd", ds,
                             kci.astype(jnp.float32))
        dk = jnp.einsum("bkgts,bkgtd->bksd", ds, qg)
        return dq, (dk, dv)

    dq0 = jnp.zeros((B, KV, G, T, hd), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(
        step, dq0,
        (kc.transpose(2, 0, 1, 3, 4), vc.transpose(2, 0, 1, 3, 4),
         jnp.arange(nc) * C),
    )
    # dq was accumulated against the SCALED q; undo the scale for d/dq
    dq = (dq * hd**-0.5).transpose(0, 3, 1, 2, 4).reshape(B, T, H, hd)
    dk = dks.transpose(1, 2, 0, 3, 4).reshape(B, KV, S, hd).transpose(
        0, 2, 1, 3
    )
    dv = dvs.transpose(1, 2, 0, 3, 4).reshape(B, KV, S, hd).transpose(
        0, 2, 1, 3
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _sdpa_flash(q, k, v, causal: bool):
    out, _ = _flash_fwd(q, k, v, causal)
    return out


def _sdpa_flash_fwd(q, k, v, causal):
    return _flash_fwd(q, k, v, causal)


_sdpa_flash.defvjp(_sdpa_flash_fwd, _flash_bwd)


def attention(
    params,
    cfg: ModelConfig,
    ctx: ShardCtx,
    x: jax.Array,
    positions: jax.Array,
    *,
    causal: bool = True,
    kv: tuple[jax.Array, jax.Array] | None = None,
    return_kv: bool = False,
):
    """Full-sequence attention (train / prefill).  ``kv`` overrides K/V for
    cross-attention (already projected, (B,S,KV,hd)); ``return_kv`` also
    returns the projected K/V for cache population at prefill."""
    B, T, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions)
    if kv is not None:
        k, v = kv
    if use_context_parallel(cfg, ctx):
        # context parallelism: q rows (and all flash internals) T-sharded,
        # K/V gathered (small); pins the partitioner to the T-sharded
        # strategy it otherwise reaches via full rematerialization
        q = ctx.constraint(q, P(ctx.dp_axis, ctx.tp, None, None))
        k = ctx.constraint(k, P(ctx.dp_axis, None, None, None))
        v = ctx.constraint(v, P(ctx.dp_axis, None, None, None))
    out = _sdpa(q, k, v, causal)
    out = out.reshape(B, T, -1) @ params["wo"]
    if cfg.use_bias:
        out = out + params["bo"]
    out = ctx.constraint(out, ctx.spec_resid())
    if return_kv:
        return out, (k, v)
    return out


def project_cross_kv(params, cfg: ModelConfig, enc: jax.Array):
    """Encoder-side K/V for cross attention (whisper)."""
    B, S, _ = enc.shape
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    k = (enc @ params["wk"]).reshape(B, S, KV, hd)
    v = (enc @ params["wv"]).reshape(B, S, KV, hd)
    if cfg.use_bias:
        k = k + params["bk"].reshape(KV, hd)
        v = v + params["bv"].reshape(KV, hd)
    return k, v


# -- decode: one new token against a seq-sharded cache -----------------------


def _decode_body(q, kc, vc, pos, *, axis: str, chunk: int, scale: float):
    """Per-device partial attention over the local cache chunk.

    q: (B, H, hd) replicated over ``axis``; kc/vc: (B, Sc, KV, hd) local
    chunk; pos: (B,) current lengths.  Combines partials with an
    LSE-weighted psum — the merge step of the paper's segment pattern.
    """
    dev = jax.lax.axis_index(axis)
    B, H, hd = q.shape
    KV = kc.shape[2]
    G = H // KV
    start = dev * chunk
    idx = start + jnp.arange(chunk)  # global positions of the local chunk
    visible = idx[None, :] <= pos[:, None]  # (B, Sc)
    qg = q.reshape(B, KV, G, hd).astype(jnp.float32) * scale
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, kc.astype(jnp.float32))
    logits = jnp.where(visible[:, None, None, :], logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)  # local max
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgs,bskd->bkgd", p, vc.astype(jnp.float32))
    # merge across segments: weight each partial by exp(m - m_global)
    m_glob = jax.lax.pmax(m[..., 0], axis)[..., None]
    w = jnp.exp(m - m_glob)
    num = jax.lax.psum(o * w, axis)
    den = jax.lax.psum(l * w, axis)
    out = num / jnp.maximum(den, 1e-30)
    return out.reshape(B, H * hd)


def decode_attention(
    params,
    cfg: ModelConfig,
    ctx: ShardCtx,
    x: jax.Array,
    kcache: jax.Array,
    vcache: jax.Array,
    pos: jax.Array,
    *,
    cross: bool = False,
):
    """One decode step.  x: (B, 1, D); caches: (B, S, KV, hd) with S sharded
    over ``ctx.tp``; pos: (B,) int32 position of the new token.

    Returns (out (B,1,D), new_kcache, new_vcache).  For ``cross=True`` the
    cache is static (encoder K/V) and no update happens.
    """
    B, _, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    S = kcache.shape[1]
    tp = ctx.tp_size
    chunk = S // tp
    q = x[:, 0] @ params["wq"]
    if cfg.use_bias:
        q = q + params["bq"]
    q = q.reshape(B, H, hd)
    if cfg.use_rope:
        q = apply_rope(q[:, None, :, :], pos[:, None], cfg.rope_theta)[:, 0]

    if not cross:
        knew = x[:, 0] @ params["wk"]
        vnew = x[:, 0] @ params["wv"]
        if cfg.use_bias:
            knew, vnew = knew + params["bk"], vnew + params["bv"]
        knew = knew.reshape(B, KV, hd)
        if cfg.use_rope:
            knew = apply_rope(knew[:, None], pos[:, None], cfg.rope_theta)[:, 0]
        vnew = vnew.reshape(B, KV, hd)
    else:
        knew = vnew = None

    def body(q_, kc, vc, pos_, kn, vn):
        dev = jax.lax.axis_index(ctx.tp)
        if kn is not None:
            # scatter the new token into the owning segment's chunk
            local = pos_ - dev * chunk  # (B,)
            owns = (local >= 0) & (local < chunk)
            li = jnp.clip(local, 0, chunk - 1)
            onehot = jax.nn.one_hot(li, chunk, dtype=kc.dtype) * owns[:, None]
            kc = kc * (1 - onehot[..., None, None]) + (
                onehot[..., None, None] * kn[:, None]
            )
            vc = vc * (1 - onehot[..., None, None]) + (
                onehot[..., None, None] * vn[:, None]
            )
        out = _decode_body(
            q_, kc, vc, pos_, axis=ctx.tp, chunk=chunk, scale=hd**-0.5
        )
        return out, kc, vc

    dpspec = ctx.dp_axis
    cache_spec = P(dpspec, ctx.tp, None, None)
    flat_spec = P(dpspec, None)
    args = [q, kcache, vcache, pos]
    in_specs = [P(dpspec, None, None), cache_spec, cache_spec, P(dpspec)]
    if knew is not None:
        args += [knew, vnew]
        in_specs += [P(dpspec, None, None), P(dpspec, None, None)]
    else:
        args += [None, None]
        in_specs += [None, None]

    # shard_map can't take None leaves; close over cross-case instead
    if knew is None:
        fn = shard_map(
            lambda q_, kc, vc, p_: body(q_, kc, vc, p_, None, None),
            mesh=ctx.mesh,
            in_specs=tuple(in_specs[:4]),
            out_specs=(flat_spec, cache_spec, cache_spec),
        )
        out, kc, vc = fn(q, kcache, vcache, pos)
    else:
        fn = shard_map(
            body,
            mesh=ctx.mesh,
            in_specs=tuple(in_specs),
            out_specs=(flat_spec, cache_spec, cache_spec),
        )
        out, kc, vc = fn(q, kcache, vcache, pos, knew, vnew)

    y = out.astype(x.dtype) @ params["wo"]
    if cfg.use_bias:
        y = y + params["bo"]
    return y.astype(x.dtype)[:, None, :], kc, vc
