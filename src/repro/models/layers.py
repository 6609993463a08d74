"""Shared layer library: norms, RoPE, embeddings, initializers.

Convention: every ``init_*`` returns a params pytree; the matching ``spec_*``
returns an identically-structured pytree of PartitionSpec.  Params are plain
dicts of jnp arrays (initializable under ``jax.eval_shape`` — nothing here
allocates when abstractly evaluated, which is how the 340B dry-run builds its
argument specs).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed.sharding import ShardCtx
from jax import shard_map


def dense_init(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else d_in**-0.5
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def init_norm(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype=dtype)}


def spec_norm():
    return {"scale": P(None)}


# -- rotary ------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(
    x: jax.Array, positions: jax.Array, theta: float
) -> jax.Array:
    """x: (..., T, H, hd); positions: broadcastable to (..., T)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., T, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., T, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -- embedding ----------------------------------------------------------------


def init_embed(key, vocab: int, d: int, dtype):
    return {"table": (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)}


def spec_embed(ctx: ShardCtx):
    return {"table": P(ctx.tp, None)}


def embed_tokens(params, tokens: jax.Array, ctx: ShardCtx | None = None
                 ) -> jax.Array:
    """Token lookup.  With tp>1 the lookup runs inside a shard_map: each
    vocab shard gathers its own rows and the shards psum — the partitioner
    otherwise all-gathers the whole table (measured 12 GiB f32 at 256k
    vocab).  Backward is the local scatter-add + the psum transpose."""
    table = params["table"]
    if ctx is None or ctx.mesh is None or ctx.tp is None or ctx.tp_size == 1:
        return table[tokens]
    vshard = table.shape[0] // ctx.tp_size
    dpspec = ctx.dp_axis
    trail = (None,) * (tokens.ndim - 1)  # tokens: (B,) decode or (B,T)

    def body(tbl, tok):
        start = jax.lax.axis_index(ctx.tp) * vshard
        local = tok - start
        ok = (local >= 0) & (local < vshard)
        rows = tbl[jnp.clip(local, 0, vshard - 1)]
        rows = jnp.where(ok[..., None], rows, 0)
        return jax.lax.psum(rows, ctx.tp)

    fn = shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(ctx.tp, None), P(dpspec, *trail)),
        out_specs=P(dpspec, *trail, None),
        check_vma=False,
    )
    return fn(table, tokens)


def init_lm_head(key, d: int, vocab: int, dtype):
    return {"w": dense_init(key, d, vocab, dtype)}


def spec_lm_head(ctx: ShardCtx):
    # vocab-sharded over tp only: FSDP-sharding the head's D dim made the
    # partitioner materialize a full f32 copy in backward (measured 12 GiB
    # at 256k vocab — §Perf cell A iteration 3)
    return {"w": P(None, ctx.tp)}


def lm_logits(params, x: jax.Array) -> jax.Array:
    return x @ params["w"]


def cross_entropy(
    logits: jax.Array, labels: jax.Array, z_loss: float = 0.0
) -> jax.Array:
    """Stable mean CE over all positions; logits may be vocab-sharded (the
    logsumexp reduces over the sharded axis — XLA inserts the psum)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = jnp.mean(lse - gold)
    if z_loss:
        loss = loss + z_loss * jnp.mean(lse**2)
    return loss


def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu2":  # nemotron squared-ReLU
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name}")
