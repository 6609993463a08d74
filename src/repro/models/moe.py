"""Mixture-of-Experts with sort-based dispatch — the paper's technique in the
training hot path.

Grouping tokens by expert id is a range sort over a small key domain
(DESIGN.md §3): experts are the switch's segments, each ``model``-axis shard
owns a contiguous expert-id *range*, and tokens are bucketed into per-expert
contiguous capacity slots via the exact rank-within-range computation used by
:mod:`repro.core.distributed` (argsort by expert id → first-of-group →
rank).  Expert outputs are merged back with a weighted psum — the "server
concatenation" of the segment pattern.

Activations stay replicated over the ``model`` axis (standard TP layout), so
dispatch needs no all_to_all — each shard ranges over its own experts and the
psum it already owes TP merges the results.  Expert weights enter the
shard_map with their FSDP dim unsharded, which makes XLA all-gather them per
layer (ZeRO-3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig, MoEConfig
from ..distributed.sharding import ShardCtx
from .layers import activation, dense_init
from .mlp import init_mlp, mlp, spec_mlp
from jax import shard_map


def padded_experts(num_experts: int, multiple: int = 16) -> int:
    """Expert count padded to the tp width (granite: 40 -> 48).  Padded
    experts own an id range the router never produces, so they process
    empty capacity buffers — pure shape padding."""
    return -(-num_experts // multiple) * multiple


def init_moe(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    D, Fe, E = cfg.d_model, m.d_expert, padded_experts(m.num_experts)
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], D, m.num_experts, jnp.float32),
        "w_in": (jax.random.normal(ks[1], (E, D, Fe)) * D**-0.5).astype(dtype),
        "w_out": (jax.random.normal(ks[2], (E, Fe, D)) * Fe**-0.5).astype(dtype),
    }
    if cfg.mlp_gated:
        p["w_gate"] = (jax.random.normal(ks[3], (E, D, Fe)) * D**-0.5).astype(dtype)
    if m.num_shared:
        p["shared"] = init_mlp(
            ks[4], D, m.num_shared * Fe, cfg.mlp_gated, cfg.use_bias, dtype
        )
    return p


def spec_moe(cfg: ModelConfig, ctx: ShardCtx):
    m = cfg.moe
    s = {
        "router": P(None, None),
        "w_in": P(ctx.tp, ctx.fsdp, None),
        "w_out": P(ctx.tp, None, ctx.fsdp),
    }
    if cfg.mlp_gated:
        s["w_gate"] = P(ctx.tp, ctx.fsdp, None)
    if m.num_shared:
        s["shared"] = spec_mlp(ctx, cfg.mlp_gated, cfg.use_bias)
    return s


def _dispatch_body(
    x, topk_idx, topk_p, w_in, w_gate, w_out,
    *, cfg: ModelConfig, capacity: int, tp_axis: str,
):
    """Per-shard: range-partition assignments to local experts, grouped GEMM,
    weighted scatter back, psum merge.  x: (n, D) local tokens (replicated
    over tp); w_*: (E_local, ...) local expert slabs."""
    m = cfg.moe
    n, D = x.shape
    k = m.top_k
    e_local = w_in.shape[0]
    dev = jax.lax.axis_index(tp_axis)
    e0 = dev * e_local

    eid = topk_idx.reshape(n * k)
    tok = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    prob = topk_p.reshape(n * k)
    local = (eid >= e0) & (eid < e0 + e_local)
    # range partition (the switch's SwitchInsert): sort assignments by
    # expert id; rank within the expert group = capacity slot
    key = jnp.where(local, eid - e0, e_local)  # non-local sorts to the end
    order = jnp.argsort(key)
    sk = key[order]
    first = jnp.searchsorted(sk, sk, side="left")
    rank = jnp.arange(n * k) - first
    live = (sk < e_local) & (rank < capacity)
    slot_e = jnp.where(live, sk, e_local)            # (n*k,) drop row idx
    slot_c = jnp.where(live, rank, 0)
    stok = tok[order]
    sprob = prob[order]

    # gather token vectors into (E_local, C, D) buffers (+1 drop row).
    # scatter-ADD with live-masking, not scatter-set: non-live assignments
    # collide on the junk row and scatter-set's transpose misattributes
    # gradients under collisions (measured 9.6x router-grad blowup at tp=16
    # — §Perf cell C); add has an exact transpose and live slots are unique.
    live_f = live.astype(x.dtype)[:, None]
    buf = jnp.zeros((e_local + 1, capacity, D), x.dtype)
    buf = buf.at[slot_e, slot_c].add(x[stok] * live_f, mode="drop")
    slot_tok = jnp.full((e_local + 1, capacity), n, jnp.int32)
    slot_tok = slot_tok.at[slot_e, slot_c].set(stok, mode="drop")
    slot_p = jnp.zeros((e_local + 1, capacity), jnp.float32)
    slot_p = slot_p.at[slot_e, slot_c].add(sprob * live, mode="drop")

    act = activation(cfg.mlp_act)
    h = jnp.einsum("ecd,edf->ecf", buf[:-1], w_in)
    if w_gate is not None:
        h = act(h) * jnp.einsum("ecd,edf->ecf", buf[:-1], w_gate)
    else:
        h = act(h)
    y = jnp.einsum("ecf,efd->ecd", h, w_out)  # (E_local, C, D)
    y = y * slot_p[:-1, :, None].astype(y.dtype)

    out = jnp.zeros((n + 1, D), y.dtype)
    out = out.at[slot_tok[:-1].reshape(-1)].add(
        y.reshape(-1, D), mode="drop"
    )
    out = out[:n]
    # merge expert contributions across the expert-range shards
    out = jax.lax.psum(out, tp_axis)
    dropped = jax.lax.psum((~live & (sk < e_local)).sum(), tp_axis)
    return out, dropped[None]  # (1,) per dp shard; caller sums over dp


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _a2a_bf16(x, axis):
    """all_to_all whose COTANGENT crosses the fabric in bf16: the plain
    transpose exchanges f32 cotangents (measured 6 GiB/op at deepseek/4k —
    §Perf cell C iteration 2)."""
    return jax.lax.all_to_all(x, axis, 0, 0, tiled=True)


def _a2a_bf16_fwd(x, axis):
    # residual: a zero-size dtype token (dtypes themselves aren't jax types)
    return _a2a_bf16(x, axis), jnp.zeros((0,), x.dtype)


def _a2a_bf16_bwd(axis, token, dout):
    d = dout.astype(jnp.bfloat16)
    return (jax.lax.all_to_all(d, axis, 0, 0, tiled=True).astype(token.dtype),)


_a2a_bf16.defvjp(_a2a_bf16_fwd, _a2a_bf16_bwd)


def _dispatch_a2a_body(
    x, w_in, w_gate, w_out, router,
    *, cfg: ModelConfig, capacity: int, send_cap: int, tp_axis: str,
    tp_size: int,
):
    """all_to_all expert dispatch (the paper's switch fabric, DESIGN.md §3).

    x: (n_loc, D) — this shard's OWN tokens (SP keeps the residual
    T-sharded, so routing/sort runs on 1/tp of the tokens instead of being
    replicated).  Assignments are range-partitioned by owning shard, sent
    over the fabric (all_to_all), grouped into per-expert capacity slots by
    the same sort-rank primitive, processed, and returned by the reverse
    exchange.  Per-device dispatch traffic drops ~tp-fold vs the replicated
    path (§Perf cell C)."""
    m = cfg.moe
    n_loc, D = x.shape
    k = m.top_k
    e_local = w_in.shape[0]
    dev = jax.lax.axis_index(tp_axis)

    logits = x.astype(jnp.float32) @ router
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_idx = jax.lax.top_k(probs, k)
    topk_p = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)
    # load-balance aux from local stats (mean over shards via pmean)
    me = jax.lax.pmean(jnp.mean(probs, axis=0), tp_axis)
    ce = jnp.zeros((m.num_experts,), jnp.float32).at[
        topk_idx.reshape(-1)].add(1.0) / (n_loc * k)
    ce = jax.lax.pmean(ce, tp_axis)
    aux = m.num_experts * jnp.sum(me * ce)

    eid = topk_idx.reshape(n_loc * k)
    tok = jnp.repeat(jnp.arange(n_loc, dtype=jnp.int32), k)
    prob = topk_p.reshape(n_loc * k)
    dst = eid // e_local  # owning shard — the range partition

    # rank within destination (same primitive as core.distributed)
    order = jnp.argsort(dst)
    sd = dst[order]
    first = jnp.searchsorted(sd, sd, side="left")
    rank = jnp.arange(n_loc * k) - first
    live = rank < send_cap
    row = jnp.where(live, sd, tp_size)
    col = jnp.where(live, rank, 0)
    overflow = (~live).sum()

    live_f = live.astype(x.dtype)[:, None]
    send_x = jnp.zeros((tp_size + 1, send_cap, D), x.dtype)
    send_x = send_x.at[row, col].add(x[tok[order]] * live_f, mode="drop")
    send_e = jnp.full((tp_size + 1, send_cap), m.num_experts, jnp.int32)
    send_e = send_e.at[row, col].set(eid[order], mode="drop")
    send_t = jnp.full((tp_size + 1, send_cap), n_loc, jnp.int32)
    send_t = send_t.at[row, col].set(tok[order], mode="drop")
    send_p = jnp.zeros((tp_size + 1, send_cap), jnp.float32)
    send_p = send_p.at[row, col].add(prob[order] * live, mode="drop")

    # the fabric (bf16 cotangents for the big payload)
    rx = _a2a_bf16(send_x[:-1], tp_axis)
    re = jax.lax.all_to_all(send_e[:-1], tp_axis, 0, 0, tiled=True)
    rp = jax.lax.all_to_all(send_p[:-1], tp_axis, 0, 0, tiled=True)

    # group received assignments into per-expert capacity slots
    nr = tp_size * send_cap
    rxf = rx.reshape(nr, D)
    ref = re.reshape(nr)
    rpf = rp.reshape(nr)
    lkey = jnp.where(ref < m.num_experts, ref - dev * e_local, e_local)
    lkey = jnp.where((lkey >= 0) & (lkey < e_local), lkey, e_local)
    order2 = jnp.argsort(lkey)
    sk = lkey[order2]
    first2 = jnp.searchsorted(sk, sk, side="left")
    rank2 = jnp.arange(nr) - first2
    live2 = (sk < e_local) & (rank2 < capacity)
    slot_e = jnp.where(live2, sk, e_local)
    slot_c = jnp.where(live2, rank2, 0)
    overflow = overflow + ((~live2) & (sk < e_local)).sum()

    live2_f = live2.astype(x.dtype)[:, None]
    buf = jnp.zeros((e_local + 1, capacity, D), x.dtype)
    buf = buf.at[slot_e, slot_c].add(rxf[order2] * live2_f, mode="drop")
    slot_src = jnp.full((e_local + 1, capacity), nr, jnp.int32)
    slot_src = slot_src.at[slot_e, slot_c].set(
        order2.astype(jnp.int32), mode="drop"
    )
    slot_p = jnp.zeros((e_local + 1, capacity), jnp.float32)
    slot_p = slot_p.at[slot_e, slot_c].add(rpf[order2] * live2, mode="drop")

    act = activation(cfg.mlp_act)
    h = jnp.einsum("ecd,edf->ecf", buf[:-1], w_in)
    if w_gate is not None:
        h = act(h) * jnp.einsum("ecd,edf->ecf", buf[:-1], w_gate)
    else:
        h = act(h)
    y = jnp.einsum("ecf,efd->ecd", h, w_out)
    y = y * slot_p[:-1, :, None].astype(y.dtype)

    # return by the reverse exchange: scatter back to receive order, a2a
    back = jnp.zeros((nr + 1, D), y.dtype)
    back = back.at[slot_src[:-1].reshape(-1)].add(
        y.reshape(-1, D), mode="drop"
    )
    back = back[:nr].reshape(tp_size, send_cap, D)
    ry = _a2a_bf16(back, tp_axis)

    out = jnp.zeros((n_loc + 1, D), y.dtype)
    out = out.at[send_t[:-1].reshape(-1)].add(
        ry.reshape(-1, D), mode="drop"
    )
    return out[:n_loc], aux[None], overflow[None]


def use_a2a(cfg: ModelConfig, ctx: ShardCtx) -> bool:
    return ctx.sp and ctx.tp_size > 1


def moe_layer_a2a(
    params, cfg: ModelConfig, ctx: ShardCtx, x: jax.Array,
    x_full: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """all_to_all expert-parallel MoE over T-sharded tokens (SP).

    x: (B, T, D) with T sharded over tp; ``x_full`` (full-T) feeds the
    TP-sharded shared experts if present.  Returns (out, aux, dropped)."""
    m = cfg.moe
    B, T, D = x.shape
    tp_size = ctx.tp_size
    n = B * T
    n_loc = n // tp_size
    capacity = max(int(n * m.top_k / m.num_experts * m.capacity_factor), 1)
    send_cap = max(int(n_loc * m.top_k / tp_size * 2.0), 8)  # 2x slack
    dpspec = ctx.dp_axis
    w_gate = params.get("w_gate")
    wspec = P(ctx.tp, None, None)

    body = functools.partial(
        _dispatch_a2a_body, cfg=cfg, capacity=capacity, send_cap=send_cap,
        tp_axis=ctx.tp, tp_size=tp_size,
    )
    xf_spec = P(dpspec, ctx.tp, None)

    def wrapped(x_, wi, wg, wo, router):
        xl = x_.reshape(-1, D)  # (n_loc, D) local tokens
        out, aux, drop = body(xl, wi, wg, wo, router)
        return out.reshape(x_.shape), aux, drop

    # scalar outputs vary over dp and tp: stack over all mesh axes
    allax = (tuple(ctx.dp) + (ctx.tp,)) if ctx.dp else (ctx.tp,)
    sspec = P(allax)
    if w_gate is None:
        fn = shard_map(
            lambda x_, wi, wo, router: wrapped(x_, wi, None, wo, router),
            mesh=ctx.mesh,
            in_specs=(xf_spec, wspec, wspec, P(None, None)),
            out_specs=(xf_spec, sspec, sspec),
        )
        out, aux, dropped = fn(
            x, params["w_in"], params["w_out"], params["router"]
        )
    else:
        fn = shard_map(
            wrapped,
            mesh=ctx.mesh,
            in_specs=(xf_spec, wspec, wspec, wspec, P(None, None)),
            out_specs=(xf_spec, sspec, sspec),
        )
        out, aux, dropped = fn(
            x, params["w_in"], w_gate, params["w_out"], params["router"]
        )

    y = out.astype(x.dtype)
    if m.num_shared:
        y = y + mlp(params["shared"], cfg, ctx,
                    x_full if x_full is not None else x)
    return y, aux.mean(), dropped.sum()


def moe_layer(
    params, cfg: ModelConfig, ctx: ShardCtx, x: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (output (B,T,D), aux load-balance loss, dropped-token count).

    NOTE: forward-correct for any tp; the GRADIENT path is oracle-validated
    only for tp == 1 (at tp > 1 the shard_map transpose of the replicated
    router-prob input mis-accumulates — §Perf cell C log).  Training with
    tp > 1 must use :func:`moe_layer_a2a` (oracle-validated fwd+bwd); the
    LM blocks select it automatically under SP."""
    m = cfg.moe
    B, T, D = x.shape
    n = B * T
    xf = x.reshape(n, D)

    # router in fp32 (replicated weights; logits tiny)
    logits = xf.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_idx = jax.lax.top_k(probs, m.top_k)
    topk_p = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance aux: E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((m.num_experts,), jnp.float32).at[topk_idx.reshape(-1)].add(
        1.0
    ) / (n * m.top_k)
    aux = m.num_experts * jnp.sum(me * ce)

    tp_size = ctx.tp_size
    if padded_experts(m.num_experts) % tp_size:
        raise ValueError(
            f"{padded_experts(m.num_experts)} padded experts not divisible "
            f"by tp={tp_size}"
        )
    capacity = max(
        int(n * m.top_k / m.num_experts * m.capacity_factor), 1
    )

    dpspec = ctx.dp_axis
    w_gate = params.get("w_gate")
    body = functools.partial(
        _dispatch_body, cfg=cfg, capacity=capacity, tp_axis=ctx.tp
    )
    wspec = P(ctx.tp, None, None)
    if w_gate is None:
        fn = shard_map(
            lambda a, b, c, wi, wo: body(a, b, c, wi, None, wo),
            mesh=ctx.mesh,
            in_specs=(P(dpspec, None), P(dpspec, None), P(dpspec, None),
                      wspec, wspec),
            out_specs=(P(dpspec, None), P(dpspec)),
        )
        out, dropped = fn(xf, topk_idx, topk_p, params["w_in"], params["w_out"])
    else:
        fn = shard_map(
            body,
            mesh=ctx.mesh,
            in_specs=(P(dpspec, None), P(dpspec, None), P(dpspec, None),
                      wspec, wspec, wspec),
            out_specs=(P(dpspec, None), P(dpspec)),
        )
        out, dropped = fn(
            xf, topk_idx, topk_p, params["w_in"], w_gate, params["w_out"]
        )

    y = out.reshape(B, T, D).astype(x.dtype)
    if m.num_shared:
        y = y + mlp(params["shared"], cfg, ctx, x)
    return y, aux, dropped.sum()
