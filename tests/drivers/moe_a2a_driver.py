"""Subprocess driver: a2a MoE dispatch == replicated psum dispatch (8 dev)."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.distributed.sharding import make_mesh
from repro.distributed.sharding import ShardCtx
from repro.models import moe as moe_mod


def main() -> None:
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = get_smoke_config("deepseek-moe-16b")
    cfg = dataclasses.replace(
        cfg, dtype="float32",
        moe=dataclasses.replace(cfg.moe, capacity_factor=8.0),
    )
    ctx = ShardCtx(mesh=mesh, tp="model", fsdp=None, dp=("data",), sp=True)
    params = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, T = 2, 32  # T % tp == 0
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, cfg.d_model)) * 0.3

    # psum path needs (router applied outside); a2a computes router inside —
    # same math, same weights
    y_ref, aux_ref, drop_ref = jax.jit(
        lambda p, x_: moe_mod.moe_layer(p, cfg, ctx, x_)
    )(params, x)
    y_a2a, aux_a2a, drop_a2a = jax.jit(
        lambda p, x_: moe_mod.moe_layer_a2a(p, cfg, ctx, x_)
    )(params, x)
    assert int(drop_ref) == 0 and int(drop_a2a) == 0, (drop_ref, drop_a2a)
    np.testing.assert_allclose(
        np.asarray(y_ref), np.asarray(y_a2a), atol=2e-4, rtol=2e-3
    )
    # aux estimators differ by construction: global sum(me*ce) vs
    # mean-over-dp-shards of per-shard sums (both standard; ~1% apart)
    np.testing.assert_allclose(
        float(aux_ref), float(aux_a2a), rtol=5e-2
    )

    # a2a gradients vs the DENSE per-token oracle (the psum path's router
    # grad is known-wrong at tp>1 — see moe_layer docstring / §Perf C)
    def dense_loss(p):
        m = cfg.moe
        xf = x.reshape(-1, cfg.d_model)
        probs = jax.nn.softmax(xf @ p["router"], -1)
        topk_p, topk_idx = jax.lax.top_k(probs, m.top_k)
        topk_p = topk_p / topk_p.sum(-1, keepdims=True)
        act = jax.nn.silu
        h = jnp.einsum("td,edf->tef", xf, p["w_in"])
        h = act(h) * jnp.einsum("td,edf->tef", xf, p["w_gate"])
        yall = jnp.einsum("tef,efd->ted", h, p["w_out"])
        y = jnp.zeros_like(xf)
        for j in range(m.top_k):
            sel = jnp.take_along_axis(
                yall, topk_idx[:, j][:, None, None], 1)[:, 0]
            y = y + topk_p[:, j][:, None] * sel
        from repro.models.mlp import mlp as mlp_fn
        y = y.reshape(x.shape) + mlp_fn(p["shared"], cfg, ctx, x)
        return jnp.sum(jnp.square(y))

    def a2a_loss(p):
        y, _, _ = moe_mod.moe_layer_a2a(p, cfg, ctx, x)
        return jnp.sum(jnp.square(y))

    g0 = jax.jit(jax.grad(dense_loss))(params)
    g2 = jax.jit(jax.grad(a2a_loss))(params)
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(g0)[0],
        jax.tree_util.tree_flatten_with_path(g2)[0],
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-3,
            err_msg=jax.tree_util.keystr(path),
        )
    print("moe-a2a-ok")


if __name__ == "__main__":
    main()
