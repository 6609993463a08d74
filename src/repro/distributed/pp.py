"""Pipeline parallelism: GPipe schedule over a mesh axis with ppermute.

``gpipe`` runs a stage function over ``S`` pipeline stages (devices along
``axis``) and ``M`` microbatches with the classic (M + S - 1)-tick schedule:
each tick every device applies its stage to its current buffer and passes
the activation to the next stage over ICI (``ppermute``).  Bubbles at the
edges are masked.  Differentiation works through ppermute (its transpose is
the reverse permute), so the same schedule backpropagates — GPipe's
activation-stash memory profile comes from the scan residuals.

This composes with the rest of the mesh: on the 512-chip mesh the ``pod``
axis can serve as the pipeline axis (2 stages across DCN, where PP's
point-to-point traffic pattern is the right fit for the weaker link).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def gpipe(
    stage_fn,
    stage_params,
    microbatches: jax.Array,
    mesh: Mesh,
    axis: str,
):
    """Run a pipelined stack.

    stage_fn: (params_slice, x (mb, ...)) -> y (mb, ...)  (shape-uniform)
    stage_params: pytree with leading stage axis (S, ...)
    microbatches: (M, mb, ...) input microbatches
    Returns (M, mb, ...) outputs of the final stage (replicated over axis).
    """
    S = mesh.shape[axis]
    M = microbatches.shape[0]

    def body(params_local, xs):
        # params_local: (1, ...) this device's stage; xs: (M, mb, ...) full
        idx = jax.lax.axis_index(axis)
        p = jax.tree.map(lambda a: a[0], params_local)
        zero = jnp.zeros_like(xs[0])
        perm = [(i, i + 1) for i in range(S - 1)]

        def tick(buf, t):
            # stage 0 ingests microbatch t (if in range); others use buf
            x_in = jax.lax.cond(
                (idx == 0),
                lambda: jax.lax.dynamic_index_in_dim(
                    xs, jnp.clip(t, 0, M - 1), keepdims=False
                ),
                lambda: buf,
            )
            live = (t - idx >= 0) & (t - idx < M)
            y = stage_fn(p, x_in)
            y = jnp.where(live, y, zero)
            nxt = jax.lax.ppermute(y, axis, perm)
            # collect final-stage outputs (masked psum later)
            out = jnp.where(live & (idx == S - 1), y, zero)
            return nxt, out

        _, outs = jax.lax.scan(tick, zero, jnp.arange(M + S - 1))
        # tick t emits microbatch t-(S-1) at the last stage
        outs = outs[S - 1 :]
        # replicate the last stage's outputs to all stages
        return jax.lax.psum(outs, axis)

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P(axis), stage_params,
                         is_leaf=lambda x: False) if False else
            _stage_specs(stage_params, axis),
            P(),
        ),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, microbatches)


def _stage_specs(stage_params, axis):
    return jax.tree.map(lambda _: P(axis), stage_params)


def sequential_reference(stage_fn, stage_params, microbatches):
    """Oracle: apply all stages in order to each microbatch."""
    S = jax.tree.leaves(stage_params)[0].shape[0]

    def apply_all(x):
        for s in range(S):
            p = jax.tree.map(lambda a: a[s], stage_params)
            x = stage_fn(p, x)
        return x

    return jax.vmap(apply_all)(microbatches)
