"""Subprocess driver: GPipe pipeline parallelism on 4 fake devices."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import make_mesh
from repro.distributed.pp import gpipe, sequential_reference


def main() -> None:
    S, M, mb, d = 4, 6, 8, 32
    mesh = make_mesh((S,), ("pipe",))
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    params = {
        "w": jax.random.normal(k1, (S, d, d)) * d**-0.5,
        "b": jax.random.normal(k2, (S, d)) * 0.1,
    }
    xs = jax.random.normal(k3, (M, mb, d))

    def stage(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    out = jax.jit(lambda p, x: gpipe(stage, p, x, mesh, "pipe"))(params, xs)
    want = sequential_reference(stage, params, xs)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), atol=1e-5, rtol=1e-5
    )

    # gradients flow through the pipeline (ppermute transpose)
    def loss_pp(p):
        return jnp.sum(gpipe(stage, p, xs, mesh, "pipe") ** 2)

    def loss_seq(p):
        return jnp.sum(sequential_reference(stage, p, xs) ** 2)

    g_pp = jax.jit(jax.grad(loss_pp))(params)
    g_seq = jax.jit(jax.grad(loss_seq))(params)
    for a, b in zip(jax.tree.leaves(g_pp), jax.tree.leaves(g_seq)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4
        )
    print("pp-ok")


if __name__ == "__main__":
    main()
