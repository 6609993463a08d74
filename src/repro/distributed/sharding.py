"""Sharding vocabulary: axis roles and the spec helpers used by every layer.

Axis roles on the production mesh (DESIGN.md §5):

* ``tp``   — tensor parallel ("model"): heads, FFN hidden, experts, vocab.
* ``fsdp`` — ZeRO-3 param shard ("data"): a non-contracting dim of each large
  weight; XLA all-gathers per layer inside the scan.
* ``dp``   — batch axes: ("data",) single-pod, ("pod", "data") multi-pod.

Every layer builds its PartitionSpecs through a ``ShardCtx`` so the same
model code runs on the 1-device test mesh, the 256-chip pod and the 512-chip
two-pod mesh unchanged.
"""

from __future__ import annotations

import dataclasses

import jax
from jax import shard_map
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the partitioner places
    what the specs leave open, as every layer here expects."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    mesh: Mesh | None = None
    tp: str | None = "model"
    fsdp: str | None = "data"
    dp: tuple[str, ...] = ("data",)
    sp: bool = False  # sequence parallelism: residuals T-sharded over tp

    def axis_size(self, name: str | None) -> int:
        if self.mesh is None or name is None:
            return 1
        return self.mesh.shape.get(name, 1)

    @property
    def tp_size(self) -> int:
        return self.axis_size(self.tp)

    @property
    def dp_axis(self):
        """The batch-dim spec entry: None (replicated, e.g. batch=1 long
        decode), a single axis name, or a tuple of axis names."""
        if not self.dp:
            return None
        return self.dp if len(self.dp) > 1 else self.dp[0]

    # -- common specs -------------------------------------------------------
    def spec_batch(self, *rest: str | None) -> P:
        return P(self.dp_axis, *rest)

    def spec_resid(self) -> P:
        """(B, T, D) residual-stream spec.  With SP on, T is sharded over tp
        (Megatron-SP): remat-saved activations and norms shrink tp-fold; XLA
        all-gathers T before attention and reduce-scatters after."""
        if self.sp:
            return P(self.dp_axis, self.tp, None)
        return P(self.dp_axis, None, None)

    def spec_full(self) -> P:
        """(B, T, D) with full T — block-internal activations.  SP blocks
        all-gather T here (cheap: activations ≪ weights) so the partitioner
        never gathers weights over tp; outputs reduce-scatter back to
        spec_resid (Megatron-SP)."""
        return P(self.dp_axis, None, None)

    def spec_w2(self, contract_tp: bool) -> P:
        """(in, out) weight: TP on out by default, on in for the down-proj."""
        if contract_tp:
            return P(self.tp, self.fsdp)
        return P(self.fsdp, self.tp)

    def constraint(self, x: jax.Array, spec: P) -> jax.Array:
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec)
        )


def fsdp_gather(ctx: ShardCtx, tree, spec_tree):
    """Explicit per-layer ZeRO-3 all-gather, applied INSIDE the layer scan.

    Without this, XLA hoists the FSDP all-gather out of the scan and
    materializes the fully-gathered parameter stack (15+ GiB for the 340B
    config — measured, EXPERIMENTS.md §Perf iteration 1).  A shard_map
    all_gather on the loop-sliced leaf cannot be hoisted, bounding gathered
    weights to one layer.  Differentiation transposes it to a
    reduce-scatter, which is exactly ZeRO gradient sharding.
    """
    if ctx.mesh is None or ctx.fsdp is None:
        return tree

    def gather_leaf(x, spec):
        if ctx.fsdp not in spec:
            return x
        dim = list(spec).index(ctx.fsdp)
        out_spec = P(*[None if s == ctx.fsdp else s for s in spec])
        fn = shard_map(
            lambda v: jax.lax.all_gather(v, ctx.fsdp, axis=dim, tiled=True),
            mesh=ctx.mesh,
            in_specs=spec,
            out_specs=out_spec,
            # all_gather output IS replicated over the gathered axis; the
            # static VMA checker can't prove it — disable the check
            check_vma=False,
        )
        return fn(x)

    # tree's array leaves align with spec_tree's P leaves (flatten_up_to
    # stops at the reference structure, so the P tuples are not recursed)
    return jax.tree.map(gather_leaf, tree, spec_tree)


def pool_mesh(num_servers: int, axis_name: str = "server"):
    """One-axis mesh for the egress server pool's distributed merge
    (:func:`repro.core.distributed.pool_concat_sharded`) over the devices
    that are present: the largest device count that divides
    ``num_servers``, each device holding ``num_servers / d`` consecutive
    server shards.  One device (a single chip, or the plain CPU backend)
    gives a one-device mesh, on which the collective still runs.
    """
    d = max(c for c in range(1, len(jax.devices()) + 1) if num_servers % c == 0)
    return make_mesh((d,), (axis_name,))


def local_ctx() -> ShardCtx:
    """1-device (1,1) mesh for unit/smoke tests — same code paths (shard_map,
    psum, all_to_all) as the production mesh, trivially sized."""
    mesh = make_mesh((1, 1), ("data", "model"))
    return ShardCtx(mesh=mesh, tp="model", fsdp=None, dp=("data",))


def pod_ctx(mesh: Mesh) -> ShardCtx:
    """Production context from a launch/mesh.py mesh (pod axis optional)."""
    dp = ("pod", "data") if "pod" in mesh.shape else ("data",)
    return ShardCtx(mesh=mesh, tp="model", fsdp="data", dp=dp)
