"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

The production meshes expose a natural stage axis: ``pod`` (2 stages at
2x16x16) — pipelining across pods converts the slow cross-pod gradient
all-reduce into point-to-point boundary ppermutes, the standard move when
inter-pod bandwidth is the binding constraint (DP/PP trade-off at 1000+
chips).

Implementation: layers are split into ``n_stages`` contiguous groups whose
parameters are sharded over the stage axis (each device holds only its
stage's layers). ``pipeline_apply`` runs the classic GPipe schedule inside
``shard_map``: with M microbatches and S stages, the loop runs M+S-1 ticks;
each tick every stage applies its block to its current microbatch and the
activations rotate one stage forward via ``jax.lax.ppermute``. Bubble
fraction = (S-1)/(M+S-1), as reported by :func:`bubble_fraction`.

Works under jit, differentiates (jax.grad through shard_map+ppermute), and
is validated against the unpipelined reference in
``tests/test_pipeline.py`` on 8 fake devices.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def stage_params_sharding(mesh: Mesh, axis: str = "pipe"):
    """Stacked per-stage params: leading dim = stage, sharded over the axis."""
    return NamedSharding(mesh, P(axis))


def pipeline_apply(block_fn, stage_params, x, *, mesh: Mesh,
                   axis: str = "pipe", n_microbatches: int | None = None):
    """Run a pipelined stack of stages.

    block_fn(params_stage, x_mb) -> y_mb — one stage's computation (itself
    typically a scan over that stage's layers).
    stage_params: pytree with leading dim = n_stages, sharded over ``axis``.
    x: (M, mb, ...) microbatched input, replicated over ``axis``.

    Returns y with the same (M, mb, ...) layout.
    """
    n_stages = mesh.shape[axis]
    m = x.shape[0]
    n_microbatches = n_microbatches or m
    assert m == n_microbatches

    def run(params_local, x_all):
        # params_local: (1, ...) this stage's slice; x_all: full (M, mb, ...)
        params_me = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        n_ticks = m + n_stages - 1
        buf = jnp.zeros_like(x_all[0])
        outputs = jnp.zeros_like(x_all)

        def tick(carry, t):
            buf, outputs = carry
            # stage 0 ingests microbatch t (when valid)
            mb_idx = jnp.clip(t, 0, m - 1)
            fresh = jax.lax.dynamic_index_in_dim(x_all, mb_idx, 0,
                                                 keepdims=False)
            cur = jnp.where(stage == 0, fresh, buf)
            # is this stage holding a real microbatch at tick t?
            my_mb = t - stage
            active = (my_mb >= 0) & (my_mb < m)
            y = block_fn(params_me, cur)
            y = jnp.where(active, y, cur)
            # last stage writes its finished microbatch
            out_idx = jnp.clip(my_mb, 0, m - 1)
            write = active & (stage == n_stages - 1)
            prev = jax.lax.dynamic_index_in_dim(outputs, out_idx, 0,
                                                keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(write, y, prev), out_idx, 0)
            # rotate activations forward one stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outputs), None

        (_, outputs), _ = jax.lax.scan(tick, (buf, outputs),
                                       jnp.arange(n_ticks))
        # every stage computed an `outputs` buffer; only the last stage's is
        # real — mask-and-psum broadcasts it back (replicated over the axis)
        outputs = jnp.where(stage == n_stages - 1, outputs, 0.0)
        return jax.lax.psum(outputs, axis)

    spec_p = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(run, mesh=mesh,
                       in_specs=(spec_p, P()), out_specs=P(),
                       check_vma=False)
    return fn(stage_params, x)
