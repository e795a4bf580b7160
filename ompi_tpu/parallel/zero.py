"""ZeRO-1 optimizer-state sharding over a mesh axis.

The optimizer's persistent tree (f32 master weights + Adam moments) is
the largest HBM resident after activations; under data parallelism it is
redundantly replicated.  ZeRO stage 1 shards it over the dp axis: each
rank stores and updates 1/dp of every leaf, then the updated parameters
are re-gathered to replicated form for the next forward.

TPU-first realization: no parameter server, no hand-written gather — each
leaf is flattened, padded to a dp multiple and reshaped to (dp, n); the
optimizer state carries a `NamedSharding(mesh, P(axis))` on that leading
axis, `with_sharding_constraint` pins the update math to the shards, and
XLA's SPMD partitioner emits exactly one all-gather per leaf to produce
the replicated updated params (the scaling-book recipe: annotate
shardings, let XLA insert the collectives).

Reference analog: there is none in Open MPI itself — this is the
distributed-training subsystem the flagship model exercises (SURVEY §5
row 77/78 scale story); the pattern matches optimizer sharding in public
JAX training stacks.
"""

from __future__ import annotations

from typing import Any

__all__ = ["zero1_wrap"]


def _flatten_pad(x, dp: int):
    import jax.numpy as jnp

    flat = jnp.ravel(x)
    n = -(-flat.size // dp) * dp
    if n != flat.size:
        flat = jnp.pad(flat, (0, n - flat.size))
    return flat.reshape(dp, n // dp)


def zero1_wrap(opt, mesh, axis: str = "dp", param_dtype: Any = None,
               param_specs: Any = None):
    """Wrap an optax GradientTransformation into a ZeRO-1 sharded update.

    Returns (init, update):
      init(params)  -> opt_state whose every leaf is (dp, n/dp)-shaped
                       and committed to NamedSharding(mesh, P(axis))
                       (state = {"opt": inner_state, "master": f32 tree})
      update(grads, opt_state, params) -> (new_params, new_opt_state)
                       for use INSIDE jit: shards the Adam math over
                       ``axis`` and re-gathers the updated params.

    ``param_dtype``: dtype of the returned live params (the master copy
    stays f32, exactly the mixed-precision master-weights scheme).
    ``param_specs``: optional pytree of PartitionSpec matching params —
    updated live params are constrained to THESE specs (tp-sharded
    weights stay tp-sharded; only the ``axis`` redundancy is gathered).
    Without it params re-gather fully replicated.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.core.scopes import scope

    if axis not in mesh.shape:
        raise ValueError(
            f"zero1 axis {axis!r} is not a mesh axis "
            f"(have {tuple(mesh.shape)}); set zero1_axis to one of "
            f"those or None")
    dp = int(mesh.shape[axis])
    shard = NamedSharding(mesh, P(axis))

    def init(params):
        def prep(p):
            return jax.device_put(
                _flatten_pad(jnp.asarray(p, jnp.float32), dp), shard)

        master = jax.tree_util.tree_map(prep, params)
        inner = opt.init(master)
        # moments inherit master's (dp, n) shape; commit them to the
        # same sharding so the jitted update starts sharded, not
        # replicated-then-resharded
        inner = jax.tree_util.tree_map(
            lambda leaf: jax.device_put(leaf, shard)
            if getattr(leaf, "ndim", 0) == 2 else leaf, inner)
        return {"opt": inner, "master": master}

    @scope("optimizer")
    def update(grads, opt_state, params):
        del params  # the master copy is authoritative
        constrain = jax.lax.with_sharding_constraint

        def shard_grad(g):
            return constrain(_flatten_pad(g.astype(jnp.float32), dp),
                             shard)

        g32 = jax.tree_util.tree_map(shard_grad, grads)
        import optax

        updates, inner = opt.update(g32, opt_state["opt"],
                                    opt_state["master"])
        master = optax.apply_updates(opt_state["master"], updates)
        master = jax.tree_util.tree_map(
            lambda m: constrain(m, shard), master)
        # moments must STAY sharded too — without the constraint their
        # post-step sharding is whatever propagation decides, and a
        # replicated resolution would silently undo the HBM saving
        inner = jax.tree_util.tree_map(
            lambda leaf: constrain(leaf, shard)
            if getattr(leaf, "ndim", 0) == 2 else leaf, inner)

        def regather(m, p_like, spec):
            # constraint to the param's own spec = the SPMD partitioner
            # gathers ONLY the `axis` redundancy; tp/ep-sharded weights
            # stay sharded
            full = m.reshape(-1)[:p_like.size].reshape(p_like.shape)
            tgt = NamedSharding(mesh, spec if spec is not None else P())
            return constrain(full, tgt).astype(
                param_dtype or p_like.dtype)

        # manual flatten: PartitionSpec is itself a pytree node, so a
        # naive tree_map over the specs tree would recurse INTO the
        # specs; flatten_up_to treats each spec as one leaf
        m_leaves, treedef = jax.tree_util.tree_flatten(master)
        g_leaves = treedef.flatten_up_to(grads)
        s_leaves = (treedef.flatten_up_to(param_specs)
                    if param_specs is not None
                    else [None] * len(m_leaves))
        new_params = jax.tree_util.tree_unflatten(
            treedef, [regather(m, g, s) for m, g, s
                      in zip(m_leaves, g_leaves, s_leaves)])
        return new_params, {"opt": inner, "master": master}

    return init, update
