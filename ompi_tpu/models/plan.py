"""Layers of different kinds in one model: the plan.

``TransformerConfig.plan`` holds a :class:`LayerPlan`: for every layer its
mixer's kind ("kda": ``models/kda.py``; "mla": ``models/mla.py``) and its
MLP's ("dense": a gated MLP of width ``cfg.d_ff``; "moe": the dropless
routed experts of ``parallel/moe.routed_moe``, of width ``d_expert``, under
the configuration's ``moe_*`` fields).  A configuration without a plan is
attention and one MLP in every layer under one ``lax.scan``, and is not
touched by this module.

Leaves are stacked by kind, not by layer: the KDA leaves over the KDA
layers, the latent leaves over the latent layers, the dense MLP's over the
dense layers, the router's, the shared expert's and the experts' over the
routed layers; ``ln1`` and ``ln2`` over all layers.  A decoder carries each
layer's own buffers (:func:`carry` says which, and why not a stack a kind).
The plan is static, so both passes are python loops over it
(:func:`backbone`, the whole sequence; :func:`step`, one cached position):
a layer's place in its kind's stacks of leaves is a python integer, its
leaves are static slices, and its state is a buffer that the step reads and
replaces.  Not a scan over whole periods with the leading layers outside it:
the plans built so far are a handful of layers whose MLPs differ inside a
period; a deep plan would want the scan (``ROADMAP.md`` D1').

Nothing imports this module but a configuration that has a plan.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

__all__ = ["LayerPlan", "kda_mla_config", "leaf_names", "init_params",
           "carry", "grows", "carried", "backbone", "step", "check_mesh"]

ROUTER_LEAVES = ("wg", "wgb")
SHARED_LEAVES = ("sw1", "sw3", "sw2")


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """``layers``: a (mixer, mlp) pair of kinds a layer.  ``kda`` and
    ``mla``: the sizes of the mixers the plan names.  ``d_expert``: a routed
    expert's width (``cfg.d_ff`` is the dense MLP's)."""
    layers: tuple
    kda: Any = None
    mla: Any = None
    d_expert: int = 0

    def count(self, kind: str) -> int:
        """Layers whose mixer or MLP is ``kind``."""
        return sum(kind in pair for pair in self.layers)

    def index(self, layer: int, kind: str) -> int:
        """Layer ``layer``'s place in the stacks of ``kind``."""
        return sum(kind in pair for pair in self.layers[:layer])


def kda_mla_config(linear_attn_config: dict, first_k_dense_replace: int,
                       kv_lora_rank: int, qk_nope_head_dim: int,
                       qk_rope_head_dim: int, v_head_dim: int,
                       moe_intermediate_size: int, num_shared_experts: int,
                       routed_scaling_factor: float, experts_held=None,
                       kda_state_dtype: str = "float32", **sizes):
    """``entry.config`` of a configuration file whose layers are KDA or
    latent attention over a leading dense MLP and routed experts after it,
    under the keys such a model is published with: a ``TransformerConfig``
    whose plan is read off the published lists.
    ``linear_attn_config``'s layer numbers are 1-based and may run past
    ``n_layers`` (a file that cuts the depth keeps the group whole); the
    first ``first_k_dense_replace`` layers have the dense MLP and the others
    are routed.  ``experts_held`` ``{"first", "count"}``: the experts this
    device holds of the router's ``moe_experts``."""
    from ompi_tpu.models.kda import KDA
    from ompi_tpu.models.mla import MLA
    from ompi_tpu.models.transformer import TransformerConfig

    la = linear_attn_config
    layers = []
    for layer in range(1, sizes["n_layers"] + 1):
        if layer in la["kda_layers"]:
            mixer = "kda"
        elif layer in la["full_attn_layers"]:
            mixer = "mla"
        else:
            raise ValueError(f"layer {layer} is in neither kda_layers nor "
                             f"full_attn_layers")
        layers.append((mixer, "dense" if layer <= first_k_dense_replace
                       else "moe"))
    plan = LayerPlan(
        layers=tuple(layers),
        kda=KDA(n_heads=la["num_heads"], head_dim=la["head_dim"],
                conv=la["short_conv_kernel_size"], rank=la["head_dim"],
                state_dtype=kda_state_dtype),
        mla=MLA(n_heads=sizes["n_heads"], nope=qk_nope_head_dim,
                rope=qk_rope_head_dim, v_dim=v_head_dim,
                kv_rank=kv_lora_rank),
        d_expert=moe_intermediate_size)
    held = (None if experts_held is None
            else (int(experts_held["first"]), int(experts_held["count"])))
    return TransformerConfig(
        plan=plan, moe_gated=True, moe_score="sigmoid", moe_select_bias=True,
        moe_scale=float(routed_scaling_factor), moe_held=held,
        moe_shared=num_shared_experts * moe_intermediate_size, **sizes)


def check_mesh(cfg, mesh) -> None:
    """A head's state and the latent are whole on a device, and over ``sp``
    the delta rule needs an exclusive scan of per-rank states: neither split
    is built, and no cell asks."""
    for axis in ("sp", "tp"):
        if int(dict(mesh.shape).get(axis, 1)) > 1:
            raise ValueError(
                f"a layer plan (KDA and latent-attention mixers) runs with "
                f"{axis} == 1 only, and the mesh has {axis}="
                f"{mesh.shape[axis]}: its mixers are not split over {axis}")
    unknown = {kind for pair in cfg.plan.layers for kind in pair} - {
        "kda", "mla", "dense", "moe"}
    if unknown:
        raise ValueError(f"a layer plan of kinds {sorted(unknown)}: not "
                         f"built (have kda, mla; dense, moe)")


def _kinds(cfg) -> dict:
    """kind -> (layers of it, one layer's leaves: name -> (shape,
    deviation or None)), for the kinds the plan has."""
    from ompi_tpu.models import kda, mla

    pl, D = cfg.plan, cfg.d_model
    depth = max(1, 2 * cfg.n_layers) ** 0.5
    F, Fe, Fs = cfg.d_ff, pl.d_expert, cfg.moe_shared
    held = cfg.moe_held[1] if cfg.moe_held else cfg.moe_experts
    moe = {"wg": ((D, cfg.moe_experts), 0.02),
           "w1": ((held, D, Fe), D ** -0.5),
           "w3": ((held, D, Fe), D ** -0.5),
           "w2": ((held, Fe, D), Fe ** -0.5 / depth)}
    if cfg.moe_select_bias:
        moe["wgb"] = ((cfg.moe_experts,), 0.01)
    if Fs:
        moe.update({"sw1": ((D, Fs), D ** -0.5), "sw3": ((D, Fs), D ** -0.5),
                    "sw2": ((Fs, D), Fs ** -0.5 / depth)})
    dense = {"dw1": ((D, F), D ** -0.5), "dw3": ((D, F), D ** -0.5),
             "dw2": ((F, D), F ** -0.5 / depth)}
    kinds = {"kda": pl.kda and kda.leaf_shapes(cfg, pl.kda),
             "mla": pl.mla and mla.leaf_shapes(cfg, pl.mla),
             "dense": dense, "moe": moe}
    return {kind: (pl.count(kind), leaves)
            for kind, leaves in kinds.items() if pl.count(kind)}


def leaf_names(cfg) -> tuple:
    """The leaves stacked over layers (each over its kind's)."""
    return ("ln1", "ln2", *(name for _n, leaves in _kinds(cfg).values()
                            for name in leaves))


def init_params(cfg, rng) -> dict:
    """The whole tree as the program initialises it, float32."""
    L, D, V = cfg.n_layers, cfg.d_model, cfg.vocab
    params = {"emb": rng.normal(0, 0.02, size=(V, D)).astype(np.float32),
              **({} if cfg.tie_head else {"head": rng.normal(
                  0, 0.02, size=(V, D)).astype(np.float32)}),
              "ln1": np.ones((L, D), np.float32),
              "ln2": np.ones((L, D), np.float32),
              "lnf": np.ones((D,), np.float32)}
    for n, leaves in _kinds(cfg).values():
        for name, (dims, std) in leaves.items():
            params[name] = (np.ones((n, *dims), np.float32) if std is None
                            else rng.normal(0, std, size=(n, *dims)
                                            ).astype(np.float32))
    return params


def carry(cfg, mesh, batch: int, t_max: int) -> list:
    """A decoder's carry for ``batch`` sequences of up to ``t_max``
    positions, zeros, a layer's own buffers after another's in the plan's
    order, each with a leading axis of one (so that whoever fills a carry a
    group of sequences at a time finds the batch on axis 1, as in a stack
    over layers): a latent layer's cache ``(1, B, t_max, kv_rank + rope)``
    in the compute type; a KDA layer's convolution inputs ``(1, B, conv - 1,
    3 heads K)`` in the compute type and its matrix states ``(1, B, heads,
    K, K)`` in the mixer's ``state_dtype``.  The order is that of
    :func:`backbone`'s collected states.

    A buffer a layer and not a stack a kind, because the steps' loop over
    the plan is unrolled: with one float32 stack ``(KDA layers, B, heads, K,
    K)`` updated in place at a static index a layer, the TPU's compiler
    rematerialised the first layer's update (``...fusion.8`` and
    ``...fusion.8.remat``, both run, both on the one buffer: the chip's
    trace, PR 45), and the cached steps read a logit error of 0.15 where the
    same program with a bfloat16 stack read 0.013.  A layer's own buffer is
    replaced whole, which needs no reasoning about slices."""
    import jax.numpy as jnp

    from ompi_tpu.models import kda

    pl, cdt, buffers = cfg.plan, cfg.compute_dtype, []
    for mixer, _mlp_kind in pl.layers:
        if mixer == "mla":
            buffers.append(jnp.zeros((1, batch, t_max, pl.mla.cached), cdt))
        else:
            conv, state = kda.state_shapes(pl.kda, batch)
            buffers += [jnp.zeros((1, *conv), cdt),
                        jnp.zeros((1, *state), pl.kda.state_dtype)]
    return buffers


def grows(cfg) -> tuple:
    """Of each of :func:`carry`'s buffers: whether it grows with the
    sequence (a latent layer's cache) or not (a KDA layer's two)."""
    return tuple(grown for mixer, _mlp_kind in cfg.plan.layers
                 for grown in ((True,) if mixer == "mla" else (False, False)))


def carried(cfg, mesh, collected, t_max: int, into=None, **group) -> list:
    """:func:`backbone`'s collected states of whole sequences, or a carry
    that ends before ``t_max`` (``collected``, used up; :func:`carry`'s
    order), as :func:`carry`'s buffers: written into ``into``, or those that
    grow padded with zeros to ``t_max`` positions (``block.written``)."""
    from ompi_tpu.models.block import written

    grown = grows(cfg)
    return [written(new, t_max, buffer, axis=2 if longer else None, **group)
            for new, buffer, longer in zip(
                collected, into or [None] * len(grown), grown)]


def _mlp(cfg, comm, params, layer: int, kind: str, h):
    """Layer ``layer``'s MLP half, ``ln2`` and the residual add in it."""
    from ompi_tpu.models import transformer as tfm

    pl = cfg.plan
    at = pl.index(layer, kind)
    if kind == "dense":
        lp = {"ln2": params["ln2"][layer], "w1": params["dw1"][at],
              "w3": params["dw3"][at], "w2": params["dw2"][at]}
        return tfm._dense_ffn_tail(h, lp, comm, h.dtype, cfg.norm_eps,
                                   gated=(1.0, 1.0))
    # the experts' whole stacks and the layer's place in them
    # (``routed_moe`` says why); the router's and the shared expert's sliced
    lp = {"ln2": params["ln2"][layer],
          **{k: params[k] for k in ("w1", "w3", "w2")},
          **{k: params[k][at] for k in (*ROUTER_LEAVES, *SHARED_LEAVES)
             if k in params}}
    return tfm._moe_ffn_tail(cfg, h, lp, comm, layer=at)[0]


def _mixer_leaves(cfg, params, layer: int, kind: str) -> dict:
    at = cfg.plan.index(layer, kind)
    return {"ln1": params["ln1"][layer],
            **{k: params[k][at] for k in _kinds(cfg)[kind][1]}}


def _own_program(layer):
    """``layer`` as a traced function of its own (a ``jax.jit`` inside the
    enclosing trace, which the compiler inlines): the python loop over the
    plan puts every layer straight into the body of the enclosing loop,
    where JAX names the inside of a reduction by the scopes of that body
    alone, ``layers/attention/...``; a reader of a profile takes the first
    scope of a name for the pass it ran in (``prefill``, ``decode.step``),
    and a layer of its own starts its names at its own scopes, as the body
    of the other configurations' loop over layers does."""
    import jax

    return jax.jit(layer)


def backbone(cfg, comm, params, tokens, collect_kv: bool = False,
             grad_axes=None):
    """``transformer._local_backbone`` of a configuration with a plan: the
    per-device forward through the last norm, a python loop over the plan.
    Returns ``(h, aux)`` (aux 0: the dropless experts have no balance term),
    or with ``collect_kv`` ``(h, (aux, *states))``, the states as
    :func:`carry` orders and shapes them but the cache ``T`` long: a latent
    layer's rows ``(1, B, T, kv_rank + rope)``, a KDA layer's convolution
    inputs and matrix state after the last position.  ``grad_axes``: the
    layers' leaves' gradients are summed over those axes where the backward
    pass reaches the start of the loop."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.models import kda, mla
    from ompi_tpu.models import transformer as tfm

    check_mesh(cfg, comm.mesh)
    pl = cfg.plan
    with scope("embed"):
        h = tfm._lookup(cfg, params["emb"], tokens)
    if grad_axes is not None:
        params = {**params, **tfm._sum_in_backward(
            {k: params[k] for k in leaf_names(cfg)}, grad_axes)}

    def layer_fn(layer, mixer, mlp):
        def run(h, params):
            lp = _mixer_leaves(cfg, params, layer, mixer)
            h, *states = (kda if mixer == "kda" else mla).mixer(cfg, lp, h)
            return _mlp(cfg, comm, params, layer, mlp, h), states

        if cfg.remat in (True, "full"):
            run = jax.checkpoint(run)
        elif cfg.remat == "dots":
            run = jax.checkpoint(run, policy=jax.checkpoint_policies
                                 .dots_with_no_batch_dims_saveable)
        return _own_program(run)

    collected = []
    with scope("layers"):
        for layer, (mixer, mlp) in enumerate(pl.layers):
            h, states = layer_fn(layer, mixer, mlp)(h, params)
            collected += [state[None] for state in states]
    h = tfm._rmsnorm(h, params["lnf"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if not collect_kv:
        return h, aux
    return h, (aux, *collected)


def step(cfg, comm, params, h, states, pos):
    """Every layer for ONE new token position: h (B, 1, D) against the
    carry ``states`` (:func:`carry`'s buffers), each layer reading and
    replacing its own.  ``params``: the leaves stacked over layers.  Returns
    ``(h, *states)``."""
    from ompi_tpu.models import kda, mla

    pl = cfg.plan

    def layer_fn(layer, mixer, mlp):
        def run(h, params, own, pos):
            lp = _mixer_leaves(cfg, params, layer, mixer)
            own = [buffer[0] for buffer in own]
            if mixer == "kda":
                h, *own = kda.mixer(cfg, lp, h, carry=tuple(own))
            else:
                h, *own = mla.mixer(cfg, lp, h, carry=(*own, pos))
            return (_mlp(cfg, comm, params, layer, mlp, h),
                    [buffer[None] for buffer in own])

        return _own_program(run)

    states, at = list(states), 0
    for layer, (mixer, mlp) in enumerate(pl.layers):
        n = 2 if mixer == "kda" else 1
        h, states[at:at + n] = layer_fn(layer, mixer, mlp)(
            h, params, states[at:at + n], pos)
        at += n
    return (h, *states)
