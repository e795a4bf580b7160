"""Layers of different kinds in one model: the plan.

``TransformerConfig.plan`` holds a :class:`LayerPlan`: for every row its
mixer's kind (``MIXERS``: "kda", ``models/kda.py``; "mla", ``models/mla.py``;
"lightning", ``models/lightning.py``; "block_select",
``models/block_select.py``; "ssm", the Mamba-2 mixer of ``models/ssm.py``;
"attention", the grouped-query attention of ``models/block.py``, rotary or
not) and its MLP's ("dense": a gated MLP of width ``cfg.d_ff``; "moe": the
dropless routed experts of ``parallel/moe.routed_moe``, of width
``d_expert``, under the configuration's ``moe_*`` fields).  A configuration
without a plan is attention and one MLP in every layer under one
``lax.scan``, and is not touched by this module.  A row of the plan is one
mixer and one MLP, or one of the two alone (the other None: a model whose
layer is a single mixer, under one norm, and nothing after it); a model's
layer that has more than one mixer is as many rows, with
``LayerPlan.branches`` saying what else reads the stream inside it and where
that lands (a shortcut-connected layer: routed experts that read the first
row's normed post-mixer stream and land after the second row's MLP, beside
everything in between).

A mixer's kind is a module and a line of ``MIXERS``.  The plan holds the
kind's sizes in the field of the kind's name, and the module (or, where the
module's own ``mixer`` and ``carry`` are another form's, its ``PLAN_KIND``)
gives: ``leaf_shapes(cfg, sizes)``, one layer's leaves; ``buffers(cfg, sizes,
batch, t_max)``, what a decoder carries for one layer, ``(shape, dtype,
axis)`` each, ``axis`` the axis of a carried buffer (its leading axis of one
counted) along which it grows with the sequence, the shape given at
``t_max`` positions, or None for a state that does not grow; ``mixer(cfg,
lp, h, carry=None)``, the layer's mixer with its norm and its residual add,
over whole sequences (returns ``(h, *states)``, the states in ``buffers``'
order, a growing one as long as the sequences) and, T == 1, against the
layer's own buffers, with the position after them where the module says
``POSITIONED`` (returns ``(h, *buffers)``), with the communicator
(``comm=``) where it says ``MESHED``, and told whether a gradient may be
asked of whole sequences where its ``mixer`` takes ``forward_only=``;
and, where a layer's place in the
model is part of its arithmetic, ``constants(sizes, layer)``, what the plan
hands the mixer beside its leaves.

Leaves are stacked by kind, not by layer: the KDA leaves over the KDA
layers, the latent leaves over the latent layers, the dense MLP's over the
dense layers, the router's, the shared expert's and the experts' over the
routed layers; ``ln1`` over the rows that have a mixer and ``ln2`` over
those that have an MLP (all rows, where every row has both).  A decoder carries each
layer's own buffers (:func:`carry` says which, and why not a stack a kind).
The plan is static, so both passes are python loops over it
(:func:`backbone`, the whole sequence; :func:`step`, one cached position):
a layer's place in its kind's stacks of leaves is a python integer, its
leaves are static slices, and its state is a buffer that the step reads and
replaces.  Not a scan over whole periods with the leading layers outside it:
the plans built so far are at most fourteen rows whose kinds differ inside a
period; a deep plan would want the scan (``ROADMAP.md`` D1').

Only a configuration with a plan imports this (its doors: ``ENTRY_CONFIGS``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import inspect
from typing import Any

import numpy as np

__all__ = ["LayerPlan", "MIXERS", "kda_mla_config", "lightning_blocks_config",
           "leaf_names", "init_params", "carry", "grows", "carried",
           "backbone", "step", "check_mesh", "mla_moe_config",
           "shortcut_moe_config", "pattern_moe_config", "ENTRY_CONFIGS"]

# a mixer's kind -> its module under ``ompi_tpu.models``
MIXERS = {"kda": "kda", "mla": "mla", "lightning": "lightning",
          "block_select": "block_select", "ssm": "ssm", "attention": "block"}
MLPS = ("dense", "moe")

ROUTER_LEAVES = ("wg", "wgb")
SHARED_LEAVES = ("sw1", "sw3", "sw2")


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """``layers``: a (mixer, mlp) pair of kinds a layer (a row: ``cfg.n_layers``
    counts rows), either of them None in a row of one half.  ``kda``,
    ``mla``, ``lightning``, ``block_select``, ``ssm`` and ``attention``: the
    sizes of the mixers the plan names.  ``d_expert``: a routed expert's width (``cfg.d_ff`` is the dense
    MLP's).  Three constants of the model, each 1 where it has none: the
    embedding is multiplied by ``scale_emb``, every branch by
    ``branch_scale`` before its residual add (in the kinds that read it:
    "lightning", "block_select" and the dense MLP), and the last norm's
    output divided by ``head_divisor`` before the head.  ``branches``:
    ``(kind, reads, lands)`` each, an MLP of ``kind`` that reads what row
    ``reads``'s own MLP reads (the stream after that row's mixer, under its
    ``ln2``) and is added to the stream after row ``lands``'s MLP, ``lands
    >= reads``: it runs beside every mixer and MLP in between, which do not
    see it."""
    layers: tuple
    kda: Any = None
    mla: Any = None
    d_expert: int = 0
    lightning: Any = None
    block_select: Any = None
    scale_emb: float = 1.0
    branch_scale: float = 1.0
    head_divisor: float = 1.0
    branches: tuple = ()
    ssm: Any = None
    attention: Any = None

    def count(self, kind: str) -> int:
        """Layers whose mixer or MLP is ``kind``, and branches of it."""
        return (sum(kind in pair for pair in self.layers)
                + sum(kind == of for of, _reads, _lands in self.branches))

    def index(self, layer: int, kind: str, branch: bool = False) -> int:
        """Layer ``layer``'s place in the stacks of ``kind``; ``branch``:
        that of the branch that reads there, which lies after the layer's
        own."""
        return (sum(kind in pair for pair in self.layers[:layer])
                + sum(kind == of and reads < layer
                      for of, reads, _lands in self.branches)
                + (branch and kind in self.layers[layer]))

    def norm(self, layer: int, half: int) -> int:
        """Row ``layer``'s place in ``ln1`` (``half`` 0: the rows that have a
        mixer) or in ``ln2`` (1: those that have an MLP)."""
        return sum(pair[half] is not None for pair in self.layers[:layer])

    def second(self, layer: int) -> bool:
        """Whether ``layer`` is past the first row of a model's layer: a
        branch that was read before it has not landed yet."""
        return any(reads < layer <= lands
                   for _kind, reads, lands in self.branches)


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


def lightning_blocks_config(mixer_types: list, lightning_nh: int,
                            lightning_nkv: int, lightning_head_dim: int,
                            lightning_use_rope: bool, qk_norm: bool,
                            use_output_norm: bool, use_output_gate: bool,
                            attn_use_rope: bool, attn_use_output_gate: bool,
                            scale_emb: float, scale_depth: float,
                            dim_model_base: int, sparse_config: dict,
                            lightning_state_dtype: str = "float32", **sizes):
    """``entry.config`` of a configuration file whose layers are Lightning
    linear attention ("lightning-attn") or block-selected grouped-query
    attention ("minicpm4") over a dense gated MLP, under the keys such a
    model is published with: a ``TransformerConfig`` whose plan is read off
    ``mixer_types``, a kind a layer, which may run past ``n_layers`` (a file
    that cuts the depth keeps the list whole: the list's length is the
    published depth, which the residual branches' scale ``scale_depth /
    sqrt(depth)`` and the decays' layer factor are of).  ``sparse_config``:
    the block selection's sizes, under MiniCPM4's names."""
    from ompi_tpu.models.block_select import BlockSelect
    from ompi_tpu.models.lightning import Lightning
    from ompi_tpu.models.transformer import TransformerConfig

    kinds = {"lightning-attn": "lightning", "minicpm4": "block_select"}
    unknown = set(mixer_types) - set(kinds)
    if unknown:
        raise ValueError(f"mixer_types names {sorted(unknown)}: not built "
                         f"(have {sorted(kinds)})")
    if lightning_nkv != lightning_nh:
        raise ValueError(f"lightning_nkv {lightning_nkv} of lightning_nh "
                         f"{lightning_nh}: grouped lightning heads are not "
                         f"built")
    missing = [key for key, has in (
        ("qk_norm", qk_norm), ("use_output_norm", use_output_norm),
        ("attn_use_output_gate", attn_use_output_gate),
        ("attn_use_rope false", not attn_use_rope)) if not has]
    if missing:
        raise ValueError(f"a plan of lightning and block-selected layers is "
                         f"built with {', '.join(missing)} alone")
    depth, sc = len(mixer_types), sparse_config
    sizes["rope_theta"] = float(sizes.get("rope_theta", 10_000))
    plan = LayerPlan(
        layers=tuple((kinds[kind], "dense")
                     for kind in mixer_types[:sizes["n_layers"]]),
        lightning=Lightning(
            n_heads=lightning_nh, head_dim=lightning_head_dim, depth=depth,
            rope=bool(lightning_use_rope), gate=bool(use_output_gate),
            state_dtype=lightning_state_dtype),
        block_select=BlockSelect(
            kernel=sc["kernel_size"], stride=sc["kernel_stride"],
            block=sc["block_size"], topk=sc["topk"],
            init_blocks=sc["init_blocks"], window=sc["window_size"],
            dense_len=sc["dense_len"]),
        scale_emb=float(scale_emb),
        branch_scale=float(scale_depth) / depth ** 0.5,
        head_divisor=sizes["d_model"] / dim_model_base)
    return TransformerConfig(plan=plan, **sizes)


def _module(kind: str):
    """What a mixer's kind gives the plan: its module, or the module's
    ``PLAN_KIND`` where its own names are another form's."""
    import importlib

    module = importlib.import_module("ompi_tpu.models." + MIXERS[kind])
    return getattr(module, "PLAN_KIND", module)


def check_mesh(cfg, mesh) -> None:
    """A head's state, the latent and a selection's candidates are whole on
    a device, and over ``sp`` a recurrence needs an exclusive scan of
    per-rank states: neither split is built, and no cell asks."""
    from ompi_tpu.models import ssm

    ssm.check_mesh(cfg, mesh, f"a layer plan (mixers of kinds "
                              f"{', '.join(MIXERS)})")
    pl = cfg.plan
    unknown = ({mixer for mixer, _mlp in pl.layers} - {None, *MIXERS}
               | {mlp for _mixer, mlp in pl.layers} - {None, *MLPS}
               | {kind for kind, _reads, _lands in pl.branches} - {"moe"})
    if unknown:
        raise ValueError(f"a layer plan of kinds {sorted(unknown)}: not "
                         f"built (have {', '.join(MIXERS)}; "
                         f"{', '.join(MLPS)}; a branch: moe)")
    if (None, None) in pl.layers:
        raise ValueError("a row of a layer plan has a mixer, an MLP or both")
    unsized = [kind for kind in MIXERS
               if pl.count(kind) and getattr(pl, kind) is None]
    if unsized:
        raise ValueError(f"a layer plan names mixers of kinds {unsized} and "
                         f"holds no sizes for them (LayerPlan.<kind>)")
    astray = [b for b in pl.branches
              if not 0 <= b[1] <= b[2] < len(pl.layers)]
    if astray:
        raise ValueError(f"a branch reads a row of the plan and lands at "
                         f"that row or a later one: {astray} of "
                         f"{len(pl.layers)} rows")


def _kinds(cfg) -> dict:
    """kind -> (layers of it, one layer's leaves: name -> (shape,
    deviation, None for ones, or a draw ``(rng, shape)``)), for the kinds the
    plan has.  The dense MLP's leaves are ``dw1``, ``dw3``, ``dw2`` beside
    routed layers, whose experts are ``w1``, ``w3``, ``w2`` (gated; ``w1``,
    ``w2`` ungated, and the shared expert's alike), and take those names
    where no layer routes."""
    pl, D = cfg.plan, cfg.d_model
    depth = max(1, 2 * cfg.n_layers) ** 0.5

    def moe():
        Fe, Fs = pl.d_expert, cfg.moe_shared
        held = (cfg.moe_held[1] if cfg.moe_held
                else cfg.moe_experts - cfg.moe_zero)
        leaves = {"wg": ((D, cfg.moe_experts), 0.02),
                  "w1": ((held, D, Fe), D ** -0.5),
                  "w3": ((held, D, Fe), D ** -0.5),
                  "w2": ((held, Fe, D), Fe ** -0.5 / depth)}
        if cfg.moe_select_bias:
            leaves["wgb"] = ((cfg.moe_experts,), 0.01)
        if Fs:
            leaves.update({"sw1": ((D, Fs), D ** -0.5),
                           "sw3": ((D, Fs), D ** -0.5),
                           "sw2": ((Fs, D), Fs ** -0.5 / depth)})
        if not cfg.moe_gated:
            leaves.pop("w3")
            leaves.pop("sw3", None)
        return leaves

    def dense():
        F, (d1, d3, d2) = cfg.d_ff, _dense_leaves(pl)
        return {d1: ((D, F), D ** -0.5), d3: ((D, F), D ** -0.5),
                d2: ((F, D), F ** -0.5 / depth)}

    makers = {**{kind: lambda kind=kind: _module(kind).leaf_shapes(
        cfg, getattr(pl, kind)) for kind in MIXERS},
        "dense": dense, "moe": moe}
    kinds = {kind: (pl.count(kind), leaves())
             for kind, leaves in makers.items() if pl.count(kind)}
    names = [name for _n, leaves in kinds.values() for name in leaves]
    twice = sorted({name for name in names if names.count(name) > 1})
    if twice:
        raise ValueError(f"two kinds of this plan name a leaf alike, "
                         f"{twice}: a leaf is stacked over the layers of one "
                         f"kind")
    return kinds


def _dense_leaves(pl) -> tuple:
    """The dense MLP's gate, up and down projections' names."""
    return (("dw1", "dw3", "dw2") if pl.count("moe") else ("w1", "w3", "w2"))


def leaf_names(cfg) -> tuple:
    """The leaves stacked over layers (each over its kind's)."""
    return (*_norms(cfg.plan), *(name for _n, leaves in _kinds(cfg).values()
                                 for name in leaves))


def _norms(pl) -> dict:
    """``ln1`` and ``ln2`` -> the rows each is stacked over: those that have
    a mixer, those that have an MLP."""
    rows = {"ln1": pl.norm(len(pl.layers), 0),
            "ln2": pl.norm(len(pl.layers), 1)}
    return {name: n for name, n in rows.items() if n}


def init_params(cfg, rng) -> dict:
    """The whole tree as the program initialises it, float32."""
    D, V = cfg.d_model, cfg.vocab
    params = {"emb": rng.normal(0, 0.02, size=(V, D)).astype(np.float32),
              **({} if cfg.tie_head else {"head": rng.normal(
                  0, 0.02, size=(V, D)).astype(np.float32)}),
              **{name: np.ones((n, D), np.float32)
                 for name, n in _norms(cfg.plan).items()},
              "lnf": np.ones((D,), np.float32)}
    for n, leaves in _kinds(cfg).values():
        for name, (dims, std) in leaves.items():
            params[name] = (np.ones((n, *dims), np.float32) if std is None
                            else std(rng, (n, *dims)) if callable(std)
                            else rng.normal(0, std, size=(n, *dims)
                                            ).astype(np.float32))
    return params


def _buffers(cfg, batch: int, t_max: int) -> list:
    """``(shape, dtype, axis)`` of every buffer a decoder carries, a layer's
    own after another's in the plan's order (a kind's ``buffers``)."""
    pl = cfg.plan
    return [buffer for mixer, _mlp_kind in pl.layers if mixer is not None
            for buffer in _module(mixer).buffers(
                cfg, getattr(pl, mixer), batch, t_max)]


def carry(cfg, mesh, batch: int, t_max: int) -> list:
    """A decoder's carry for ``batch`` sequences of up to ``t_max``
    positions, zeros, a layer's own buffers after another's in the plan's
    order, each with a leading axis of one (so that whoever fills a carry a
    group of sequences at a time finds the batch on axis 1, as in a stack
    over layers); what they are is each kind's to say (``buffers``): a
    latent layer's cache ``(1, B, t_max, kv_rank + rope)`` in the compute
    type; a KDA layer's convolution inputs ``(1, B, conv - 1, 3 heads K)``
    in the compute type and its matrix states ``(1, B, heads, K, K)`` in the
    mixer's ``state_dtype``; a lightning layer's matrix states alike; a
    block-selected layer's K and V rows and, one for every ``stride``
    positions, its pooled keys; a state-space layer's convolution inputs and
    heads' states; an attention layer's K and V; nothing for a row without a
    mixer.  The order is that of :func:`backbone`'s collected states.

    A buffer a layer and not a stack a kind, because the steps' loop over
    the plan is unrolled: with one float32 stack ``(KDA layers, B, heads, K,
    K)`` updated in place at a static index a layer, the TPU's compiler
    rematerialised the first layer's update (``...fusion.8`` and
    ``...fusion.8.remat``, both run, both on the one buffer: the chip's
    trace, PR 45), and the cached steps read a logit error of 0.15 where the
    same program with a bfloat16 stack read 0.013.  A layer's own buffer is
    replaced whole, which needs no reasoning about slices.

    The leaves need no counterpart of this rule: they stay stacked by kind,
    a layer's are static slices (:func:`_mixer_leaves`), and a cached step
    reads each where it lies, inside the product's own fusion.  Where the
    compiler would want a weight re-laid behind a product and copy the
    layer's slice out every step, the kind's mixer ends the product before
    what it would fold in (``lightning.mixer``, PR 53), and
    ``tests/parallel/test_plan_step_compiled.py`` holds the compiled step of
    every cell with a plan to moving no weight."""
    import jax.numpy as jnp

    return [jnp.zeros((1, *shape), dtype)
            for shape, dtype, _axis in _buffers(cfg, batch, t_max)]


def grows(cfg) -> tuple:
    """Of each of :func:`carry`'s buffers: whether it grows with the
    sequence (a latent layer's cache, a selected layer's rows and pooled
    keys) or not (a matrix state, a convolution's inputs)."""
    return tuple(axis is not None for _shape, _dtype, axis
                 in _buffers(cfg, 0, 0))


def carried(cfg, mesh, collected, t_max: int, into=None, **group) -> list:
    """:func:`backbone`'s collected states of whole sequences, or a carry
    that ends before ``t_max`` (``collected``, used up; :func:`carry`'s
    order), as :func:`carry`'s buffers: written into ``into``, or those that
    grow padded with zeros to what they hold at ``t_max`` positions
    (``block.written``), each along its own axis."""
    from ompi_tpu.models.block import written

    buffers = _buffers(cfg, 0, t_max)
    return [written(new, axis and shape[axis - 1], buffer, axis=axis, **group)
            for new, buffer, (shape, _dtype, axis) in zip(
                collected, into or [None] * len(buffers), buffers)]


def _mlp(cfg, comm, params, layer: int, kind: str, h, branch: bool = False):
    """Layer ``layer``'s MLP half, ``ln2`` and the residual add in it; or,
    ``branch``, what the branch of ``kind`` that reads there adds to the
    stream where it lands."""
    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.parallel.moe import EXPERT_LEAVES

    pl = cfg.plan
    at, ln2 = pl.index(layer, kind, branch), params["ln2"][pl.norm(layer, 1)]
    if kind == "dense":
        lp = {"ln2": ln2, **{
            name: params[leaf][at] for name, leaf in zip(
                ("w1", "w3", "w2"), _dense_leaves(pl))}}
        return tfm._dense_ffn_tail(h, lp, comm, h.dtype, cfg.norm_eps,
                                   gated=(1.0, pl.branch_scale))
    # the experts' whole stacks and the layer's place in them
    # (``routed_moe`` says why); the router's and the shared expert's sliced
    lp = {"ln2": ln2,
          **{k: params[k] for k in EXPERT_LEAVES if k in params},
          **{k: params[k][at] for k in (*ROUTER_LEAVES, *SHARED_LEAVES)
             if k in params}}
    return tfm._moe_ffn_tail(cfg, h, lp, comm, layer=at,
                             residual=not branch)[0]


def _row(cfg, comm, params, layer: int, mixer: str, mlp: str, lp, h,
         landing, carry=None, forward_only: bool = False):
    """Row ``layer`` of the plan on the stream ``h``: its mixer on the leaves
    ``lp`` (against ``carry`` in a cached step), the branches that read
    there, its MLP, and the branches that land there (``landing``) added; a
    half the row lacks (``mixer`` or ``mlp`` None) is passed over.
    ``forward_only`` (``backbone``'s) goes to a kind whose mixer takes it
    (read off the function, so that one a benchmark's control puts in its
    place without the argument is called without it).
    Returns ``(h, what the mixer hands back, the branches begun that land
    later: row -> those that land there)``."""
    from ompi_tpu.core.scopes import scope, second

    pl = cfg.plan
    half = second if pl.second(layer) else contextlib.nullcontext
    with half():
        states = []
        if mixer is not None:
            module = _module(mixer)
            told = {}
            if getattr(module, "MESHED", False):
                told["comm"] = comm
            if "forward_only" in inspect.signature(module.mixer).parameters:
                told["forward_only"] = forward_only
            h, *states = module.mixer(cfg, lp, h, carry=carry, **told)
        begun = {}
        for kind, reads, lands in pl.branches:
            if reads == layer:
                begun.setdefault(lands, []).append(
                    _mlp(cfg, comm, params, layer, kind, h, branch=True))
        if mlp is not None:
            h = _mlp(cfg, comm, params, layer, mlp, h)
        landing = [*landing, *begun.pop(layer, [])]
        if landing:
            with scope("ffn"):
                for branch in landing:
                    h = h + branch
    return h, states, begun


def _take_off(flying: dict, begun: dict) -> None:
    """``begun`` (row -> branches that land there) joins what is in flight."""
    for lands, branches in begun.items():
        flying.setdefault(lands, []).extend(branches)


def _mixer_leaves(cfg, params, layer: int, kind: str) -> dict:
    """Layer ``layer``'s mixer's leaves, and the constants of its place
    (nothing where the row has no mixer)."""
    if kind is None:
        return {}
    at = cfg.plan.index(layer, kind)
    constants = getattr(_module(kind), "constants", None)
    return {"ln1": params["ln1"][cfg.plan.norm(layer, 0)],
            **{k: params[k][at] for k in _kinds(cfg)[kind][1]},
            **(constants(getattr(cfg.plan, kind), layer) if constants
               else {})}


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
             grad_axes=None, forward_only: bool = False):
    """``transformer._local_backbone`` of a configuration with a plan: the
    per-device forward through the last norm, a python loop over the plan.
    Returns ``(h, aux)`` (aux 0: the dropless experts have no balance term),
    or with ``collect_kv`` ``(h, (aux, *states))``, the states as
    :func:`carry` orders and shapes them but what grows as long as the
    sequences: a latent layer's rows ``(1, B, T, kv_rank + rope)``, a KDA
    layer's convolution inputs and matrix state after the last position.
    The embedding comes times the plan's ``scale_emb`` and ``h`` over its
    ``head_divisor``.  ``grad_axes``: the
    layers' leaves' gradients are summed over those axes where the backward
    pass reaches the start of the loop.  ``forward_only`` (a decoder's
    prefill): no gradient will be asked of this pass, so a kind may take a
    kernel that has no backward pass."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu.core.scopes import host, scope
    from ompi_tpu.models import transformer as tfm

    check_mesh(cfg, comm.mesh)
    pl = cfg.plan
    with scope("embed"):
        h = tfm._lookup(cfg, params["emb"], tokens)
        if pl.scale_emb != 1:
            h = h * pl.scale_emb
    if grad_axes is not None:
        params = {**params, **tfm._sum_in_backward(
            {k: params[k] for k in leaf_names(cfg)}, grad_axes)}

    def layer_fn(layer, mixer, mlp):
        def run(h, params, landing):
            lp = _mixer_leaves(cfg, params, layer, mixer)
            return _row(cfg, comm, params, layer, mixer, mlp, lp, h, landing,
                        forward_only=forward_only)

        if cfg.remat in (True, "full"):
            run = jax.checkpoint(run)
        elif cfg.remat == "dots":
            run = jax.checkpoint(run, policy=jax.checkpoint_policies
                                 .dots_with_no_batch_dims_saveable)
        return _own_program(run)

    collected, flying = [], {}
    with scope("layers"):
        for layer, (mixer, mlp) in enumerate(pl.layers):
            # the host's record of what tracing this layer costs, by its
            # mixer's kind (none for a row that is an MLP alone)
            with host("trace.layer", program=mixer):
                h, states, begun = layer_fn(layer, mixer, mlp)(
                    h, params, flying.pop(layer, []))
            _take_off(flying, begun)
            collected += [state[None] for state in states]
    h = tfm._rmsnorm(h, params["lnf"], cfg.norm_eps)
    if pl.head_divisor != 1:
        h = h / pl.head_divisor
    aux = jnp.zeros((), jnp.float32)
    if not collect_kv:
        return h, aux
    return h, (aux, *collected)


def step(cfg, comm, params, h, states, pos):
    """Every layer for ONE new token position: h (B, 1, D) against the
    carry ``states`` (:func:`carry`'s buffers), each layer reading and
    replacing its own.  ``params``: the leaves stacked over layers.  Returns
    ``(h, *states)``."""
    from ompi_tpu.core.scopes import host

    pl = cfg.plan

    def layer_fn(layer, mixer, mlp):
        positioned = mixer is not None and _module(mixer).POSITIONED

        def run(h, params, own, pos, landing):
            lp = _mixer_leaves(cfg, params, layer, mixer)
            own = tuple(buffer[0] for buffer in own)
            h, own, begun = _row(
                cfg, comm, params, layer, mixer, mlp, lp, h, landing,
                carry=(*own, pos) if positioned else own)
            return h, [buffer[None] for buffer in own], begun

        return _own_program(run)

    states, at, flying = list(states), 0, {}
    for layer, (mixer, mlp) in enumerate(pl.layers):
        n = (0 if mixer is None else
             len(_module(mixer).buffers(cfg, getattr(pl, mixer), 0, 0)))
        with host("trace.layer", program=mixer):
            h, states[at:at + n], begun = layer_fn(layer, mixer, mlp)(
                h, params, states[at:at + n], pos, flying.pop(layer, []))
        _take_off(flying, begun)
        at += n
    return (h, *states)


def mla_moe_config(first_k_dense_replace: int, kv_lora_rank: int,
                   q_lora_rank, qk_nope_head_dim: int, qk_rope_head_dim: int,
                   v_head_dim: int, moe_intermediate_size: int,
                   n_shared_experts: int, routed_scaling_factor: float,
                   n_group: int, topk_group: int, moe_layer_freq: int,
                   scoring_func: str, topk_method: str, rope_theta: float,
                   rope_scaling=None, **sizes):
    """``entry.config`` of a configuration file whose every layer is latent
    attention with a rotary embedding on the shared key part, over a leading
    dense MLP and routed experts after it, under the keys DeepSeek-V3's
    family is published with (no ``linear_attn_config``): a
    ``TransformerConfig`` whose plan is ``("mla", "dense")`` for the first
    ``first_k_dense_replace`` layers and ``("mla", "moe")`` for the others.
    The router is the family's: sigmoid scores, a selection bias that picks
    and does not weigh (``noaux_tc``), the picks' weights renormalised where
    ``moe_norm_topk`` says so and times ``routed_scaling_factor``, and
    ``n_shared_experts`` shared experts as one gated MLP of their summed
    width; with ``q_lora_rank`` the queries come from a normed query latent
    of that rank.  What is not built raises:
    grouped top-k (``n_group``, ``topk_group`` over 1), a scaled rotary
    embedding (``rope_scaling``), dense layers among the routed ones
    (``moe_layer_freq`` other than 1), another ``scoring_func`` or
    ``topk_method``."""
    from ompi_tpu.models.mla import MLA
    from ompi_tpu.models.transformer import TransformerConfig

    not_built = [f"{key} {value!r}" for key, value, built in (
        ("n_group", n_group, 1),
        ("topk_group", topk_group, 1), ("rope_scaling", rope_scaling, None),
        ("moe_layer_freq", moe_layer_freq, 1),
        ("scoring_func", scoring_func, "sigmoid"),
        ("topk_method", topk_method, "noaux_tc")) if value != built]
    if not_built:
        raise ValueError(f"a plan of rotary latent layers over routed "
                         f"experts is not built for {', '.join(not_built)}")
    plan = LayerPlan(
        layers=tuple(("mla", "dense" if layer < first_k_dense_replace
                      else "moe") for layer in range(sizes["n_layers"])),
        mla=MLA(n_heads=sizes["n_heads"], nope=qk_nope_head_dim,
                rope=qk_rope_head_dim, v_dim=v_head_dim,
                kv_rank=kv_lora_rank, theta=float(rope_theta),
                q_rank=int(q_lora_rank or 0)),
        d_expert=moe_intermediate_size)
    return TransformerConfig(
        plan=plan, moe_gated=True, moe_score="sigmoid", moe_select_bias=True,
        moe_scale=float(routed_scaling_factor),
        moe_shared=n_shared_experts * moe_intermediate_size, **sizes)


def shortcut_moe_config(kv_lora_rank: int, q_lora_rank, qk_nope_head_dim: int,
                        qk_rope_head_dim: int, v_head_dim: int,
                        expert_ffn_hidden_size: int,
                        routed_scaling_factor: float, router_experts: int,
                        zero_expert_num: int, zero_expert_type: str,
                        mla_scale_q_lora: bool, mla_scale_kv_lora: bool,
                        rope_theta: float, attention_method: str,
                        attention_bias: bool, experts_held=None, **sizes):
    """``entry.config`` of a configuration file whose layer is
    shortcut-connected, under the keys LongCat-Flash is published with: a
    ``TransformerConfig`` whose plan has two rows a layer, each rotary
    latent attention with a query latent over a dense MLP, and one branch a
    layer, routed experts that read the first row's normed post-attention
    stream and land after the second row's MLP.  ``sizes["n_layers"]`` comes
    in as the model's layers and goes on as the plan's rows, twice as many.
    The router is ``router_experts + zero_expert_num`` wide, the last
    ``zero_expert_num`` of its outputs identity experts: softmax scores, a
    selection bias that picks and does not weigh, the picks' weights as they
    are (or renormalised where ``moe_norm_topk`` says so) times
    ``routed_scaling_factor``.  The two normed latents are multiplied by
    ``sqrt(d_model / rank)`` where ``mla_scale_q_lora`` and
    ``mla_scale_kv_lora`` say so.  ``experts_held`` as in
    :func:`kda_mla_config`.  What is not built raises: another
    ``attention_method`` than "MLA", an ``attention_bias``, another
    ``zero_expert_type`` than "identity"."""
    from ompi_tpu.models.mla import MLA
    from ompi_tpu.models.transformer import TransformerConfig

    not_built = [f"{key} {value!r}" for key, value, built in (
        ("attention_method", attention_method, "MLA"),
        ("attention_bias", bool(attention_bias), False),
        ("zero_expert_type", zero_expert_type, "identity"))
        if value != built]
    if not_built:
        raise ValueError(f"a plan of shortcut-connected layers is not built "
                         f"for {', '.join(not_built)}")
    D, q_rank = sizes["d_model"], int(q_lora_rank or 0)
    blocks = sizes["n_layers"]
    sizes["n_layers"] = 2 * blocks
    plan = LayerPlan(
        layers=(("mla", "dense"),) * (2 * blocks),
        branches=tuple(("moe", 2 * block, 2 * block + 1)
                       for block in range(blocks)),
        mla=MLA(n_heads=sizes["n_heads"], nope=qk_nope_head_dim,
                rope=qk_rope_head_dim, v_dim=v_head_dim,
                kv_rank=kv_lora_rank, theta=float(rope_theta), q_rank=q_rank,
                q_scale=((D / q_rank) ** 0.5 if mla_scale_q_lora and q_rank
                         else 1.0),
                kv_scale=((D / kv_lora_rank) ** 0.5 if mla_scale_kv_lora
                          else 1.0)),
        d_expert=expert_ffn_hidden_size)
    held = (None if experts_held is None
            else (int(experts_held["first"]), int(experts_held["count"])))
    return TransformerConfig(
        plan=plan, moe_gated=True, moe_score="softmax", moe_select_bias=True,
        moe_scale=float(routed_scaling_factor), moe_held=held,
        moe_experts=router_experts + zero_expert_num,
        moe_zero=zero_expert_num, **sizes)


def pattern_moe_config(hybrid_override_pattern: str, mamba_num_heads: int,
                       mamba_head_dim: int, ssm_state_size: int,
                       n_groups: int, conv_kernel: int, chunk_size: int,
                       moe_intermediate_size: int,
                       moe_shared_expert_intermediate_size: int,
                       n_shared_experts: int, routed_scaling_factor: float,
                       router_experts: int, n_group: int, topk_group: int,
                       mlp_hidden_act: str, mamba_hidden_act: str,
                       use_conv_bias: bool, residual_in_fp32: bool,
                       attention_bias: bool, mlp_bias: bool, use_bias: bool,
                       mamba_proj_bias: bool,
                       attention_use_rope: bool = False,
                       experts_held=None, ssm_state_dtype: str = "float32",
                       **sizes):
    """``entry.config`` of a configuration file whose layer is one mixer
    alone under one norm, its kind a character of
    ``hybrid_override_pattern``, under the keys Nemotron-H's family is
    published with: a ``TransformerConfig`` whose plan has a row a layer, a
    row of one half: ``M`` a Mamba-2 mixer (``mamba_num_heads`` heads of
    ``mamba_head_dim`` over a state of ``ssm_state_size``, ``n_groups``
    groups; the inner width is their product, no ``expand`` is read), ``*``
    grouped-query attention (the configuration's heads; no rotary embedding
    unless ``attention_use_rope``), ``E`` routed experts.  The pattern may run
    past ``n_layers`` (a file that cuts the depth keeps it whole).  The
    router is ``router_experts`` wide: sigmoid scores, a selection bias that
    picks and does not weigh, the picks' weights renormalised where
    ``moe_norm_topk`` says so, times ``routed_scaling_factor``; an expert is
    ungated, ``w2(relu(w1 x)^2)``, and the shared expert, of
    ``n_shared_experts x moe_shared_expert_intermediate_size``, alike.
    ``experts_held`` as in :func:`kda_mla_config`.  What is
    not built raises: a dense MLP layer (``-``) or any other character,
    grouped top-k, another activation than relu2 in the MLPs and silu in the
    mixer, a convolution without its bias, a bias on any projection
    (``attention_bias``, ``mlp_bias``, ``use_bias``, ``mamba_proj_bias``), a
    float32 residual stream."""
    from ompi_tpu.models.block import Attention
    from ompi_tpu.models.ssm import Mamba2
    from ompi_tpu.models.transformer import TransformerConfig

    rows = {"M": ("ssm", None), "*": ("attention", None), "E": (None, "moe")}
    pattern = hybrid_override_pattern[:sizes["n_layers"]]
    not_built = [f"{key} {value!r}" for key, value, built in (
        ("n_group", n_group, 1), ("topk_group", topk_group, 1),
        ("mlp_hidden_act", mlp_hidden_act, "relu2"),
        ("mamba_hidden_act", mamba_hidden_act, "silu"),
        ("use_conv_bias", bool(use_conv_bias), True),
        ("residual_in_fp32", bool(residual_in_fp32), False),
        ("a projection's bias", bool(attention_bias or mlp_bias or use_bias
                                     or mamba_proj_bias), False),
        ("layers of kinds", sorted(set(pattern) - set(rows)), []),
        ("layers", len(pattern), sizes["n_layers"])) if value != built]
    if not_built:
        raise ValueError(f"a plan of single-mixer layers by a pattern is not "
                         f"built for {', '.join(not_built)}")
    plan = LayerPlan(
        layers=tuple(rows[kind] for kind in pattern),
        ssm=Mamba2(d_ssm=mamba_num_heads * mamba_head_dim,
                   d_state=ssm_state_size, n_groups=n_groups,
                   n_heads=mamba_num_heads, d_conv=conv_kernel,
                   chunk=chunk_size, state_dtype=ssm_state_dtype),
        attention=Attention(rope=bool(attention_use_rope)),
        d_expert=moe_intermediate_size)
    held = (None if experts_held is None
            else (int(experts_held["first"]), int(experts_held["count"])))
    return TransformerConfig(
        plan=plan, moe_gated=False, moe_act="relu2", moe_score="sigmoid",
        moe_select_bias=True, moe_scale=float(routed_scaling_factor),
        moe_held=held, moe_experts=router_experts,
        moe_shared=n_shared_experts * moe_shared_expert_intermediate_size,
        **sizes)


# A configuration file reaches a plan through its ``entry.config``, one
# function a published family's keys (each raises for what is not built):
# ``kda_mla_config``: KDA and NoPE latent layers by ``linear_attn_config``'s
# lists, a leading dense MLP, then a sigmoid router with a shared expert;
# ``mla_moe_config``: DeepSeek-V3's keys, rotary latent attention in every
# layer, a leading dense MLP, then the same router with ``n_shared_experts``;
# ``lightning_blocks_config``: lightning and block-selected layers by
# ``mixer_types`` over a dense MLP; ``shortcut_moe_config``: LongCat-Flash's
# keys, two rows of rotary latent attention with a query latent over a dense
# MLP a layer and a softmax router with identity experts as a branch across
# them; ``pattern_moe_config``: Nemotron-H's keys, a row of one half a layer
# by ``hybrid_override_pattern`` (a Mamba-2 mixer, NoPE grouped-query
# attention, or ungated relu2 experts under a sigmoid router).
ENTRY_CONFIGS = (kda_mla_config, mla_moe_config, lightning_blocks_config,
                 shortcut_moe_config, pattern_moe_config)
