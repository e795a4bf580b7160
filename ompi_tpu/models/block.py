"""One layer of a configuration without a plan, written once: attention as a
mixer of the form ``ssm.mixer``, ``kda.mixer`` and ``mla.mixer`` have
(:func:`mixer`: ln1, the q/k/v projections, q/k-norm, the rotary embedding
and ``wo`` are there and nowhere else) and the layer around it
(:func:`block`), each ONE function for whole sequences (trainer, prefill) and
for one position against a carry (``models/decode.py``).

What this mixer carries: K and V after the rotary embedding, stacked over
layers at their final length, ``(L, B, t_max, Hkv/tp, hd)`` each in the
compute type, head-sharded like the weights.  A step writes ``B Hkv hd``
values a layer in place at ``(layer, :, pos)`` and reads layer ``layer``
through a slice the compiler fuses into the products; K/V heads may be fewer
than query heads and are read once for the heads they serve.  With an index
the K and V are ``models/sparse_index.py``'s to lay out.

The same attention alone in a layer, ``h <- h + attention(RMSNorm(h; ln1))``,
is the kind "attention" of a layer plan (``models/plan.py``): what the plan
asks of a kind (``leaf_shapes``, ``buffers``, ``mixer`` with its residual add
against the layer's own K and V) is under :data:`PLAN_KIND`, because this
module's own ``mixer`` and ``carry`` are the stacked layer's; the plan's
``LayerPlan.attention`` holds an :class:`Attention`, which says whether the
queries and keys are rotated and what the scores are scaled by.

A decoder needs two things of whatever a configuration carries, and every
module that carries state gives them under the same names (:func:`mechanisms`
lists the modules): ``carry(cfg, mesh, batch, t_max)``, the zeros of its
stacks, and ``carried(cfg, mesh, collected, t_max, into)``, what a
whole-sequence pass collected for it as those stacks hold it (:func:`written`).
"""

from __future__ import annotations

import dataclasses
import inspect
import types

__all__ = ["mixer", "block", "mechanisms", "carry", "carried", "written",
           "check_mesh", "Attention", "PLAN_KIND"]


@dataclasses.dataclass(frozen=True)
class Attention:
    """The kind "attention" of a plan: the heads are the configuration's
    (``n_heads`` over ``kv_heads`` of ``head_dim``, ``qk_norm``); ``rope``
    False: no rotary embedding (NoPE), q and k go to the scores as they are
    projected; ``scale``: what the scores are multiplied by before the
    softmax, a constant of the model, or None: ``head_dim ** -0.5``."""
    rope: bool = True
    scale: float | None = None


def mechanisms(cfg) -> tuple:
    """The modules whose state a decoder of ``cfg`` carries, in the carry's
    order: ``plan`` (whose layers' kinds, ``plan.MIXERS``, are modules of
    their own: ``kda``, ``mla``, ``lightning``, ``block_select``, ``ssm``,
    ``selective``, ``differential``, ``gmu``, and this one's
    ``PLAN_KIND``); or this
    one (with an index ``sparse_index``, which lays
    K and V out with its keys; with power retention ``retention``, which
    keeps a state and no K/V) and, with a hybrid block, ``ssm``.  The one
    place that reads the configuration for them."""
    import importlib

    names = ["retention" if cfg.retention is not None
             else "block" if cfg.index is None else "sparse_index"]
    if cfg.hybrid is not None:
        names.append("ssm")
    return tuple(importlib.import_module("ompi_tpu.models." + name)
                 for name in (names if cfg.plan is None else ["plan"]))


def check_mesh(cfg, mesh) -> None:
    """Query and K/V heads are split over ``tp`` whole."""
    tp = int(dict(mesh.shape).get("tp", 1))
    if cfg.n_heads % tp or cfg.kv_heads % tp:
        raise ValueError(f"tp={tp} does not divide {cfg.n_heads} query "
                         f"heads and {cfg.kv_heads} K/V heads")


def carry(cfg, mesh, batch: int, t_max: int) -> list:
    """K and V for ``batch`` sequences of up to ``t_max`` positions, zeros."""
    import jax.numpy as jnp

    shape = (cfg.n_layers, batch, t_max,
             cfg.kv_heads // int(mesh.shape["tp"]), cfg.head_dim)
    return [jnp.zeros(shape, cfg.compute_dtype) for _ in "kv"]


def carried(cfg, mesh, collected, t_max: int, into=None, **group) -> list:
    """Every layer's K and V of whole sequences, the next two of the iterator
    ``collected``, as :func:`carry`'s stacks (:func:`written`)."""
    return [written(next(collected), t_max, stack, **group)
            for stack in into or (None, None)]


def written(new, t_max: int, into=None, g=0, group: int = 0, axis=2):
    """``new``, a state of ``group`` whole sequences stacked over layers
    (sequences on axis 1), as a carry holds it: written into the stack
    ``into`` from sequence ``g * group`` on, in the stack's type; with no
    stack, padded with zeros to ``t_max`` positions along ``axis`` (None: a
    state that does not grow)."""
    import jax.numpy as jnp
    from jax import lax

    if into is not None:
        return lax.dynamic_update_slice(
            into, new.astype(into.dtype),
            (0, g * group) + (0,) * (into.ndim - 2))
    if axis is None:
        return new
    pad = [(0, 0)] * new.ndim
    pad[axis] = (0, t_max - new.shape[axis])
    return jnp.pad(new, pad)


def _attend_whole_cache(q, kc, vc, layer, pos, scale=None):
    """q (B, 1, H, hd) against every position up to ``pos`` of layer
    ``layer`` of the cache (L, B, Tmax, Hkv, hd), a K/V head read once for
    the query heads it serves: the context, float32, (B, 1, H, hd) or
    grouped (B, 1, Hkv, H / Hkv, hd).  ``scale``: the scores' (None:
    ``hd ** -0.5``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope

    B, hl, hd = q.shape[0], q.shape[2], q.shape[3]
    Tmax, hkv = kc.shape[2], kc.shape[3]
    scale = hd ** -0.5 if scale is None else scale
    with scope("attention"):
        # scores against every cached position, masked beyond `pos`
        k_all = lax.dynamic_index_in_dim(kc, layer, keepdims=False)
        v_all = lax.dynamic_index_in_dim(vc, layer, keepdims=False)
        if hkv == hl:
            s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                           k_all.astype(jnp.float32)) * scale
            live = jnp.arange(Tmax)[None, None, None, :] <= pos
            s = jnp.where(live, s, -1e30)
            w = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", w, v_all.astype(jnp.float32))
        else:       # K/V head g serves the query heads (g, r): read it once
            qg = q.astype(jnp.float32).reshape(B, 1, hkv, hl // hkv, hd)
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qg,
                           k_all.astype(jnp.float32)) * scale
            s = jnp.where(jnp.arange(Tmax) <= pos, s, -1e30)
            w = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bgrqk,bkgd->bqgrd", w,
                           v_all.astype(jnp.float32))
    return o


def mixer(cfg, comm, lp, h, positions, carry=None, impl: str = "jnp",
          weights=None, forward_only: bool = False):
    """The attention branch of one layer on the block's input ``h``
    (B, T, D) at ``positions`` (T,), without its residual add: ln1, the
    projections, q/k-norm and then the hybrid's key multiplier (an RMS norm
    would take a multiplier before it away), the rotary embedding in the
    form ``impl`` reads, attention (or, where the configuration has power
    retention, ``retention.core`` on the same q, k and v and the gate's
    projection), ``wo``.  No rotary embedding where a plan's
    ``Attention.rope`` says so, and the scores at its ``Attention.scale``.
    ``lp``: the layer's leaves.
    ``weights(x, *names)``, the train step's: ``(x, leaves)`` for the block
    whose first matmuls read ``x`` (``transformer._local_backbone`` says
    what it ties to them), and ``wo``'s product is then made and summed
    over ``tp`` by halves (``row_parallel(by_halves=True)``); None: ``lp``'s
    own.

    ``carry`` None: whole sequences, the layout's attention over ``sp`` or
    the index's (``forward_only``: no gradient will be asked, so it may take
    the kernel that has none); returns ``(a, x, collected)``, the branch's
    output, the normed input (a hybrid's mixer reads it too) and what a
    decoder collects: this layer's k and v (B, T, Hkv/tp, hd), and the
    index's keys.  ``carry = (stacks, layer, pos)``: T == 1, position ``pos``
    against layer ``layer`` of the stacks (:func:`carry`'s, or the index's),
    its own k and v written in place first; returns ``(a, x, stacks)``."""
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.ops import _chip
    from ompi_tpu.parallel import attention as attn_mod
    from ompi_tpu.parallel.layers import column_parallel, row_parallel

    if cfg.index is not None:
        from ompi_tpu.models import sparse_index
    if cfg.retention is not None:
        from ompi_tpu.models import retention
    staged = weights is not None
    weights = weights or (lambda x, *_names: (x, lp))
    cdt, hy, rt = h.dtype, cfg.hybrid, cfg.retention
    B, T, tp = h.shape[0], h.shape[1], int(comm.mesh.shape["tp"])
    hl, hkv, hd = cfg.n_heads // tp, cfg.kv_heads // tp, cfg.head_dim
    with scope("attn_proj"):
        # the norms are called through the module: a benchmark's control
        # plants a wrong one there while a decoder is traced
        x = tfm._rmsnorm(h, lp["ln1"], cfg.norm_eps)
        xa = x if hy is None else x * hy.attention_in_multiplier
        xa, w = weights(xa, "wo", "wq", "wk", "wv")
        q, k, v = (column_parallel(xa, w[name].astype(cdt))
                   for name in ("wq", "wk", "wv"))
        if cfg.qk_norm:
            q = tfm._qk_norm(cfg, q, lp["qn"], comm)
            k = tfm._qk_norm(cfg, k, lp["kn"], comm)
        if hy is not None:
            k = k * hy.key_multiplier
        rotates = cfg.plan is None or cfg.plan.attention.rope
        scale = None if cfg.plan is None else cfg.plan.attention.scale
        q = q.reshape(B, T, hl, hd)
        if rotates:
            q = tfm._rope(q, positions, impl, cfg.rope_theta)
        k = k.reshape(B, T, hkv, hd)
        if rotates:
            k = tfm._rope(k, positions, impl, cfg.rope_theta)
        v = v.reshape(B, T, hkv, hd)
        k_all, v_all = k, v
        if rt is not None:      # the decay a K/V head and position
            logg = retention.log_gate(rt, jnp.einsum(
                "btd,dg->btg", x, lp["wd"].astype(cdt)))
        elif carry is None and cfg.index is None and hkv != hl:
            # each K/V head before its queries
            k_all, v_all = (jnp.repeat(y, hl // hkv, axis=2) for y in (k, v))
    if rt is not None:
        o, out = retention.core(cfg, q, k, v, logg, carry and carry[:2],
                                forward_only)
    elif carry is None and cfg.index is None:
        layout = tfm._ATTENTION_LAYOUT.get(cfg.attention, "gathered")
        with scope("attention"):
            o = getattr(attn_mod, layout + "_attention")(
                comm, q, k_all, v_all, axis="sp", impl=impl, scale=scale)
        out = (k, v)
    elif carry is None:
        # each query over its own selection (the kernel has no backward
        # pass and compiles for the TPU)
        o, ki = sparse_index.attend(
            cfg, lp, x, q, k, v, positions,
            kernel=forward_only and _chip._traced_for_tpus())
        out = (k, v, ki)
    elif cfg.index is None:
        (kc, vc), layer, pos = carry
        with scope("kv_cache"):
            kc = lax.dynamic_update_slice(kc, k.astype(kc.dtype)[None],
                                          (layer, 0, pos, 0, 0))
            vc = lax.dynamic_update_slice(vc, v.astype(vc.dtype)[None],
                                          (layer, 0, pos, 0, 0))
        o, out = _attend_whole_cache(q, kc, vc, layer, pos, scale), [kc, vc]
    else:
        o, *out = sparse_index._attend_selection(cfg, lp, x, q, k, v, *carry)
    with scope("attn_proj"):
        o = o.astype(cdt).reshape(B, T, hl * hd)
        return row_parallel(o, w["wo"].astype(cdt), comm, axis="tp",
                            by_halves=staged), x, out


def block(cfg, comm, lp, h, positions, carry=None, **how):
    """One layer on ``h`` (B, T, D): :func:`mixer` (``how``: its ``impl``,
    ``weights``, ``forward_only``) and the residual, with a hybrid block
    ``h + a * attention_out_multiplier + ssm.mixer(x)``, both branches
    reading the one normed input; then ln2, the MLP or the experts.

    ``carry`` None: whole sequences; returns ``(h, collected)``, a tuple of
    what the mixers hand a decoder, in :func:`mechanisms`' order.  ``carry =
    (stacks, layer, pos)``: T == 1 against the carry, a list of each
    mechanism's own stacks; ``lp``'s expert leaves (``moe.EXPERT_LEAVES``)
    are then the whole stacks over layers, which ``routed_moe`` indexes by
    ``layer``.  Returns ``(h, stacks)``.

    Runs while a program is traced: what that costs the host is a
    ``trace.layer`` span of its record (``core/scopes.py``), kind "block"."""
    from ompi_tpu.core.scopes import host

    with host("trace.layer", program="block"):
        return _block(cfg, comm, lp, h, positions, carry, **how)


def _block(cfg, comm, lp, h, positions, carry, **how):
    from ompi_tpu.core.scopes import scope
    from ompi_tpu.models import transformer as tfm

    hy, layer = cfg.hybrid, None
    if carry is not None:
        stacks, layer, pos = carry
        carry = (stacks[0], layer, pos)
    a, x, own = mixer(cfg, comm, lp, h, positions, carry, **how)
    if hy is None:
        with scope("attn_proj"):
            h = h + a
    else:
        from ompi_tpu.models import ssm

        # told what the pass is for where it takes that (a benchmark's
        # control puts a mixer without the argument in its place)
        told = {k: v for k, v in how.items() if k == "forward_only"
                and k in inspect.signature(ssm.mixer).parameters}
        s, *states = ssm.mixer(cfg, lp, x, carry and (*stacks[1], layer),
                               **told)
        with scope("attn_proj"):
            h = h + a * hy.attention_out_multiplier + s
    if cfg.moe_experts:
        # the routed experts, which a cached step gets as whole stacks
        h = tfm._moe_ffn_tail(cfg, h, lp, comm, layer=layer)
    else:
        # a hybrid block's MLP is gated under its multipliers, a retention
        # layer's plainly
        gated = (hy.mlp_multipliers if hy is not None
                 else cfg.retention and (1.0, 1.0))
        h = tfm._dense_ffn_tail(h, lp, comm, h.dtype, cfg.norm_eps,
                                gated=gated, weights=how.get("weights"))
    if carry is not None:
        return h, [own] if hy is None else [own, states]
    if hy is not None:      # the states as the carry stores them
        own += (states[0], states[1].astype(hy.state_dtype))
    return h, tuple(own)


# ---- attention alone in a layer: the kind "attention" of a plan -------------

def _kind_leaf_shapes(cfg, at: Attention) -> dict:
    """One layer's leaves: name -> (shape, deviation of the program's own
    initializer or None for ones)."""
    D = cfg.d_model
    Dq, Dkv = cfg.n_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    leaves = {"wq": ((D, Dq), D ** -0.5), "wk": ((D, Dkv), D ** -0.5),
              "wv": ((D, Dkv), D ** -0.5),
              "wo": ((Dq, D), Dq ** -0.5 / max(1, 2 * cfg.n_layers) ** 0.5)}
    if cfg.qk_norm:
        per_head = cfg.qk_norm == "head"
        leaves.update(qn=((cfg.head_dim if per_head else Dq,), None),
                      kn=((cfg.head_dim if per_head else Dkv,), None))
    return leaves


def _kind_buffers(cfg, at: Attention, batch: int, t_max: int) -> tuple:
    """What a decoder carries for one layer (``models/plan.py``'s form): K
    and V ``(B, t_max, Hkv, hd)`` in the compute type, which grow along the
    carry's axis 2."""
    shape = (batch, t_max, cfg.kv_heads, cfg.head_dim)
    return ((shape, cfg.compute_dtype, 2), (shape, cfg.compute_dtype, 2))


def _kind_mixer(cfg, lp, h, carry=None, *, comm):
    """One layer's mixer on the layer's input ``h`` (B, T, D): :func:`mixer`
    and the residual add of the branch times the plan's ``branch_factor``.

    ``carry`` None: whole sequences at positions 0 to T - 1; returns ``(h, k,
    v)``, every position's K and V ``(B, T, Hkv, hd)``.  ``carry = (kc, vc,
    pos)``: T == 1, position ``pos`` against this layer's own K and V ``(B,
    Tmax, Hkv, hd)``, a stack of one layer to :func:`mixer`; returns ``(h,
    kc, vc)``."""
    import jax.numpy as jnp

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.parallel import attention as attn_mod

    if carry is None:
        B, T = h.shape[:2]
        shape = (B, T, cfg.n_heads, cfg.head_dim)
        impl = attn_mod.layout_impl(
            comm, tfm._ATTENTION_LAYOUT.get(cfg.attention, "gathered"),
            shape, shape, h.dtype, "sp")
        a, _x, own = mixer(cfg, comm, lp, h, jnp.arange(T), impl=impl)
    else:
        kc, vc, pos = carry
        a, _x, own = mixer(cfg, comm, lp, h, pos[None],
                           carry=((kc[None], vc[None]), 0, pos))
        own = [buffer[0] for buffer in own]
    with scope("attn_proj"):
        if cfg.plan.branch_factor != 1:
            a = a * cfg.plan.branch_factor
        return (h + a, *own)


PLAN_KIND = types.SimpleNamespace(
    leaf_shapes=_kind_leaf_shapes, buffers=_kind_buffers, mixer=_kind_mixer,
    POSITIONED=True, MESHED=True)
