"""The hybrid block: a Mamba-2 mixer beside attention in every layer.

``TransformerConfig.hybrid`` holds a :class:`HybridBlock`; the block
(``models/block.block``) then computes, from one normed input ``u``,

    x <- x + attention(u) * attention_out_multiplier + mixer(u)
    x <- x + down(silu(gate(f) * m0) * up(f)) * m1,   f = RMSNorm(x; ln2)

with grouped K/V heads, the embedding, the keys, the attention's input and
the logits each scaled by a published constant (``falcon_h1``'s block).
This module has what is the mixer's own: its sizes and the multipliers, its
leaves, and :func:`mixer`, the one function both paths call: the whole
sequence (trainer, prefill: a causal convolution over the sequence and the
chunked scan, from a zero state) and one position against a carried state
(its states in :func:`carry`'s stacks: the convolution from its last
inputs, the recurrence once).  Everything here is ``jax.numpy`` and ``lax``
but two kernels a decoder takes on TPUs, each behind a rule of static
facts: a prefill of more than one chunk scans with the pallas
``ops/ssm_scan.py`` (:func:`fused` is the rule), and a cached step updates a
layer's own bfloat16 state of depth 128 in place with ``ops/ssm_update.py``
(:func:`in_place` is the rule; a float32 state, another depth and a layer
of a stack take the ``jax.numpy`` update, and do not import the kernel).

The same mixer alone in a layer, ``h <- h + r mixer(RMSNorm(h; ln1))`` with
no multiplier but the plan's ``branch_factor`` ``r``, is the kind "ssm" of a
layer plan (``models/plan.py``):
its sizes a :class:`Mamba2` in ``LayerPlan.ssm``, and what the plan asks of a
kind (``leaf_shapes``, ``buffers``, ``mixer`` with its norm and residual add
against the layer's own buffers) under :data:`PLAN_KIND`, because this
module's own ``mixer`` and ``carry`` are the hybrid block's, whose states are
stacks over layers.  One arithmetic (:func:`_mix`), two ways to hold a state.

Nothing imports this module but a configuration that has the block or a
plan (whose ``check_mesh`` says what split it refuses through this module's),
and importing it costs numpy alone, so the other programs' set-up does not
pay for it.
"""

from __future__ import annotations

import dataclasses
import math
import types

import numpy as np

__all__ = ["Mamba2", "HybridBlock", "hybrid_config", "mixer", "chunked_scan",
           "fused", "in_place", "init_leaves", "leaf_names", "state_shapes",
           "carry", "carried", "check_mesh", "PLAN_KIND"]


@dataclasses.dataclass(frozen=True)
class Mamba2:
    """Sizes of the mixer, under the published configuration's names where
    it has one."""
    d_ssm: int                  # the mixer's inner width: heads x head width
    d_state: int                # N: a head's state is (head width, N)
    n_groups: int               # groups that share one B and one C
    n_heads: int
    d_conv: int                 # taps of the causal depthwise convolution
    chunk: int                  # positions a chunk of the whole-sequence scan
    # what the carried state is stored in between cached steps; the update
    # itself is float32 and is rounded once, on the way back
    state_dtype: str = "bfloat16"

    @property
    def head_dim(self) -> int:
        return self.d_ssm // self.n_heads

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: x, B and C."""
        return self.d_ssm + 2 * self.n_groups * self.d_state

    @property
    def in_dim(self) -> int:
        """Columns of the input projection: z, x, B, C, dt in this order."""
        return self.d_ssm + self.conv_dim + self.n_heads


@dataclasses.dataclass(frozen=True, kw_only=True)
class HybridBlock(Mamba2):
    """The mixer's sizes and the hybrid block's constant multipliers."""
    embedding_multiplier: float
    attention_in_multiplier: float
    attention_out_multiplier: float
    key_multiplier: float
    lm_head_multiplier: float
    ssm_in_multiplier: float
    ssm_multipliers: tuple      # on z, x, B, C, dt of the input projection
    ssm_out_multiplier: float
    mlp_multipliers: tuple      # on the gate's pre-activation, on the output


_BLOCK_FIELDS = {f.name for f in dataclasses.fields(HybridBlock)}


def hybrid_config(**sizes):
    """``entry.config`` of a configuration file with this block: a
    ``TransformerConfig`` from flat keys, those of :class:`HybridBlock`
    (``n_heads`` of the mixer as ``ssm_heads``) gathered under ``hybrid``."""
    from ompi_tpu.models.transformer import TransformerConfig

    ssm_heads = sizes.pop("ssm_heads")      # n_heads stays attention's
    block = {k: sizes.pop(k) for k in list(sizes)
             if k in _BLOCK_FIELDS and k != "n_heads"}
    for pair in ("ssm_multipliers", "mlp_multipliers"):
        block[pair] = tuple(float(m) for m in block[pair])
    # a float: 1e11 is past int32, which a python int would be traced as
    sizes["rope_theta"] = float(sizes.get("rope_theta", 10_000))
    return TransformerConfig(
        hybrid=HybridBlock(n_heads=ssm_heads, **block), **sizes)


def check_mesh(cfg, mesh, what: str = "the hybrid block (a state-space "
               "mixer beside attention)") -> None:
    """A head's state is whole on a device (the mixer's few groups and K/V
    heads bound any split over ``tp``), and over ``sp`` a recurrence needs an
    exclusive scan of per-rank states: neither is built, and no cell asks.
    ``what``: who runs the mixers, for the message (``plan.check_mesh`` says
    the same of its kinds through this function)."""
    for axis in ("sp", "tp"):
        if int(dict(mesh.shape).get(axis, 1)) > 1:
            raise ValueError(
                f"{what} runs with {axis} == 1 only, and the mesh has "
                f"{axis}={mesh.shape[axis]}: its mixers are not split over "
                f"{axis}")


def leaf_names() -> tuple:
    """The block's leaves beside those of the dense block, stacked over
    layers: ``w3`` is the MLP's up projection (``w1`` its gate, ``w2`` its
    down projection)."""
    return ("w3", "ssm_in", "ssm_out", "conv_w", "conv_b", "a_log",
            "dt_bias", "ssm_d", "ssm_norm")


def _dt_bias(rng, shape):
    """dt log-uniform in [1e-3, 1e-1], through the inverse softplus."""
    dt = np.exp(rng.uniform(math.log(1e-3), math.log(1e-1), size=shape))
    return (dt + np.log(-np.expm1(-dt))).astype(np.float32)


def _a_log(rng, shape):
    """A uniform in [1, 16]."""
    return np.log(rng.uniform(1, 16, size=shape)).astype(np.float32)


def _conv_w(rng, shape):
    bound = shape[-2] ** -0.5               # (..., taps, channels)
    return rng.uniform(-bound, bound, size=shape).astype(np.float32)


def _zeros(_rng, shape):
    return np.zeros(shape, np.float32)


def init_leaves(cfg, rng) -> dict:
    """The block's own leaves as the model initialises them, float32: dt
    log-uniform in [1e-3, 1e-1] through the inverse softplus, A uniform in
    [1, 16], D ones."""
    hy, L, D, F = cfg.hybrid, cfg.n_layers, cfg.d_model, cfg.d_ff
    depth = math.sqrt(max(1, 2 * L))

    def w(*shape, scale):
        return rng.normal(0, scale, size=shape).astype(np.float32)

    dt_bias = _dt_bias(rng, (L, hy.n_heads))
    return {
        "w3": w(L, D, F, scale=D ** -0.5),
        "ssm_in": w(L, D, hy.in_dim, scale=D ** -0.5),
        "ssm_out": w(L, hy.d_ssm, D, scale=hy.d_ssm ** -0.5 / depth),
        "conv_w": _conv_w(rng, (L, hy.d_conv, hy.conv_dim)),
        "conv_b": np.zeros((L, hy.conv_dim), np.float32),
        "a_log": _a_log(rng, (L, hy.n_heads)),
        "dt_bias": dt_bias,
        "ssm_d": np.ones((L, hy.n_heads), np.float32),
        "ssm_norm": np.ones((L, hy.d_ssm), np.float32),
    }


def state_shapes(cfg, batch: int) -> tuple:
    """One layer's carried state for ``batch`` sequences: the convolution's
    last inputs ``(B, d_conv - 1, conv_dim)`` and the heads' states
    ``(B, heads, head width, N)``."""
    hy = cfg.hybrid
    return ((batch, hy.d_conv - 1, hy.conv_dim),
            (batch, hy.n_heads, hy.head_dim, hy.d_state))


def carry(cfg, mesh, batch: int, t_max: int) -> list:
    """What a decoder carries for the mixer, zeros: every layer's
    :func:`state_shapes` stacked over layers (no ``t_max`` in them), the
    convolution's inputs in the compute type and the heads' states in the
    block's ``state_dtype``.  A step updates layer ``l``'s in place."""
    import jax.numpy as jnp

    types = (cfg.compute_dtype, cfg.hybrid.state_dtype)
    return [jnp.zeros((cfg.n_layers, *shape), dtype)
            for shape, dtype in zip(state_shapes(cfg, batch), types)]


def carried(cfg, mesh, collected, t_max: int, into=None, **group) -> list:
    """Every layer's two states after the last position of whole sequences,
    the next two of the iterator ``collected``, as :func:`carry`'s stacks
    (``block.written``)."""
    from ompi_tpu.models.block import written

    return [written(next(collected), t_max, stack, axis=None, **group)
            for stack in into or (None, None)]


def _column_multipliers(hy: HybridBlock) -> np.ndarray:
    """The constant vector on the input projection's columns."""
    z, x, b, c, dt = hy.ssm_multipliers
    gn = hy.n_groups * hy.d_state
    return np.concatenate([np.full(hy.d_ssm, z), np.full(hy.d_ssm, x),
                           np.full(gn, b), np.full(gn, c),
                           np.full(hy.n_heads, dt)]).astype(np.float32)


def chunked_scan(x, dt, a, b, c, chunk: int):
    """``h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t``
    over whole sequences from a zero state, a chunk of ``chunk`` positions at
    a time: inside a chunk as products over its positions, across chunks as
    the recurrence on the chunks' final states.

    x: (B, T, H, P); dt: (B, T, H) float32, positive; a: (H,) float32,
    negative; b, c: (B, T, G, N), head ``h`` reading group ``h // (H / G)``.
    The decays are float32; the products run in x's type and add up in
    float32.  A length that is no multiple of the chunk is
    padded with positions of dt = 0, which leave the state as it is.
    Returns y (B, T, H, P) float32 and the final state (B, H, P, N) float32.
    """
    import jax.numpy as jnp
    from jax import lax

    f32, cdt = jnp.float32, x.dtype
    B, T, H, P = x.shape
    G, N = b.shape[2:]
    R, Q = H // G, chunk
    nc = -(-T // Q)
    pad = [(0, 0), (0, nc * Q - T)]
    xs = jnp.pad(x.astype(f32) * dt[..., None], pad + [(0, 0), (0, 0)])
    xs = xs.reshape(B, nc, Q, G, R, P).astype(cdt)      # dt-weighted input
    cs = jnp.cumsum(jnp.pad(dt * a, pad + [(0, 0)]).reshape(B, nc, Q, G, R),
                    axis=2)                             # log-decay, inclusive
    bc = jnp.pad(b, pad + [(0, 0), (0, 0)]).reshape(B, nc, Q, G, N)
    cc = jnp.pad(c, pad + [(0, 0), (0, 0)]).reshape(B, nc, Q, G, N)

    # inside a chunk: position q reads k <= q through exp(cs_q - cs_k)
    cb = jnp.einsum("bxqgn,bxkgn->bxgqk", cc.astype(cdt), bc.astype(cdt),
                    preferred_element_type=f32)
    seg = (jnp.moveaxis(cs, 2, -1)[..., :, None]
           - jnp.moveaxis(cs, 2, -1)[..., None, :])     # (B, nc, G, R, q, k)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    weight = cb[:, :, :, None] * jnp.exp(jnp.where(causal, seg, -jnp.inf))
    y = jnp.einsum("bxgrqk,bxkgrp->bxqgrp", weight.astype(cdt), xs,
                   preferred_element_type=f32)
    # a chunk's own contribution to the state at its end
    to_end = jnp.exp(cs[:, :, -1:] - cs)                # (B, nc, Q, G, R)
    own = jnp.einsum("bxkgrp,bxkgn->bxgrpn",
                     (xs.astype(f32) * to_end[..., None]).astype(cdt),
                     bc.astype(cdt), preferred_element_type=f32)
    if nc == 1:                 # one chunk: nothing comes in from before
        return (y.reshape(B, Q, H, P)[:, :T],
                own.reshape(B, H, P, N))

    def across(h, chunk_of):
        own_c, decay_c = chunk_of
        return h * decay_c[..., None, None] + own_c, h  # emits the state before

    last, before = lax.scan(
        across, jnp.zeros((B, G, R, P, N), f32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(jnp.exp(cs[:, :, -1]), 1, 0)))
    carried = jnp.einsum("bxqgn,bxgrpn->bxqgrp", cc.astype(cdt),
                         jnp.moveaxis(before, 0, 1).astype(cdt),
                         preferred_element_type=f32)
    y = y + carried * jnp.exp(cs)[..., None]
    return (y.reshape(B, nc * Q, H, P)[:, :T], last.reshape(B, H, P, N))


def fused(sz: Mamba2, forward_only: bool, tpu: bool, T: int, dtype) -> bool:
    """Whether whole sequences of ``T`` positions in the type ``dtype`` are
    scanned by the one kernel (``ops/ssm_scan.py``) and not by
    :func:`chunked_scan`, from static facts alone: no gradient will be asked
    (the kernel has no backward pass), the trace is for TPUs (the kernel
    compiles for nothing else), the sequence is more than one chunk (one
    chunk has no recurrence and nothing to keep in VMEM: the kernel's module
    is not imported for it) and the sizes tile."""
    if not (forward_only and tpu and T > sz.chunk):
        return False
    from ompi_tpu.ops import ssm_scan

    return ssm_scan.tiles(T, sz.chunk, sz.head_dim, sz.d_state,
                          sz.n_heads // sz.n_groups, dtype)


def in_place(sz: Mamba2, tpu: bool, dtype) -> bool:
    """Whether a cached step's update of a layer's carried state of the type
    ``dtype`` is the one kernel (``ops/ssm_update.py``: the state moved once
    each way, in its own buffer) and not the ``jax.numpy`` form, which the
    compiler makes two fusions and three passes of, from static facts alone:
    the trace is for TPUs (the kernel compiles for nothing else), the state
    is carried in bfloat16 (two bytes an element, which the matrix unit reads
    exactly; a float32 state's update is one fusion as it is) and its depth
    is one tile of 128 lanes, the shape the kernel is swept on (its module
    is not imported for another), and the kernel's own rule: heads of whole
    sublane tiles, a sequence's within its VMEM."""
    if not (tpu and np.dtype(dtype).name == "bfloat16"
            and sz.d_state == 128):
        return False
    from ompi_tpu.ops import ssm_update

    return ssm_update.block(tpu, dtype, sz.n_heads, sz.head_dim, sz.d_state,
                            sz.n_groups) is not None


def _conv_before(conv_c, layer):
    """A cached step's last ``d_conv - 1`` inputs of layer ``layer`` of the
    stack ``conv_c``; ``layer`` None: ``conv_c`` is the layer's own buffer.
    (A function of its own, like ``_state_before``, so that the benchmark's
    controls can plant a state that is not read while a decoder is traced.)"""
    from jax import lax

    if layer is None:
        return conv_c
    return lax.dynamic_index_in_dim(conv_c, layer, keepdims=False)


def _state_before(ssm_c, layer):
    """A cached step's state as the update starts from it, float32."""
    import jax.numpy as jnp
    from jax import lax

    if layer is None:
        return ssm_c.astype(jnp.float32)
    return lax.dynamic_index_in_dim(ssm_c, layer,
                                    keepdims=False).astype(jnp.float32)


def _written(into, new, layer):
    """``new``, layer ``layer``'s state after a cached step, as the carry
    holds it: in the stack ``into`` at ``layer``, in place; ``layer`` None:
    in the place of the layer's own buffer ``into``.  In ``into``'s type."""
    from jax import lax

    new = new.astype(into.dtype)
    if layer is None:
        return new
    return lax.dynamic_update_slice(into, new[None],
                                    (layer,) + (0,) * new.ndim)


def mixer(cfg, lp, u, carry=None, forward_only: bool = False):
    """The mixer branch of one layer of the hybrid block on the block's
    normed input ``u`` (B, T, D), without its residual add, under the
    block's multipliers.  ``forward_only`` (a decoder's prefill): no
    gradient will be asked, so whole sequences may take :func:`fused`'s
    kernel.

    ``carry`` None: whole sequences from a zero state; returns
    ``(s, conv_state, ssm_state)``, the layer's states after the last
    position, ``(B, d_conv - 1, conv_dim)`` in u's type and ``(B, H, P, N)``
    float32.  ``carry = (conv_c, ssm_c, layer)``: T == 1 against the states
    of layer ``layer`` of the stacks ``(L, ...)``, read and written in place;
    returns ``(s, conv_c, ssm_c)``.  The two differ only in where the
    convolution's window comes from and in scan against one update."""
    hy = cfg.hybrid
    return _mix(hy, cfg.norm_eps, lp, u, carry, multipliers=(
        hy.ssm_in_multiplier, _column_multipliers(hy),
        hy.ssm_out_multiplier), forward_only=forward_only)


def _mix(sz: Mamba2, eps: float, lp, u, carry=None, multipliers=None,
         forward_only: bool = False):
    """:func:`mixer`'s arithmetic on the sizes ``sz``, the hybrid block's
    and the plan's kind's: ``carry`` as :func:`mixer`'s, its ``layer`` None
    where the two states are the layer's own buffers and not stacks.
    ``multipliers``: the hybrid block's, on the normed input, on the input
    projection's columns (a vector) and on the output; None: none.
    ``forward_only`` as :func:`mixer`'s."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.ops import _chip

    f32, cdt = jnp.float32, u.dtype
    B, T, _ = u.shape
    H, P, G, N = sz.n_heads, sz.head_dim, sz.n_groups, sz.d_state
    m_in, m_columns, m_out = multipliers or (None, None, None)
    with scope("ssm_proj"):
        p = jnp.einsum("btd,df->btf", u if m_in is None else u * m_in,
                       lp["ssm_in"].astype(cdt))
        if m_columns is not None:
            p = p * jnp.asarray(m_columns, cdt)
        z, xbc, dt = jnp.split(p, [sz.d_ssm, sz.d_ssm + sz.conv_dim], -1)
    with scope("ssm.conv"):
        taps = sz.d_conv
        if carry is None:
            window = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
            conv_out = window[:, T:]
        else:
            conv_c, ssm_c, layer = carry
            window = jnp.concatenate(
                [_conv_before(conv_c, layer).astype(cdt), xbc], axis=1)
            conv_out = _written(conv_c, window[:, 1:], layer)
        w = lp["conv_w"].astype(f32)                    # (taps, C)
        acc = lp["conv_b"].astype(f32) + sum(
            window[:, k:k + T].astype(f32) * w[k] for k in range(taps))
        xbc = jax.nn.silu(acc).astype(cdt)
    x, b, c = jnp.split(xbc, [sz.d_ssm, sz.d_ssm + G * N], -1)
    x = x.reshape(B, T, H, P)
    b, c = b.reshape(B, T, G, N), c.reshape(B, T, G, N)
    dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
    a = -jnp.exp(lp["a_log"].astype(f32))
    tpus = _chip._traced_for_tpus()
    kernel = carry is None and fused(sz, forward_only, tpus, T, cdt)
    if carry is None:
        with scope("ssm.scan"):
            if kernel:
                from ompi_tpu.ops.ssm_scan import ssm_scan

                # x, B and C as the convolution left them, one array; y
                # comes with the skip D x and as the gate reads it
                y, ssm_out = ssm_scan(xbc, dt, a, lp["ssm_d"], G, N)
            else:
                y, ssm_out = chunked_scan(x, dt, a, b, c, sz.chunk)
    elif layer is None and in_place(sz, tpus, ssm_c.dtype):
        from ompi_tpu.ops.ssm_update import ssm_update

        with scope("ssm.update"):
            # the state as ``_state_before`` gives it, so that what a control
            # plants there reaches the kernel, narrowed back: of the carried
            # buffer a pair of converts, which the compiler removes, and the
            # kernel writes where the state lies
            y, ssm_out = ssm_update(
                _state_before(ssm_c, layer).astype(ssm_c.dtype), x[:, 0],
                dt[:, 0], a, b[:, 0], c[:, 0])
            y = y[:, None]                              # T == 1
    else:
        with scope("ssm.update"):
            h = _state_before(ssm_c, layer).reshape(B, G, H // G, P, N)
            dth = dt.reshape(B, G, H // G)              # T == 1
            xh = x.astype(f32).reshape(B, G, H // G, P) * dth[..., None]
            h = (h * jnp.exp(dth * a.reshape(G, H // G))[..., None, None]
                 + xh[..., None] * b.astype(f32).reshape(B, G, 1, 1, N))
            y = jnp.einsum("bgrpn,bgn->bgrp", h,
                           c.astype(f32).reshape(B, G, N)).reshape(B, 1, H, P)
            ssm_out = _written(ssm_c, h.reshape(B, H, P, N), layer)
    with scope("ssm_proj"):
        if not kernel:
            y = y + lp["ssm_d"].astype(f32)[:, None] * x.astype(f32)
        y = y.reshape(B, T, sz.d_ssm) * jax.nn.silu(z.astype(f32))
        # gate, then an RMSNorm over each group with one scale of d_ssm
        yg = y.reshape(B, T, G, sz.d_ssm // G)
        yg = yg * lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
        y = (yg.reshape(B, T, sz.d_ssm)
             * lp["ssm_norm"].astype(f32)).astype(cdt)
        s = jnp.einsum("btf,fd->btd", y, lp["ssm_out"].astype(cdt))
        return (s if m_out is None else s * m_out), conv_out, ssm_out


# ---- the mixer alone in a layer: the kind "ssm" of a plan -------------------

def _kind_leaf_shapes(cfg, sz: Mamba2) -> dict:
    """One layer's leaves: name -> (shape, deviation of the program's own
    initializer, None for ones, or a draw ``(rng, shape)``: :func:`init_
    leaves`' own of dt, A and the taps)."""
    D = cfg.d_model
    return {
        "ssm_in": ((D, sz.in_dim), D ** -0.5),
        "ssm_out": ((sz.d_ssm, D),
                    sz.d_ssm ** -0.5 / max(1, 2 * cfg.n_layers) ** 0.5),
        "conv_w": ((sz.d_conv, sz.conv_dim), _conv_w),
        "conv_b": ((sz.conv_dim,), _zeros),
        "a_log": ((sz.n_heads,), _a_log),
        "dt_bias": ((sz.n_heads,), _dt_bias),
        "ssm_d": ((sz.n_heads,), None),
        "ssm_norm": ((sz.d_ssm,), None),
    }


def _kind_buffers(cfg, sz: Mamba2, batch: int, t_max: int) -> tuple:
    """What a decoder carries for one layer (``models/plan.py``'s form): the
    convolution's last inputs ``(B, d_conv - 1, conv_dim)`` in the compute
    type and the heads' states ``(B, heads, head width, N)`` in
    ``state_dtype``; neither grows."""
    return (((batch, sz.d_conv - 1, sz.conv_dim), cfg.compute_dtype, None),
            ((batch, sz.n_heads, sz.head_dim, sz.d_state), sz.state_dtype,
             None))


def _kind_mixer(cfg, lp, h, carry=None, forward_only: bool = False):
    """One layer's mixer on the layer's input ``h`` (B, T, D): the norm, the
    mixer and the residual add of the branch times the plan's
    ``branch_factor``; ``forward_only`` as :func:`mixer`'s.

    ``carry`` None: whole sequences from a zero state; returns ``(h,
    conv_state, ssm_state)`` as :func:`mixer` does.  ``carry = (conv_c,
    ssm_c)``: T == 1 against this layer's own two buffers; returns ``(h,
    conv_c, ssm_c)``, the new ones in the same types."""
    from ompi_tpu.core.scopes import scope
    from ompi_tpu.models import transformer as tfm

    with scope("ssm_proj"):
        # the norm is called through the module: a benchmark's control
        # plants a wrong one there while a decoder is traced
        u = tfm._rmsnorm(h, lp["ln1"], cfg.norm_eps)
    s, *states = _mix(cfg.plan.ssm, cfg.norm_eps, lp, u,
                      carry and (*carry, None), forward_only=forward_only)
    with scope("ssm_proj"):
        if cfg.plan.branch_factor != 1:
            s = s * cfg.plan.branch_factor
        return (h + s, *states)


PLAN_KIND = types.SimpleNamespace(
    leaf_shapes=_kind_leaf_shapes, buffers=_kind_buffers, mixer=_kind_mixer,
    POSITIONED=False)
