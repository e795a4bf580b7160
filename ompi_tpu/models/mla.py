"""Latent attention, the mixer of a ``models/plan.py`` layer of kind "mla",
in both published forms: without a rotary embedding (NoPE, ``MLA.theta`` 0)
and with one on the key part all heads share (``MLA.theta`` the base).

From the block's input ``h`` and its norm ``x = RMSNorm(h; ln1)``:

    q        = x mla_q                        heads x (nope + rope)
               or, with a query latent (``MLA.q_rank``),
               q_scale RMSNorm(x mla_qa; mla_qn) mla_qb
    [c, k_r] = x mla_kva                      kv_rank + rope;
                                              c <- kv_scale RMSNorm(c)
    [k_n, v] = c mla_kvb                      heads x (nope + v_dim)
    theta > 0: every head's q[nope:] and k_r rotated at their position
               (``rotate``: neighbouring pairs, pair i by position x
               theta^(-2i / rope)); theta 0: neither is
    a head's key is [k_n, k_r], k_r shared by all heads
    h       += softmax(q . k (nope + rope)^-1/2, causal) v  wo

What is cached of a position is the normed latent and the shared key part
as the scores read it (rotated, where the form rotates), ``kv_rank + rope``
elements for all heads.  The whole-sequence form (trainer, prefill)
multiplies a head's keys and values out of the latent, the cheaper order at
T rows a sequence; its attention is ``jax.numpy`` over a sequence's whole (T,
T) scores anywhere but on TPUs and under ``KERNEL_FROM`` positions, and from
there on the pallas ``ops/latent_attention.py``, which holds a tile of them
and cuts the tile to the length (its backward pass is the ``jax.numpy``
form's, so a loss takes the same rule).  The cached step
absorbs: ``q_lat = q_n W^K`` (heads x kv_rank), scores ``q_lat . c_s + q_r .
k_r,s``, the context ``sum_s p_s c_s`` (heads x kv_rank) through ``W^V``: it
reads the latent for all heads at once and never multiplies a cached
position out.  On TPUs, where the cache is whole blocks of positions and the
latent whole lane tiles (``ops/latent_decode.tiles``), that is one pallas
pass, ``ops/latent_decode.py``, which holds a block of rows for the scores
and for the context; anywhere else ``jax.numpy``, which reads the cache once
for each.

A layer may carry an **index** (``MLA.index``, the sizes of
``models/sparse_index.py``: learned sparse attention inside a latent layer).
Its queries come from the normed query latent (``q_rank`` is then needed),
its one key a position and its head weights from ``x``; the first ``rope``
elements of each index head and of the key are rotated as the layer rotates;
what it selects is rows of the latent cache, shared by all heads.  Such a
layer carries a second buffer, the index keys with the positions last ``(B,
width, Tmax)``.  A cached step scores the new position against them
(``sparse_index.scores``), finds the set as a mask (``sparse_index.select``)
and reads the cache once under it: the same absorbed pass, the mask one more
operand of ``ops/latent_decode.py`` (every head reads the same rows, so the
selection saves no row of a stream and a gather of them costs more than the
stream up to ``sparse_index._STREAM_UP_TO`` selections of cache; PERF.md
section 6, PR 67).  Whole sequences go a slice of ``index.q_slice`` queries
at a time, the whole slices one ``lax.scan`` over one shape (as
``models/block_select.py``): a slice's index scores against every key, its
selection as a mask, and attention in the expanded form under it, the pallas
``ops/masked_latent_attention.py`` on TPUs, handed the slice's end as
``k_len``; the slices that end within the first ``topk`` positions see every
earlier position and compute no score.

A **scaled rotation** (``MLA.yarn``, :class:`Yarn`: the published
``rope_scaling`` of type "yarn") turns pair i by ``position x`` a blend of
``theta^(-2i / rope)`` and that over ``factor`` (:meth:`Yarn.frequencies`)
and multiplies the softmax scale by :attr:`Yarn.softmax_factor`.

Nothing imports this module but a configuration whose plan has the kind.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any

import numpy as np

__all__ = ["MLA", "Yarn", "mixer", "rotate", "leaf_shapes", "buffers",
           "POSITIONED", "KERNEL_FROM"]

POSITIONED = True       # a cached step's carry ends with its position
# Whole sequences from this many positions on take the kernel where it tiles:
# the shortest length at which it was measured against the ``jax.numpy`` form
# inside a cell's own prefill, and it won at every one (the ``attention``
# scope of a prefill on a v5e, PERF.md section 6, PR 61: 8 x 512 positions of
# 32 heads 76.5 -> 19.0 ms, 4 x 896 of 64 heads 1812 -> 268 ms; at 16,128
# positions the form's scores do not fit).  Under it nothing is measured and
# no cell runs, so the form that needs no kernel stays.
KERNEL_FROM = 512


@dataclasses.dataclass(frozen=True)
class Yarn:
    """A published ``rope_scaling`` of type "yarn", under its keys."""
    factor: float
    original: int           # original_max_position_embeddings
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def _magnitude(self, mscale: float) -> float:
        return (0.1 * mscale * math.log(self.factor) + 1.0
                if self.factor > 1 else 1.0)

    def limits(self, rope: int, theta: float) -> tuple:
        """``(low, high)``: the pairs under ``low`` turn as published, those
        over ``high`` ``factor`` times slower, those between by a ramp."""
        def pair(turns):    # the pair that makes ``turns`` over ``original``
            return (rope * math.log(self.original / (turns * 2 * math.pi))
                    / (2 * math.log(theta)))

        return (max(math.floor(pair(self.beta_fast)), 0),
                min(math.ceil(pair(self.beta_slow)), rope - 1))

    def frequencies(self, rope: int, theta: float) -> np.ndarray:
        """Radians a position of the ``rope / 2`` pairs, float32."""
        i = np.arange(rope // 2, dtype=np.float64)
        plain = theta ** (-2 * i / rope)
        low, high = self.limits(rope, theta)
        ramp = np.clip((i - low) / max(high - low, 1e-3), 0, 1)
        return ((1 - ramp) * plain + ramp * plain / self.factor
                ).astype(np.float32)

    @property
    def softmax_factor(self) -> float:
        """What the scores' scale is multiplied by: ``m^2``, ``m = 0.1
        mscale_all_dim ln(factor) + 1``."""
        return self._magnitude(self.mscale_all_dim) ** 2

    @property
    def rotation_factor(self) -> float:
        """What cos and sin are multiplied by (1 where ``mscale`` equals
        ``mscale_all_dim``; no other case is built)."""
        return (self._magnitude(self.mscale)
                / self._magnitude(self.mscale_all_dim))


@dataclasses.dataclass(frozen=True)
class MLA:
    """Sizes under the published configuration's names."""
    n_heads: int
    nope: int           # qk_nope_head_dim: a head's own key part
    rope: int           # qk_rope_head_dim: the key part all heads share
    v_dim: int          # v_head_dim
    kv_rank: int        # kv_lora_rank: the latent
    theta: float = 0.0  # rope_theta: the rotation's base; 0: NoPE, no rotation
    q_rank: int = 0     # q_lora_rank: the query latent; 0: none, the queries
                        # are projected straight from the stream
    # what the two normed latents are multiplied by (``mla_scale_q_lora``,
    # ``mla_scale_kv_lora``: sqrt(d_model / rank) each); 1: nothing
    q_scale: float = 1.0
    kv_scale: float = 1.0
    # ``sparse_index.SparseIndex``: an index picks the cached rows a query
    # reads (its queries from the query latent); None: every earlier row
    index: Any = None
    yarn: Any = None    # :class:`Yarn`: the rotation scaled; None: as it is

    @property
    def cached(self) -> int:
        """Elements a position holds in the cache."""
        return self.kv_rank + self.rope

    @property
    def scale(self) -> float:
        """What the scores are multiplied by."""
        return ((self.nope + self.rope) ** -0.5
                * (self.yarn.softmax_factor if self.yarn else 1.0))

    def frequencies(self):
        """The rotation's radians a position and pair where they are not
        ``theta^(-2i / rope)`` (a scaled rotation); None where they are."""
        return self.yarn and self.yarn.frequencies(self.rope, self.theta)


def leaf_shapes(cfg, ml: MLA) -> dict:
    """One layer's leaves: name -> (shape, deviation of the program's own
    initializer or None for ones)."""
    D, H = cfg.d_model, ml.n_heads
    out = H * ml.v_dim
    wide = H * (ml.nope + ml.rope)
    ix = ml.index
    return {
        **({"mla_qa": ((D, ml.q_rank), D ** -0.5),
            "mla_qn": ((ml.q_rank,), None),
            "mla_qb": ((ml.q_rank, wide), ml.q_rank ** -0.5)} if ml.q_rank
           else {"mla_q": ((D, wide), D ** -0.5)}),
        "mla_kva": ((D, ml.cached), D ** -0.5),
        "mla_n": ((ml.kv_rank,), None),
        "mla_kvb": ((ml.kv_rank, H * (ml.nope + ml.v_dim)),
                    ml.kv_rank ** -0.5),
        "wo": ((out, D), out ** -0.5 / max(1, 2 * cfg.n_layers) ** 0.5),
        # the index's leaves (``sparse_index.leaf_names``): its queries out
        # of the query latent, its key (with a LayerNorm's scale and bias)
        # and its head weights out of the stream
        **({"wiq": ((ml.q_rank, ix.n_heads * ix.head_dim),
                    ml.q_rank ** -0.5),
            "wik": ((D, ix.head_dim), D ** -0.5),
            "wiw": ((D, ix.n_heads), D ** -0.5),
            "ikn": ((ix.head_dim,), None),
            "ikb": ((ix.head_dim,),
                    lambda _rng, dims: np.zeros(dims, np.float32))}
           if ix else {}),
    }


def buffers(cfg, ml: MLA, batch: int, t_max: int) -> tuple:
    """What a decoder carries for one layer (``models/plan.py``'s form): the
    cached rows ``(B, t_max, kv_rank + rope)`` in the compute type, which
    grow along the carry's axis 2; with an index also its keys ``(B, width,
    t_max)``, positions last (one product of the buffer as it lies scores a
    step), which grow along the carry's axis 3."""
    rows = ((batch, t_max, ml.cached), cfg.compute_dtype, 2)
    if ml.index is None:
        return (rows,)
    return (rows, ((batch, ml.index.head_dim, t_max), cfg.compute_dtype, 3))


@contextlib.contextmanager
def _proj(ml: MLA):
    """The projections' scope: the latent's own name under the one every
    attending layer's projections have.  The form that rotates has a name of
    its own, and so has the form with a query latent, so that a metric of one
    form's projections finds nothing in a cell of another's."""
    from ompi_tpu.core.scopes import scope

    with scope("attn_proj"), scope(
            "mla_proj.query_latent" if ml.q_rank
            else "mla_proj.rope" if ml.theta else "mla_proj"):
        yield


def rotate(x, positions, theta: float, frequencies=None):
    """The rotary embedding of x (B, T, ..., rope) at ``positions`` (T,), as
    the models of this family are published: elements 2i and 2i + 1 are a
    pair, turned by ``position x theta^(-2i / rope)``, or by ``position x
    frequencies[i]`` where a scaled rotation gives its own (``rope / 2`` of
    them: ``MLA.frequencies``); the pairs stay where
    they are (a key and a query turned alike, so their product is that of any
    other placing of the pairs).  Float32 inside, x's type out.  A neighbour
    is fetched by a roll along the lanes, which needs no re-layout."""
    import jax.numpy as jnp

    from ompi_tpu.core.scopes import scope

    with scope("mla.rotate"):
        rope, f32 = x.shape[-1], jnp.float32
        lane = jnp.arange(rope)
        ang = (positions.astype(f32)[:, None]
               * (theta ** (-(lane // 2 * 2).astype(f32) / rope)
                  if frequencies is None
                  else jnp.asarray(np.repeat(frequencies, 2), f32)))
        ang = ang.reshape(-1, *(1,) * (x.ndim - 3), rope)
        xf = x.astype(f32)
        # pair (a, b) -> (a cos - b sin, b cos + a sin)
        other = jnp.where(lane % 2 == 0, jnp.roll(xf, -1, axis=-1),
                          -jnp.roll(xf, 1, axis=-1))
        return (xf * jnp.cos(ang) - other * jnp.sin(ang)).astype(x.dtype)


def _scaled_norm(cfg, x, scale, times: float):
    """``times x RMSNorm(x; scale)``, the factor folded into the norm's
    float32 scale (one rounding, the norm's own)."""
    import jax.numpy as jnp

    from ompi_tpu.models.transformer import _rmsnorm

    if times != 1:
        scale = scale.astype(jnp.float32) * times
    return _rmsnorm(x, scale, cfg.norm_eps)


def _turned(ml: MLA, x, positions):
    """``rotate`` as the layer turns: a scaled rotation hands its frequencies
    on, a plain one calls ``rotate`` as it always has (a benchmark's control
    wraps it with three arguments)."""
    freqs = ml.frequencies()
    return rotate(x, positions, ml.theta,
                  *(() if freqs is None else (freqs,)))


def _index_rotation(ml: MLA):
    """``rotate(y, positions)`` of the index's heads and key: the first
    ``rope`` elements of each turned as the layer turns its own."""
    import jax.numpy as jnp

    def turn(y, positions):
        return jnp.concatenate([_turned(ml, y[..., :ml.rope], positions),
                                y[..., ml.rope:]], axis=-1)

    return turn


def _select_attend(ml: MLA, q, kv, k_r, qi, ki, wi, forward_only: bool):
    """Attention of whole sequences from position 0 under the index's
    selection: q (B, T, H, nope + rope), kv (B, T, H, nope + v_dim) and k_r
    (B, T, rope), rotated; the index's queries qi (B, T, heads, width), keys
    ki (B, T, width) and head weights wi (B, T, heads).  (B, T, H, v_dim)
    in q's type.

    A slice of ``index.q_slice`` queries at a time.  The whole slices are the
    iterations of a ``lax.scan`` over one shape, so a prompt of any length
    traces, lowers and compiles a slice once: the slice's index scores
    against the sequence's every key, which its positions mask down to those
    so far, the selection as a mask, and attention that stops at the slice's
    end (the kernel's ``k_len``).  The slices that end within the first
    ``topk`` positions attend to every earlier position and compute no
    score: a scan of their own, before the selected ones.  A tail shorter
    than ``q_slice`` is one more call.  The kernel (TPUs, ``forward_only``:
    it has no backward pass) where a slice tiles for it, the jnp form
    anywhere else."""
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.models import sparse_index
    from ompi_tpu.ops import _chip
    from ompi_tpu.ops import masked_latent_attention as kernel

    ix = ml.index
    B, T, H, _ = q.shape
    kt = lax.stop_gradient(ki.swapaxes(1, 2))               # (B, width, T)
    qi, wi = lax.stop_gradient(qi), lax.stop_gradient(wi)
    at = jnp.arange(T)

    def slice_(lo, n: int, select: bool):
        """The context (B, n, H, v_dim) of the ``n`` queries from ``lo``."""
        def cut(y):
            return lax.dynamic_slice_in_dim(y, lo, n, axis=1)

        mask = (at <= (lo + jnp.arange(n))[:, None])[None]  # (1, n, T)
        if select:
            with scope("latent_index.score"):
                found = sparse_index.scores(cut(qi), cut(wi), kt)
            with scope("latent_index.select"):
                mask = sparse_index.select(found, mask, ix.topk)
        mask = jnp.broadcast_to(mask, (B, n, T))
        with scope("attention"), scope("attention.selected"):
            if (forward_only and _chip._traced_for_tpus()
                    and kernel.tiles(n, H, ml.nope, ml.rope, ml.v_dim)):
                return kernel.masked_latent_attention(
                    cut(q), kv, k_r, mask, ml.scale, k_len=lo + n)
            return kernel.jnp_form(cut(q), kv, k_r, mask,
                                   ml.scale).astype(q.dtype)

    def whole(o, first: int, count: int, select: bool):
        """``o`` with the slices ``first .. first + count - 1`` written."""
        def one(o, lo):
            return lax.dynamic_update_slice_in_dim(
                o, slice_(lo, ix.q_slice, select), lo, axis=1), None

        return lax.scan(one, o,
                        ix.q_slice * jnp.arange(first, first + count))[0]

    slices = T // ix.q_slice
    dense = min(slices, ix.topk // ix.q_slice)  # they end within topk
    o = jnp.zeros((B, T, H, ml.v_dim), q.dtype) if slices else None
    for first, count, select in ((0, dense, False),
                                 (dense, slices - dense, True)):
        if count:
            o = whole(o, first, count, select)
    if T % ix.q_slice:
        lo = slices * ix.q_slice
        tail = slice_(lo, T - lo, T > ix.topk)
        o = tail if o is None else lax.dynamic_update_slice_in_dim(
            o, tail, lo, axis=1)
    return o


def mixer(cfg, lp, h, carry=None, forward_only: bool = False):
    """One layer's mixer on the block's input ``h`` (B, T, D): the norm,
    attention and the residual add.

    ``carry`` None: whole sequences; returns ``(h, latent)``, every
    position's cached row ``(B, T, kv_rank + rope)`` in h's type, and with
    an index ``(h, latent, index keys (B, width, T))``.  ``carry = (lat_c,
    pos)``, with an index ``(lat_c, keys_c, pos)``: T == 1, the new position
    ``pos`` against this layer's own cache ``(B, Tmax, kv_rank + rope)`` (and
    index keys ``(B, width, Tmax)``), its row (and key) written in place
    first; returns ``(h, lat_c)`` or ``(h, lat_c, keys_c)``.
    ``forward_only`` (a decoder's prefill): no gradient is asked of this
    pass, so an indexed layer may take its kernel, which has no backward
    pass."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.models.transformer import _rmsnorm
    from ompi_tpu.ops import _chip, latent_attention, latent_decode

    ml, f32, cdt = cfg.plan.mla, jnp.float32, h.dtype
    B, T, _ = h.shape
    H, N, P, W, R = ml.n_heads, ml.nope, ml.rope, ml.v_dim, ml.kv_rank
    scale = ml.scale
    with _proj(ml):
        x = _rmsnorm(h, lp["ln1"], cfg.norm_eps)
        if ml.q_rank:
            cq = _scaled_norm(
                cfg, jnp.einsum("btd,dr->btr", x, lp["mla_qa"].astype(cdt)),
                lp["mla_qn"], ml.q_scale)
            q = jnp.einsum("btr,rf->btf", cq, lp["mla_qb"].astype(cdt))
            if carry is not None and ml.index is not None:
                # a cached step's product of 8 rows ends here: the TPU's
                # compiler otherwise folds the split into heads and parts
                # into it, wants the weight with the latent minor, and
                # copies every layer's matrix out of its stack re-laid every
                # step (75 MB read and written a layer; PR 67, as
                # ``lightning.mixer``'s since PR 53).  The plans without an
                # index keep the program they have.
                q = lax.optimization_barrier(q)
        else:
            q = jnp.einsum("btd,df->btf", x, lp["mla_q"].astype(cdt))
        q = q.reshape(B, T, H, N + P)
        kva = jnp.einsum("btd,df->btf", x, lp["mla_kva"].astype(cdt))
        c = _scaled_norm(cfg, kva[..., :R], lp["mla_n"], ml.kv_scale)
        k_r = kva[..., R:]
        if ml.theta:
            at = jnp.arange(T) if carry is None else carry[-1][None]
            q = jnp.concatenate([q[..., :N], _turned(ml, q[..., N:], at)],
                                axis=-1)
            k_r = _turned(ml, k_r, at)
        lat = jnp.concatenate([c, k_r], axis=-1)
        wkv = lp["mla_kvb"].astype(cdt).reshape(R, H, N + W)
    if ml.index is not None:
        from ompi_tpu.models import sparse_index

        qi, ki, wi = sparse_index.project(
            cfg, lp, x, at, queries_from=cq, ix=ml.index,
            rotate=_index_rotation(ml), within="latent_index_proj",
            ends_product=carry is not None)
    if carry is None:
        with _proj(ml):
            kv = jnp.einsum("btr,rhf->bthf", lat[..., :R], wkv)
        if ml.index is not None:
            o = _select_attend(ml, q, kv, lat[..., R:], qi, ki, wi,
                               forward_only)
            out = (lat, ki.swapaxes(1, 2))
        else:
            with scope("attention"):
                form = (latent_attention.latent_attention
                        if (T >= KERNEL_FROM and _chip._traced_for_tpus()
                            and latent_attention.tiles(T, H, N, P, W))
                        else latent_attention.jnp_form)
                o = form(q, kv, lat[..., R:], scale)
            out = (lat,)
    else:
        lat_c, *keys_c, pos = carry
        with scope("kv_cache"):
            cache = lax.dynamic_update_slice(
                lat_c, lat.astype(lat_c.dtype), (0, pos, 0))
            out = (cache,)
            if keys_c:
                out += (lax.dynamic_update_slice(
                    keys_c[0], ki.swapaxes(1, 2).astype(keys_c[0].dtype),
                    (0, 0, pos)),)
        with _proj(ml):
            q_abs = jnp.concatenate([
                jnp.einsum("bthn,rhn->bthr", q[..., :N], wkv[..., :N]),
                q[..., N:]], axis=-1)[:, 0]             # (B, H, R + P)
        Tmax = cache.shape[1]                           # (B, Tmax, R + P)
        chosen = None
        if keys_c and ml.index.topk < Tmax:
            live = jnp.arange(Tmax) <= pos
            with scope("latent_index.score"):
                found = sparse_index.scores(qi, wi, out[1])[:, 0]
            with scope("latent_index.select"):
                chosen = sparse_index.select(found, live, ml.index.topk)
        with scope("attention"), (contextlib.nullcontext() if chosen is None
                                  else scope("attention.selected")):
            if _chip._traced_for_tpus() and latent_decode.tiles(Tmax, R):
                ctx = latent_decode.latent_decode(q_abs, cache, pos, scale,
                                                  R, chosen)
            else:
                s = jnp.einsum("bhc,bkc->bhk", q_abs, cache.astype(cdt),
                               preferred_element_type=f32) * scale
                s = jnp.where(jnp.arange(Tmax) <= pos, s, -1e30)
                if chosen is not None:
                    s = jnp.where(chosen[:, None], s, -1e30)
                w = jax.nn.softmax(s, axis=-1)
                ctx = jnp.einsum("bhk,bkr->bhr", w.astype(cdt),
                                 cache[..., :R].astype(cdt),
                                 preferred_element_type=f32)
        with _proj(ml):
            o = jnp.einsum("bhr,rhw->bhw", ctx.astype(cdt), wkv[..., N:],
                           preferred_element_type=f32)[:, None]
    with _proj(ml):
        o = jnp.einsum("btf,fd->btd", o.astype(cdt).reshape(B, T, H * W),
                       lp["wo"].astype(cdt))
        if cfg.plan.branch_factor != 1:
            o = o * cfg.plan.branch_factor
        return (h + o, *out)
