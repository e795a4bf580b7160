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

Nothing imports this module but a configuration whose plan has the kind.
"""

from __future__ import annotations

import contextlib
import dataclasses

__all__ = ["MLA", "mixer", "rotate", "leaf_shapes", "buffers", "POSITIONED",
           "KERNEL_FROM"]

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

    @property
    def cached(self) -> int:
        """Elements a position holds in the cache."""
        return self.kv_rank + self.rope


def leaf_shapes(cfg, ml: MLA) -> dict:
    """One layer's leaves: name -> (shape, deviation of the program's own
    initializer or None for ones)."""
    D, H = cfg.d_model, ml.n_heads
    out = H * ml.v_dim
    wide = H * (ml.nope + ml.rope)
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
    }


def buffers(cfg, ml: MLA, batch: int, t_max: int) -> tuple:
    """What a decoder carries for one layer (``models/plan.py``'s form): the
    cached rows ``(B, t_max, kv_rank + rope)`` in the compute type, which
    grow along the carry's axis 2."""
    return (((batch, t_max, ml.cached), cfg.compute_dtype, 2),)


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


def rotate(x, positions, theta: float):
    """The rotary embedding of x (B, T, ..., rope) at ``positions`` (T,), as
    the models of this family are published: elements 2i and 2i + 1 are a
    pair, turned by ``position x theta^(-2i / rope)``; the pairs stay where
    they are (a key and a query turned alike, so their product is that of any
    other placing of the pairs).  Float32 inside, x's type out.  A neighbour
    is fetched by a roll along the lanes, which needs no re-layout."""
    import jax.numpy as jnp

    from ompi_tpu.core.scopes import scope

    with scope("mla.rotate"):
        rope, f32 = x.shape[-1], jnp.float32
        lane = jnp.arange(rope)
        ang = (positions.astype(f32)[:, None]
               * theta ** (-(lane // 2 * 2).astype(f32) / rope))
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


def mixer(cfg, lp, h, carry=None):
    """One layer's mixer on the block's input ``h`` (B, T, D): the norm,
    attention and the residual add.

    ``carry`` None: whole sequences; returns ``(h, latent)``, every
    position's cached row ``(B, T, kv_rank + rope)`` in h's type.  ``carry =
    (lat_c, pos)``: T == 1, the new position ``pos`` against this layer's
    own cache ``(B, Tmax, kv_rank + rope)``, its row written in place first;
    returns ``(h, lat_c)``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.models.kda import _traced_for_tpus
    from ompi_tpu.models.transformer import _rmsnorm
    from ompi_tpu.ops import latent_attention, latent_decode

    ml, f32, cdt = cfg.plan.mla, jnp.float32, h.dtype
    B, T, _ = h.shape
    H, N, P, W, R = ml.n_heads, ml.nope, ml.rope, ml.v_dim, ml.kv_rank
    scale = (N + P) ** -0.5
    with _proj(ml):
        x = _rmsnorm(h, lp["ln1"], cfg.norm_eps)
        if ml.q_rank:
            q = jnp.einsum("btr,rf->btf", _scaled_norm(
                cfg, jnp.einsum("btd,dr->btr", x, lp["mla_qa"].astype(cdt)),
                lp["mla_qn"], ml.q_scale), lp["mla_qb"].astype(cdt))
        else:
            q = jnp.einsum("btd,df->btf", x, lp["mla_q"].astype(cdt))
        q = q.reshape(B, T, H, N + P)
        kva = jnp.einsum("btd,df->btf", x, lp["mla_kva"].astype(cdt))
        c = _scaled_norm(cfg, kva[..., :R], lp["mla_n"], ml.kv_scale)
        k_r = kva[..., R:]
        if ml.theta:
            at = jnp.arange(T) if carry is None else carry[1][None]
            q = jnp.concatenate([q[..., :N], rotate(q[..., N:], at,
                                                    ml.theta)], axis=-1)
            k_r = rotate(k_r, at, ml.theta)
        lat = jnp.concatenate([c, k_r], axis=-1)
        wkv = lp["mla_kvb"].astype(cdt).reshape(R, H, N + W)
    if carry is None:
        with _proj(ml):
            kv = jnp.einsum("btr,rhf->bthf", lat[..., :R], wkv)
        with scope("attention"):
            form = (latent_attention.latent_attention
                    if (T >= KERNEL_FROM and _traced_for_tpus()
                        and latent_attention.tiles(T, H, N, P, W))
                    else latent_attention.jnp_form)
            o = form(q, kv, lat[..., R:], scale)
        out = lat
    else:
        lat_c, pos = carry
        with scope("kv_cache"):
            out = lax.dynamic_update_slice(
                lat_c, lat.astype(lat_c.dtype), (0, pos, 0))
        with _proj(ml):
            q_abs = jnp.concatenate([
                jnp.einsum("bthn,rhn->bthr", q[..., :N], wkv[..., :N]),
                q[..., N:]], axis=-1)[:, 0]             # (B, H, R + P)
        with scope("attention"):
            cache = out                                 # (B, Tmax, R + P)
            if _traced_for_tpus() and latent_decode.tiles(cache.shape[1], R):
                ctx = latent_decode.latent_decode(q_abs, cache, pos, scale, R)
            else:
                s = jnp.einsum("bhc,bkc->bhk", q_abs, cache.astype(cdt),
                               preferred_element_type=f32) * scale
                s = jnp.where(jnp.arange(cache.shape[1]) <= pos, s, -1e30)
                w = jax.nn.softmax(s, axis=-1)
                ctx = jnp.einsum("bhk,bkr->bhr", w.astype(cdt),
                                 cache[..., :R].astype(cdt),
                                 preferred_element_type=f32)
        with _proj(ml):
            o = jnp.einsum("bhr,rhw->bhw", ctx.astype(cdt), wkv[..., N:],
                           preferred_element_type=f32)[:, None]
    with _proj(ml):
        o = o.astype(cdt).reshape(B, T, H * W)
        return h + jnp.einsum("btf,fd->btd", o, lp["wo"].astype(cdt)), out
