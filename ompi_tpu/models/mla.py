"""Latent attention without a rotary embedding (NoPE), the mixer of a
``models/plan.py`` layer of kind "mla".

From the block's input ``h`` and its norm ``x = RMSNorm(h; ln1)``:

    q        = x mla_q                        heads x (nope + rope)
    [c, k_r] = x mla_kva                      kv_rank + rope; c <- RMSNorm(c)
    [k_n, v] = c mla_kvb                      heads x (nope + v_dim)
    a head's key is [k_n, k_r], k_r shared by all heads and not rotated
    h       += softmax(q . k (nope + rope)^-1/2, causal) v  wo

What is cached of a position is the normed latent and the shared key part
alone, ``kv_rank + rope`` elements for all heads.  The whole-sequence form
(trainer, prefill) multiplies a head's keys and values out of the latent,
the cheaper order at T rows a sequence.  The cached step absorbs: ``q_lat =
q_n W^K`` (heads x kv_rank), scores ``q_lat . c_s + q_r . k_r,s``, the
context ``sum_s p_s c_s`` (heads x kv_rank) through ``W^V``: it reads the
latent once for all heads and never multiplies a cached position out.
``jax.numpy`` alone.

Nothing imports this module but a configuration whose plan has the kind.
"""

from __future__ import annotations

import contextlib
import dataclasses

__all__ = ["MLA", "mixer", "leaf_shapes", "buffers", "POSITIONED"]

POSITIONED = True       # a cached step's carry ends with its position


@dataclasses.dataclass(frozen=True)
class MLA:
    """Sizes under the published configuration's names."""
    n_heads: int
    nope: int           # qk_nope_head_dim: a head's own key part
    rope: int           # qk_rope_head_dim: the key part all heads share
    v_dim: int          # v_head_dim
    kv_rank: int        # kv_lora_rank: the latent

    @property
    def cached(self) -> int:
        """Elements a position holds in the cache."""
        return self.kv_rank + self.rope


def leaf_shapes(cfg, ml: MLA) -> dict:
    """One layer's leaves: name -> (shape, deviation of the program's own
    initializer or None for ones)."""
    D, H = cfg.d_model, ml.n_heads
    out = H * ml.v_dim
    return {
        "mla_q": ((D, H * (ml.nope + ml.rope)), D ** -0.5),
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
def _proj():
    """The projections' scope: the latent's own name under the one every
    attending layer's projections have."""
    from ompi_tpu.core.scopes import scope

    with scope("attn_proj"), scope("mla_proj"):
        yield


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
    from ompi_tpu.models.transformer import _rmsnorm

    ml, f32, cdt = cfg.plan.mla, jnp.float32, h.dtype
    B, T, _ = h.shape
    H, N, P, W, R = ml.n_heads, ml.nope, ml.rope, ml.v_dim, ml.kv_rank
    scale = (N + P) ** -0.5
    with _proj():
        x = _rmsnorm(h, lp["ln1"], cfg.norm_eps)
        q = jnp.einsum("btd,df->btf", x, lp["mla_q"].astype(cdt)
                       ).reshape(B, T, H, N + P)
        kva = jnp.einsum("btd,df->btf", x, lp["mla_kva"].astype(cdt))
        lat = jnp.concatenate([
            _rmsnorm(kva[..., :R], lp["mla_n"], cfg.norm_eps),
            kva[..., R:]], axis=-1)
        wkv = lp["mla_kvb"].astype(cdt).reshape(R, H, N + W)
    if carry is None:
        with _proj():
            kv = jnp.einsum("btr,rhf->bthf", lat[..., :R], wkv)
        with scope("attention"):
            s = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :N], kv[..., :N],
                            preferred_element_type=f32)
                 + jnp.einsum("bqhd,bkd->bhqk", q[..., N:], lat[..., R:],
                              preferred_element_type=f32)) * scale
            causal = jnp.tril(jnp.ones((T, T), bool))
            w = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", w.astype(cdt), kv[..., N:],
                           preferred_element_type=f32)
        out = lat
    else:
        lat_c, pos = carry
        with scope("kv_cache"):
            out = lax.dynamic_update_slice(
                lat_c, lat.astype(lat_c.dtype), (0, pos, 0))
        with _proj():
            q_abs = jnp.concatenate([
                jnp.einsum("bthn,rhn->bthr", q[..., :N], wkv[..., :N]),
                q[..., N:]], axis=-1)[:, 0]             # (B, H, R + P)
        with scope("attention"):
            cache = out                                 # (B, Tmax, R + P)
            s = jnp.einsum("bhc,bkc->bhk", q_abs, cache.astype(cdt),
                           preferred_element_type=f32) * scale
            s = jnp.where(jnp.arange(cache.shape[1]) <= pos, s, -1e30)
            w = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum("bhk,bkr->bhr", w.astype(cdt),
                             cache[..., :R].astype(cdt),
                             preferred_element_type=f32)
        with _proj():
            o = jnp.einsum("bhr,rhw->bhw", ctx.astype(cdt), wkv[..., N:],
                           preferred_element_type=f32)[:, None]
    with _proj():
        o = o.astype(cdt).reshape(B, T, H * W)
        return h + jnp.einsum("btf,fd->btd", o, lp["wo"].astype(cdt)), out
