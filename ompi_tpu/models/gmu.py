"""The gated memory unit alone in a layer: the kind "gmu" of a layer plan
(``models/plan.py``).  ``out = (silu(u W_1) * m_t) W_2``: a gate out of the
layer's own normed input on the scan output ``m_t`` that an earlier row's
selective mixer handed on at the same position (``LayerPlan.reads``).  It
mixes no positions and carries nothing: a decoder allocates no buffer for it,
and a prefill runs it on a prompt's last position alone.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Gmu", "leaf_shapes", "buffers", "mixer", "hands", "reads",
           "POSITIONED"]

POSITIONED = False


@dataclasses.dataclass(frozen=True)
class Gmu:
    """``width``: the memory's channels (the source mixer's inner width)."""
    width: int


def hands(sz: Gmu):
    return None


def reads(sz: Gmu):
    """What its source has to hand on: a mixer's output of the same pass."""
    return "output"


def leaf_shapes(cfg, sz: Gmu) -> dict:
    D, W = cfg.d_model, sz.width
    return {"gmu_in": ((D, W), D ** -0.5),
            "gmu_out": ((W, D), W ** -0.5 / max(1, 2 * cfg.n_layers) ** 0.5)}


def buffers(cfg, sz: Gmu, batch: int, t_max: int) -> tuple:
    return ()


def mixer(cfg, lp, h, carry=None, *, source):
    """One layer's mixer on ``h`` (B, T, D) and the memory ``source`` (B, T,
    width) of the same positions: the norm, the unit and the residual add of
    the branch times the plan's ``branch_factor``; the same for whole
    sequences and for a cached step.  Returns ``(h,)``."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.models import transformer as tfm

    cdt = jnp.dtype(cfg.compute_dtype)
    with scope("gmu"):
        u = tfm._norm(h, lp["ln1"], cfg.norm_eps,
                      lp.get("ln1b")).astype(cdt)
        gate = jax.nn.silu(jnp.einsum(
            "btd,df->btf", u, lp["gmu_in"].astype(cdt),
            preferred_element_type=jnp.float32))
        s = jnp.einsum("btf,fd->btd", (gate * source).astype(cdt),
                       lp["gmu_out"].astype(cdt))
        if cfg.plan.branch_factor != 1:
            s = s * cfg.plan.branch_factor
        return (h + s,)
