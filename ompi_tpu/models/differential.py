"""Differential attention alone in a layer: three kinds of a layer plan
(``models/plan.py``) under one :class:`Differential`, which says which.

Query heads ``2i`` and ``2i + 1`` are pair ``i``'s ``(q1, q2)``; K heads ``2j``
and ``2j + 1`` are ``(k1, k2)`` and V heads ``2j, 2j + 1`` side by side one
value of width ``2 hd``; pair ``i`` reads ``j = i // (n_heads / kv_heads)``.
Two softmaxes over one value, subtracted: ``a_c = softmax(q_c k_c^T /
sqrt(hd)) V``, ``o = (1 - lam0) RMSNorm(a_1 - lam a_2) * sub`` with ``lam =
exp(lq1 . lk1) - exp(lq2 . lk2) + lam0`` and ``lam0 = 0.8 - 0.6 exp(-0.3
layer)`` (:func:`constants`: the layer's place is part of its arithmetic).
Both projections carry a bias; no rotary embedding.

- "window" (``window`` > 0): causal over the last ``window`` keys, the
  query's own counted.  A decoder carries a **ring**: the last ``window``
  positions' K and V ``(B, window, Hkv, hd)``, position ``p`` at slot ``p mod
  window``, written in place and never shifted; it does not grow.  A whole
  sequence is attended in blocks of queries over the band of keys they see
  (:func:`_whole`), so a long prompt costs ``T x window`` and not ``T^2``.
- "differential" (``window`` 0): causal over every earlier position; a decoder
  carries K and V of every position ``(B, t_max, Hkv, hd)``, which grow, and
  **hands them on** (``hands``): a "shared" row of the plan reads them.  A
  decoder's prefill traced for TPUs takes the flash kernel over heads padded
  to the value's width (:func:`_flash`); anything else the blocks of
  :func:`_whole`.
- "shared" (``cross``): a query projection alone against the K and V of the
  row it reads (``LayerPlan.reads``), every position up to its own; it owns no
  buffer.  Its queries may be the last few of the source's positions (a
  prefill that runs it at a prompt's last position).
"""

from __future__ import annotations

import contextlib
import dataclasses

__all__ = ["Differential", "leaf_shapes", "buffers", "mixer", "constants",
           "hands", "reads", "POSITIONED", "QUERY_BLOCK"]

POSITIONED = True

# queries whose scores are held at a time in a whole-sequence pass
QUERY_BLOCK = 512
_SUB_EPS = 1e-5


@dataclasses.dataclass(frozen=True)
class Differential:
    """``n_heads`` query heads over ``kv_heads`` K/V heads of ``head_dim``;
    ``window``: the keys a query sees, its own counted (0: every earlier
    one); ``cross``: no K/V of its own, another row's; ``prefix``: what the
    kind's leaves' names start with (three kinds of one plan have the same
    leaves)."""
    n_heads: int
    kv_heads: int
    head_dim: int
    window: int = 0
    cross: bool = False
    prefix: str = ""


def hands(sz: Differential):
    """The full layer's K and V are what a "shared" row reads."""
    return None if sz.cross or sz.window else "buffers"


def reads(sz: Differential):
    return "buffers" if sz.cross else None


def constants(sz: Differential, layer: int) -> dict:
    """What the plan hands the mixer beside its leaves: ``lam0`` of the
    layer's place in the model, and which of the three kinds it is."""
    import math

    return {"lam0": 0.8 - 0.6 * math.exp(-0.3 * layer), "sizes": sz}


def leaf_shapes(cfg, sz: Differential) -> dict:
    """One layer's leaves: name -> (shape, deviation or None for ones, or a
    draw ``(rng, shape)``)."""
    from ompi_tpu.models.ssm import _zeros as zeros

    D, hd, p = cfg.d_model, sz.head_dim, sz.prefix
    q, kv = sz.n_heads * hd, sz.kv_heads * hd
    first = ({p + "q": ((D, q), D ** -0.5), p + "qb": ((q,), zeros)}
             if sz.cross else
             {p + "qkv": ((D, q + 2 * kv), D ** -0.5),
              p + "qkvb": ((q + 2 * kv,), zeros)})
    return {**first,
            p + "o": ((q, D), q ** -0.5 / max(1, 2 * cfg.n_layers) ** 0.5),
            p + "ob": ((D,), zeros),
            p + "lam": ((4, hd), 0.1),
            p + "sub": ((2 * hd,), None)}


def buffers(cfg, sz: Differential, batch: int, t_max: int) -> tuple:
    """What a decoder carries for one layer (``models/plan.py``'s form):
    nothing (``cross``); a ring of ``window`` positions, which does not grow;
    or K and V of ``t_max`` positions, which grow along the carry's axis 2."""
    if sz.cross:
        return ()
    rows, axis = (sz.window, None) if sz.window else (t_max, 2)
    shape = (batch, rows, sz.kv_heads, sz.head_dim)
    return ((shape, cfg.compute_dtype, axis),) * 2


def _attend(q, k, v, seen):
    """q (B, Tq, G, P, 2, hd) over k (B, Tk, G, 2, hd) and v (B, Tk, G, 2
    hd) under ``seen`` (Tq, Tk): the two softmaxes' contexts (B, Tq, G, P, 2,
    2 hd) float32; a K/V pair read once for the query pairs it serves."""
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("bqgpcd,bkgcd->bgpcqk", q, k,
                   preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    w = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
    return jnp.einsum("bgpcqk,bkge->bqgpce", w.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def _lambda(rows, lam0):
    """``lam`` of a layer's four vectors ``rows`` (4, hd) and its ``lam0``
    (a benchmark's control plants a wrong one here while a decoder is traced;
    the same of :func:`_sub_norm`, :func:`_ring_slot` and
    :func:`_ring_seen`)."""
    import jax.numpy as jnp

    lq1, lk1, lq2, lk2 = rows.astype(jnp.float32)
    return jnp.exp(lq1 @ lk1) - jnp.exp(lq2 @ lk2) + lam0


def _sub_norm(o):
    """The RMS norm of a pair's subtracted context, over the value's width."""
    import jax.numpy as jnp
    from jax import lax

    return o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + _SUB_EPS)


def _ring_slot(pos, window: int):
    """Where a ring of ``window`` slots holds position ``pos``."""
    return pos % window


def _ring_seen(pos, window: int):
    """Which of a ring's slots a query at ``pos`` sees, its own written: a
    slot holds a position once it was written, and then one inside the
    window."""
    import jax.numpy as jnp

    return jnp.arange(window)[None, :] <= pos


def _whole(q, k, v, window: int):
    """:func:`_attend` of whole sequences, the queries the last ``Tq`` of the
    keys' ``Tk`` positions, causal and over the last ``window`` keys a query
    (0: all): a block of ``QUERY_BLOCK`` queries at a time (``lax.map``: the
    scores of one block are held), each over the band of keys it sees where
    that is shorter than the sequence."""
    import jax.numpy as jnp
    from jax import lax

    Tq, Tk = q.shape[1], k.shape[1]
    first = Tk - Tq
    block = min(QUERY_BLOCK, Tq)
    blocks = -(-Tq // block)
    back = -(-max(window - 1, 0) // 128) * 128
    banded = bool(window) and block + back < Tk
    at = jnp.arange(Tk)

    def seen(qpos, kpos):
        ok = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] >= 0)
        if window:
            ok &= qpos[:, None] - kpos[None, :] < window
        return ok

    if blocks == 1 and not banded:
        return _attend(q, k, v, seen(first + jnp.arange(Tq), at))
    q = jnp.pad(q, ((0, 0), (0, blocks * block - Tq)) + ((0, 0),) * 4)
    q = jnp.moveaxis(q.reshape(q.shape[0], blocks, block, *q.shape[2:]), 1, 0)
    if banded:      # the keys in front by ``back`` zeros, behind to the end
        pad = ((0, 0), (back, blocks * block - Tq)) + ((0, 0),) * 3
        k, v = jnp.pad(k, pad[:k.ndim]), jnp.pad(v, pad[:v.ndim])

    def some(args):
        rows, b = args
        qpos = first + b * block + jnp.arange(block)
        if not banded:
            return _attend(rows, k, v, seen(qpos, at))
        kpos = first + b * block - back + jnp.arange(block + back)
        return _attend(
            rows, lax.dynamic_slice_in_dim(k, first + b * block,
                                           block + back, axis=1),
            lax.dynamic_slice_in_dim(v, first + b * block, block + back,
                                     axis=1), seen(qpos, kpos))

    out = lax.map(some, (q, jnp.arange(blocks)))
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape(out.shape[0], blocks * block, *out.shape[3:])[:, :Tq]


def _flashes(sz: Differential, T: int, dtype, forward_only: bool) -> bool:
    """Whether the full layer's whole-sequence pass takes the flash kernel:
    static facts alone.  A decoder's prefill traced for TPUs, a value of 128
    lanes, a length the kernel's blocks tile, from the keys where
    ``parallel/attention.local_impl`` takes it."""
    from ompi_tpu.ops import _chip
    from ompi_tpu.parallel.attention import _FLASH_FROM_KEYS

    if not (forward_only and _chip._traced_for_tpus()
            and 2 * sz.head_dim == 128 and T >= _FLASH_FROM_KEYS):
        return False
    from ompi_tpu.ops.flash_attention import flash_tiles, whole_seq_fits

    return flash_tiles(T, T) and whole_seq_fits(T, 128, dtype)


def _flash(q, k, v):
    """:func:`_attend` of whole sequences, causal, through
    ``ops/flash_attention.py``: every query head against its own K head, both
    padded with zeros to the value's 128 lanes (the scores are the same), and
    its pair's value."""
    import jax.numpy as jnp

    from ompi_tpu.ops.flash_attention import flash_attention

    B, T, G, P, _two, hd = q.shape
    wide = ((0, 0),) * 5 + ((0, hd),)
    q = jnp.pad(q, wide).reshape(B, T, G * P * 2, 2 * hd)
    k = jnp.broadcast_to(jnp.pad(k, wide[1:])[:, :, :, None],
                         (B, T, G, P, 2, 2 * hd)).reshape(q.shape)
    v = jnp.broadcast_to(v[:, :, :, None, None],
                         (B, T, G, P, 2, 2 * hd)).reshape(q.shape)
    out = flash_attention(q, k, v, causal=True, scale=hd ** -0.5)
    return out.reshape(B, T, G, P, 2, 2 * hd).astype(jnp.float32)


def _ring(rows, window: int):
    """Whole sequences' K or V rows (B, T, Hkv, hd) as the ring holds them
    after the last position: position ``p`` at slot ``p mod window``."""
    import jax.numpy as jnp

    T = rows.shape[1]
    if T < window:
        return jnp.pad(rows, ((0, 0), (0, window - T), (0, 0), (0, 0)))
    return jnp.roll(rows[:, T - window:], (T - window) % window, axis=1)


def mixer(cfg, lp, h, carry=None, forward_only: bool = False, source=None):
    """One layer's mixer on the layer's input ``h`` (B, T, D): the norm,
    differential attention of the kind ``lp["sizes"]`` says, and the residual
    add of the branch times the plan's ``branch_factor``.  ``source``: the K
    and V a "shared" row reads, of whole sequences at least as long as ``h``
    (``carry`` None) or the source row's buffers (a cached step).

    ``carry`` None: whole sequences; returns ``(h, k, v)``: a window layer's
    ring as it stands after the last position, the full layer's K and V of
    every position; ``(h,)`` of a shared row.  ``carry = (kc, vc, pos)``, or
    ``(pos,)`` of a shared row: T == 1, position ``pos`` against the layer's
    own buffers, its k and v written first (a ring's at ``pos mod window``);
    returns ``(h, kc, vc)`` or ``(h,)``."""
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.models import transformer as tfm

    sz, lam0, p = lp["sizes"], lp["lam0"], lp["sizes"].prefix
    f32, cdt = jnp.float32, jnp.dtype(cfg.compute_dtype)
    B, T, _ = h.shape
    H, K, hd = sz.n_heads, sz.kv_heads, sz.head_dim
    G, P = K // 2, H // K
    def attending():
        """``attention``, and inside it the kind's own name."""
        stack = contextlib.ExitStack()
        stack.enter_context(scope("attention"))
        if sz.cross or sz.window:
            stack.enter_context(scope(
                "attention.shared" if sz.cross else "attention.window"))
        return stack

    with scope("attn_proj"):
        # the norm is called through the module: a benchmark's control
        # plants a wrong one there while a decoder is traced
        u = tfm._norm(h, lp["ln1"], cfg.norm_eps,
                      lp.get("ln1b")).astype(cdt)
        name = "q" if sz.cross else "qkv"
        qkv = (jnp.einsum("btd,df->btf", u, lp[p + name].astype(cdt))
               + lp[p + name + "b"].astype(cdt))
        q = qkv[..., :H * hd].reshape(B, T, G, P, 2, hd)
        if not sz.cross:
            k = qkv[..., H * hd:(H + K) * hd].reshape(B, T, K, hd)
            v = qkv[..., (H + K) * hd:].reshape(B, T, K, hd)
        lam = _lambda(lp[p + "lam"], lam0)
    if carry is None:
        own = () if sz.cross else (k, v)
        ks, vs = source if sz.cross else own
        pairs = (ks.reshape(*ks.shape[:2], G, 2, hd),
                 vs.reshape(*vs.shape[:2], G, 2 * hd))
        with attending():
            if not sz.cross and not sz.window and _flashes(
                    sz, T, cdt, forward_only):
                a = _flash(q, *pairs)
            else:
                a = _whole(q, *pairs, sz.window)
        if sz.window:
            own = tuple(_ring(rows, sz.window) for rows in own)
    else:
        *own, pos = carry
        if not sz.cross:
            with scope("kv_cache"):
                at = _ring_slot(pos, sz.window) if sz.window else pos
                own = [lax.dynamic_update_slice(
                    buffer, new.astype(buffer.dtype), (0, at, 0, 0))
                    for buffer, new in zip(own, (k, v))]
        ks, vs = source if sz.cross else own
        with attending():
            a = _attend(q, ks.reshape(*ks.shape[:2], G, 2, hd),
                        vs.reshape(*vs.shape[:2], G, 2 * hd),
                        _ring_seen(pos, sz.window) if sz.window
                        else jnp.arange(ks.shape[1])[None, :] <= pos)
    with attending():
        o = a[..., 0, :] - lam * a[..., 1, :]
        o = ((1 - lam0) * _sub_norm(o)
             * lp[p + "sub"].astype(f32)).astype(cdt)
    with scope("attn_proj"):
        s = (jnp.einsum("btf,fd->btd", o.reshape(B, T, H * hd),
                        lp[p + "o"].astype(cdt)) + lp[p + "ob"].astype(cdt))
        if cfg.plan.branch_factor != 1:
            s = s * cfg.plan.branch_factor
        return (h + s, *own)
