"""Flash attention: blockwise online-softmax attention as a pallas kernel.

The framework's densest compute op.  The jnp path in
ompi_tpu.parallel.attention materializes the full (Tq, Tk) score matrix in
HBM; this kernel streams K/V blocks through VMEM and keeps only the
running (max, normalizer, accumulator) per query row — O(Tq·D) memory,
MXU-fed matmuls, no HBM round-trip for the scores.  It is the per-chip
building block under ring/Ulysses sequence parallelism (the ring supplies
one K/V block per hop; this kernel handles the within-block math).

Autodiff: wrapped in jax.custom_vjp; the backward pass recomputes
attention weights in pure XLA from the saved (q, k, v, out, logsumexp)
residuals — the standard flash-attention recompute strategy (no O(T²)
activation storage).

The kernels are always compiled for the TPU: there is no interpret
selection here.  Off-TPU the call fails to lower unless the caller traces
it under ``pltpu.force_tpu_interpret_mode()`` (tests/conftest.py does, for
the virtual CPU mesh).  Shapes that don't tile (T % block != 0) are
rejected; ``parallel.attention.resolve_impl`` is where callers choose
between this kernel and the jnp reference.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ompi_tpu.core.config import VarType, register_var

__all__ = ["flash_attention", "flash_attention_lse", "flash_tiles"]

register_var("ops", "flash_block_q", VarType.INT, 128,
             "flash kernel q-block rows per grid cell (tuning knob; "
             "t_q must tile by it)")
register_var("ops", "flash_block_k", VarType.INT, 128,
             "flash kernel k/v streaming block size (tuning knob; "
             "t_k must tile by it)")
register_var("ops", "flash_bwd_kernel", VarType.BOOL, False,
             "use the pallas backward kernels for flash attention "
             "(recompute-from-lse, O(T·D) memory) instead of the "
             "materialized pure-XLA backward")

_NEG = -1e30

# K and V (in the dk/dv kernel Q and dO) enter a grid cell as ONE
# whole-sequence VMEM block each.  Measured on a v5e (libtpu 0.0.34): 32768
# rows of 128 bf16 compile, forward and both backwards; 65536 rows are
# refused ("Scoped allocation with size 32.xM and limit 16.00M").  Past
# this many bytes per operand the call raises here instead of deep in the
# compiler; re-blocking K/V is ROADMAP S3.
_WHOLE_SEQ_VMEM_BYTES = 8 << 20


def _check_whole_seq_fits(t: int, d: int, dtype, what: str) -> None:
    nbytes = t * d * jnp.dtype(dtype).itemsize
    if nbytes > _WHOLE_SEQ_VMEM_BYTES:
        raise ValueError(
            f"flash_attention: {what} of {t} rows x {d} x {jnp.dtype(dtype)} "
            f"is {nbytes >> 20} MiB, and the kernel holds it in VMEM as one "
            f"block (limit {_WHOLE_SEQ_VMEM_BYTES >> 20} MiB, where the TPU "
            f"compiler refuses it).  Shard the sequence (ring_attention "
            f"over sp) so that each device's share fits.")


def flash_tiles(t_q: int, t_k: int, block_q: int = 128,
                block_k: int = 128) -> bool:
    """True when these sequence lengths tile for :func:`flash_attention`
    (the single source of the tiling rule — callers deciding between the
    kernel and the jnp fallback use this, not a re-derived check)."""
    return (t_q % min(block_q, t_q) == 0 and t_k % min(block_k, t_k) == 0
            and t_q > 0 and t_k > 0)


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                *, scale: float, causal: bool, block_q: int, block_k: int,
                t_k: int):
    """One (batch·head, q-block) grid cell: stream K/V blocks, online
    softmax in float32, write the normalized output + per-row logsumexp
    (lse is laid out (bh, n_q_blocks, block_q) so its last dim is a full
    128 lane tile — the TPU lowering disallows a (1, block_q) block).

    Matmul inputs stay in the storage dtype (bf16 feeds the MXU natively;
    bf16 values are exactly representable in f32, so bf16×bf16→f32 equals
    the f32 product) with float32 accumulation via preferred_element_type.
    """
    from jax import lax
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    q = q_ref[0]                                             # (bq, D)
    d = q.shape[-1]
    qpos = (qoff_ref[0] + iq * block_q
            + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0))

    def body(j, carry):
        m, l, acc = carry
        ks = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k_blk = k_ref[0, ks, :]                              # (bk, D)
        v_blk = v_ref[0, ks, :]
        s = jax.lax.dot_general(                             # (bq, bk)
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            kpos = (koff_ref[0] + j * block_k
                    + lax.broadcasted_iota(jnp.int32,
                                           (block_q, block_k), 1))
            s = jnp.where(qpos >= kpos, s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        if causal:
            p = jnp.where(qpos >= kpos, p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        # p→storage dtype for the MXU; accumulation stays f32
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q,), _NEG, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m, l, acc = lax.fori_loop(0, t_k // block_k, body, (m0, l0, acc0))
    safe_l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / safe_l[:, None]).astype(o_ref.dtype)
    # lse broadcast over 8 sublanes: the TPU lowering needs the block's
    # last two dims (8, block_q)-tileable; callers read sublane 0
    lse_ref[0, 0] = jnp.broadcast_to((m + jnp.log(safe_l))[None, :],
                                     (8, block_q))


def _flash_fwd_raw(q3, k3, v3, q_offset, k_offset, scale: float,
                   causal: bool, block_q: int, block_k: int):
    """(BH, Tq, D) × (BH, Tk, D) → ((BH, Tq, D), (BH, Tq) lse f32)."""
    from jax.experimental import pallas as pl

    bh, t_q, d = q3.shape
    t_k = k3.shape[1]
    nq = t_q // block_q
    grid = (bh, nq)
    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, t_k=t_k)
    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1)
    koff = jnp.asarray(k_offset, jnp.int32).reshape(1)
    o3, lse3 = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=_smem()),
            pl.BlockSpec(memory_space=_smem()),
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, t_k, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, t_k, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, 8, block_q), lambda b, i: (b, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_q, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, nq, 8, block_q), jnp.float32),
        ],
        name="flash_fwd",
    )(qoff, koff, q3, k3, v3)
    return o3, lse3[:, :, 0, :].reshape(bh, t_q)


def _smem():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.SMEM


# ---------------------------------------------------------------------------
# backward kernels (opt-in: --mca ops flash_bwd_kernel 1)
#
# The pure-XLA backward materializes (B,H,Tq,Tk) f32 score/weight tensors —
# HBM-bound at scale.  These kernels recompute p blockwise from the saved
# lse (the standard flash strategy): dq streams k/v blocks per q block;
# dk/dv streams q/g blocks per k block.  delta' = rowsum(g·out) − g_lse is
# precomputed in XLA (cheap elementwise) and folds the lse cotangent into
# the same ds term.
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, g_ref, lse_ref,
                   dm_ref, dq_ref, *, scale: float, causal: bool,
                   block_q: int, block_k: int, t_k: int):
    from jax import lax
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    q = q_ref[0]                                             # (bq, D)
    g = g_ref[0]
    # lse/dm ride the forward's (…, 8, block_q) sublane-broadcast layout
    # (a (block_q, 1) trailing-dim block does not lower on TPU); read
    # sublane 0
    lse = lse_ref[0, 0, 0]                                   # (bq,)
    dm = dm_ref[0, 0, 0]                                     # (bq,)
    qpos = (qoff_ref[0] + iq * block_q
            + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0))

    def body(j, acc):
        ks = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k_blk = k_ref[0, ks, :]
        v_blk = v_ref[0, ks, :]
        s = lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            kpos = (koff_ref[0] + j * block_k
                    + lax.broadcasted_iota(jnp.int32,
                                           (block_q, block_k), 1))
            s = jnp.where(qpos >= kpos, s, _NEG)
        p = jnp.exp(s - lse[:, None])                        # (bq, bk)
        if causal:
            p = jnp.where(qpos >= kpos, p, 0.0)
        dp = lax.dot_general(g, v_blk, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - dm[:, None]) * scale).astype(q.dtype)
        return acc + lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    acc0 = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    dq = lax.fori_loop(0, t_k // block_k, body, acc0)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, g_ref,
                    lse_ref, dm_ref, dk_ref, dv_ref, *, scale: float,
                    causal: bool, block_q: int, block_k: int, t_q: int):
    from jax import lax
    from jax.experimental import pallas as pl

    jk = pl.program_id(1)
    k_blk = k_ref[0]                                         # (bk, D)
    v_blk = v_ref[0]
    kpos = (koff_ref[0] + jk * block_k
            + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))

    def body(i, carry):
        dk, dv = carry
        qs = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        q = q_ref[0, qs, :]                                  # (bq, D)
        g = g_ref[0, qs, :]
        lse = lse_ref[0, i, 0]                               # (bq,)
        dm = dm_ref[0, i, 0]
        s = lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = (qoff_ref[0] + i * block_q
                    + lax.broadcasted_iota(jnp.int32,
                                           (block_q, block_k), 0))
            s = jnp.where(qpos >= kpos, s, _NEG)
        p = jnp.exp(s - lse[:, None])
        if causal:
            p = jnp.where(qpos >= kpos, p, 0.0)
        pc = p.astype(g.dtype)
        dv = dv + lax.dot_general(pc, g, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dp = lax.dot_general(g, v_blk, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - dm[:, None]) * scale).astype(q.dtype)
        dk = dk + lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return dk, dv

    d = k_blk.shape[-1]
    z = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = lax.fori_loop(0, t_q // block_q, body, (z, z))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_raw(q3, k3, v3, g3, lse3, dm3, qoff, koff, scale: float,
                   causal: bool, block_q: int, block_k: int):
    """(BH,·,D) inputs → (dq3, dk3, dv3)."""
    from jax.experimental import pallas as pl

    bh, t_q, d = q3.shape
    t_k = k3.shape[1]
    nq = t_q // block_q
    # same layout the forward emits: (bh, nq, 8, block_q) with the value
    # broadcast over the 8 sublanes — the last two block dims form a full
    # (8, block_q) tile, which the TPU lowering accepts (a trailing-dim-1
    # block does not lower; ADVICE r3)
    lse_c = jnp.broadcast_to(lse3.reshape(bh, nq, 1, block_q),
                             (bh, nq, 8, block_q))
    dm_c = jnp.broadcast_to(dm3.reshape(bh, nq, 1, block_q),
                            (bh, nq, 8, block_q))
    row = [
        pl.BlockSpec(memory_space=_smem()),
        pl.BlockSpec(memory_space=_smem()),
    ]
    dq3 = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, t_k=t_k),
        grid=(bh, t_q // block_q),
        in_specs=row + [
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),   # q
            pl.BlockSpec((1, t_k, d), lambda b, i: (b, 0, 0)),       # k
            pl.BlockSpec((1, t_k, d), lambda b, i: (b, 0, 0)),       # v
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),   # g
            pl.BlockSpec((1, 1, 8, block_q),
                         lambda b, i: (b, i, 0, 0)),                 # lse
            pl.BlockSpec((1, 1, 8, block_q),
                         lambda b, i: (b, i, 0, 0)),                 # dm
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t_q, d), q3.dtype),
        name="flash_bwd_dq",
    )(qoff, koff, q3, k3, v3, g3, lse_c, dm_c)
    dk3, dv3 = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, t_q=t_q),
        grid=(bh, t_k // block_k),
        in_specs=row + [
            pl.BlockSpec((1, t_q, d), lambda b, j: (b, 0, 0)),       # q
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),   # k
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),   # v
            pl.BlockSpec((1, t_q, d), lambda b, j: (b, 0, 0)),       # g
            pl.BlockSpec((1, nq, 8, block_q),
                         lambda b, j: (b, 0, 0, 0)),                 # lse
            pl.BlockSpec((1, nq, 8, block_q),
                         lambda b, j: (b, 0, 0, 0)),                 # dm
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_k, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, t_k, d), v3.dtype),
        ],
        name="flash_bwd_dkv",
    )(qoff, koff, q3, k3, v3, g3, lse_c, dm_c)
    return dq3, dk3, dv3


def _bwd_kernel_wanted() -> bool:
    from ompi_tpu.core.config import var_registry

    return bool(var_registry.get("ops_flash_bwd_kernel"))


# ---------------------------------------------------------------------------
# public op with recompute backward
# ---------------------------------------------------------------------------

def _to3(x):
    """(B, T, H, D) → (B·H, T, D)."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _from3(x3, b, h):
    bh, t, d = x3.shape
    return x3.reshape(b, h, t, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash(q, k, v, qoff, koff, scale, causal, blocks):
    return _flash_core(q, k, v, qoff, koff, scale, causal, blocks)


def _flash_core(q, k, v, qoff, koff, scale, causal, blocks):
    b, t_q, h, d = q.shape
    block_q, block_k = blocks
    o3, lse3 = _flash_fwd_raw(_to3(q), _to3(k), _to3(v), qoff, koff,
                              scale, causal, block_q, block_k)
    return _from3(o3, b, h), lse3.reshape(b, h, t_q)


def _flash_fwd(q, k, v, qoff, koff, scale, causal, blocks):
    out, lse = _flash_core(q, k, v, qoff, koff, scale, causal, blocks)
    return (out, lse), (q, k, v, qoff, koff, out, lse)


def _flash_bwd(scale, causal, blocks, res, cts):
    """Backward via recompute.  Default: pure XLA (rebuild s + logsumexp —
    same bf16 matmul inputs with f32 accumulation, so the weights match
    the forward exactly) with the lse cotangent folded into ds
    (d lse/d s = p).  With ``--mca ops flash_bwd_kernel 1``: the pallas
    dq and dk/dv kernels recompute p blockwise from the SAVED lse —
    O(T·D) memory instead of materialized (B,H,Tq,Tk) tensors."""
    q, k, v, qoff, koff, out, lse = res
    g, g_lse = cts
    zoff = np.zeros((1,), dtype=jax.dtypes.float0)  # int args: no tangent
    b, t_q, h, d = q.shape
    if _bwd_kernel_wanted():
        _check_whole_seq_fits(t_q, d, q.dtype, "Q/dO in the dk/dv kernel")
        block_q, block_k = blocks
        f32 = jnp.float32
        g3, o3, q3 = _to3(g), _to3(out), _to3(q)
        delta = jnp.sum(g3.astype(f32) * o3.astype(f32), axis=-1)  # (BH,T)
        dm = delta
        if g_lse is not None:
            # fold the lse cotangent: ds = p·(dp − (delta − g_lse))·scale
            dm = delta - g_lse.reshape(b * h, t_q).astype(f32)
        dq3, dk3, dv3 = _flash_bwd_raw(
            q3, _to3(k), _to3(v), g3, lse.reshape(b * h, t_q), dm,
            qoff, koff, scale, causal, block_q, block_k)
        return (_from3(dq3, b, h), _from3(dk3, b, h), _from3(dv3, b, h),
                zoff, zoff)
    f32 = jnp.float32
    gf32 = g.astype(f32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=f32) * scale
    if causal:
        qpos = qoff + jnp.arange(t_q)
        kpos = koff + jnp.arange(k.shape[1])
        keep = (qpos[:, None] >= kpos[None, :])[None, None]
        s = jnp.where(keep, s, _NEG)
    m = s.max(axis=-1, keepdims=True)
    l = jnp.sum(jnp.exp(s - m), axis=-1, keepdims=True)
    p = jnp.exp(s - m) / jnp.maximum(l, 1e-30)       # fwd weights
    if causal:
        p = jnp.where(keep, p, 0.0)
    pc = p.astype(q.dtype)
    dv = jnp.einsum("bhqk,bqhd->bkhd", pc, g, preferred_element_type=f32)
    dp = jnp.einsum("bqhd,bkhd->bhqk", g, v, preferred_element_type=f32)
    delta = jnp.einsum("bqhd,bqhd->bqh", gf32,
                       out.astype(f32)).transpose(0, 2, 1)
    resid = dp - delta[..., None]
    if g_lse is not None:
        resid = resid + g_lse.astype(f32)[..., None]  # (B,H,Tq,1)
    ds = (p * resid * scale).astype(q.dtype)
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, k, preferred_element_type=f32)
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q, preferred_element_type=f32)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            zoff, zoff)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _check_blocks(q, k, block_q, block_k):
    t_q, t_k = q.shape[1], k.shape[1]
    if not flash_tiles(t_q, t_k, block_q, block_k):
        raise ValueError(
            f"flash_attention: T ({t_q},{t_k}) must tile by blocks "
            f"({block_q},{block_k})")
    _check_whole_seq_fits(t_k, k.shape[-1], k.dtype, "K/V")
    return min(block_q, t_q), min(block_k, t_k)


def flash_attention(q, k, v, causal: bool = True,
                    q_offset=0, k_offset=0,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128):
    """Blockwise-streamed exact attention (pallas; MXU matmuls, O(T·D)
    memory).  Same contract as parallel.attention.local_attention:
    q (B, Tq, H, D), k/v (B, Tk, H, D) → (B, Tq, H, D); offsets give
    global positions for causal masking of sequence slices and may be
    **traced** int32 scalars (the ring-attention hop index feeds one in).

    Shapes must tile (Tq % block_q == 0, Tk % block_k == 0) — callers
    (local_attention) fall back to the jnp path otherwise.
    """
    out, _ = flash_attention_lse(q, k, v, causal=causal, q_offset=q_offset,
                                 k_offset=k_offset, scale=scale,
                                 block_q=block_q, block_k=block_k)
    return out


def flash_attention_lse(q, k, v, causal: bool = True,
                        q_offset=0, k_offset=0,
                        scale: Optional[float] = None,
                        block_q: int = 128, block_k: int = 128):
    """:func:`flash_attention` that also returns the per-row logsumexp
    ((B, H, Tq) float32) — the merge state ring attention needs to combine
    this block's contribution with other hops' (≈ the reference's segmented
    ring allreduce partial, coll_base_allreduce.c:615)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    block_q, block_k = _check_blocks(q, k, block_q, block_k)
    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1)
    koff = jnp.asarray(k_offset, jnp.int32).reshape(1)
    return _flash(q, k, v, qoff, koff, float(scale), bool(causal),
                  (block_q, block_k))
