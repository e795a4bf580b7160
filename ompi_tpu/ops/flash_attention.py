"""Flash attention: blockwise online-softmax attention as pallas kernels,
forward and backward.

The framework's densest compute op.  The jnp path in
ompi_tpu.parallel.attention materializes the full (Tq, Tk) score matrix in
HBM; these kernels keep a (block_q, block_k) tile of scores in VMEM, stream
K/V blocks past it and hold only the running (max, normalizer, accumulator)
per query row: O(T·D) memory, MXU-fed matmuls, no HBM round-trip for the
scores in either pass.  A causal call visits only the blocks at or below the
diagonal and masks only the blocks the diagonal crosses.  It is the per-chip
building block under ring/Ulysses sequence parallelism (the ring supplies
one K/V block per hop; this kernel handles the within-block math).

Autodiff: ``jax.custom_vjp``.  The backward is two kernels that rebuild the
weights blockwise from the saved logsumexp (the standard flash strategy):
``flash_bwd_dq`` streams K/V blocks per q block, ``flash_bwd_dkv`` streams
q/dO blocks per k block.  ``out`` and ``lse`` carry checkpoint names
(``RESIDUAL_NAMES``) so that a remat policy can keep them and the forward
kernel does not run again in the backward pass.

Block sizes are chosen here from the lengths (``_block``), not by the
caller.  The kernels are always compiled for the TPU: there is no interpret
selection here.  Off-TPU the call fails to lower unless the caller traces
it under ``pltpu.force_tpu_interpret_mode()`` (tests/conftest.py does, for
the virtual CPU mesh).  Lengths no block divides are rejected;
``parallel.attention.local_impl`` is where callers choose between this
kernel and the jnp reference.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["flash_attention", "flash_attention_lse", "flash_tiles",
           "whole_seq_fits", "RESIDUAL_NAMES"]

_NEG = -1e30

# ``jax.ad_checkpoint.checkpoint_name`` tags of the forward's two results:
# a policy that saves them spares the backward pass a second forward kernel.
RESIDUAL_NAMES = ("flash_out", "flash_lse")

# K and V (in the dk/dv kernel Q and dO) enter a grid cell as ONE
# whole-sequence VMEM block each.  Measured on a v5e (libtpu 0.0.34): 32768
# rows of 128 bf16 compile, forward and both backwards; 65536 rows are
# refused.  Past this many bytes per operand the call raises here instead
# of deep in the compiler; re-blocking K/V is ROADMAP S3.
_WHOLE_SEQ_VMEM_BYTES = 8 << 20

# What a kernel may take of the v5e's 128 MiB of VMEM: two whole-sequence
# operands at the limit above, double buffered, are 32 MiB; the score tiles
# of the largest blocks a few more.
_VMEM_LIMIT_BYTES = 64 << 20

# The largest block (rows of q, and of k) the three kernels take; a length
# takes the largest power of two from here down to 128 that divides it.
# Measured on a v5e at (B, T, H, D) = (8, 2048, 16, 128), causal, every pair
# of {128, 256, 512, 1024}: 512 x 512 is the fastest for each kernel alone
# (forward 2.33 ms, dq 2.05, dk/dv 2.60; 128 x 128 takes 6.95, 6.24, 6.14 and
# 1024 x 1024 2.57, 2.20, 2.68; PERF.md section 6, PR 28, chip call 26); a
# tile of float32 scores is then 1 MiB of VMEM.
_BLOCK_CAP = 512


def whole_seq_fits(t: int, d: int, dtype) -> bool:
    """True when a sequence of ``t`` rows of ``d`` fits the kernels'
    whole-sequence VMEM block."""
    return t * d * jnp.dtype(dtype).itemsize <= _WHOLE_SEQ_VMEM_BYTES


def _check_whole_seq_fits(t: int, d: int, dtype, what: str) -> None:
    if not whole_seq_fits(t, d, dtype):
        nbytes = t * d * jnp.dtype(dtype).itemsize
        raise ValueError(
            f"flash_attention: {what} of {t} rows x {d} x {jnp.dtype(dtype)} "
            f"is {nbytes >> 20} MiB, and the kernel holds it in VMEM as one "
            f"block (limit {_WHOLE_SEQ_VMEM_BYTES >> 20} MiB, where the TPU "
            f"compiler refuses it).  Shard the sequence (ring_attention "
            f"over sp) so that each device's share fits.")


def _block(t: int) -> int:
    """Rows a block of a sequence of ``t``: ``t`` itself up to 128 (one
    block), else the largest of ``_BLOCK_CAP``, half of it … 128 that
    divides it; 0 when none does."""
    if 0 < t <= 128:
        return t
    b = _BLOCK_CAP
    while b >= 128:
        if t > 0 and t % b == 0:
            return b
        b //= 2
    return 0


def flash_tiles(t_q: int, t_k: int) -> bool:
    """True when these sequence lengths tile for :func:`flash_attention`
    (the single source of the tiling rule — callers deciding between the
    kernel and the jnp fallback use this, not a re-derived check)."""
    return _block(t_q) > 0 and _block(t_k) > 0


# ---------------------------------------------------------------------------
# kernels
#
# Every operand is (B', T, H'·D): a grid cell is (batch, head, block) and a
# BlockSpec of D lanes picks the head (``_fold``).  lse and dm (= delta −
# the lse cotangent) are (B', H', 1, T) float32: a row of lanes, written
# once.  The two offsets are SMEM scalars and may be traced; the loops'
# bounds are computed from them in the kernel.
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))      # a · bᵀ
_NN = (((1,), (0,)), ((), ()))      # a · b


def _floor_div_pos(x, b: int):
    """floor(x / b) for x >= 0, and 0 below."""
    from jax import lax

    return lax.div(jnp.maximum(x, 0), jnp.int32(b))


def _positions(rows_first, cols_first, n_rows: int, n_cols: int):
    """Global positions down the rows and across the columns of a tile."""
    from jax import lax

    rpos = rows_first + lax.broadcasted_iota(jnp.int32, (n_rows, n_cols), 0)
    cpos = cols_first + lax.broadcasted_iota(jnp.int32, (n_rows, n_cols), 1)
    return rpos, cpos


def _k_block_bounds(rel, block_q: int, block_k: int, nk: int):
    """k blocks a q block visits, whose first row is ``rel`` positions past
    the first key: [0, full) wholly visible, [full, end) crossed by the
    diagonal, the rest above it."""
    full = jnp.minimum(_floor_div_pos(rel + 1, block_k), nk)
    end = jnp.minimum(_floor_div_pos(rel + block_q - 1 + block_k, block_k),
                      nk)
    return full, jnp.maximum(end, full)


def _q_block_bounds(rel, block_q: int, block_k: int, nq: int):
    """q blocks a k block visits, whose first key is ``rel`` positions past
    the first query row: [0, start) wholly above the diagonal, [start, full)
    crossed by it, [full, nq) wholly visible."""
    start = jnp.minimum(_floor_div_pos(rel, block_q), nq)
    full = jnp.minimum(_floor_div_pos(rel + block_k + block_q - 2, block_q),
                       nq)
    return start, jnp.maximum(full, start)


def _fwd_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                *, scale: float, causal: bool, block_q: int, block_k: int):
    """One (batch, head, q-block) grid cell: stream K/V blocks, online
    softmax in float32, write the normalized output + per-row logsumexp.

    Matmul inputs stay in the storage dtype (bf16 feeds the MXU natively;
    bf16 values are exactly representable in f32, so bf16×bf16→f32 equals
    the f32 product) with float32 accumulation via preferred_element_type.
    """
    from jax import lax
    from ompi_tpu.ops._pallas import pl

    iq = pl.program_id(2)
    q = q_ref[0]                                             # (bq, D)
    d = q.shape[-1]
    nk = k_ref.shape[1] // block_k
    q_first = qoff_ref[0] + iq * block_q

    def step(j, carry, masked):
        m, l, acc = carry
        ks = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k_blk = k_ref[0, ks, :]                              # (bk, D)
        v_blk = v_ref[0, ks, :]
        s = lax.dot_general(q, k_blk, _NT,
                            preferred_element_type=jnp.float32) * scale
        if masked:
            qpos, kpos = _positions(q_first, koff_ref[0] + j * block_k,
                                  block_q, block_k)
            # -inf under a finite running max: exp gives an exact 0 and a
            # row with no visible key keeps l = 0
            s = jnp.where(qpos >= kpos, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        # p→storage dtype for the MXU; accumulation stays f32
        acc_new = acc * corr[:, None] + lax.dot_general(
            p.astype(v_blk.dtype), v_blk, _NN,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    carry = (jnp.full((block_q,), _NEG, jnp.float32),
             jnp.zeros((block_q,), jnp.float32),
             jnp.zeros((block_q, d), jnp.float32))
    if causal:
        full, end = _k_block_bounds(q_first - koff_ref[0], block_q, block_k,
                                    nk)
        carry = lax.fori_loop(0, full,
                              functools.partial(step, masked=False), carry)
        carry = lax.fori_loop(full, end,
                              functools.partial(step, masked=True), carry)
    else:
        carry = lax.fori_loop(0, nk, functools.partial(step, masked=False),
                              carry)
    m, l, acc = carry
    safe_l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / safe_l[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log(safe_l))[None, :]


def _bwd_dq_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, g_ref, lse_ref,
                   dm_ref, dq_ref, *, scale: float, causal: bool,
                   block_q: int, block_k: int):
    """One (batch, head, q-block) cell: dq = scale · Σ_j ds_j · k_j with
    ds = p · (dO·vᵀ − dm), p rebuilt from the saved lse."""
    from jax import lax
    from ompi_tpu.ops._pallas import pl

    iq = pl.program_id(2)
    q = q_ref[0]                                             # (bq, D)
    g = g_ref[0]
    nk = k_ref.shape[1] // block_k
    lse = lse_ref[0, 0, 0][:, None]                          # (bq, 1)
    dm = dm_ref[0, 0, 0][:, None]
    q_first = qoff_ref[0] + iq * block_q

    def step(j, acc, masked):
        ks = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k_blk = k_ref[0, ks, :]
        v_blk = v_ref[0, ks, :]
        s = lax.dot_general(q, k_blk, _NT,
                            preferred_element_type=jnp.float32) * scale
        if masked:
            qpos, kpos = _positions(q_first, koff_ref[0] + j * block_k,
                                  block_q, block_k)
            s = jnp.where(qpos >= kpos, s, -jnp.inf)
        p = jnp.exp(s - lse)                                 # (bq, bk)
        dp = lax.dot_general(g, v_blk, _NT,
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - dm)).astype(q.dtype)
        return acc + lax.dot_general(ds, k_blk, _NN,
                                     preferred_element_type=jnp.float32)

    acc = jnp.zeros(q.shape, jnp.float32)
    if causal:
        full, end = _k_block_bounds(q_first - koff_ref[0], block_q, block_k,
                                    nk)
        acc = lax.fori_loop(0, full, functools.partial(step, masked=False),
                            acc)
        acc = lax.fori_loop(full, end, functools.partial(step, masked=True),
                            acc)
    else:
        acc = lax.fori_loop(0, nk, functools.partial(step, masked=False),
                            acc)
    dq_ref[0] = (acc * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, g_ref,
                    lse_ref, dm_ref, dk_ref, dv_ref, *, scale: float,
                    causal: bool, block_q: int, block_k: int):
    """One (batch, head, k-block) cell, on transposed tiles (keys down,
    queries across) so that lse and dm broadcast along lanes and no matmul
    transposes its left operand: dv = Σ_i pᵀ·dO, dk = scale · Σ_i dsᵀ·q."""
    from jax import lax
    from ompi_tpu.ops._pallas import pl

    jk = pl.program_id(2)
    k_blk = k_ref[0]                                         # (bk, D)
    v_blk = v_ref[0]
    nq = q_ref.shape[1] // block_q
    k_first = koff_ref[0] + jk * block_k

    def step(i, carry, masked):
        dk, dv = carry
        qs = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        q = q_ref[0, qs, :]                                  # (bq, D)
        g = g_ref[0, qs, :]
        lse = lse_ref[0, 0, :, qs]                           # (1, bq)
        dm = dm_ref[0, 0, :, qs]
        st = lax.dot_general(k_blk, q, _NT,                  # (bk, bq)
                             preferred_element_type=jnp.float32) * scale
        if masked:
            kpos, qpos = _positions(k_first, qoff_ref[0] + i * block_q,
                                  block_k, block_q)
            st = jnp.where(qpos >= kpos, st, -jnp.inf)
        pt = jnp.exp(st - lse)
        dv = dv + lax.dot_general(pt.astype(g.dtype), g, _NN,
                                  preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v_blk, g, _NT,
                              preferred_element_type=jnp.float32)
        dst = (pt * (dpt - dm)).astype(q.dtype)
        dk = dk + lax.dot_general(dst, q, _NN,
                                  preferred_element_type=jnp.float32)
        return dk, dv

    z = jnp.zeros(k_blk.shape, jnp.float32)
    if causal:
        start, full = _q_block_bounds(k_first - qoff_ref[0], block_q,
                                      block_k, nq)
        carry = lax.fori_loop(start, full,
                              functools.partial(step, masked=True), (z, z))
        carry = lax.fori_loop(full, nq,
                              functools.partial(step, masked=False), carry)
    else:
        carry = lax.fori_loop(0, nq, functools.partial(step, masked=False),
                              (z, z))
    dk, dv = carry
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _specs(d: int):
    """BlockSpecs over (B', T, H'·D) operands and (B', H', 1, T) rows for a
    grid of (batch, head, block): one block of a sequence, a whole one."""
    from ompi_tpu.ops._pallas import pl
    from ompi_tpu.ops._pallas import pltpu

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    def blk(rows):
        return pl.BlockSpec((1, rows, d), lambda b, h, i: (b, i, h))

    def whole(rows):
        return pl.BlockSpec((1, rows, d), lambda b, h, i: (b, 0, h))

    def row_blk(cols):
        return pl.BlockSpec((1, 1, 1, cols), lambda b, h, i: (b, h, 0, i))

    def row_whole(cols):
        return pl.BlockSpec((1, 1, 1, cols), lambda b, h, i: (b, h, 0, 0))

    return smem, blk, whole, row_blk, row_whole


def _params():
    from ompi_tpu.ops._pallas import pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


# jitted: a decoder's programs and a remat's two traces share one trace of the
# kernel a shape (the backward kernels have one call site a program)
@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _flash_fwd_raw(q3, k3, v3, qoff, koff, heads: int, scale: float,
                   causal: bool, blocks: tuple[int, int]):
    """(B', Tq, H'·D) × (B', Tk, H'·D) → (out like q3, lse (B', H', 1, Tq)
    float32)."""
    from ompi_tpu.ops._pallas import pallas_call

    b, t_q, hd = q3.shape
    t_k, d = k3.shape[1], hd // heads
    block_q, block_k = blocks
    smem, blk, whole, row_blk, _ = _specs(d)
    return pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(b, heads, t_q // block_q),
        in_specs=[smem, smem, blk(block_q), whole(t_k), whole(t_k)],
        out_specs=[blk(block_q), row_blk(block_q)],
        out_shape=[jax.ShapeDtypeStruct(q3.shape, q3.dtype),
                   jax.ShapeDtypeStruct((b, heads, 1, t_q), jnp.float32)],
        compiler_params=_params(),
        name="flash_fwd",
    )(qoff, koff, q3, k3, v3)


def _flash_bwd_raw(q3, k3, v3, g3, lse4, dm4, qoff, koff, heads: int,
                   scale: float, causal: bool, blocks: tuple[int, int]):
    """(B', ·, H'·D) operands, (B', H', 1, Tq) rows → (dq3, dk3, dv3)."""
    from ompi_tpu.ops._pallas import pallas_call

    b, t_q, hd = q3.shape
    t_k, d = k3.shape[1], hd // heads
    smem, blk, whole, row_blk, row_whole = _specs(d)
    block_q, block_k = blocks
    dq3 = pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(b, heads, t_q // block_q),
        in_specs=[smem, smem, blk(block_q), whole(t_k), whole(t_k),
                  blk(block_q), row_blk(block_q), row_blk(block_q)],
        out_specs=blk(block_q),
        out_shape=jax.ShapeDtypeStruct(q3.shape, q3.dtype),
        compiler_params=_params(),
        name="flash_bwd_dq",
    )(qoff, koff, q3, k3, v3, g3, lse4, dm4)
    dk3, dv3 = pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(b, heads, t_k // block_k),
        in_specs=[smem, smem, whole(t_q), blk(block_k), blk(block_k),
                  whole(t_q), row_whole(t_q), row_whole(t_q)],
        out_specs=[blk(block_k), blk(block_k)],
        out_shape=[jax.ShapeDtypeStruct(k3.shape, k3.dtype),
                   jax.ShapeDtypeStruct(v3.shape, v3.dtype)],
        compiler_params=_params(),
        name="flash_bwd_dkv",
    )(qoff, koff, q3, k3, v3, g3, lse4, dm4)
    return dq3, dk3, dv3


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

def _fold(x):
    """(B, T, H, D) → ((B', T, H'·D), H') with B'·H' = B·H in that order.
    Where a head is whole lane tiles (D % 128 == 0) this is a reshape and
    the kernels' BlockSpecs pick the head out of (B, T, H·D) in place;
    narrower heads are transposed to (B·H, T, D) through HBM."""
    b, t, h, d = x.shape
    if d % 128 == 0 or h == 1:
        return x.reshape(b, t, h * d), h
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d), 1


def _unfold(x3, b: int, h: int):
    t = x3.shape[1]
    if x3.shape[0] == b:
        return x3.reshape(b, t, h, -1)
    return x3.reshape(b, h, t, -1).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash(q, k, v, qoff, koff, scale, causal):
    return _flash_core(q, k, v, qoff, koff, scale, causal)


def _flash_core(q, k, v, qoff, koff, scale, causal):
    b, t_q, h, _ = q.shape
    q3, heads = _fold(q)
    o3, lse4 = _flash_fwd_raw(q3, _fold(k)[0], _fold(v)[0], qoff, koff,
                              heads, scale, causal,
                              (_block(t_q), _block(k.shape[1])))
    return _unfold(o3, b, h), lse4.reshape(b, h, t_q)


def _flash_fwd(q, k, v, qoff, koff, scale, causal):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _flash_core(q, k, v, qoff, koff, scale, causal)
    out = checkpoint_name(out, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    return (out, lse), (q, k, v, qoff, koff, out, lse)


def _flash_bwd(scale, causal, res, cts):
    """Backward via recompute: the dq and dk/dv kernels rebuild p blockwise
    from the SAVED lse, O(T·D) memory.  delta = rowsum(dO·out) is
    precomputed in XLA (one elementwise pass) and the lse cotangent folded
    into it (d lse/d s = p, so ds = p·(dp − (delta − g_lse)))."""
    q, k, v, qoff, koff, out, lse = res
    g, g_lse = cts
    zoff = np.zeros((1,), dtype=jax.dtypes.float0)  # int args: no tangent
    b, t_q, h, d = q.shape
    t_k = k.shape[1]
    _check_whole_seq_fits(t_q, d, q.dtype, "Q/dO in the dk/dv kernel")
    f32 = jnp.float32
    dm = jnp.einsum("bthd,bthd->bht", g.astype(f32), out.astype(f32))
    if g_lse is not None:
        dm = dm - g_lse.astype(f32)
    q3, heads = _fold(q)
    rows = (q3.shape[0], heads, 1, t_q)
    dq3, dk3, dv3 = _flash_bwd_raw(
        q3, _fold(k)[0], _fold(v)[0], _fold(g)[0], lse.reshape(rows),
        dm.reshape(rows), qoff, koff, heads, scale, causal,
        (_block(t_q), _block(t_k)))
    return (_unfold(dq3, b, h), _unfold(dk3, b, h), _unfold(dv3, b, h),
            zoff, zoff)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = True,
                    q_offset=0, k_offset=0,
                    scale: Optional[float] = None):
    """Blockwise-streamed exact attention (pallas; MXU matmuls, O(T·D)
    memory).  Same contract as parallel.attention.local_attention:
    q (B, Tq, H, D), k/v (B, Tk, H, D) → (B, Tq, H, D); offsets give
    global positions for causal masking of sequence slices and may be
    **traced** int32 scalars (the ring-attention hop index feeds one in).

    The lengths must tile (:func:`flash_tiles`) — callers
    (local_attention) fall back to the jnp path otherwise.
    """
    out, _ = flash_attention_lse(q, k, v, causal=causal, q_offset=q_offset,
                                 k_offset=k_offset, scale=scale)
    return out


def flash_attention_lse(q, k, v, causal: bool = True,
                        q_offset=0, k_offset=0,
                        scale: Optional[float] = None):
    """:func:`flash_attention` that also returns the per-row logsumexp
    ((B, H, Tq) float32) — the merge state ring attention needs to combine
    this block's contribution with other hops' (≈ the reference's segmented
    ring allreduce partial, coll_base_allreduce.c:615)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    t_q, t_k = q.shape[1], k.shape[1]
    if not flash_tiles(t_q, t_k):
        raise ValueError(
            f"flash_attention: no block of 128 rows or more divides the "
            f"sequence lengths ({t_q}, {t_k})")
    _check_whole_seq_fits(t_k, k.shape[-1], k.dtype, "K/V")
    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1)
    koff = jnp.asarray(k_offset, jnp.int32).reshape(1)
    return _flash(q, k, v, qoff, koff, float(scale), bool(causal))
