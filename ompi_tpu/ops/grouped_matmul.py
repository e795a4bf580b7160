"""Grouped matmul: row tiles that each multiply one matrix of a stack,
chosen at run time — the matmul under dropless mixture-of-experts routing
(``parallel/moe.routed_moe``).

``rows`` is ``(n_tiles · tm, K)`` and ``w`` is ``(G, K, N)``; tile ``t`` (the
rows ``t·tm .. (t+1)·tm``) multiplies ``w[tile_group[t]]``.  The caller lays
the rows out so that a tile never straddles two groups (a group's rows
start at a tile boundary and its last tile is filled up with rows nobody
reads back), which is what keeps the kernel this small: no masks, no tile
visited twice, the group of a tile one scalar read from SMEM by the index
map of the weights' block.  Tiles from ``tiles_used`` on hold no row of any
group; they are written as zeros and multiply nothing.

The weights' block (``weight_block``, from static shapes alone) is chosen
by the bytes a row tile moves.  Where the kernel's whole working set fits
its VMEM budget (``_VMEM_BUDGET_BYTES``), a whole ``(K, N)`` matrix is one
block, at any rows a tile: its index ``(tile_group[t], 0, 0)`` stays put
while the group does (``tile_group`` is non-decreasing), so consecutive
tiles of one group, and all the unused tiles after them, fetch it once, and
every tile reads its rows once.  Up to 128 rows a tile that is what bounds
the call (128 operations a byte of bfloat16 weights against the v5e's ridge
of 240).  A matrix too large for that keeps its whole ``K`` and takes the
widest block of ``N`` that fits: the weights' index then changes at every
grid step and *every row tile reads its group's whole matrix again*, the
used ones (a tile past ``tiles_used`` names the block that the step before
it named and copies nothing: where a device holds a few of a wide router's
experts, nearly every tile is one), which a tile of 256 or 512 rows
amortises; but the rows' index ``(t, 0)`` stays
put under every block of ``N``, so the pipeline copies a tile's rows once.
A width that has no such block (1856 is fourteen and a half lane tiles:
``N`` is then one block or none) keeps its whole ``N`` instead and goes by
blocks of ``K``, which moves the same bytes: a tile's rows once, block by
block, and its group's matrix once.
Such a stack is also handed to the kernel with ``K`` last, ``(G, N, K)``, and
multiplied as ``rows x matrix^T`` (``_forward``): the TPU stores an array
whose last dimension is no whole number of lane tiles with the dimension
before it minor where that one is (a parameter ``(.., 2688, 1856)`` lies
``K``-minor on the chip), a pallas call takes its operands row-major, and
handed ``(G, K, N)`` the compiler copied the whole stack re-laid, 3.7 GB of
Nemotron's experts, before the first call (PR 62; the swap is then no copy).
Only where not even one lane tile of a whole ``K`` fits do both dimensions
go by blocks, and a tile's rows are read again for every block of ``N``:
at 512 rows that re-read, not the weights, was what bound Keye-VL's
prefill calls (PERF.md section 6, PR 55).

XLA's own ``lax.ragged_dot`` lowers on the TPU to kernels of the same kind,
but under the one ``op_name`` ``ragged-dot-none``: the scopes around it are
lost, and with them the time of the experts in every profile
(``core/scopes.py``).  This kernel carries its scopes and its own name,
``grouped_matmul``.

Autodiff: ``jax.custom_vjp``.  Since every group is a run of whole tiles,
the function is ``lax.ragged_dot(rows, w, rows-a-group)``
(``grouped_matmul_xla``), and the backward is that function's own (pure
XLA), from the saved ``rows`` and ``w``.

Like the other kernels here it always compiles for the TPU: off the TPU a
caller traces it under ``pltpu.force_tpu_interpret_mode()`` (the tests of
this file's do), or chooses ``grouped_matmul_xla`` itself, as the model
does on a mesh that is not of TPUs (``models/transformer._moe_ffn_tail``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ompi_tpu.ops._chip import _VMEM_BUDGET_BYTES

__all__ = ["grouped_matmul", "grouped_matmul_xla", "tile_rows",
           "weight_block"]

# The largest working set a cell's call makes within the budget a kernel gets
# unasked (``ops/_chip.py`` says why no limit is named), as
# ``_working_set_bytes`` counts it, is Keye-VL's 2048 x 768 bfloat16 beside
# 512 rows, 15.2 MB; the count is an upper bound (the float32 product is not
# held whole beside the accumulator): Kimi-Linear's 1024 x 2304 at 128 rows
# counts 13.5 MB and, compiled for a v5e, needs 11 MiB.  Beside rows of 4 MiB
# it is not one (``weight_block``).

# a tile's rows from which ``weight_block`` is wary: 512 rows of 4096 and, PR
# 67's cell, 256 of 7168 (3.5 MiB)
_LONG_ROWS_BYTES = 7 << 19


def tile_rows(rows_a_group: float, matrices=(), itemsize: int = 2) -> int:
    """Rows a tile for groups of about ``rows_a_group`` rows: the largest
    power of two not above it, from 16 (one bfloat16 sublane tile) to 512
    (above the v5e's ridge of 240 operations a byte of weights).

    ``matrices``: the ``(K, N)`` of the stacks the tiles will multiply.
    Where a cached step's handful of rows (a mean under one smallest tile)
    meets a matrix that goes by blocks (``weight_block``: every tile then
    reads its group's matrix again), the tile is the smallest that holds
    the mean and six deviations of a Poisson count, so that a group is one
    tile and its matrix is read once whatever the router sent it, a router
    that favours some experts twice over included: at 12 rows on the mean
    and 16 a tile one expert in eight read its 20 MB twice a step, and
    ``moe.experts`` moved by 2% with the seed (Nemotron's cell, PR 62: 64
    rows a tile, which the stream of the matrices still hides)."""
    tm = 16
    while tm * 2 <= min(rows_a_group, 512):
        tm *= 2
    if tm == 16 and any(weight_block(tm, K, N, itemsize) != (K, N)
                        for K, N in matrices):
        while tm < rows_a_group + 6 * rows_a_group ** 0.5:
            tm *= 2
    return tm


def _block(dim: int, cap: int) -> int:
    """``dim`` itself, or its largest power-of-two divisor up to ``cap``."""
    if dim <= cap:
        return dim
    b = cap
    while b >= 128 and dim % b:
        b //= 2
    if b < 128:
        raise ValueError(f"grouped_matmul: {dim} has no power-of-two tile "
                         f"between 128 and {cap}")
    return b


def _working_set_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """VMEM the kernel holds at blocks of ``(tm, tk)`` rows, ``(tk, tn)``
    weights and ``(tm, tn)`` output: each of the three twice (the pipeline
    copies the next while this one is multiplied), the float32 accumulator
    and the product's float32 result."""
    return (2 * itemsize * (tk * tn + tm * tk + tm * tn)
            + 2 * 4 * tm * tn)


def _widths(N: int) -> list[int]:
    """``N``, then its divisors that are whole lane tiles (multiples of
    128), widest first."""
    return [N] + [N // d for d in range(2, N // 128 + 1)
                  if N % (128 * d) == 0]


def weight_block(tm: int, K: int, N: int, itemsize: int) -> tuple[int, int]:
    """``(tk, tn)``, the block of a ``(K, N)`` matrix that a tile of ``tm``
    rows multiplies, by the bytes the tile then moves: the whole ``K`` and
    the widest block of ``N`` whose working set fits the kernel's VMEM
    budget (one lane tile of it beside rows of 3.5 MiB), so that a tile's rows
    are read once; that is the whole matrix where it fits, and a group's
    matrix is then read once too.  Where no
    block of ``N`` under a whole ``K`` fits, the whole ``N`` under the widest
    block of ``K`` that does (the same bytes: rows and matrix once each);
    blocks of both dimensions only where neither fits."""
    # beside rows of 3.5 MiB a part of ``N`` is one lane tile.  The count is
    # what the pipeline and the accumulator hold; Mosaic's own stack for a
    # product of long rows with more than one lane tile of columns is not in
    # it: for 512 rows of 4096 beside (4096, 256) it asks 16.7 MiB where
    # 13.5 are counted (and 19.1 beside (4096, 384), counted 16.25; for 256
    # rows of 7168 beside (7168, 256) 17.7 where 14.8 are, PR 67), while
    # 3968 beside 256 columns, and 4096 and 6144 beside 128, compile as
    # counted (for a described v5e, PR 65; no term of the blocks' sizes that
    # I tried gives all of these, so the rule is this threshold and
    # ``PERF.md`` section 7 asks for the count's repair).  Cell 11 has such
    # rows (512 of 6144, 6 MiB) and is unchanged: the budget alone already
    # gave it (6144, 128); the calls the threshold moves are PR 65's cell's
    # and PR 67's (no other cell has rows between 3.5 and 4 MiB)
    long_rows = tm * K * itemsize >= _LONG_ROWS_BYTES
    for tn in _widths(N):
        if long_rows and 128 < tn < N:
            continue
        if _working_set_bytes(tm, K, tn, itemsize) <= _VMEM_BUDGET_BYTES:
            return K, tn
    for tk in _widths(K)[1:]:
        if _working_set_bytes(tm, tk, N, itemsize) <= _VMEM_BUDGET_BYTES:
            return tk, N
    return _block(K, 1024), _block(N, 512)


def _kernel(tile_group, tiles_used, lhs, rhs, out, acc, *, k_last=False):
    from ompi_tpu.ops._pallas import pl

    del tile_group          # read by the index map of ``rhs``
    i, k = pl.program_id(0), pl.program_id(2)
    last = k == pl.num_programs(2) - 1

    @pl.when(i < tiles_used[0])
    def _():
        @pl.when(k == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        if k_last:      # the block of weights is (tn, tk)
            acc[...] += jax.lax.dot_general(
                lhs[...], rhs[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            acc[...] += jnp.dot(lhs[...], rhs[...],
                                preferred_element_type=jnp.float32)

        @pl.when(last)
        def _():
            out[...] = acc[...].astype(out.dtype)

    @pl.when(jnp.logical_and(i >= tiles_used[0], last))
    def _():
        out[...] = jnp.zeros_like(out)


def _forward(rows, w, tile_group, tiles_used):
    from ompi_tpu.ops._pallas import pallas_call, pl
    from ompi_tpu.ops._pallas import pltpu

    n_tiles = tile_group.shape[0]
    m, K = rows.shape
    G, K2, N = w.shape
    if K != K2 or m % n_tiles:
        raise ValueError(f"grouped_matmul: rows {rows.shape}, w {w.shape}, "
                         f"{n_tiles} tiles")
    tm = m // n_tiles
    tk, tn = weight_block(tm, K, N, jnp.dtype(w.dtype).itemsize)
    nj, nk = N // tn, K // tk
    # a width of lane tiles and a part of one: the stack as the chip stores
    # it, K last (the module's docstring)
    k_last = N > 128 and N % 128 != 0
    if k_last:
        w = jnp.swapaxes(w, 1, 2)

    def weights_at(i, j, k, tg, used):
        return (tg[i], j, k) if k_last else (tg[i], k, j)

    def weights_at_or_where_they_were(i, j, k, tg, used):
        """A tile past ``tiles_used`` multiplies nothing: it names the block
        the last step of the tile before it named, and copies none."""
        live = i < used[0]
        k, j = jnp.where(live, k, nk - 1), jnp.where(live, j, nj - 1)
        return weights_at(i, j, k, tg, used)

    return pallas_call(
        functools.partial(_kernel, k_last=k_last),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles, nj, nk),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda i, j, k, tg, used: (i, k)),
                # a whole matrix a block stays put by itself
                pl.BlockSpec((None, tn, tk) if k_last else (None, tk, tn),
                             weights_at if nj * nk == 1
                             else weights_at_or_where_they_were),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda i, j, k, tg, used: (i, j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, N), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="grouped_matmul",
    )(tile_group, tiles_used, rows, w)


@jax.custom_vjp
def grouped_matmul(rows, w, tile_group, tiles_used):
    """rows (n_tiles·tm, K) × w (G, K, N) → (n_tiles·tm, N) in rows' dtype,
    float32 accumulation: tile ``t`` times ``w[tile_group[t]]``.

    ``tile_group``: (n_tiles,) int32, non-decreasing, every entry a valid
    group.  ``tiles_used``: (1,) int32; tiles from there on come back zero.
    """
    return _forward(rows, w, tile_group, tiles_used)


def _fwd(rows, w, tile_group, tiles_used):
    return (_forward(rows, w, tile_group, tiles_used),
            (rows, w, tile_group, tiles_used))


def grouped_matmul_xla(rows, w, tile_group, tiles_used):
    """The same function without the kernel: every group is a run of whole
    tiles, so it is ``lax.ragged_dot`` with those runs as its groups (rows
    past the last run come back zero there too).  The kernel's backward,
    and what ``parallel/moe.routed_moe`` runs where the kernel does not
    compile."""
    from jax import lax

    n_tiles = tile_group.shape[0]
    tm = rows.shape[0] // n_tiles
    used = jnp.arange(n_tiles) < tiles_used[0]
    tiles_a_group = jnp.sum(
        (tile_group[:, None] == jnp.arange(w.shape[0])[None, :])
        & used[:, None], axis=0, dtype=jnp.int32)
    return lax.ragged_dot(rows, w, tiles_a_group * tm)


def _bwd(saved, g):
    rows, w, tile_group, tiles_used = saved
    _, vjp = jax.vjp(
        lambda r, m: grouped_matmul_xla(r, m, tile_group, tiles_used),
        rows, w)
    return (*vjp(g), None, None)


grouped_matmul.defvjp(_fwd, _bwd)
