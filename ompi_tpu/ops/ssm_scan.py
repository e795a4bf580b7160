"""The Mamba-2 mixer's whole-sequence scan as one pallas kernel: the forward
pass of a decoder's prefill on TPUs, ``models/ssm.chunked_scan``'s equations
with a chunk's weights, decays and states held in VMEM.

    h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t,    y_t = h_t C_t + D x_t

``chunked_scan`` writes out, between its products, the running sum of the
log-decay (minor dimensions (G, R), padded 16-fold), a chunk's ``(Q, Q)``
float32 weights a head, every chunk's own state, the states before each
chunk twice, and ``y`` re-laid twice: 2 GB a layer and pass of 8 sequences of
1024 positions, where the operands and results are a quarter of one.  Here a
grid cell is (sequence, group of the ``R = H / G`` heads that share ``B`` and
``C``, span of up to ``SPAN`` chunks); the spans of a sequence follow one
another with the group's state, float32, in VMEM scratch, and the chunks of a
span go by under a ``fori_loop``.  With ``cs`` the log-decay summed inside the
chunk (inclusive), a chunk of ``Q`` positions

  * forms ``C B^T`` ``(Q, Q)`` once for the group;
  * a head at a time, the masked weights ``C B^T exp(cs_q - cs_k) dt_k`` and
    their product with the input: ``y`` inside the chunk;
  * adds what the carried state gives, ``(C h) exp(cs_q)``, and the skip;
  * steps the state: ``h exp(cs_Q) + (x dt exp(cs_Q - cs_k))^T B``.

**Every exponent of a decay is at most zero** (up to the rounding of a sum),
and none is a difference of sums over the sequence: ``cs`` starts at each
chunk.  The same precisions as ``chunked_scan``: decays, sums, ``y`` and the
state float32, products in ``x``'s type with float32 accumulation.  A key's
``dt`` goes into its weight (as ``log2 dt_k`` in the exponent) and not into
``x``, so the input enters both products as it lies, rounded once where
``chunked_scan`` rounds twice (on the chip, bfloat16 at cell 12's shapes
against float32: ``y`` 0.00054 at the root mean square for ``chunked_scan``'s
0.00074, the end state the same 0.000046: PR 64).  Decays are powers of two of
sums scaled once, by ``log2 e`` on ``a``: the chip's exponential is that.

Operands are read as they lie.  ``x``, ``B`` and ``C`` are lane blocks of the
convolution's one output ``(B, T, H P + 2 G N)``: the array is passed three
times under three index maps and nothing is sliced outside (which is why the
skip is added here: outside, ``x`` lies beside ``B`` and ``C``).  ``y`` leaves
positions-major, ``(B, T, H P)`` float32, which is what the gate and the norm
read next.  ``dt`` comes heads-major ``(B, G, R, T)`` (2 MB re-laid outside),
so that a chunk's decays are one vreg ``(R, Q)``: their running sum is a
product with a triangle of ones, and what a position scales by down the
sublanes (a column a head) is those rows through the MXU against the
identity; both in float32 at the highest precision, which is exact for the
identity.

**What binds the body is the lane broadcast**, a column ``(Q, 1)`` spread
over a tile's 128 lanes on the XLU (by the compiler's bundles for the v5e,
1870 a chunk: the XLU's slots 2540 of 3 a bundle, the MXU's 2630 of 4, the
VALU's 2130 of 4): two a head and chunk are left, ``cs_q`` (the weights'
exponent and, through ``exp2``, what the carried state's part scales by: the
state is ``N == Q`` lanes deep) and the state's step's scale.  The first body
made five and took 0.91 ms where this takes 0.77 (cell 12's pass of 8
prompts, PR 64).

Heads narrower than a lane tile go ``128 / P`` to a tile: their inputs,
states and ``y`` are handled a whole tile at a time, a column's scale chosen
by lane, and only the weights are a head's own.  The state is held transposed,
``(N, R P)``, so that both of its products are plain ones and one transpose a
chunk (``B``'s) serves the group; it is turned once, at the end, into the
second output ``(B, H, P, N)``.

Forward only: a trainer keeps ``chunked_scan``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["ssm_scan", "tiles", "CHUNK", "SPAN"]

LANES = 128
# positions a chunk: the weights of a head are one (128, 128) float32 tile
CHUNK = 128
# chunks a grid cell: at cell 12's widths a span's x, B, C (bfloat16) and y
# (float32), double buffered, are 7 MiB, so the call names no
# ``vmem_limit_bytes`` (``ops/retention_prefill.MAX_ROWS`` says why not)
SPAN = 8
_LOG2_E = 1.4426950408889634
_NT = (((1,), (1,)), ((), ()))      # a . b^T
_NN = (((1,), (0,)), ((), ()))      # a . b


def tiles(t: int, chunk: int, p: int, n: int, r: int, dtype=jnp.bfloat16
          ) -> bool:
    """True where the kernel takes sequences of ``t`` positions in chunks of
    ``chunk``, of groups of ``r`` heads ``p`` wide with states ``n`` deep:
    whole chunks of :data:`CHUNK` (nothing is padded here), ``n`` one lane
    tile, whole heads to a lane tile (a head no wider than one) and a group's
    heads whole lane tiles, an ``x`` of float32 or bfloat16."""
    return (chunk == CHUNK and t > 0 and t % CHUNK == 0 and n == LANES
            and p > 0 and LANES % p == 0 and (r * p) % LANES == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                     jnp.dtype(jnp.bfloat16)))


def _kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref, y_ref, end_ref, h_ref,
            *, p: int):
    """One (sequence, group, span) cell: the span's chunks in order against
    the group's state ``h_ref`` (N, R P), which the sequence's first span
    clears and its last turns into ``end_ref`` (R P, N)."""
    from jax import lax

    from ompi_tpu.ops._pallas import pl

    f32, cdt = jnp.float32, x_ref.dtype
    exact = lax.Precision.HIGHEST
    q = CHUNK
    r = dt_ref.shape[0]
    span = pl.program_id(2)

    @pl.when(span == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    row = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = row >= col
    upto = (row <= col).astype(f32)     # sums a row's entries up to a lane
    same = (row == col).astype(f32)
    head_of = lax.broadcasted_iota(jnp.int32, (1, LANES), 1) // p
    a = a_ref[...] * _LOG2_E                            # (R, 1)
    skip = d_ref[...]                                   # (1, R)

    def by_lane(of_head, first):
        """The tile's scale a lane: ``of_head(first + k)`` over the lanes of
        the tile's head ``k``."""
        wide = of_head(first)
        for k in range(1, LANES // p):
            wide = jnp.where(head_of == k, of_head(first + k), wide)
        return wide

    def chunk(i, carry):
        at = pl.ds(pl.multiple_of(i * q, q), q)
        bm, cm = b_ref[0, at, :], c_ref[0, at, :]       # (Q, N)
        dt = dt_ref[:, at]                              # (R, Q)
        # every decay as a power of two of a sum made here, a vreg a group
        cs = lax.dot_general(dt * a, upto, _NN, precision=exact,
                             preferred_element_type=f32)
        total = cs[:, q - 1:q]                          # (R, 1)
        # key k's weight carries its dt: exp2(cs_q - (cs_k - log2 dt_k))
        of_key = cs - jnp.log2(dt)
        to_end = jnp.exp2(total - of_key)               # dt_k exp(cs_Q - cs_k)
        # down the sublanes, a column a head: (Q, 2 R)
        down = lax.dot_general(same, jnp.concatenate([cs, to_end], axis=0),
                               _NT, precision=exact,
                               preferred_element_type=f32)
        whole = jnp.exp2(down[q - 1:q, :r])             # (1, R)
        cb = lax.dot_general(cm, bm, _NT, preferred_element_type=f32)
        b_t = bm.T                                      # (N, Q)
        # a head's own: its sum at each query over the lanes (N == Q wide),
        # which the weights and what the carried state adds both scale by
        at_query = [jnp.broadcast_to(down[:, head:head + 1], (q, LANES))
                    for head in range(r)]
        for tile in range(x_ref.shape[-1] // LANES):
            lanes = slice(tile * LANES, (tile + 1) * LANES)
            first = tile * LANES // p
            x = x_ref[0, at, lanes]
            y = None
            for k in range(LANES // p):
                seg = at_query[first + k] - of_key[first + k:first + k + 1, :]
                weight = cb * jnp.exp2(jnp.where(causal, seg, -jnp.inf))
                mine = lax.dot_general(weight.astype(cdt), x, _NN,
                                       preferred_element_type=f32)
                y = mine if y is None else jnp.where(head_of == k, mine, y)
            h = h_ref[:, lanes]                         # (N, lanes) float32
            x32 = x.astype(f32)
            y_ref[0, at, lanes] = (
                y + lax.dot_general(cm, h.astype(cdt), _NN,
                                    preferred_element_type=f32)
                * by_lane(lambda head: jnp.exp2(at_query[head]), first)
                + x32 * by_lane(lambda head: skip[:, head:head + 1], first))
            own = (x32 * by_lane(
                lambda head: down[:, r + head:r + head + 1],
                first)).astype(cdt)
            h_ref[:, lanes] = (
                h * by_lane(lambda head: whole[:, head:head + 1], first)
                + lax.dot_general(b_t, own, _NN, preferred_element_type=f32))
        return carry

    lax.fori_loop(0, x_ref.shape[1] // q, chunk, None)

    @pl.when(span == pl.num_programs(2) - 1)
    def _():
        for tile in range(x_ref.shape[-1] // LANES):
            lanes = slice(tile * LANES, (tile + 1) * LANES)
            end_ref[lanes, :] = h_ref[:, lanes].T


@functools.partial(jax.jit, static_argnums=(4, 5))
def _call(xbc, dt, a, d, groups: int, n: int):
    """xbc (B, T, H P + 2 G N), dt (B, T, H) float32, a and d (H,) float32
    -> y (B, T, H P) and the end state (B, H P, N), float32; T whole
    chunks."""
    from ompi_tpu.ops._pallas import pallas_call, pl
    from ompi_tpu.ops._pallas import pltpu

    b, t, h = dt.shape
    r = h // groups
    hp = xbc.shape[-1] - 2 * groups * n
    chunks = t // CHUNK
    spans = next(s for s in range(min(SPAN, chunks), 0, -1)
                 if chunks % s == 0)
    rows = spans * CHUNK
    width = r * hp // h                 # a group's heads, lanes
    # a group's B and C, lane blocks of N after the heads' H P lanes
    first_b = hp // n

    def operand(block, index):
        return pl.BlockSpec((1, rows, block), index)

    return pallas_call(
        functools.partial(_kernel, p=hp // h),
        grid=(b, groups, t // rows),
        in_specs=[
            operand(width, lambda b, g, s: (b, s, g)),
            operand(n, lambda b, g, s: (b, s, first_b + g)),
            operand(n, lambda b, g, s: (b, s, first_b + groups + g)),
            pl.BlockSpec((None, None, r, rows), lambda b, g, s: (b, g, 0, s)),
            pl.BlockSpec((None, r, 1), lambda b, g, s: (g, 0, 0)),
            pl.BlockSpec((None, 1, r), lambda b, g, s: (g, 0, 0)),
        ],
        out_specs=(
            operand(width, lambda b, g, s: (b, s, g)),
            pl.BlockSpec((None, width, n), lambda b, g, s: (b, g, 0))),
        out_shape=(jax.ShapeDtypeStruct((b, t, hp), jnp.float32),
                   jax.ShapeDtypeStruct((b, hp, n), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((n, width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="ssm_scan",
    )(xbc, xbc, xbc,
      jnp.moveaxis(dt, 1, 2).reshape(b, groups, r, t), a.reshape(groups, r, 1),
      d.reshape(groups, 1, r))


def ssm_scan(xbc, dt, a, d, groups: int, n: int):
    """``chunked_scan`` from a zero state with the mixer's skip, on the
    convolution's output as it lies: xbc (B, T, H P + 2 G N), x's H heads P
    wide, then B's and C's ``groups`` groups ``n`` wide, head ``h`` reading
    group ``h // (H / G)``; dt (B, T, H) float32, positive; a (H,) float32,
    negative; d (H,), what a head adds of its own input (x is in VMEM here,
    and outside it lies beside B and C).  Returns ``y + d x`` (B, T, H P)
    float32 and the state after the last position (B, H, P, N) float32."""
    b, t, h = dt.shape
    hp = xbc.shape[-1] - 2 * groups * n
    if (h % groups or hp <= 0 or hp % h
            or not tiles(t, CHUNK, hp // h, n, h // groups, xbc.dtype)):
        raise ValueError(
            f"ssm_scan: {t} positions of {h} heads in {groups} groups over "
            f"{xbc.shape[-1]} channels with states {n} deep do not tile "
            f"(whole chunks of {CHUNK}, states of {LANES} lanes, a group's "
            f"heads whole lane tiles, float32 or bfloat16)")
    y, end = _call(xbc, *(t.astype(jnp.float32) for t in (dt, a, d)),
                   groups, n)
    return y, end.reshape(b, h, hp // h, n)
