"""The pallas grouped matmul under dropless expert routing
(``ops/grouped_matmul.py``), in TPU interpret mode: each row tile against
the matrix its entry of ``tile_group`` names, the tiles past ``tiles_used``
zero, and the custom backward against ``lax.ragged_dot``'s own; and the
rule that picks the weights' block (``weight_block``), held to what every
routed cell of the benchmark hands it."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from ompi_tpu.ops.grouped_matmul import (_VMEM_BUDGET_BYTES,
                                         _working_set_bytes, grouped_matmul,
                                         grouped_matmul_xla, tile_rows,
                                         weight_block)


@pytest.mark.parametrize("rows_a_group,want", [
    (0.5, 16), (6, 16), (16, 16), (31, 16), (32, 32), (100, 64),
    (6144, 512), (1e6, 512)])
def test_tile_rows_follow_the_groups_size(rows_a_group, want):
    assert tile_rows(rows_a_group) == want


def test_a_steps_tile_has_room_for_the_spread_where_the_matrix_goes_by_blocks():
    """Nemotron's cached step: 12 rows an expert on the mean, matrices of
    2688 x 1856 that no tile holds whole, so 64 rows a tile (the smallest over
    12 + 6 x 3.5); a matrix that is
    one block (Kimi-Linear's, the same 12 rows), a mean of more than a tile,
    and a handful whose spread still fits 16 rows (LongCat's 2.5) stay."""
    nemotron = ((2688, 1856), (1856, 2688))
    assert weight_block(16, *nemotron[0], 2) != nemotron[0]
    assert tile_rows(12, nemotron) == 64
    assert tile_rows(12, ((2304, 1024), (1024, 2304))) == 16
    assert tile_rows(2.5, ((6144, 2048), (2048, 6144))) == 16
    assert tile_rows(56, ((6144, 2048), (2048, 6144))) == 32
    assert tile_rows(384, nemotron) == 256


def _case(tm, K, N, G, tile_group, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    tile_group = np.asarray(tile_group, np.int32)
    rows = rng.normal(size=(len(tile_group) * tm, K)).astype(np.float32)
    w = rng.normal(size=(G, K, N)).astype(np.float32)
    return (jnp.asarray(rows, dtype), jnp.asarray(w, dtype),
            jnp.asarray(tile_group))


# Groups 1 and 4 have no tile and the last two tiles hold no row.
_SPARSE = dict(G=6, tile_group=[0, 0, 2, 3, 3, 3, 5, 5], used=6,
               dtype=np.float32)
# Kimi-Linear's held experts: 2304 x 1024 and 1024 x 2304, 4.72 MB in
# bfloat16 (over the 4 MiB a whole-matrix block stopped at until PR 46), a K
# with a factor of 9.  Group 0 has two tiles, group 1 none, and the last two
# tiles hold no row.
_KIMI = dict(G=4, tile_group=[0, 0, 2, 3, 3, 3], used=4, dtype=jnp.bfloat16)
# A routed prefill's tiles of 512 rows (OLMoE's and Keye-VL's matrices):
# group 0 has three tiles, group 1 none, and the last two tiles hold no row.
_PREFILL = dict(G=4, tile_group=[0, 0, 0, 2, 3, 3, 3, 3], used=6,
                dtype=jnp.bfloat16)
# A K of which not one lane tile fits whole beside 512 rows of float32: the
# whole N under blocks of K where that fits, blocks of both where N is wide.
_DEEP = dict(G=3, tile_group=[0, 0, 2, 2], used=3, dtype=np.float32)


@pytest.mark.parametrize("tm,K,N,block,case", [
    pytest.param(16, 64, 32, (64, 32), _SPARSE, id="whole-matrix-blocks"),
    pytest.param(128, 2048, 1024, (2048, 512), _SPARSE,
                 id="whole-k-blocks-of-n"),
    pytest.param(512, 4096, 256, (2048, 256), _DEEP,
                 id="whole-n-blocks-of-k"),
    pytest.param(512, 4096, 2048, (1024, 512), _DEEP,
                 id="k-and-n-in-blocks"),
    pytest.param(16, 2304, 1024, (2304, 1024), _KIMI,
                 id="over-4-mib-step-w1"),
    pytest.param(16, 1024, 2304, (1024, 2304), _KIMI,
                 id="over-4-mib-step-w2"),
    pytest.param(128, 2304, 1024, (2304, 1024), _KIMI,
                 id="over-4-mib-prefill-w1"),
    pytest.param(128, 1024, 2304, (1024, 2304), _KIMI,
                 id="over-4-mib-prefill-w2"),
    pytest.param(512, 2304, 1024, (2304, 512), _KIMI,
                 id="over-4-mib-mxu-bound"),
    pytest.param(512, 2048, 768, (2048, 768), _PREFILL,
                 id="keye-prefill-w1-whole"),
    pytest.param(512, 768, 2048, (768, 1024), _PREFILL,
                 id="keye-prefill-w2"),
    pytest.param(512, 2048, 1024, (2048, 512), _PREFILL,
                 id="olmoe-prefill-w1"),
    pytest.param(512, 1024, 2048, (1024, 1024), _PREFILL,
                 id="olmoe-prefill-w2"),
    # Nemotron's experts, 2688 x 1856: fourteen and a half lane tiles wide,
    # so the whole N under blocks of K, the stack handed over K last
    pytest.param(16, 2688, 1856, (896, 1856), _KIMI,
                 id="nemotron-step-w1-k-last"),
    pytest.param(512, 2688, 1856, (384, 1856), _PREFILL,
                 id="nemotron-prefill-w1-k-last"),
    pytest.param(512, 1856, 2688, (1856, 896), _PREFILL,
                 id="nemotron-prefill-w2"),
])
def test_each_tile_multiplies_its_groups_matrix(tm, K, N, block, case):
    tile_group, n_used, dtype = case["tile_group"], case["used"], case["dtype"]
    rows, w, tg = _case(tm, K, N, case["G"], tile_group, dtype=dtype)
    used = jnp.asarray([n_used], jnp.int32)
    assert weight_block(tm, K, N, rows.dtype.itemsize) == block
    got = np.asarray(jax.jit(grouped_matmul)(rows, w, tg, used), np.float64)
    # float32: the accumulation's own error; bfloat16: the result's one
    # rounding, a unit in the last place of the largest entry
    tol = 1e-4 if dtype == np.float32 else 2.0 ** -7
    for t, g in enumerate(tile_group):
        tile = slice(t * tm, (t + 1) * tm)
        if t < n_used:
            want = np.asarray(rows[tile], np.float64) @ np.asarray(
                w[g], np.float64)
            np.testing.assert_allclose(got[tile], want, rtol=tol,
                                       atol=tol * np.abs(want).max())
        else:
            assert not got[tile].any()
    same = np.asarray(jax.jit(grouped_matmul_xla)(rows, w, tg, used),
                      np.float64)
    np.testing.assert_allclose(got, same, rtol=tol,
                               atol=tol * np.abs(same).max())


_OLMOE = "olmoe-1b-7b.decode-1k-128"
_KEYE = "keye-vl-2.0-30b-a3b.decode-8k-128-b64"
_KIMI_CELL = "kimi-linear-48b-a3b.decode-512-128-b384"


def _routed_call(workload, phase):
    """``(tm, n_tiles, {leaf: (K, N)}, itemsize)`` of the ``grouped_matmul``
    calls one routed layer makes in a cached step or in a pass of the
    prefill of a benchmark cell, from the cell's configuration and traffic
    files as ``parallel/moe.routed_moe`` tiles them."""
    from benchmarks.lib import cells, program
    from ompi_tpu.models.decode import _prefill_group

    cell = cells.resolve(workload)
    cfg = program.program_config(cell.config)
    ref = program.reference(cell.config)
    routed = program.counts(ref, ref.Shape.from_config(cell.config))["routed"]
    B, Tp = cell.traffic["batch"], cell.traffic["prompt_len"]
    n = B if phase == "step" else Tp * _prefill_group(B, Tp,
                                                      cfg.prefill_tokens)
    picks = n * cfg.moe_top_k
    tm = tile_rows(picks / cfg.moe_experts)
    D, F = routed["d_model"], routed["d_expert"]
    return (tm, -(-picks // tm) + routed["experts"],
            {"w1": (D, F), "w2": (F, D)},
            jnp.dtype(cfg.compute_dtype).itemsize)


# Which path every routed cell's calls take (PERF.md section 5).  The four
# calls of up to 128 rows a tile take a whole matrix, as since PR 46 (the
# steps' since before it): their programs are the parent's.  The two
# prefills of 512 rows a tile took ``(1024, 512)`` and ``(1024, 256)`` /
# ``(768, 512)`` until PR 55, which read a tile's rows once for every block
# of ``N``.
@pytest.mark.parametrize("workload,phase,tm,n_tiles,blocks", [
    pytest.param(_OLMOE, "step", 16, 88, "whole", id="olmoe-step"),
    pytest.param(_KEYE, "step", 16, 160, "whole", id="keye-step"),
    pytest.param(_OLMOE, "prefill", 512, 832,
                 {"w1": (2048, 512), "w2": (1024, 1024)}, id="olmoe-prefill"),
    pytest.param(_KEYE, "prefill", 512, 380,
                 {"w1": (2048, 768), "w2": (768, 1024)}, id="keye-prefill"),
    pytest.param(_KIMI_CELL, "step", 16, 320, "whole", id="kimi-step"),
    pytest.param(_KIMI_CELL, "prefill", 128, 384, "whole",
                 id="kimi-prefill"),
])
def test_which_block_each_routed_cell_takes(workload, phase, tm, n_tiles,
                                            blocks):
    got_tm, got_tiles, matrices, itemsize = _routed_call(workload, phase)
    assert (got_tm, got_tiles) == (tm, n_tiles)
    for leaf, (K, N) in matrices.items():
        tk, tn = weight_block(tm, K, N, itemsize)
        want = (K, N) if blocks == "whole" else blocks[leaf]
        assert (tk, tn) == want, leaf
        # every cell's tile reads its rows once, and the blocks tile
        assert tk == K and N % tn == 0, leaf
        held = _working_set_bytes(tm, tk, tn, itemsize)
        assert held <= _VMEM_BUDGET_BYTES, (leaf, held)
        if (tk, tn) == (K, N):
            assert 2 * K * N * itemsize < held, (leaf, held)


def test_the_working_set_is_counted_as_the_kernel_holds_it():
    # Kimi-Linear's w2 at 128 rows: the matrix, the rows and the output
    # twice each, the accumulator and the product in float32: 13.5 MB
    assert _working_set_bytes(128, 1024, 2304, 2) == 13_500_416
    # past the ridge too a whole matrix is one block where that fits:
    # Keye-VL's w1 beside 512 rows, 15.2 MB of 16 MiB
    assert _working_set_bytes(512, 2048, 768, 2) == 15_204_352
    assert weight_block(512, 2048, 768, 2) == (2048, 768)
    assert weight_block(256, 256, 256, 2) == (256, 256)
    assert weight_block(256, 2304, 1024, 2) == (2304, 1024)
    # where it does not, the whole K and the widest block of N that does,
    # a power of two or not, so that a tile's rows are read once
    assert _working_set_bytes(512, 2304, 1024, 2) > _VMEM_BUDGET_BYTES
    assert weight_block(512, 2304, 1024, 2) == (2304, 512)
    assert weight_block(512, 1024, 2304, 2) == (1024, 1152)
    # under the ridge as well, where the matrix cannot be held twice
    assert weight_block(16, 4096, 2048, 2) == (4096, 512)
    # where not one lane tile of a whole K fits, the whole N under the
    # widest block of K that does (the same bytes: rows and matrix once);
    # and so for a width that has no block of whole lane tiles (Nemotron's
    # 1856), whose matrix does not fit whole
    assert _working_set_bytes(512, 8192, 128, 2) > _VMEM_BUDGET_BYTES
    assert weight_block(512, 4096, 256, 4) == (2048, 256)
    assert _working_set_bytes(16, 2688, 1856, 2) > _VMEM_BUDGET_BYTES
    assert weight_block(16, 2688, 1856, 2) == (896, 1856)
    assert weight_block(512, 2688, 1856, 2) == (384, 1856)
    assert weight_block(512, 1856, 2688, 2) == (1856, 896)
    # both dimensions only where neither fits
    assert weight_block(512, 8192, 4096, 2) == (1024, 512)


def test_backward_is_ragged_dots_own():
    tm = 16
    tile_group = [0, 1, 1, 3, 3]
    rows, w, tg = _case(tm, 32, 48, 4, tile_group, seed=1)
    used = jnp.asarray([4], jnp.int32)      # the last tile holds nothing
    weight = jnp.asarray(np.random.default_rng(2).normal(
        size=(len(tile_group) * tm, 48)).astype(np.float32))
    # what the caller reads back: nothing of an unused tile
    weight = weight.at[4 * tm:].set(0)
    sizes = jnp.asarray([16, 32, 0, 16], jnp.int32)

    got = jax.grad(lambda r, m: (grouped_matmul(r, m, tg, used)
                                 * weight).sum(), argnums=(0, 1))(rows, w)
    want = jax.grad(lambda r, m: (lax.ragged_dot(r, m, sizes)
                                  * weight).sum(), argnums=(0, 1))(rows, w)
    for g, v in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(v), rtol=1e-5,
                                   atol=1e-5)
    assert not np.asarray(got[1][2]).any()      # group 2 got no row


def test_shapes_that_do_not_tile_are_refused():
    rows, w, tg = _case(16, 32, 48, 4, [0, 1, 2])
    used = jnp.asarray([3], jnp.int32)
    with pytest.raises(ValueError, match="grouped_matmul"):
        grouped_matmul(rows[:-8], w, tg, used)          # 40 rows, 3 tiles
    with pytest.raises(ValueError, match="grouped_matmul"):
        grouped_matmul(rows, w[:, :16], tg, used)       # K differs
