"""Flash-attention kernel tests — cross-checked against the jnp reference
path (same strategy as the rest of the attention suite), including
gradients through the custom VJP and the sequence-parallel wiring.
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ompi_tpu.ops import flash_attention  # noqa: E402
from ompi_tpu.parallel import attention as attn  # noqa: E402


def _qkv(b=2, t=256, h=2, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    ref = attn.local_attention(q, k, v, causal=causal, impl="jnp")
    out = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_offsets_mask_globally():
    """Blocks that are slices of a longer sequence: the causal mask uses
    global positions via the offsets."""
    q, k, v = _qkv(t=128)
    # q block sits at positions 128..255, k at 0..127 → fully visible
    out = flash_attention(q, k, v, causal=True, q_offset=128, k_offset=0)
    ref = attn.local_attention(q, k, v, causal=True,
                               q_offset=128, k_offset=0, impl="jnp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # reversed: q at 0.., k at 128.. → nothing visible, uniform over zero
    # weights is undefined; the kernel returns zeros (l clamped)
    out2 = flash_attention(q, k, v, causal=True, q_offset=0, k_offset=128)
    assert np.isfinite(np.asarray(out2)).all()


def test_flash_bf16():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v)
    ref = attn.local_attention(q, k, v, impl="jnp")
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2, rtol=3e-2)


def test_flash_gradients_match_reference():
    q, k, v = _qkv(t=128)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v) ** 2).sum()

    def loss_ref(q, k, v):
        return (attn.local_attention(q, k, v, impl="jnp") ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_flash_under_jit_and_small_t():
    q, k, v = _qkv(t=96)          # < one block: block shrinks to T
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v))(q, k, v)
    ref = attn.local_attention(q, k, v, impl="jnp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_rejects_untileable():
    q, k, v = _qkv(t=200)         # above 128 and no block divides it
    with pytest.raises(ValueError):
        flash_attention(q, k, v)


def test_local_attention_impl_dispatch():
    q, k, v = _qkv(t=128)
    out_flash = attn.local_attention(q, k, v, impl="flash")
    out_jnp = attn.local_attention(q, k, v, impl="jnp")
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_jnp),
                               atol=2e-5, rtol=2e-5)
    # traced offsets run through the kernel (the ring hop feeds one in)
    out_traced = jax.jit(lambda off: attn.local_attention(
        q, k, v, q_offset=off, impl="flash"))(jnp.int32(64))
    ref = attn.local_attention(q, k, v, q_offset=64, impl="jnp")
    np.testing.assert_allclose(np.asarray(out_traced), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ulysses_flash_parity():
    """The sequence-parallel wiring: ulysses with the flash kernel equals
    ulysses with the jnp kernel on the device mesh (seq-sharded inputs)."""
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.mpi.device_comm import device_world

    comm = device_world()
    n = comm.size
    b, t, h, d = 2, 64 * n, max(n, 2), 32
    q, k, v = _qkv(b=b, t=t, h=h, d=d, seed=3)
    ax = comm.axes[-1]

    def run(impl):
        shm = jax.shard_map(
            lambda q, k, v: attn.ulysses_attention(
                comm, q, k, v, axis=ax, impl=impl),
            mesh=comm.mesh, in_specs=(P(None, ax),) * 3,
            out_specs=P(None, ax), check_vma=False)
        return jax.jit(shm)(q, k, v)

    out_f = run("flash")
    out_j = run("jnp")
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_j),
                               atol=2e-5, rtol=2e-5)


def _grads(fn, q, k, v):
    return jax.grad(fn, argnums=(0, 1, 2))(q, k, v)


def _close(got, ref, tol):
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=tol, rtol=tol)


@pytest.mark.parametrize("through_lse", [False, True],
                         ids=["out", "out_and_lse"])
def test_flash_bwd_kernel_matches_jnp_autodiff(through_lse):
    """The backward kernels (dq, dk/dv from the saved lse) against autodiff
    of the jnp path, with and without a cotangent through lse (ring
    attention's merge path folds it into delta)."""
    from ompi_tpu.ops.flash_attention import flash_attention_lse

    q, k, v = _qkv(t=256)

    def loss(attend):
        def f(q, k, v):
            o, lse = attend(q, k, v, causal=True, q_offset=128)
            w = jnp.arange(o.size, dtype=jnp.float32).reshape(o.shape)
            total = (o * w / o.size).sum()
            return total + (lse * 0.01).sum() if through_lse else total
        return f

    got = _grads(loss(flash_attention_lse), q, k, v)
    ref = _grads(loss(functools.partial(attn.local_attention_lse,
                                        impl="jnp")), q, k, v)
    _close(got, ref, 2e-4)


# (q_offset, k_offset) of 256 x 256 calls, two blocks of 128 each way: the
# q blocks wholly below the diagonal, wholly above it, and crossing it at
# a block's edge and inside one
_OFFSETS = {"below": (256, 0), "above": (0, 256), "square": (0, 0),
            "edge": (0, 128), "edge_q": (128, 0), "inside": (0, 37),
            "inside_q": (37, 0), "inside_far": (0, 200)}


@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("where", list(_OFFSETS))
def test_flash_skipped_blocks_leave_nothing_out(where, traced):
    """A causal call visits only the blocks at or below the diagonal; the
    bounds come from the offsets' run-time values, so a traced offset (a
    ring hop's) gives what a static one gives: the jnp path's output, lse
    and gradients."""
    from ompi_tpu.ops.flash_attention import flash_attention_lse

    q, k, v = _qkv(t=256, h=1, d=128)
    q_off, k_off = _OFFSETS[where]

    def run(attend, off):
        def loss(q, k, v):
            o, lse = attend(q, k, v, causal=True, q_offset=q_off,
                            k_offset=off)
            # rows that see no key carry lse = -1e30: keep them out
            seen = lse > -1e29
            return ((o ** 2).sum()
                    + (jnp.where(seen, lse, 0.0) * 0.01).sum()), (o, seen)
        (_, (o, seen)), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (o, seen, *grads)

    ref = run(functools.partial(attn.local_attention_lse, impl="jnp"), k_off)
    if traced:
        got = jax.jit(functools.partial(run, flash_attention_lse))(
            jnp.int32(k_off))
    else:
        got = run(flash_attention_lse, k_off)
    _close(got, ref, 2e-4)


@pytest.mark.parametrize("t_q,t_k,q_off", [(128, 384, 256), (384, 128, 0),
                                           (256, 512, 100)])
def test_flash_rectangular(t_q, t_k, q_off):
    """T_q != T_k: forward and gradients (dq loops over T_k, dk/dv over
    T_q)."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, t_q, 2, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, t_k, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, t_k, 2, 64), jnp.float32)

    def run(attend):
        def loss(q, k, v):
            o = attend(q, k, v, causal=True, q_offset=q_off)
            return (o ** 2).sum(), o
        (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                           has_aux=True)(q, k, v)
        return (o, *grads)

    _close(run(flash_attention),
           run(functools.partial(attn.local_attention, impl="jnp")), 2e-4)


@pytest.mark.parametrize("t,blocks", [(256, 256), (1024, 512), (2048, 512),
                                      (384, 128), (96, 96)])
def test_flash_every_block_size_the_code_chooses(t, blocks):
    """The blocks are chosen from the length: each choice against the jnp
    path, forward and backward, in bfloat16 at the cells' head width."""
    import importlib

    fa = importlib.import_module("ompi_tpu.ops.flash_attention")
    assert fa._block(t) == blocks
    q, k, v = _qkv(b=1, t=t, h=1, d=128, dtype=jnp.bfloat16, seed=t)

    def run(attend):
        def loss(q, k, v):
            o = attend(q, k, v, causal=True)
            return (o.astype(jnp.float32) ** 2).sum(), o
        (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                           has_aux=True)(q, k, v)
        return (o, *grads)

    got = run(flash_attention)
    ref = run(functools.partial(attn.local_attention, impl="jnp"))
    for a, b in zip(got, ref):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= 3e-2 * max(1.0, np.abs(b).max())


@pytest.mark.parametrize("bq,bk,n", [(128, 128, 4), (512, 128, 3),
                                     (128, 512, 3), (256, 256, 2)])
def test_flash_loop_bounds_against_a_direct_count(bq, bk, n):
    """The bounds of the kernels' loops, for every offset between the two
    sequences: which blocks are wholly visible, crossed by the diagonal,
    wholly masked."""
    import importlib

    fa = importlib.import_module("ompi_tpu.ops.flash_attention")
    for rel in range(-n * max(bq, bk) - 3, n * max(bq, bk) + 4, 7):
        # forward / dq: the q block's first row is rel past the first key
        rows = rel + np.arange(bq)[:, None]
        vis = np.stack([(rows >= j * bk + np.arange(bk)[None, :])
                        for j in range(n)])
        full, end = (int(x) for x in fa._k_block_bounds(
            jnp.int32(rel), bq, bk, n))
        assert vis[:full].all() and not vis[end:].any(), rel
        assert all(v.any() and not v.all() for v in vis[full:end]), rel
        # dk/dv: the k block's first key is rel past the first query row
        cols = rel + np.arange(bk)[None, :]
        vis = np.stack([(i * bq + np.arange(bq)[:, None] >= cols)
                        for i in range(n)])
        start, full = (int(x) for x in fa._q_block_bounds(
            jnp.int32(rel), bq, bk, n))
        assert not vis[:start].any() and vis[full:].all(), rel
        assert all(v.any() and not v.all() for v in vis[start:full]), rel
