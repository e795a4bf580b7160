"""Sequence-parallel attention: ring attention and all-to-all (Ulysses-style).

Long-context support is first-class (SURVEY.md §5): a sequence longer than
one chip's HBM is sharded over a mesh axis, and attention runs either as

- **ring attention** — K/V blocks rotate around the ``sp`` ring via
  ``ppermute`` while each device accumulates its queries' attention with an
  online (flash-style) softmax.  Communication shape = the reference's
  segmented-ring allreduce (coll_base_allreduce.c:615): p-1 neighbor hops of
  1/p of the data, overlapped with compute by XLA. O(T_local²·sp) FLOPs,
  O(T_local) memory.
- **all-to-all (Ulysses)** — one ``all_to_all`` re-shards from
  sequence-sharded to head-sharded, full attention runs locally, and a
  second ``all_to_all`` restores sequence sharding.  Communication shape =
  pairwise alltoall (coll_base_alltoall.c:132). Needs heads % sp == 0.

Both are exact (not approximations) and differentiable; tests cross-check
them against gathered full attention on the virtual CPU mesh.
"""

from __future__ import annotations

from typing import Optional

__all__ = ["local_attention", "local_attention_lse", "ring_attention",
           "ulysses_attention", "gathered_attention", "local_impl",
           "layout_impl"]

_NEG = -1e30


# Keys one device attends over from which "auto" takes the kernels.  Not the
# length from which the kernels are faster: on a v5e they beat the jnp path
# from 256 positions standalone, and a train step of cell 1's model at the
# same tokens takes 358.6 against 452.8 ms at 16 x 1024 and 352.6 against
# 405.4 at 32 x 512 (at 8 x 2048: 369.3 against 611.5; PERF.md section 6,
# PR 28, chip call 26).  It is the length under which the one forward-only
# program the records hold, the decoders' 1024-token prefill, gains 1 ms a
# layer, while a process's first pallas kernel costs it 0.9 s of start-up,
# 0.75 s of that the import of jax.experimental.pallas (ledger, PR 27: cell
# 2's ttft_ms -6 ms, its setup_s +1.16 s against a bound of 0.466 s).  A
# length cannot tell a trainer from a prefill, so a trainer under 2048
# positions stays on the jnp path too: PERF.md section 7 has what that costs.
_FLASH_FROM_KEYS = 2048

# Head widths the kernels have run at on the chip: a head of 128 lanes is a
# column block of (B, T, H*D); narrower ones are transposed through HBM.
_FLASH_MAX_HEAD_DIM = 128


def local_impl(impl: str, q_shape, k_shape, dtype,
               platform: Optional[str] = None) -> str:
    """Which local attention runs for q (B, Tq, H, D) over k (B, Tk, H, D)
    of ``dtype`` on one device: ``"flash"`` or ``"jnp"``.

    "jnp" and "flash" are taken at their word (the kernels raise for lengths
    no block tiles).  "auto" is the rule, from shape and platform alone:
    the pallas kernels, forward and backward, where the devices are TPUs,
    the keys are ``_FLASH_FROM_KEYS`` or more, the lengths tile, the head is
    no wider than ``_FLASH_MAX_HEAD_DIM`` and a sequence's K and V (in the
    backward Q and dO) fit the kernels' whole-sequence VMEM block; the jnp
    path otherwise.  ``platform`` is that of the devices the program is
    built for (a mesh's, attached or only described: the layouts pass their
    communicator's); None asks the default backend.  Callers that must know
    which path ran (chip_smoke.py) ask here instead of re-deriving the rule.
    """
    import jax

    from ompi_tpu.ops.flash_attention import flash_tiles, whole_seq_fits

    if impl == "jnp":
        return "jnp"
    if impl not in ("auto", "flash"):
        raise ValueError(f"unknown attention impl {impl!r}")
    if impl == "flash":
        return "flash"
    (_, t_q, _, d), t_k = q_shape, k_shape[1]
    takes = ((platform or jax.default_backend()) == "tpu"
             and t_k >= _FLASH_FROM_KEYS and flash_tiles(t_q, t_k)
             and d <= _FLASH_MAX_HEAD_DIM
             and whole_seq_fits(max(t_q, t_k), d, dtype))
    return "flash" if takes else "jnp"


def layout_impl(comm, layout: str, q_shape, k_shape, dtype,
                axis: Optional[str] = None, impl: str = "auto") -> str:
    """:func:`local_impl` for what one device attends over when
    ``<layout>_attention`` is given q and k of these shapes a device, on the
    platform of the devices ``comm``'s mesh is made of: a ring hop's own
    pieces, all positions of its share of the heads after the ulysses
    re-shard, its own queries against all keys when gathered.  The three
    layouts ask here, and so does a caller that prepares their operands
    (the model's rotary embedding), so the rule exists once."""
    sp = int(comm.mesh.shape[axis or comm.axes[-1]])
    (b, t_q, h, d), t_k = q_shape, k_shape[1]
    if layout == "ulysses":
        q_shape, k_shape = (b, t_q * sp, h // sp, d), (b, t_k * sp, h // sp, d)
    elif layout == "gathered":
        k_shape = (b, t_k * sp, h, d)
    elif layout != "ring":
        raise ValueError(f"unknown attention layout {layout!r}")
    return local_impl(impl, q_shape, k_shape, dtype,
                      comm.mesh.devices.flat[0].platform)


def local_attention(q, k, v, causal: bool = True,
                    q_offset=0, k_offset=0, scale: Optional[float] = None,
                    impl: str = "auto"):
    """Plain attention over local blocks; offsets give global positions for
    causal masking when the blocks are slices of a longer sequence (they
    may be traced int32 scalars — e.g. a ring hop's source index).

    Shapes: q (B, Tq, H, D), k/v (B, Tk, H, D) → (B, Tq, H, D).

    ``impl``: "flash" = the pallas blockwise kernel (ompi_tpu.ops),
    "jnp" = materialized scores, "auto" = see :func:`local_impl`.
    """
    o, _ = local_attention_lse(q, k, v, causal=causal, q_offset=q_offset,
                               k_offset=k_offset, scale=scale, impl=impl)
    return o.astype(q.dtype)


def local_attention_lse(q, k, v, causal: bool = True,
                        q_offset=0, k_offset=0,
                        scale: Optional[float] = None, impl: str = "auto"):
    """:func:`local_attention` that also returns the (B, H, Tq) f32
    logsumexp — the merge state for combining partial attention blocks
    (ring hops).  Output dtype follows q for flash, f32 for jnp."""
    import jax.numpy as jnp

    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if local_impl(impl, q.shape, k.shape, q.dtype) == "flash":
        from ompi_tpu.core.scopes import scope
        from ompi_tpu.ops.flash_attention import flash_attention_lse

        with scope("attention.flash"):
            return flash_attention_lse(q, k, v, causal=causal,
                                       q_offset=q_offset, k_offset=k_offset,
                                       scale=scale)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = k_offset + jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask[None, None], scores, _NEG)
    m = scores.max(axis=-1)                                   # (B,H,Tq)
    w = jnp.exp(scores - m[..., None])
    if causal:
        w = jnp.where(mask[None, None], w, 0.0)
    l = w.sum(axis=-1)
    safe_l = jnp.maximum(l, 1e-30)
    o = jnp.einsum("bhqk,bkhd->bqhd", w.astype(q.dtype), v,
                   preferred_element_type=jnp.float32)
    o = o / safe_l.transpose(0, 2, 1)[..., None]
    return o, m + jnp.log(safe_l)


def ring_attention(comm, q, k, v, axis: Optional[str] = None,
                   causal: bool = True, scale: Optional[float] = None,
                   impl: str = "auto"):
    """Exact attention over a sequence sharded along ``axis`` of
    ``comm.mesh``; call inside shard_map.

    Each step attends my queries against the currently-held K/V block —
    through the pallas flash kernel on TPU (``impl="auto"``; the hop's
    traced source index feeds the kernel's k_offset) — then rotates K/V
    one hop around the ring (device r → r+1), so after sp steps every
    (query, key) pair has met.  Hop results are merged by their logsumexp
    (out' = out·σ(lse) + out_i·σ(lse_i), σ = softmax over hop lse), the
    blockwise-attention identity; everything accumulates in float32.
    """
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import coll, scope

    ax = axis or comm.axes[-1]
    sp = int(comm.mesh.shape[ax])
    B, T, H, D = q.shape
    impl = layout_impl(comm, "ring", q.shape, k.shape, q.dtype, ax, impl)
    if sp == 1:  # degenerate ring: skip the loop machinery entirely
        return local_attention(q, k, v, causal=causal, scale=scale,
                               impl=impl)
    my = lax.axis_index(ax)
    scale = scale if scale is not None else D ** -0.5
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def step(i, carry):
        out, lse, k_cur, v_cur = carry
        src = (my - i) % sp  # whose block I currently hold
        o_i, lse_i = local_attention_lse(
            q, k_cur, v_cur, causal=causal, q_offset=my * T,
            k_offset=src * T, scale=scale, impl=impl)
        lse_new = jnp.logaddexp(lse, lse_i)               # (B,H,Tq)
        c_old = jnp.exp(lse - lse_new)
        c_new = jnp.exp(lse_i - lse_new)
        # (B,H,Tq) coefficients against (B,Tq,H,D) outputs
        out = (out * c_old.transpose(0, 2, 1)[..., None]
               + o_i.astype(jnp.float32)
               * c_new.transpose(0, 2, 1)[..., None])
        with coll("permute", ax):
            k_nxt = lax.ppermute(k_cur, ax, perm)
            v_nxt = lax.ppermute(v_cur, ax, perm)
        return (out, lse_new, k_nxt, v_nxt)

    with scope("attention.ring"):
        out0 = jnp.zeros((B, T, H, D), jnp.float32)
        lse0 = jnp.full((B, H, T), _NEG, jnp.float32)
        out, _, _, _ = lax.fori_loop(0, sp, step, (out0, lse0, k, v))
        return out.astype(q.dtype)


def ulysses_attention(comm, q, k, v, axis: Optional[str] = None,
                      causal: bool = True, scale: Optional[float] = None,
                      impl: str = "auto"):
    """All-to-all sequence parallelism: re-shard seq→heads, attend fully
    locally, re-shard back.  Exact; one alltoall each way.  The local
    attention is :func:`local_attention`'s, ``impl`` resolved for the
    platform of ``comm``'s mesh."""
    from jax import lax

    from ompi_tpu.core.scopes import coll, scope

    ax = axis or comm.axes[-1]
    sp = int(comm.mesh.shape[ax])
    H = q.shape[2]
    if H % sp:
        raise ValueError(f"ulysses needs heads ({H}) divisible by sp ({sp})")
    impl = layout_impl(comm, "ulysses", q.shape, k.shape, q.dtype, ax, impl)
    if sp == 1:
        # degenerate axis: a single-participant all_to_all still lowers
        # to a channel op (copy + scheduling barrier, 4 per layer) —
        # skip the resharding entirely
        return local_attention(q, k, v, causal=causal, scale=scale,
                               impl=impl)
    with scope("attention.ulysses"):
        # (B, T/sp, H, D) → (B, T, H/sp, D)
        with coll("alltoall", ax):
            q2, k2, v2 = [lax.all_to_all(t, ax, split_axis=2, concat_axis=1,
                                         tiled=True) for t in (q, k, v)]
        o = local_attention(q2, k2, v2, causal=causal, scale=scale,
                            impl=impl)
        # (B, T, H/sp, D) → (B, T/sp, H, D)
        with coll("alltoall", ax):
            return lax.all_to_all(o, ax, split_axis=1, concat_axis=2,
                                  tiled=True)


def gathered_attention(comm, q, k, v, axis: Optional[str] = None,
                       causal: bool = True, scale: Optional[float] = None,
                       impl: str = "auto"):
    """Reference implementation: allgather K/V and attend (O(T) memory per
    device — the thing ring attention exists to avoid). Used for testing."""
    import jax.numpy as jnp
    from jax import lax

    ax = axis or comm.axes[-1]
    sp = int(comm.mesh.shape[ax])
    T = q.shape[1]
    impl = layout_impl(comm, "gathered", q.shape, k.shape, q.dtype, ax,
                       impl)
    if sp == 1:
        return local_attention(q, k, v, causal=causal, scale=scale,
                               impl=impl)
    from ompi_tpu.core.scopes import coll

    my = lax.axis_index(ax)
    with coll("allgather", ax):
        k_all = lax.all_gather(k, ax, axis=1, tiled=True)
        v_all = lax.all_gather(v, ax, axis=1, tiled=True)
    return local_attention(q, k_all, v_all, causal=causal,
                           q_offset=my * T, k_offset=0, scale=scale,
                           impl=impl)
