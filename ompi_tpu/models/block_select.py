"""Block-selected grouped-query attention (InfLLM-v2): every K/V head's
group of query heads attends to ``topk`` blocks of ``block`` positions, found
over mean-pooled keys; the mixer of a ``models/plan.py`` layer of kind
"block_select".

From the block's input ``h`` and its norm ``x = RMSNorm(h; ln1)``, query head
``a`` in group ``g = a // r`` over K/V head ``g`` (``r`` query heads a K/V
head), no rotary embedding, the query at position ``t``:

    q = x wq  (heads x hd),   k = x wk,  v = x wv  (K/V heads x hd)
    c_j    = mean(k_{stride j} .. k_{stride j + kernel - 1})     every j whose
             kernel is complete, ``stride j + kernel - 1 <= t``
    p_a    = softmax_j(q_{t,a} . c_j hd^-1/2)                    over those j
    R_g(b) = max over the kernels j that overlap block b of sum_{a in g} p_a(j)
             (0 where none is complete)
    B_t    = the first ``init_blocks`` blocks, the blocks of positions
             ``t - window + 1 .. t``, and the blocks of largest R_g that start
             at or before t, ties to the lower block, until ``topk`` in all
             (all of them while fewer exist)
    y_{t,a} = softmax_{s <= t, s in a block of B_t}(q_{t,a} . k_s hd^-1/2) v_s
    h     += r_b (y_t * sigmoid(x wz)) wo

with ``r_b`` the plan's ``branch_factor``.  A sequence of at most
``dense_len`` positions attends densely: a whole-sequence pass by its
length, a cached step by its cache's.  The selection has no weights and
passes no gradient (it is a set).

What a decoder carries for a layer (:func:`buffers`), both in the compute
type and heads before positions, so that a (sequence, K/V head) pair is a
run of rows that a kernel reads as a sequence of its own under that head's
own mask: K and V as one row a position, ``(B, Hkv, t_max, 2 hd)``, and the
pooled keys, ``(B, Hkv, pooled, hd)``, one for every ``stride`` positions
(:func:`pooled_count`): two buffers that grow, at different lengths.

Two paths call the same :func:`block_scores` and :func:`chosen`.  **Whole
sequences** (trainer, prefill): :func:`attend`, every pooled key first, then
a slice of ``q_slice`` queries at a time, the slices the iterations of a
``lax.scan`` over one shape: a slice's scores against the sequence's pooled
keys (its positions mask those not yet complete), its blocks, attention
under the blocks' mask widened to positions, which the kernel stops reading
at the slice's end.  **One position against the carry**
(:func:`attend_cached`): the row and, where the position completes a
kernel, the pooled key written first; the scores against the layer's pooled
keys, the blocks, and the layer's rows streamed once under the mask.  The
set is found by ``sparse_index.select`` (a threshold by bisection, exact,
ties included) over blocks, with the forced blocks scored infinite.

Everything here is ``jax.numpy`` and ``lax`` but attention under a mask,
which on TPUs is a pallas kernel where the sizes tile:
``ops/masked_attention.py`` for whole sequences, ``ops/selected_attention.py``
in a cached step (both read a K/V head once for all its query heads; here a
K/V head is a sequence of its own, so each reads under that head's mask).
A step streams the layer (``Tmax`` rows a sequence and head) and does not
fetch the ``topk`` blocks by number: that is a kernel with a prefetched
index map, which is not built (``ROADMAP.md``).

Nothing imports this module but a configuration whose plan has the kind.
"""

from __future__ import annotations

import dataclasses

__all__ = ["BlockSelect", "mixer", "attend", "attend_cached", "pool_keys",
           "pooled_count", "block_scores", "group_sum", "forced", "chosen",
           "written_pooled", "leaf_shapes", "buffers", "POSITIONED"]

POSITIONED = True       # a cached step's carry ends with its position


@dataclasses.dataclass(frozen=True)
class BlockSelect:
    """The selection's sizes, under MiniCPM4's ``sparse_config`` names."""
    kernel: int         # kernel_size: positions a pooled key is the mean of
    stride: int         # kernel_stride: positions from one kernel to the next
    block: int          # block_size: positions a selected block
    topk: int           # blocks a query attends to, the forced ones among them
    init_blocks: int    # leading blocks every query attends to
    window: int         # window_size: the last positions, whose blocks are forced
    dense_len: int      # a sequence this long or shorter attends densely
    q_slice: int = 512  # queries a slice of the whole-sequence path

    def __post_init__(self):
        if self.kernel % self.stride or self.block % self.stride:
            raise ValueError(
                f"a kernel of {self.kernel} and a block of {self.block} "
                f"positions are no multiples of the stride {self.stride}")


def leaf_shapes(cfg, bs: BlockSelect) -> dict:
    """One layer's leaves: name -> (shape, deviation of the program's own
    initializer or None for ones).  The gate is ``wz`` (a ``wg`` is a
    router's)."""
    D, q, kv = (cfg.d_model, cfg.n_heads * cfg.head_dim,
                cfg.kv_heads * cfg.head_dim)
    return {"wq": ((D, q), D ** -0.5), "wk": ((D, kv), D ** -0.5),
            "wv": ((D, kv), D ** -0.5), "wz": ((D, q), D ** -0.5),
            "wo": ((q, D), q ** -0.5 / max(1, 2 * cfg.n_layers) ** 0.5)}


def pooled_count(bs: BlockSelect, positions: int) -> int:
    """The kernels complete within ``positions`` positions."""
    return max(0, (positions - bs.kernel) // bs.stride + 1)


def buffers(cfg, bs: BlockSelect, batch: int, t_max: int) -> tuple:
    """What a decoder carries for one layer (``models/plan.py``'s form): a
    position's K head and then its V head in one row, ``(B, Hkv, t_max, 2
    hd)``, and the pooled keys ``(B, Hkv, pooled_count(t_max), hd)``, both
    in the compute type, both growing along the carry's axis 3."""
    hkv, hd = cfg.kv_heads, cfg.head_dim
    return (((batch, hkv, t_max, 2 * hd), cfg.compute_dtype, 3),
            ((batch, hkv, pooled_count(bs, t_max), hd), cfg.compute_dtype, 3))


def pool_keys(bs: BlockSelect, k):
    """Every complete kernel's mean of the keys k (B, Hkv, T, hd): (B, Hkv,
    pooled_count(T), hd) float32.  Sums over ``stride`` positions first, then
    ``kernel / stride`` of those a kernel."""
    import jax.numpy as jnp

    B, hkv, T, hd = k.shape
    n, per = pooled_count(bs, T), bs.kernel // bs.stride
    parts = k[:, :, :(n + per - 1) * bs.stride].astype(jnp.float32).reshape(
        B, hkv, n + per - 1, bs.stride, hd).sum(axis=3)
    return sum(parts[:, :, o:o + n] for o in range(per)) / bs.kernel


def group_sum(p):
    """A group's score of each kernel: its query heads' probabilities (B,
    Hkv, r, Tq, J) summed."""
    return p.sum(axis=2)


def block_scores(bs: BlockSelect, q, pooled, t, blocks: int):
    """``R_g(b)`` of the queries q (B, Hkv, r, Tq, hd) at positions t (Tq,)
    against the pooled keys (B, Hkv, J, hd), whatever lies in the rows of
    kernels not yet complete: (B, Hkv, Tq, blocks) float32, at least 0."""
    import jax
    import jax.numpy as jnp

    J, hd = pooled.shape[2], q.shape[-1]
    s = jnp.einsum("bgrqd,bgjd->bgrqj", q, pooled.astype(q.dtype),
                   preferred_element_type=jnp.float32) * hd ** -0.5
    complete = (bs.stride * jnp.arange(J) + bs.kernel - 1) <= t[:, None]
    p = jnp.where(complete, jax.nn.softmax(
        jnp.where(complete, s, -1e30), axis=-1), 0.0)
    scores = group_sum(p)                                   # (B, Hkv, Tq, J)
    # block b's kernels: ratio b + first .. ratio b + last
    ratio = bs.block // bs.stride
    first, last = -((bs.kernel - 1) // bs.stride), (bs.block - 1) // bs.stride
    need = last - first + ratio * (blocks - 1) + 1
    scores = jnp.pad(scores, [(0, 0)] * 3 + [(-first, max(
        0, need + first - J))])
    return jnp.stack([scores[..., o:o + ratio * (blocks - 1) + 1:ratio]
                      for o in range(last - first + 1)]).max(axis=0)


def forced(bs: BlockSelect, t, blocks: int):
    """The blocks every query at positions t (Tq,) attends to: the first
    ``init_blocks`` and those of its last ``window`` positions; (Tq, blocks)
    bool."""
    import jax.numpy as jnp

    start = bs.block * jnp.arange(blocks)
    t = t[:, None]
    return ((jnp.arange(blocks) < bs.init_blocks)
            | ((start + bs.block - 1 >= t - bs.window + 1) & (start <= t)))


def chosen(bs: BlockSelect, scores, t):
    """``B_t`` of the block scores (B, Hkv, Tq, blocks) of queries at
    positions t (Tq,): bool of the same shape, ``topk`` blocks a row or
    every block that starts at or before t where those are fewer."""
    import jax.numpy as jnp

    from ompi_tpu.models.sparse_index import select

    blocks = scores.shape[-1]
    live = bs.block * jnp.arange(blocks) <= t[:, None]
    return select(jnp.where(forced(bs, t, blocks), jnp.inf, scores), live,
                  bs.topk)


def _positions(bs: BlockSelect, blocks, t, keys: int):
    """The blocks' mask (..., Tq, blocks) widened to the first ``keys``
    positions, each query's own position the last it may see."""
    import jax.numpy as jnp

    return (jnp.repeat(blocks, bs.block, axis=-1)[..., :keys]
            & (jnp.arange(keys) <= t[:, None]))


def _attention(q, k, v, mask, kernel: bool, k_len=None):
    """Softmax attention of q (N, Tq, r, hd) over k, v (N, Tk, 1, hd) under
    mask (N, Tq, Tk), in q's type: the pallas kernel where ``kernel``, with
    the jnp form's backward pass behind it (the kernel has none).  ``k_len``
    (a traced int32) is a length from which the mask allows no key: the
    kernel stops there, the jnp form and the backward pass have the mask."""
    import jax
    import numpy as np

    from ompi_tpu.models.sparse_index import _grouped_attention

    def plain(q, k, v, mask):
        return _grouped_attention(q, k, v, mask).astype(q.dtype)

    if not kernel:
        return plain(q, k, v, mask)
    from ompi_tpu.ops.masked_attention import masked_attention

    lengths = () if k_len is None else (k_len,)

    @jax.custom_vjp
    def attention(q, k, v, mask, *lengths):
        return masked_attention(q, k, v, mask, *lengths)

    def fwd(q, k, v, mask, *lengths):
        return attention(q, k, v, mask, *lengths), (q, k, v, mask)

    def bwd(saved, g):
        *qkv, mask = saved
        return (*jax.vjp(lambda *a: plain(*a, mask), *qkv)[1](g),
                np.zeros(mask.shape, jax.dtypes.float0),
                *(np.zeros((), jax.dtypes.float0) for _ in lengths))

    attention.defvjp(fwd, bwd)
    return attention(q, k, v, mask, *lengths)


def attend(bs: BlockSelect, q, k, v):
    """Attention of whole sequences from position 0 under the selection, of
    q (B, T, H, hd) and k, v (B, T, Hkv, hd).  Returns ``(o, rows, pooled)``:
    the context (B, T, H, hd) in q's type and what a decoder carries, the
    rows (B, Hkv, T, 2 hd) and the pooled keys (B, Hkv, pooled_count(T),
    hd) in q's type.

    A slice of ``q_slice`` queries at a time.  The whole slices are the
    iterations of a ``lax.scan`` over one shape, so that a prompt of any
    length traces, lowers and compiles a slice once: the slice's queries
    against the sequence's every key, pooled key and block, which ``t``
    masks down to those so far, and attention that stops at the slice's end
    (``masked_attention``'s ``k_len``).  A sequence of at most ``dense_len``
    positions, and a slice that ends within the first ``topk`` blocks,
    attends to every earlier position and computes no score: those slices
    are a scan of their own, before the selected ones.  A tail shorter than
    ``q_slice`` is one more call, against the keys it has."""
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.ops import _chip
    from ompi_tpu.ops.masked_attention import tiles

    B, T, H, hd = q.shape
    hkv = k.shape[2]
    r = H // hkv
    kh, vh = (y.swapaxes(1, 2) for y in (k, v))             # (B, Hkv, T, hd)
    with scope("blocks.pool"):
        pooled = pool_keys(bs, kh).astype(q.dtype)
    q5 = q.reshape(B, T, hkv, r, hd)
    k1, v1 = (y.reshape(B * hkv, T, 1, hd) for y in (kh, vh))

    def slice_(qs, t, select: bool, k_len=None):
        """The context (B, Tq, H, hd) of the queries qs (B, Tq, Hkv, r, hd)
        at positions t (Tq,), which mask the sequence's keys, pooled keys
        and blocks down to those so far."""
        n = qs.shape[1]
        if select:
            with scope("blocks.score"):
                scores = block_scores(
                    bs, lax.stop_gradient(jnp.moveaxis(qs, 1, 3)),
                    lax.stop_gradient(pooled), t, -(-T // bs.block))
            with scope("blocks.select"):
                mask = _positions(bs, chosen(bs, scores, t), t, T)
        else:
            mask = jnp.broadcast_to(jnp.arange(T) <= t[:, None],
                                    (B, hkv, n, T))
        with scope("attention"):
            o = _attention(
                jnp.moveaxis(qs, 1, 2).reshape(B * hkv, n, r, hd), k1, v1,
                mask.reshape(B * hkv, n, T),
                kernel=_chip._traced_for_tpus() and tiles(n, hd),
                k_len=k_len)
            return jnp.moveaxis(o.reshape(B, hkv, n, r, hd), 1, 2).reshape(
                B, n, H, hd)

    def whole(o, first: int, count: int, select: bool):
        """``o`` (B, T, H, hd) with the slices ``first .. first + count -
        1`` written."""
        def one(o, lo):
            new = slice_(lax.dynamic_slice_in_dim(q5, lo, bs.q_slice, axis=1),
                         lo + jnp.arange(bs.q_slice), select, lo + bs.q_slice)
            return lax.dynamic_update_slice_in_dim(o, new, lo, axis=1), None

        return lax.scan(one, o,
                        bs.q_slice * jnp.arange(first, first + count))[0]

    slices = T // bs.q_slice
    # the slices that end within the first topk blocks, or all of a sequence
    # that attends densely
    dense = slices if T <= bs.dense_len else min(
        slices, bs.topk * bs.block // bs.q_slice)
    o = jnp.zeros((B, T, H, hd), q.dtype) if slices else None
    for first, count, select in ((0, dense, False),
                                 (dense, slices - dense, True)):
        if count:
            o = whole(o, first, count, select)
    if T % bs.q_slice:
        lo = slices * bs.q_slice
        tail = slice_(q5[:, lo:], jnp.arange(lo, T),
                      T > bs.dense_len and T > bs.topk * bs.block)
        o = tail if o is None else lax.dynamic_update_slice_in_dim(
            o, tail, lo, axis=1)
    return o, jnp.concatenate([kh, vh], axis=-1), pooled


def written_pooled(bs: BlockSelect, rows, pooled, pos):
    """The pooled keys (B, Hkv, J, hd) after position ``pos``, whose row is
    already in rows (B, Hkv, Tmax, 2 hd): where ``pos`` completes a kernel,
    that kernel's mean written at its place; as they were anywhere else."""
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope

    B, hkv, J, hd = pooled.shape
    if not J:
        return pooled
    with scope("blocks.pool"):
        start = pos + 1 - bs.kernel
        complete = (start >= 0) & (start % bs.stride == 0)
        window = lax.dynamic_slice(rows, (0, 0, jnp.maximum(start, 0), 0),
                                   (B, hkv, bs.kernel, hd))
        mean = window.astype(jnp.float32).mean(axis=2, keepdims=True)
        at = jnp.clip(start // bs.stride, 0, J - 1)
        was = lax.dynamic_slice(pooled, (0, 0, at, 0), (B, hkv, 1, hd))
        new = jnp.where(complete, mean.astype(pooled.dtype), was)
    with scope("kv_cache"):
        return lax.dynamic_update_slice(pooled, new, (0, 0, at, 0))


def attend_cached(bs: BlockSelect, q, rows, pooled, pos):
    """One new position's attention against the carry: q (B, 1, H, hd); rows
    (B, Hkv, Tmax, 2 hd) and pooled (B, Hkv, J, hd) with the position's own
    row, and the pooled key it may complete, already written.  (B, 1, H, hd)
    float32.  A cache of at most ``dense_len`` positions is read whole."""
    import jax.numpy as jnp

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.ops import _chip
    from ompi_tpu.models.sparse_index import _grouped_attention
    from ompi_tpu.ops.selected_attention import selected_attention, tiles

    B, _, H, hd = q.shape
    hkv, Tmax = rows.shape[1], rows.shape[2]
    qh = q.reshape(B, hkv, H // hkv, 1, hd)
    t = pos[None]
    mask = jnp.broadcast_to(jnp.arange(Tmax) <= pos, (B, hkv, 1, Tmax))
    if Tmax > bs.dense_len:
        with scope("blocks.score"):
            scores = block_scores(bs, qh, pooled, t, -(-Tmax // bs.block))
        with scope("blocks.select"):
            mask = _positions(bs, chosen(bs, scores, t), t, Tmax)
    q1 = qh.reshape(B * hkv, 1, H // hkv, hd)
    with scope("attention"):
        if _chip._traced_for_tpus() and tiles(Tmax, hd):
            o = selected_attention(
                q1, rows.reshape(1, B * hkv, Tmax, 2 * hd),
                mask.reshape(B * hkv, Tmax), jnp.int32(0))
        else:
            r1 = rows.reshape(B * hkv, Tmax, 1, 2 * hd)
            o = _grouped_attention(q1.astype(rows.dtype), r1[..., :hd],
                                   r1[..., hd:],
                                   mask.reshape(B * hkv, 1, Tmax))
    return o.reshape(B, 1, H, hd).astype(jnp.float32)


def mixer(cfg, lp, h, carry=None):
    """One layer's mixer on the block's input ``h`` (B, T, D): the norm,
    attention and the residual add.

    ``carry`` None: whole sequences from position 0; returns ``(h, rows,
    pooled)``, every position's row ``(B, Hkv, T, 2 hd)`` and every complete
    kernel's pooled key ``(B, Hkv, pooled_count(T), hd)`` in h's type.
    ``carry = (rows, pooled, pos)``: T == 1, the new position ``pos`` against
    this layer's own buffers, its row and the pooled key it completes written
    in place first; returns ``(h, rows, pooled)``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.models import transformer as tfm

    bs, cdt = cfg.plan.block_select, h.dtype
    B, T, _ = h.shape
    H, hkv, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim

    def proj(y, name):
        return jnp.einsum("btd,df->btf", y, lp[name].astype(cdt))

    with scope("attn_proj"):
        x = tfm._rmsnorm(h, lp["ln1"], cfg.norm_eps)
        q = proj(x, "wq").reshape(B, T, H, hd)
        k, v = (proj(x, name).reshape(B, T, hkv, hd) for name in ("wk", "wv"))
    if carry is None:
        o, rows, pooled = attend(bs, q, k, v)
    else:
        rows, pooled, pos = carry
        with scope("kv_cache"):
            rows = lax.dynamic_update_slice(
                rows, jnp.concatenate([k, v], axis=-1).swapaxes(1, 2).astype(
                    rows.dtype), (0, 0, pos, 0))
        pooled = written_pooled(bs, rows, pooled, pos)
        o = attend_cached(bs, q, rows, pooled, pos)
    with scope("attn_proj"):
        o = o.reshape(B, T, H * hd).astype(jnp.float32) * jax.nn.sigmoid(
            proj(x, "wz").astype(jnp.float32))
        return (h + proj(o.astype(cdt), "wo") * cfg.plan.branch_factor,
                rows, pooled)
