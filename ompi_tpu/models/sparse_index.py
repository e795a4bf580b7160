"""Learned sparse attention: an index picks the cached positions a query
attends to.

``TransformerConfig.index`` holds a :class:`SparseIndex`; attention
(``models/block.mixer``) then reads, for the query at position ``t``, the
set ``S_t`` alone:

    qI = x Wiq  (heads x width),  kI = LayerNorm(x Wik)  (one key),
    wI = (x Wiw) * heads^-1/2 * width^-1/2,  rotary embedding on qI and kI
    I[t, s] = sum_j wI[t, j] * relu(qI[t, j] . kI[s])         for s <= t
    S_t = every s <= t while t < topk, else the topk positions of the
          largest I[t, .], ties to the lower position (``lax.top_k``'s order)
    o_t = softmax_{s in S_t}(q_t . k_s / sqrt(hd)) v_s

with ``x`` the block's normed input.  This module has what is the index's
own: its sizes, its leaves, and the two paths that call the same
:func:`project` and :func:`scores`.  **Whole sequences** (trainer, prefill):
:func:`attend`, a slice of ``q_slice`` queries at a time against the keys so
far, so that neither the index's scores nor attention's are ever held for a
whole sequence; the set is found as a threshold, the ``topk``-th largest
score of a row by bisection on the scores' bits (:func:`select`; exact, ties
included), and applied as a mask.  **One position against the carry**
(:func:`carry`: the index keys, and K and V as one row a position):
:func:`attend_cached`, the scores against the layer's index keys, then one
of two reads of the same set.  **The gather:**
``lax.top_k`` and the selected positions' rows gathered out of the carry: a
step reads ``topk`` rows of the layer's K/V, not the layer's K/V.  **The
stream:** the set as a mask (:func:`select`, as the prefill finds it) and the
layer's rows streamed once under it by the pallas kernel
``ops/selected_attention.py``.  A gathered row costs this chip eight times
what a streamed one does, so a step streams where its cache is no more than
``_STREAM_UP_TO`` times its selection, the mesh is of TPUs and the sizes
tile (:func:`streams`, which :func:`row_shape` asks where the carry is laid
out: a streamed carry holds a row flat, ``(L, B, Tmax, 2 Hkv hd)``).  The index
keys are carried with the positions last, ``(L, B, width, Tmax)``, and held
to that layout (:func:`positions_minor`): a step's scores are then one
product of the layer's slice as it lies, where the compiler, left to itself,
stored the stack batch-minor at twice its size and re-laid a layer's keys
every step (PERF.md section 6).  The selection passes no gradient (it is a
set); the index's alignment loss is not built, so a trainer leaves its
leaves where they were.

Everything here is ``jax.numpy`` and ``lax`` but attention under a mask,
which may be a pallas kernel: ``ops/masked_attention.py`` in the prefill
(:func:`attend` says when), ``ops/selected_attention.py`` in a cached step
(:func:`streams`).  Nothing imports this module but a configuration
that has an index, so the other programs' set-up does not pay for it.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["SparseIndex", "sparse_config", "project", "scores", "select",
           "attend", "attend_cached", "streams", "positions_minor",
           "row_shape", "in_rows", "carry", "carried", "init_leaves",
           "leaf_names", "check_mesh"]

# A cached step streams the layer's K/V under a mask where its cache is at
# most this many times its selection, and gathers the selected rows beyond.
# One layer of cell 6 on the chip (64 sequences, 8192 positions, rows of
# 2 KB; ms with the selection; PERF.md section 6, PR 43): the stream 1.65
# whatever the selection (0.21 of it ``select``); the gather with its
# ``lax.top_k`` and its two products 5.61 at topk 4096, 2.97 at 2048, 1.66
# at 1024, 0.72 at 512.  They meet at topk 1024: a cache of 8 selections.
_STREAM_UP_TO = 8


@dataclasses.dataclass(frozen=True)
class SparseIndex:
    """Sizes of the index, under the published configuration's names where
    it has one (``sa_config``)."""
    n_heads: int        # index query heads, over one index key a position
    head_dim: int       # width of an index head and of the key
    topk: int           # positions a query attends to
    q_slice: int        # queries a slice of the whole-sequence path


def sparse_config(**sizes):
    """``entry.config`` of a configuration file with an index: a
    ``TransformerConfig`` from flat keys, the published ``sa_config`` group
    gathered under ``index``."""
    from ompi_tpu.models.transformer import TransformerConfig

    sa = dict(sizes.pop("sa_config"))
    if sa.get("indexer_num_kv_heads", 1) != 1:
        raise ValueError(f"the index has one key a position; sa_config "
                         f"gives {sa['indexer_num_kv_heads']} key heads")
    # a float: 1e7 fits int32, a larger theta (1e11) would not
    sizes["rope_theta"] = float(sizes.get("rope_theta", 10_000))
    return TransformerConfig(
        index=SparseIndex(n_heads=sa["indexer_num_heads"],
                          head_dim=sa["indexer_head_dim"], topk=sa["topk"],
                          q_slice=sa["q_chunk_size"]), **sizes)


def check_mesh(cfg, mesh) -> None:
    """Over ``tp`` the query and K/V heads split as they do without an index
    and the index (one key head, a thousandth of a layer) is computed whole
    on every rank.  Over ``sp`` a query's candidates lie on other ranks, and
    a selection across them is not built; nor is an index beside a mixer."""
    if int(dict(mesh.shape).get("sp", 1)) > 1:
        raise ValueError(
            f"an index (learned sparse attention) runs with sp == 1 only, "
            f"and the mesh has sp={mesh.shape['sp']}: a query selects among "
            f"every earlier position, which sp spreads over ranks")
    if cfg.hybrid is not None:
        raise ValueError("an index beside a hybrid block is not built: the "
                         "decoder's carry holds one or the other")


def leaf_names() -> tuple:
    """The index's leaves, stacked over layers: the three projections and
    the key's LayerNorm (scale, bias)."""
    return ("wiq", "wik", "wiw", "ikn", "ikb")


def init_leaves(cfg, rng) -> dict:
    """The index's own leaves as the model initialises them, float32."""
    ix, L, D = cfg.index, cfg.n_layers, cfg.d_model

    def w(*shape):
        return rng.normal(0, D ** -0.5, size=shape).astype(np.float32)

    return {"wiq": w(L, D, ix.n_heads * ix.head_dim),
            "wik": w(L, D, ix.head_dim), "wiw": w(L, D, ix.n_heads),
            "ikn": np.ones((L, ix.head_dim), np.float32),
            "ikb": np.zeros((L, ix.head_dim), np.float32)}


def project(cfg, lp, x, positions, queries_from=None, ix=None, rotate=None,
            within: str = "index_proj", ends_product: bool = False):
    """The index's queries, key and head weights of the normed input ``x``
    (B, T, D) at ``positions`` (T,): ``qi`` (B, T, heads, width) and ``ki``
    (B, T, width) in x's type, rotated; ``wi`` (B, T, heads) float32,
    scaled.

    An index inside another mixer (``models/mla.py``) says what is its own:
    ``queries_from`` (B, T, R), what the queries are projected from where
    that is not the layer's input (a latent layer's normed query latent: the
    key and the head weights still read ``x``); ``ix``, the index's sizes
    where ``cfg.index`` does not hold them; ``rotate(y, positions)``, the
    rotary embedding of heads y (B, T, heads, width), where it is not the
    configuration's over a head's whole width; ``within``, the scope the
    projections run under (``tests/benchmarks/test_keye_vl2_rows.py`` holds
    the plain names to the one cell whose attention is indexed K/V);
    ``ends_product``, a cached step's: the queries' product ends before the
    split into heads, so that the compiler reads its weight where it lies
    (``mla.mixer`` says what it does otherwise)."""
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.models.transformer import _rope

    ix, f32, cdt = ix or cfg.index, jnp.float32, x.dtype
    B, T, _ = x.shape
    if rotate is None:
        def rotate(y, positions):
            return _rope(y, positions, theta=cfg.rope_theta)
    with scope(within):
        qi = jnp.einsum("btd,df->btf", x if queries_from is None
                        else queries_from, lp["wiq"].astype(cdt))
        if ends_product:
            qi = lax.optimization_barrier(qi)
        ki = jnp.einsum("btd,df->btf", x, lp["wik"].astype(cdt),
                        preferred_element_type=f32)
        ki = ki - ki.mean(axis=-1, keepdims=True)
        ki = ki * lax.rsqrt(jnp.mean(ki * ki, axis=-1, keepdims=True)
                            + cfg.norm_eps)
        ki = (ki * lp["ikn"].astype(f32) + lp["ikb"].astype(f32)).astype(cdt)
        wi = jnp.einsum("btd,dh->bth", x, lp["wiw"].astype(cdt),
                        preferred_element_type=f32)
        wi = wi * (ix.n_heads ** -0.5 * ix.head_dim ** -0.5)
        qi = rotate(qi.reshape(B, T, ix.n_heads, ix.head_dim), positions)
        ki = rotate(ki[:, :, None, :], positions)[:, :, 0]
    return qi, ki, wi


def scores(qi, wi, kt):
    """``I[t, s]`` of queries ``qi`` (B, Tq, heads, width) with head weights
    ``wi`` (B, Tq, heads) against keys ``kt`` (B, width, Tk), positions last
    as the carry holds them: (B, Tq, Tk) float32, every pair (the callers
    mask what lies ahead of a query)."""
    import jax
    import jax.numpy as jnp

    dots = jnp.einsum("bqhd,bdk->bqhk", qi, kt.astype(qi.dtype),
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots) * wi[..., None], axis=2)


def _sortable(x):
    """float32 -> uint32 in the same order."""
    import jax.numpy as jnp
    from jax import lax

    u = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


def select(index_scores, live, k: int):
    """Of float32 ``index_scores`` (..., T): True at each row's ``k`` largest
    among the positions ``live`` (bool, broadcastable) says it may see, ties
    to the lower position, which is the set ``lax.top_k`` returns; at every
    live position of a row that has no more than ``k``.

    No sort: the row's ``k``-th largest score is found bit by bit, 32
    counts of the scores at or over a candidate, and the set is the scores
    over it and the first of those equal to it.  Ties at the threshold want
    a running count along the row, which is made only where some row has one
    (a ``lax.cond``)."""
    import jax.numpy as jnp
    from jax import lax

    T = index_scores.shape[-1]
    live = jnp.broadcast_to(live, index_scores.shape)
    if k >= T:
        return live
    # a live score's key is at least 1, what a row may not see is 0
    key = jnp.where(live, jnp.maximum(_sortable(index_scores), 1),
                    0).astype(jnp.uint32)

    def bit(i, thr):
        candidate = thr | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = jnp.sum(key >= candidate[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, candidate, thr)

    # the largest value that k keys reach: the k-th largest key, or 0 where
    # fewer than k are live
    thr = lax.fori_loop(0, 32, bit, jnp.zeros(key.shape[:-1], jnp.uint32))
    above = key > thr[..., None]
    at = key == thr[..., None]
    spare = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    tied = (jnp.sum(at, axis=-1, dtype=jnp.int32) > spare) & (thr > 0)

    def first_of_the_ties():
        return above | (at & (jnp.cumsum(at, axis=-1, dtype=jnp.int32)
                              <= spare[..., None]))

    chosen = lax.cond(jnp.any(tied), first_of_the_ties, lambda: above | at)
    return chosen & (key > 0)


def _grouped_attention(q, k, v, mask):
    """Softmax attention of q (B, Tq, H, hd) over k, v (B, Tk, Hkv, hd) at
    the pairs ``mask`` (B, Tq, Tk) allows, K/V head g serving the query
    heads ``g * r .. g * r + r - 1`` and read once for them.  Products in
    q's type, sums float32; (B, Tq, H, hd) float32."""
    import jax.numpy as jnp

    B, Tq, H, hd = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, Tq, Hkv, H // Hkv, hd)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    allowed = mask[:, None, None]
    s = jnp.where(allowed, s, -1e30)
    w = jnp.where(allowed, jnp.exp(s - s.max(axis=-1, keepdims=True)), 0.0)
    total = jnp.maximum(w.sum(axis=-1), 1e-30)              # (B, g, r, Tq)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", w.astype(q.dtype), v,
                   preferred_element_type=jnp.float32)
    return (o / jnp.moveaxis(total, 3, 1)[..., None]).reshape(B, Tq, H, hd)


def attend(cfg, lp, x, q, k, v, positions, kernel: bool = False):
    """Attention of whole sequences under the index's selection, from the
    block's normed input ``x`` (B, T, D) and its rotated q (B, T, H, hd) and
    k, v (B, T, Hkv, hd), the heads this device holds.  Returns ``(o, kt)``:
    the context (B, T, H, hd) in q's type and the index keys (B, width, T),
    positions last, which a prefill hands to the decoder's carry beside k
    and v.

    A slice of ``q_slice`` queries at a time against the keys up to the
    slice's end: its index scores, its selection, attention under it.  A
    slice that ends within the first ``topk`` positions selects them all and
    computes no score.  With ``kernel`` attention under the mask is the
    pallas kernel ``ops/masked_attention.py`` where the slice tiles for it
    (it compiles for the TPU alone and has no backward pass: the decoder's
    prefill asks for it); without, the jnp form, which holds a slice's scores
    for every head."""
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope

    ix, T = cfg.index, x.shape[1]
    if kernel:
        from ompi_tpu.ops.masked_attention import masked_attention, tiles
    qi, ki, wi = project(cfg, lp, x, positions)
    kt = ki.swapaxes(1, 2)
    at = jnp.arange(T)
    out = []
    for lo in range(0, T, ix.q_slice):
        hi = min(T, lo + ix.q_slice)
        mask = (at[:hi] <= at[lo:hi, None])[None]           # (1, tq, hi)
        if hi > ix.topk:
            with scope("index.score"):
                found = lax.stop_gradient(
                    scores(qi[:, lo:hi], wi[:, lo:hi], kt[:, :, :hi]))
            with scope("index.select"):
                mask = select(found, mask, ix.topk)
        mask = jnp.broadcast_to(mask, (x.shape[0], hi - lo, hi))
        with scope("attention"):
            attention = (masked_attention if kernel and tiles(
                hi - lo, q.shape[-1]) else _grouped_attention)
            out.append(attention(q[:, lo:hi], k[:, :hi], v[:, :hi],
                                 mask).astype(q.dtype))
    return jnp.concatenate(out, axis=1), kt


def positions_minor(ic):
    """The index keys' stack (L, B, width, Tmax) held to the layout it is
    read in, positions along the lanes.  Left to itself the compiler lays the
    carry out as the prefill writes it, batch-minor, and every step of every
    layer re-lays a layer's keys before it can multiply them."""
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(
        ic, Layout(major_to_minor=tuple(range(ic.ndim))))


def streams(ix: SparseIndex, t_max: int, head_dim: int, tpu: bool) -> bool:
    """True where a cached step against ``t_max`` carried positions streams
    the layer's K/V under the selection's mask (``ops/selected_attention``)
    and does not gather the selected rows: on a mesh of TPUs (``tpu``:
    attached, or described for a compile; the kernel compiles for nothing
    else), where the sizes tile for the kernel, and where the cache is longer
    than the selection by no more than ``_STREAM_UP_TO`` times.  All static:
    a program streams in every step or in none, and the decoder lays the
    carry's rows out flat for it."""
    from ompi_tpu.ops.selected_attention import tiles

    return (tpu and tiles(t_max, head_dim)
            and ix.topk < t_max <= _STREAM_UP_TO * ix.topk)


def row_shape(cfg, mesh, t_max: int, heads: int) -> tuple:
    """What a carry of ``t_max`` positions holds of one position, its
    ``heads`` K heads and then its V heads in one row: ``(2 heads, hd)``, and
    the row flat, ``(2 heads hd,)``, where the program's cached steps stream
    it (:func:`streams`: static, so all of a program's steps or none)."""
    from ompi_tpu.ops import _chip

    # a decoder shapes its carry per device, under its mesh's shard_map
    if streams(cfg.index, t_max, cfg.head_dim, _chip._traced_for_tpus()):
        return (2 * heads * cfg.head_dim,)
    return (2 * heads, cfg.head_dim)


def in_rows(cfg, mesh, ks, vs, t_max: int):
    """A whole-sequence pass's K and V, (L, B, T, Hkv/tp, hd) each, as the
    rows of a carry of ``t_max`` positions."""
    import jax.numpy as jnp

    return jnp.concatenate([ks, vs], axis=3).reshape(
        *ks.shape[:3], *row_shape(cfg, mesh, t_max, ks.shape[3]))


def carry(cfg, mesh, batch: int, t_max: int) -> list:
    """What a decoder carries with an index, zeros in the compute type: K and
    V as one stack of rows ``(L, B, t_max, *row_shape)`` (one, because a
    gather costs this chip its 15 ns a row whether the row is 1 KB or 2) and
    the index's keys ``(L, B, width, t_max)``, positions last."""
    import jax.numpy as jnp

    heads = cfg.kv_heads // int(mesh.shape["tp"])
    return [jnp.zeros((cfg.n_layers, batch, t_max,
                       *row_shape(cfg, mesh, t_max, heads)),
                      cfg.compute_dtype),
            jnp.zeros((cfg.n_layers, batch, cfg.index.head_dim, t_max),
                      cfg.compute_dtype)]


def carried(cfg, mesh, collected, t_max: int, into=None, **group) -> list:
    """Every layer's k, v and index keys of whole sequences, the next three
    of the iterator ``collected``, as :func:`carry`'s two stacks."""
    from ompi_tpu.models.block import written

    ks, vs, ki = (next(collected) for _ in range(3))
    rows, keys = into or (None, None)
    return [written(in_rows(cfg, mesh, ks, vs, t_max), t_max, rows, **group),
            written(ki, t_max, keys, axis=3, **group)]


def _attend_selection(cfg, lp, x, q, k, v, stacks, layer, pos):
    """The cached attention of ONE new position: from the block's normed
    input x (B, 1, D) and its rotated q (B, 1, H, hd) and k, v (B, 1, Hkv,
    hd), against :func:`carry`'s ``stacks``, the rows kvc and the index keys
    ic.  Writes the row and the index key at ``pos``, then reads the
    selection (:func:`attend_cached`), or every row where the cache is no
    longer than ``topk``.  Returns (context float32, kvc, ic)."""
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.models.block import _attend_whole_cache

    kvc, ic = stacks
    Tmax, hkv = kvc.shape[2], k.shape[2]
    qi, ki, wi = project(cfg, lp, x, pos[None])
    with scope("kv_cache"):
        row = jnp.concatenate([k, v], axis=2).astype(kvc.dtype)
        kvc = lax.dynamic_update_slice(
            kvc, row.reshape(1, *row.shape[:2], *kvc.shape[3:]),
            (layer, 0, pos) + (0,) * (kvc.ndim - 3))
        ic = positions_minor(lax.dynamic_update_slice(
            ic, ki.swapaxes(1, 2).astype(ic.dtype)[None], (layer, 0, 0, pos)))
    if cfg.index.topk < Tmax:
        o = attend_cached(cfg, q, kvc, ic, qi, wi, layer, pos)
    else:       # never flat: a cache within its selection does not stream
        o = _attend_whole_cache(q, kvc[..., :hkv, :], kvc[..., hkv:, :],
                                layer, pos)
    return o, kvc, ic


def attend_cached(cfg, q, kvc, ic, qi, wi, layer, pos):
    """One new position's attention against the carry: q (B, 1, H, hd); kvc
    (L, B, Tmax, 2 Hkv, hd), a position's K heads and then its V heads in
    one row, or the same rows flat, (L, B, Tmax, 2 Hkv hd), where the decoder
    found that this program :func:`streams`; and ic (L, B, width, Tmax), with
    the position's own k, v and index key already written at ``pos``; qi, wi
    its index queries and head weights (:func:`project`).  Scores against
    the layer's index keys up to ``pos``, the ``topk`` largest, attention
    over those positions: (B, 1, H, hd) float32.

    Over the 5-D carry the set is ``lax.top_k``'s indices and its rows are
    read out of the carry by one gather, of which the layer's whole K and V
    are the operand and of nothing else.  Over the flat carry the set is a
    mask (:func:`select`: the same set, ties included) and the layer's rows
    pass once through ``ops/selected_attention``, the whole stack its
    operand and the layer a scalar: no gather, and nothing under
    ``attention.gather``."""
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope

    B, Tmax = kvc.shape[1], kvc.shape[2]
    flat = kvc.ndim == 4
    live = jnp.arange(Tmax) <= pos
    with scope("index.score"):
        keys = lax.dynamic_index_in_dim(ic, layer, keepdims=False)
        found = scores(qi, wi, keys)[:, 0]                  # (B, Tmax)
        if not flat:
            found = jnp.where(live, found, -jnp.inf)
    if flat:
        from ompi_tpu.ops.selected_attention import selected_attention

        with scope("index.select"):
            chosen = select(found, live, cfg.index.topk)    # (B, Tmax) bool
        with scope("attention"):
            return selected_attention(q, kvc, chosen, layer)
    with scope("index.select"):
        best, chosen = lax.top_k(found, cfg.index.topk)     # (B, topk)
    with scope("attention.gather"):
        picked = kvc[layer, jnp.arange(B)[:, None], chosen].astype(q.dtype)
    with scope("attention"):
        return _attend_rows(q, picked, best > -jnp.inf)


def _attend_rows(q, picked, allowed):
    """Softmax attention of one query a sequence, q (B, 1, H, hd), over the
    rows ``picked`` (B, K, 2 Hkv, hd), a position's K heads and then its V
    heads, at the rows ``allowed`` (B, K): (B, 1, H, hd) float32.

    Both products read the rows as they were gathered.  Cutting them into a
    K and a V array first is a pass of its own over all of them (1.16 ms a
    layer at the cell's sizes, three times what both products take), so the
    query is given heads of zeros against the V heads, whose scores are
    dropped, and the weights heads of zeros against the K heads, whose
    context is dropped: twice the operations, which are not what bounds a
    step, for no copy."""
    import jax.numpy as jnp

    B, _, H, hd = q.shape
    hkv = picked.shape[2] // 2
    qg = q.reshape(B, hkv, H // hkv, hd)
    s = jnp.einsum("bgrd,bkgd->bgrk",
                   jnp.concatenate([qg, jnp.zeros_like(qg)], axis=1), picked,
                   preferred_element_type=jnp.float32)[:, :hkv] * (hd ** -0.5)
    at = allowed[:, None, None, :]
    s = jnp.where(at, s, -1e30)
    w = jnp.where(at, jnp.exp(s - s.max(axis=-1, keepdims=True)), 0.0)
    total = jnp.maximum(w.sum(axis=-1), 1e-30)              # (B, g, r)
    w = w.astype(q.dtype)
    o = jnp.einsum("bgrk,bkgd->bgrd",
                   jnp.concatenate([jnp.zeros_like(w), w], axis=1), picked,
                   preferred_element_type=jnp.float32)[:, hkv:]
    return (o / total[..., None]).reshape(B, 1, H, hd)
