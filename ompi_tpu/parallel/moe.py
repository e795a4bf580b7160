"""Mixture-of-experts layers: dropless top-k routing over the experts this
device holds (:func:`routed_moe`): every expert, or, told ``held``, one
device's share of them, with no exchange.

There is no exchange of tokens over an ``ep`` axis here: a cell holds a share
of a wide router's experts by ``held`` and its tokens stay where they are.
An exchange that ships tokens to their experts' devices starts from
``DeviceCommunicator.alltoall_stacked`` (``ROADMAP.md`` M9).
"""

from __future__ import annotations

import math

__all__ = ["ACTIVATIONS", "routed_moe", "EXPERT_LEAVES"]

# the experts' matrices in a parameter tree: gate (or the one up
# projection), down, and with gated experts up
EXPERT_LEAVES = ("w1", "w2", "w3")


def _relu2(x):
    import jax.numpy as jnp

    return jnp.square(jnp.maximum(x, 0))


def _gelu(x):
    import jax

    return jax.nn.gelu(x)


# what an ungated expert (and an ungated shared expert) puts between its two
# matrices, by ``TransformerConfig.moe_act``'s word
ACTIVATIONS = {"gelu": _gelu, "relu2": _relu2}


# Where a device holds few of a wide router's experts, ``routed_moe`` lays
# out, multiplies and sums windows of the picks it holds and not all it
# routes.  Both constants are read against static shapes alone (the picks
# routed, the experts held, the router's width).  A window has room for
# ``_WINDOW_ROOM`` times the picks expected here, so that one window nearly
# always takes them all (a second one costs a second read of the held
# experts' matrices); and windows are taken only where one's layout is at
# most ``_WINDOW_SHARE`` of the whole one: above that the loop and its branch
# cost what the smaller layout saves.
_WINDOW_ROOM = 2
_WINDOW_SHARE = 1 / 4


def _window_rows(picks: int, tm: int, held: int, width: int) -> int:
    """The sorted rows ``routed_moe`` lays out at a time: a window, a
    multiple of ``tm``, where ``held`` of a router's ``width`` outputs get
    tiles here and that is few of them; all ``picks`` otherwise."""
    cap = math.ceil(_WINDOW_ROOM * picks * held / (width * tm)) * tm
    if cap + held * tm <= _WINDOW_SHARE * (picks + held * tm):
        return cap
    return picks


def _within_groups(choice, groups: tuple):
    """``choice`` (n, E) float32, the scores a token picks by, with every
    expert outside the token's kept groups at ``-inf``: ``groups = (n_group,
    topk_group)``, a group ``E / n_group`` neighbouring experts, its score the
    sum of its two largest ``choice``, the ``topk_group`` best groups kept
    (ties to the lower group, ``lax.top_k``'s order)."""
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope

    n_group, keep = groups
    n, E = choice.shape
    with scope("moe.groups"):
        by_group = choice.reshape(n, n_group, E // n_group)
        best = lax.top_k(lax.top_k(by_group, 2)[0].sum(axis=-1), keep)[1]
        kept = jnp.any(best[:, :, None] == jnp.arange(n_group), axis=1)
        return jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(n, E)


def _sum_of_picks(out, slot, gate):
    """``sum_j gate[t, j] out[slot[j n + t]]`` in float32, (n, D): ``out``
    (rows, D) the experts' result rows as they are laid out, ``gate`` (n, k)
    each token's weights and ``slot`` (k n,) its picks' rows, picks major.
    One gather, then one fusion that reads the k runs of n rows as the gather
    leaves them and widens them as it adds.  Gathered tokens major and summed
    over a middle axis of ``k``, the rows are first written out again in
    float32 with the picks on the sublane axis, of which a float32 tile has
    eight: six picks are stored as eight and ten as sixteen (1074 MB written
    and read again a pass of cell 13 for 335 MB of rows).  The barrier keeps
    the sum a fusion of its own: a matrix product that takes it as its
    epilogue (a shared expert's down projection, which the sum is added to)
    cannot widen its operands, and the rows are written out in float32 for
    it first."""
    import jax.numpy as jnp
    from jax import lax

    n, k = gate.shape
    rows = out[slot]
    y = None
    for j in range(k):      # lax's slices: jnp's index is a helper to trace
        pick = (lax.slice_in_dim(rows, j * n, (j + 1) * n).astype(jnp.float32)
                * lax.slice_in_dim(gate, j, j + 1, axis=1))
        y = pick if y is None else y + pick
    return lax.optimization_barrier(y)


def routed_moe(x, params, top_k: int, gated: bool = False, layer=None,
               kernel: bool = False, renorm: bool = False,
               score: str = "softmax", scale: float = 1.0, held=None,
               zero: int = 0, act: str = "gelu", groups=None):
    """Dropless top-k MoE layer over the experts this device holds: x (B, T,
    D) local tokens → (B, T, D).

    ``params``: ``wg`` (D, E) the router; ``w1`` (E, D, F) and ``w2``
    (E, F, D) the experts, ``w2(act(w1 x))`` (``act`` of ``ACTIVATIONS``:
    "gelu", or "relu2", ``relu(.)^2``: two grouped products an expert); with
    ``gated`` also ``w3`` (E, D, F), and an expert is ``w2(silu(w1 x) * w3
    x)``, three.  With ``layer``
    (an index, traced or not) the three are the whole stacks over layers,
    (L, E, ·, ·), and the kernel reads layer ``layer``'s matrices out of
    them: a layer loop that sliced them first would copy every expert of
    the layer out of the stack each time (a pallas call takes whole
    arrays), 0.8 GB a layer of a cached step at OLMoE's widths.

    ``held`` None: every expert of the router is on this device.  ``held =
    (first, count)``: the device is one of several that share the layer by
    expert and holds experts ``first .. first + count - 1`` alone, so the
    three stacks are ``count`` long where the router stays ``E`` wide.  A
    token still picks ``top_k`` of all ``E`` and its weights are made over
    all its picks; the picks that fall to an expert held elsewhere are given
    no tile here and weigh nothing (the device that holds the expert adds
    them: the shares of all devices add up to the whole layer), and the
    weights are *not* renormalised over the picks held here.  No exchange is
    made and nothing stands in for one.

    ``zero``: the router's last ``zero`` outputs are identity experts
    (zero-computation experts: a pick of one adds the token itself, times the
    pick's weight), so ``E - zero`` of its ``E`` outputs have matrices.  A
    token picks ``top_k`` of all ``E`` and its weights are made over all its
    picks; the identity picks get no tile, and what they add, ``(the sum of
    their weights) x`` in float32, is computed here for this device's own
    tokens (scope ``moe.zero``), whatever ``held`` says: the devices that
    share a layer by expert each add it for the tokens they route.

    Routing (all shapes static, no capacity, no token dropped): the
    router's logits, scores and top-k in float32.  ``score`` "softmax":
    each token keeps its ``top_k`` most probable experts with their
    probabilities as they are, or with ``renorm`` divided by their sum, so
    that a token's experts weigh one together.  ``score`` "sigmoid": the
    scores are each expert's own sigmoid.  Under either, with a leaf ``wgb``
    (E,) in ``params``, the selection bias, the ``top_k`` largest of ``score
    + wgb`` are picked and weigh their scores without it.  With ``groups``
    ``(n_group, topk_group)`` under "sigmoid" the picks are group-limited:
    the router's outputs are ``n_group`` groups of neighbouring experts, a
    group scores the sum of its two largest ``score + wgb``, and a token picks
    its ``top_k`` inside its ``topk_group`` best groups alone
    (:func:`_within_groups`).  Either way the
    weights are then multiplied by ``scale``.  The ``tokens × top_k``
    assignments are sorted by
    expert (stable) and laid out in row tiles of ``tm`` rows, every
    expert's run starting at a tile boundary, so a tile's rows all go to
    one expert and the experts run as ``ops.grouped_matmul`` over the
    tiles (with ``kernel`` the pallas kernel, which compiles for the TPU
    alone; without, the same tiles through ``lax.ragged_dot``, which is
    what the kernel computes; an expert's rows, and with them the tiles in use, are known
    only at run time; ``tokens·top_k/tm + E`` tiles always suffice).  The
    slots that fill an expert's last tile up hold other real rows and are
    read back by nobody.  Each token then sums its ``top_k`` result rows
    weighted by their probabilities, in float32, from rows gathered picks
    major (:func:`_sum_of_picks`: summed over a middle axis of six or ten
    picks they are first written out in float32, padded to a tile's eight).

    Where the device holds few of a wide router's experts
    (``_window_rows``, from static shapes: LongCat-Flash's 16 of 768
    outputs, one pick in 48), the layout, the experts' calls and the sum are
    sized by the picks it holds and not by all it routes: the held picks
    are the first sorted rows, and a *window* of them at a time (twice the
    picks expected here) is laid out on ``window/tm + E`` tiles, multiplied,
    and its real rows added, times their weights, in float32, to their
    tokens.  One window nearly always; a further one for as long as held
    picks are left (a loop of static length whose body runs under
    ``lax.cond``, so it differentiates in reverse), so no pick is dropped
    and nothing is a capacity: with every pick of the batch held here every
    window runs.  The same picks, weights and products as the whole
    layout's; a token's held picks are added by expert, not by pick.

    ``tm`` follows the rows an expert gets on average, with room for their
    spread where a step's handful meets a matrix too large for one block
    (``ops.grouped_matmul.tile_rows``): 512 for a prefill of thousands of
    rows an expert, 16 for a cached step's handful, where the layer is the
    stream of every expert's weights.  One function, two tilings; and the
    kernel picks the weights' block from ``tm`` and the matrix
    (``ops.grouped_matmul.weight_block``): a whole matrix is one block
    wherever the working set fits the kernel's VMEM budget, so that each
    expert's matrix is read once and every tile its rows once; a matrix too
    large beside 256 or 512 rows keeps its whole ``K`` and goes by blocks
    of ``N``, every tile reading its expert's matrix for itself, which that
    many rows amortise, and still its own rows once.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.ops.grouped_matmul import (grouped_matmul,
                                             grouped_matmul_xla, tile_rows)

    B, T, D = x.shape
    n, k = B * T, top_k
    E = params["wg"].shape[-1]
    if groups is not None and (score != "sigmoid" or E % groups[0]
                               or groups[1] * (E // groups[0]) < k):
        raise ValueError(
            f"group-limited top-k is built for sigmoid scores, groups that "
            f"divide the router and hold the picks: groups {groups} of {E} "
            f"outputs, {k} picks under score {score!r}")
    cdt = x.dtype
    xf = jnp.asarray(x).reshape(n, D)
    F = params["w1"].shape[-1]
    tm = tile_rows(n * k / E, ((D, F), (F, D)), jnp.dtype(cdt).itemsize)
    if zero and held is None:
        held = (0, E - zero)            # every expert that has matrices
    first_zero = E - zero
    cap = _window_rows(n * k, tm, E if held is None else held[1], E)
    if held is not None:
        E = held[1]                     # the groups that get tiles
    with scope("moe.route"):
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                            params["wg"].astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        if score == "softmax" and "wgb" in params:
            probs = jax.nn.softmax(logits, axis=-1)
            expert = lax.top_k(probs + params["wgb"].astype(jnp.float32),
                               k)[1]
            gate = jnp.take_along_axis(probs, expert, axis=-1)
        elif score == "softmax":
            gate, expert = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        else:
            scores = jax.nn.sigmoid(logits)
            biased = (scores + params["wgb"].astype(jnp.float32)
                      if "wgb" in params else scores)
            if groups is not None:
                biased = _within_groups(biased, groups)
            expert = lax.top_k(biased, k)[1]
            gate = jnp.take_along_axis(scores, expert, axis=-1)
        if renorm:
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        if scale != 1.0:
            gate = gate * scale
        if zero:
            # a token's identity picks weigh their sum, on the token itself
            zero_gate = jnp.sum(jnp.where(expert >= first_zero, gate, 0.0),
                                axis=-1)
        if held is not None:
            # a pick held elsewhere: weighs nothing, and sorts past the last
            # expert of this device, where no tile is made for it
            first = held[0]
            here = (expert >= first) & (expert < first + E)
            gate = jnp.where(here, gate, 0.0)
            expert = jnp.where(here, expert - first, E)
    with scope("moe.dispatch"):
        # assignments sorted by expert; ``order``: sorted row -> assignment
        sorted_group, order = lax.sort(
            (expert.reshape(n * k), jnp.arange(n * k, dtype=jnp.int32)),
            num_keys=1, is_stable=True)

    def window(group, order, y=None):
        """The layout, the experts and the weighted sum of a run of sorted
        rows (``group`` their experts, ``order`` their assignments): all of
        them, and then the sum is every token's over its ``k`` result rows;
        or, given ``y`` (n, D) float32, a window of them, whose real rows'
        results are added to ``y`` at their tokens."""
        rows_in = group.shape[0]
        n_tiles = -(-rows_in // tm) + E     # sum of ceil(rows_e / tm) is below
        with scope("moe.dispatch"):
            is_group = group[:, None] == jnp.arange(E)[None, :]
            rows_of = jnp.sum(is_group, axis=0, dtype=jnp.int32)    # (E,)
            tiles_of = -(-rows_of // tm)
            tile_end = jnp.cumsum(tiles_of)         # expert e ends before
            # a run of sorted rows moves up by this much into the tiled
            # layout
            shift = ((tile_end - tiles_of) * tm
                     - (jnp.cumsum(rows_of) - rows_of))
            tile_group = jnp.minimum(
                jnp.searchsorted(tile_end, jnp.arange(n_tiles),
                                 side="right"),
                E - 1).astype(jnp.int32)
            # slot -> the sorted row it holds (where a slot only fills a
            # tile up, a real row of the next run, or the last)
            holds = jnp.clip(jnp.arange(n_tiles * tm).reshape(n_tiles, tm)
                             - shift[tile_group][:, None], 0, rows_in - 1)
            rows = xf[order[holds.reshape(-1)] // k]    # (n_tiles·tm, D)
            # sorted row -> its slot (a row held elsewhere: any slot)
            slot = jnp.arange(rows_in) + jnp.sum(
                jnp.where(is_group, shift[None, :], 0), axis=1)
            if y is None:
                # assignment -> its slot (a sort by ``order`` is its
                # inverse), a token's first picks before every second one
                _, slot = lax.sort(((order % k) * n + order // k, slot),
                                   num_keys=1)
        with scope("moe.experts"):
            used = tile_end[-1:]
            stacks = {name: params[name].astype(cdt)
                      for name in EXPERT_LEAVES if name in params}
            if layer is not None:   # (L, E, ·, ·): layer l's are l·E + e
                stacks = {name: w.reshape(-1, *w.shape[2:])
                          for name, w in stacks.items()}
                tile_group = tile_group + layer * E
            matmul = grouped_matmul if kernel else grouped_matmul_xla
            hid = matmul(rows, stacks["w1"], tile_group, used)
            if gated:
                hid = jax.nn.silu(hid) * matmul(rows, stacks["w3"],
                                                tile_group, used)
            else:
                hid = ACTIVATIONS[act](hid)
            out = matmul(hid, stacks["w2"], tile_group, used)
        with scope("moe.combine"):
            if y is None:
                return _sum_of_picks(out, slot, gate)
            # a row past the held picks (held elsewhere, or the padding of
            # the last window) weighs nothing
            weight = jnp.where(group < E, gate.reshape(n * k)[order], 0.0)
            return y.at[order // k].add(
                out[slot].astype(jnp.float32) * weight[:, None])

    if cap == n * k:
        y = window(sorted_group, order)
    else:
        # the held picks are the first sorted rows: windows of ``cap`` rows
        # are worked through until they are exhausted (one, nearly always;
        # every one if every pick of the batch fell here), under a static
        # count so that the loop is reverse-differentiable
        windows = -(-n * k // cap)
        with scope("moe.dispatch"):
            held_count = jnp.sum(sorted_group < E, dtype=jnp.int32)
            pad = windows * cap - n * k
            sorted_group = jnp.pad(sorted_group, (0, pad), constant_values=E)
            order = jnp.pad(order, (0, pad))

        def step(w, y):
            group, at = (lax.dynamic_slice_in_dim(rows, w * cap, cap)
                         for rows in (sorted_group, order))
            return lax.cond(w * cap < held_count,
                            lambda y: window(group, at, y), lambda y: y, y)

        y = lax.fori_loop(0, windows, step,
                          jnp.zeros((n, D), jnp.float32))
    with scope("moe.combine"):
        if not zero:
            y = y.astype(cdt)
    if zero:
        with scope("moe.zero"):
            y = (y + zero_gate[:, None] * xf.astype(jnp.float32)).astype(cdt)
    return y.reshape(B, T, D)
