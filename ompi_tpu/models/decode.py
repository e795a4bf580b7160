"""Autoregressive decoding with a KV cache — the inference counterpart
of the train step, built from the same layer math.

TPU-first shape: ONE compiled program per (prompt_len, max_new) pair —
prefill runs the training backbone (``collect_kv`` returns every
layer's post-rope K/V, and with a hybrid block its mixer's states after
the last position, in a single pass), then a ``lax.scan`` generates
tokens against a static-shape cache (no growing arrays, no
recompilation per token).

The life of the cache: it is allocated once, stacked over layers at its
final length ``(L, B, Tp+max_new, Hkv/tp, hd)``, and from then on it is
loop carry — of the token scan and, inside it, of a ``lax.fori_loop``
over the layer index.  A layer writes its new K/V in place at the one
position ``(l, 0, pos, 0, 0)`` of the whole stack and attention reads
layer ``l`` through a slice that the compiler fuses into the scores and
context products, so a step writes ``B·Hkv·hd`` values a layer and reads
the cache once.  The cache is never the ``xs`` or ``ys`` of a scan:
those are separate buffers, and a step would then copy every layer's
cache out of the stack and back (``tests/parallel/test_decode.py`` holds
the compiled program to this).  K/V heads may be fewer than query heads
(``TransformerConfig.n_kv_heads``): the cache holds the K/V heads and a
step reads each once for the query heads it serves.

A hybrid block (``models/ssm.py``: a state-space mixer beside attention
in every layer) carries two more stacks the same way, of a size that
does not grow with the sequence: the convolution's last inputs ``(L, B,
d_conv - 1, conv_dim)`` and the heads' states ``(L, B, H, P, N)`` in the
block's ``state_dtype``.  A step reads layer ``l``'s states out of the
stacks and writes them back in place at ``(l,)``; the update is float32
and is rounded once on the way back (the same test file holds the chip's
compiled program to one write a step and no copy of the stack).

An index (``models/sparse_index.py``: learned sparse attention) carries a
stack more that does grow with the sequence, the index's keys ``(L, B,
width, Tp+max_new)``, positions last, written at ``pos`` beside K and V by
the prefill and by every step.  A step scores the new position's index
queries against the layer's index keys, takes the ``topk`` positions of the
largest scores, and attends to those.  With an index a position's K and V
are one row of one stack, the K heads and then the V heads, and there is no
second stack (``vc`` is ``None``).  How a step reads the rows it selected is
decided once a program, from its static sizes
(``sparse_index.streams``), and the carry is laid out for it.  **The
gather**, ``(L, B, Tp+max_new, 2 Hkv/tp, hd)``: the layer's whole K and V
are the operand of one gather of ``topk`` rows and of nothing else; one
gather, because a gather on this chip costs its 15 ns a row whether the row
is 1 KB or 2, so two stacks would take twice as long to read the same bytes.
**The stream**, rows flat, ``(L, B, Tp+max_new, 2 Hkv/tp · hd)``: where the
cache is no more than eight selections long, on a mesh of TPUs and at sizes
that tile, the layer's rows pass once through the pallas kernel
``ops/selected_attention`` under the selection's mask, at the HBM's rate,
which a gather of a quarter of them does not reach (PERF.md section 5).
CPU meshes, tiny sizes and long caches gather.

A layer plan (``models/plan.py``: layers of different kinds, a delta-rule
mixer or latent attention in place of attention) carries no K and V of heads
at all (``kc`` and ``vc`` are ``None``): every layer has buffers of its own
(``plan.carry``), a latent layer's cache ``(1, B, Tp+max_new, kv_rank +
rope)``, one normed latent and one shared key part a position for all heads,
a KDA layer's convolution inputs and its float32 matrix states ``(1, B,
heads, K, K)``.  The plan is static, so a step is a python loop over it and
not a ``fori_loop``: a layer reads its own buffers and replaces them.  Such
a configuration's decoder is two programs (``_two_programs``): the prefill,
one executable for every ``max_new`` whose carry ends at the prompt, and a
second that takes the carry over donated, lengthens the latent caches and
generates, so that every decoder of the configuration picks the same first
token for the same prompt.

The prefill hands the carry over.  By default it is one pass over every
prompt whose K/V are padded to the cache's length.  With
``TransformerConfig.prefill_tokens`` (and for a hybrid block or a plan,
always) the
carry is allocated first at its final size and the prompts are
prefilled a group of whole sequences at a time, each group writing its
K/V and final states into it, so the pass's temporaries are a group's
and not the batch's.

Sharding: batch over dp, heads over tp (the cache is
head-sharded exactly like the weights), the table's rows over tp where
``param_specs`` splits them: a rank looks up the tokens whose rows it
holds and a psum over tp completes them (``transformer._lookup``), and it
makes the logits of its own rows, which are gathered over tp
(``transformer._whole_vocab``), so greedy argmax, sampling and
``keep_logits`` see the full vocab.  Sequence parallelism is a
training-time layout — decode requires sp == 1.  MoE configs route each
generated token through the same layer as training and prefill
(``_moe_ffn_tail``).  The top-1 switch
(``moe_top_k == 0``) computes its capacity per single-token step (B
tokens), so under a binding capacity the drop pattern can differ from a
full-sequence forward — cached and full paths agree exactly whenever
capacity doesn't bind.  The dropless path (``moe_top_k >= 1``) has no
capacity: a token's experts and their weights depend on that token alone,
so the cached step (``B·k`` rows over all experts, a handful a tile) and
the full forward agree at any batch, up to the order of summation.
"""

from __future__ import annotations

import functools

from ompi_tpu.models.transformer import (TransformerConfig,
                                         _dense_ffn_tail, _head,
                                         _moe_ffn_tail, _qk_norm, _rmsnorm,
                                         _rope, layer_leaves, param_specs)
from ompi_tpu.parallel.moe import EXPERT_LEAVES

__all__ = ["make_decoder"]


def _attend_whole_cache(q, kc, vc, layer, pos):
    """q (B, 1, H, hd) against every position up to ``pos`` of layer
    ``layer`` of the cache (L, B, Tmax, Hkv, hd), a K/V head read once for
    the query heads it serves: the context, float32, (B, 1, H, hd) or
    grouped (B, 1, Hkv, H / Hkv, hd)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope

    B, hl, hd = q.shape[0], q.shape[2], q.shape[3]
    Tmax, hkv = kc.shape[2], kc.shape[3]
    with scope("attention"):
        # scores against every cached position, masked beyond `pos`
        k_all = lax.dynamic_index_in_dim(kc, layer, keepdims=False)
        v_all = lax.dynamic_index_in_dim(vc, layer, keepdims=False)
        if hkv == hl:
            s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                           k_all.astype(jnp.float32)) * (hd ** -0.5)
            live = jnp.arange(Tmax)[None, None, None, :] <= pos
            s = jnp.where(live, s, -1e30)
            w = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", w, v_all.astype(jnp.float32))
        else:       # K/V head g serves the query heads (g, r): read it once
            qg = q.astype(jnp.float32).reshape(B, 1, hkv, hl // hkv, hd)
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qg,
                           k_all.astype(jnp.float32)) * (hd ** -0.5)
            s = jnp.where(jnp.arange(Tmax) <= pos, s, -1e30)
            w = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bgrqk,bkgd->bqgrd", w,
                           v_all.astype(jnp.float32))
    return o


def _attend_selection(cfg, lp, x, q, k, v, kvc, ic, layer, pos):
    """The indexed block's cached attention of ONE new position: from the
    block's normed input x (B, 1, D) and its rotated q (B, 1, H, hd) and k,
    v (B, 1, Hkv, hd), against kvc, a position's K heads and then its V
    heads in one row, (L, B, Tmax, 2 Hkv, hd) or, where the program streams
    (``sparse_index.streams``), flat, (L, B, Tmax, 2 Hkv hd), and the index
    keys ic (L, B, width, Tmax).  Writes the row and the index key at
    ``pos``, then reads the ``topk`` selected rows alone, by a gather, or
    the layer's rows once under the selection's mask, by the kernel
    (``sparse_index.attend_cached``); every row where the cache is no
    longer than ``topk``.  Returns (context float32, kvc, ic)."""
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.models import sparse_index

    Tmax, hkv = kvc.shape[2], k.shape[2]
    qi, ki, wi = sparse_index.project(cfg, lp, x, pos[None])
    with scope("kv_cache"):
        row = jnp.concatenate([k, v], axis=2).astype(kvc.dtype)
        kvc = lax.dynamic_update_slice(
            kvc, row.reshape(1, *row.shape[:2], *kvc.shape[3:]),
            (layer, 0, pos) + (0,) * (kvc.ndim - 3))
        ic = sparse_index.positions_minor(lax.dynamic_update_slice(
            ic, ki.swapaxes(1, 2).astype(ic.dtype)[None], (layer, 0, 0, pos)))
    if cfg.index.topk < Tmax:
        o = sparse_index.attend_cached(cfg, q, kvc, ic, qi, wi, layer, pos)
    else:       # never flat: a cache within its selection does not stream
        o = _attend_whole_cache(q, kvc[..., :hkv, :], kvc[..., hkv:, :],
                                layer, pos)
    return o, kvc, ic


def _step_layer(cfg: TransformerConfig, comm, lp, h, kc, vc, layer, pos,
                states=()):
    """Layer ``layer`` for ONE new token position, against the whole cache.

    h: (B, 1, D); kc/vc: the stacked cache (L, B, Tmax, Hkv/tp, hd), or with
    an index kc alone, a position's K and V heads in one row (L, B, Tmax,
    2 Hkv/tp, hd), or that row flat where the step streams it, and vc
    ``None``; lp:
    this layer's parameters, but for the dropless experts' leaves
    (``moe.EXPERT_LEAVES``), which are the whole stacks over layers that
    ``routed_moe`` indexes by ``layer``.  ``states``: with a hybrid block
    the mixer's two stacks, the convolution's last inputs ``(L, B,
    d_conv - 1, conv_dim)`` and the heads' states ``(L, B, H, P, N)``; with
    an index its keys' stack ``(L, B, width, Tmax)``.
    Returns (h, kc, vc, *states) with the new token's k/v written in place
    at ``(layer, :, pos)``, its index key at ``(layer, :, :, pos)`` and the
    layer's states at ``(layer,)``.
    """
    import math

    import jax
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.parallel.layers import column_parallel, row_parallel

    cdt = h.dtype
    B = h.shape[0]
    hd = cfg.head_dim
    hkv = math.prod(kc.shape[3:]) // hd
    hy, ix = cfg.hybrid, cfg.index
    if ix is not None:
        hkv //= 2       # a row of kc holds the K heads and then the V heads
    hl = hkv * (cfg.n_heads // cfg.kv_heads)

    with scope("attn_proj"):
        x = _rmsnorm(h, lp["ln1"], cfg.norm_eps)
        xa = x if hy is None else x * hy.attention_in_multiplier

        def project(w, heads, norm=None):
            y = column_parallel(xa, lp[w].astype(cdt))
            if cfg.qk_norm and norm:
                y = _qk_norm(cfg, y, lp[norm], comm)
            return y.reshape(B, 1, heads, hd)

        q, k, v = (project("wq", hl, "qn"), project("wk", hkv, "kn"),
                   project("wv", hkv))
        if hy is not None:
            k = k * hy.key_multiplier
        q = _rope(q, pos[None], theta=cfg.rope_theta)
        k = _rope(k, pos[None], theta=cfg.rope_theta)
    if ix is None:
        with scope("kv_cache"):
            kc = lax.dynamic_update_slice(kc, k.astype(kc.dtype)[None],
                                          (layer, 0, pos, 0, 0))
            vc = lax.dynamic_update_slice(vc, v.astype(vc.dtype)[None],
                                          (layer, 0, pos, 0, 0))
        o = _attend_whole_cache(q, kc, vc, layer, pos)
    else:
        o, kc, ic = _attend_selection(cfg, lp, x, q, k, v, kc, states[0],
                                      layer, pos)
        states = (ic,)
    with scope("attn_proj"):
        o = o.astype(cdt).reshape(B, 1, hl * hd)
        a = row_parallel(o, lp["wo"].astype(cdt), comm, axis="tp")
        if hy is None:
            h = h + a
    if hy is not None:
        from ompi_tpu.models import ssm

        s, *states = ssm.mixer(cfg, lp, x, carry=(*states, layer))
        with scope("attn_proj"):
            h = h + a * hy.attention_out_multiplier + s
    if cfg.moe_experts:
        # aux is training-only; the dropless experts come as whole stacks
        h, _aux = _moe_ffn_tail(cfg, h, lp, comm,
                                layer=layer if cfg.moe_top_k else None)
        return h, kc, vc, *states
    return (_dense_ffn_tail(h, lp, comm, cdt, cfg.norm_eps,
                            gated=hy and hy.mlp_multipliers), kc, vc, *states)


def _prefill_group(batch: int, prompt_len: int, prefill_tokens: int) -> int:
    """Sequences one pass of the prefill holds: the most that divide the
    batch and stay within ``prefill_tokens`` tokens (0: the whole batch)."""
    if not prefill_tokens:
        return batch
    return max(g for g in range(1, batch + 1)
               if batch % g == 0 and (g == 1 or g * prompt_len
                                      <= prefill_tokens))


def make_decoder(cfg: TransformerConfig, mesh, max_new: int,
                 temperature: float = 0.0, top_k: int = 0,
                 keep_logits: int = 0):
    """jitted (params, prompt (B, Tp) int32[, seed]) → (B, Tp+max_new).

    Greedy decode by default: prefill through the training backbone
    (K/V collected per layer), then ``max_new`` single-token
    steps over the static cache.  Requires sp == 1; dense, switch-MoE,
    dropless top-k MoE, hybrid (``models/ssm.py``), indexed
    (``models/sparse_index.py``) and planned (``models/plan.py``) configs
    are supported (MoE routes each
    token through the same layer as training).

    The carry of the token scan and of the loop over layers inside it:
    K and V ``(L, B, Tp+max_new, Hkv/tp, hd)`` in the compute dtype (with an
    index one stack of both, a position's K and V heads in one row, ``(L,
    B, Tp+max_new, 2 Hkv/tp, hd)`` where a cached step gathers the rows it
    selected and flat, ``(L, B, Tp+max_new, 2 Hkv/tp · hd)``, where it
    streams the layer's rows under the selection's mask:
    ``sparse_index.streams`` says which from the mesh's platform and the
    program's static sizes, and no argument chooses) and, with a hybrid
    block, the mixer's two states beside them, stacked over
    layers alike: the convolution's last inputs ``(L, B, d_conv - 1,
    conv_dim)`` and the heads' states ``(L, B, H, P, N)`` in the block's
    ``state_dtype``; with an index, its keys ``(L, B, width, Tp+max_new)``
    in the compute dtype.  The prefill hands over each layer's K/V, its
    index keys, and its states after the last prompt position.  It runs in
    one pass and pads its K/V to the cache's length; with ``cfg.prefill_tokens``, or a hybrid
    block, it runs a group of whole sequences at a time, each group
    writing its K/V and states into the carry allocated once at its final
    size (one group where ``prefill_tokens`` is 0).

    ``keep_logits=n``: returns ``(tokens, logits)``, ``logits`` float32
    ``(n, max_new, vocab)``: what each generated token of the first ``n``
    sequences was picked from, the prefill's logits for the first and the
    cached step's after.  Needs dp == 1.

    The embedding and the head are read as :func:`transformer.param_specs`
    places them: whole on every device, or, where ``tp`` is above 1 and
    divides the vocabulary, rank r's rows ``[r·V/tp, (r+1)·V/tp)``.  Of
    split rows the prefill's and every step's lookup is completed by one
    psum over ``tp`` and their logits, made against the rank's rows, are
    gathered over ``tp`` before a token is picked, so every rank picks
    from the whole vocabulary and ``keep_logits`` hands it back whole.

    ``temperature > 0`` switches to sampling (optionally truncated to
    the ``top_k`` highest logits); the returned callable then takes a
    third argument ``seed`` (int32 scalar).  Each step folds the
    position — and the dp coordinate, so data-parallel shards draw
    independent noise — into the key; tp ranks share the key and hence
    agree on every sampled token (their logits are identical).
    """
    from ompi_tpu.core import scopes

    with scopes.host("build.decoder", program="decode"):
        if cfg.plan is not None:
            return _two_programs(cfg, mesh, max_new, temperature, top_k,
                                 keep_logits)
        return _one_program(cfg, mesh, max_new, temperature, top_k,
                            keep_logits)


def _halves(cfg: TransformerConfig, mesh, max_new: int,
            temperature: float, top_k: int, keep_logits: int):
    """A decoder's two halves, per device (under ``shard_map``), and the
    head both multiply by, ``unembedding(params)`` in the compute type:
    ``prefill(params, head, prompt, seed) -> (tok0, logits, kc, vc,
    *states)``, the first token, the logits (B, V) it was picked from and
    the carry, and ``generate(params, head, prompt, seed, tok0, logits, kc,
    vc, *states)``, what the decoder returns and the states after the last
    step."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.mpi.device_comm import DeviceCommunicator

    for ax in ("dp", "sp", "tp"):
        if ax not in mesh.shape:
            raise ValueError(f"decode needs a mesh with dp/sp/tp axes "
                             f"(missing {ax!r}; have "
                             f"{tuple(mesh.shape)})")
    if int(mesh.shape["sp"]) != 1:
        raise ValueError("decode requires sp == 1 (sequence parallelism "
                         "is a training-time layout)")
    if keep_logits and int(mesh.shape["dp"]) != 1:
        raise ValueError(f"keep_logits={keep_logits} hands back the first "
                         f"sequences' logits whole and needs dp == 1; the "
                         f"mesh has dp={mesh.shape['dp']}")
    hy = cfg.hybrid
    if hy is not None:
        from ompi_tpu.models import ssm

        ssm.check_mesh(cfg, mesh)
    if cfg.index is not None:
        from ompi_tpu.models import sparse_index

        sparse_index.check_mesh(cfg, mesh)
    if cfg.plan is not None:
        from ompi_tpu.models import plan

        plan.check_mesh(cfg, mesh)
    axes = tuple(a for a in ("dp", "sp", "tp", "ep")
                 if a in mesh.axis_names)
    comm = DeviceCommunicator(mesh, axes)
    cdt = jnp.dtype(cfg.compute_dtype)
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if top_k and not temperature:
        raise ValueError("top_k needs temperature > 0")
    if top_k < 0 or top_k > cfg.vocab:
        raise ValueError(f"top_k must be in [0, vocab={cfg.vocab}], "
                         f"got {top_k}")

    def pick(logits, pos, seed):
        """Next token from (B, V) f32 logits."""
        if not temperature:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits / jnp.float32(temperature)
        if top_k:
            kth = lax.top_k(scaled, top_k)[0][..., -1:]
            scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(seed), pos),
            lax.axis_index("dp"))
        return jax.random.categorical(key, scaled,
                                      axis=-1).astype(jnp.int32)

    def row_shape(t_max, heads):
        """With an index: what a carry of ``t_max`` positions holds of one
        position, its ``heads`` K heads and then its V heads in one row:
        ``(2 heads, hd)``, and the row flat, ``(2 heads hd,)``, where the
        program's cached steps stream it (``sparse_index.streams``: static,
        so all of a program's steps or none)."""
        if sparse_index.streams(cfg.index, t_max, cfg.head_dim,
                                mesh.devices.flat[0].platform == "tpu"):
            return (2 * heads * cfg.head_dim,)
        return (2 * heads, cfg.head_dim)

    def in_rows(ks, vs, t_max):
        """The prefill's K and V, (L, B, T, Hkv/tp, hd) each, as the rows of
        a carry of ``t_max`` positions."""
        return jnp.concatenate([ks, vs], axis=3).reshape(
            *ks.shape[:3], *row_shape(t_max, ks.shape[3]))

    def prefill_in_groups(params, prompt):
        """The carry, filled a group of sequences at a time: (last hidden
        states (B, D), kc, vc, *states); with an index kc holds K and V and
        vc is ``None``."""
        B, Tp = prompt.shape
        group = _prefill_group(B, Tp, cfg.prefill_tokens)
        kv = (cfg.n_layers, B, Tp + max_new,
              cfg.kv_heads // int(mesh.shape["tp"]), cfg.head_dim)
        ix = cfg.index
        if cfg.plan is not None:    # a layer's own buffers; no K and V of heads
            stacks = plan.carry(cfg, B, Tp + max_new, cdt)
        else:
            stacks = ([jnp.zeros(kv, cdt), jnp.zeros(kv, cdt)] if ix is None
                      else [jnp.zeros((*kv[:3], *row_shape(*kv[2:4])), cdt)])
        if hy is not None:
            stacks += [jnp.zeros((cfg.n_layers, *shape), dtype)
                       for shape, dtype in zip(ssm.state_shapes(cfg, B),
                                               (cdt, hy.state_dtype))]
        if ix is not None:
            stacks.append(jnp.zeros((*kv[:2], ix.head_dim, kv[2]), cdt))

        def one(g, carry):
            last, *stacks = carry
            rows = lax.dynamic_slice_in_dim(prompt, g * group, group)
            h, (_aux, *cached) = tfm._local_backbone(
                cfg, comm, params, rows, collect_kv=True, forward_only=True)
            if ix is not None:
                cached[:2] = [in_rows(*cached[:2], kv[2])]
            stacks = [lax.dynamic_update_slice(
                stack, new.astype(stack.dtype),
                (0, g * group) + (0,) * (stack.ndim - 2))
                for stack, new in zip(stacks, cached)]
            return (lax.dynamic_update_slice(last, h[:, -1, :],
                                             (g * group, 0)), *stacks)

        last, *stacks = lax.fori_loop(
            0, B // group, one, (jnp.zeros((B, cfg.d_model), cdt), *stacks))
        if cfg.plan is not None:
            return (last, None, None, *stacks)
        kc, *stacks = stacks
        return (last, kc, *stacks) if ix is None else (last, kc, None, *stacks)

    def unembedding(params):
        return _head(cfg, params).astype(cdt)

    def prefill(params, head, prompt, seed):
        B, Tp = prompt.shape
        if keep_logits > B:
            raise ValueError(f"keep_logits={keep_logits} of {B} sequences")
        # ---- prefill: the training backbone, K/V collected ----
        with scope("prefill"):
            if hy is None and cfg.plan is None and not cfg.prefill_tokens:
                h, (_aux, ks, vs, *states) = tfm._local_backbone(
                    cfg, comm, params, prompt, collect_kv=True,
                    forward_only=True)
                pad = [(0, 0), (0, 0), (0, max_new), (0, 0), (0, 0)]
                if cfg.index is None:
                    kc = jnp.pad(ks, pad)   # (L, B, Tp+max_new, Hl, hd)
                    vc = jnp.pad(vs, pad)
                else:   # one row a position; the index's keys beside it
                    kc = in_rows(ks, vs, Tp + max_new)
                    kc = jnp.pad(kc, pad[:kc.ndim])
                    vc = None
                    states = [jnp.pad(ki, [(0, 0)] * 3 + [(0, max_new)])
                              for ki in states]
                last = h[:, -1, :]
            else:
                last, kc, vc, *states = prefill_in_groups(params, prompt)
            logits = tfm._whole_vocab(cfg, jnp.einsum(
                "bd,vd->bv", last, head, preferred_element_type=jnp.float32))
            tok0 = pick(logits, jnp.int32(Tp - 1), seed)          # (B,)
        return (tok0, logits, kc, vc, *states)

    def generate(params, head, prompt, seed, tok0, logits, kc, vc, *states):
        Tp = prompt.shape[1]
        if cfg.plan is not None:    # the prefill program's carry ends at Tp
            with scope("prefill"):
                states = plan.lengthened(cfg, states, Tp + max_new)
        layer_params = {k: params[k] for k in layer_leaves(cfg)}
        # the dropless experts' kernel reads its layer out of the stack
        whole = EXPERT_LEAVES if cfg.moe_top_k else ()

        def gen(carry, _):
            kc, vc, *states, tok, pos = carry
            with scope("embed"):
                h = tfm._lookup(cfg, params["emb"], tok)[:, None, :]
                if hy is not None:
                    h = h * hy.embedding_multiplier

            # the whole stacked cache is this loop's carry too; as a
            # scan's xs and ys it would be sliced out and copied back
            def per_layer(layer, state):
                lp = {k: w if k in whole
                      else lax.dynamic_index_in_dim(w, layer, keepdims=False)
                      for k, w in layer_params.items()}
                return _step_layer(cfg, comm, lp, *state[:3], layer, pos,
                                   state[3:])

            with scope("layers"):
                if cfg.plan is not None:    # a python loop over the plan
                    h, *states = plan.step(cfg, comm, layer_params, h,
                                           states, pos)
                else:
                    h, kc, vc, *states = lax.fori_loop(
                        0, cfg.n_layers, per_layer, (h, kc, vc, *states))
            with scope("unembed"):
                h = _rmsnorm(h, params["lnf"], cfg.norm_eps)
                if hy is not None:
                    h = h * hy.lm_head_multiplier
                logits = tfm._whole_vocab(cfg, jnp.einsum(
                    "bd,vd->bv", h[:, 0, :], head,
                    preferred_element_type=jnp.float32))
            with scope("sample"):
                nxt = pick(logits, pos, seed)
            out = (nxt, logits[:keep_logits]) if keep_logits else nxt
            return (kc, vc, *states, nxt, pos + 1), out

        # emit the PRODUCED token and scan max_new-1 steps: tok0 is
        # already known from prefill, so the last single-token pass is
        # not computed just to be thrown away
        # (the scope is around the scan, not inside ``gen``, so that a
        # copy XLA makes of the loop's carry would be the step's as well)
        with scope("decode.step"):
            (_kc, _vc, *states, _tok, _pos), toks = lax.scan(
                gen, (kc, vc, *states, tok0, jnp.int32(Tp)), None,
                length=max_new - 1)
        if keep_logits:
            toks, kept = toks
            kept = jnp.concatenate([logits[None, :keep_logits], kept],
                                   axis=0).swapaxes(0, 1)
        gen_toks = jnp.concatenate(
            [tok0[None], toks], axis=0)       # (max_new, B)
        tokens = jnp.concatenate([prompt, gen_toks.swapaxes(0, 1)], axis=1)
        return ((tokens, kept) if keep_logits else tokens), tuple(states)

    return unembedding, prefill, generate


def _greedy(decode, temperature: float):
    """``decode`` as :func:`make_decoder` hands it out."""
    if temperature:
        return decode
    # greedy keeps its two-argument signature; seed is inert
    import numpy as _np

    return lambda params, prompt: decode(params, prompt, _np.int32(0))


def _one_program(cfg: TransformerConfig, mesh, max_new: int,
                 temperature: float, top_k: int, keep_logits: int):
    """:func:`make_decoder`: prefill and generation in one program."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.core import scopes

    unembedding, prefill, generate = _halves(
        cfg, mesh, max_new, temperature, top_k, keep_logits)

    def local(params, prompt, seed):
        head = unembedding(params)
        return generate(params, head, prompt, seed,
                        *prefill(params, head, prompt, seed))[0]

    mapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(param_specs(P, cfg, mesh), P("dp", None), P()),
        out_specs=(P("dp", None), P()) if keep_logits else P("dp", None),
        check_vma=False)
    record = scopes.program("decode")

    # the function's name is the program's name in a profile and in the
    # host's record (``scopes.startup()``)
    @jax.jit
    def decode(params, prompt, seed):
        record.traced()
        return mapped(params, prompt, seed)

    return _greedy(decode, temperature)


@functools.lru_cache(maxsize=8)
def _prefill_program(cfg: TransformerConfig, mesh, temperature: float,
                     top_k: int, keep_logits: int):
    """jitted (params, prompt (B, Tp), seed) -> (tokens (B, Tp+1), logits
    (keep_logits, 1, V), carry): what a decoder of ``max_new=1`` returns, and
    the carry ``Tp`` positions long (``plan.carry``'s buffers).  One object
    for every ``max_new`` of a configuration on a mesh, so one executable:
    :func:`_two_programs` says why."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.core import scopes

    unembedding, prefill, _ = _halves(cfg, mesh, 0, temperature, top_k,
                                      keep_logits)

    def local(params, prompt, seed):
        tok0, logits, _kc, _vc, *states = prefill(
            params, unembedding(params), prompt, seed)
        return (jnp.concatenate([prompt, tok0[:, None]], axis=1),
                logits[:keep_logits, None], tuple(states))

    mapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(param_specs(P, cfg, mesh), P("dp", None), P()),
        out_specs=(P("dp", None), P(), P(None, "dp")), check_vma=False)
    record = scopes.program("decode")

    # both halves of a job are ``decode`` in a profile and in the host's
    # record (``scopes.startup()``), as the one program is
    @jax.jit
    def decode(params, prompt, seed):
        record.traced()
        return mapped(params, prompt, seed)

    return decode


def _two_programs(cfg: TransformerConfig, mesh, max_new: int,
                  temperature: float, top_k: int, keep_logits: int):
    """:func:`make_decoder` of a configuration with a layer plan: the prefill
    a program of its own (:func:`_prefill_program`), and for ``max_new > 1``
    a second that takes its carry over (donated: the states are updated in
    the buffers the prefill filled), lengthens the latent caches to ``Tp +
    max_new`` and generates.

    Every decoder of one configuration on one mesh starts from the same
    prefill executable, so what two of them make of the same prompts is the
    same bits, the first token too.  Two programs that each hold a prefill
    are compiled apart; on the chip a few of 384 first tokens then differed
    between ``max_new=1`` and ``max_new=128`` in every run (PR 45): a sum in
    another order turns a router's tie somewhere in a prompt, and the state
    remembers it.  A service that answers with the first token from one
    program and goes on from another cannot have that."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.core import scopes
    from ompi_tpu.models import plan

    first = _prefill_program(cfg, mesh, float(temperature), top_k,
                             keep_logits)
    if max_new == 1:
        def decode(params, prompt, seed):
            tokens, kept, _carry = first(params, prompt, seed)
            return (tokens, kept) if keep_logits else tokens

        return _greedy(decode, temperature)

    unembedding, _, generate = _halves(cfg, mesh, max_new, temperature,
                                       top_k, keep_logits)
    # a latent cache grows to Tp + max_new here, so its buffer is of no use
    # to this program's outputs; every other one is written where it lies
    grows = plan.grows(cfg)

    def local(params, tokens, kept, seed, fixed, growing):
        fixed, growing = list(fixed), list(growing)
        carry = [(growing if g else fixed).pop(0) for g in grows]
        return generate(params, unembedding(params), tokens[:, :-1], seed,
                        tokens[:, -1], kept[:, 0], None, None, *carry)

    mapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(param_specs(P, cfg, mesh), P("dp", None), P(), P(),
                  P(None, "dp"), P(None, "dp")),
        out_specs=((P("dp", None), P()) if keep_logits else P("dp", None),
                   P(None, "dp")),
        check_vma=False)
    record = scopes.program("decode")

    # the states come back so that each is written in the buffer it came
    # in: a donated buffer is reused for an output of its shape alone
    @functools.partial(jax.jit, donate_argnums=4)
    def decode(params, tokens, kept, seed, fixed, growing):
        record.traced()
        return mapped(params, tokens, kept, seed, fixed, growing)

    def both(params, prompt, seed):
        tokens, kept, carry = first(params, prompt, seed)
        return decode(params, tokens, kept, seed,
                      [b for b, g in zip(carry, grows) if not g],
                      [b for b, g in zip(carry, grows) if g])[0]

    return _greedy(both, temperature)
