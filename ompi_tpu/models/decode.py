"""Autoregressive decoding with a KV cache — the inference counterpart
of the train step, built from the same layer math.

TPU-first shape: ONE compiled program per (prompt_len, max_new) pair —
prefill runs the training backbone once (``collect_kv`` returns every
layer's post-rope K/V in a single pass), then a ``lax.scan`` generates
tokens against a static-shape cache (no growing arrays, no
recompilation per token).

The life of the cache: it is allocated once, stacked over layers at its
final length ``(L, B, Tp+max_new, Hl, hd)``, and from then on it is loop
carry — of the token scan and, inside it, of a ``lax.fori_loop`` over
the layer index.  A layer writes its new K/V in place at the one
position ``(l, 0, pos, 0, 0)`` of the whole stack and attention reads
layer ``l`` through a slice that the compiler fuses into the scores and
context products, so a step writes ``B·Hl·hd`` values a layer and reads
the cache once.  The cache is never the ``xs`` or ``ys`` of a scan:
those are separate buffers, and a step would then copy every layer's
cache out of the stack and back (``tests/parallel/test_decode.py`` holds
the compiled program to this).

Sharding: batch over dp, heads over tp (the cache is
head-sharded exactly like the weights); greedy argmax over the full
vocab.  Sequence parallelism is a training-time layout — decode
requires sp == 1.  MoE configs route each generated token through the
same layer as training and prefill (``_moe_ffn_tail``).  The top-1 switch
(``moe_top_k == 0``) computes its capacity per single-token step (B
tokens), so under a binding capacity the drop pattern can differ from a
full-sequence forward — cached and full paths agree exactly whenever
capacity doesn't bind.  The dropless path (``moe_top_k >= 1``) has no
capacity: a token's experts and their weights depend on that token alone,
so the cached step (``B·k`` rows over all experts, a handful a tile) and
the full forward agree at any batch, up to the order of summation.
"""

from __future__ import annotations

from ompi_tpu.models.transformer import (TransformerConfig,
                                         _dense_ffn_tail, _head,
                                         _moe_ffn_tail, _qk_norm, _rmsnorm,
                                         _rope, layer_leaves, param_specs)
from ompi_tpu.parallel.moe import EXPERT_LEAVES

__all__ = ["make_decoder"]


def _step_layer(cfg: TransformerConfig, comm, lp, h, kc, vc, layer, pos):
    """Layer ``layer`` for ONE new token position, against the whole cache.

    h: (B, 1, D); kc/vc: the stacked cache (L, B, Tmax, Hl, hd); lp:
    this layer's parameters, but for the dropless experts' leaves
    (``moe.EXPERT_LEAVES``), which are the whole stacks over layers that
    ``routed_moe`` indexes by ``layer``.  Returns (h, kc, vc) with the new
    token's k/v written in place at ``(layer, :, pos)``.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.parallel.layers import column_parallel, row_parallel

    cdt = h.dtype
    B = h.shape[0]
    Tmax, hl, hd = kc.shape[2:]

    with scope("attn_proj"):
        x = _rmsnorm(h, lp["ln1"], cfg.norm_eps)

        def project(w, norm=None):
            y = column_parallel(x, lp[w].astype(cdt))
            if cfg.qk_norm and norm:
                y = _qk_norm(cfg, y, lp[norm], comm)
            return y.reshape(B, 1, hl, hd)

        q, k, v = project("wq", "qn"), project("wk", "kn"), project("wv")
        q = _rope(q, pos[None])
        k = _rope(k, pos[None])
    with scope("kv_cache"):
        kc = lax.dynamic_update_slice(kc, k.astype(kc.dtype)[None],
                                      (layer, 0, pos, 0, 0))
        vc = lax.dynamic_update_slice(vc, v.astype(vc.dtype)[None],
                                      (layer, 0, pos, 0, 0))
    with scope("attention"):
        # scores against every cached position, masked beyond `pos`
        k_all = lax.dynamic_index_in_dim(kc, layer, keepdims=False)
        v_all = lax.dynamic_index_in_dim(vc, layer, keepdims=False)
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       k_all.astype(jnp.float32)) * (hd ** -0.5)
        live = jnp.arange(Tmax)[None, None, None, :] <= pos
        s = jnp.where(live, s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, v_all.astype(jnp.float32))
    with scope("attn_proj"):
        o = o.astype(cdt).reshape(B, 1, hl * hd)
        h = h + row_parallel(o, lp["wo"].astype(cdt), comm, axis="tp")
    if cfg.moe_experts:
        # aux is training-only; the dropless experts come as whole stacks
        h, _aux = _moe_ffn_tail(cfg, h, lp, comm,
                                layer=layer if cfg.moe_top_k else None)
        return h, kc, vc
    return _dense_ffn_tail(h, lp, comm, cdt, cfg.norm_eps), kc, vc


def make_decoder(cfg: TransformerConfig, mesh, max_new: int,
                 temperature: float = 0.0, top_k: int = 0):
    """jitted (params, prompt (B, Tp) int32[, seed]) → (B, Tp+max_new).

    Greedy decode by default: prefill through the training backbone
    (one pass, K/V collected per layer), then ``max_new`` single-token
    steps over the static cache.  Requires sp == 1; dense, switch-MoE
    and dropless top-k MoE configs are supported (MoE routes each token
    through the same layer as training).

    ``temperature > 0`` switches to sampling (optionally truncated to
    the ``top_k`` highest logits); the returned callable then takes a
    third argument ``seed`` (int32 scalar).  Each step folds the
    position — and the dp coordinate, so data-parallel shards draw
    independent noise — into the key; tp ranks share the key and hence
    agree on every sampled token (their logits are identical).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

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

    def local(params, prompt, seed):
        B, Tp = prompt.shape
        head = _head(cfg, params).astype(cdt)
        # ---- prefill: one training-backbone pass, K/V collected ----
        with scope("prefill"):
            h, (_aux, ks, vs) = tfm._local_backbone(
                cfg, comm, params, prompt, collect_kv=True)
            pad = [(0, 0), (0, 0), (0, max_new), (0, 0), (0, 0)]
            kc = jnp.pad(ks, pad)       # (L, B, Tp+max_new, Hl, hd)
            vc = jnp.pad(vs, pad)
            logits = jnp.einsum("bd,vd->bv", h[:, -1, :], head,
                                preferred_element_type=jnp.float32)
            tok0 = pick(logits, jnp.int32(Tp - 1), seed)          # (B,)

        layer_params = {k: params[k] for k in layer_leaves(cfg)}
        # the dropless experts' kernel reads its layer out of the stack
        whole = EXPERT_LEAVES if cfg.moe_top_k else ()

        def gen(carry, _):
            kc, vc, tok, pos = carry
            with scope("embed"):
                h = params["emb"][tok].astype(cdt)[:, None, :]  # (B, 1, D)

            # the whole stacked cache is this loop's carry too; as a
            # scan's xs and ys it would be sliced out and copied back
            def per_layer(layer, state):
                lp = {k: w if k in whole
                      else lax.dynamic_index_in_dim(w, layer, keepdims=False)
                      for k, w in layer_params.items()}
                return _step_layer(cfg, comm, lp, *state, layer, pos)

            with scope("layers"):
                h, kc, vc = lax.fori_loop(0, cfg.n_layers, per_layer,
                                          (h, kc, vc))
            with scope("unembed"):
                h = _rmsnorm(h, params["lnf"], cfg.norm_eps)
                logits = jnp.einsum("bd,vd->bv", h[:, 0, :], head,
                                    preferred_element_type=jnp.float32)
            with scope("sample"):
                nxt = pick(logits, pos, seed)
            return (kc, vc, nxt, pos + 1), nxt

        # emit the PRODUCED token and scan max_new-1 steps: tok0 is
        # already known from prefill, so the last single-token pass is
        # not computed just to be thrown away
        # (the scope is around the scan, not inside ``gen``, so that a
        # copy XLA makes of the loop's carry would be the step's as well)
        with scope("decode.step"):
            (_, _, _, _), toks = lax.scan(
                gen, (kc, vc, tok0, jnp.int32(Tp)), None,
                length=max_new - 1)
        gen_toks = jnp.concatenate(
            [tok0[None], toks], axis=0)       # (max_new, B)
        return jnp.concatenate([prompt, gen_toks.swapaxes(0, 1)], axis=1)

    mapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(param_specs(P, cfg, mesh), P("dp", None), P()),
        out_specs=P("dp", None), check_vma=False)
    # the function's name is the program's name in a profile
    @jax.jit
    def decode(params, prompt, seed):
        return mapped(params, prompt, seed)

    if temperature:
        return decode
    # greedy keeps its two-argument signature; seed is inert
    import numpy as _np

    return lambda params, prompt: decode(params, prompt, _np.int32(0))
