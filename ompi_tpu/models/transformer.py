"""Flagship model: a 3D-parallel (dp × sp × tp) transformer LM whose every
communication goes through this framework's device collectives.

This is the "7B-param data-parallel gradient harness" config of BASELINE.json
generalized: data parallelism over ``dp``, sequence/context parallelism over
``sp`` (ring attention — K/V ppermute ring, exact online-softmax), Megatron
column/row tensor parallelism over ``tp`` (one psum per block), gradient
synchronization over dp×sp via the AD transpose of replicated params.

Everything is expressed with shard_map + explicit collectives (no GSPMD
auto-sharding): the model is the framework's integration test and benchmark.
Compute dtype is bfloat16 (MXU-native), accumulation float32.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import numpy as np

__all__ = ["TransformerConfig", "FLAGSHIP", "FLAGSHIP_BATCH", "init_params",
           "param_specs", "shard_params", "make_loss_fn", "make_train_step",
           "make_train_loop", "make_forward"]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 32_000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    seq: int = 512
    attention: str = "ring"  # ring | ulysses | flash | xla | gathered
    # The word selects a layout over "sp", never an implementation: "flash",
    # "xla" and "ulysses" all mean the ulysses layout (they are kept because
    # configuration files and tests spell them), and the local attention of
    # every layout is parallel/attention.local_impl("auto")'s choice from
    # shape and the mesh's platform (the pallas flash kernels, forward and
    # backward, on a mesh of TPUs from 2048 keys a device; the jnp path
    # elsewhere).
    # MoE model family: >0 replaces every layer's dense FFN with a
    # switch-MoE of this many experts, sharded over the mesh's "ep" axis
    # (experts % ep == 0); the load-balancing aux loss joins the training
    # loss with weight moe_aux_weight.
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # Experts a token: 0 keeps the top-1 switch above (capacity drops, the
    # aux loss, experts over "ep").  k >= 1 routes every token to its k
    # most probable experts and drops none (parallel/moe.routed_moe:
    # sort by expert, grouped matmul, weighted sum back); every expert is
    # then on every device (ep == 1) and no aux term joins the loss.
    moe_top_k: int = 0
    # Gated experts: down(silu(gate(x)) * up(x)) with a third leaf "w3"
    # (up) beside "w1" (gate) and "w2" (down), instead of w2(gelu(w1 x)).
    moe_gated: bool = False
    # RMSNorm of q and k over their whole projected width, before the
    # split into heads and the rotary embedding (leaves "qn", "kn").
    qk_norm: bool = False
    norm_eps: float = 1e-6
    # False: the output projection is a leaf of its own, "head" (V, D),
    # and "emb" is a lookup table only.
    tie_head: bool = True
    # chunked cross-entropy: >0 computes the loss over sequence chunks of
    # this length without materializing the full (B, T, V) logits/log-
    # softmax pair — at vocab 32k that pair is the single largest HBM
    # tensor in the train step (f32, ~4 GiB at batch 16 / seq 1024).
    # Each chunk's gradient is made in the pass that makes its logits
    # (a custom_vjp: nothing recomputed), so the peak is O(chunk·V).  0 = full.
    ce_chunk: int = 0
    compute_dtype: Any = "bfloat16"
    # jax.checkpoint policy per layer — HBM ↔ FLOPs trade:
    #   True/"full" = save only layer inputs (max recompute, min HBM);
    #   "dots"      = save matmul outputs, recompute elementwise (cheap
    #                 recompute, still drops the big attention temporaries);
    #   False/None  = no remat (fastest when activations fit).
    remat: Any = "dots"
    # AdamW first-moment dtype: "bfloat16" halves the m buffer (~0.9 GiB
    # at 468M params) — the HBM lever that lets batch 32 fit without
    # paying full remat.  The second moment stays f32 (v's dynamic range
    # spans grad², where bf16's 8-bit mantissa visibly hurts; m is a
    # smoothed gradient and tolerates it — standard mixed-precision
    # Adam practice).  None = f32 moments.
    adam_mu_dtype: Any = None
    # Parameter STORAGE dtype (distinct from compute_dtype, which is the
    # matmul dtype).  "bfloat16": live params and their gradients are
    # bf16; the optimizer keeps a float32 master copy and applies
    # updates there, so small lr·update increments are not lost to
    # bf16's 8-bit mantissa — the standard mixed-precision
    # master-weights scheme.  Note this is HBM-NEUTRAL on one chip (the
    # resident f32 master cancels what bf16 params+grads save); its
    # value is halved param-read bandwidth per step and, under dp
    # sharding, a master/optimizer tree that can shard ZeRO-style while
    # live params stay replicated.  None/float32 = f32, no master.
    param_dtype: Any = None
    # Gradient accumulation: >1 splits the batch into this many
    # microbatches inside ONE compiled step — a lax.scan accumulates
    # the (mean) gradients, then the optimizer runs once.  Peak
    # activation memory scales with the MICRObatch, so effective batch
    # sizes that would OOM in one pass fit.  Constraints: batch %
    # grad_accum == 0 AND (batch / grad_accum) % dp == 0 (each
    # microbatch still shards over dp).
    grad_accum: int = 1
    # ZeRO-1: name a mesh axis (normally "dp") to shard the optimizer's
    # persistent tree (f32 master + Adam moments) over it — each rank
    # stores/updates 1/dp of every leaf and XLA's SPMD partitioner
    # inserts the one all-gather per leaf that re-replicates updated
    # params (see parallel/zero.py).  None = replicated optimizer state.
    zero1_axis: Any = None
    # K/V heads, each serving n_heads / n_kv_heads query heads in order
    # (0: as many as query heads), and a head's width where it is not
    # d_model / n_heads (0).  The cache holds the K/V heads.
    n_kv_heads: int = 0
    head_width: int = 0
    rope_theta: Any = 10_000
    # models/ssm.HybridBlock: every layer runs a state-space mixer beside
    # attention on one normed input, its dense MLP is gated (a third leaf
    # "w3": down(silu(gate(x)) * up(x))), and the block's constant
    # multipliers apply.  None: the block above.
    hybrid: Any = None
    # The most tokens one pass of a decoder's prefill holds: the prompts are
    # then prefilled a group of whole sequences at a time, each group writing
    # into the cache that was allocated once.  0: every prompt in one pass.
    prefill_tokens: int = 0

    @property
    def head_dim(self) -> int:
        return self.head_width or self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads


# The one model the repo runs at a real size: 468M dense, 16 heads of 128.
# chip_smoke.py takes the dims from here and varies them with
# dataclasses.replace.  ce_chunk drops the (B, T, V) f32 logits +
# log-softmax pair (~4 GiB at batch 16) to O(chunk·V); batch 24 ran out of
# HBM on one 16 GB chip in the old sweep.
FLAGSHIP = TransformerConfig(
    vocab=32_000, d_model=2048, n_heads=16, n_layers=8, d_ff=8192,
    seq=1024, attention="xla", ce_chunk=256, compute_dtype="bfloat16",
    remat="dots")
FLAGSHIP_BATCH = 16


def init_params(cfg: TransformerConfig, seed: int = 0) -> dict:
    """Global (unsharded) parameter pytree; layers stacked for lax.scan."""
    rng = np.random.default_rng(seed)
    L, D, F, V = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab

    def w(*shape, scale=None):
        scale = scale if scale is not None else (shape[-2] ** -0.5)
        return rng.normal(0, scale, size=shape).astype(np.float32)

    Dq, Dkv = cfg.n_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    params = {
        "emb": w(V, D, scale=0.02),
        "wq": w(L, D, Dq), "wk": w(L, D, Dkv), "wv": w(L, D, Dkv),
        "wo": w(L, Dq, D, scale=(Dq ** -0.5) / max(1, 2 * L) ** 0.5),
        "ln1": np.ones((L, D), np.float32),
        "ln2": np.ones((L, D), np.float32),
        "lnf": np.ones((D,), np.float32),
    }
    if cfg.qk_norm:
        params["qn"] = np.ones((L, D), np.float32)
        params["kn"] = np.ones((L, D), np.float32)
    if not cfg.tie_head:
        params["head"] = w(V, D, scale=0.02)
    if cfg.moe_experts:
        E = cfg.moe_experts
        params["wg"] = w(L, D, E, scale=0.02)
        params["w1"] = w(L, E, D, F)
        params["w2"] = w(L, E, F, D,
                         scale=(F ** -0.5) / max(1, 2 * L) ** 0.5)
        if cfg.moe_gated:
            params["w3"] = w(L, E, D, F)
    else:
        params["w1"] = w(L, D, F)
        params["w2"] = w(L, F, D, scale=(F ** -0.5) / max(1, 2 * L) ** 0.5)
    if cfg.hybrid is not None:
        from ompi_tpu.models import ssm

        params.update(ssm.init_leaves(cfg, rng))
    if cfg.param_dtype not in (None, "float32"):
        # live params are stored in param_dtype; the optimizer's f32
        # master copy is created from them at init (one-time rounding)
        import jax.numpy as jnp

        sd = jnp.dtype(cfg.param_dtype)
        params = {k: np.asarray(v).astype(sd) for k, v in params.items()}
    return params


def param_specs(P, cfg: Optional[TransformerConfig] = None, mesh=None):
    """PartitionSpecs: attention weights tp-sharded Megatron-style, dense
    FFN tp-sharded, MoE experts ep-sharded (replicated when the mesh has
    no "ep" axis), everything else replicated (grad-synced over dp/sp/tp
    by the AD transpose).  The embedding (and an untied head) is stored
    whole on every device: the loss splits its work over ``tp`` by
    positions (:func:`_local_loss`), not by vocabulary rows, so each
    rank's gradient of the table is a partial sum over its positions that
    the transpose's psum over dp, sp and tp completes."""
    specs = {
        "emb": P(), "lnf": P(), "ln1": P(), "ln2": P(),
        "wq": P(None, None, "tp"), "wk": P(None, None, "tp"),
        "wv": P(None, None, "tp"), "wo": P(None, "tp", None),
    }
    if cfg is not None and cfg.qk_norm:
        specs["qn"] = specs["kn"] = P(None, "tp")
    if cfg is not None and not cfg.tie_head:
        specs["head"] = P()
    if cfg is not None and cfg.moe_experts:
        has_ep = mesh is not None and "ep" in mesh.axis_names
        specs["wg"] = P()
        specs["w1"] = P(None, "ep", None, None) if has_ep else P()
        specs["w2"] = P(None, "ep", None, None) if has_ep else P()
        if cfg.moe_gated:
            specs["w3"] = specs["w1"]
    else:
        specs["w1"] = P(None, None, "tp")
        specs["w2"] = P(None, "tp", None)
    if cfg is not None and cfg.hybrid is not None:
        from ompi_tpu.models import ssm

        specs.update({leaf: P() for leaf in ssm.leaf_names()})
    return specs


def shard_params(cfg: TransformerConfig, mesh, params: dict) -> dict:
    """Place each parameter on ``mesh`` with its :func:`param_specs`
    sharding.  ``init(params)`` of the optimizers then builds state with
    the same shardings (``zeros_like`` keeps them), so the first step
    neither reshards nor loses its donation."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    specs = param_specs(P, cfg, mesh)
    return {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in params.items()}


def _rmsnorm(x, scale, eps: float = 1e-6):
    import jax.numpy as jnp
    from jax import lax

    xf = x.astype(jnp.float32)
    norm = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (norm * scale).astype(x.dtype)


def _qk_norm(cfg, x, scale, comm):
    """RMSNorm of a projected q or k over its whole width ``d_model``, of
    which this device holds the ``tp`` shard of its heads: the squares are
    summed over ``tp`` before the root."""
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import coll

    if int(comm.mesh.shape["tp"]) == 1:
        return _rmsnorm(x, scale, cfg.norm_eps)
    xf = x.astype(jnp.float32)
    with coll("allreduce", "tp"):
        total = lax.psum(jnp.sum(xf * xf, axis=-1, keepdims=True), "tp")
    norm = xf * lax.rsqrt(total / cfg.d_model + cfg.norm_eps)
    return (norm * scale).astype(x.dtype)


def layer_leaves(cfg: TransformerConfig) -> tuple:
    """Names of the leaves stacked over layers: what the layer loops of the
    backbone and of the cached decode step slice a layer's parameters
    from."""
    leaves = ["wq", "wk", "wv", "wo", "w1", "w2", "ln1", "ln2"]
    if cfg.moe_experts:
        leaves.append("wg")
        if cfg.moe_gated:
            leaves.append("w3")
    if cfg.qk_norm:
        leaves += ["qn", "kn"]
    if cfg.hybrid is not None:
        from ompi_tpu.models import ssm

        leaves += ssm.leaf_names()
    return tuple(leaves)


def _head(cfg: TransformerConfig, params):
    """The (V, D) output projection: the embedding itself when tied."""
    return params["emb"] if cfg.tie_head else params["head"]


# TransformerConfig.attention's words, as layouts of parallel/attention
_ATTENTION_LAYOUT = {"ring": "ring", "ulysses": "ulysses", "flash": "ulysses",
                     "xla": "ulysses", "gathered": "gathered"}


def _rope(x, positions, impl: str = "jnp", theta=10_000):
    """Rotary embeddings with *global* positions (sp-offset aware).
    ``impl`` is the local attention's that reads the result
    (``parallel/attention.layout_impl``): for "flash" the pallas form where
    it tiles (``ops/rope.py``: the same sums on row-major (B, T, H·D),
    which is what the flash kernels read)."""
    import jax.numpy as jnp

    B, T, H, D = x.shape
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # (T, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if impl == "flash":
        from ompi_tpu.ops import rope as rope_kernel

        if rope_kernel.rope_tiles(T, H, D, x.dtype):
            return rope_kernel.rope(x, cos, sin)
    x1, x2 = x[..., :half], x[..., half:]
    rot = jnp.concatenate([x1 * cos[None, :, None] - x2 * sin[None, :, None],
                           x1 * sin[None, :, None] + x2 * cos[None, :, None]],
                          axis=-1)
    return rot.astype(x.dtype)


def _moe_ffn_tail(cfg, h, lp, comm, layer=None):
    """Post-attention half of the MoE layer: ln2 → ep-sharded switch, or
    dropless top-k routed experts → residual (shared by the training
    layer, the prefill and the cached decode step — one source of truth,
    like _dense_ffn_tail).  Returns (h, aux).  With ``layer``, the
    dropless path's expert leaves (``moe.EXPERT_LEAVES``) are the whole
    stacks over layers and ``layer`` this layer's index in them
    (``routed_moe`` says why)."""
    import jax.numpy as jnp

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.parallel.moe import EXPERT_LEAVES, routed_moe, switch_moe

    with scope("ffn"):
        x = _rmsnorm(h, lp["ln2"], cfg.norm_eps)
        if cfg.moe_top_k:
            if int(dict(comm.mesh.shape).get("ep", 1)) > 1:
                raise ValueError(
                    f"moe_top_k={cfg.moe_top_k} routes without drops, so "
                    f"the exchange over ep={comm.mesh.shape['ep']} would be "
                    f"ragged: not built; keep every expert on the device "
                    f"(ep == 1), or use the top-1 switch (moe_top_k=0)")
            weights = {k: lp[k] for k in ("wg", *EXPERT_LEAVES) if k in lp}
            # the pallas kernel where the mesh is of TPUs (attached, or
            # described for a compile); XLA's ragged_dot on any other
            mo = routed_moe(x, weights, cfg.moe_top_k, gated=cfg.moe_gated,
                            layer=layer, kernel=comm.mesh.devices.flat[
                                0].platform == "tpu")
            return h + mo, jnp.zeros((), jnp.float32)
        mo, aux = switch_moe(
            comm, x, {"wg": lp["wg"], "w1": lp["w1"], "w2": lp["w2"]},
            axis="ep", capacity_factor=cfg.moe_capacity_factor,
            with_aux=True)
        return h + mo, aux


def _dense_ffn_tail(h, lp, comm, cdt, eps: float = 1e-6, gated=None):
    """Post-attention half of the dense layer: ln2 → gelu MLP →
    residual (shared by the training layer and the cached decode step,
    models/decode.py — one source of truth for this math).  ``gated``, a
    pair of multipliers (m0, m1): down(silu(gate(x)·m0) * up(x))·m1 with
    the up projection in a third leaf "w3"."""
    import jax

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.parallel.layers import column_parallel, row_parallel

    with scope("ffn"):
        x = _rmsnorm(h, lp["ln2"], eps)
        if gated is not None:
            y = (jax.nn.silu(column_parallel(x, lp["w1"].astype(cdt))
                             * gated[0])
                 * column_parallel(x, lp["w3"].astype(cdt)))
            return h + row_parallel(y, lp["w2"].astype(cdt), comm,
                                    axis="tp") * gated[1]
        y = jax.nn.gelu(column_parallel(x, lp["w1"].astype(cdt)))
        return h + row_parallel(y, lp["w2"].astype(cdt), comm, axis="tp")


def _local_backbone(cfg: TransformerConfig, comm, params, tokens,
                    collect_kv: bool = False):
    """Per-device forward through the final rmsnorm (everything except the
    unembed matmul).

    tokens: (B/dp, S/sp) int32.  Returns (h (B/dp, S/sp, D) compute-dtype,
    aux) — aux is the summed MoE load-balancing loss (0.0 for dense).
    With ``collect_kv`` returns (h, (aux, k, v)) where k/v are the
    post-rope per-layer attention inputs stacked (L, B, T, Hkv/tp, hd) —
    the KV-cache prefill (models/decode.py); with a hybrid block
    (h, (aux, k, v, conv, ssm)): every layer's mixer states after the last
    position too, stacked alike, the second in the block's ``state_dtype``.
    With that block h comes scaled by its ``lm_head_multiplier``.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.parallel import attention as attn_mod
    from ompi_tpu.parallel.layers import column_parallel, row_parallel

    cdt = jnp.dtype(cfg.compute_dtype)
    tp = int(comm.mesh.shape["tp"])
    sp = int(comm.mesh.shape["sp"])
    hy = cfg.hybrid
    if hy is not None:
        from ompi_tpu.models import ssm

        ssm.check_mesh(cfg, comm.mesh)
    h_local, kv_local = cfg.n_heads // tp, cfg.kv_heads // tp
    hd = cfg.head_dim
    T = tokens.shape[1]
    sp_idx = lax.axis_index("sp")
    positions = sp_idx * T + jnp.arange(T)
    # one choice of local attention for the layer, made where the layouts
    # make theirs; the rotary embedding writes what that attention reads
    layout = _ATTENTION_LAYOUT.get(cfg.attention, "gathered")
    attend = getattr(attn_mod, layout + "_attention")
    shape = (tokens.shape[0], T, h_local, hd)
    impl = attn_mod.layout_impl(comm, layout, shape, shape, cdt, "sp")

    with scope("embed"):
        h = params["emb"][tokens].astype(cdt)  # (b, t, D)
        if hy is not None:
            h = h * hy.embedding_multiplier

    def layer(h, lp):
        with scope("attn_proj"):
            x = _rmsnorm(h, lp["ln1"], cfg.norm_eps)
            xa = x if hy is None else x * hy.attention_in_multiplier
            q = column_parallel(xa, lp["wq"].astype(cdt))
            k = column_parallel(xa, lp["wk"].astype(cdt))
            v = column_parallel(xa, lp["wv"].astype(cdt))
            if hy is not None:
                k = k * hy.key_multiplier
            if cfg.qk_norm:
                q = _qk_norm(cfg, q, lp["qn"], comm)
                k = _qk_norm(cfg, k, lp["kn"], comm)
            B, t = x.shape[0], x.shape[1]
            q = _rope(q.reshape(B, t, h_local, hd), positions, impl,
                      cfg.rope_theta)
            k = _rope(k.reshape(B, t, kv_local, hd), positions, impl,
                      cfg.rope_theta)
            v = v.reshape(B, t, kv_local, hd)
            k_all, v_all = k, v
            if kv_local != h_local:     # each K/V head before its queries
                k_all, v_all = (jnp.repeat(y, h_local // kv_local, axis=2)
                                for y in (k, v))
        with scope("attention"):
            o = attend(comm, q, k_all, v_all, axis="sp", impl=impl)
        with scope("attn_proj"):
            o = o.reshape(B, t, h_local * hd)
            a = row_parallel(o, lp["wo"].astype(cdt), comm, axis="tp")
            if hy is None:
                h = h + a
        if hy is not None:
            # both branches read the one normed input and share a residual
            s, *states = ssm.mixer(cfg, lp, x)
            with scope("attn_proj"):
                h = h + a * hy.attention_out_multiplier + s
        if cfg.moe_experts:
            # MoE family: expert-parallel switch FFN over the "ep" axis
            # (tp ranks replicate the expert compute — activations are
            # identical across tp after the row_parallel psum)
            h, aux = _moe_ffn_tail(cfg, h, lp, comm)
        else:
            h = _dense_ffn_tail(h, lp, comm, cdt, cfg.norm_eps,
                                gated=hy and hy.mlp_multipliers)
            aux = jnp.zeros((), jnp.float32)
        if collect_kv and hy is not None:
            return h, (aux, k, v, states[0],
                       states[1].astype(hy.state_dtype))
        if collect_kv:
            return h, (aux, k, v)
        return h, aux

    layer_params = {k: params[k] for k in layer_leaves(cfg)}
    if cfg.remat in (True, "full"):
        layer_fn = jax.checkpoint(layer)
    elif cfg.remat == "dots":
        # a pallas_call's result is no saveable dot: the flash kernel's out
        # and lse are kept by name, or its forward would run twice
        from ompi_tpu.ops.flash_attention import RESIDUAL_NAMES

        policies = jax.checkpoint_policies
        layer_fn = jax.checkpoint(layer, policy=policies.save_from_both_policies(
            policies.dots_with_no_batch_dims_saveable,
            policies.save_only_these_names(*RESIDUAL_NAMES)))
    else:
        layer_fn = layer
    with scope("layers"):
        h, ys = lax.scan(layer_fn, h, layer_params)
    h = _rmsnorm(h, params["lnf"], cfg.norm_eps)
    if hy is not None:
        h = h * hy.lm_head_multiplier
    if collect_kv:
        aux, *cached = ys
        return h, (aux.sum(), *cached)
    return h, ys.sum()


def _local_forward(cfg: TransformerConfig, comm, params, tokens):
    """Per-device forward inside shard_map.

    tokens: (B/dp, S/sp) int32.  Returns (logits (B/dp, S/sp, V) float32,
    aux) — aux is the summed MoE load-balancing loss (0.0 for dense).
    """
    h, aux = _local_backbone(cfg, comm, params, tokens)
    return _unembed(cfg, h, _head(cfg, params)), aux


def _unembed(cfg: TransformerConfig, h, emb):
    """(B, T, D) -> (B, T, V) float32 logits: on the MXU in compute dtype
    with f32 accumulation — a f32×f32 matmul here would run at a fraction
    of the bf16 rate."""
    import jax.numpy as jnp

    return jnp.einsum("btd,vd->btv", h,
                      emb.astype(jnp.dtype(cfg.compute_dtype)),
                      preferred_element_type=jnp.float32)


def _chunked_nll_sum(cfg: TransformerConfig, h, emb, labels, weight):
    """Σ weight·nll over the local shard WITHOUT materializing the full
    (B, T, V) logits: lax.scan over sequence chunks, one vocabulary matmul
    a chunk.  A jax.custom_vjp: where it is differentiated, the same scan
    forms each chunk's (softmax − onehot)·weight from the logits it has
    just made and multiplies it into the gradients of h and emb (three
    vocabulary matmuls a chunk), so no chunk's logits are kept or made
    twice; the backward pass only scales the two by its cotangent.

    h: (B, T, D) compute dtype; emb: (V, D) f32; labels: (B, T) int32;
    weight: (B, T) f32.  Returns a f32 scalar.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    B, T, D = h.shape
    c = cfg.ce_chunk
    n = T // c

    def scan_chunks(body, init, *xs):  # each x: (B, T, ...) -> (n, B, c, ...)
        return lax.scan(body, init, tuple(
            jnp.moveaxis(x.reshape(B, n, c, *x.shape[2:]), 1, 0) for x in xs))

    def chunk_nll(emb_c, h_c, lab_c, w_c):
        logits = jnp.einsum("btd,vd->btv", h_c, emb_c,
                            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab_logit = jnp.take_along_axis(
            logits, lab_c[..., None], axis=-1)[..., 0]
        return logits, lse, ((lse - lab_logit) * w_c).sum()

    @jax.custom_vjp
    def nll_sum(h, emb, labels, weight):
        emb_c = emb.astype(h.dtype)

        def body(acc, inp):
            return acc + chunk_nll(emb_c, *inp)[2], None

        total, _ = scan_chunks(body, jnp.zeros((), jnp.float32),
                               h, labels, weight)
        return total

    def fwd(h, emb, labels, weight):
        emb_c = emb.astype(h.dtype)

        def body(carry, inp):
            acc, d_emb = carry
            h_c, lab_c, w_c = inp  # (B, c, D), (B, c), (B, c)
            logits, lse, nll = chunk_nll(emb_c, h_c, lab_c, w_c)
            # d nll / d logits in f32, rounded where it meets the MXU
            d_logits = ((jnp.exp(logits - lse[..., None])
                         - jax.nn.one_hot(lab_c, logits.shape[-1],
                                          dtype=jnp.float32))
                        * w_c[..., None]).astype(h.dtype)
            d_h = jnp.einsum("btv,vd->btd", d_logits, emb_c,
                             preferred_element_type=jnp.float32)
            d_emb = d_emb + jnp.einsum("btv,btd->vd", d_logits, h_c,
                                       preferred_element_type=jnp.float32)
            return (acc + nll, d_emb), d_h.astype(h.dtype)

        (total, d_emb), d_hs = scan_chunks(
            body, (jnp.zeros((), jnp.float32),
                   jnp.zeros(emb.shape, jnp.float32)), h, labels, weight)
        d_h = jnp.moveaxis(d_hs, 0, 1).reshape(B, T, D)
        return total, (d_h, d_emb.astype(emb.dtype))

    def bwd(res, g):
        d_h, d_emb = res
        return ((g * d_h).astype(d_h.dtype),
                (g * d_emb).astype(d_emb.dtype), None, None)

    nll_sum.defvjp(fwd, bwd)
    return nll_sum(h, emb, labels, weight)


def _local_loss(cfg: TransformerConfig, comm, params, tokens):
    """Next-token cross entropy; labels cross sp-shard boundaries via a ring
    shift (the first token of my right neighbor labels my last position).

    The hidden states are the same on every rank of a ``tp`` group (the last
    ``row_parallel`` psum left them so) and the head is stored whole on each,
    so where ``tp`` divides the local length, rank ``r`` of ``tp`` takes the
    cross entropy of local positions ``[r·T/tp, (r+1)·T/tp)`` alone and the
    sums are added over ``tp`` as over ``dp`` and ``sp``: each position's
    logits are made once a group, not ``tp`` times.  Differentiated, the
    slice pads the hidden states' gradient with zeros outside the rank's
    positions and the head's gradient is a partial sum on each rank; the
    sums over ``tp`` that the backward pass already holds (a
    ``row_parallel`` psum's transpose, shard_map's psum for a replicated
    leaf) add the parts.  A length ``tp`` does not divide keeps every
    position on every rank."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import coll, scope

    sp = int(comm.mesh.shape["sp"])
    tp = int(comm.mesh.shape["tp"])
    B, T = tokens.shape
    sp_idx = lax.axis_index("sp")

    # labels: tokens shifted left by one *global* position
    first_col = tokens[:, :1]
    if sp == 1:
        from_right = first_col  # self-permute: skip the channel op
    else:
        # neighbor's first token: device r receives from r+1 (shift -1)
        perm = [((i + 1) % sp, i) for i in range(sp)]
        with coll("permute", "sp"):
            from_right = lax.ppermute(first_col, "sp", perm)
    labels = jnp.concatenate([tokens[:, 1:], from_right], axis=1)
    # the final global position has no next token
    positions = sp_idx * T + jnp.arange(T)
    weight = (positions < cfg.seq - 1).astype(jnp.float32)[None, :]

    h, aux = _local_backbone(cfg, comm, params, tokens)
    sum_axes = ("dp", "sp")
    with scope("loss"):
        if tp > 1 and T % tp == 0:
            T = T // tp
            start = lax.axis_index("tp") * T
            h, labels, weight = (
                lax.dynamic_slice_in_dim(x, start, T, axis=1)
                for x in (h, labels, weight))
            sum_axes += ("tp",)
        if cfg.ce_chunk and T % cfg.ce_chunk == 0:
            local_sum = _chunked_nll_sum(
                cfg, h, _head(cfg, params), labels,
                jnp.broadcast_to(weight, (B, T)))
        else:
            logprobs = jax.nn.log_softmax(
                _unembed(cfg, h, _head(cfg, params)), axis=-1)
            nll = -jnp.take_along_axis(
                logprobs, labels[..., None], axis=-1)[..., 0]
            local_sum = (nll * weight).sum()
    local_cnt = weight.sum() * B
    if all(int(comm.mesh.shape[a]) == 1 for a in sum_axes):
        total, count = local_sum, local_cnt  # psum is identity
    else:
        with coll("allreduce", sum_axes):
            total = lax.psum(local_sum, sum_axes)
            count = lax.psum(local_cnt, sum_axes)
    loss = total / count
    if cfg.moe_experts and not cfg.moe_top_k:
        # average the per-device balance loss over the whole mesh (tp/ep
        # ranks see replicated tokens, so the mean is layout-invariant)
        if comm.size == 1:
            aux_mean = aux
        else:
            with coll("allreduce", comm.axes):
                aux_mean = lax.psum(aux, comm.axes) / comm.size
        loss = loss + cfg.moe_aux_weight * aux_mean
    return loss


def make_loss_fn(cfg: TransformerConfig, mesh):
    """shard_map'd global loss: (params, tokens) → scalar."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.mpi.device_comm import DeviceCommunicator

    axes = tuple(a for a in ("dp", "sp", "tp", "ep")
                 if a in mesh.axis_names)
    comm = DeviceCommunicator(mesh, axes)

    local = functools.partial(_local_loss, cfg, comm)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(param_specs(P, cfg, mesh), P("dp", "sp")),
        out_specs=P(), check_vma=False)


def make_forward(cfg: TransformerConfig, mesh):
    """shard_map'd forward: (params, tokens) → logits, for entry()/serving."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.mpi.device_comm import DeviceCommunicator

    axes = tuple(a for a in ("dp", "sp", "tp", "ep")
                 if a in mesh.axis_names)
    comm = DeviceCommunicator(mesh, axes)

    def local(params, tokens):
        return _local_forward(cfg, comm, params, tokens)[0]  # drop aux

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(param_specs(P, cfg, mesh), P("dp", "sp")),
        out_specs=P("dp", "sp", None), check_vma=False)


def _make_step_body(cfg: TransformerConfig, mesh, lr: float):
    """Shared optimizer-step body: (params, opt_state, tokens) →
    (params, opt_state, loss) — the single definition both the one-step
    and the scanned-loop entry points compile."""
    import jax
    import optax

    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope

    loss_fn = make_loss_fn(cfg, mesh)
    opt = optax.adamw(lr, b1=0.9, b2=0.95, weight_decay=0.01,
                      mu_dtype=cfg.adam_mu_dtype)

    accum = int(cfg.grad_accum)
    if accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {cfg.grad_accum}")

    def loss_and_grads(params, tokens):
        """(mean loss, mean grads) — one pass, or a lax.scan over
        ``grad_accum`` microbatches whose activations never coexist."""
        if accum == 1:
            return jax.value_and_grad(loss_fn)(params, tokens)
        B = tokens.shape[0]
        if B % accum:
            raise ValueError(f"batch {B} not divisible by "
                             f"grad_accum {accum}")
        micro = tokens.reshape(accum, B // accum, *tokens.shape[1:])

        def body(carry, toks):
            acc_loss, acc_g = carry
            loss, g = jax.value_and_grad(loss_fn)(params, toks)
            # accumulate in f32 even when grads arrive in a storage
            # dtype (param_dtype=bf16): summing K microbatches in bf16
            # rounds small components away before the optimizer's own
            # f32 cast ever sees them
            acc_g = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), acc_g, g)
            return (acc_loss + loss, acc_g), None

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (total, g_sum), _ = lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros), micro)
        inv = 1.0 / accum
        return total * inv, jax.tree_util.tree_map(
            lambda g: g * inv, g_sum)
    store = (None if cfg.param_dtype in (None, "float32", jnp.float32)
             else jnp.dtype(cfg.param_dtype))

    if cfg.zero1_axis:
        from jax.sharding import PartitionSpec as _P

        from ompi_tpu.parallel.zero import zero1_wrap

        z_init, z_update = zero1_wrap(
            opt, mesh, cfg.zero1_axis, param_dtype=store,
            # updated live params keep their Megatron/MoE shardings —
            # only the zero1-axis redundancy is re-gathered
            param_specs=param_specs(_P, cfg, mesh))

        def body(params, opt_state, tokens):
            loss, grads = loss_and_grads(params, tokens)
            params, opt_state = z_update(grads, opt_state, params)
            return params, opt_state, loss

        class _ZeroOpt:
            init = staticmethod(z_init)

        return body, _ZeroOpt

    if store is None:
        def body(params, opt_state, tokens):
            loss, grads = loss_and_grads(params, tokens)
            with scope("optimizer"):
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        return body, opt

    # master-weights scheme: live params (and grads) in `store` dtype,
    # f32 master copy updated by the optimizer, live params re-derived
    # by casting the master down each step
    def master_init(params):
        master = jax.tree_util.tree_map(
            lambda p: jnp.asarray(p, jnp.float32), params)
        return {"opt": opt.init(master), "master": master}

    def body(params, opt_state, tokens):
        loss, grads = loss_and_grads(params, tokens)
        with scope("optimizer"):
            g32 = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32), grads)
            updates, inner = opt.update(g32, opt_state["opt"],
                                        opt_state["master"])
            master = optax.apply_updates(opt_state["master"], updates)
            params = jax.tree_util.tree_map(
                lambda m: m.astype(store), master)
        return params, {"opt": inner, "master": master}, loss

    class _MasterOpt:
        init = staticmethod(master_init)

    return body, _MasterOpt


def _init_on_mesh(cfg: TransformerConfig, mesh, init):
    """``init`` with every leaf of its state committed to ``mesh``.

    The parameters are placed first, so leaves that mirror one inherit its
    sharding (``zeros_like`` keeps it); what is left, the step counters,
    is replicated.  A state that enters the first step with an uncommitted
    or differently placed leaf comes back placed, which makes the second
    call compile the whole step again.
    """
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    def on_mesh(params):
        state = init(shard_params(cfg, mesh, params))
        replicated = NamedSharding(mesh, P())
        return jax.tree_util.tree_map(
            lambda x: x if isinstance(x.sharding, NamedSharding)
            else jax.device_put(x, replicated), state)

    return on_mesh


def make_train_step(cfg: TransformerConfig, mesh, lr: float = 3e-4):
    """jitted (params, opt_state, tokens) → (params, opt_state, loss).

    AdamW via optax; gradients arrive already synchronized (psum over
    dp/sp/tp is the AD transpose of the replicated in_specs; tp shards
    update their local slice only — exactly ZeRO-0 + Megatron semantics).
    Rank r of ``tp`` computes the loss of local positions
    ``[r·T/tp, (r+1)·T/tp)`` (:func:`_local_loss`), so before that psum a
    replicated leaf's gradient is each rank's partial sum, not a copy.
    """
    import jax

    body, opt = _make_step_body(cfg, mesh, lr)

    # params/opt_state are donated: the updated trees reuse their HBM
    # in place of a second full copy (≈1.6 GiB at 133M params with Adam).
    # The function's name is the program's name in a profile.
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, tokens):
        return body(params, opt_state, tokens)

    return train_step, _init_on_mesh(cfg, mesh, opt.init)


def make_train_loop(cfg: TransformerConfig, mesh, lr: float = 3e-4,
                    steps: int = 8):
    """jitted (params, opt_state, tokens) → (params, opt_state, losses):
    ``steps`` optimizer steps inside ONE compiled program (lax.scan over
    the step), donated carry.

    One dispatch per K steps keeps the chip busy back-to-back: the host's
    per-call dispatch cost is paid once per K steps.
    """
    import jax
    from jax import lax

    body, opt = _make_step_body(cfg, mesh, lr)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_loop(params, opt_state, tokens):
        def scan_body(carry, _):
            p, s, loss = body(*carry, tokens)
            return (p, s), loss

        (params, opt_state), losses = lax.scan(
            scan_body, (params, opt_state), None, length=steps)
        return params, opt_state, losses

    return train_loop, _init_on_mesh(cfg, mesh, opt.init)
