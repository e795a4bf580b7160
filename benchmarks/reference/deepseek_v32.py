"""Plain decoder of DeepSeek-V3.2-Exp as one chip of an expert-parallel
deployment holds it: latent attention with a query latent, a YaRN-scaled
rotation and a learned index that selects the cached positions a query reads,
a leading dense MLP and then a sigmoid router with a selection bias whose
picks are limited to a token's best groups, over the experts held here beside
one shared expert; in float32 ``jax.numpy`` with nothing of the program in it.

No shard_map, no cache, no absorption, no kernel, no scan over slices, no
bisection and no grouped matmul: every head's keys and values are multiplied
out of the latent at every position, the index's scores are dense, a query's
set is the first ``index_topk`` of a sort of its scores,
attention is dense under that mask, and every held expert is run on every
token under the router's weights.  Everything is worked through in blocks so
that two sequences of 16,384 positions fit beside the program's parameters: a
sequence at a time, the selection's (T, T) mask ``QUERY_BLOCK`` queries at a
time, attention ``HEAD_BLOCK`` heads at a time inside that, the dense MLP
``ROW_BLOCK`` rows at a time, an expert at a time.  Matrix multiplications at
``jax.default_matmul_precision("highest")``, because a TPU runs a float32
product in bfloat16 passes unless told otherwise.  The parameters arrive as
the program stores them (bfloat16 on the chip), and each slice is upcast
inside the call that reads it.  The logits are multiplied out only for the
positions a caller reads (:class:`PositionLogits`).

The layer, from the published keys (what no key settles is listed under
``assumed`` in the configuration file).  A block is pre-norm: ``h +=
mixer(RMSNorm(h; ln1))``, ``h += mlp(RMSNorm(h; ln2))``, eps ``rms_norm_eps``;
a last norm ``lnf``; an untied head.

**Latent attention** (``q_lora_rank`` Q, ``kv_lora_rank`` R,
``qk_nope_head_dim`` N, ``qk_rope_head_dim`` P, ``v_head_dim`` W,
``num_attention_heads`` heads), on the normed stream ``x``: ``cq = RMSNorm(x
mla_qa; mla_qn)`` (Q); ``q = cq mla_qb`` (heads x (N + P), a head's N and
then its P); ``[c, k_r] = x mla_kva`` (R + P); ``c <- RMSNorm(c; mla_n)``;
``[k_n, v] = c mla_kvb`` (heads x (N + W)).  A head's ``q[N:]`` and the one
``k_r`` are rotated (:func:`rotary`); a head's key is ``[k_n, rotated k_r]``.

**The rotation** (``rope_theta``, ``rope_scaling`` of type yarn: ``factor``,
``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
``mscale``, ``mscale_all_dim``): elements 2i and 2i + 1 are a pair; pair i of
P / 2 turns by ``position x f_i``, ``f_i = (1 - r_i) theta^(-2i / P) + r_i
theta^(-2i / P) / factor``, ``r_i = clip((i - low) / (high - low), 0, 1)``,
``low = floor(P ln(original / (beta_fast 2 pi)) / (2 ln theta))``, ``high =
ceil(P ln(original / (beta_slow 2 pi)) / (2 ln theta))`` (:func:`yarn_pairs`);
cos and sin times ``m(mscale) / m(mscale_all_dim)``, ``m(s) = 0.1 s
ln(factor) + 1``, and the softmax scale ``(N + P)^-1/2 m(mscale_all_dim)^2``.

**The index** (``index_n_heads`` J, ``index_head_dim`` K, ``index_topk``):
``qI = cq wiq`` (J x K, from the query latent), ``kI = LayerNorm(x wik; ikn,
ikb)`` (one key of K a position), the first P elements of each rotated as
above, ``wI = x wiw J^-1/2 K^-1/2``; ``I[t, s] = sum_j wI[t, j] relu(qI[t, j]
. kI[s])`` for ``s <= t``; ``S_t`` the ``index_topk`` positions of the
largest ``I[t, :t + 1]`` (equal scores: the lower position first), all of
them while ``t < index_topk``.  Softmax in float32 over ``s in S_t`` of ``q_h . k_h[s]`` times
the scale; context over ``v_h``; ``wo``.

**MLP.**  The first ``first_k_dense_replace`` layers: ``dw2(silu(x dw1) * x
dw3)`` of ``intermediate_size``.  After them: ``s = sigmoid(x wg)`` over
``router_experts``; ``c = s + wgb`` (``noaux_tc``: the bias picks and does not
weigh); the router's outputs are ``n_group`` groups of neighbouring experts, a
group's score the sum of its two largest ``c``, the ``topk_group`` best groups
stay; the ``num_experts_per_tok`` largest ``c`` inside them are the picks;
their weights ``s`` at the picks over ``(their sum + 1e-20)``
(``norm_topk_prob``) times ``routed_scaling_factor``; an expert is
``w2(silu(x w1) * x w3)`` of ``moe_intermediate_size``, and of a token's picks
those among ``experts_held`` add here (the chips that share a layer add up to
the whole: ``tests/benchmarks/test_v32.py``); plus ``n_shared_experts`` shared
experts as one gated MLP on every token, unweighted.

The tree has the program's leaf names, because the reference is handed the
program's own parameters; each kind's leaves are stacked over the layers of
that kind, in order.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

# The declared scales of the seeded weights that the chip check rests on
# (``assumed`` in the configuration file; PERF.md has the chip's readings).
# Every leaf is centred on zero or is one (``lib/program.init_params``).  The
# stream starts at unit size (EMB); the dense MLP, the experts and the shared
# expert each add about half of that (the *_OUT factors on unit-gain
# products) and attention a quarter (MLA_OUT, as keye-vl-2.0-30b-a3b's).
# The query's up-projection is drawn at Q_GAIN times unit gain: with the
# softmax scale's ``m^2`` = 1.87 a score then has a deviation of about 1.6,
# so a query weighs some 160 of its 2048 selected positions (2048 / e^2.56)
# and the selection shows (with unit-gain scores attention is a mean over
# positions, which no selection changes: PR 35's lesson).  Not more: the
# index's scores are made of a bfloat16 stream, and a rounding of them turns
# some sixty of a query's 2048 positions a layer at the threshold; at a
# deviation of 2.6 (Q_GAIN 1.4) two or three positions hold a query's whole
# weight and one of them turned is the whole context (the first chip run read
# 0.063 at the median), at 2.0 with attention at half the stream 0.023
# (PERF.md section 6, PR 67).
# The index's queries are at unit gain out of a normed latent and
# its key is LayerNormed with a bias drawn at IKB (a leaf is normal x
# deviation or ones): a pair's product has deviation about 11, a score about
# 0.7.  The router's logits have deviation ROUTER_SPREAD.  The selection
# bias is drawn at BIAS, small: it decides which experts a seed favours, and
# this chip's step reads the matrices of the held experts that have a row, so
# the share of a batch's picks that falls on the 32 held ones is a tenth of
# what moves ``decode_tokens_per_s`` between seeds.  At 0.02 (kimi-vl-a3b's,
# about one of a token's eight picks moved) that share varied by 6% between
# seeds and the rate by 1.1% between its quartiles, over the half of its
# bound that a cell is admitted under (my chip runs, PR 67: 980.0 to 997.5
# tokens/s against shares of 0.0335 to 0.0286); at 0.003 a draw of the bias
# adds 1% to the 1.6% that 4088 tokens' picks vary by on their own.
EMB = 1.0
Q_GAIN = 0.85
MLA_OUT = 0.25
DENSE_OUT = 0.5
EXPERT_OUT = 0.5
SHARED_OUT = 0.5
ROUTER_SPREAD = 1.0
BIAS = 0.003
IKB = 0.1

QUERY_BLOCK = 128       # queries whose index scores are held at a time
HEAD_BLOCK = 16         # heads whose scores over a block of queries are
ROW_BLOCK = 4096        # rows the dense MLP holds its width for at a time
HEAD_ROWS = 32_768      # rows of the head upcast at a time


@dataclasses.dataclass(frozen=True)
class Shape:
    vocab: int
    d_model: int
    n_layers: int
    eps: float
    n_dense: int            # leading layers whose MLP is dense
    heads: int
    nope: int
    rope: int
    v_dim: int
    kv_rank: int
    q_rank: int
    theta: float
    yarn: tuple             # (factor, original, beta_fast, beta_slow,
                            #  mscale, mscale_all_dim)
    index_heads: int
    index_dim: int
    index_topk: int
    d_ff: int               # the dense MLP's width
    d_expert: int
    n_router: int           # the router's outputs
    n_group: int
    topk_group: int
    top_k: int
    held: tuple             # (first, count): the experts on this chip
    n_shared: int
    scale: float            # routed_scaling_factor
    renorm: bool
    at_batch: int           # sequences a step, for ``counts``; 0: unknown

    @classmethod
    def from_config(cls, config: dict) -> "Shape":
        """From a configuration file's published keys, ``router_experts``
        (the published ``n_routed_experts``; the file's own counts the
        experts held) and ``experts_held``."""
        c, rs = config, config["rope_scaling"]
        if (c["scoring_func"] != "sigmoid" or c["topk_method"] != "noaux_tc"
                or c["moe_layer_freq"] != 1 or rs["type"] != "yarn"
                or c["q_lora_rank"] is None):
            raise ValueError("written for a sigmoid router with a selection "
                             "bias, every layer after the dense ones routed, "
                             "a yarn rotation and a query latent")
        held = c.get("experts_held", {"first": 0,
                                      "count": c["router_experts"]})
        if held["count"] != c["n_routed_experts"]:
            raise ValueError("n_routed_experts counts the experts held")
        return cls(
            vocab=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"], eps=c["rms_norm_eps"],
            n_dense=min(c["first_k_dense_replace"], c["num_hidden_layers"]),
            heads=c["num_attention_heads"], nope=c["qk_nope_head_dim"],
            rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
            kv_rank=c["kv_lora_rank"], q_rank=c["q_lora_rank"],
            theta=float(c["rope_theta"]),
            yarn=(float(rs["factor"]),
                  int(rs["original_max_position_embeddings"]),
                  float(rs["beta_fast"]), float(rs["beta_slow"]),
                  float(rs["mscale"]), float(rs["mscale_all_dim"])),
            index_heads=c["index_n_heads"], index_dim=c["index_head_dim"],
            index_topk=c["index_topk"], d_ff=c["intermediate_size"],
            d_expert=c["moe_intermediate_size"],
            n_router=c["router_experts"], n_group=c["n_group"],
            topk_group=c["topk_group"], top_k=c["num_experts_per_tok"],
            held=(held["first"], held["count"]),
            n_shared=c["n_shared_experts"],
            scale=float(c["routed_scaling_factor"]),
            renorm=bool(c["norm_topk_prob"]),
            at_batch=int(c.get("counters", {}).get("sequences_a_step", 0)))

    @property
    def n_routed(self) -> int:
        """Layers whose MLP is routed."""
        return self.n_layers - self.n_dense


MLA_LEAVES = ("mla_qa", "mla_qn", "mla_qb", "mla_kva", "mla_n", "mla_kvb",
              "wo", "wiq", "wik", "wiw", "ikn", "ikb")
DENSE_LEAVES = ("dw1", "dw3", "dw2")
ROUTER_LEAVES = ("wg", "wgb", "sw1", "sw3", "sw2")
EXPERT_LEAVES = ("w1", "w3", "w2")


def param_init(shape: Shape, serving: bool = False
               ) -> dict[str, tuple[tuple[int, ...], float | None]]:
    """Leaf name -> (shape, standard deviation of its normal initializer);
    ``None`` marks a leaf that starts at one (the norms' scales).  The latent
    and index leaves are stacked over all layers, the dense MLP's over the
    leading layers, the router's, the shared expert's and the held experts'
    over the routed ones.  One draw, the constants above: no cell trains this
    configuration, so ``serving`` changes nothing."""
    s = shape
    L, D, V = s.n_layers, s.d_model, s.vocab
    Ld, Lr = s.n_dense, s.n_routed
    q, kv = s.heads * (s.nope + s.rope), s.heads * (s.nope + s.v_dim)
    out = s.heads * s.v_dim
    F, Fe, Fs = s.d_ff, s.d_expert, s.d_expert * s.n_shared
    table = {
        "emb": ((V, D), EMB),
        "head": ((V, D), D ** -0.5),
        "ln1": ((L, D), None),
        "ln2": ((L, D), None),
        "lnf": ((D,), None),
        "mla_qa": ((L, D, s.q_rank), D ** -0.5),
        "mla_qn": ((L, s.q_rank), None),
        "mla_qb": ((L, s.q_rank, q), Q_GAIN * s.q_rank ** -0.5),
        "mla_kva": ((L, D, s.kv_rank + s.rope), D ** -0.5),
        "mla_n": ((L, s.kv_rank), None),
        "mla_kvb": ((L, s.kv_rank, kv), s.kv_rank ** -0.5),
        "wo": ((L, out, D), MLA_OUT * out ** -0.5),
        "wiq": ((L, s.q_rank, s.index_heads * s.index_dim),
                s.q_rank ** -0.5),
        "wik": ((L, D, s.index_dim), D ** -0.5),
        "wiw": ((L, D, s.index_heads), D ** -0.5),
        "ikn": ((L, s.index_dim), None),
        "ikb": ((L, s.index_dim), IKB),
    }
    if Ld:
        table.update({
            "dw1": ((Ld, D, F), D ** -0.5),
            "dw3": ((Ld, D, F), D ** -0.5),
            "dw2": ((Ld, F, D), DENSE_OUT * F ** -0.5),
        })
    if Lr:
        table.update({
            "wg": ((Lr, D, s.n_router), ROUTER_SPREAD * D ** -0.5),
            "wgb": ((Lr, s.n_router), BIAS),
            "w1": ((Lr, s.held[1], D, Fe), D ** -0.5),
            "w3": ((Lr, s.held[1], D, Fe), D ** -0.5),
            "w2": ((Lr, s.held[1], Fe, D), EXPERT_OUT * Fe ** -0.5),
            "sw1": ((Lr, D, Fs), D ** -0.5),
            "sw3": ((Lr, D, Fs), D ** -0.5),
            "sw2": ((Lr, Fs, D), SHARED_OUT * Fs ** -0.5),
        })
    return table


def idle_share(shape: Shape) -> float:
    """The share of (step, held expert) pairs without a row at
    ``at_batch`` sequences a step: a token picks ``top_k`` of the router's
    outputs, each alike by symmetry, so it leaves a given expert out with
    ``1 - top_k / n_router`` and a step's tokens all do with that to their
    number (0 where the batch is unknown: every expert counted read)."""
    s = shape
    return (1 - s.top_k / s.n_router) ** s.at_batch if s.at_batch else 0.0


def counts(shape: Shape) -> dict:
    """What ``lib/costs.py`` counts of this family on this chip, so that no
    share of a peak can pass 100%: each figure is what the leanest exact
    program needs, not what a dense one touches.

    ``active_params``: what one token multiplies *here*: every layer's five
    latent matrices and the index's three; the dense MLP's three; a routed
    layer's router (all its outputs), its shared experts and, of the experts
    a token picks, the fraction that falls to this chip on the mean (``top_k
    x held / n_router``, 0.25 of an expert a token and layer, counted as the
    fraction it is); and the head.  ``lookup_params``: what a cached step
    reads no more than rows of, or nothing: the embedding's table and, of
    the held experts' matrices, the share no token of a step is routed to at
    the cell's batch (:func:`idle_share`: 8 sequences x 8 picks fall on 256
    experts, so a held expert has no row in a step 0.776 of the time;
    ``lib/costs.decode_step_bytes`` would else count all of them read every
    step and ``decode_hbm_share`` could pass 100%).  ``kv_elements``: what a
    step must read of *every* live position in a layer, the index key alone;
    the latent rows it reads are those of the ``index_topk`` selected
    positions, of fixed size once a sequence is past ``index_topk``:
    ``state_elements``, over all layers.  ``attention_width``:
    ``lib/costs.prefill_flops`` counts ``4 x layers x width x T`` operations
    a position for attention.  A query's index scores against its ``t``
    earlier positions are ``2 x index_heads x index_dim`` operations a pair,
    ``index_heads x index_dim x T`` a position on the mean over a prompt of T
    (the causal half): a width of ``index_heads x index_dim / 4`` counts them
    exactly.  Attention itself over the ``min(t, topk)`` selected keys,
    ``2 x heads x (nope + rope + v_dim)`` a pair, is no multiple of T and
    cannot be written as a width: twice the index's width counts as much
    again, which stays under attention's own need for every prompt up to
    about 19,000 positions (at 15,872: 8192 T counted of 19,159 T needed), so
    ``prefill_mfu`` counts no operation that an exact program can skip.
    ``routed``: the held experts (what the chip may stream a step); its
    ``top_k`` is a whole number by the harness's form, 1, where 0.25 picks a
    token land here: no metric this cell reports reads it."""
    s = shape
    D, V = s.d_model, s.vocab
    mla = (D * s.q_rank + s.q_rank * s.heads * (s.nope + s.rope)
           + D * (s.kv_rank + s.rope)
           + s.kv_rank * s.heads * (s.nope + s.v_dim)
           + s.heads * s.v_dim * D)
    index = (s.q_rank * s.index_heads * s.index_dim + D * s.index_dim
             + D * s.index_heads)
    expert = 3 * D * s.d_expert
    here = s.top_k * s.held[1] / s.n_router         # picks a token, here
    moe = D * s.n_router + s.n_shared * expert + round(here * expert)
    block = (s.n_layers * (mla + index) + s.n_dense * 3 * D * s.d_ff
             + s.n_routed * moe)
    idle = round(idle_share(s) * s.held[1] * s.n_routed * expert)
    out = {"active_params": block + V * D,
           "projection_params": V * D,
           "lookup_params": V * D + idle,
           "kv_elements": s.index_dim,
           "state_elements": s.n_layers * s.index_topk * (s.kv_rank + s.rope),
           "attention_layers": s.n_layers,
           "attention_width": 2 * s.index_heads * s.index_dim // 4}
    if s.n_routed:
        out["routed"] = {"layers": s.n_routed, "experts": s.held[1],
                         "top_k": 1, "d_model": D, "d_expert": s.d_expert}
    return out


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _magnitude(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_pairs(shape: Shape) -> tuple:
    """``(low, high, frequencies)``: the ramp's ends and the radians a
    position of each of the ``rope / 2`` pairs (numpy float64)."""
    factor, original, fast, slow, _m, _ma = shape.yarn
    P, theta = shape.rope, shape.theta

    def pair(turns):
        return (P * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair(fast)), 0)
    high = min(math.ceil(pair(slow)), P - 1)
    i = np.arange(P // 2, dtype=np.float64)
    plain = theta ** (-2 * i / P)
    ramp = np.clip((i - low) / (high - low if high > low else 1e-3), 0, 1)
    return low, high, (1 - ramp) * plain + ramp * plain / factor


def softmax_scale(shape: Shape) -> float:
    factor, _o, _f, _s, _m, all_dim = shape.yarn
    return ((shape.nope + shape.rope) ** -0.5
            * _magnitude(factor, all_dim) ** 2)


def rotary(shape: Shape, x):
    """x (B, T, ..., P) at positions 0 .. T - 1: the pairs (2i, 2i + 1) moved
    to places (i, i + P/2), then ``x cos + rotate_half(x) sin`` with the
    scaled frequencies repeated over both halves (the family's published
    order of operations; a query and a key moved alike)."""
    T, P = x.shape[1], x.shape[-1]
    factor, _o, _f, _s, mscale, all_dim = shape.yarn
    times = _magnitude(factor, mscale) / _magnitude(factor, all_dim)
    x = jnp.swapaxes(x.reshape(*x.shape[:-1], P // 2, 2), -1, -2
                     ).reshape(x.shape)
    inv = jnp.asarray(yarn_pairs(shape)[2], jnp.float32)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv          # (T, P/2)
    ang = jnp.concatenate([ang, ang], axis=-1).reshape(
        T, *(1,) * (x.ndim - 3), P)
    half = jnp.concatenate([-x[..., P // 2:], x[..., :P // 2]], axis=-1)
    return (x * jnp.cos(ang) + half * jnp.sin(ang)) * times


def _blocks(y, block: int):
    """(T, ...) -> (n, block, ...), the tail padded with zeros."""
    n = -(-y.shape[0] // block)
    y = jnp.pad(y, [(0, n * block - y.shape[0])] + [(0, 0)] * (y.ndim - 1))
    return y.reshape(n, block, *y.shape[1:])


def selection(shape: Shape, cq, x, p):
    """``S_t`` of one sequence as a mask (T, T) bool, from its normed query
    latent cq (T, Q) and normed stream x (T, D)."""
    s = shape
    T, P = x.shape[0], s.rope
    qi = (cq @ p["wiq"]).reshape(T, s.index_heads, s.index_dim)
    ki = x @ p["wik"]
    ki = ki - ki.mean(axis=-1, keepdims=True)
    ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, axis=-1, keepdims=True) + s.eps)
    ki = ki * p["ikn"] + p["ikb"]
    wi = (x @ p["wiw"]) * (s.index_heads ** -0.5 * s.index_dim ** -0.5)
    qi = jnp.concatenate([rotary(s, qi[None, ..., :P])[0], qi[..., P:]], -1)
    ki = jnp.concatenate([rotary(s, ki[None, :, None, :P])[0, :, 0],
                          ki[..., P:]], -1)
    block = min(T, QUERY_BLOCK)

    def one(of):
        first, qi_b, wi_b = of
        found = jnp.einsum("qh,qhk->qk", wi_b, jax.nn.relu(
            jnp.einsum("qhd,kd->qhk", qi_b, ki)))
        causal = jnp.arange(T) <= (first + jnp.arange(block))[:, None]
        if s.index_topk >= T:
            return causal
        # the first index_topk of a stable sort by falling score: ties to
        # the lower position (a row of relu's sums can hold equal scores)
        order = jnp.argsort(-jnp.where(causal, found, -jnp.inf), axis=-1,
                            stable=True)[:, :s.index_topk]
        return causal & jnp.zeros((block, T), bool).at[
            jnp.arange(block)[:, None], order].set(True)

    n = -(-T // block)
    mask = jax.lax.map(one, (jnp.arange(n) * block, _blocks(qi, block),
                             _blocks(wi, block)))
    return mask.reshape(n * block, T)[:T]


def _mla_one(shape: Shape, p: dict, x):
    """One sequence's ``o wo``: x (T, D) normed."""
    s = shape
    T = x.shape[0]
    H, N, P, W, R = s.heads, s.nope, s.rope, s.v_dim, s.kv_rank
    cq = _rmsnorm(x @ p["mla_qa"], p["mla_qn"], s.eps)
    kva = x @ p["mla_kva"]
    c = _rmsnorm(kva[..., :R], p["mla_n"], s.eps)
    k_r = rotary(s, kva[None, :, R:])[0]                    # (T, P)
    mask = selection(s, cq, x, p)                           # (T, T)
    hb = min(H, HEAD_BLOCK)
    wq = p["mla_qb"].reshape(s.q_rank, H // hb, hb, N + P)
    wkv = p["mla_kvb"].reshape(R, H // hb, hb, N + W)
    block = min(T, 2 * QUERY_BLOCK)
    scale = softmax_scale(s)

    def heads(of):
        wq_b, wkv_b = of                        # (Q, hb, N + P), (R, hb, ..)
        q = jnp.einsum("tq,qhf->thf", cq, wq_b)
        q = jnp.concatenate([q[..., :N], rotary(s, q[None, ..., N:])[0]], -1)
        kv = jnp.einsum("tr,rhf->thf", c, wkv_b)
        k = jnp.concatenate([kv[..., :N], jnp.broadcast_to(
            k_r[:, None, :], (T, hb, P))], axis=-1)
        v = kv[..., N:]

        def one(of):
            q_b, mask_b = of
            sc = jnp.einsum("qhd,khd->hqk", q_b, k) * scale
            w = jax.nn.softmax(jnp.where(mask_b[None], sc, -1e30), -1)
            return jnp.einsum("hqk,khd->qhd", w, v)

        # a padded query (past the last position) sees no key: dropped
        o = jax.lax.map(one, (_blocks(q, block), _blocks(mask, block)))
        return o.reshape(-1, hb, W)[:T]

    o = jax.lax.map(heads, (jnp.moveaxis(wq, 1, 0), jnp.moveaxis(wkv, 1, 0)))
    return jnp.moveaxis(o, 0, 1).reshape(T, H * W) @ p["wo"]


def route(shape: Shape, p: dict, x):
    """(..., n_router) weights: zero but at a token's ``top_k`` picks."""
    s = shape
    score = jax.nn.sigmoid(x @ p["wg"])
    choice = score + p["wgb"]
    if s.n_group > 1:
        by_group = choice.reshape(*choice.shape[:-1], s.n_group, -1)
        group = jnp.sort(by_group, axis=-1)[..., -2:].sum(axis=-1)
        kth = jnp.sort(group, axis=-1)[..., -s.topk_group, None]
        choice = jnp.where((group >= kth)[..., None], by_group,
                           -jnp.inf).reshape(choice.shape)
    kth = jnp.sort(choice, axis=-1)[..., -s.top_k, None]
    weight = jnp.where(choice >= kth, score, 0.0)
    if s.renorm:
        weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-20)
    return weight * s.scale


def _gated(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


@functools.partial(jax.jit, static_argnums=(0, 2))
def _mixer_layer(shape, stacks, layer, h):
    """The mixer half of block ``layer``, a sequence at a time; ``stacks``
    the latent and index leaves as stored and ``ln1``."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v[layer], jnp.float32) for k, v in stacks.items()}
        return h + jax.lax.map(
            lambda row: _mla_one(shape, p, _rmsnorm(row, p["ln1"],
                                                    shape.eps)), h)


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _moe_layer(shape, stacks, at, layer, h):
    """The routed half of block ``layer``: the held experts read out of
    their stacks one at a time.  Returns ``(h, router weights)``."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(stacks[k][at], jnp.float32)
             for k in ROUTER_LEAVES}
        x = _rmsnorm(h, jnp.asarray(stacks["ln2"][layer], jnp.float32),
                     shape.eps)
        weight = route(shape, p, x)
        first, count = shape.held

        def one(e, total):
            gate, up, down = (jnp.asarray(stacks[k][at, e], jnp.float32)
                              for k in EXPERT_LEAVES)
            w = jax.lax.dynamic_index_in_dim(weight, first + e, axis=-1)
            return total + w * _gated(x, gate, up, down)

        out = jax.lax.fori_loop(0, count, one, jnp.zeros_like(x))
        if shape.n_shared:
            out = out + _gated(x, p["sw1"], p["sw3"], p["sw2"])
        return h + out, weight


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _dense_layer(shape, stacks, at, layer, h):
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(stacks[k][at], jnp.float32)
             for k in DENSE_LEAVES}
        B, T, D = h.shape
        x = _rmsnorm(h, jnp.asarray(stacks["ln2"][layer], jnp.float32),
                     shape.eps).reshape(B * T, D)
        rows = _blocks(x, min(B * T, ROW_BLOCK))
        out = jax.lax.map(
            lambda r: _gated(r, p["dw1"], p["dw3"], p["dw2"]), rows)
        return h + out.reshape(-1, D)[:B * T].reshape(B, T, D)


@functools.partial(jax.jit, static_argnums=0)
def _project(shape: Shape, rows, h):
    """``h`` already normed, onto a block of the head's rows."""
    with jax.default_matmul_precision("highest"):
        return h @ jnp.asarray(rows, jnp.float32).T


class PositionLogits:
    """The (B, T, V) float32 logits of a forward pass, multiplied out for the
    positions that are read: ``self[:, a:b]`` projects those positions'
    hidden states onto the head and is a ``jax`` array; ``np.asarray(self)``
    and ``jnp.asarray(self)`` project every position."""

    def __init__(self, shape: Shape, head, h) -> None:
        self._shape, self._head, self._h = shape, head, h   # h: normed
        self.shape = (*h.shape[:2], head.shape[0])
        self.dtype = jnp.dtype(jnp.float32)

    def __getitem__(self, at):
        at = at if isinstance(at, tuple) else (at,)
        h = self._h[at[:2]]
        out = jnp.concatenate(
            [_project(self._shape, self._head[lo:lo + HEAD_ROWS], h)
             for lo in range(0, self._head.shape[0], HEAD_ROWS)], axis=-1)
        return out[(..., *at[2:])] if len(at) > 2 else out

    def __jax_array__(self):
        return self[:, :]

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self[:, :], dtype)


def forward(shape: Shape, params: dict, tokens, weights=None):
    """(B, T) int32 tokens -> the last norm's output (B, T, D) float32;
    ``weights``: a list that every routed layer's router weights (B, T,
    n_router) are appended to."""
    s = shape
    h = jnp.asarray(params["emb"][tokens], jnp.float32)
    for layer in range(s.n_layers):
        h = _mixer_layer(s, {k: params[k] for k in (*MLA_LEAVES, "ln1")},
                         layer, h)
        if layer < s.n_dense:
            h = _dense_layer(s, {k: params[k] for k in (*DENSE_LEAVES, "ln2")},
                             layer, layer, h)
        else:
            h, weight = _moe_layer(s, {k: params[k] for k in (
                *ROUTER_LEAVES, *EXPERT_LEAVES, "ln2")},
                layer - s.n_dense, layer, h)
            if weights is not None:
                weights.append(weight)
    return _rmsnorm(h, jnp.asarray(params["lnf"], jnp.float32), s.eps)


def counters(shape: Shape, weights: list, first: int = 0) -> dict:
    """The routing's counters from every routed layer's router weights of a
    forward pass, positions ``first`` on: ``moe_held_pick_share``, the share
    of picks that land on the experts held here (``held / n_router`` by
    symmetry), and, where the configuration says how many sequences a step
    holds, ``moe_empty_group_share``: the share of (step, held expert) pairs
    without a row at that batch, from the pooled rate at which a token picks
    one held expert, ``(1 - top_k x held share / held)^sequences``: an
    extrapolation from these sequences, beside :func:`idle_share`, which
    ``counts`` takes from the shapes alone
    (``benchmarks/controls_deepseek_v32.py``'s ``counters`` reads the
    program's own router over the whole batch)."""
    s = shape
    picked = np.concatenate([np.asarray(w[:, first:] > 0).reshape(
        -1, s.n_router) for w in weights])
    lo, n = s.held
    out = {"moe_held_pick_share":
           float(picked[:, lo:lo + n].sum() / picked.sum())}
    if s.at_batch:
        out["moe_empty_group_share"] = float(
            (1 - s.top_k * out["moe_held_pick_share"] / n) ** s.at_batch)
    return out


def logits(shape: Shape, params: dict, tokens) -> PositionLogits:
    """(B, T) int32 tokens -> (B, T, V) float32 logits, projected where they
    are read.  The routing's counters over these sequences go to stderr, in
    the form ``run.py`` prints a check's numbers in."""
    weights: list = []
    h = forward(shape, params, tokens, weights)
    if weights:
        for name, value in counters(shape, weights).items():
            print(f"check {name} = {value}", file=sys.stderr)
    return PositionLogits(shape, params["head"], h)


def nll_sum(shape: Shape, params: dict, tokens):
    """Summed next-token negative log-likelihood over (B, T) tokens: position
    t predicts token t + 1, and the last position predicts nothing."""
    z = PositionLogits(shape, params["head"], forward(shape, params, tokens))
    logp = jax.nn.log_softmax(z[:, :-1], -1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -picked.sum()


def loss(shape: Shape, params: dict, tokens, block: int = 1) -> float:
    """Mean next-token cross entropy of a (B, T) batch, worked through in
    blocks of ``block`` sequences."""
    B, T = tokens.shape
    total = 0.0
    for lo in range(0, B, block):
        total += float(nll_sum(shape, params, tokens[lo:lo + block]))
    return total / (B * (T - 1))


def token_deficits(shape: Shape, params: dict, sequences, prompt_len: int):
    """For greedy continuations: how far below the reference's best logit
    the chosen token's reference logit lies, in units of the standard
    deviation of that position's logits; (B, T - prompt_len) float32."""
    z = logits(shape, params, sequences)[:, prompt_len - 1:-1]
    chosen = jnp.take_along_axis(
        z, jnp.asarray(sequences)[:, prompt_len:, None], axis=-1)[..., 0]
    return (z.max(axis=-1) - chosen) / z.std(axis=-1)
