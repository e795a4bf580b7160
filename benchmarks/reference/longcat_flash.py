"""Plain decoder of LongCat-Flash-Chat as one chip of an expert-parallel group
computes it: the shortcut-connected layer (two latent attentions with a query
latent and two dense MLPs in sequence, and one routed branch that reads the
first sublayer's normed post-attention stream and lands at the end of the
layer), a softmax router with a selection bias over the experts and the
identity experts, in float32 ``jax.numpy`` with nothing of the program in it.

No shard_map, no cache, no absorption, no kernel, no sort and no grouped
matmul: every head's keys and values are multiplied out of the latent at
every position, attention is dense under the causal mask, and every expert
*held here* is run on every token under the top-k mask.  Matrix
multiplications at ``jax.default_matmul_precision("highest")``, because a TPU
runs a float32 product in bfloat16 passes unless told otherwise.  A sublayer
at a time and an expert at a time: the parameters arrive as the program stores
them (bfloat16 on the chip, 10.35 GB resident), and each slice is upcast
inside the call that reads it (a dense MLP is 0.9 GB in float32, a whole
layer 5).  The logits are multiplied out only for the positions a caller
reads (:class:`PositionLogits`).

The layer, from the published keys (what no key settles is listed under
``assumed`` in the configuration file), ``h`` the stream, RMSNorm eps
``rms_norm_eps``, no bias anywhere, SiLU-gated MLPs:

    a1 = h  + MLA_0(RMSNorm(h;  ln1[2l]))
    x1 = RMSNorm(a1; ln2[2l])
    s  = MoE(x1)                                # the shortcut branch
    b1 = a1 + MLP_0(x1)                         # dense, ffn_hidden_size
    a2 = b1 + MLA_1(RMSNorm(b1; ln1[2l + 1]))
    x2 = RMSNorm(a2; ln2[2l + 1])
    h' = a2 + MLP_1(x2) + s

``MLA_i(x)``: ``c_q = a_q RMSNorm(x mla_qa; mla_qn)`` (``q_lora_rank``, ``a_q
= sqrt(hidden_size / q_lora_rank)`` with ``mla_scale_q_lora``); ``q = c_q
mla_qb`` (heads x (nope + rope)); ``[c_kv, k_r] = x mla_kva``; ``c = a_kv
RMSNorm(c_kv; mla_n)`` (``a_kv = sqrt(hidden_size / kv_lora_rank)`` with
``mla_scale_kv_lora``); ``[k_n, v] = c mla_kvb`` (heads x (nope + v)); the
rope parts of q and ``k_r`` rotated in neighbouring pairs at ``rope_theta``
(pair i by ``position x theta^(-2i / rope)``), ``k_r`` shared by all heads and
not scaled; scores x ``(nope + rope)^-1/2``, causal softmax, ``wo``.

``MoE(x)``: ``p = softmax(x wg)`` over all ``router_experts +
zero_expert_num`` outputs; picks = the ``moe_topk`` largest of ``p + wgb``
(the selection bias picks and does not weigh); ``g_e =
routed_scaling_factor p_e``, not renormalised; ``MoE(x) = sum over picks e <
router_experts held here of g_e Expert_e(x) + (sum over picks e >=
router_experts of g_e) x``.  This chip is one of several that share a layer
by expert and holds experts ``experts_held.first .. first + count - 1``: a
pick held elsewhere adds nothing here (the chip that holds it adds it), the
identity picks are this chip's own tokens' and are added whole.
:func:`moe` with ``held`` and ``identity`` given evaluates any share, for the
test that adds the shares up.

Last: RMSNorm ``lnf``, an untied head over the vocabulary rows held here.

The tree has the program's leaf names, because the reference is handed the
program's own parameters: a plan's row is one attention and one dense MLP, so
``ln1``, ``ln2``, the latent leaves and the dense MLP's are stacked over ``2
x num_layers`` rows (row ``2l + i`` is sublayer ``i`` of layer ``l``), the
router's and the held experts' over the layers.
"""

from __future__ import annotations

import dataclasses
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

# The declared scales of the seeded weights that the chip check rests on
# (``assumed`` in the configuration file; PERF.md has the chip's readings).
# Every leaf is centred on zero or is one (``lib/program.init_params``).  The
# stream starts at unit size (EMB) and every branch adds about half of that
# (the *_OUT factors on unit-gain products).  The query latent is drawn at
# QA_GAIN of unit gain, so that its RMSNorm is no identity at random weights
# (a latent of unit rms reads the same with its norm dropped: kimi-vl-a3b's
# lesson); the normed latents come out at a_q = 2 and a_kv = 3.46, so the
# up-projections are drawn at unit gain for the queries (scores of deviation
# about 2, as kimi-vl-a3b's Q_SCALE: a query weighs a few dozen of its
# positions and a third of a score's variance is the rotated part's) and at
# KVB_GAIN = 1 / a_kv for keys and values (unit keys, unit values).  The
# router: logits of deviation ROUTER_SPREAD over 768 outputs, so a token's
# twelve probabilities are 0.12 down to 0.014 and weigh 2.5 together after
# the factor 6 (numpy, tests/benchmarks/test_longcat_flash.py); the selection
# bias at deviation BIAS changes about one of a token's twelve picks.  An
# expert's output at EXPERT_OUT: eight real picks of about 0.2 each then add
# what a dense MLP adds.
EMB = 1.0
QA_GAIN = 0.5
KVB_GAIN = 12 ** -0.5
MLA_OUT = 0.5
DENSE_OUT = 0.5
EXPERT_OUT = 1.5
ROUTER_SPREAD = 2.0
BIAS = 0.002

HEAD_BLOCK = 32_768     # rows of the head upcast at a time


@dataclasses.dataclass(frozen=True)
class Shape:
    vocab: int
    d_model: int
    n_layers: int           # attending sublayers: a plan's rows, 2 a layer
    eps: float
    heads: int
    nope: int
    rope: int
    v_dim: int
    kv_rank: int
    q_rank: int
    q_scale: float          # a_q: what the normed query latent is times
    kv_scale: float         # a_kv
    theta: float
    d_ff: int               # a dense MLP's width
    d_expert: int
    n_router: int           # the router's outputs that are experts
    n_zero: int             # ... and, after them, identity experts
    top_k: int
    held: tuple             # (first, count): the experts on this chip
    scale: float            # routed_scaling_factor
    renorm: bool
    at_batch: int           # sequences a step, for the counters; 0: unknown

    @classmethod
    def from_config(cls, config: dict) -> "Shape":
        """From a configuration file's published keys, ``router_experts``
        (the published ``n_routed_experts``; the file's own counts the
        experts held) and ``experts_held``."""
        c = config
        if (c["attention_method"] != "MLA" or c["attention_bias"]
                or c["zero_expert_type"] != "identity"
                or c["q_lora_rank"] is None):
            raise ValueError("written for latent attention with a query "
                             "latent, no bias, and identity experts")
        held = c.get("experts_held", {"first": 0,
                                      "count": c["router_experts"]})
        if held["count"] != c["n_routed_experts"]:
            raise ValueError("n_routed_experts counts the experts held")
        D = c["hidden_size"]
        return cls(
            vocab=c["vocab_size"], d_model=D, n_layers=2 * c["num_layers"],
            eps=c["rms_norm_eps"], heads=c["num_attention_heads"],
            nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
            v_dim=c["v_head_dim"], kv_rank=c["kv_lora_rank"],
            q_rank=c["q_lora_rank"],
            q_scale=((D / c["q_lora_rank"]) ** 0.5
                     if c["mla_scale_q_lora"] else 1.0),
            kv_scale=((D / c["kv_lora_rank"]) ** 0.5
                      if c["mla_scale_kv_lora"] else 1.0),
            theta=float(c["rope_theta"]), d_ff=c["ffn_hidden_size"],
            d_expert=c["expert_ffn_hidden_size"],
            n_router=c["router_experts"], n_zero=c["zero_expert_num"],
            top_k=c["moe_topk"], held=(held["first"], held["count"]),
            scale=float(c["routed_scaling_factor"]),
            renorm=bool(c["norm_topk_prob"]),
            at_batch=int(c.get("counters", {}).get("sequences_a_step", 0)))

    @property
    def n_blocks(self) -> int:
        """The model's layers, two sublayers each."""
        return self.n_layers // 2


MLA_LEAVES = ("mla_qa", "mla_qn", "mla_qb", "mla_kva", "mla_n", "mla_kvb",
              "wo")
DENSE_LEAVES = ("dw1", "dw3", "dw2")
ROUTER_LEAVES = ("wg", "wgb")
EXPERT_LEAVES = ("w1", "w3", "w2")


def param_init(shape: Shape, serving: bool = False
               ) -> dict[str, tuple[tuple[int, ...], float | None]]:
    """Leaf name -> (shape, standard deviation of its normal initializer);
    ``None`` marks a leaf that starts at one (the norms' scales).  One draw,
    the constants above: no cell trains this configuration, so ``serving``
    changes nothing."""
    s = shape
    R, L, D, V = s.n_layers, s.n_blocks, s.d_model, s.vocab
    q, kv = s.heads * (s.nope + s.rope), s.heads * (s.nope + s.v_dim)
    out, F, Fe = s.heads * s.v_dim, s.d_ff, s.d_expert
    E, held = s.n_router + s.n_zero, s.held[1]
    return {
        "emb": ((V, D), EMB),
        "head": ((V, D), D ** -0.5),
        "ln1": ((R, D), None),
        "ln2": ((R, D), None),
        "lnf": ((D,), None),
        "mla_qa": ((R, D, s.q_rank), QA_GAIN * D ** -0.5),
        "mla_qn": ((R, s.q_rank), None),
        "mla_qb": ((R, s.q_rank, q), s.q_rank ** -0.5),
        "mla_kva": ((R, D, s.kv_rank + s.rope), D ** -0.5),
        "mla_n": ((R, s.kv_rank), None),
        "mla_kvb": ((R, s.kv_rank, kv), KVB_GAIN * s.kv_rank ** -0.5),
        "wo": ((R, out, D), MLA_OUT * out ** -0.5),
        "dw1": ((R, D, F), D ** -0.5),
        "dw3": ((R, D, F), D ** -0.5),
        "dw2": ((R, F, D), DENSE_OUT * F ** -0.5),
        "wg": ((L, D, E), ROUTER_SPREAD * D ** -0.5),
        "wgb": ((L, E), BIAS),
        "w1": ((L, held, D, Fe), D ** -0.5),
        "w3": ((L, held, D, Fe), D ** -0.5),
        "w2": ((L, held, Fe, D), EXPERT_OUT * Fe ** -0.5),
    }


def counts(shape: Shape) -> dict:
    """What ``lib/costs.py`` counts of this family on this chip.

    ``active_params``: what one token multiplies *here*: each sublayer's five
    latent matrices and its dense MLP's three; a layer's router (all its
    outputs) and, of the experts a token picks, the fraction that falls to
    this chip on the mean: ``top_k x n_router / (n_router + n_zero)`` of its
    picks are real experts (8 of 12) and ``held / n_router`` of those are
    here, 0.25 of an expert a token and layer, counted as the fraction it is;
    and the head.  The embedding is a lookup table (``lookup_params``).
    ``attention_layers``: every sublayer attends, two a layer;
    ``attention_width``: scores over ``nope + rope`` and a context over
    ``v_dim`` a head, so ``heads x (nope + rope + v_dim) / 2`` makes
    ``lib/costs.prefill_flops``'s ``4 x layers x width x T`` their count.
    ``kv_elements``: the normed latent and the rotated shared key part of one
    position in one sublayer.  ``routed``: the held experts (what the chip
    streams a step); its ``top_k`` is a whole number by the harness's form,
    1, where 0.25 picks a token land here: no metric this cell reports reads
    it."""
    s = shape
    D, V = s.d_model, s.vocab
    mla = (D * s.q_rank + s.q_rank * s.heads * (s.nope + s.rope)
           + D * (s.kv_rank + s.rope)
           + s.kv_rank * s.heads * (s.nope + s.v_dim)
           + s.heads * s.v_dim * D)
    expert = 3 * D * s.d_expert
    here = s.top_k * s.held[1] / (s.n_router + s.n_zero)    # picks a token
    moe = D * (s.n_router + s.n_zero) + round(here * expert)
    block = s.n_layers * (mla + 3 * D * s.d_ff) + s.n_blocks * moe
    return {"active_params": block + V * D,
            "projection_params": V * D,
            "lookup_params": V * D,
            "kv_elements": s.kv_rank + s.rope,
            "attention_layers": s.n_layers,
            "attention_width": s.heads * (s.nope + s.rope + s.v_dim) // 2,
            "routed": {"layers": s.n_blocks, "experts": s.held[1],
                       "top_k": 1, "d_model": D, "d_expert": s.d_expert}}


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rotary(x, theta: float):
    """x (B, T, ..., P) at positions 0 .. T - 1: elements 2i and 2i + 1 are a
    pair, turned by ``position x theta^(-2i / P)``."""
    T, P = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, P, 2, dtype=jnp.float32) / P)
    ang = (jnp.arange(T, dtype=jnp.float32)[:, None] * inv).reshape(
        T, *(1,) * (x.ndim - 3), P // 2)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      b * jnp.cos(ang) + a * jnp.sin(ang)],
                     axis=-1).reshape(x.shape)


def _mla(shape: Shape, p: dict, x):
    s = shape
    B, T, _ = x.shape
    H, N, P, W, R = s.heads, s.nope, s.rope, s.v_dim, s.kv_rank
    c_q = s.q_scale * _rmsnorm(x @ p["mla_qa"], p["mla_qn"], s.eps)
    q = (c_q @ p["mla_qb"]).reshape(B, T, H, N + P)
    kva = x @ p["mla_kva"]
    c = s.kv_scale * _rmsnorm(kva[..., :R], p["mla_n"], s.eps)
    kv = (c @ p["mla_kvb"]).reshape(B, T, H, N + W)
    q = jnp.concatenate([q[..., :N], rotary(q[..., N:], s.theta)], axis=-1)
    k_r = rotary(kva[..., R:], s.theta)
    k = jnp.concatenate([kv[..., :N], jnp.broadcast_to(
        k_r[:, :, None, :], (B, T, H, P))], axis=-1)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (N + P) ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, kv[..., N:])
    return o.reshape(B, T, H * W) @ p["wo"]


def route(shape: Shape, p: dict, x):
    """(B, T, n_router + n_zero) weights: zero but at a token's ``top_k``
    picks, where they are the softmax's probabilities times ``scale`` (and,
    with ``renorm``, over their sum first)."""
    s = shape
    prob = jax.nn.softmax(x @ p["wg"], axis=-1)
    _best, at = jax.lax.top_k(prob + p["wgb"], s.top_k)
    picked = jax.nn.one_hot(at, s.n_router + s.n_zero,
                            dtype=prob.dtype).sum(axis=-2)
    weight = prob * picked
    if s.renorm:
        weight = weight / weight.sum(axis=-1, keepdims=True)
    return weight * s.scale


def _gated(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


@functools.partial(jax.jit, static_argnums=(0, 2))
def _attend(shape, stacks, row, h):
    """``h + MLA(RMSNorm(h; ln1[row]))``; ``stacks`` the latent leaves as
    stored and ``ln1``."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v[row], jnp.float32) for k, v in stacks.items()}
        return h + _mla(shape, p, _rmsnorm(h, p["ln1"], shape.eps))


@functools.partial(jax.jit, static_argnums=(0, 2))
def _normed(shape, ln2, row, a):
    """``RMSNorm(a; ln2[row])``: what row ``row``'s dense MLP reads, and the
    branch that reads there."""
    return _rmsnorm(a, jnp.asarray(ln2[row], jnp.float32), shape.eps)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _dense(shape, stacks, row, x):
    """``MLP(x)`` of row ``row``."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(stacks[k][row], jnp.float32)
             for k in DENSE_LEAVES}
        return _gated(x, p["dw1"], p["dw3"], p["dw2"])


@functools.partial(jax.jit, static_argnums=(0, 2, 4, 5))
def moe(shape, stacks, layer, x, held=None, identity=True):
    """``(MoE(x), the router's weights)`` of layer ``layer`` on the normed
    stream ``x``; the experts read out of their stacks one at a time.
    ``held`` (first, count): the share evaluated, of experts stacked from
    ``first`` on (the chip's own by default); ``identity``: with the identity
    picks' part, which every chip adds for its own tokens."""
    first, count = held or shape.held
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(stacks[k][layer], jnp.float32)
             for k in ROUTER_LEAVES}
        weight = route(shape, p, x)

        def one(e, total):
            gate, up, down = (jnp.asarray(stacks[k][layer, e], jnp.float32)
                              for k in EXPERT_LEAVES)
            w = jax.lax.dynamic_index_in_dim(weight, first + e, axis=-1)
            return total + w * _gated(x, gate, up, down)

        out = jax.lax.fori_loop(0, count, one, jnp.zeros_like(x))
        if identity and shape.n_zero:
            out = out + weight[..., shape.n_router:].sum(
                axis=-1, keepdims=True) * x
        return out, weight


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
            [_project(self._shape, self._head[lo:lo + HEAD_BLOCK], h)
             for lo in range(0, self._head.shape[0], HEAD_BLOCK)], axis=-1)
        return out[(..., *at[2:])] if len(at) > 2 else out

    def __jax_array__(self):
        return self[:, :]

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self[:, :], dtype)


def forward(shape: Shape, params: dict, tokens, weights: list | None = None):
    """(B, T) int32 tokens -> the last norm's output (B, T, D) float32.
    ``weights``: a list that is handed every layer's router weights (B, T,
    n_router + n_zero), for :func:`counters`."""
    s = shape
    h = jnp.asarray(params["emb"][tokens], jnp.float32)
    latent = {k: params[k] for k in (*MLA_LEAVES, "ln1")}
    dense = {k: params[k] for k in DENSE_LEAVES}
    routed = {k: params[k] for k in (*ROUTER_LEAVES, *EXPERT_LEAVES)}
    for layer in range(s.n_blocks):
        a = _attend(s, latent, 2 * layer, h)
        x = _normed(s, params["ln2"], 2 * layer, a)
        branch, weight = moe(s, routed, layer, x)
        if weights is not None:
            weights.append(weight)
        b = a + _dense(s, dense, 2 * layer, x)
        a = _attend(s, latent, 2 * layer + 1, b)
        x = _normed(s, params["ln2"], 2 * layer + 1, a)
        h = a + _dense(s, dense, 2 * layer + 1, x) + branch
    return _rmsnorm(h, jnp.asarray(params["lnf"], jnp.float32), s.eps)


def counters(shape: Shape, weights: list, first: int = 0) -> dict:
    """The routing's counters from every layer's router weights of a forward
    pass, positions ``first`` on: ``moe_identity_pick_share``, the share of
    picks that are identity experts; ``moe_held_pick_share``, the share that
    land on the experts held here; and, where the configuration says how many
    sequences a step holds (``counters.sequences_a_step``),
    ``moe_empty_group_share``: the share of (step, held expert) pairs without
    a row at that batch, from the pooled rate at which a pick lands on one
    held expert, ``(1 - held share / held)^(sequences x top_k)``: an
    extrapolation from these sequences, where
    ``benchmarks/controls_longcat_flash.py``'s ``counters`` reads the
    program's own router over the whole batch."""
    s = shape
    picked = np.concatenate([np.asarray(w[:, first:] > 0).reshape(
        -1, s.n_router + s.n_zero) for w in weights])
    lo, n = s.held
    picks = picked.sum()
    out = {"moe_identity_pick_share":
           float(picked[:, s.n_router:].sum() / picks),
           "moe_held_pick_share": float(picked[:, lo:lo + n].sum() / picks)}
    if s.at_batch:
        out["moe_empty_group_share"] = float(
            (1 - out["moe_held_pick_share"] / n) ** (s.at_batch * s.top_k))
    return out


def logits(shape: Shape, params: dict, tokens) -> PositionLogits:
    """(B, T) int32 tokens -> (B, T, V) float32 logits, projected where they
    are read.  The routing's counters over these sequences go to stderr, in
    the form ``run.py`` prints a check's numbers in."""
    weights: list = []
    h = forward(shape, params, tokens, weights)
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
    z = PositionLogits(shape, params["head"], forward(
        shape, params, sequences))[:, prompt_len - 1:-1]
    chosen = jnp.take_along_axis(
        z, jnp.asarray(sequences)[:, prompt_len:, None], axis=-1)[..., 0]
    return (z.max(axis=-1) - chosen) / z.std(axis=-1)
