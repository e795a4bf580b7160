"""Plain decoder of Kimi-VL-A3B's language model (the ``text_config`` of
``Kimi-VL-A3B-Instruct``, published under DeepSeek-V3's keys): latent
attention with a rotary embedding on the shared key part in every layer, a
leading dense MLP and then a sigmoid router with a selection bias over 64
experts beside two shared ones, in float32 ``jax.numpy`` with nothing of the
program in it.

No shard_map, no cache, no absorption, no kernel, no sort and no grouped
matmul: every head's keys and values are multiplied out of the latent at
every position, attention is dense under the causal mask, a block of
``QUERY_BLOCK`` queries at a time against every key so that two sequences of
16,384 positions fit beside the program's parameters (a block's scores are
0.54 GB a sequence pair and 16 heads), and every expert is run on every token
under the top-k mask.  Matrix multiplications at
``jax.default_matmul_precision("highest")``, because a TPU runs a float32
product in bfloat16 passes unless told otherwise.  A layer at a time, an
expert at a time, the dense MLP ``ROW_BLOCK`` rows at a time: the parameters
arrive as the program stores them (bfloat16 on the chip), and each slice is
upcast inside the call that reads it.  The logits are multiplied out only for
the positions a caller reads (:class:`PositionLogits`: 2 x 16,384 x 163,840
float32 would be 21 GB).

The layers, from the published keys (what no key settles is listed under
``assumed`` in the configuration file).  A block is pre-norm: ``h +=
mixer(RMSNorm(h; ln1))``, ``h += mlp(RMSNorm(h; ln2))``, eps ``rms_norm_eps``;
a last norm ``lnf``; an untied head.

**Latent attention** (``kv_lora_rank`` R, ``qk_nope_head_dim`` N,
``qk_rope_head_dim`` P, ``v_head_dim`` W, ``num_attention_heads`` heads,
``q_lora_rank`` null, ``rope_theta``, ``rope_scaling`` null), on the normed
stream ``x``: ``q = x mla_q`` (heads x (N + P), a head's N and then its P);
``[c, k_r] = x mla_kva`` (R + P); ``c <- RMSNorm(c; mla_n)``; ``[k_n, v] = c
mla_kvb`` (heads x (N + W), a head's N and then its W).  The rotary embedding
as the family's published code applies it (from memory: ``assumed``): a
head's ``q[N:]`` and the one ``k_r`` have their elements 2i and 2i + 1 taken
as a pair, the pairs moved to places i and i + P/2 (the code's ``view(...,
P/2, 2).transpose``), and then turned split-half at the position, pair i by
``position x theta^(-2i / P)``.  A head's key is ``[k_n, rotated k_r]``,
``k_r`` shared by all heads; causal softmax of ``q . k (N + P)^-1/2``;
context over ``v``; ``wo``.

**MLP.**  The first ``first_k_dense_replace`` layers: ``dw2(silu(x dw1) * x
dw3)`` of width ``intermediate_size``.  After them (``moe_layer_freq`` 1):
``s = sigmoid(x wg)`` over ``n_routed_experts``; the ``num_experts_per_tok``
largest of ``s + wgb`` (the selection bias of ``topk_method`` "noaux_tc"; one
group, so no grouped top-k); their weights ``s`` at those, divided by their
sum (``norm_topk_prob``) and times ``routed_scaling_factor``; an expert is
``w2(silu(x w1) * x w3)`` of width ``moe_intermediate_size``; plus the
``n_shared_experts`` shared experts as the family builds them, one gated MLP
``sw2(silu(x sw1) * x sw3)`` of ``n_shared_experts x moe_intermediate_size``
on every token, unweighted.

The tree has the program's leaf names, because the reference is handed the
program's own parameters; each kind's leaves are stacked over the layers of
that kind, in order.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

# The declared scales of the seeded weights that the chip check rests on
# (``assumed`` in the configuration file; PERF.md has the chip's readings).
# Every leaf is centred on zero or is one (``lib/program.init_params``).  The
# stream starts at unit size (EMB) and every branch adds about half of that
# (the *_OUT factors on unit-gain products).  The queries are drawn at
# Q_SCALE times unit gain so that scores have a deviation of about 2 and a
# query weighs a few dozen of its 16,384 positions: with unit-gain scores
# attention is a mean over positions and nothing of the cache shows (PR 35's
# lesson).  With tokens drawn alike at every position the rotation is the
# only thing that tells one position's key from another's with the same
# token, and a third of a score's variance is the rotated part's (64 of 192).
# The router: sigmoid scores of logits of deviation ROUTER_SPREAD; the
# selection bias at deviation BIAS moves about one of a token's six picks
# (tests/benchmarks/test_kimi_vl.py measures it with numpy).
EMB = 1.0
Q_SCALE = 2.0
MLA_OUT = 0.5
DENSE_OUT = 0.5
EXPERT_OUT = 0.5
SHARED_OUT = 0.5
ROUTER_SPREAD = 1.0
BIAS = 0.02

QUERY_BLOCK = 256       # queries that hold their scores at a time
ROW_BLOCK = 4096        # rows the dense MLP holds its width for at a time
HEAD_BLOCK = 32_768     # rows of the head upcast at a time


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
    theta: float
    d_ff: int               # the dense MLP's width
    d_expert: int
    n_experts: int
    top_k: int
    n_shared: int
    scale: float            # routed_scaling_factor
    renorm: bool

    @classmethod
    def from_config(cls, config: dict) -> "Shape":
        """From a configuration file's published keys."""
        c = config
        if c["q_lora_rank"] is not None or c["rope_scaling"] is not None:
            raise ValueError("written for q_lora_rank null and rope_scaling "
                             "null alone")
        if (c["scoring_func"] != "sigmoid" or c["topk_method"] != "noaux_tc"
                or c["n_group"] != 1 or c["topk_group"] != 1
                or c["moe_layer_freq"] != 1):
            raise ValueError("written for a sigmoid router with a selection "
                             "bias over one group, every layer after the "
                             "dense ones routed")
        return cls(vocab=c["vocab_size"], d_model=c["hidden_size"],
                   n_layers=c["num_hidden_layers"], eps=c["rms_norm_eps"],
                   n_dense=min(c["first_k_dense_replace"],
                               c["num_hidden_layers"]),
                   heads=c["num_attention_heads"],
                   nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
                   v_dim=c["v_head_dim"], kv_rank=c["kv_lora_rank"],
                   theta=float(c["rope_theta"]),
                   d_ff=c["intermediate_size"],
                   d_expert=c["moe_intermediate_size"],
                   n_experts=c["n_routed_experts"],
                   top_k=c["num_experts_per_tok"],
                   n_shared=c["n_shared_experts"],
                   scale=float(c["routed_scaling_factor"]),
                   renorm=bool(c["norm_topk_prob"]))

    @property
    def n_routed(self) -> int:
        """Layers whose MLP is routed."""
        return self.n_layers - self.n_dense


MLA_LEAVES = ("mla_q", "mla_kva", "mla_n", "mla_kvb", "wo")
DENSE_LEAVES = ("dw1", "dw3", "dw2")
ROUTER_LEAVES = ("wg", "wgb", "sw1", "sw3", "sw2")
EXPERT_LEAVES = ("w1", "w3", "w2")


def param_init(shape: Shape, serving: bool = False
               ) -> dict[str, tuple[tuple[int, ...], float | None]]:
    """Leaf name -> (shape, standard deviation of its normal initializer);
    ``None`` marks a leaf that starts at one (the norms' scales).  The latent
    leaves are stacked over all layers, the dense MLP's over the leading
    layers, the router's, the shared experts' and the experts' over the
    routed ones.  One draw, the constants above: no cell trains this
    configuration, so ``serving`` changes nothing."""
    s = shape
    L, D, V = s.n_layers, s.d_model, s.vocab
    Ld, Lr = s.n_dense, s.n_routed
    q, kv = s.heads * (s.nope + s.rope), s.heads * (s.nope + s.v_dim)
    out = s.heads * s.v_dim
    F, Fe, Fs, E = s.d_ff, s.d_expert, s.d_expert * s.n_shared, s.n_experts
    table = {
        "emb": ((V, D), EMB),
        "head": ((V, D), D ** -0.5),
        "ln1": ((L, D), None),
        "ln2": ((L, D), None),
        "lnf": ((D,), None),
        "mla_q": ((L, D, q), Q_SCALE * D ** -0.5),
        "mla_kva": ((L, D, s.kv_rank + s.rope), D ** -0.5),
        "mla_n": ((L, s.kv_rank), None),
        "mla_kvb": ((L, s.kv_rank, kv), s.kv_rank ** -0.5),
        "wo": ((L, out, D), MLA_OUT * out ** -0.5),
    }
    if Ld:
        table.update({
            "dw1": ((Ld, D, F), D ** -0.5),
            "dw3": ((Ld, D, F), D ** -0.5),
            "dw2": ((Ld, F, D), DENSE_OUT * F ** -0.5),
        })
    if Lr:
        table.update({
            "wg": ((Lr, D, E), ROUTER_SPREAD * D ** -0.5),
            "wgb": ((Lr, E), BIAS),
            "w1": ((Lr, E, D, Fe), D ** -0.5),
            "w3": ((Lr, E, D, Fe), D ** -0.5),
            "w2": ((Lr, E, Fe, D), EXPERT_OUT * Fe ** -0.5),
            "sw1": ((Lr, D, Fs), D ** -0.5),
            "sw3": ((Lr, D, Fs), D ** -0.5),
            "sw2": ((Lr, Fs, D), SHARED_OUT * Fs ** -0.5),
        })
    return table


def counts(shape: Shape) -> dict:
    """What ``lib/costs.py`` counts of this family.

    ``active_params``: what one token multiplies: every layer's four latent
    matrices; the dense MLP's three; a routed layer's router, its ``top_k``
    experts and its shared experts; and the head.  The embedding is a lookup
    table (``lookup_params``).  ``attention_layers``: every layer attends;
    ``attention_width``: scores over ``nope + rope`` and a context over
    ``v_dim`` a head, so ``heads x (nope + rope + v_dim) / 2`` makes
    ``lib/costs.prefill_flops``'s ``4 x layers x width x T`` their count.
    ``kv_elements``: the latent and the rotated shared key part of one
    position, for all heads.  ``routed``: the routed layers' own shape."""
    s = shape
    D, V = s.d_model, s.vocab
    mla = (D * s.heads * (s.nope + s.rope) + D * (s.kv_rank + s.rope)
           + s.kv_rank * s.heads * (s.nope + s.v_dim)
           + s.heads * s.v_dim * D)
    expert = 3 * D * s.d_expert
    moe = D * s.n_experts + (s.top_k + s.n_shared) * expert
    block = (s.n_layers * mla + s.n_dense * 3 * D * s.d_ff
             + s.n_routed * moe)
    out = {"active_params": block + V * D,
           "projection_params": V * D,
           "lookup_params": V * D,
           "kv_elements": s.kv_rank + s.rope,
           "attention_layers": s.n_layers,
           "attention_width": s.heads * (s.nope + s.rope + s.v_dim) // 2}
    if s.n_routed:
        out["routed"] = {"layers": s.n_routed, "experts": s.n_experts,
                         "top_k": s.top_k, "d_model": D,
                         "d_expert": s.d_expert}
    return out


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rotary(x, theta: float):
    """x (B, T, ..., P) at positions 0 .. T - 1, as the family's published
    code turns it: pairs (2i, 2i + 1) moved to places (i, i + P/2), then
    ``x cos + rotate_half(x) sin`` with ``rotate_half(x) = [-x2, x1]`` and
    the angles ``position x theta^(-2i / P)`` repeated over both halves."""
    T, P = x.shape[1], x.shape[-1]
    x = jnp.swapaxes(x.reshape(*x.shape[:-1], P // 2, 2), -1, -2
                     ).reshape(x.shape)
    inv = 1.0 / theta ** (jnp.arange(0, P, 2, dtype=jnp.float32) / P)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv          # (T, P/2)
    ang = jnp.concatenate([ang, ang], axis=-1).reshape(
        T, *(1,) * (x.ndim - 3), P)
    half = jnp.concatenate([-x[..., P // 2:], x[..., :P // 2]], axis=-1)
    return x * jnp.cos(ang) + half * jnp.sin(ang)


def _mla(shape: Shape, p: dict, x):
    s = shape
    B, T, _ = x.shape
    H, N, P, W, R = s.heads, s.nope, s.rope, s.v_dim, s.kv_rank
    q = (x @ p["mla_q"]).reshape(B, T, H, N + P)
    kva = x @ p["mla_kva"]
    c = _rmsnorm(kva[..., :R], p["mla_n"], s.eps)
    kv = (c @ p["mla_kvb"]).reshape(B, T, H, N + W)
    q = jnp.concatenate([q[..., :N], rotary(q[..., N:], s.theta)], axis=-1)
    k_r = rotary(kva[..., R:], s.theta)
    k = jnp.concatenate([kv[..., :N], jnp.broadcast_to(
        k_r[:, :, None, :], (B, T, H, P))], axis=-1)
    v = kv[..., N:]
    block = min(T, QUERY_BLOCK)
    n = -(-T // block)
    qs = jnp.pad(q, [(0, 0), (0, n * block - T), (0, 0), (0, 0)])
    qs = jnp.moveaxis(qs.reshape(B, n, block, H, N + P), 1, 0)

    def one(of):
        first, q_b = of
        t = first + jnp.arange(block)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q_b, k) * (N + P) ** -0.5
        w = jax.nn.softmax(
            jnp.where(jnp.arange(T) <= t[:, None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v)

    # a padded query (past the last position) sees every key: dropped below
    o = jax.lax.map(one, (jnp.arange(n) * block, qs))
    o = jnp.moveaxis(o, 0, 1).reshape(B, n * block, H * W)[:, :T]
    return o @ p["wo"]


def route(shape: Shape, p: dict, x):
    """(B, T, n_experts) weights: zero but at a token's ``top_k`` picks."""
    s = shape
    score = jax.nn.sigmoid(x @ p["wg"])
    _best, at = jax.lax.top_k(score + p["wgb"], s.top_k)
    picked = jax.nn.one_hot(at, s.n_experts, dtype=score.dtype).sum(axis=-2)
    weight = score * picked
    if s.renorm:
        weight = weight / weight.sum(axis=-1, keepdims=True)
    return weight * s.scale


def _gated(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


@functools.partial(jax.jit, static_argnums=(0, 2))
def _mixer_layer(shape, stacks, layer, h):
    """The mixer half of block ``layer``; ``stacks`` the latent leaves as
    stored and ``ln1``."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v[layer], jnp.float32) for k, v in stacks.items()}
        return h + _mla(shape, p, _rmsnorm(h, p["ln1"], shape.eps))


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _moe_layer(shape, stacks, at, layer, h):
    """The routed half of block ``layer``; the experts read out of their
    stacks one at a time."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(stacks[k][at], jnp.float32)
             for k in ROUTER_LEAVES}
        x = _rmsnorm(h, jnp.asarray(stacks["ln2"][layer], jnp.float32),
                     shape.eps)
        weight = route(shape, p, x)

        def one(e, total):
            gate, up, down = (jnp.asarray(stacks[k][at, e], jnp.float32)
                              for k in EXPERT_LEAVES)
            w = jax.lax.dynamic_index_in_dim(weight, e, axis=-1)
            return total + w * _gated(x, gate, up, down)

        out = jax.lax.fori_loop(0, shape.n_experts, one, jnp.zeros_like(x))
        if shape.n_shared:
            out = out + _gated(x, p["sw1"], p["sw3"], p["sw2"])
        return h + out


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _dense_layer(shape, stacks, at, layer, h):
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(stacks[k][at], jnp.float32)
             for k in DENSE_LEAVES}
        B, T, D = h.shape
        x = _rmsnorm(h, jnp.asarray(stacks["ln2"][layer], jnp.float32),
                     shape.eps).reshape(B * T, D)
        block = min(B * T, ROW_BLOCK)
        n = -(-B * T // block)
        rows = jnp.pad(x, [(0, n * block - B * T), (0, 0)]
                       ).reshape(n, block, D)
        out = jax.lax.map(
            lambda r: _gated(r, p["dw1"], p["dw3"], p["dw2"]), rows)
        return h + out.reshape(n * block, D)[:B * T].reshape(B, T, D)


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


def forward(shape: Shape, params: dict, tokens):
    """(B, T) int32 tokens -> the last norm's output (B, T, D) float32."""
    s = shape
    h = jnp.asarray(params["emb"][tokens], jnp.float32)
    for layer in range(s.n_layers):
        h = _mixer_layer(s, {k: params[k] for k in (*MLA_LEAVES, "ln1")},
                         layer, h)
        if layer < s.n_dense:
            h = _dense_layer(s, {k: params[k] for k in (*DENSE_LEAVES, "ln2")},
                             layer, layer, h)
        else:
            h = _moe_layer(s, {k: params[k] for k in (
                *ROUTER_LEAVES, *EXPERT_LEAVES, "ln2")},
                layer - s.n_dense, layer, h)
    return _rmsnorm(h, jnp.asarray(params["lnf"], jnp.float32), s.eps)


def logits(shape: Shape, params: dict, tokens) -> PositionLogits:
    """(B, T) int32 tokens -> (B, T, V) float32 logits, projected where they
    are read."""
    return PositionLogits(shape, params["head"],
                          forward(shape, params, tokens))


def nll_sum(shape: Shape, params: dict, tokens):
    """Summed next-token negative log-likelihood over (B, T) tokens: position
    t predicts token t + 1, and the last position predicts nothing."""
    logp = jax.nn.log_softmax(logits(shape, params, tokens)[:, :-1], -1)
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
