"""Plain decoder of Keye-VL-2.0-30B-A3B's language model (``model_type``
``KeyeVL2``): grouped-query attention over the positions a learned index
selects, per-head q/k norm, and a renormalised top-k of gated experts in
every layer, in float32 ``jax.numpy`` with nothing of the program in it.

No shard_map, no cache, no kernel, no threshold: a query's positions are
``lax.top_k`` of its index scores, made into a mask, and attention
materialises its scores.  Matrix multiplications at
``jax.default_matmul_precision("highest")``, because a TPU runs a float32
product in bfloat16 passes unless told otherwise.  A layer at a time: the
parameters arrive as the program stores them (bfloat16 on the chip) and a
whole tree in float32 does not fit beside them, so each layer's slice is
upcast inside that layer's call and dropped after it.  The index and
attention run a block of ``QUERY_BLOCK`` queries at a time against every
key, so that two sequences of 8192 positions fit beside the program's
parameters (a block's attention scores are 1.07 GB, a sequence's whole
would be 8.6), and the logits are multiplied out only for the positions a
caller reads (:class:`PositionLogits`: 2 x 8192 x 151,936 float32 would be
9.96 GB).

The layer, from the published keys (what no key says is listed under
``assumed`` in the configuration file).  All ``num_hidden_layers`` alike
(``mlp_only_layers`` empty, ``decoder_sparse_step`` 1, no sliding window).
With ``x = RMSNorm(h; ln1)``:

- ``q = x Wq`` (``num_attention_heads`` x ``head_dim``), ``k = x Wk``, ``v =
  x Wv`` (``num_key_value_heads`` x ``head_dim``), no bias; q and k
  RMS-normed over each head's ``head_dim`` with one scale of that width for
  q and one for k; rotary embedding over the whole head, split-half, theta
  ``rope_theta`` (``mrope_section``: a text token carries one position in
  all three sections, so on text the rotation is the one-dimensional one).
- the index (``sa_config``): ``qI = x Wiq`` (``indexer_num_heads`` x
  ``indexer_head_dim``); ``kI = LayerNorm(x Wik)`` (one key, scale and
  bias, eps ``rms_norm_eps``); ``wI = (x Wiw) * heads^-1/2 * width^-1/2``;
  the rotary embedding on qI and kI over the whole index head.  ``I[t, s] =
  sum_j wI[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``; ``S_t`` is every
  ``s <= t`` while ``t < topk``, else the ``topk`` positions of the largest
  ``I[t, .]``, ties to the lower position.
- ``o_t = softmax_{s in S_t}(q_t . k_s / sqrt(head_dim)) v_s``, K/V head g
  serving the query heads ``g r .. g r + r - 1``; ``h <- h + o Wo``.
- ``y = RMSNorm(h; ln2)``; ``p = softmax(y Wg)`` over all experts; the
  ``num_experts_per_tok`` largest kept, their weights divided by their sum
  (``norm_topk_prob`` true); an expert is ``W2(silu(W1 y) * W3 y)`` of width
  ``moe_intermediate_size``; no shared expert; ``h <- h + sum``.

``logits = RMSNorm(h; lnf) W_head^T``; the head is not the embedding.

The tree has the program's leaf names, because the reference is handed the
program's own parameters: ``w1`` an expert's gate projection, ``w3`` its up
projection, ``w2`` its down projection, ``wg`` the router, ``qn`` and ``kn``
the q- and k-norm's scales, ``wiq``, ``wik``, ``wiw`` the index's
projections, ``ikn`` and ``ikb`` its key norm's scale and bias.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

# The declared scales of the seeded weights that the chip check rests on
# (``assumed`` in the configuration file; PERF.md section 2 has the chip's
# readings).  q and k are normed to one a head, so the matrices' scales do
# not reach the scores: the q-norm's scale is drawn at Q_SCALE (the k-norm's
# is one), which gives scores of deviation 2, so a query weighs a few dozen
# of its 2048 positions and not all alike.  With unit-gain weights attention
# would be a mean over positions, which no selection changes (PR 35's
# lesson); at these, dropping the selection, halving it or shifting it by a
# position replaces most of what attention adds.  The index's queries are of
# unit deviation and its key is LayerNormed, so a pair's product has
# deviation 8 and an index score 0.7: a row of 8064 scores is 2.7e-4 apart
# at its 2048th, which bfloat16 products blur over about ten positions.
# The stream is the embedding at deviation one; an attending layer adds
# about 0.04 of it (a context of deviation 0.16, a few dozen values' mean,
# times ATTN_OUT) and a routed layer about 0.15 (EXPERT_OUT).
# The router's logits have deviation ROUTER_SPREAD: at 2 the eighth of a
# token's renormalised weights is 0.05, so where its eighth and ninth expert
# change places on bfloat16 noise (a token in forty a layer) the layer's
# output moves by a seventh, the stream by 0.02.  IKB: ``lib/program.
# init_params`` draws a leaf as normal x deviation or as ones, so the key
# norm's bias is drawn and not zero.
EMB = 1.0
Q_SCALE = 2.0
ATTN_OUT = 0.25
EXPERT_OUT = 0.65
ROUTER_SPREAD = 2.0
IKB = 0.1

QUERY_BLOCK = 512       # queries the index and attention hold at a time
HEAD_BLOCK = 32_768     # rows of the head upcast at a time: 0.27 GB


@dataclasses.dataclass(frozen=True)
class Shape:
    vocab: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    n_layers: int
    d_ff: int               # the width of one expert
    n_experts: int
    top_k: int
    eps: float
    rope_theta: float
    index_heads: int
    index_dim: int
    index_topk: int

    @classmethod
    def from_config(cls, config: dict) -> "Shape":
        """From a configuration file's published keys."""
        c, sa = config, config["sa_config"]
        return cls(vocab=c["vocab_size"], d_model=c["hidden_size"],
                   n_heads=c["num_attention_heads"],
                   n_kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], n_layers=c["num_hidden_layers"],
                   d_ff=c["moe_intermediate_size"],
                   n_experts=c["num_experts"],
                   top_k=c["num_experts_per_tok"], eps=c["rms_norm_eps"],
                   rope_theta=float(c["rope_theta"]),
                   index_heads=sa["indexer_num_heads"],
                   index_dim=sa["indexer_head_dim"], index_topk=sa["topk"])


def param_init(shape: Shape, serving: bool = False
               ) -> dict[str, tuple[tuple[int, ...], float | None]]:
    """Leaf name -> (shape, standard deviation of its normal initializer);
    ``None`` marks a leaf that starts at one (the norms' scales but the
    q-norm's).  Layers are stacked on the leading axis, experts on the one
    after it.  One draw, the constants above: no cell trains this
    configuration, so ``serving`` changes nothing."""
    s = shape
    L, D, F, V, E = s.n_layers, s.d_model, s.d_ff, s.vocab, s.n_experts
    q, kv = s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim
    return {
        "emb": ((V, D), EMB),
        "head": ((V, D), D ** -0.5),
        "wq": ((L, D, q), D ** -0.5),
        "wk": ((L, D, kv), D ** -0.5),
        "wv": ((L, D, kv), D ** -0.5),
        "wo": ((L, q, D), ATTN_OUT * q ** -0.5),
        "qn": ((L, s.head_dim), Q_SCALE),
        "kn": ((L, s.head_dim), None),
        "wiq": ((L, D, s.index_heads * s.index_dim), D ** -0.5),
        "wik": ((L, D, s.index_dim), D ** -0.5),
        "wiw": ((L, D, s.index_heads), D ** -0.5),
        "ikn": ((L, s.index_dim), None),
        "ikb": ((L, s.index_dim), IKB),
        "wg": ((L, D, E), ROUTER_SPREAD * D ** -0.5),
        "w1": ((L, E, D, F), D ** -0.5),
        "w3": ((L, E, D, F), D ** -0.5),
        "w2": ((L, E, F, D), EXPERT_OUT * F ** -0.5),
        "ln1": ((L, D), None),
        "ln2": ((L, D), None),
        "lnf": ((D,), None),
    }


def counts(shape: Shape) -> dict:
    """What ``lib/costs.py`` counts of this family, so that no share of a
    peak can pass 100%: each figure is what the leanest exact program needs,
    not what a dense one touches.

    ``active_params``: what one token multiplies: in every layer the four
    attention projections, the index's three, the router and ``top_k`` of
    the experts' three matrices; and the head.  The embedding is a lookup
    table (``lookup_params``) and the norms multiply no matrix.
    ``kv_elements``: what a cached step must read of *every* live position
    in a layer, which is the index key alone (``index_dim``); the K and V a
    step reads are those of the ``index_topk`` selected positions, of fixed
    size once a sequence is past ``index_topk``: ``state_elements``, over
    all layers, ``layers x topk x 2 x kv_heads x head_dim``.
    ``attention_width``: ``lib/costs.prefill_flops`` counts ``4 x layers x
    width x T`` operations a position for attention.  A query's index scores
    against its ``t`` earlier positions are ``2 x index_heads x index_dim``
    operations a pair, ``index_heads x index_dim x T`` a position on the
    mean over a prompt of T (the causal half): a width of ``index_heads x
    index_dim / 4`` (256) counts them exactly.  Attention itself over the
    ``min(t, topk)`` selected keys is ``4 x heads x head_dim x min(t, topk)``
    a position more, which is no multiple of T and cannot be written as a
    width.  512 counts the index twice over, 1024 T a position more than it
    needs, and that stays under attention's own ``16384 x mean min(t,
    topk)`` (8192 T up to ``topk``, ``16384 topk (1 - topk / 2T)`` past it)
    for every prompt up to 15 x ``topk`` positions, 30,000 here: so at the
    cell's 8064 ``prefill_mfu`` counts no operation that an exact program
    can skip, and undercounts attention (29M of the 31M a position).
    ``routed``: every layer is routed."""
    s = shape
    L, D, F, V = s.n_layers, s.d_model, s.d_ff, s.vocab
    q, kv = s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim
    index = D * (s.index_heads * s.index_dim + s.index_dim + s.index_heads)
    block = (2 * D * q + 2 * D * kv + index + D * s.n_experts
             + s.top_k * 3 * D * F)
    return {"active_params": L * block + V * D,
            "projection_params": V * D,
            "lookup_params": V * D,
            "kv_elements": s.index_dim,
            "state_elements": L * s.index_topk * 2 * kv,
            "attention_layers": L,
            "attention_width": 2 * s.index_heads * s.index_dim // 4,
            "routed": {"layers": L, "experts": s.n_experts,
                       "top_k": s.top_k, "d_model": D, "d_expert": F}}


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, theta: float):
    """x: (B, T, H, hd).  Rotates the pair (i, i + hd/2) of every head by
    position * theta**(-i / (hd/2))."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def selected(shape: Shape, index_scores, first: int):
    """``S_t`` as a mask: ``index_scores`` (B, Q, T) of the queries at
    positions ``first .. first + Q - 1`` against every position -> (B, Q, T)
    bool, a real ``lax.top_k`` a query."""
    B, Q, T = index_scores.shape
    t = first + jnp.arange(Q)
    causal = jnp.arange(T)[None, :] <= t[:, None]
    if shape.index_topk >= T:
        return jnp.broadcast_to(causal, (B, Q, T))
    best, at = jax.lax.top_k(jnp.where(causal, index_scores, -jnp.inf),
                             shape.index_topk)
    return jnp.zeros((B, Q, T), bool).at[
        jnp.arange(B)[:, None, None], jnp.arange(Q)[None, :, None], at
    ].set(best > -jnp.inf)


def _attention(shape: Shape, p: dict, x):
    """The attending half's context ``o Wo`` of the normed input x (B, T, D),
    a block of queries at a time."""
    s = shape
    B, T, _ = x.shape
    H, K, hd = s.n_heads, s.n_kv_heads, s.head_dim
    q = _rmsnorm((x @ p["wq"]).reshape(B, T, H, hd), p["qn"], s.eps)
    k = _rmsnorm((x @ p["wk"]).reshape(B, T, K, hd), p["kn"], s.eps)
    q, k = _rope(q, s.rope_theta), _rope(k, s.rope_theta)
    v = (x @ p["wv"]).reshape(B, T, K, hd)
    k, v = (jnp.repeat(y, H // K, axis=2) for y in (k, v))

    qi = (x @ p["wiq"]).reshape(B, T, s.index_heads, s.index_dim)
    ki = x @ p["wik"]
    ki = ki - ki.mean(axis=-1, keepdims=True)
    ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, axis=-1, keepdims=True) + s.eps)
    ki = ki * p["ikn"] + p["ikb"]
    wi = (x @ p["wiw"]) * (s.index_heads ** -0.5 * s.index_dim ** -0.5)
    qi = _rope(qi, s.rope_theta)
    ki = _rope(ki[:, :, None, :], s.rope_theta)[:, :, 0]

    block = min(T, QUERY_BLOCK)
    n = -(-T // block)

    def blocks(y):      # (B, T, ...) -> (n, B, block, ...), the tail padded
        y = jnp.pad(y, [(0, 0), (0, n * block - T)] + [(0, 0)] * (y.ndim - 2))
        return jnp.moveaxis(y.reshape(B, n, block, *y.shape[2:]), 1, 0)

    def one(of):
        first, q_b, qi_b, wi_b = of
        found = jnp.einsum("bqh,bqhk->bqk", wi_b, jax.nn.relu(
            jnp.einsum("bqhd,bkd->bqhk", qi_b, ki)))
        mask = selected(s, found, first)[:, None]           # (B, 1, Q, T)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q_b, k) * hd ** -0.5
        w = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v)

    # a padded query (past the last position) sees every key: dropped below
    ctx = jax.lax.map(one, (jnp.arange(n) * block, blocks(q), blocks(qi),
                            blocks(wi)))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(B, n * block, H * hd)[:, :T]
    return ctx @ p["wo"]


def _experts(shape: Shape, p: dict, x):
    """x: (B, T, D) -> the routed experts' weighted sum, (B, T, D)."""
    probs = jax.nn.softmax(x @ p["wg"], axis=-1)            # (B, T, E)
    kth = jnp.sort(probs, axis=-1)[..., -shape.top_k, None]
    weight = jnp.where(probs >= kth, probs, 0.0)
    weight = weight / weight.sum(axis=-1, keepdims=True)    # norm_topk_prob

    def one(total, expert):
        gate, up, down, w = expert
        y = (jax.nn.silu(x @ gate) * (x @ up)) @ down
        return total + w[..., None] * y, None

    total, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (p["w1"], p["w3"], p["w2"], jnp.moveaxis(weight, -1, 0)))
    return total


@functools.partial(jax.jit, static_argnums=0)
def _layer(shape: Shape, stacks: dict, layer, h):
    """Block ``layer`` on (B, T, D) float32; ``stacks`` the layers' leaves as
    stored, stacked over layers.  The layer's leaves are read out of the
    stacks in here, so they and their float32 copies are temporaries of this
    program: cut out by the caller, a layer's 1.25 GB of stored leaves would
    be a live array, and the next layer's beside it while this one runs."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(jax.lax.dynamic_index_in_dim(
            v, layer, keepdims=False), jnp.float32)
            for k, v in stacks.items()}
        h = h + _attention(shape, p, _rmsnorm(h, p["ln1"], shape.eps))
        return h + _experts(shape, p, _rmsnorm(h, p["ln2"], shape.eps))


@functools.partial(jax.jit, static_argnums=0)
def _project(shape: Shape, rows, h):
    """``h`` already normed, onto a block of the head's rows."""
    with jax.default_matmul_precision("highest"):
        return h @ jnp.asarray(rows, jnp.float32).T


LAYER_LEAVES = ("wq", "wk", "wv", "wo", "qn", "kn", "wiq", "wik", "wiw",
                "ikn", "ikb", "wg", "w1", "w3", "w2", "ln1", "ln2")


class PositionLogits:
    """The (B, T, V) float32 logits of a forward pass, multiplied out for the
    positions that are read: ``self[:, a:b]`` projects those positions'
    hidden states onto the head and is a ``jax`` array; ``np.asarray(self)``
    and ``jnp.asarray(self)`` project every position.  At this vocabulary two
    sequences of 8192 positions are 9.96 GB whole, beside the program's
    parameters, and a decode check reads 128 positions of each."""

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


def logits(shape: Shape, params: dict, tokens) -> PositionLogits:
    """(B, T) int32 tokens -> (B, T, V) float32 logits, projected where they
    are read."""
    h = jnp.asarray(params["emb"][tokens], jnp.float32)
    stacks = {k: params[k] for k in LAYER_LEAVES}
    for l in range(params["wq"].shape[0]):
        h = _layer(shape, stacks, np.int32(l), h)
    h = _rmsnorm(h, jnp.asarray(params["lnf"], jnp.float32), shape.eps)
    return PositionLogits(shape, params["head"], h)


def nll_sum(shape: Shape, params: dict, tokens):
    """Summed next-token negative log-likelihood over (B, T) tokens: position
    t predicts token t + 1, and the last position predicts nothing."""
    logp = jax.nn.log_softmax(logits(shape, params, tokens)[:, :-1], -1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -picked.sum()


def loss(shape: Shape, params: dict, tokens, block: int = 1) -> float:
    """Mean next-token cross entropy of a (B, T) batch, worked through in
    blocks of ``block`` sequences so that one device holds the float32
    logits of a block and not of the batch."""
    B, T = tokens.shape
    total = 0.0
    for lo in range(0, B, block):
        total += float(nll_sum(shape, params, tokens[lo:lo + block]))
    return total / (B * (T - 1))


def token_deficits(shape: Shape, params: dict, sequences, prompt_len: int):
    """For greedy continuations: how far below the reference's best logit
    the chosen token's reference logit lies, in units of the standard
    deviation of that position's logits.

    ``sequences``: (B, T) prompt plus generated tokens.  Position t's logits
    score token t + 1, so generated token t (t >= prompt_len) is scored at
    t - 1.  Returns a (B, T - prompt_len) float32 array, 0 where the decoder
    chose the reference's own argmax.
    """
    z = logits(shape, params, sequences)[:, prompt_len - 1:-1]
    chosen = jnp.take_along_axis(
        z, jnp.asarray(sequences)[:, prompt_len:, None], axis=-1)[..., 0]
    return (z.max(axis=-1) - chosen) / z.std(axis=-1)
