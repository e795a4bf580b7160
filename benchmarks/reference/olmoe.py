"""Plain OLMoE decoder (``model_type`` ``olmoe``): the equations the
published configuration and the OLMoE paper (arXiv:2409.02060) give, in
float32 ``jax.numpy``, with nothing of the program in it.

No shard_map, no cache, no kernels, no chunking of the loss, and for the
experts no sort and no grouped matmul: a loop over the experts, each run on
every token and weighted by that token's routing probability, which is zero
where the expert is not among the token's best.  Matrix multiplications at
``jax.default_matmul_precision("highest")``, because a TPU runs a float32
product in bfloat16 passes unless told otherwise.  A layer at a time: the
parameters arrive as the program stores them (bfloat16 on the chip) and a
whole tree in float32 does not fit beside them, so each layer's slice is
upcast inside that layer's call and dropped after it.

The block: pre-norm sequential residual; RMSNorm without bias, eps from
``rms_norm_eps``; q, k and v without bias; q and k each RMS-normed over the
whole projected width (all heads together) before they are split into
heads; rotary embedding over the whole head in the split-half convention
(``rope_theta`` 10000); causal softmax attention scaled by head_dim**-0.5;
the router's logits over all experts in float32, softmax over all of them,
the ``num_experts_per_tok`` largest kept with their probabilities as they
are (``norm_topk_prob`` false: not renormalised), no token dropped; an
expert is ``down(silu(gate(x)) * up(x))``; no shared expert; final RMSNorm;
an output head that is not the embedding (``tie_word_embeddings`` false).

Departure from the published training recipe (listed in the configuration
file): ``loss`` is the cross entropy alone, without the load-balancing term
and the router z-loss.

Sizes are read from the published keys of the configuration (the Hugging
Face names); the parameter tree has the program's leaf names, because the
reference is handed the program's own parameters: ``w1`` is an expert's
gate projection, ``w3`` its up projection, ``w2`` its down projection,
``wg`` the router, ``qn`` and ``kn`` the scales of the q- and k-norm.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

# The declared scales of the seeded weights that the chip check rests on
# (``assumed`` in the configuration file; PERF.md section 2 has the chip's
# readings).  The router's input is RMS-normed, so a column of ``wg`` drawn
# at ROUTER_SPREAD / sqrt(d_model) gives logits of about that deviation: the
# program's own initializer's (0.02 at d_model 2048); it sets how the tokens
# spread over the experts, and with that the cell's rate, and is left as it
# was drawn.  The other four make greedy decoding of random prompts generic.
# With every matrix at the program's own scale, q and k normed to one and
# the norms' scales at one, attention is a mean over a thousand positions:
# it passes what all positions share at gain one and what differs at a
# twentieth, so through seven layers the stream becomes one constant vector
# (0.5596 of 0.5606 at the last layer, CPU box, float32) and the decoder
# repeats one token whatever the experts add: no fault of theirs shows.
# Q_SCALE draws the q-norm's scale at that deviation (the k-norm's at one),
# so scores have deviation 2 and a query weighs a few dozen positions, not
# all.  EMB, ATTN_OUT and EXPERT_OUT (against the program's own initializer)
# set the shares of the last layer's stream (CPU box, float32, seed 3): the
# embedding 97.1%, an attending layer 0.28%, a routed layer 0.13%.  A routed
# layer cannot be more: where a token's 8th and 9th expert change places on
# bfloat16 noise (one token in thirty a layer) its logits move as far as
# that layer's share lets them, and the worst token of a sound run has to
# stay inside the configuration's ``check.deficit_max``; at these shares a
# zeroed attending layer moves every logit by 0.05 deviations, all experts
# off by 0.1, and the sound program by 0.005.
ROUTER_SPREAD = 0.9
EXPERT_OUT = 0.15
EMB = 0.1
ATTN_OUT = 0.1
Q_SCALE = 2.0


@dataclasses.dataclass(frozen=True)
class Shape:
    vocab: int
    d_model: int
    n_heads: int
    n_layers: int
    d_ff: int           # the width of one expert
    n_experts: int
    top_k: int
    eps: float

    @classmethod
    def from_config(cls, config: dict) -> "Shape":
        """From a configuration file's published keys."""
        return cls(vocab=config["vocab_size"], d_model=config["hidden_size"],
                   n_heads=config["num_attention_heads"],
                   n_layers=config["num_hidden_layers"],
                   d_ff=config["intermediate_size"],
                   n_experts=config["num_experts"],
                   top_k=config["num_experts_per_tok"],
                   eps=config["rms_norm_eps"])


def param_init(shape: Shape, serving: bool = False
               ) -> dict[str, tuple[tuple[int, ...], float | None]]:
    """Leaf name -> (shape, standard deviation of its normal initializer);
    ``None`` marks a norm scale, which starts at one.  Layers are stacked on
    the leading axis, experts on the one after it.  Scaled as the program's
    own initializer scales its matrices, but for the constants above.  One
    draw: no cell trains this configuration, so ``serving`` changes nothing."""
    L, D, F, V = shape.n_layers, shape.d_model, shape.d_ff, shape.vocab
    E = shape.n_experts
    depth = math.sqrt(max(1, 2 * L))
    return {
        "emb": ((V, D), EMB),
        "head": ((V, D), 0.02),
        "wq": ((L, D, D), D ** -0.5),
        "wk": ((L, D, D), D ** -0.5),
        "wv": ((L, D, D), D ** -0.5),
        "wo": ((L, D, D), ATTN_OUT * D ** -0.5 / depth),
        "qn": ((L, D), Q_SCALE),
        "kn": ((L, D), 1.0),
        "wg": ((L, D, E), ROUTER_SPREAD * D ** -0.5),
        "w1": ((L, E, D, F), D ** -0.5),
        "w3": ((L, E, D, F), D ** -0.5),
        "w2": ((L, E, F, D), EXPERT_OUT * F ** -0.5 / depth),
        "ln1": ((L, D), None),
        "ln2": ((L, D), None),
        "lnf": ((D,), None),
    }


def counts(shape: Shape) -> dict:
    """What ``lib/costs.py`` counts of this family.  ``active_params``: the
    parameters one token multiplies: in every layer the four attention
    projections, the router, and ``top_k`` of the experts' three matrices;
    and the output head.  The embedding is a lookup table and the norms'
    scales multiply no matrix: they count nothing; ``lookup_params`` says
    how large that table is, for the bytes of a cached step, which looks up
    a row a sequence.  ``kv_elements``: one position's keys and values in
    one layer (as many K/V heads as query heads).  ``routed``: every layer
    is routed, over ``n_experts`` gated experts of width ``d_ff``, ``top_k``
    a token."""
    L, D, F, V = shape.n_layers, shape.d_model, shape.d_ff, shape.vocab
    block = 4 * D * D + D * shape.n_experts + shape.top_k * 3 * D * F
    return {"active_params": L * block + V * D,
            "projection_params": V * D,
            "lookup_params": V * D,
            "kv_elements": 2 * D,
            "routed": {"layers": L, "experts": shape.n_experts,
                       "top_k": shape.top_k, "d_model": D, "d_expert": F}}


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x):
    """x: (B, T, H, hd).  Rotates the pair (i, i + hd/2) of every head by
    position · 10000**(-i / (hd/2))."""
    half = x.shape[-1] // 2
    freqs = 10_000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _experts(shape: Shape, p: dict, x):
    """x: (B, T, D) -> the routed experts' weighted sum, (B, T, D)."""
    probs = jax.nn.softmax(x @ p["wg"], axis=-1)            # (B, T, E)
    kth = jnp.sort(probs, axis=-1)[..., -shape.top_k, None]
    weight = jnp.where(probs >= kth, probs, 0.0)            # not renormalised

    def one(total, expert):
        gate, up, down, w = expert
        y = (jax.nn.silu(x @ gate) * (x @ up)) @ down
        return total + w[..., None] * y, None

    total, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (p["w1"], p["w3"], p["w2"], jnp.moveaxis(weight, -1, 0)))
    return total


@functools.partial(jax.jit, static_argnums=0)
def _layer(shape: Shape, layer_params: dict, h):
    """One block on (B, T, D) float32; ``layer_params`` as stored."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in layer_params.items()}
        B, T, D = h.shape
        n_heads, hd = shape.n_heads, D // shape.n_heads
        causal = jnp.tril(jnp.ones((T, T), bool))
        x = _rmsnorm(h, p["ln1"], shape.eps)
        q = _rmsnorm(x @ p["wq"], p["qn"], shape.eps)
        k = _rmsnorm(x @ p["wk"], p["kn"], shape.eps)
        q = _rope(q.reshape(B, T, n_heads, hd))
        k = _rope(k.reshape(B, T, n_heads, hd))
        v = (x @ p["wv"]).reshape(B, T, n_heads, hd)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, T, D)
        h = h + o @ p["wo"]
        return h + _experts(shape, p, _rmsnorm(h, p["ln2"], shape.eps))


@functools.partial(jax.jit, static_argnums=0)
def _unembed(shape: Shape, lnf, head, h):
    with jax.default_matmul_precision("highest"):
        return (_rmsnorm(h, jnp.asarray(lnf, jnp.float32), shape.eps)
                @ jnp.asarray(head, jnp.float32).T)


LAYER_LEAVES = ("wq", "wk", "wv", "wo", "qn", "kn", "wg", "w1", "w3", "w2",
                "ln1", "ln2")


def logits(shape: Shape, params: dict, tokens):
    """(B, T) int32 tokens -> (B, T, V) float32 logits."""
    h = jnp.asarray(params["emb"][tokens], jnp.float32)
    for l in range(params["wq"].shape[0]):
        h = _layer(shape, {k: params[k][l] for k in LAYER_LEAVES}, h)
    return _unembed(shape, params["lnf"], params["head"], h)


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
        z, sequences[:, prompt_len:, None], axis=-1)[..., 0]
    return (z.max(axis=-1) - chosen) / z.std(axis=-1)
