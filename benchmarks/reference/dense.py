"""Plain dense decoder-only transformer: the equations the configuration
files declare, in float32 ``jax.numpy``, with nothing of the program in it.

No scan, no shard_map, no cache, no kernels, no chunking of the loss; matrix
multiplications at ``jax.default_matmul_precision("highest")``, because a
TPU runs a float32 product in bfloat16 passes unless told otherwise.

The block (each departure from GPT-NeoX is listed in the configuration
file): sequential pre-norm residual, RMSNorm without bias (eps 1e-6), rotary
embedding over the whole head in the split-half convention (base 10000),
causal softmax attention scaled by head_dim**-0.5, a two-matrix MLP with
tanh-GELU, no linear biases, output head tied to the input embedding.

Sizes are read from the published keys of the configuration (the Hugging
Face names); the parameter tree has the program's leaf names, because the
reference is handed the program's own parameters.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Shape:
    vocab: int
    d_model: int
    n_heads: int
    n_layers: int
    d_ff: int

    @classmethod
    def from_config(cls, config: dict) -> "Shape":
        """From a configuration file's published keys."""
        return cls(vocab=config["vocab_size"], d_model=config["hidden_size"],
                   n_heads=config["num_attention_heads"],
                   n_layers=config["num_hidden_layers"],
                   d_ff=config["intermediate_size"])


# The draw for serving (``param_init(shape, serving=True)``: a decode runner
# asks for it, the train runner does not, so the train cells' parameters and
# losses are what they were), as gains on fan-in ** -0.5; PERF.md section 2
# has the chip's readings.  At the trainer's draw greedy decoding of random
# prompts repeats one token (``repeat_share`` 0.99 to 1.0 in 10 seeds on the
# chip), for two reasons.  The head is the embedding, so a token's own
# embedding votes for it by sqrt(d_model) * |emb| / |stream| deviations: 2
# where the blocks' outputs are divided by sqrt(2 * layers).  And tanh-GELU
# of a unit-deviation input has a mean of 0.28, so every FFN adds one fixed
# vector at every position, and attention whose scores have deviation 1 is a
# mean over a thousand positions: it passes what all positions share at gain
# one and what differs at a twentieth, until the stream is that vector.
# Hence: ``w1`` at a tenth (the FFN's fixed vector is then 0.6% of its
# output's power, not 18%) and ``w2`` at 27, so the stream is 170 times the
# embedding and a token's vote for itself 0.27 deviations; ``wq`` at 1.5,
# so scores have deviation 1.5 and a query weighs 150 positions of 1024 (at
# 1.0 the constant still takes over in some seeds, ``repeat_share`` up to
# 0.38; at 3 the sound program's worst deficit reads 0.05 to 0.11 and at 4
# 0.14 to 0.23, where 1.5 reads up to 0.056, because a sharper softmax
# multiplies the noise of its scores); ``wo`` at 0.7.  Of the last layer's
# stream an FFN layer is then 16% and an attending layer 0.5% (CPU box,
# float32, seed 3): a zeroed attending layer moves every logit by 0.07
# deviations and the sound program by 0.006.
SERVING = {"wq": 1.5, "w1": 0.1, "wo": 0.7, "w2": 27.0}


def param_init(shape: Shape, serving: bool = False
               ) -> dict[str, tuple[tuple[int, ...], float | None]]:
    """Leaf name -> (shape, standard deviation of its normal initializer);
    ``None`` marks a norm scale, which starts at one.  Layers are stacked on
    the leading axis.  Scaled as the program's own initializer scales them,
    so that a loss at the initial parameters is near ln(vocab); ``serving``
    gives the draw for greedy decoding, above."""
    L, D, F, V = shape.n_layers, shape.d_model, shape.d_ff, shape.vocab
    depth = math.sqrt(max(1, 2 * L))
    gain = {"wq": 1.0, "w1": 1.0, "wo": 1 / depth, "w2": 1 / depth}
    if serving:
        gain = SERVING
    return {
        "emb": ((V, D), 0.02),
        "wq": ((L, D, D), D ** -0.5 * gain["wq"]),
        "wk": ((L, D, D), D ** -0.5),
        "wv": ((L, D, D), D ** -0.5),
        "wo": ((L, D, D), D ** -0.5 * gain["wo"]),
        "w1": ((L, D, F), D ** -0.5 * gain["w1"]),
        "w2": ((L, F, D), F ** -0.5 * gain["w2"]),
        "ln1": ((L, D), None),
        "ln2": ((L, D), None),
        "lnf": ((D,), None),
    }


def param_shapes(shape: Shape) -> dict[str, tuple[int, ...]]:
    return {name: dims for name, (dims, _std) in param_init(shape).items()}


def counts(shape: Shape) -> dict[str, int]:
    """What ``lib/costs.py`` counts of this family.  ``active_params``: the
    parameters one token multiplies, which are the blocks' and the output
    projection (a lookup table that is not also the projection would count
    nothing); here every parameter, the tied embedding once, as the
    projection.  ``projection_params``: those of the output projection
    alone.  ``kv_elements``: the elements of one position's keys and values
    in one layer."""
    every = sum(math.prod(dims) for dims in param_shapes(shape).values())
    return {"active_params": every,
            "projection_params": shape.vocab * shape.d_model,
            "kv_elements": 2 * shape.d_model}


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + 1e-6) * scale


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


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def logits(shape: Shape, params: dict, tokens):
    """(B, T) int32 tokens -> (B, T, V) float32 logits."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        B, T = tokens.shape
        D = p["emb"].shape[1]
        n_heads, hd = shape.n_heads, D // shape.n_heads
        causal = jnp.tril(jnp.ones((T, T), bool))
        h = p["emb"][tokens]
        for l in range(p["wq"].shape[0]):
            x = _rmsnorm(h, p["ln1"][l])
            q = _rope((x @ p["wq"][l]).reshape(B, T, n_heads, hd))
            k = _rope((x @ p["wk"][l]).reshape(B, T, n_heads, hd))
            v = (x @ p["wv"][l]).reshape(B, T, n_heads, hd)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
            w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, T, D)
            h = h + o @ p["wo"][l]
            x = _rmsnorm(h, p["ln2"][l])
            h = h + _gelu_tanh(x @ p["w1"][l]) @ p["w2"][l]
        return _rmsnorm(h, p["lnf"]) @ p["emb"].T


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
    run = jax.jit(nll_sum, static_argnums=0)
    B, T = tokens.shape
    total = 0.0
    for lo in range(0, B, block):
        total += float(run(shape, params, tokens[lo:lo + block]))
    return total / (B * (T - 1))


def token_deficits(shape: Shape, params: dict, sequences, prompt_len: int):
    """For greedy continuations: how far below the reference's best logit
    the chosen token's reference logit lies, in units of the standard
    deviation of that position's logits.

    ``sequences``: (B, T) prompt plus generated tokens.  Position t's logits
    score token t + 1, so generated token t (t >= prompt_len) is scored at
    t - 1.  Returns a (B, T - prompt_len) float32 array, 0 where the decoder
    chose the reference's own argmax.  Logits are compared through the
    tokens because the decoder returns tokens only, and in standard
    deviations because a random token sits about four of them below the
    maximum of a vocabulary this size.
    """
    z = jax.jit(logits, static_argnums=0)(shape, params, sequences)
    z = z[:, prompt_len - 1:-1]
    chosen = jnp.take_along_axis(
        z, sequences[:, prompt_len:, None], axis=-1)[..., 0]
    return (z.max(axis=-1) - chosen) / z.std(axis=-1)
