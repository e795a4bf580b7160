"""Plain decoder of Brumby-14B-Base (``model_type`` ``brumby``): power
retention of degree 2 in every layer, per-head q/k norm, a SiLU-gated MLP, in
float32 ``jax.numpy`` with nothing of the program in it.

No shard_map, no cache, no state, no chunks, no feature map: a layer's
retention is the quadratic form below over every earlier position, a block of
``QUERY_BLOCK`` queries at a time against every key so that two sequences of
2176 positions fit beside the program's parameters (a block's weights are
0.36 GB, a sequence pair's whole would be 1.5).  Matrix multiplications at
``jax.default_matmul_precision("highest")``, because a TPU runs a float32
product in bfloat16 passes unless told otherwise.  A layer at a time: the
parameters arrive as the program stores them (bfloat16 on the chip) and a
whole tree in float32 does not fit beside them, so each layer's slice is
upcast inside that layer's call and dropped after it, and the logits are
multiplied out only for the positions a caller reads
(:class:`PositionLogits`: 2 x 2176 x 151,936 float32 would be 2.6 GB).

The layer.  The catalog row's keys are Qwen3-14B's (the model was retrained
from it with attention replaced in every layer); what no key settles is
listed under ``assumed`` in the configuration file, in the words below.  All
``num_hidden_layers`` alike.  With ``x = RMSNorm(h; ln1)``:

- ``q = x Wq`` (``num_attention_heads`` x ``head_dim``), ``k = x Wk``, ``v =
  x Wv`` (``num_key_value_heads`` x ``head_dim``), ``gamma = x Wd`` (one a
  K/V head), no bias (``attention_bias`` false); q and k RMS-normed over each
  head's ``head_dim`` with one scale of that width for q and one for k
  (Qwen3's convention), then the rotary embedding over the whole head,
  split-half, theta ``rope_theta``.
- the gate: ``log g_t = logsigmoid(c + gamma_t)``, float32, one a K/V head
  and position, ``c = retention_gate_offset`` (ln 999: g = 0.999 at a zero
  projection), a constant of the layer and no leaf.
- for query head ``h`` over its K/V head (K/V head ``g`` serves the query
  heads ``g r .. g r + r - 1``):

      a(t, j) = exp(sum_{s=j+1..t} log g_s) (q_t . k_j / sqrt(head_dim))^2
      y_t     = sum_{j<=t} a(t, j) v_j / (sum_{j<=t} a(t, j) + eps)

  ``eps = retention_eps``; the power is ``retention_degree`` = 2.  ``h <- h
  + concat_h(y) Wo``.
- ``f = RMSNorm(h; ln2)``; ``h <- h + (silu(f W1) * (f W3)) W2``, width
  ``intermediate_size``.

``logits = RMSNorm(h; lnf) W_head^T``; the head is not the embedding
(``tie_word_embeddings`` false).

The same layer as a recurrence, which is what a decoder carries: with
``phi(u)`` the degree-2 symmetric power of ``u`` (``u_a u_b`` for ``a <= b``,
the off-diagonal entries times sqrt 2, so that ``phi(q) . phi(k) = (q .
k)^2``; 8256 wide for a head of 128), a K/V head holds ``S_t = g_t S_{t-1} +
phi(k_t) v_t^T`` and ``z_t = g_t z_{t-1} + phi(k_t)`` and a query head reads
``y_t = S_t^T phi(q_t) / (z_t . phi(q_t) + eps d)``, the ``1/d`` of the
scores taken out of both.  ``tests/benchmarks/test_brumby.py`` writes that
out a position at a time and holds this file to it; ``counts`` gives the
state's elements as the configuration file's ``retention_state_dim`` lays
them out.

The tree has the program's leaf names, because the reference is handed the
program's own parameters: ``w1`` the MLP's gate projection, ``w3`` its up
projection, ``w2`` its down projection, ``wd`` the retention gate's (the
decay's) projection, ``qn`` and ``kn`` the q- and k-norm's scales.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

# The declared scales of the seeded weights that the chip check rests on
# (``assumed`` in the configuration file; PERF.md has the chip's readings).
# The stream is the embedding at deviation one.  The gate: ``gamma = x Wd``
# has deviation GATE (x is normed to one), so ``g = sigmoid(ln 999 + gamma)``
# runs from 0.92 (three deviations down, some twenty of a sequence's 2048 x 8
# a layer) over 0.999 at the median to 0.99999; the mean of ``-log g`` is
# about e^-6.9 e^(GATE^2 / 2) = 0.0031, so a state forgets by 1/e over some
# 320 positions: a fault in what is carried shows for hundreds of steps, and
# a prompt of 2048 is several memories long.
# q and k are normed to one a head, and whatever scale they had cancels in
# the quotient, so no draw of the norms' scales sharpens a query's weights:
# over the positions j, ``q_t . k_j`` is a fixed functional of a normed
# Gaussian key, Gaussian whatever q is, and its square is chi-square of one
# degree: the tenth of the positions with the largest weights carries 44% of
# the sum, (sum a)^2 / sum a^2 is a third of the positions within the decay's
# reach, a hundred-odd, and not all alike.  Both scales are ones.
# A retention layer then adds a context of deviation about 0.1 (that mean of
# values of deviation one) times ATTN_OUT, a quarter of the stream, and the
# MLP (silu(N(0,1)) N(0,1) has deviation 0.6) MLP_OUT x 0.6, a quarter too:
# a fault of either shows in the logits, and four layers leave the stream
# near one.  The logits have deviation one (HEAD).
EMB = 1.0
GATE = 1.5
ATTN_OUT = 2.5
MLP_OUT = 0.4

QUERY_BLOCK = 512       # queries that hold their weights at a time
HEAD_BLOCK = 32_768     # rows of the head upcast at a time: 0.67 GB


@dataclasses.dataclass(frozen=True)
class Shape:
    vocab: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    n_layers: int
    d_ff: int
    eps: float
    rope_theta: float
    degree: int
    gate_offset: float
    retention_eps: float
    state_dim: int          # what a K/V head's phi is laid out over

    @classmethod
    def from_config(cls, config: dict) -> "Shape":
        """From a configuration file's published keys and its
        ``retention_*`` ones."""
        c = config
        return cls(vocab=c["vocab_size"], d_model=c["hidden_size"],
                   n_heads=c["num_attention_heads"],
                   n_kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], n_layers=c["num_hidden_layers"],
                   d_ff=c["intermediate_size"], eps=c["rms_norm_eps"],
                   rope_theta=float(c["rope_theta"]),
                   degree=c["retention_degree"],
                   gate_offset=c["retention_gate_offset"],
                   retention_eps=c["retention_eps"],
                   state_dim=c["retention_state_dim"])


def param_init(shape: Shape, serving: bool = False
               ) -> dict[str, tuple[tuple[int, ...], float | None]]:
    """Leaf name -> (shape, standard deviation of its normal initializer);
    ``None`` marks a leaf that starts at one (every norm's scale).  Layers
    are stacked on the leading axis.  One draw, the constants above: no cell
    trains this configuration, so ``serving`` changes nothing."""
    s = shape
    L, D, F, V = s.n_layers, s.d_model, s.d_ff, s.vocab
    q, kv = s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim
    return {
        "emb": ((V, D), EMB),
        "head": ((V, D), D ** -0.5),
        "wq": ((L, D, q), D ** -0.5),
        "wk": ((L, D, kv), D ** -0.5),
        "wv": ((L, D, kv), D ** -0.5),
        "wd": ((L, D, s.n_kv_heads), GATE * D ** -0.5),
        "wo": ((L, q, D), ATTN_OUT * q ** -0.5),
        "qn": ((L, s.head_dim), None),
        "kn": ((L, s.head_dim), None),
        "w1": ((L, D, F), D ** -0.5),
        "w3": ((L, D, F), D ** -0.5),
        "w2": ((L, F, D), MLP_OUT * F ** -0.5),
        "ln1": ((L, D), None),
        "ln2": ((L, D), None),
        "lnf": ((D,), None),
    }


def counts(shape: Shape) -> dict:
    """What ``lib/costs.py`` counts of this family, each figure what the
    leanest exact program needs.

    ``active_params``: every matrix (the norms multiply none) but the
    embedding, a lookup table (``lookup_params``).  ``kv_elements``: no
    position is cached and a step reads nothing of one, but the harness
    asks a whole number above zero of every reference
    (``tests/benchmarks/test_reference.py``), so this is 1: 1.6 MB of the
    10.8 GB a step of the cell is counted to read (PERF.md, open questions).
    ``state_elements``: what a cached step reads of a
    sequence whatever its length, over all layers: a K/V head's ``state_dim x
    head_dim`` matrix and its ``state_dim`` normaliser (a step writes them
    back too, which ``lib/costs.decode_step_bytes`` does not count: a sound
    step's ``decode_hbm_share`` stays under the weights' part plus half of
    the state's).  ``attention_width``: ``lib/costs.prefill_flops`` counts
    ``4 x layers x width x T`` operations a position for attention; at the
    cell's prompts the leanest exact form is the quadratic one, two products
    of ``heads x head_dim`` over the ``T / 2`` earlier positions on the
    mean, ``2 x heads x head_dim x T`` a position, so the width is half the
    query heads' (the state's form costs ``2 x state_dim x head_dim x (heads
    + kv_heads)``, five times that at 2048 positions, and an exact program
    may skip it)."""
    s = shape
    L, D, F, V = s.n_layers, s.d_model, s.d_ff, s.vocab
    q, kv = s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim
    block = 2 * D * q + 2 * D * kv + D * s.n_kv_heads + 3 * D * F
    return {"active_params": L * block + V * D,
            "projection_params": V * D,
            "lookup_params": V * D,
            "kv_elements": 1,
            "state_elements": L * s.n_kv_heads * s.state_dim
            * (s.head_dim + 1),
            "attention_layers": L,
            "attention_width": q // 2}


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


def projections(shape: Shape, p: dict, x):
    """The normed input x (B, T, D) -> q (B, T, H, hd), k and v as wide as q
    (each K/V head before the query heads it serves) and the decay's running
    sum ``c_t = sum_{s<=t} log g_s`` (B, T, H), float32."""
    s = shape
    B, T, _ = x.shape
    H, K, hd = s.n_heads, s.n_kv_heads, s.head_dim
    q = _rmsnorm((x @ p["wq"]).reshape(B, T, H, hd), p["qn"], s.eps)
    k = _rmsnorm((x @ p["wk"]).reshape(B, T, K, hd), p["kn"], s.eps)
    q, k = _rope(q, s.rope_theta), _rope(k, s.rope_theta)
    v = (x @ p["wv"]).reshape(B, T, K, hd)
    logg = jax.nn.log_sigmoid(s.gate_offset + x @ p["wd"])      # (B, T, K)
    k, v, logg = (jnp.repeat(y, H // K, axis=2) for y in (k, v, logg))
    return q, k, v, jnp.cumsum(logg, axis=1)


def _retention(shape: Shape, p: dict, x):
    """The retention half's ``y Wo`` of the normed input x (B, T, D), a block
    of queries at a time."""
    s = shape
    B, T, _ = x.shape
    H, hd = s.n_heads, s.head_dim
    q, k, v, c = projections(s, p, x)
    block = min(T, QUERY_BLOCK)
    n = -(-T // block)

    def blocks(y):      # (B, T, ...) -> (n, B, block, ...), the tail padded
        y = jnp.pad(y, [(0, 0), (0, n * block - T)] + [(0, 0)] * (y.ndim - 2))
        return jnp.moveaxis(y.reshape(B, n, block, *y.shape[2:]), 1, 0)

    c_k = jnp.moveaxis(c, 1, -1)[:, :, None, :]                 # (B, H, 1, T)

    def one(of):
        first, q_b, c_b = of
        t = first + jnp.arange(block)
        live = jnp.arange(T)[None, :] <= t[:, None]             # (Q, T)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q_b, k) * hd ** -0.5
        fade = jnp.moveaxis(c_b, 1, -1)[..., None] - c_k        # (B, H, Q, T)
        a = sc ** s.degree * jnp.exp(jnp.where(live, fade, -jnp.inf))
        y = jnp.einsum("bhqk,bkhd->bqhd", a, v)
        return y / (jnp.moveaxis(a.sum(-1), 1, -1)[..., None]
                    + s.retention_eps)

    # a padded query (past the last position) sees every key: dropped below
    y = jax.lax.map(one, (jnp.arange(n) * block, blocks(q), blocks(c)))
    y = jnp.moveaxis(y, 0, 1).reshape(B, n * block, H * hd)[:, :T]
    return y @ p["wo"]


@functools.partial(jax.jit, static_argnums=0)
def _layer(shape: Shape, stacks: dict, layer, h):
    """Block ``layer`` on (B, T, D) float32; ``stacks`` the layers' leaves as
    stored, stacked over layers.  The layer's leaves are read out of the
    stacks in here, so they and their float32 copies are temporaries of this
    program and not live arrays beside the next layer's."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(jax.lax.dynamic_index_in_dim(
            v, layer, keepdims=False), jnp.float32)
            for k, v in stacks.items()}
        h = h + _retention(shape, p, _rmsnorm(h, p["ln1"], shape.eps))
        f = _rmsnorm(h, p["ln2"], shape.eps)
        return h + (jax.nn.silu(f @ p["w1"]) * (f @ p["w3"])) @ p["w2"]


@functools.partial(jax.jit, static_argnums=0)
def _project(shape: Shape, rows, h):
    """``h`` already normed, onto a block of the head's rows."""
    with jax.default_matmul_precision("highest"):
        return h @ jnp.asarray(rows, jnp.float32).T


LAYER_LEAVES = ("wq", "wk", "wv", "wd", "wo", "qn", "kn", "w1", "w3", "w2",
                "ln1", "ln2")


class PositionLogits:
    """The (B, T, V) float32 logits of a forward pass, multiplied out for the
    positions that are read: ``self[:, a:b]`` projects those positions'
    hidden states onto the head and is a ``jax`` array; ``np.asarray(self)``
    and ``jnp.asarray(self)`` project every position.  A decode check reads
    128 positions of each of two sequences of 2176."""

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
