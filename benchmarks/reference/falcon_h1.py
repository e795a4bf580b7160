"""Plain Falcon-H1 decoder (``model_type`` ``falcon_h1``): a Mamba-2 mixer
beside grouped-query attention in every block, in float32 ``jax.numpy`` with
nothing of the program in it.

No shard_map, no cache, no kernel, no chunks: the mixer's recurrence is a
``lax.scan`` over positions, one state update a position, and attention
materialises its scores.  Matrix multiplications at
``jax.default_matmul_precision("highest")``, because a TPU runs a float32
product in bfloat16 passes unless told otherwise.  A layer at a time: the
parameters arrive as the program stores them (bfloat16 on the chip) and a
whole tree in float32 does not fit beside them, so each layer's slice is
upcast inside that layer's call and dropped after it, and the head, 5.35 GB
in float32, is multiplied in blocks of vocabulary rows.

The block, from the published keys (what is from memory of the published
``falcon_h1`` code is listed under ``assumed`` in the configuration file).
Stream ``x0 = E[tok] * embedding_multiplier``.  A layer, with ``u =
RMSNorm(x; ln1)``:

- attention: ``q = (u * attention_in_multiplier) Wq``, ``k`` and ``v``
  alike with ``num_key_value_heads`` heads, ``k`` times ``key_multiplier``;
  rotary embedding over the whole head, split-half, theta ``rope_theta``,
  on q and k; K/V head ``j`` serves the query heads ``j*r .. j*r + r - 1``;
  causal softmax of scores over sqrt(head_dim); ``a = (ctx Wo) *
  attention_out_multiplier``; no bias, no q/k norm.
- mixer: ``p = ((u * ssm_in_multiplier) W_in) * m``, split into gate ``z``
  (``mamba_d_ssm``), ``xBC`` (``d_ssm + 2 * groups * d_state``) and ``dt``
  (``mamba_n_heads``); ``m`` is ``ssm_multipliers`` spread over the columns
  of z, x, B, C, dt.  ``xBC <- silu(conv(xBC) + bias)``, causal, depthwise,
  ``mamba_d_conv`` taps.  ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``, ``y_t =
  h_t C_t + D x_t``, a head reading the B and C of its group.  Gate, then
  norm: ``y <- y * silu(z)``, RMSNorm over each group's share of ``d_ssm``
  with one scale.  ``s = (y W_out) * ssm_out_multiplier``.
- one residual: ``x <- x + a + s``.
- MLP: ``f = RMSNorm(x; ln2)``; ``x <- x + ((silu((f W_gate) *
  mlp_multipliers[0]) * (f W_up)) W_down) * mlp_multipliers[1]``.

``logits = (RMSNorm(x; lnf) W_head^T) * lm_head_multiplier``; the head is
not the embedding.

The tree has the program's leaf names, because the reference is handed the
program's own parameters: ``w1`` is the MLP's gate projection, ``w3`` its up
projection, ``w2`` its down projection, ``wo`` attention's output
projection; ``ssm_in``, ``ssm_out``, ``conv_w`` (taps, channels), ``conv_b``,
``a_log``, ``dt_bias``, ``ssm_d``, ``ssm_norm`` are the mixer's.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

# The declared scales of the seeded weights (``assumed`` in the configuration
# file; PERF.md section 2 has the chip's readings).  The block's multipliers
# are those of a model trained under them: 0.011 on the keys, 0.0375, 0.088
# and 0.011 on the three branches' outputs, 0.25 x 0.06 to 0.5 on the mixer's
# inputs.  With every matrix at a unit-gain scale the keys are a hundredth of
# the queries, so attention is a mean over all positions (which turns the
# stream into one constant vector, PERF.md section 6, PR 35), the mixer's
# pre-activations are under a tenth, so its gated output falls under the
# norm's eps, and each branch adds a hundredth of the stream: no planted
# fault of a branch would show.  So the matrices under a multiplier are drawn
# larger by about its inverse, as trained ones are: scores of deviation about
# 2 (QK x QK x key_multiplier), the mixer's and the gate's pre-activations
# about 1 (SSM_IN, GATE), and each branch's output about a fifth of the
# embedded stream (ATTN_VO x ATTN_VO, SSM_OUT, MLP_OUT), the stream itself
# at about 1 (EMB x embedding_multiplier) and the logits too (HEAD x
# lm_head_multiplier).  Each but EMB times fan_in ** -0.5.
EMB = 0.18
HEAD = 128.0
QK = 13.5
ATTN_VO = 4.0
SSM_IN = 12.0
SSM_OUT = 2.5
GATE = 6.0
MLP_OUT = 30.0
CONV = 0.5
CONV_BIAS = 0.1
# ``lib/program.init_params`` draws a leaf as normal x deviation or as ones,
# so dt and A cannot be drawn as the model initialises them (dt in [1e-3,
# 1e-1], A in [1, 16]: a decay of 0.2 to 0.999 a position).  ``dt_bias`` and
# ``D`` are ones (dt about 1.3) and ``A_log`` is normal at this deviation:
# a head's decay is exp(-1.3 exp(A_log)), 0.27 at the median, over 0.9 in
# one head of ten and nought in as many, and a slow head's state is the
# larger.  Speed and bytes do not depend on it; what ``correct`` sees of a
# state that is dropped or not carried does.
A_LOG = 2.0


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
    d_ssm: int
    d_state: int
    n_groups: int
    ssm_heads: int
    d_conv: int
    embedding_multiplier: float
    attention_in_multiplier: float
    attention_out_multiplier: float
    key_multiplier: float
    lm_head_multiplier: float
    ssm_in_multiplier: float
    ssm_multipliers: tuple
    ssm_out_multiplier: float
    mlp_multipliers: tuple

    @classmethod
    def from_config(cls, config: dict) -> "Shape":
        """From a configuration file's published keys."""
        c = config
        return cls(
            vocab=c["vocab_size"], d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            n_layers=c["num_hidden_layers"], d_ff=c["intermediate_size"],
            eps=c["rms_norm_eps"], rope_theta=float(c["rope_theta"]),
            d_ssm=c["mamba_d_ssm"], d_state=c["mamba_d_state"],
            n_groups=c["mamba_n_groups"], ssm_heads=c["mamba_n_heads"],
            d_conv=c["mamba_d_conv"],
            embedding_multiplier=c["embedding_multiplier"],
            attention_in_multiplier=c["attention_in_multiplier"],
            attention_out_multiplier=c["attention_out_multiplier"],
            key_multiplier=c["key_multiplier"],
            lm_head_multiplier=c["lm_head_multiplier"],
            ssm_in_multiplier=c["ssm_in_multiplier"],
            ssm_multipliers=tuple(c["ssm_multipliers"]),
            ssm_out_multiplier=c["ssm_out_multiplier"],
            mlp_multipliers=tuple(c["mlp_multipliers"]))

    @property
    def conv_dim(self) -> int:
        return self.d_ssm + 2 * self.n_groups * self.d_state

    @property
    def in_dim(self) -> int:
        return self.d_ssm + self.conv_dim + self.ssm_heads

    @property
    def state_elements(self) -> int:
        """What one sequence holds in one layer beside its K/V: the
        convolution's last inputs and the heads' states."""
        return ((self.d_conv - 1) * self.conv_dim
                + self.d_ssm * self.d_state)


def param_init(shape: Shape, serving: bool = False
               ) -> dict[str, tuple[tuple[int, ...], float | None]]:
    """Leaf name -> (shape, standard deviation of its normal initializer);
    ``None`` marks a leaf that starts at one (the norms' scales, ``dt_bias``
    and ``D``).  Layers are stacked on the leading axis.  One draw, the
    constants above: no cell trains this configuration, so ``serving``
    changes nothing."""
    s = shape
    L, D, F, V = s.n_layers, s.d_model, s.d_ff, s.vocab
    q, kv = s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim
    return {
        "emb": ((V, D), EMB),
        "head": ((V, D), HEAD * D ** -0.5),
        "wq": ((L, D, q), QK * D ** -0.5),
        "wk": ((L, D, kv), QK * D ** -0.5),
        "wv": ((L, D, kv), ATTN_VO * D ** -0.5),
        "wo": ((L, q, D), ATTN_VO * q ** -0.5),
        "w1": ((L, D, F), GATE * D ** -0.5),
        "w3": ((L, D, F), D ** -0.5),
        "w2": ((L, F, D), MLP_OUT * F ** -0.5),
        "ssm_in": ((L, D, s.in_dim), SSM_IN * D ** -0.5),
        "ssm_out": ((L, s.d_ssm, D), SSM_OUT * s.d_ssm ** -0.5),
        "conv_w": ((L, s.d_conv, s.conv_dim), CONV),
        "conv_b": ((L, s.conv_dim), CONV_BIAS),
        "a_log": ((L, s.ssm_heads), A_LOG),
        "dt_bias": ((L, s.ssm_heads), None),
        "ssm_d": ((L, s.ssm_heads), None),
        "ssm_norm": ((L, s.d_ssm), None),
        "ln1": ((L, D), None),
        "ln2": ((L, D), None),
        "lnf": ((D,), None),
    }


def counts(shape: Shape) -> dict:
    """What ``lib/costs.py`` counts of this family.  ``active_params``:
    every parameter but the embedding, which a token looks one row up in
    (``lookup_params``); the head is the projection.  ``kv_elements``: one
    position's keys and values in one layer, of the K/V heads;
    ``attention_width``: the query heads' summed width, which is not
    ``d_model``; ``state_elements``: a sequence's fixed-size state over all
    layers, which a cached step reads and writes whole."""
    table = param_init(shape)
    stored = sum(math.prod(dims) for dims, _std in table.values())
    lookup = math.prod(table["emb"][0])
    return {"active_params": stored - lookup,
            "projection_params": shape.vocab * shape.d_model,
            "lookup_params": lookup,
            "kv_elements": 2 * shape.n_kv_heads * shape.head_dim,
            "attention_layers": shape.n_layers,
            "attention_width": shape.n_heads * shape.head_dim,
            "state_elements": shape.n_layers * shape.state_elements}


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


def _attention(shape: Shape, p: dict, u):
    B, T, _ = u.shape
    H, K, hd = shape.n_heads, shape.n_kv_heads, shape.head_dim
    u = u * shape.attention_in_multiplier
    q = _rope((u @ p["wq"]).reshape(B, T, H, hd), shape.rope_theta)
    k = _rope(((u @ p["wk"]) * shape.key_multiplier).reshape(B, T, K, hd),
              shape.rope_theta)
    v = (u @ p["wv"]).reshape(B, T, K, hd)
    k, v = (jnp.repeat(y, H // K, axis=2) for y in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, T, H * hd)
    return (ctx @ p["wo"]) * shape.attention_out_multiplier


def recurrence(x, dt, a, b, c):
    """The state-space recurrence, a position at a time from a zero state.
    x: (B, T, H, P); dt: (B, T, H); a: (H,); b, c: (B, T, H, N), a head's
    own.  Returns y (B, T, H, P) with ``y_t = h_t c_t`` and the last state
    (B, H, P, N)."""
    def step(h, at):
        x_t, dt_t, b_t, c_t = at
        h = (h * jnp.exp(dt_t * a)[..., None, None]
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    B, _, H, P = x.shape
    last, ys = jax.lax.scan(
        step, jnp.zeros((B, H, P, b.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(ys, 0, 1), last


def _mixer(shape: Shape, p: dict, u):
    s = shape
    B, T, _ = u.shape
    H, G, N = s.ssm_heads, s.n_groups, s.d_state
    mz, mx, mb, mc, mdt = s.ssm_multipliers
    m = jnp.concatenate([
        jnp.full(s.d_ssm, mz), jnp.full(s.d_ssm, mx), jnp.full(G * N, mb),
        jnp.full(G * N, mc), jnp.full(H, mdt)]).astype(jnp.float32)
    proj = ((u * s.ssm_in_multiplier) @ p["ssm_in"]) * m
    z, xbc, dt = jnp.split(proj, [s.d_ssm, s.d_ssm + s.conv_dim], axis=-1)
    # causal depthwise convolution: tap k reads the input d_conv - 1 - k back
    padded = jnp.pad(xbc, ((0, 0), (s.d_conv - 1, 0), (0, 0)))
    conv = p["conv_b"] + sum(padded[:, k:k + T] * p["conv_w"][k]
                             for k in range(s.d_conv))
    x, b, c = jnp.split(jax.nn.silu(conv), [s.d_ssm, s.d_ssm + G * N], -1)
    x = x.reshape(B, T, H, s.d_ssm // H)
    b, c = (jnp.repeat(t.reshape(B, T, G, N), H // G, axis=2) for t in (b, c))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y, _last = recurrence(x, dt, -jnp.exp(p["a_log"]), b, c)
    y = (y + p["ssm_d"][:, None] * x).reshape(B, T, s.d_ssm)
    y = y * jax.nn.silu(z)                      # gate, then norm
    y = _rmsnorm(y.reshape(B, T, G, s.d_ssm // G), 1.0, s.eps)
    y = y.reshape(B, T, s.d_ssm) * p["ssm_norm"]
    return (y @ p["ssm_out"]) * s.ssm_out_multiplier


@functools.partial(jax.jit, static_argnums=0)
def _layer(shape: Shape, layer_params: dict, h):
    """One block on (B, T, D) float32; ``layer_params`` as stored."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in layer_params.items()}
        u = _rmsnorm(h, p["ln1"], shape.eps)
        h = h + _attention(shape, p, u) + _mixer(shape, p, u)
        f = _rmsnorm(h, p["ln2"], shape.eps)
        m0, m1 = shape.mlp_multipliers
        return h + ((jax.nn.silu((f @ p["w1"]) * m0) * (f @ p["w3"]))
                    @ p["w2"]) * m1


@functools.partial(jax.jit, static_argnums=0)
def _project(shape: Shape, rows, h):
    """``h`` already normed, onto a block of the head's rows."""
    with jax.default_matmul_precision("highest"):
        return (h @ jnp.asarray(rows, jnp.float32).T
                ) * shape.lm_head_multiplier


LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w1", "w3", "w2", "ssm_in", "ssm_out",
                "conv_w", "conv_b", "a_log", "dt_bias", "ssm_d", "ssm_norm",
                "ln1", "ln2")
HEAD_BLOCK = 32_768     # rows of the head upcast at a time: 0.67 GB


def logits(shape: Shape, params: dict, tokens):
    """(B, T) int32 tokens -> (B, T, V) float32 logits."""
    h = (jnp.asarray(params["emb"][tokens], jnp.float32)
         * shape.embedding_multiplier)
    for l in range(params["wq"].shape[0]):
        h = _layer(shape, {k: params[k][l] for k in LAYER_LEAVES}, h)
    h = _rmsnorm(h, jnp.asarray(params["lnf"], jnp.float32), shape.eps)
    head = params["head"]
    return jnp.concatenate(
        [_project(shape, head[lo:lo + HEAD_BLOCK], h)
         for lo in range(0, head.shape[0], HEAD_BLOCK)], axis=-1)


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
