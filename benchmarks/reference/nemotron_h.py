"""Plain decoder of NVIDIA-Nemotron-3-Nano-30B-A3B (``nemotron_h``) as one chip
of an expert-parallel pair computes it: a layer that is one mixer alone under
one norm (a Mamba-2 mixer, grouped-query attention without a rotary embedding,
or routed ungated relu2 experts beside a shared one), in float32 ``jax.numpy``
with nothing of the program in it.

No shard_map, no cache, no chunked scan, no kernel, no sort and no grouped
matmul: the state-space recurrence runs a position at a time from a zero
state, attention is dense under the causal mask, and every expert *held here*
is run on every token under the top-k mask.  Matrix multiplications at
``jax.default_matmul_precision("highest")``, because a TPU runs a float32
product in bfloat16 passes unless told otherwise.  A layer at a time and an
expert at a time: the parameters arrive as the program stores them (bfloat16
on the chip, 9.17 GB resident), and each slice is upcast inside the call that
reads it.  The logits are multiplied out only for the positions a caller
reads (:class:`PositionLogits`).

The model, from the published keys (what no key settles is listed under
``assumed`` in the configuration file).  The stream ``h`` starts as the
embedding's rows (no multiplier); layer ``l`` has one kind, the ``l``-th
character of ``hybrid_override_pattern``, and is

    h <- h + mixer_l(RMSNorm(h; norm_l)),    eps ``layer_norm_epsilon``

with nothing after it; after the last layer ``RMSNorm(h; lnf)`` and an untied
head over the vocabulary rows held here.  No bias on any projection.

``M``, Mamba-2.  ``H = mamba_num_heads`` heads of ``P = mamba_head_dim``,
``d_inner = H P`` (``expand`` is not read), ``G = n_groups`` groups, a state
of ``N = ssm_state_size`` a head and channel:

    [z, xBC, dt] = x ssm_in                 (d_inner, d_inner + 2 G N, H)
    xBC = silu(conv_b + causal depthwise convolution of conv_kernel taps)
    [x, B, C] = xBC                         (H x P, G x N, G x N)
    dt = softplus(dt + dt_bias);  A = -exp(a_log), one a head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        (P x N a head)
    y_t = S_t C_t + D x_t                   (B, C: the head's group's)
    y = RMSNorm_group(y * silu(z)) * ssm_norm    (groups of d_inner / G)
    out = y ssm_out

``*``, attention.  ``q = x wq`` (``num_attention_heads`` x ``head_dim``),
``k = x wk``, ``v = x wv`` (``num_key_value_heads`` x ``head_dim``, K/V head
``j`` serving the query heads ``j H/K .. (j + 1) H/K - 1``), causal softmax at
scale ``head_dim^-1/2``, ``wo``; **no rotary embedding** (``Shape.rope``
False; True turns q and k in the split-half convention at ``rope_theta``,
for the control that plants one).

``E``, routed experts.  ``s = sigmoid(x wg)`` in float32 over all
``router_experts`` outputs; picks = the ``num_experts_per_tok`` largest of
``s + wgb`` (the selection bias picks and does not weigh; ``n_group`` and
``topk_group`` are 1: no grouping); ``g_e = routed_scaling_factor s_e / sum
over picks of s`` (``norm_topk_prob``); ``Expert_e(x) = relu(x w1_e)^2 w2_e``
(no gate matrix); ``out = sum over picks e held here of g_e Expert_e(x) +
relu(x sw1)^2 sw2``, the shared expert of width
``moe_shared_expert_intermediate_size`` on every token.  This chip is one of
two that share a layer by expert and holds experts ``experts_held.first ..
first + count - 1``: a pick held elsewhere adds nothing here (the chip that
holds it adds it).  :func:`moe` with ``held`` and ``shared`` given evaluates
any share, for the test that adds the shares up.

The tree has the program's leaf names, because the reference is handed the
program's own parameters: ``ln1`` stacked over the layers that are a mixer
(``M`` and ``*`` in the model's order), ``ln2`` over the routed layers, each
kind's leaves over the layers of the kind.
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
# stream starts at unit size (EMB) and a mixer adds about half of that.
# The mixer: unit pre-activations (``ssm_in`` at unit gain), taps of CONV and
# a bias of CONV_BIAS; ``dt_bias`` and ``D`` are ones (dt about 1.3) and
# ``a_log`` normal at A_LOG, so a head's decay a position is exp(-1.3
# exp(a_log)): over 0.99 in one head of twelve (whose state sums a hundred
# positions and is what a state that is dropped, not carried or rounded
# shows in), nought in a third, between the two in the rest; the gated
# norm's output is unit, so SSM_OUT is the branch's size.  Attention: q and k
# at QK of unit gain (scores of deviation QK^2 = 2: a query weighs a few
# dozen of its positions), values at V_GAIN so that their mean over those is
# not lost in the stream, ATTN_OUT on the output.  The router: logits of
# deviation ROUTER_SPREAD over 128 outputs, so a token's six scores are 0.8
# to 0.95 and weigh about 0.42 each after the factor 2.5; the selection bias
# at deviation BIAS changes one of a token's six picks in three tokens
# (tests/benchmarks/test_nemotron_h.py measures it with numpy).  In the model
# the bias is what evens the experts' load; at random weights the load is
# even without it, and a bias of 0.02 (one pick in six moved) left a held
# expert anything from half to nearly twice its share, so that the row tiles
# a pass uses, and with them ``ttft_ms``, moved with the seed by a percent
# (my chip runs, PR 62).  A sixth and a
# seventh score lie 0.01 apart, so on a bfloat16 stream about one token in
# twelve picks another expert than the float32 reference in a routed layer,
# and the state-space layers carry that on: what a turned pick costs is set
# by EXPERT_OUT.  relu(.)^2 of a unit pre-activation has rms 1.22, so three
# held picks add about 0.09 of the unit stream; at 0.5 the sound program read
# a median error of 0.074 on the chip and at 0.25 of 0.020, which no limit
# under tier-1's cap of 0.02 holds, at 0.12 0.011 to 0.0145 (my chip runs,
# PR 62).
# The shared expert, which no router turns, at SHARED_OUT adds 0.18:
# relu(.)^2 is positive, so 0.45 of what such an MLP adds is one vector for
# every token, which the stream carries to the next router as a bias of its
# own that favours some experts; at 0.4 a held expert got from a quarter to
# thrice its share, and the row tiles a step and a pass use, and with them
# both end-to-end metrics, moved by a percent with the seed (my chip runs,
# PR 62: ``moe.experts`` 2.1% and 2.3% apart between two seeds).
EMB = 1.0
CONV = 0.5
CONV_BIAS = 0.1
A_LOG = 3.5
SSM_OUT = 0.5
QK = 2 ** 0.5
V_GAIN = 2.0
ATTN_OUT = 0.5
ROUTER_SPREAD = 1.0
BIAS = 0.005
EXPERT_OUT = 0.1
SHARED_OUT = 0.15

HEAD_BLOCK = 32_768     # rows of the head upcast at a time

KINDS = "M*E"           # the pattern's characters that are built


@dataclasses.dataclass(frozen=True)
class Shape:
    vocab: int
    d_model: int
    n_layers: int
    pattern: str            # a character a layer, ``n_layers`` long
    eps: float
    ssm_heads: int
    ssm_head_dim: int
    d_state: int
    n_groups: int
    d_conv: int
    heads: int
    kv_heads: int
    head_dim: int
    rope: bool              # False as the model is assumed; a control's True
    theta: float
    d_expert: int
    d_shared: int
    n_router: int           # the router's outputs
    top_k: int
    held: tuple             # (first, count): the experts on this chip
    scale: float            # routed_scaling_factor
    renorm: bool
    state_itemsize: int     # bytes an element of the carried state
    at_batch: int           # sequences a step, for the counters; 0: unknown

    @classmethod
    def from_config(cls, config: dict) -> "Shape":
        """From a configuration file's published keys, ``router_experts``
        (the published ``n_routed_experts``; the file's own counts the
        experts held) and ``experts_held``."""
        c = config
        pattern = c["hybrid_override_pattern"][:c["num_hidden_layers"]]
        if (set(pattern) - set(KINDS) or len(pattern) != c["num_hidden_layers"]
                or c["mlp_hidden_act"] != "relu2" or c["n_group"] != 1
                or c["topk_group"] != 1 or c["mamba_hidden_act"] != "silu"
                or not c["use_conv_bias"] or c["residual_in_fp32"]
                or c["attention_bias"] or c["mlp_bias"] or c["use_bias"]
                or c["mamba_proj_bias"]):
            raise ValueError("written for layers of kinds M, * and E, relu2 "
                             "experts under an ungrouped sigmoid router, a "
                             "convolution with a bias and no other bias")
        held = c.get("experts_held", {"first": 0,
                                      "count": c["router_experts"]})
        if held["count"] != c["n_routed_experts"]:
            raise ValueError("n_routed_experts counts the experts held")
        return cls(
            vocab=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"], pattern=pattern,
            eps=c["layer_norm_epsilon"], ssm_heads=c["mamba_num_heads"],
            ssm_head_dim=c["mamba_head_dim"], d_state=c["ssm_state_size"],
            n_groups=c["n_groups"], d_conv=c["conv_kernel"],
            heads=c["num_attention_heads"],
            kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            rope=bool(c.get("attention_use_rope", False)),
            theta=float(c["rope_theta"]),
            d_expert=c["moe_intermediate_size"],
            d_shared=(c["n_shared_experts"]
                      * c["moe_shared_expert_intermediate_size"]),
            n_router=c["router_experts"], top_k=c["num_experts_per_tok"],
            held=(held["first"], held["count"]),
            scale=float(c["routed_scaling_factor"]),
            renorm=bool(c["norm_topk_prob"]),
            state_itemsize=jnp.dtype(c["ssm_state_dtype"]).itemsize,
            at_batch=int(c.get("counters", {}).get("sequences_a_step", 0)))

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_ssm + 2 * self.n_groups * self.d_state

    @property
    def in_dim(self) -> int:
        return self.d_ssm + self.conv_dim + self.ssm_heads


SSM_LEAVES = ("ssm_in", "ssm_out", "conv_w", "conv_b", "a_log", "dt_bias",
              "ssm_d", "ssm_norm")
ATTN_LEAVES = ("wq", "wk", "wv", "wo")
ROUTER_LEAVES = ("wg", "wgb")
EXPERT_LEAVES = ("w1", "w2")
SHARED_LEAVES = ("sw1", "sw2")


def param_init(shape: Shape, serving: bool = False
               ) -> dict[str, tuple[tuple[int, ...], float | None]]:
    """Leaf name -> (shape, standard deviation of its normal initializer);
    ``None`` marks a leaf that starts at one (the norms' scales, ``dt_bias``
    and ``D``).  One draw, the constants above: no cell trains this
    configuration, so ``serving`` changes nothing."""
    s = shape
    M, A, E = s.count("M"), s.count("*"), s.count("E")
    D, V, Fe, Fs = s.d_model, s.vocab, s.d_expert, s.d_shared
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    return {
        "emb": ((V, D), EMB),
        "head": ((V, D), D ** -0.5),
        "ln1": ((M + A, D), None),
        "ln2": ((E, D), None),
        "lnf": ((D,), None),
        "ssm_in": ((M, D, s.in_dim), D ** -0.5),
        "ssm_out": ((M, s.d_ssm, D), SSM_OUT * s.d_ssm ** -0.5),
        "conv_w": ((M, s.d_conv, s.conv_dim), CONV),
        "conv_b": ((M, s.conv_dim), CONV_BIAS),
        "a_log": ((M, s.ssm_heads), A_LOG),
        "dt_bias": ((M, s.ssm_heads), None),
        "ssm_d": ((M, s.ssm_heads), None),
        "ssm_norm": ((M, s.d_ssm), None),
        "wq": ((A, D, q), QK * D ** -0.5),
        "wk": ((A, D, kv), QK * D ** -0.5),
        "wv": ((A, D, kv), V_GAIN * D ** -0.5),
        "wo": ((A, q, D), ATTN_OUT * q ** -0.5),
        "wg": ((E, D, s.n_router), ROUTER_SPREAD * D ** -0.5),
        "wgb": ((E, s.n_router), BIAS),
        "w1": ((E, s.held[1], D, Fe), D ** -0.5),
        "w2": ((E, s.held[1], Fe, D), EXPERT_OUT * Fe ** -0.5),
        "sw1": ((E, D, Fs), D ** -0.5),
        "sw2": ((E, Fs, D), SHARED_OUT * Fs ** -0.5),
    }


def state_bytes(shape: Shape) -> int:
    """What one sequence holds of fixed-size state over all layers, in bytes:
    a Mamba layer's heads' states at ``ssm_state_dtype`` and its
    convolution's last inputs in bfloat16."""
    s = shape
    return s.count("M") * (s.d_ssm * s.d_state * s.state_itemsize
                           + (s.d_conv - 1) * s.conv_dim * 2)


def counts(shape: Shape) -> dict:
    """What ``lib/costs.py`` counts of this family on this chip.

    ``active_params``: what one token multiplies *here*: a Mamba layer's two
    projections and its taps; an attention layer's four matrices; a routed
    layer's router (all its outputs), its shared expert and, of the experts
    a token picks, the ``top_k x held / n_router`` that fall to this chip on
    the mean (3 of 6); and the head.  The embedding is a lookup table
    (``lookup_params``).  The recurrence's own operations are not a
    parameter's and are not counted, as cell 5's are not: ``prefill_mfu``
    understates.  ``attention_layers``: the ``*`` layers alone attend;
    ``attention_width``: the query heads' summed width.  ``kv_elements``:
    one position's keys and values in one attending layer.
    ``state_elements``: ``lib/costs.decode_step_bytes`` multiplies it by
    ``kv_cache_dtype``'s itemsize (bfloat16: 2) and has no second itemsize,
    so it is given as the state's *bytes* (:func:`state_bytes`: a float32
    state beside bfloat16 convolution inputs) over 2, as
    ``kimi_linear.py``'s.  A step reads the state and writes it; the count
    has it once.  ``routed``: the held experts (what the chip streams a
    step) and the picks that land here; the harness's form takes an expert
    for three matrices of ``d_model x d_expert``
    (``tests/benchmarks/test_reference.py`` holds ``layers x experts x 3 x
    d_model x d_expert`` under the stored parameters), and these have two, so
    ``d_expert`` is given as the width at which three would hold what the
    two do, ``2 x 1856 // 3 = 1237``: no metric this cell reports reads it
    (``grouped_matmul_roofline``, which does, counts three kernel calls a
    layer and is not listed for it)."""
    s = shape
    D, V = s.d_model, s.vocab
    mamba = D * s.in_dim + s.d_ssm * D + s.d_conv * s.conv_dim
    attention = 2 * D * s.heads * s.head_dim + 2 * D * s.kv_heads * s.head_dim
    here = max(1, s.top_k * s.held[1] // s.n_router)
    moe = D * s.n_router + 2 * D * s.d_shared + here * 2 * D * s.d_expert
    block = (s.count("M") * mamba + s.count("*") * attention
             + s.count("E") * moe)
    return {"active_params": block + V * D,
            "projection_params": V * D,
            "lookup_params": V * D,
            "kv_elements": 2 * s.kv_heads * s.head_dim,
            "state_elements": state_bytes(s) // 2,
            "attention_layers": max(1, s.count("*")),
            "attention_width": s.heads * s.head_dim,
            "routed": {"layers": s.count("E"), "experts": s.held[1],
                       "top_k": here, "d_model": D,
                       "d_expert": 2 * s.d_expert // 3}}


def ssm_update(shape: Shape) -> dict:
    """The cached state update's shape, for the reader of its roofline
    (``metrics/ssm_state_update_roofline.py``): the layers that hold a state
    (the ``M`` layers, not every layer), a state's heads, head width and
    size, and the bytes an element is carried in."""
    s = shape
    return {"layers": s.count("M"), "heads": s.ssm_heads,
            "head_dim": s.ssm_head_dim, "d_state": s.d_state,
            "itemsize": s.state_itemsize}


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, theta: float):
    """x (B, T, H, hd) at positions 0 .. T - 1: the pair (i, i + hd / 2)
    turned by ``position x theta^(-i / (hd / 2))``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def recurrence(x, dt, a, b, c):
    """The state-space recurrence, a position at a time from a zero state.
    x: (B, T, H, P); dt: (B, T, H); a: (H,); b, c: (B, T, H, N), a head's
    own.  Returns y (B, T, H, P) with ``y_t = S_t c_t`` and the last state
    (B, H, P, N)."""
    def step(S, at):
        x_t, dt_t, b_t, c_t = at
        S = (S * jnp.exp(dt_t * a)[..., None, None]
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, c_t)

    B, _, H, P = x.shape
    last, ys = jax.lax.scan(
        step, jnp.zeros((B, H, P, b.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(ys, 0, 1), last


def mamba(shape: Shape, p: dict, x):
    """The mixer of an ``M`` layer on the normed stream ``x`` (B, T, D); its
    leaves float32."""
    s = shape
    B, T, _ = x.shape
    H, G, N = s.ssm_heads, s.n_groups, s.d_state
    z, xbc, dt = jnp.split(x @ p["ssm_in"], [s.d_ssm, s.d_ssm + s.conv_dim],
                           axis=-1)
    # causal depthwise convolution: tap k reads the input d_conv - 1 - k back
    padded = jnp.pad(xbc, ((0, 0), (s.d_conv - 1, 0), (0, 0)))
    conv = p["conv_b"] + sum(padded[:, k:k + T] * p["conv_w"][k]
                             for k in range(s.d_conv))
    u, b, c = jnp.split(jax.nn.silu(conv), [s.d_ssm, s.d_ssm + G * N], -1)
    u = u.reshape(B, T, H, s.ssm_head_dim)
    b, c = (jnp.repeat(t.reshape(B, T, G, N), H // G, axis=2) for t in (b, c))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y, _last = recurrence(u, dt, -jnp.exp(p["a_log"]), b, c)
    y = (y + p["ssm_d"][:, None] * u).reshape(B, T, s.d_ssm)
    y = y * jax.nn.silu(z)                      # gate, then norm
    y = _rmsnorm(y.reshape(B, T, G, s.d_ssm // G), 1.0, s.eps)
    return (y.reshape(B, T, s.d_ssm) * p["ssm_norm"]) @ p["ssm_out"]


def attention(shape: Shape, p: dict, x):
    """The mixer of a ``*`` layer on the normed stream ``x``."""
    s = shape
    B, T, _ = x.shape
    H, K, hd = s.heads, s.kv_heads, s.head_dim
    q = (x @ p["wq"]).reshape(B, T, H, hd)
    k = (x @ p["wk"]).reshape(B, T, K, hd)
    v = (x @ p["wv"]).reshape(B, T, K, hd)
    if s.rope:
        q, k = _rope(q, s.theta), _rope(k, s.theta)
    k, v = (jnp.repeat(y, H // K, axis=2) for y in (k, v))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, T, H * hd) @ p["wo"]


def route(shape: Shape, p: dict, x):
    """(B, T, n_router) weights: zero but at a token's ``top_k`` picks, where
    they are the sigmoid scores (with ``renorm`` over their sum) times
    ``scale``."""
    s = shape
    score = jax.nn.sigmoid(x @ p["wg"])
    _best, at = jax.lax.top_k(score + p["wgb"], s.top_k)
    picked = jax.nn.one_hot(at, s.n_router, dtype=score.dtype).sum(axis=-2)
    weight = score * picked
    if s.renorm:
        weight = weight / weight.sum(axis=-1, keepdims=True)
    return weight * s.scale


def _relu2(x, up, down):
    return jnp.square(jax.nn.relu(x @ up)) @ down


@functools.partial(jax.jit, static_argnums=(0, 2))
def _mixer_layer(shape, stacks, at, h):
    """``h + mixer(RMSNorm(h; ln1))`` of an ``M`` or ``*`` layer: ``stacks``
    its kind's leaves as stored and the layer's norm as ``ln1``, ``at`` its
    place among its kind."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v if k == "ln1" else v[at], jnp.float32)
             for k, v in stacks.items()}
        mixer = mamba if "ssm_in" in p else attention
        return h + mixer(shape, p, _rmsnorm(h, p["ln1"], shape.eps))


@functools.partial(jax.jit, static_argnums=(0, 2, 4, 5))
def moe(shape, stacks, layer, x, held=None, shared=True):
    """``(MoE(x), the router's weights)`` of routed layer ``layer`` on the
    normed stream ``x``; the experts read out of their stacks one at a time.
    ``held`` (first, count): the share evaluated, of experts stacked from
    ``first`` on (the chip's own by default); ``shared``: with the shared
    expert, which every chip adds for its own tokens."""
    first, count = held or shape.held
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(stacks[k][layer], jnp.float32)
             for k in (*ROUTER_LEAVES, *SHARED_LEAVES)}
        weight = route(shape, p, x)

        def one(e, total):
            up, down = (jnp.asarray(stacks[k][layer, e], jnp.float32)
                        for k in EXPERT_LEAVES)
            w = jax.lax.dynamic_index_in_dim(weight, first + e, axis=-1)
            return total + w * _relu2(x, up, down)

        out = jax.lax.fori_loop(0, count, one, jnp.zeros_like(x))
        if shared:
            out = out + _relu2(x, p["sw1"], p["sw2"])
        return out, weight


@functools.partial(jax.jit, static_argnums=0)
def _normed(shape, scale, h):
    return _rmsnorm(h, jnp.asarray(scale, jnp.float32), shape.eps)


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
    ``weights``: a list that is handed every routed layer's router weights
    (B, T, n_router), for :func:`counters`."""
    s = shape
    h = jnp.asarray(params["emb"][tokens], jnp.float32)
    kinds = {"M": {k: params[k] for k in SSM_LEAVES},
             "*": {k: params[k] for k in ATTN_LEAVES}}
    routed = {k: params[k] for k in (*ROUTER_LEAVES, *EXPERT_LEAVES,
                                     *SHARED_LEAVES)}
    for layer, kind in enumerate(s.pattern):
        at = s.pattern[:layer].count(kind)
        if kind == "E":
            branch, weight = moe(s, routed, at, _normed(
                s, params["ln2"][at], h))
            if weights is not None:
                weights.append(weight)
            h = h + branch
        else:
            mixers = layer - s.pattern[:layer].count("E")
            h = _mixer_layer(s, {**kinds[kind],
                                 "ln1": params["ln1"][mixers]}, at, h)
    return _normed(s, params["lnf"], h)


def counters(shape: Shape, weights: list, first: int = 0) -> dict:
    """The routing's counters from every routed layer's router weights of a
    forward pass, positions ``first`` on: ``moe_held_pick_share``, the share
    of picks that land on the experts held here; and, where the
    configuration says how many sequences a step holds
    (``counters.sequences_a_step``), ``moe_rows_a_held_expert``, the rows a
    held expert gets a step on the mean at that batch, and
    ``moe_empty_group_share``, the share of (step, held expert) pairs
    without a row, from the pooled rate at which a pick lands on one held
    expert, ``(1 - held share / held)^(sequences x top_k)``: an
    extrapolation from these sequences."""
    s = shape
    picked = np.concatenate([np.asarray(w[:, first:] > 0).reshape(
        -1, s.n_router) for w in weights])
    lo, n = s.held
    out = {"moe_held_pick_share":
           float(picked[:, lo:lo + n].sum() / picked.sum())}
    if s.at_batch:
        rate = out["moe_held_pick_share"] / n
        out["moe_rows_a_held_expert"] = s.at_batch * s.top_k * rate
        out["moe_empty_group_share"] = float(
            (1 - rate) ** (s.at_batch * s.top_k))
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
    z = PositionLogits(shape, params["head"], forward(
        shape, params, sequences))[:, prompt_len - 1:-1]
    chosen = jnp.take_along_axis(
        z, jnp.asarray(sequences)[:, prompt_len:, None], axis=-1)[..., 0]
    return (z.max(axis=-1) - chosen) / z.std(axis=-1)
