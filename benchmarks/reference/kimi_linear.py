"""Plain decoder of Kimi-Linear-48B-A3B's language model (``model_type``
``kimi_linear``) as one chip of an expert-parallel pair holds it: layers of
several kinds, Kimi Delta Attention (a gated delta rule with a matrix state a
head) in most, NoPE latent attention in the rest, a leading dense MLP and
then a sigmoid router with a selection bias over 256 experts and one shared
expert, in float32 ``jax.numpy`` with nothing of the program in it.

No shard_map, no cache, no chunks, no absorption, no sort and no grouped
matmul: the delta rule is a ``lax.scan`` over positions, a head's keys and
values are multiplied out of the latent, and every expert *held here* is run
on every token under the top-k mask (a pick that falls to an expert the chip
does not hold adds nothing).  Matrix multiplications at
``jax.default_matmul_precision("highest")``, because a TPU runs a float32
product in bfloat16 passes unless told otherwise.  A layer at a time, an
expert at a time: the parameters arrive as the program stores them (bfloat16
on the chip), and each slice is upcast inside the call that reads it.  The
logits are multiplied out only for the positions a caller reads
(:class:`PositionLogits`).

The layers, from the published keys (what no key settles is listed under
``assumed`` in the configuration file).  Layer indices in
``linear_attn_config`` are 1-based: ``kda_layers`` 1, 2, 3, 5, ...;
``full_attn_layers`` 4, 8, ....  A block is pre-norm: ``h += mixer(RMSNorm(h;
ln1))``, ``h += mlp(RMSNorm(h; ln2))``, eps ``rms_norm_eps``; a last norm
``lnf``; an untied head.  The top-level ``head_dim`` 72 is read by neither
mixer.

**KDA** (``linear_attn_config``: ``num_heads`` H, ``head_dim`` K for keys and
values alike, ``short_conv_kernel_size`` taps), on the normed stream ``x``:
``q~ = x kda_q``, ``k~ = x kda_k``, ``v~ = x kda_v`` (no bias); each through
a causal depthwise convolution of ``taps`` taps (``kda_cq``, ``kda_ck``,
``kda_cv``; tap ``j`` weighs position ``t - taps + 1 + j``; no bias) and a
SiLU; q and k L2-normalised over each head's K (``x rsqrt(sum x^2 + 1e-6)``)
and q scaled by ``K^-1/2``.  Decay, a head and *a key channel*: ``g_t =
-exp(kda_a[h]) softplus((x kda_f1) kda_f2 + kda_dt)``, ``alpha_t = exp(g_t)``.
Write strength ``beta_t = sigmoid(x kda_b)``, one a head.  State ``S`` (K x
K, key x value) a head, zero before the first position:

    S <- diag(alpha_t) S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T;
    o_t = S^T q_t

Output: RMSNorm of ``o_t`` over each head's K with one scale of K
(``kda_n``), times ``sigmoid((x kda_g1) kda_g2)``, then ``kda_o``.

**Latent attention** (``kv_lora_rank`` R, ``qk_nope_head_dim`` N,
``qk_rope_head_dim`` P, ``v_head_dim`` W, ``num_attention_heads`` heads,
``q_lora_rank`` null, ``mla_use_nope`` true): ``q = x mla_q`` (heads x (N +
P)); ``[c, k_r] = x mla_kva`` (R + P); ``c <- RMSNorm(c; mla_n)``; ``[k_n,
v] = c mla_kvb`` (heads x (N + W), a head's N and then its W); a head's key
is ``[k_n, k_r]``, ``k_r`` shared by all heads and, being NoPE, *not
rotated*; causal softmax of ``q . k (N + P)^-1/2``; context over ``v``;
``wo``.

**MLP.**  The first ``first_k_dense_replace`` layers: ``dw2(silu(x dw1) * x
dw3)`` of width ``intermediate_size``.  After them (``moe_layer_freq`` 1):
``s = sigmoid(x wg)`` over all ``num_experts``; the
``num_experts_per_token`` largest of ``s + wgb`` (the selection bias; one
group, so no grouped top-k); their weights ``s`` at those, divided by their
sum (``moe_renormalize``) and times ``routed_scaling_factor``; an expert is
``w2(silu(x w1) * x w3)`` of width ``moe_intermediate_size``; this chip
holds experts ``experts_held.first .. first + count - 1`` and the picks that
fall to the others add nothing here (their chip adds them); plus
``num_shared_experts`` shared expert(s) ``sw2(silu(x sw1) * x sw3)`` on
every token, unweighted.  :func:`forward` with ``held`` given evaluates
another share; the shares of all ranks and the shared expert once are the
uncut layer.

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
# ``lib/program.init_params`` draws a leaf as normal x deviation or as ones,
# so every leaf below is centred on zero or is one: the decay's two leaves
# cannot be drawn as the model initialises them (A uniform in [1, 16], dt
# log-uniform through an inverse softplus).  Drawn so instead: ``kda_a`` at
# A_LOG, so exp(kda_a) spreads a factor of three about one; ``kda_dt`` at
# DT_BIAS and the low-rank gate's output at deviation F_GATE, so softplus's
# argument has deviation 2.7 and a key channel's decay a step runs from 0.002
# (a memory of hundreds of positions) through 0.7 at the median to 5 (gone in
# a position): fast and slow channels in every head, neither 1 nor 0 over
# tens of positions for a third of them, and a third of the argument's
# spread is the token's own.  ``kda_b`` at deviation B_GATE gives beta's
# logit a deviation of 1.5: beta spread about a half, from 0.1 to 0.9.
# q and k are L2-normed, so the projections' scales do not reach the state;
# the output is RMS-normed a head, so the state's size does not reach the
# stream either: what a mixer adds is set by KDA_OUT (0.5 of a gate's mean)
# and MLA_OUT.  The latent layer's queries are drawn at Q_SCALE so that its
# scores have deviation about 2 and a query weighs a few dozen of its 512
# positions (with unit-gain scores attention is a mean over positions and
# nothing of the cache shows: PR 35's lesson).
# The router: sigmoid scores of logits of deviation ROUTER_SPREAD; the
# selection bias at deviation BIAS moves about one of a token's eight picks
# (tests/benchmarks/test_kimi_linear.py measures it with numpy: 0.8 to 1.2).  A routed
# layer adds EXPERT_OUT-sized experts under weights of about 2.446 / 8 each,
# of which this chip holds half on the mean, and the shared expert at
# SHARED_OUT.
EMB = 1.0
A_LOG = 1.0
DT_BIAS = 2.5
F_GATE = 1.0
B_GATE = 1.5
O_GATE = 1.0
KDA_OUT = 0.5
MLA_OUT = 0.5
Q_SCALE = 2.0
DENSE_OUT = 0.5
EXPERT_OUT = 0.5
SHARED_OUT = 0.5
ROUTER_SPREAD = 1.0
BIAS = 0.015

L2_EPS = 1e-6
HEAD_BLOCK = 32_768     # rows of the head upcast at a time


@dataclasses.dataclass(frozen=True)
class Shape:
    vocab: int
    d_model: int
    n_layers: int
    eps: float
    kinds: tuple            # a layer's (mixer, mlp): "kda" | "mla", "dense" | "moe"
    kda_heads: int
    kda_dim: int            # K: a head's keys and values alike
    kda_rank: int           # of the two low-rank gates
    conv: int               # taps
    heads: int              # the latent layers' query heads
    nope: int
    rope: int               # the shared key part (not rotated)
    v_dim: int
    kv_rank: int
    d_ff: int               # the dense MLP's width
    d_expert: int
    n_experts: int          # the router's width
    top_k: int
    held: tuple             # (first, count): the experts on this chip
    n_shared: int
    scale: float            # routed_scaling_factor
    renorm: bool

    @classmethod
    def from_config(cls, config: dict) -> "Shape":
        """From a configuration file's published keys, and ``experts_held``,
        which says what the chip holds of ``num_experts``."""
        c, la = config, config["linear_attn_config"]
        L = c["num_hidden_layers"]
        kinds = []
        for layer in range(1, L + 1):
            if layer in la["kda_layers"]:
                mixer = "kda"
            elif layer in la["full_attn_layers"]:
                mixer = "mla"
            else:
                raise ValueError(f"layer {layer} is in neither list of "
                                 f"linear_attn_config")
            kinds.append((mixer, "dense" if layer <= c["first_k_dense_replace"]
                          else "moe"))
        if c["q_lora_rank"] is not None or not c["mla_use_nope"]:
            raise ValueError("written for q_lora_rank null and NoPE alone")
        if (c["moe_router_activation_func"] != "sigmoid"
                or c["num_expert_group"] != 1 or c["topk_group"] != 1
                or c["moe_layer_freq"] != 1):
            raise ValueError("written for a sigmoid router of one group, "
                             "every layer after the dense ones routed")
        held = c.get("experts_held", {"first": 0, "count": c["num_experts"]})
        return cls(vocab=c["vocab_size"], d_model=c["hidden_size"],
                   n_layers=L, eps=c["rms_norm_eps"], kinds=tuple(kinds),
                   kda_heads=la["num_heads"], kda_dim=la["head_dim"],
                   kda_rank=la["head_dim"],
                   conv=la["short_conv_kernel_size"],
                   heads=c["num_attention_heads"],
                   nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
                   v_dim=c["v_head_dim"], kv_rank=c["kv_lora_rank"],
                   d_ff=c["intermediate_size"],
                   d_expert=c["moe_intermediate_size"],
                   n_experts=c["num_experts"],
                   top_k=c["num_experts_per_token"],
                   held=(held["first"], held["count"]),
                   n_shared=c["num_shared_experts"],
                   scale=float(c["routed_scaling_factor"]),
                   renorm=bool(c["moe_renormalize"]))

    def count(self, kind: str) -> int:
        """Layers whose mixer or MLP is ``kind``."""
        return sum(kind in pair for pair in self.kinds)

    def index(self, layer: int, kind: str) -> int:
        """Layer ``layer``'s place in the stack of ``kind``'s leaves."""
        return sum(kind in pair for pair in self.kinds[:layer])


KDA_LEAVES = ("kda_q", "kda_k", "kda_v", "kda_cq", "kda_ck", "kda_cv",
              "kda_f1", "kda_f2", "kda_a", "kda_dt", "kda_b", "kda_g1",
              "kda_g2", "kda_n", "kda_o")
MLA_LEAVES = ("mla_q", "mla_kva", "mla_n", "mla_kvb", "wo")
DENSE_LEAVES = ("dw1", "dw3", "dw2")
ROUTER_LEAVES = ("wg", "wgb", "sw1", "sw3", "sw2")
EXPERT_LEAVES = ("w1", "w3", "w2")


def param_init(shape: Shape, serving: bool = False
               ) -> dict[str, tuple[tuple[int, ...], float | None]]:
    """Leaf name -> (shape, standard deviation of its normal initializer);
    ``None`` marks a leaf that starts at one (the norms' scales).  Each
    kind's leaves are stacked over that kind's layers, the held experts on
    the axis after it.  One draw, the constants above: no cell trains this
    configuration, so ``serving`` changes nothing."""
    s = shape
    L, D, V = s.n_layers, s.d_model, s.vocab
    Lk, Lm, Ld, Lr = (s.count(k) for k in ("kda", "mla", "dense", "moe"))
    HK, r = s.kda_heads * s.kda_dim, s.kda_rank
    q, kv = s.heads * (s.nope + s.rope), s.heads * (s.nope + s.v_dim)
    F, Fe, Fs = s.d_ff, s.d_expert, s.d_expert * s.n_shared
    E, held = s.n_experts, s.held[1]
    table = {
        "emb": ((V, D), EMB),
        "head": ((V, D), D ** -0.5),
        "ln1": ((L, D), None),
        "ln2": ((L, D), None),
        "lnf": ((D,), None),
    }
    if Lk:
        table.update({
            "kda_q": ((Lk, D, HK), D ** -0.5),
            "kda_k": ((Lk, D, HK), D ** -0.5),
            "kda_v": ((Lk, D, HK), D ** -0.5),
            "kda_cq": ((Lk, s.conv, HK), s.conv ** -0.5),
            "kda_ck": ((Lk, s.conv, HK), s.conv ** -0.5),
            "kda_cv": ((Lk, s.conv, HK), s.conv ** -0.5),
            "kda_f1": ((Lk, D, r), D ** -0.5),
            "kda_f2": ((Lk, r, HK), F_GATE * r ** -0.5),
            "kda_a": ((Lk, s.kda_heads), A_LOG),
            "kda_dt": ((Lk, HK), DT_BIAS),
            "kda_b": ((Lk, D, s.kda_heads), B_GATE * D ** -0.5),
            "kda_g1": ((Lk, D, r), D ** -0.5),
            "kda_g2": ((Lk, r, HK), O_GATE * r ** -0.5),
            "kda_n": ((Lk, s.kda_dim), None),
            "kda_o": ((Lk, HK, D), KDA_OUT * HK ** -0.5),
        })
    if Lm:
        table.update({
            "mla_q": ((Lm, D, q), Q_SCALE * D ** -0.5),
            "mla_kva": ((Lm, D, s.kv_rank + s.rope), D ** -0.5),
            "mla_n": ((Lm, s.kv_rank), None),
            "mla_kvb": ((Lm, s.kv_rank, kv), s.kv_rank ** -0.5),
            "wo": ((Lm, s.heads * s.v_dim, D),
                      MLA_OUT * (s.heads * s.v_dim) ** -0.5),
        })
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
            "w1": ((Lr, held, D, Fe), D ** -0.5),
            "w3": ((Lr, held, D, Fe), D ** -0.5),
            "w2": ((Lr, held, Fe, D), EXPERT_OUT * Fe ** -0.5),
            "sw1": ((Lr, D, Fs), D ** -0.5),
            "sw3": ((Lr, D, Fs), D ** -0.5),
            "sw2": ((Lr, Fs, D), SHARED_OUT * Fs ** -0.5),
        })
    return table


def state_bytes(shape: Shape) -> int:
    """What one sequence holds of fixed-size state over all KDA layers, as
    the program carries it (the configuration's ``deployment``): a head's K
    x K matrix in float32 (``kda_state_dtype``) and the three convolutions'
    last ``taps - 1`` inputs in bfloat16."""
    s = shape
    HK = s.kda_heads * s.kda_dim
    return s.count("kda") * (HK * s.kda_dim * 4 + (s.conv - 1) * 3 * HK * 2)


def counts(shape: Shape) -> dict:
    """What ``lib/costs.py`` counts of this family on this chip.

    ``active_params``: what one token multiplies *here*: a KDA layer's four
    projections, its two low-rank gates, beta's projection and the
    convolutions' taps; a latent layer's four matrices; the dense MLP's
    three; a routed layer's router (all ``n_experts`` columns), its shared
    expert and, of the ``top_k`` experts a token picks, the ``top_k x held /
    n_experts`` that fall to this chip on the mean (4 of 8); and the head.
    The embedding is a lookup table (``lookup_params``).
    ``attention_layers``: the latent layers alone attend;
    ``attention_width``: scores over ``nope + rope`` and a context over
    ``v_dim`` a head, so ``heads x (nope + rope + v_dim) / 2`` makes
    ``lib/costs.prefill_flops``'s ``4 x layers x width x T`` their count.
    The delta rule's own operations (the chunked form's, 3.1M a token and
    layer) are not a parameter's and are not counted, as the Mamba scan's
    are not: ``prefill_mfu`` understates.  ``kv_elements``: the latent and
    the shared key part of one position.  ``state_elements``:
    ``lib/costs.decode_step_bytes`` multiplies it by ``kv_cache_dtype``'s
    itemsize (bfloat16: 2) and has no second itemsize, so it is given as the
    state's *bytes* (:func:`state_bytes`: a float32 matrix state) over 2,
    which is about twice the state's elements.  A step reads the state and
    writes it; the count has it once.  ``routed``: the held experts (what
    the chip streams a step) and the picks that land here."""
    s = shape
    D, V = s.d_model, s.vocab
    HK, r = s.kda_heads * s.kda_dim, s.kda_rank
    kda = (4 * D * HK + 2 * (D * r + r * HK) + D * s.kda_heads
           + 3 * s.conv * HK)
    mla = (D * s.heads * (s.nope + s.rope) + D * (s.kv_rank + s.rope)
           + s.kv_rank * s.heads * (s.nope + s.v_dim)
           + s.heads * s.v_dim * D)
    expert = 3 * D * s.d_expert
    here = max(1, s.top_k * s.held[1] // s.n_experts)
    moe = D * s.n_experts + (here + s.n_shared) * expert
    block = (s.count("kda") * kda + s.count("mla") * mla
             + s.count("dense") * 3 * D * s.d_ff + s.count("moe") * moe)
    out = {"active_params": block + V * D,
           "projection_params": V * D,
           "lookup_params": V * D,
           "kv_elements": s.kv_rank + s.rope,
           "state_elements": state_bytes(s) // 2,
           "attention_layers": max(1, s.count("mla")),
           "attention_width": s.heads * (s.nope + s.rope + s.v_dim) // 2}
    if s.count("moe"):
        out["routed"] = {"layers": s.count("moe"), "experts": s.held[1],
                         "top_k": here, "d_model": D, "d_expert": s.d_expert}
    return out


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _conv(x, w):
    """Causal depthwise convolution: x (B, T, C), w (taps, C); tap ``j``
    weighs position ``t - taps + 1 + j``."""
    taps, T = w.shape[0], x.shape[1]
    window = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(window[:, j:j + T] * w[j] for j in range(taps))


def kda_inputs(shape: Shape, p: dict, x):
    """The delta rule's inputs of the normed stream x (B, T, D): q, k, v
    (B, T, H, K), the log decay g (B, T, H, K), beta (B, T, H)."""
    s = shape
    B, T, _ = x.shape
    H, K = s.kda_heads, s.kda_dim

    def branch(w, c):
        return jax.nn.silu(_conv(x @ p[w], p[c])).reshape(B, T, H, K)

    def unit(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True)
                                 + L2_EPS)

    q = unit(branch("kda_q", "kda_cq")) * K ** -0.5
    k = unit(branch("kda_k", "kda_ck"))
    v = branch("kda_v", "kda_cv")
    g = -jnp.exp(p["kda_a"])[:, None] * jax.nn.softplus(
        ((x @ p["kda_f1"]) @ p["kda_f2"] + p["kda_dt"]).reshape(B, T, H, K))
    beta = jax.nn.sigmoid(x @ p["kda_b"])
    return q, k, v, g, beta


def delta_rule(q, k, v, g, beta):
    """The recurrence, a position at a time from a zero state: o (B, T, H,
    K) and the last state (B, H, K, K)."""
    B, T, H, K = q.shape

    def step(S, at):
        q_t, k_t, v_t, g_t, b_t = at
        S = S * jnp.exp(g_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    S, o = jax.lax.scan(step, jnp.zeros((B, H, K, K), jnp.float32),
                        tuple(jnp.moveaxis(y, 1, 0)
                              for y in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


def _kda(shape: Shape, p: dict, x):
    B, T, _ = x.shape
    o, _state = delta_rule(*kda_inputs(shape, p, x))
    gate = jax.nn.sigmoid((x @ p["kda_g1"]) @ p["kda_g2"])
    o = _rmsnorm(o, p["kda_n"], shape.eps).reshape(B, T, -1) * gate
    return o @ p["kda_o"]


def _mla(shape: Shape, p: dict, x):
    s = shape
    B, T, _ = x.shape
    H, N, P, W, R = s.heads, s.nope, s.rope, s.v_dim, s.kv_rank
    q = (x @ p["mla_q"]).reshape(B, T, H, N + P)
    kva = x @ p["mla_kva"]
    c = _rmsnorm(kva[..., :R], p["mla_n"], s.eps)
    k_r = kva[..., R:]                                  # NoPE: not rotated
    kv = (c @ p["mla_kvb"]).reshape(B, T, H, N + W)
    k = jnp.concatenate([kv[..., :N], jnp.broadcast_to(
        k_r[:, :, None, :], (B, T, H, P))], axis=-1)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (N + P) ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, kv[..., N:])
    return o.reshape(B, T, H * W) @ p["wo"]


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


@functools.partial(jax.jit, static_argnums=(0, 1, 3, 4))
def _mixer_layer(shape, kind, stacks, at, layer, h):
    """The mixer half of block ``layer``; ``stacks`` this kind's leaves as
    stored and ``ln1``, ``at`` the layer's place in the kind's stacks."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v[layer if k == "ln1" else at], jnp.float32)
             for k, v in stacks.items()}
        x = _rmsnorm(h, p["ln1"], shape.eps)
        return h + (_kda if kind == "kda" else _mla)(shape, p, x)


@functools.partial(jax.jit, static_argnums=(0, 2, 3, 5, 6))
def _moe_layer(shape, stacks, at, layer, h, held=None, shared=True):
    """The routed half of block ``layer``; the experts read out of their
    stacks one at a time.  ``held`` (first, count): the share evaluated, of
    experts stacked from ``first`` on; the chip's own by default."""
    first, count = held or shape.held
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(stacks[k][at], jnp.float32)
             for k in ROUTER_LEAVES}
        x = _rmsnorm(h, jnp.asarray(stacks["ln2"][layer], jnp.float32),
                     shape.eps)
        weight = route(shape, p, x)

        def one(e, total):
            gate, up, down = (jnp.asarray(stacks[k][at, e], jnp.float32)
                              for k in EXPERT_LEAVES)
            w = jax.lax.dynamic_index_in_dim(weight, first + e, axis=-1)
            return total + w * _gated(x, gate, up, down)

        out = jax.lax.fori_loop(0, count, one, jnp.zeros_like(x))
        if shared and shape.n_shared:
            out = out + _gated(x, p["sw1"], p["sw3"], p["sw2"])
        return h + out


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _dense_layer(shape, stacks, at, layer, h):
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(stacks[k][at], jnp.float32)
             for k in DENSE_LEAVES}
        x = _rmsnorm(h, jnp.asarray(stacks["ln2"][layer], jnp.float32),
                     shape.eps)
        return h + _gated(x, p["dw1"], p["dw3"], p["dw2"])


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
    for layer, (mixer, mlp) in enumerate(s.kinds):
        names = KDA_LEAVES if mixer == "kda" else MLA_LEAVES
        h = _mixer_layer(s, mixer, {k: params[k] for k in (*names, "ln1")},
                         s.index(layer, mixer), layer, h)
        if mlp == "dense":
            h = _dense_layer(s, {k: params[k] for k in (*DENSE_LEAVES, "ln2")},
                             s.index(layer, "dense"), layer, h)
        else:
            h = _moe_layer(s, {k: params[k] for k in (
                *ROUTER_LEAVES, *EXPERT_LEAVES, "ln2")},
                s.index(layer, "moe"), layer, h)
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
