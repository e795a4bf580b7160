"""Plain decoder of Phi-4-mini-flash-reasoning (``phi4flash``), a
decoder-hybrid-decoder: a lower half of Mamba-1 and sliding-window layers, one
full-attention layer whose K and V every later attending layer reads, and an
upper half that keeps no state of its own (gated memory units on one Mamba
layer's scan output, cross attention on that one layer's K and V), in float32
``jax.numpy`` with nothing of the program in it.

No shard_map, no cache, no ring, no kernel, no skipped position: the selective
recurrence runs a position at a time from a zero state, every layer runs on
every position, and a sequence's scores are taken under explicit masks, a block
of queries at a time so that 16,384 positions fit.  Matrix multiplications at
``jax.default_matmul_precision("highest")``, because a TPU runs a float32
product in bfloat16 passes unless told otherwise.  A sequence at a time and a
layer at a time: the parameters arrive as the program stores them (bfloat16 on
the chip, 7.7 GB resident) and each layer's slice is upcast inside the call
that reads it.  The logits are multiplied out only for the positions a caller
reads (:class:`PositionLogits`).

The layers (what no key of the published file settles is listed under
``assumed`` in the configuration file).  ``LN(x; g, b)`` is LayerNorm: the
mean removed, over the root of the variance plus ``layer_norm_eps``, times a
scale ``g`` plus a bias ``b``.  ``L = num_hidden_layers`` (32), ``D =
hidden_size`` (2560).  The stream starts as ``h = emb[ids]``.  Every layer
``l``:

    h <- h + mixer_l(LN(h; ln1_l, ln1b_l))
    [g, u] = LN(h; ln2_l, ln2b_l) [w1_l, w3_l];   h <- h + (silu(g) * u) w2_l

(the gate first; width ``intermediate_size``, no bias), after the last layer
``LN(h; lnf, lnfb)`` and the **tied** head ``logits = h emb^T``, no bias.  No
rotary embedding anywhere: the Mamba layers carry order.  Which mixer: with
``mb_per_layer`` 2 a layer of even ``l`` is Mamba's and one of odd ``l``
attends; layers ``l < L/2`` are the lower half (Mamba-1 and window), ``L/2``
(16) a Mamba-1 layer that hands its scan output ``m`` on, ``L/2 + 1`` (17)
full attention that hands its K and V on, and above them even ``l`` is a
gated memory unit and odd ``l`` cross attention.

**Mamba-1** (``sel_*`` leaves; inner width ``Di = 2 D``, state ``N`` 16, rank
``R = ceil(D / 16)``, ``d_conv`` 4 taps):

    [x, z] = u sel_in                         (D -> 2 Di, x first)
    x <- silu(sel_convb + causal depthwise convolution of x, tap k reading
              the input d_conv - 1 - k back)
    [r, B_t, C_t] = x sel_x                   (Di -> R + N + N)
    dt = softplus(r sel_dt + sel_dtb)         (R -> Di)
    A = -exp(sel_alog)                        (stored (N, Di))
    S_t = exp(dt_t A) * S_{t-1} + (dt_t x_t) (x) B_t     (Di x N, float32)
    y_t = S_t C_t + sel_d * x_t
    out = (y * silu(z)) sel_out

Layer ``L/2`` also hands ``m_t = y_t``, **before the gate** and with the ``D
x`` skip in it, to the gated memory units.

**Differential attention**, window (``w*``: ``wqkv``, ``wo``, ...; ``l`` odd under ``L/2``), full
(``a*``, ``L/2 + 1``) and cross (``x*``): ``[q, k, v] = u qkv + qkvb``
(``H`` query heads and ``K`` K/V heads of ``hd``; a cross layer projects ``q =
u xq + xqb`` alone and reads layer ``L/2 + 1``'s k and v).  Query heads
``2i`` and ``2i + 1`` are pair ``i``'s ``(q1, q2)``; K heads ``2j, 2j + 1``
are ``(k1, k2)`` and V heads ``2j, 2j + 1`` side by side one value ``V_j`` of
width ``2 hd``; pair ``i`` reads ``j = i // (H / K)``.

    a1 = softmax(q1 k1^T / sqrt(hd)) V_j,  a2 = softmax(q2 k2^T / sqrt(hd)) V_j

causal, and in a window layer over the keys ``s`` with ``t - s <
sliding_window`` (the query's own counted);

    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0_l,
    lam0_l = 0.8 - 0.6 exp(-0.3 l)            (the four rows of ``*lam``)
    o_i = (1 - lam0_l) RMSNorm_{2 hd}(a1 - lam a2) * sub   (eps 1e-5)
    out = [o_0 .. o_{H/2 - 1}] o + ob

**Gated memory unit** (``gmu_*``): ``out = (silu(u gmu_in) * m_t) gmu_out``,
``m_t`` layer ``L/2``'s at the same position, no bias.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

# The serving draw (no cell trains this configuration, so ``serving`` changes
# nothing), settled on the chip (PERF.md section 6, PR 77, has every reading).
# ``lib/program.init_params`` draws a leaf as normal x deviation or as ones.
# Every branch at about unit size: the MLP (silu(a) b of unit a and b has rms
# 0.6: MLP_OUT 1.7), the Mamba branch (its scan output y reads about 5.6, the
# slow states' sums: SSM_OUT 0.3 on y * silu(z)), the gated memory unit alike
# (GMU_OUT 0.3), the attention branch at ATTN_OUT on a sub-normed (unit)
# context times (1 - lam0), 0.65 down to 0.22.  Scores q k^T / 8 of unit
# deviation.  dt = softplus(r sel_dt + 1) about 1.3 under A = -exp(normal x
# A_LOG): a third of the (channel, state) pairs forget in a step and a tenth
# remember twenty.  The embedding at EMB 8, the size of the 64 branches'
# sum: the sound program's error against the float32 reference is the
# rounding of a 64-branch bfloat16 program, 0.041 to 0.047 at EMB 0.25 to 1
# whatever the branches' sizes (where a continuation is not one token
# repeated: the tied head's lead of a token's own logit is sqrt(D) EMB /
# rms(h), under two deviations at 0.25), 0.029 at 2, 0.021 at 4, 0.017 at 8
# and 0.015 at 12, where what is left is the stream's own 64 roundings; the
# benchmark's held tests allow a limit under 0.02
# (tests/benchmarks/controls_cases.py: a shift of a tenth must read five
# limits), so the stream is carried in float32 (residual_in_fp32) and the
# embedding leads it, as granite-4.0-h-small's does: every continuation is
# then one token repeated (repeat_share 1.0, which `correct` does not ask
# where logits are compared), and what a checked position sees is the same
# token at a growing state and cache (PERF.md section 7).
EMB = 8.0
BIAS = 0.1
CONV = 0.5
A_LOG = 2.0
DT = 0.5
SSM_OUT = 0.3
ATTN_OUT = 1.0
LAMBDA = 0.1
GMU_OUT = 0.3
MLP_OUT = 1.7

HEAD_BLOCK = 32_768     # rows of the head upcast at a time
QUERY_BLOCK = 512       # queries whose scores are held at a time
MLP_BLOCK = 4096        # positions an MLP's hidden rows are held for

SUB_EPS = 1e-5


@dataclasses.dataclass(frozen=True)
class Shape:
    vocab: int
    d_model: int
    n_layers: int
    heads: int
    kv_heads: int
    d_ff: int
    window: int
    mb_per_layer: int
    eps: float
    d_state: int
    d_conv: int
    expand: int
    state_itemsize: int     # bytes an element of the carried scan state
    emb: float = 8.0        # the embedding's deviation (``EMB``; a file's
                            # ``embedding_deviation``: its tiny sizes' 0.25)

    @classmethod
    def from_config(cls, config: dict) -> "Shape":
        c = config
        if (c["hidden_act"] != "silu" or not c["tie_word_embeddings"]
                or c["mlp_bias"] or c["lm_head_bias"]
                or c["mb_per_layer"] != 2 or c["num_hidden_layers"] % 4
                or c["num_hidden_layers"] < 12
                or c["num_attention_heads"] % c["num_key_value_heads"]
                or c["num_key_value_heads"] % 2):
            raise ValueError("written for silu, a tied head without a bias, "
                             "an MLP without one, a Mamba layer every second "
                             "layer, a depth of whole fours and paired heads")
        return cls(
            vocab=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"], heads=c["num_attention_heads"],
            kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
            window=c["sliding_window"], mb_per_layer=c["mb_per_layer"],
            eps=c["layer_norm_eps"], d_state=c["mamba_d_state"],
            d_conv=c["mamba_d_conv"], expand=c["mamba_expand"],
            state_itemsize=jnp.dtype(c["ssm_state_dtype"]).itemsize,
            emb=float(c.get("embedding_deviation", EMB)))

    @property
    def head_dim(self) -> int:
        return self.d_model // self.heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return -(-self.d_model // 16)

    @property
    def kinds(self) -> tuple:
        """A layer's mixer: "mamba", "window", "full", "gmu" or "cross"."""
        half = self.n_layers // 2
        lower = ("mamba", "window")
        upper = ("gmu", "cross")
        return tuple(lower[l % 2] if l < half else "mamba" if l == half
                     else "full" if l == half + 1 else upper[l % 2]
                     for l in range(self.n_layers))

    def count(self, kind: str) -> int:
        return self.kinds.count(kind)


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * float(np.exp(-0.3 * layer))


MAMBA_LEAVES = ("sel_in", "sel_conv", "sel_convb", "sel_x", "sel_dt",
                "sel_dtb", "sel_alog", "sel_d", "sel_out")
SELF_LEAVES = ("qkv", "qkvb", "o", "ob", "lam", "sub")
CROSS_LEAVES = ("q", "qb", "o", "ob", "lam", "sub")
GMU_LEAVES = ("gmu_in", "gmu_out")
MLP_LEAVES = ("w1", "w3", "w2")
PREFIX = {"window": "w", "full": "a", "cross": "x"}


def param_init(shape: Shape, serving: bool = False
               ) -> dict[str, tuple[tuple[int, ...], float | None]]:
    """Leaf name -> (shape, standard deviation of its normal initializer);
    ``None`` marks a leaf that starts at one (the norms' scales, ``sel_dtb``
    and ``sel_d``).  The leaves are stacked by kind, as the program stacks
    them; the norms' biases are drawn, so that a norm that loses its bias or
    keeps its mean reads otherwise."""
    s = shape
    D, V, F, L = s.d_model, s.vocab, s.d_ff, s.n_layers
    Di, N, R, hd = s.d_inner, s.d_state, s.dt_rank, s.head_dim
    q, kv = s.heads * hd, s.kv_heads * hd
    M, G = s.count("mamba"), s.count("gmu")
    table = {
        "emb": ((V, D), s.emb),
        "ln1": ((L, D), None), "ln1b": ((L, D), BIAS),
        "ln2": ((L, D), None), "ln2b": ((L, D), BIAS),
        "lnf": ((D,), None), "lnfb": ((1, D), BIAS),
        "w1": ((L, D, F), D ** -0.5), "w3": ((L, D, F), D ** -0.5),
        "w2": ((L, F, D), MLP_OUT * F ** -0.5),
        "sel_in": ((M, D, 2 * Di), D ** -0.5),
        "sel_conv": ((M, s.d_conv, Di), CONV),
        "sel_convb": ((M, Di), BIAS),
        "sel_x": ((M, Di, R + 2 * N), Di ** -0.5),
        "sel_dt": ((M, R, Di), DT * R ** -0.5),
        "sel_dtb": ((M, Di), None),
        "sel_alog": ((M, N, Di), A_LOG),
        "sel_d": ((M, Di), None),
        "sel_out": ((M, Di, D), SSM_OUT * Di ** -0.5),
        "gmu_in": ((G, D, Di), D ** -0.5),
        "gmu_out": ((G, Di, D), GMU_OUT * Di ** -0.5),
    }
    for kind, prefix in PREFIX.items():
        n = s.count(kind)
        if kind == "cross":
            table.update({prefix + "q": ((n, D, q), D ** -0.5),
                          prefix + "qb": ((n, q), BIAS)})
        else:
            # q, k and v at unit gain: one deviation a fused leaf
            table.update({prefix + "qkv": ((n, D, q + 2 * kv), D ** -0.5),
                          prefix + "qkvb": ((n, q + 2 * kv), BIAS)})
        table.update({prefix + "o": ((n, q, D), ATTN_OUT * q ** -0.5),
                      prefix + "ob": ((n, D), BIAS),
                      prefix + "lam": ((n, 4, hd), LAMBDA),
                      prefix + "sub": ((n, 2 * hd), None)})
    return table


def param_counts(shape: Shape) -> dict:
    """Parameters by kind, one layer's each: what the published card's 3.8B is
    made of."""
    table = param_init(shape)

    def one(names):
        return sum(int(np.prod(table[n][0][1:])) for n in names)

    return {"embedding": int(np.prod(table["emb"][0])),
            "mlp": one(MLP_LEAVES), "mamba": one(MAMBA_LEAVES),
            "self_attention": one(PREFIX["window"] + n for n in SELF_LEAVES),
            "cross_attention": one(PREFIX["cross"] + n for n in CROSS_LEAVES),
            "gmu": one(GMU_LEAVES),
            "norm": 2 * shape.d_model}


def state_bytes(shape: Shape) -> int:
    """What one sequence holds of fixed-size state over all layers, in bytes:
    a Mamba layer's scan state at ``ssm_state_dtype`` and its convolution's
    last inputs in bfloat16, a window layer's ring of K and V in bfloat16."""
    s = shape
    return (s.count("mamba") * (s.d_inner * s.d_state * s.state_itemsize
                                + (s.d_conv - 1) * s.d_inner * 2)
            + s.count("window") * s.window * 2 * s.kv_heads * s.head_dim * 2)


def attention_flops_a_token(shape: Shape, prompt_len: int) -> float:
    """A prompt's attention products a token, on the mean: the one full
    layer's causal pairs (scores ``hd`` wide and values ``2 hd``, all query
    heads) and each window layer's ``window`` keys a query."""
    s = shape
    a_pair = 2 * s.heads * (s.head_dim + 2 * s.head_dim)
    return a_pair * ((prompt_len + 1) / 2
                     + s.count("window") * min(s.window, prompt_len))


def counts(shape: Shape, prompt_len: int = 16_128) -> dict:
    """What ``lib/costs.py`` counts of this model.

    ``lib/costs`` has one ``attention_layers`` for two uses.  A cached step
    must read layer ``L/2 + 1``'s K and V once for that layer and once for
    every cross layer (each cross layer's queries wait for the layer under
    it): ``attention_layers`` is that many reads, ``kv_elements`` one
    position's K and V.  ``prefill_flops`` then multiplies the same number by
    ``4 x attention_width x prompt_len`` a token, so ``attention_width`` is
    the width at which that product is what a prompt's attention needs at
    ``prompt_len`` (:func:`attention_flops_a_token`: the full layer's causal
    pairs and the windows'), rounded down: it must not overcount.
    ``active_params``: what a prompt's token multiplies: the rows up to the
    full layer (the upper rows run on a prompt's last position alone) and the
    head, which is the embedding (``projection_params``; read whole by every
    step, so ``lookup_params`` is 0).  The scan's own operations are no
    parameter's and are not counted: ``prefill_mfu`` understates.
    ``state_elements``: ``lib/costs.decode_step_bytes`` multiplies it by
    ``kv_cache_dtype``'s itemsize (2), so it is :func:`state_bytes` over 2, as
    ``kimi_linear.py``'s; a step reads the scan states and writes them, and
    the count has them once."""
    s = shape
    per = param_counts(s)
    half = s.n_layers // 2
    lower = (half // 2 + 1) * per["mamba"] + (half // 2 + 1) * per[
        "self_attention"] + (half + 2) * per["mlp"]
    reads = 1 + s.count("cross")
    width = int(attention_flops_a_token(s, prompt_len)
                // (4 * reads * prompt_len))
    return {"active_params": lower + per["embedding"],
            "projection_params": per["embedding"],
            "lookup_params": 0,
            "kv_elements": 2 * s.kv_heads * s.head_dim,
            "state_elements": state_bytes(s) // 2,
            "attention_layers": reads,
            "attention_width": width}


def selective_scan(shape: Shape) -> dict:
    """The Mamba layers' shape, for the reader of the scan's roofline
    (``metrics/selective_scan_roofline.py``)."""
    s = shape
    return {"layers": s.count("mamba"), "d_inner": s.d_inner,
            "d_state": s.d_state}


def shared_kv(shape: Shape) -> dict:
    """The shared cache's shape, for the reader of its read's roofline
    (``metrics/shared_kv_read_roofline.py``): the layers that read another
    layer's K and V and one position's elements of both."""
    s = shape
    return {"readers": s.count("cross"),
            "kv_elements": 2 * s.kv_heads * s.head_dim}


def layernorm(x, scale, bias, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale + bias


def recurrence(x, dt, a, b, c):
    """The selective recurrence, a position at a time from a zero state.  x,
    dt: (T, Di); a: (N, Di); b, c: (T, N).  Returns ``y`` (T, Di) with ``y_t =
    S_t c_t`` and the last state (N, Di)."""

    def step(S, at):
        x_t, dt_t, b_t, c_t = at
        S = jnp.exp(dt_t * a) * S + (dt_t * x_t) * b_t[:, None]
        return S, jnp.sum(S * c_t[:, None], axis=0)

    last, ys = jax.lax.scan(step, jnp.zeros(a.shape, jnp.float32),
                            (x, dt, b, c))
    return ys, last


def mamba(shape: Shape, p: dict, u):
    """A Mamba-1 layer's mixer on the normed stream ``u`` (T, D): ``(out,
    m)``, ``m`` the scan's output before the gate."""
    s = shape
    T, N, R = u.shape[0], s.d_state, s.dt_rank
    x, z = jnp.split(u @ p["sel_in"], 2, axis=-1)
    padded = jnp.pad(x, ((s.d_conv - 1, 0), (0, 0)))
    x = jax.nn.silu(p["sel_convb"] + sum(
        padded[k:k + T] * p["sel_conv"][k] for k in range(s.d_conv)))
    r, b, c = jnp.split(x @ p["sel_x"], [R, R + N], axis=-1)
    dt = jax.nn.softplus(r @ p["sel_dt"] + p["sel_dtb"])
    y, _last = recurrence(x, dt, -jnp.exp(p["sel_alog"]), b, c)
    y = y + p["sel_d"] * x
    return (y * jax.nn.silu(z)) @ p["sel_out"], y


def differential(shape: Shape, p: dict, lam0, q, k, v, window: int):
    """Differential attention of q (T, H, hd) over k, v (T, K, hd) of the
    same positions, causal and (``window`` > 0) over the last ``window`` keys
    a query, the query's own counted; ``lam0`` the layer's ``lam0_l``; before
    the output projection: (T, H hd)."""
    T, H, hd = q.shape
    K = k.shape[1]
    lq1, lk1, lq2, lk2 = p["lam"]
    lam = jnp.exp(lq1 @ lk1) - jnp.exp(lq2 @ lk2) + lam0
    # query head h = 2i + c reads K head 2 (i // (H / K)) + c and value i //
    # (H / K): the K/V pair of its own pair
    pair = jnp.arange(H) // 2 // (H // K)
    k_of = k[:, 2 * pair + jnp.arange(H) % 2]                  # (T, H, hd)
    v_of = v.reshape(T, K // 2, 2 * hd)[:, pair]               # (T, H, 2 hd)
    block = min(QUERY_BLOCK, T)
    blocks = -(-T // block)
    at = jnp.arange(T)

    def some(args):
        q_rows, rows = args
        sc = jnp.einsum("qhd,khd->hqk", q_rows, k_of) / hd ** 0.5
        seen = at[None, :] <= rows[:, None]
        if window:
            seen &= rows[:, None] - at[None, :] < window
        w = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khe->qhe", w, v_of)

    padded = jnp.pad(q, ((0, blocks * block - T), (0, 0), (0, 0)))
    a = jax.lax.map(some, (padded.reshape(blocks, block, H, hd),
                           jnp.arange(blocks * block).reshape(blocks, block)))
    a = a.reshape(blocks * block, H // 2, 2, 2 * hd)[:T]
    o = a[:, :, 0] - lam * a[:, :, 1]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + SUB_EPS)
    return ((1 - lam0) * o * p["sub"]).reshape(T, H * hd)


def self_attention(shape: Shape, p: dict, lam0, u, window: int):
    """A window or the full layer's mixer on ``u`` (T, D): ``(out, k, v)``."""
    s = shape
    T, hd = u.shape[0], s.head_dim
    q, k, v = jnp.split(u @ p["qkv"] + p["qkvb"],
                        [s.heads * hd, (s.heads + s.kv_heads) * hd], axis=-1)
    k, v = k.reshape(T, s.kv_heads, hd), v.reshape(T, s.kv_heads, hd)
    o = differential(s, p, lam0, q.reshape(T, s.heads, hd), k, v, window)
    return o @ p["o"] + p["ob"], k, v


def cross_attention(shape: Shape, p: dict, lam0, u, k, v):
    """A cross layer's mixer on ``u`` against another layer's k and v."""
    s = shape
    q = (u @ p["q"] + p["qb"]).reshape(u.shape[0], s.heads, s.head_dim)
    return differential(s, p, lam0, q, k, v, 0) @ p["o"] + p["ob"]


def gmu(p: dict, u, m):
    return (jax.nn.silu(u @ p["gmu_in"]) * m) @ p["gmu_out"]


def _upcast(stacks: dict, at, strip: str = "") -> dict:
    return {k.removeprefix(strip): jnp.asarray(jax.lax.dynamic_index_in_dim(
        v, at, keepdims=False), jnp.float32) for k, v in stacks.items()}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _mixer_layer(shape, kind, stacks, at, lam0, norm, h, shared):
    """``(h + mixer(LN(h)), what the layer hands on)`` of one sequence ``h``
    (T, D): ``stacks`` its kind's leaves as stored, ``at`` its place among
    them, ``lam0`` its ``lam0_l``, ``norm`` its ln1 and ln1b, ``shared`` what
    it reads of another layer (``m``, or ``(k, v)``)."""
    with jax.default_matmul_precision("highest"):
        p = _upcast(stacks, at, PREFIX.get(kind, ""))
        u = layernorm(h, *(jnp.asarray(n, jnp.float32) for n in norm),
                      shape.eps)
        if kind == "mamba":
            out, handed = mamba(shape, p, u)
        elif kind == "gmu":
            out, handed = gmu(p, u, shared), None
        elif kind == "cross":
            out, handed = cross_attention(shape, p, lam0, u, *shared), None
        else:
            out, *handed = self_attention(
                shape, p, lam0, u, shape.window if kind == "window" else 0)
        return h + out, handed


@functools.partial(jax.jit, static_argnums=(0,))
def _mlp_layer(shape, stacks, layer, norm, h):
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(jax.lax.dynamic_index_in_dim(
            v, layer, keepdims=False), jnp.float32) for k, v in stacks.items()}
        scale, bias = (jnp.asarray(n, jnp.float32) for n in norm)
        out = []
        for lo in range(0, h.shape[0], MLP_BLOCK):
            u = layernorm(h[lo:lo + MLP_BLOCK], scale, bias, shape.eps)
            out.append((jax.nn.silu(u @ p["w1"]) * (u @ p["w3"])) @ p["w2"])
        return h + jnp.concatenate(out)


@functools.partial(jax.jit, static_argnums=0)
def _final_norm(shape, scale, bias, h):
    return layernorm(h, jnp.asarray(scale, jnp.float32),
                     jnp.asarray(bias, jnp.float32), shape.eps)


@functools.partial(jax.jit, static_argnums=0)
def _project(shape: Shape, rows, h):
    """``h`` already normed, onto a block of the tied head's rows."""
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


def _kind_stacks(shape: Shape, params: dict) -> dict:
    names = {"mamba": MAMBA_LEAVES, "gmu": GMU_LEAVES,
             **{kind: tuple(prefix + n for n in (
                 CROSS_LEAVES if kind == "cross" else SELF_LEAVES))
                for kind, prefix in PREFIX.items()}}
    return {kind: {n: params[n] for n in leaves}
            for kind, leaves in names.items()}


def forward_one(shape: Shape, params: dict, tokens):
    """(T,) int32 tokens of one sequence -> the last norm's output (T, D)
    float32: every layer on every position."""
    s = shape
    kinds, stacks = s.kinds, _kind_stacks(s, params)
    mlp = {k: params[k] for k in MLP_LEAVES}
    h = jnp.asarray(params["emb"][tokens], jnp.float32)
    m = kv = None
    for layer, kind in enumerate(kinds):
        shared = m if kind == "gmu" else kv if kind == "cross" else None
        h, handed = _mixer_layer(
            s, kind, stacks[kind], kinds[:layer].count(kind),
            lambda_init(layer), (params["ln1"][layer], params["ln1b"][layer]),
            h, shared)
        if kind == "mamba" and layer == s.n_layers // 2:
            m = handed
        elif kind == "full":
            kv = tuple(handed)
        h = _mlp_layer(s, mlp, layer,
                       (params["ln2"][layer], params["ln2b"][layer]), h)
    return _final_norm(s, params["lnf"], params["lnfb"][0], h)


def forward(shape: Shape, params: dict, tokens):
    """(B, T) int32 tokens -> the last norm's output (B, T, D) float32, a
    sequence at a time."""
    return jnp.stack([forward_one(shape, params, row)
                      for row in jnp.asarray(tokens)])


def logits(shape: Shape, params: dict, tokens) -> PositionLogits:
    """(B, T) int32 tokens -> (B, T, V) float32 logits, projected where they
    are read."""
    return PositionLogits(shape, params["emb"], forward(shape, params, tokens))


def nll_sum(shape: Shape, params: dict, tokens):
    """Summed next-token negative log-likelihood over (B, T) tokens: position
    t predicts token t + 1, and the last position predicts nothing."""
    z = logits(shape, params, tokens)
    logp = jax.nn.log_softmax(z[:, :-1], -1)
    picked = jnp.take_along_axis(logp, jnp.asarray(tokens)[:, 1:, None],
                                 axis=-1)
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
