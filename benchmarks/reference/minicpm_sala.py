"""Plain decoder of MiniCPM-SALA (``model_type`` ``minicpm_sala``): a layer
is block-selected grouped-query attention (``minicpm4``: InfLLM-v2) or
Lightning linear attention (``lightning-attn``) as ``mixer_types`` says, over
a SiLU-gated MLP, with the family's three muP constants, in float32
``jax.numpy`` with nothing of the program in it.

No shard_map, no cache, no state carried from call to call, no chunks, no
kernels, no bisection: the linear layer is its recurrence a position at a
time (``lax.scan``), the selection is ``lax.top_k`` on float32 block scores,
and attention is dense under the selection's mask, a block of
``QUERY_BLOCK`` queries at a time against every key so that two sequences of
16,384 positions fit beside the program's parameters (a block's scores are
0.27 GB a sequence pair and 32 heads).  Matrix multiplications at
``jax.default_matmul_precision("highest")``, because a TPU runs a float32
product in bfloat16 passes unless told otherwise.  A layer at a time: the
parameters arrive as the program stores them (bfloat16 on the chip), so each
layer's slice is upcast inside that layer's call and dropped after it, and
the logits are multiplied out only for the positions a caller reads
(:class:`PositionLogits`: 2 x 16,384 x 73,448 float32 would be 9.6 GB).

The model.  With ``r = scale_depth / sqrt(depth)``, ``depth`` the published
number of layers (the length of ``mixer_types``: a constant of the model,
not of a cut), a layer is ``h <- h + r mixer(RMSNorm(h; ln1))``, ``h <- h +
r W2 (silu(W1 x) * W3 x)`` with ``x = RMSNorm(h; ln2)``; the model is ``h_0 =
scale_emb emb[token]``, the layers, ``logits = head (RMSNorm(h; lnf) /
(hidden_size / dim_model_base))``; the head is not the embedding.

*The lightning mixer*, head ``a`` of ``lightning_nh``, each ``K =
lightning_head_dim`` wide, ``x`` the normed input, ``l`` the layer's number
in the published model:

    q = x lt_q,  k = x lt_k,  v = x lt_v
    q, k: RMSNorm a head (scales lt_qn, lt_kn; qk_norm), then the rotary
          embedding over the whole head, split-half, theta rope_theta
    S_t = lam_a S_{t-1} + k_t v_t^T,      y_t = S_t^T q_t / sqrt(K)
    lam_a = exp(-s_a f_l),  s_a = 2^(-8 (a + 1) / heads),  f_l = 1 - l / (depth - 1) + 1e-5
    out = (RMSNorm(y_t; lt_on) a head * sigmoid(x lt_z)) lt_o

*The block-selected mixer*, query head ``a`` in group ``g = a // r_g`` over
K/V head ``g``, no rotary embedding, the query at position ``t``; the sizes
are the configuration file's ``sparse_config``:

    q = x wq,  k = x wk,  v = x wv
    c_j    = mean(k_{stride j} .. k_{stride j + kernel - 1})   every j with stride j + kernel - 1 <= t
    p_a    = softmax_j(q_{t,a} . c_j / sqrt(hd))               over those j
    R_g(b) = max over the kernels j that overlap block b (and are complete)
             of sum_{a in g} p_a(j);  0 where none is
    B_t    = the first init_blocks blocks, the blocks of positions
             t - window + 1 .. t, and the blocks of largest R_g that start at
             or before t, ties to the lower block, until topk in all
    y_{t,a} = softmax_{s <= t, s in a block of B_t}(q_{t,a} . k_s / sqrt(hd)) v_s
    out = (y_t * sigmoid(x wz)) wo

A sequence of at most ``dense_len`` positions attends to every earlier
position.  What no key of the published configuration settles is listed
under ``assumed`` in the configuration file.

The tree has the program's leaf names, because the reference is handed the
program's own parameters: ``lt_*`` the lightning layers' leaves stacked over
them, ``wq``, ``wk``, ``wv``, ``wz`` (the gate), ``wo`` the selected layers'
stacked over them, ``w1`` the MLP's gate projection, ``w3`` its up
projection, ``w2`` its down projection, stacked over all layers.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

# The declared scales of the seeded weights that the chip check rests on
# (``assumed`` in the configuration file; PERF.md has the chip's readings).
# The stream starts at deviation one: the embedding is drawn at 1 /
# scale_emb.  Every branch is added times r = 1.4 / sqrt(32) = 0.247, and
# each output projection is drawn so that the branch adds about a quarter of
# the stream: a fault of any one shows in the logits, and eight branches
# leave the stream near 1.2.
# The selected layer's queries are drawn at QUERY times unit gain, so that a
# head's scores q . k / sqrt(hd) have deviation QUERY over the positions and
# a query weighs a few dozen of its 4096 selected positions and not all
# alike (at deviation one the context would be a mean of thousands of values,
# a fiftieth of one, and no selection could show): the context has deviation
# about 0.3 and, gated, 0.15; ATTN_OUT brings it to one.  A pooled key is
# the mean of 32 keys, so a kernel's score has deviation QUERY / sqrt(32)
# and the blocks' scores lie close: the selection is decided by small
# differences, as a trained model's is not; PERF.md says what that does to
# the check.
# A lightning head's output is RMS-normed to one and gated (0.54): LT_OUT
# brings 0.54 to one.  The MLP's silu(N(0,1)) N(0,1) has deviation 0.6.
# The logits have deviation one: the last norm's output is divided by 16.
QUERY = 3.0
ATTN_OUT = 6.5
LT_OUT = 1.9
MLP_OUT = 1.7

QUERY_BLOCK = 256       # queries that hold their scores at a time
HEAD_BLOCK = 32_768     # rows of the head upcast at a time


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
    mixers: tuple           # a kind a layer, the first n_layers of the list
    depth: int              # the published model's layers
    lt_heads: int
    lt_head_dim: int
    scale_emb: float
    scale_depth: float
    dim_model_base: int
    kernel: int
    stride: int
    block: int
    topk: int
    init_blocks: int
    window: int
    dense_len: int

    @classmethod
    def from_config(cls, config: dict) -> "Shape":
        """From a configuration file's published keys and its
        ``sparse_config``."""
        c, sc = config, config["sparse_config"]
        return cls(vocab=c["vocab_size"], d_model=c["hidden_size"],
                   n_heads=c["num_attention_heads"],
                   n_kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], n_layers=c["num_hidden_layers"],
                   d_ff=c["intermediate_size"], eps=c["rms_norm_eps"],
                   rope_theta=float(c["rope_theta"]),
                   mixers=tuple(c["mixer_types"][:c["num_hidden_layers"]]),
                   depth=len(c["mixer_types"]),
                   lt_heads=c["lightning_nh"],
                   lt_head_dim=c["lightning_head_dim"],
                   scale_emb=float(c["scale_emb"]),
                   scale_depth=float(c["scale_depth"]),
                   dim_model_base=c["dim_model_base"],
                   kernel=sc["kernel_size"], stride=sc["kernel_stride"],
                   block=sc["block_size"], topk=sc["topk"],
                   init_blocks=sc["init_blocks"], window=sc["window_size"],
                   dense_len=sc["dense_len"])

    def layers_of(self, kind: str) -> list:
        return [l for l, mixer in enumerate(self.mixers) if mixer == kind]

    @property
    def branch_scale(self) -> float:
        return self.scale_depth / self.depth ** 0.5


SELECTED, LIGHTNING = "minicpm4", "lightning-attn"
LT_LEAVES = ("lt_q", "lt_k", "lt_v", "lt_z", "lt_o", "lt_qn", "lt_kn",
             "lt_on")
BS_LEAVES = ("wq", "wk", "wv", "wz", "wo")
MLP_LEAVES = ("w1", "w3", "w2", "ln1", "ln2")


def param_init(shape: Shape, serving: bool = False
               ) -> dict[str, tuple[tuple[int, ...], float | None]]:
    """Leaf name -> (shape, standard deviation of its normal initializer);
    ``None`` marks a leaf that starts at one (every norm's scale).  Leaves
    are stacked over the layers of their kind.  One draw, the constants
    above: no cell trains this configuration, so ``serving`` changes
    nothing."""
    s = shape
    L, D, F, V = s.n_layers, s.d_model, s.d_ff, s.vocab
    q, kv = s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim
    HK, K = s.lt_heads * s.lt_head_dim, s.lt_head_dim
    nl, ns = len(s.layers_of(LIGHTNING)), len(s.layers_of(SELECTED))
    table = {
        "emb": ((V, D), 1.0 / s.scale_emb),
        "head": ((V, D), (D / s.dim_model_base) * D ** -0.5),
        "w1": ((L, D, F), D ** -0.5),
        "w3": ((L, D, F), D ** -0.5),
        "w2": ((L, F, D), MLP_OUT * F ** -0.5),
        "ln1": ((L, D), None),
        "ln2": ((L, D), None),
        "lnf": ((D,), None),
    }
    if nl:
        table.update({
            "lt_q": ((nl, D, HK), D ** -0.5),
            "lt_k": ((nl, D, HK), D ** -0.5),
            "lt_v": ((nl, D, HK), D ** -0.5),
            "lt_z": ((nl, D, HK), D ** -0.5),
            "lt_o": ((nl, HK, D), LT_OUT * HK ** -0.5),
            "lt_qn": ((nl, K), None), "lt_kn": ((nl, K), None),
            "lt_on": ((nl, K), None)})
    if ns:
        table.update({
            "wq": ((ns, D, q), QUERY * D ** -0.5),
            "wk": ((ns, D, kv), D ** -0.5),
            "wv": ((ns, D, kv), D ** -0.5),
            "wz": ((ns, D, q), D ** -0.5),
            "wo": ((ns, q, D), ATTN_OUT * q ** -0.5)})
    return table


def counts(shape: Shape) -> dict:
    """What ``lib/costs.py`` counts of this family, each figure what the
    leanest exact program needs.

    ``active_params``: every matrix (the norms multiply none) but the
    embedding, a lookup table (``lookup_params``).  ``attention_layers``: the
    selected layers, whose cache alone grows.  ``kv_elements``: what a cached
    step reads of every live position in such a layer, the pooled keys:
    ``n_kv_heads x head_dim`` for every ``stride`` positions.
    ``state_elements``: what a step reads of a sequence whatever its length,
    over all layers: a selected layer's ``topk x block`` K and V rows, and a
    lightning layer's ``heads x K x K`` state, which is float32 where
    ``lib/costs.decode_step_bytes`` counts at the cache's two bytes, so each
    of its elements counts as two (PERF.md section 7 items 11 and 12: a step
    writes the state back too, which is not counted).  ``attention_width``:
    ``lib/costs.prefill_flops`` counts ``4 x layers x width x T`` operations
    a position; half the query heads' width is the causal half of a dense
    read, which overstates a selection of 4096 positions at 15,872 by 2.4% of
    the pass, while the lightning layers' own products, like every scan's,
    are not counted."""
    s = shape
    L, D, F, V = s.n_layers, s.d_model, s.d_ff, s.vocab
    q, kv = s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim
    HK = s.lt_heads * s.lt_head_dim
    nl, ns = len(s.layers_of(LIGHTNING)), len(s.layers_of(SELECTED))
    return {"active_params": (L * 3 * D * F + nl * 5 * D * HK
                              + ns * (3 * D * q + 2 * D * kv) + V * D),
            "projection_params": V * D,
            "lookup_params": V * D,
            "kv_elements": max(1, kv // s.stride),
            "state_elements": (ns * s.topk * s.block * 2 * kv
                               + 2 * nl * s.lt_heads * s.lt_head_dim ** 2),
            "attention_layers": max(1, ns),
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


# ---- the lightning mixer ------------------------------------------------------

def log_decay(shape: Shape, layer: int):
    """``log lam_a`` of the heads of layer ``layer`` of the model: (heads,)."""
    slopes = 2.0 ** (-8.0 * np.arange(1, shape.lt_heads + 1) / shape.lt_heads)
    return jnp.asarray(-slopes * (1.0 - layer / max(1, shape.depth - 1)
                                  + 1e-5), jnp.float32)


def recurrence(q, k, v, log_lam):
    """``S_t = lam S_{t-1} + k_t v_t^T``, ``y_t = S_t^T q_t``, a position at
    a time from a zero state: q, k, v (B, T, H, K), ``log_lam`` (H,).
    Returns y (B, T, H, K) and the last state (B, H, K, K)."""
    B, _, H, K = q.shape
    lam = jnp.exp(log_lam)[:, None, None]

    def one(S, now):
        q_t, k_t, v_t = now
        S = lam * S + k_t[..., None] * v_t[..., None, :]
        return S, jnp.sum(S * q_t[..., None], axis=2)

    S, y = jax.lax.scan(one, jnp.zeros((B, H, K, K), jnp.float32),
                        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v)))
    return jnp.moveaxis(y, 0, 1), S


def _lightning(shape: Shape, p: dict, x, layer: int):
    s = shape
    B, T, _ = x.shape
    H, K = s.lt_heads, s.lt_head_dim
    q = _rmsnorm((x @ p["lt_q"]).reshape(B, T, H, K), p["lt_qn"], s.eps)
    k = _rmsnorm((x @ p["lt_k"]).reshape(B, T, H, K), p["lt_kn"], s.eps)
    q, k = _rope(q, s.rope_theta), _rope(k, s.rope_theta)
    v = (x @ p["lt_v"]).reshape(B, T, H, K)
    y, _ = recurrence(q, k, v, log_decay(s, layer))
    y = _rmsnorm(y * K ** -0.5, p["lt_on"], s.eps).reshape(B, T, H * K)
    return (y * jax.nn.sigmoid(x @ p["lt_z"])) @ p["lt_o"]


# ---- the block-selected mixer ---------------------------------------------------

def pooled_keys(shape: Shape, k):
    """Every complete kernel's mean of k (B, T, Hkv, hd): (B, J, Hkv, hd)."""
    s = shape
    J = max(0, (k.shape[1] - s.kernel) // s.stride + 1)
    at = s.stride * jnp.arange(J)[:, None] + jnp.arange(s.kernel)
    return k[:, at].mean(axis=2)


def selected_blocks(shape: Shape, q, c, t, blocks: int):
    """``B_t`` of the queries q (B, Q, H, hd) at positions t (Q,) over the
    pooled keys c (B, J, Hkv, hd): (B, Hkv, Q, blocks) bool."""
    s = shape
    B, Q, H, hd = q.shape
    J, hkv = c.shape[1], c.shape[2]
    live = jnp.arange(blocks) * s.block <= t[:, None]           # (Q, blocks)
    if not J:
        return jnp.broadcast_to(live, (B, hkv, Q, blocks))
    qg = q.reshape(B, Q, hkv, H // hkv, hd)
    sc = jnp.einsum("bqgrd,bjgd->bgrqj", qg, c) * hd ** -0.5
    done = s.stride * jnp.arange(J) + s.kernel - 1 <= t[:, None]    # (Q, J)
    # a query before the first complete kernel has a row of nothing
    p = jnp.where(done, jax.nn.softmax(jnp.where(done, sc, -jnp.inf), -1),
                  0.0).sum(axis=2)
    # the kernels that overlap block b
    lo = -((s.kernel - 1) // s.stride)
    hi = (s.block - 1) // s.stride
    of = ((s.block // s.stride) * jnp.arange(blocks)[:, None]
          + jnp.arange(lo, hi + 1))                             # (blocks, n)
    there = (of >= 0) & (of < J)
    R = jnp.where(there, p[..., jnp.clip(of, 0, J - 1)], 0.0).max(-1)
    start = jnp.arange(blocks) * s.block
    forced = ((jnp.arange(blocks) < s.init_blocks)
              | ((start + s.block - 1 >= t[:, None] - s.window + 1) & live))
    R = jnp.where(live, jnp.where(forced, jnp.inf, R), -jnp.inf)
    best, at = jax.lax.top_k(R, min(s.topk, blocks))
    picked = (at[..., None] == jnp.arange(blocks)) & (best > -jnp.inf)[..., None]
    return picked.any(axis=-2)


def _selected(shape: Shape, p: dict, x):
    """The selected half's output of the normed input x (B, T, D), a block
    of queries at a time."""
    s = shape
    B, T, _ = x.shape
    H, hkv, hd = s.n_heads, s.n_kv_heads, s.head_dim
    q = (x @ p["wq"]).reshape(B, T, H, hd)
    k = (x @ p["wk"]).reshape(B, T, hkv, hd)
    v = (x @ p["wv"]).reshape(B, T, hkv, hd)
    c = pooled_keys(s, k)
    blocks = -(-T // s.block)
    block = min(T, QUERY_BLOCK)
    n = -(-T // block)
    qs = jnp.pad(q, [(0, 0), (0, n * block - T), (0, 0), (0, 0)])
    qs = jnp.moveaxis(qs.reshape(B, n, block, H, hd), 1, 0)

    def one(of):
        first, q_b = of
        t = first + jnp.arange(block)
        mask = jnp.arange(T) <= t[:, None]                      # (Q, T)
        mask = jnp.broadcast_to(mask, (B, hkv, block, T))
        if T > s.dense_len:
            chosen = selected_blocks(s, q_b, c, t, blocks)
            mask = mask & jnp.repeat(chosen, s.block, axis=-1)[..., :T]
        qg = q_b.reshape(B, block, hkv, H // hkv, hd)
        sc = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k) * hd ** -0.5
        w = jax.nn.softmax(jnp.where(mask[:, :, None], sc, -jnp.inf), -1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", w, v)

    # a padded query (past the last position) sees every key: dropped below
    y = jax.lax.map(one, (jnp.arange(n) * block, qs))
    y = jnp.moveaxis(y, 0, 1).reshape(B, n * block, H * hd)[:, :T]
    return (y * jax.nn.sigmoid(x @ p["wz"])) @ p["wo"]


# ---- the model --------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(shape: Shape, stacks: dict, at, layer: int, h):
    """Block ``layer`` of the model on (B, T, D) float32; ``stacks`` the
    leaves as stored, stacked over the layers of their kind, ``at`` the
    layer's place in its mixer's stacks.  The layer's leaves are read out of
    the stacks in here, so they and their float32 copies are temporaries of
    this program and not live arrays beside the next layer's."""
    kind = shape.mixers[layer]
    own = LT_LEAVES if kind == LIGHTNING else BS_LEAVES
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(jax.lax.dynamic_index_in_dim(
            stacks[k], at if k in own else layer, keepdims=False),
            jnp.float32) for k in (*own, *MLP_LEAVES)}
        x = _rmsnorm(h, p["ln1"], shape.eps)
        mixed = (_lightning(shape, p, x, layer) if kind == LIGHTNING
                 else _selected(shape, p, x))
        h = h + shape.branch_scale * mixed
        f = _rmsnorm(h, p["ln2"], shape.eps)
        return h + shape.branch_scale * (
            (jax.nn.silu(f @ p["w1"]) * (f @ p["w3"])) @ p["w2"])


@functools.partial(jax.jit, static_argnums=0)
def _project(shape: Shape, rows, h):
    """``h`` already normed and divided, onto a block of the head's rows."""
    with jax.default_matmul_precision("highest"):
        return h @ jnp.asarray(rows, jnp.float32).T


class PositionLogits:
    """The (B, T, V) float32 logits of a forward pass, multiplied out for the
    positions that are read: ``self[:, a:b]`` projects those positions'
    hidden states onto the head and is a ``jax`` array; ``np.asarray(self)``
    and ``jnp.asarray(self)`` project every position.  A decode check reads
    512 positions of each of two sequences of 16,384."""

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
    s = shape
    h = s.scale_emb * jnp.asarray(params["emb"][tokens], jnp.float32)
    stacks = {k: params[k] for k in (*LT_LEAVES, *BS_LEAVES, *MLP_LEAVES)
              if k in params}
    for layer, kind in enumerate(s.mixers):
        at = s.layers_of(kind).index(layer)
        h = _layer(s, stacks, np.int32(at), layer, h)
    h = _rmsnorm(h, jnp.asarray(params["lnf"], jnp.float32), s.eps)
    return PositionLogits(s, params["head"], h / (s.d_model
                                                  / s.dim_model_base))


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
