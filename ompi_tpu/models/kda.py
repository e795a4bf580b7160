"""Kimi Delta Attention: a gated delta rule with a matrix state a head, the
mixer of a ``models/plan.py`` layer of kind "kda".

From the block's input ``h`` and its norm ``x = RMSNorm(h; ln1)``:

    q~, k~, v~ = x kda_q, x kda_k, x kda_v            (heads x K each)
    q, k, v    = silu(conv(.)) a branch; q, k L2-normed a head; q K^-1/2
    g_t  = -exp(kda_a[h]) softplus((x kda_f1) kda_f2 + kda_dt)   (a channel)
    beta = sigmoid(x kda_b)                                      (a head)
    S <- diag(exp g_t) S;  u = beta (v_t - S^T k_t);  S <- S + k_t u^T
    o_t  = S^T q_t
    h   += (RMSNorm(o_t; kda_n) a head * sigmoid((x kda_g1) kda_g2)) kda_o

:func:`mixer` is the one function both paths call, as ``ssm.mixer`` is: the
whole sequence from a zero state (trainer, prefill: a causal convolution
over the sequence and :func:`chunked`, the rule in blocks of 64 positions:
what reads the inputs alone, the triangular solve among it, is made for all
the blocks of a pass at once, and a scan over the blocks carries the state
through five products a block) and one position against a carried state
(``models/decode.py``: the convolution from its last inputs, the recurrence
once).  Everything here is ``jax.numpy`` and ``lax`` but the cached step's
recurrence, :func:`update`, which on TPUs is one pallas pass over the
layer's state (``ops/kda_update.py``; ``kda_update.block`` is the rule, from
static facts alone).  The decay, the recurrence and the norms are float32
whatever the compute type.

Nothing imports this module but a configuration whose plan has the kind.
"""

from __future__ import annotations

import dataclasses

__all__ = ["KDA", "mixer", "chunked", "update", "leaf_shapes",
           "state_shapes", "buffers", "POSITIONED"]

L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class KDA:
    """The mixer's sizes, under the published configuration's names where it
    has one (``linear_attn_config``)."""
    n_heads: int
    head_dim: int               # K: a head's keys and values alike
    conv: int                   # taps of the causal depthwise convolutions
    rank: int                   # of the decay's and the output gate's
    #                             low-rank projections
    # positions a block of the whole-sequence form (:func:`chunked`): the
    # recurrence on the blocks' final states runs across blocks, so a block
    # is a scan step and half a pass of the matrix unit's rows; inside it
    # position i reads j <= i through exp(G_i - G_j) a channel, never
    # through exp(-G_j), which overflows where a channel decays fast
    chunk: int = 64
    # what the carried matrix state is stored in between cached steps; the
    # update itself is float32
    state_dtype: str = "float32"

    @property
    def width(self) -> int:
        return self.n_heads * self.head_dim


def leaf_shapes(cfg, kd: KDA) -> dict:
    """One layer's leaves: name -> (shape, deviation of the program's own
    initializer or None for ones)."""
    D, HK, r = cfg.d_model, kd.width, kd.rank
    return {
        "kda_q": ((D, HK), D ** -0.5), "kda_k": ((D, HK), D ** -0.5),
        "kda_v": ((D, HK), D ** -0.5),
        "kda_cq": ((kd.conv, HK), kd.conv ** -0.5),
        "kda_ck": ((kd.conv, HK), kd.conv ** -0.5),
        "kda_cv": ((kd.conv, HK), kd.conv ** -0.5),
        "kda_f1": ((D, r), D ** -0.5), "kda_f2": ((r, HK), r ** -0.5),
        "kda_a": ((kd.n_heads,), 1.0), "kda_dt": ((HK,), 1.0),
        "kda_b": ((D, kd.n_heads), D ** -0.5),
        "kda_g1": ((D, r), D ** -0.5), "kda_g2": ((r, HK), r ** -0.5),
        "kda_n": ((kd.head_dim,), None),
        "kda_o": ((HK, D), HK ** -0.5 / max(1, 2 * cfg.n_layers) ** 0.5),
    }


def state_shapes(kd: KDA, batch: int) -> tuple:
    """One layer's carried state for ``batch`` sequences: the three
    convolutions' last inputs ``(B, conv - 1, 3 heads K)`` (q's, k's, v's
    channels in this order) and the heads' states ``(B, heads, K, K)``, key
    by value."""
    return ((batch, kd.conv - 1, 3 * kd.width),
            (batch, kd.n_heads, kd.head_dim, kd.head_dim))


def chunked(q, k, v, g, beta, chunk: int):
    """The recurrence over whole sequences from a zero state, a block of
    ``chunk`` positions at a time.  q, k, v, g: (B, T, H, K) float32, g the
    log decay (<= 0); beta: (B, T, H).  Returns o (B, T, H, K) and the state
    after the last position (B, H, K, K), float32.

    Inside a block that starts from state ``S0``, with ``G`` the decay's
    running sum: ``u_i = beta_i (v_i - S0^T (k_i e^{G_i}) - sum_{j<i} A_ij
    u_j)``, ``A_ij = sum_d k_id k_jd e^{G_id - G_jd}``, a unit lower
    triangular system; ``o_i = S0^T (q_i e^{G_i}) + sum_{j<=i} B_ij u_j``
    with ``B`` as ``A`` but of q against k; the state after the block
    ``e^{G_last} S0 + sum_j (k_j e^{G_last - G_j}) u_j^T``.

    ``A``, ``B`` and ``beta`` read the inputs alone, so they and the
    triangle's inverse ``T = (I + beta A)^-1`` are made for every block of
    the pass at once, and a step of the scan over blocks is what reads the
    state: ``U = T (beta (v - (k e^G) S0))``, ``o`` and the next state, five
    products of ``chunk`` rows.  A block is sub-blocks of ``min(16, chunk)``
    positions.  Where i and j share one, ``A_ij`` and ``B_ij`` are summed
    over the differences themselves, a channel at a time; where j's lies
    before i's they are products of ``q_i e^{G_i - r}`` (or k_i) with ``k_j
    e^{r - G_j}``, ``r`` the running sum where i's sub-block starts.  Every
    exponent is a sum of g's, at most zero: no factor passes 1, and one
    underflows only where the whole term does.  ``T``'s diagonal sub-blocks
    are inverted by forward substitution a row at a time, all of them
    together, and a sub-block's row of the others follows from the rows
    above it; for that the blocks are laid out last (the lanes, on TPUs,
    which a 16 x 16 matrix a block would fill an eighth of).  A length that
    is no multiple of the block is padded with positions of g = 0 and
    beta = 0, which leave the state as it is."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental.layout import Layout, with_layout_constraint

    f32, hi = jnp.float32, lax.Precision.HIGHEST
    B, T, H, K = q.shape
    c, s = chunk, min(16, chunk)
    if c % s:
        raise ValueError(f"a block of {c} positions is no multiple of its "
                         f"sub-blocks' {s}")
    n, N = c // s, -(-T // c)

    def blocks(y):      # (B, T, H, ...) -> (N, B, H, c, ...)
        y = jnp.pad(y.astype(f32),
                    [(0, 0), (0, N * c - T)] + [(0, 0)] * (y.ndim - 2))
        y = y.reshape(B, N, c, *y.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(y, 1, 0), 2, 3)

    def sub(y):         # (N, B, H, c, ...) -> (N, B, H, n, s, ...)
        return y.reshape(N, B, H, n, s, *y.shape[4:])

    def mm(spec, x, y):
        return jnp.einsum(spec, x, y, precision=hi)

    q, k, v, g, b = (blocks(y) for y in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=3)
    ks, Gs = sub(k), sub(G)
    # j <= i of one sub-block: k_j e^{G_i - G_j}, from the differences
    near = ks[..., None, :, :] * jnp.exp(jnp.where(
        jnp.tril(jnp.ones((s, s), bool))[:, :, None],
        Gs[..., :, None, :] - Gs[..., None, :, :], -jnp.inf))
    # sub-blocks 1 to n - 1 against every position before them, through the
    # running sum where each starts
    start = Gs[..., :-1, -1, :]                             # (..., n - 1, K)
    since = jnp.exp(Gs[..., 1:, :, :] - start[..., None, :])
    before = jnp.arange(c) < s * jnp.arange(1, n)[:, None]  # (n - 1, c)
    far = k[..., None, :, :] * jnp.exp(jnp.where(
        before[..., None], start[..., None, :] - G[..., None, :, :],
        -jnp.inf))                                          # (..., n - 1, c, K)

    def against_k(y):   # (N, B, H, c, K) -> (N, B, H, c, c)
        diagonal = jnp.sum(sub(y)[..., :, None, :] * near, axis=-1)
        return jnp.concatenate(
            [jnp.pad(diagonal[..., a, :, :],
                     [(0, 0)] * 4 + [(a * s, c - (a + 1) * s)])
             for a in range(n)], axis=-2) + jnp.pad(
            mm("...aik,...ajk->...aij", sub(y)[..., 1:, :, :] * since, far
               ).reshape(N, B, H, c - s, c), [(0, 0)] * 3 + [(s, 0), (0, 0)])

    A, Bm = against_k(k), against_k(q)

    # T = (I + beta A_strict)^-1 with the blocks of the pass laid out last:
    # the diagonal sub-blocks a row at a time, all of them together, then a
    # sub-block's row of the others from the rows above it
    def blocks_last(y):
        return with_layout_constraint(
            y, Layout(major_to_minor=tuple(range(y.ndim))))

    L = blocks_last(jnp.moveaxis(
        (b[..., None] * A * jnp.tril(jnp.ones((c, c), f32), -1)
         ).reshape(N * B * H, n, s, c), 0, -1))             # (n, s, c, blocks)
    Ld = jnp.stack([L[a, :, a * s:(a + 1) * s] for a in range(n)])
    X = jnp.broadcast_to(jnp.eye(s, dtype=f32)[:, :, None], Ld.shape)
    for i in range(1, s):
        X = X.at[:, i].add(-jnp.sum(Ld[:, i, :i, None] * X[:, :i], axis=1))
    rows = [X[0]]                                           # (s, s, blocks)
    for a in range(1, n):
        above = jnp.concatenate([jnp.pad(r, [
            (0, 0), (0, a * s - r.shape[1]), (0, 0)]) for r in rows])
        left = jnp.sum(L[a, :, :a * s, None] * above, axis=1)
        rows.append(jnp.concatenate(
            [-jnp.sum(X[a][:, :, None] * left, axis=1), X[a]], axis=1))
    Tm = jnp.moveaxis(blocks_last(jnp.concatenate([jnp.pad(r, [
        (0, 0), (0, c - r.shape[1]), (0, 0)]) for r in rows])), -1, 0
    ).reshape(N, B, H, c, c)

    def one(S, block):      # what reads the state, and nothing else
        q, k, v, G, b, Tm, Bm = block
        eG = jnp.exp(G)
        U = mm("bhcj,bhjv->bhcv", Tm, b[..., None] * (
            v - mm("bhck,bhkv->bhcv", k * eG, S)))
        o = mm("bhck,bhkv->bhcv", q * eG, S) + mm("bhcj,bhjv->bhcv", Bm, U)
        return S * eG[:, :, -1][..., None] + mm(
            "bhck,bhcv->bhkv", k * jnp.exp(G[:, :, -1:] - G), U), o

    S, o = lax.scan(one, jnp.zeros((B, H, K, K), f32),
                    (q, k, v, G, b, Tm, Bm))
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1).reshape(B, N * c, H, K)
    return o[:, :T], S


def update(state, q, k, v, g, beta):
    """The recurrence once, for one new position: ``state`` (B, H, K, K) as
    it is carried, q, k, v, g (B, H, K) and beta (B, H) float32.  Returns o
    (B, H, K) float32 and the new state in ``state``'s type.

    Where ``ops/kda_update.block`` gives a block (on TPUs, a float32 state
    of heads that tile), one pallas pass: a block of matrices is read into
    VMEM, swept twice there and written back into ``state``'s own buffer.
    Anywhere else (the CPU, a state carried in bfloat16, a head narrower
    than 128) the ``jax.numpy`` form below, which XLA makes two fusions of,
    three passes over the state: its products with k and q, then the decayed
    state plus the write."""
    import jax.numpy as jnp

    from ompi_tpu.ops import _chip, kda_update

    if kda_update.block(_chip._traced_for_tpus(), state.dtype,
                        *state.shape[1:3]) is not None:
        return kda_update.kda_update(state, q, k, v, g, beta)
    S = state.astype(jnp.float32) * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.sum(S * k[..., None], axis=2))
    o = (jnp.sum(S * q[..., None], axis=2)
         + jnp.sum(q * k, axis=-1, keepdims=True) * u)
    return o, (S + k[..., None] * u[..., None, :]).astype(state.dtype)


def mixer(cfg, lp, h, carry=None):
    """One layer's mixer on the block's input ``h`` (B, T, D): the norm, the
    mixer and the residual add.

    ``carry`` None: whole sequences from a zero state; returns ``(h,
    conv_state, state)``, the layer's states after the last position, ``(B,
    conv - 1, 3 heads K)`` in h's type and ``(B, heads, K, K)`` float32.
    ``carry = (conv, state)``: T == 1 against this layer's own carried
    states, ``(B, conv - 1, 3 heads K)`` and ``(B, heads, K, K)`` in whatever
    they are stored in; returns ``(h, conv, state)``, the new ones in the
    same types.  A layer's state is a buffer of its own and not a slice of a
    stack over layers (``models/plan.carry`` says why)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.models.transformer import _rmsnorm

    kd, f32, cdt = cfg.plan.kda, jnp.float32, h.dtype
    B, T, _ = h.shape
    H, K, HK = kd.n_heads, kd.head_dim, kd.width

    def proj(y, *names):
        for name in names:
            y = jnp.einsum("btd,df->btf", y, lp[name].astype(cdt))
        return y

    with scope("kda_proj"):
        x = _rmsnorm(h, lp["ln1"], cfg.norm_eps)
        raw = jnp.concatenate([proj(x, w) for w in
                               ("kda_q", "kda_k", "kda_v")], axis=-1)
        g = -jnp.exp(lp["kda_a"].astype(f32))[:, None] * jax.nn.softplus(
            proj(x, "kda_f1", "kda_f2").astype(f32).reshape(B, T, H, K)
            + lp["kda_dt"].astype(f32).reshape(H, K))
        beta = jax.nn.sigmoid(proj(x, "kda_b").astype(f32))
        gate = jax.nn.sigmoid(proj(x, "kda_g1", "kda_g2").astype(f32))
    with scope("kda.conv"):
        taps = kd.conv
        if carry is None:
            window = jnp.pad(raw, ((0, 0), (taps - 1, 0), (0, 0)))
            conv_out = window[:, T:]
        else:
            conv, state = carry
            window = jnp.concatenate([conv.astype(cdt), raw], axis=1)
            conv_out = window[:, 1:].astype(conv.dtype)
        w = jnp.concatenate([lp[name].astype(f32) for name in
                             ("kda_cq", "kda_ck", "kda_cv")], axis=-1)
        qkv = jax.nn.silu(sum(window[:, j:j + T].astype(f32) * w[j]
                              for j in range(taps)))
    with scope("kda_proj"):
        q, k, v = (y.reshape(B, T, H, K) for y in jnp.split(qkv, 3, axis=-1))
        q, k = (y * lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + L2_EPS)
                for y in (q, k))
        q = q * K ** -0.5
    if carry is None:
        with scope("kda.scan"):
            o, state = chunked(q, k, v, g, beta, kd.chunk)
    else:
        with scope("kda.update"):
            o, state = update(state, *(y[:, 0] for y in
                                       (q, k, v, g, beta)))     # T == 1
            o = o[:, None]
    with scope("kda_proj"):
        o = (o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                           + cfg.norm_eps) * lp["kda_n"].astype(f32))
        y = proj((o.reshape(B, T, HK) * gate).astype(cdt), "kda_o")
        if cfg.plan.branch_factor != 1:
            y = y * cfg.plan.branch_factor
        return h + y, conv_out, state


# what ``models/plan.py`` asks of a mixer's kind beside the above (kept below
# the cached update: a kernel's compile cache key holds its call site's lines)
POSITIONED = False      # a cached step reads no position


def buffers(cfg, kd: KDA, batch: int, t_max: int) -> tuple:
    """What a decoder carries for one layer (``models/plan.py``'s form):
    :func:`state_shapes`' two, the inputs in the compute type, the states in
    the mixer's ``state_dtype``; neither grows."""
    conv, state = state_shapes(kd, batch)
    return ((conv, cfg.compute_dtype, None), (state, kd.state_dtype, None))
