"""Lightning linear attention: a decayed outer-product state a head, the
mixer of a ``models/plan.py`` layer of kind "lightning".

From the block's input ``h`` and its norm ``x = RMSNorm(h; ln1)``, for head
``a`` of ``n_heads``, each ``K = head_dim`` wide:

    q, k, v = x lt_q, x lt_k, x lt_v                  (heads x K each)
    q, k: RMSNorm a head (scales lt_qn, lt_kn), then the rotary embedding
    S_t = lam_a S_{t-1} + k_t v_t^T                   (K x K, state_dtype)
    y_t = S_t^T q_t K^-1/2
    lam_a = exp(-s_a f_l),  s_a = 2^(-8 (a + 1) / heads),
    f_l = 1 - l / (depth - 1) + 1e-5,  l the layer's number in the model
    h  += r (RMSNorm(y_t; lt_on) a head * sigmoid(x lt_z)) lt_o

with ``r`` the plan's ``branch_scale``.  No weight is in the decay: it is a
constant of the head and of the layer's place (:func:`constants`, which the
plan hands the mixer as ``lp["log_decay"]``).

:func:`mixer` is the one function both paths call, as ``kda.mixer`` is: the
whole sequence from a zero state (trainer, prefill: :func:`chunked`, a block
of ``chunk`` positions at a time, inside it ``((Q K^T) . D) V`` with ``D[i,
j] = lam^(i - j)`` for ``j <= i``, across blocks the state decayed to the
block's end; every exponent is at most zero) and one position against a
carried state (``models/decode.py``: :func:`update`, the recurrence once).
Everything here is ``jax.numpy`` and ``lax``.  The decay, the recurrence and
the norms are float32 whatever the compute type.

Nothing imports this module but a configuration whose plan has the kind.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Lightning", "mixer", "chunked", "update", "constants",
           "leaf_shapes", "buffers", "POSITIONED"]

POSITIONED = True       # the rotary embedding reads a cached step's position


@dataclasses.dataclass(frozen=True)
class Lightning:
    """The mixer's sizes, under the published configuration's names where it
    has one (``lightning_nh``, ``lightning_head_dim``; ``depth`` is the
    published model's number of layers, which the decays' layer factor is
    of), and the two parts a configuration may lack (``lightning_use_rope``,
    ``use_output_gate``; the head norms of q, k and the output are always
    there)."""
    n_heads: int
    head_dim: int               # K: a head's keys and values alike
    depth: int
    rope: bool = True
    gate: bool = True
    # positions a block of the whole-sequence form (:func:`chunked`): a scan
    # step, and one pass of the matrix unit's rows
    chunk: int = 128
    # what the carried matrix state is stored in between cached steps; the
    # update itself is float32
    state_dtype: str = "float32"

    @property
    def width(self) -> int:
        return self.n_heads * self.head_dim


def leaf_shapes(cfg, lt: Lightning) -> dict:
    """One layer's leaves: name -> (shape, deviation of the program's own
    initializer or None for ones)."""
    D, HK = cfg.d_model, lt.width
    leaves = {
        "lt_q": ((D, HK), D ** -0.5), "lt_k": ((D, HK), D ** -0.5),
        "lt_v": ((D, HK), D ** -0.5),
        "lt_o": ((HK, D), HK ** -0.5 / max(1, 2 * cfg.n_layers) ** 0.5),
        "lt_qn": ((lt.head_dim,), None), "lt_kn": ((lt.head_dim,), None),
        "lt_on": ((lt.head_dim,), None),
    }
    if lt.gate:
        leaves["lt_z"] = ((D, HK), D ** -0.5)
    return leaves


def buffers(cfg, lt: Lightning, batch: int, t_max: int) -> tuple:
    """What a decoder carries for one layer (``models/plan.py``'s form): the
    heads' states ``(B, heads, K, K)``, key by value, in ``state_dtype``;
    they do not grow."""
    return (((batch, lt.n_heads, lt.head_dim, lt.head_dim), lt.state_dtype,
             None),)


def constants(lt: Lightning, layer: int) -> dict:
    """What layer ``layer`` (its number in the model, from 0) reads beside
    its leaves: ``log_decay`` (heads,) float32, ``log lam_a = -s_a f_l``."""
    slopes = 2.0 ** (-8.0 * np.arange(1, lt.n_heads + 1) / lt.n_heads)
    factor = 1.0 - layer / max(1, lt.depth - 1) + 1e-5
    return {"log_decay": (-slopes * factor).astype(np.float32)}


def chunked(q, k, v, log_decay, chunk: int):
    """The recurrence over whole sequences from a zero state, a block of
    ``chunk`` positions at a time.  q, k, v: (B, T, H, K) float32;
    ``log_decay`` (H,), at most zero.  Returns y (B, T, H, K), without the
    ``K^-1/2``, and the state after the last position (B, H, K, K), float32.

    Inside a block that starts from state ``S0`` position i (from 0) reads
    ``lam^(i + 1) S0^T q_i + sum_{j <= i} lam^(i - j) (q_i . k_j) v_j``, and
    the state after a block of c positions is ``lam^c S0 + sum_j lam^(c - 1
    - j) k_j v_j^T``.  What reads the inputs alone, the block's own
    quadratic form, is made for every block of the pass at once; a step of
    the scan over blocks is the two products with the state.  A length that
    is no multiple of the block is padded with positions of k = 0, and the
    state is taken where the sequence ends."""
    import jax.numpy as jnp
    from jax import lax

    f32, hi = jnp.float32, lax.Precision.HIGHEST
    B, T, H, K = q.shape
    c = min(chunk, T)
    N = -(-T // c)

    def blocks(y):      # (B, T, H, K) -> (N, B, H, c, K)
        y = jnp.pad(y.astype(f32), [(0, 0), (0, N * c - T), (0, 0), (0, 0)])
        return jnp.moveaxis(y.reshape(B, N, c, H, K), (1, 3), (0, 2))

    def mm(spec, x, y):
        return jnp.einsum(spec, x, y, precision=hi)

    q, k, v = blocks(q), blocks(k), blocks(v)
    lg = log_decay.astype(f32)[:, None]                     # (H, 1)
    at = jnp.arange(c, dtype=f32)
    # D[i, j] = lam^(i - j) for j <= i: every exponent at most zero
    D = jnp.where(at[:, None] >= at[None, :],
                  jnp.exp(lg[..., None] * jnp.maximum(
                      at[:, None] - at[None, :], 0.0)), 0.0)    # (H, c, c)
    inside = mm("nbhij,nbhjv->nbhiv", mm("nbhik,nbhjk->nbhij", q, k) * D, v)
    since = jnp.exp(lg * (at + 1.0))[..., None]             # (H, c, 1)
    # a block that the sequence ends in decays its state to the end alone
    last = T - (N - 1) * c
    left = jnp.full((N,), c, f32).at[-1].set(last)

    def one(S, block):
        q, k, v, inside, n = block
        y = inside + since * mm("bhck,bhkv->bhcv", q, S)
        until = jnp.exp(lg * jnp.maximum(n - 1.0 - at, 0.0))[..., None]
        return (S * jnp.exp(lg * n)[..., None]
                + mm("bhck,bhcv->bhkv", k * until, v)), y

    S, y = lax.scan(one, jnp.zeros((B, H, K, K), f32),
                    (q, k, v, inside, left))
    y = jnp.moveaxis(y, (0, 2), (1, 3)).reshape(B, N * c, H, K)
    return y[:, :T], S


def update(state, q, k, v, log_decay):
    """The recurrence once, for one new position: ``state`` (B, H, K, K) as
    it is carried, q, k, v (B, H, K) float32, ``log_decay`` (H,).  Returns y
    (B, H, K) float32, without the ``K^-1/2``, and the new state in
    ``state``'s type."""
    import jax.numpy as jnp

    S = (state.astype(jnp.float32)
         * jnp.exp(log_decay.astype(jnp.float32))[:, None, None]
         + k[..., None] * v[..., None, :])
    return jnp.sum(S * q[..., None], axis=2), S.astype(state.dtype)


def mixer(cfg, lp, h, carry=None):
    """One layer's mixer on the block's input ``h`` (B, T, D): the norm, the
    mixer and the residual add.

    ``carry`` None: whole sequences from a zero state, at positions 0 to T -
    1; returns ``(h, state)``, the layer's state after the last position,
    ``(B, heads, K, K)`` float32.  ``carry = (state, pos)``: T == 1, position
    ``pos`` against this layer's own carried state in whatever it is stored
    in; returns ``(h, state)``, the new one in the same type."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.models import transformer as tfm

    lt, f32, cdt = cfg.plan.lightning, jnp.float32, h.dtype
    B, T, _ = h.shape
    H, K = lt.n_heads, lt.head_dim
    positions = jnp.arange(T) if carry is None else carry[1][None]

    def proj(y, name):
        return jnp.einsum("btd,df->btf", y, lp[name].astype(cdt))

    def heads(name):
        y = proj(x, name)
        if carry is not None:
            # a cached step's product ends here, (B, 1, H K) as lt_z's is:
            # the TPU's compiler otherwise folds the reshape into it (the
            # weight seen (D, H, K), the head norm's sums a second result),
            # wants that weight with D minor, and copies the layer's matrix
            # out of its stack re-laid every step, 32 MiB read and written
            # for each of lt_q, lt_k, lt_v (PR 53; ``tests/parallel/
            # test_plan_step_compiled.py``).  Behind the barrier the weight
            # is read where it lies, a static slice inside the product
            y = lax.optimization_barrier(y)
        return y.reshape(B, T, H, K)

    with scope("lightning_proj"):
        # the norms and the rotary embedding are called through the module:
        # a benchmark's control plants a wrong one there
        x = tfm._rmsnorm(h, lp["ln1"], cfg.norm_eps)
        q, k, v = (heads(name) for name in ("lt_q", "lt_k", "lt_v"))
        q = tfm._rmsnorm(q, lp["lt_qn"], cfg.norm_eps)
        k = tfm._rmsnorm(k, lp["lt_kn"], cfg.norm_eps)
        if lt.rope:
            q = tfm._rope(q, positions, theta=cfg.rope_theta)
            k = tfm._rope(k, positions, theta=cfg.rope_theta)
        q, k, v = (y.astype(f32) for y in (q, k, v))
        log_decay = jnp.asarray(lp["log_decay"], f32)
    if carry is None:
        with scope("lightning.scan"):
            y, state = chunked(q, k, v, log_decay, lt.chunk)
    else:
        with scope("lightning.update"):
            y, state = update(carry[0], q[:, 0], k[:, 0], v[:, 0], log_decay)
            y = y[:, None]
    with scope("lightning_proj"):
        y = y * K ** -0.5
        y = (y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                           + cfg.norm_eps) * lp["lt_on"].astype(f32))
        y = y.reshape(B, T, H * K)
        if lt.gate:
            y = y * jax.nn.sigmoid(proj(x, "lt_z").astype(f32))
        return (h + proj(y.astype(cdt), "lt_o") * cfg.plan.branch_scale,
                state)
