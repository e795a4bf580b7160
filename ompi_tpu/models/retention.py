"""Power retention: the attention core of a layer whose weights are a power
of ``q . k`` under a learned decay, carried as a fixed-size state.

``TransformerConfig.retention`` holds a :class:`Retention`; the layer
(``models/block.mixer``) is then the one every configuration has (ln1, the
projections with K/V heads fewer than query heads, per-head q/k-norm, the
rotary embedding, ``wo``; a SiLU-gated MLP after it) with one leaf more, the
gate's projection ``wd`` (d_model x K/V heads: the decay's; ``wg`` is a
router's, and how the benchmark's controls tell a routed configuration),
and this core in place of softmax over cached keys.  With ``log g_t =
logsigmoid(gate_offset + x_t wd)`` a K/V head and position, for query head
``h`` over its K/V head:

    a(t, j) = exp(sum_{s=j+1..t} log g_s) (q_t . k_j)^2 / d          j <= t
    y_t     = sum_j a(t, j) v_j / (sum_j a(t, j) + eps)

Because ``(q . k)^2 = phi(q) . phi(k)`` for the degree-2 symmetric power
``phi`` (:func:`phi`), the same thing is a recurrence on a matrix state and a
normaliser a K/V head, which is what a decoder carries (no K/V cache):

    S_t = g_t S_{t-1} + phi(k_t) v_t^T        z_t = g_t z_{t-1} + phi(k_t)
    y_t = S_t^T phi(q_t) / (z_t . phi(q_t) + eps)

:func:`chunked` is the whole-sequence form (trainer, prefill): inside a chunk
the quadratic form with the decays' differences, every exponent at most
zero, and no expansion; across chunks ``S`` and ``z`` decayed to the chunk's
end.  Reading the past through the state costs a query head ``2 D d`` FLOP
whatever the chunk, a key read directly ``4 d``: up to :data:`CROSSOVER`
positions a decoder's prefill on TPUs (:func:`direct`: forward only, static
facts alone) sums the first pair of equations over the whole prompt in one
pallas kernel (``ops/retention_prefill.py``) and forms ``S`` and ``z`` once,
after the last position: :func:`end_state`'s sums, there in a second kernel
(``ops/retention_end_state.py``: ``phi(k)`` never leaves the chip); a
trainer, the CPU, a longer prompt and a length that does not tile keep the
scan over chunks.
:func:`update` is one position against the carried state
(:func:`read`, once for the query heads of its K/V head, then
:func:`write`).  The gate, the cumulative decays, ``S``, ``z`` and the
quotient are float32 whatever the compute type;
a cached step's products against the state are float32 too, the chunked
form's run in the compute type and add up in float32, as the other mixers'.
Everything here is ``jax.numpy`` and ``lax`` but that prefill's two kernels
and a cached step's pass over the matrix state, which on TPUs is one pallas
kernel over the layer where it lies in the stack (``ops/retention_update.py``;
``retention_update.block`` is the rule, from static facts alone):
:func:`read`'s sums over the state and :func:`write`'s decay and outer
product in one sweep (:class:`InPlace`).

What the state's layout is: ``phi(u)`` holds ``u_a u_{a+s}`` (indices modulo
the head width ``d``) for the shifts ``s = 0 .. d/2``, a row of ``d`` a shift:
every unordered pair once (times sqrt 2), the squares once, and the pairs
half a turn apart twice at weight one.  ``(d/2 + 1) d`` wide, 8320 for a head
of 128 where the least exact layout is ``d (d + 1) / 2`` = 8256: 0.8% more
bytes for rows that are whole lanes and an expansion that is one product with a
matrix of zeros and ones (the ``d/2 + 1`` rotations) and no gather.

Nothing imports this module but a configuration that has the field.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np

__all__ = ["Retention", "retention_config", "phi", "chunked", "direct",
           "end_state", "CROSSOVER", "read", "write",
           "update", "InPlace", "core", "log_gate", "leaf_names", "init_leaves",
           "state_shapes", "carry", "carried", "grows", "check_mesh"]


@dataclasses.dataclass(frozen=True)
class Retention:
    """What the core needs beside the layer's own sizes."""
    degree: int = 2             # the power of q . k; 2 alone is built
    # c of log g = logsigmoid(c + x wd): a constant of the layer, no leaf;
    # ln 999 makes g 0.999 at a zero projection
    gate_offset: float = math.log(999.0)
    chunk: int = 256            # positions a chunk of the whole-sequence form
    eps: float = 1e-6           # beside the normaliser in the quotient
    # what S and z are stored in between cached steps; the update itself is
    # float32
    state_dtype: str = "float32"

    def __post_init__(self):
        if self.degree != 2:
            raise ValueError(f"power retention of degree {self.degree}: "
                             f"the symmetric power of degree 2 alone is built")


_FIELDS = {f.name for f in dataclasses.fields(Retention)}

# The longest whole sequence a prefill reads directly (:func:`direct`).  A
# query reads what came before it through the state for 2 D d FLOP (2.13 M a
# head of 128) and directly for 4 d a key, so over a causal sequence the two
# meet at D = 8320 positions, where the authors too switch from a K/V cache
# to the state.  On a v5e, in a loop at cell 8's sizes (2 sequences, 40 over
# 8 heads), the direct form led the chunked one at every length the kernel
# takes: 2.30 ms for 7.91 at 1024 positions, 4.95 for 15.38 at 2048, 11.61
# for 30.44 at 4096, 29.25 for 60.69 at 8192 (``PERF.md`` section 6, PR 63),
# so this is the kernel's longest sequence and the chip's crossover lies
# past it.
CROSSOVER = 8192


def retention_config(**sizes):
    """``entry.config`` of a configuration file with this core: a
    ``TransformerConfig`` from flat keys, those of :class:`Retention`
    gathered under ``retention``."""
    from ompi_tpu.models.transformer import TransformerConfig

    own = {k: sizes.pop(k) for k in list(sizes) if k in _FIELDS}
    # a float: a large theta is past int32, which a python int is traced as
    sizes["rope_theta"] = float(sizes.get("rope_theta", 10_000))
    return TransformerConfig(retention=Retention(**own), **sizes)


def check_mesh(cfg, mesh) -> None:
    """The state would split over ``tp`` by K/V head and a sequence over
    ``sp`` needs an exclusive scan of per-rank states: neither is built, and
    no cell asks."""
    for axis in ("sp", "tp"):
        if int(dict(mesh.shape).get(axis, 1)) > 1:
            raise ValueError(
                f"power retention runs with {axis} == 1 only, and the mesh "
                f"has {axis}={mesh.shape[axis]}: its state is not split "
                f"over {axis}")


def leaf_names() -> tuple:
    """The layer's leaves beside those of the dense block, stacked over
    layers: the gate's projection and the MLP's up projection (``w1`` its
    gate, ``w2`` its down projection)."""
    return ("wd", "w3")


def init_leaves(cfg, rng) -> dict:
    """The two leaves as the program initialises them, float32."""
    L, D, F = cfg.n_layers, cfg.d_model, cfg.d_ff

    def w(*shape):
        return rng.normal(0, D ** -0.5, size=shape).astype(np.float32)

    return {"wd": w(L, D, cfg.kv_heads), "w3": w(L, D, F)}


def state_dim(head_dim: int) -> int:
    """Width of ``phi`` of a head ``head_dim`` wide."""
    return (head_dim // 2 + 1) * head_dim


def _weights(d: int) -> np.ndarray:
    """``phi``'s constant a shift, (d // 2 + 1,): one on the squares, sqrt 2
    on a pair that one shift holds once, one on the half turn of an even
    width, which holds each of its pairs twice."""
    c = np.full(d // 2 + 1, math.sqrt(2.0), np.float32)
    c[0] = 1.0
    if d % 2 == 0:
        c[-1] = 1.0
    return c


@functools.lru_cache(maxsize=None)
def _turns(d: int) -> np.ndarray:
    """(d, state_dim(d)) of zeros and ones: ``u @ _turns(d)`` is ``u`` turned
    by every shift ``0 .. d // 2``, ``u[(a + s) % d]`` at ``s d + a``."""
    turns = np.zeros((d, state_dim(d)), np.float32)
    for s in range(d // 2 + 1):
        turns[(np.arange(d) + s) % d, s * d + np.arange(d)] = 1.0
    return turns


def phi(u, scale: float = 1.0):
    """The degree-2 symmetric power of ``u`` (..., d) in this module's
    layout, float32 (..., state_dim(d)), times ``scale``: ``phi(q) . phi(k)
    == (q . k)^2``.  The turns are one product with a matrix of zeros and
    ones, exact (a sum of one term), so that the expansion is one matmul and
    one fusion and not a kernel a shift."""
    import jax.numpy as jnp
    from jax import lax

    d, f32 = u.shape[-1], jnp.float32
    # float32 operands, so that any backend multiplies them; one bfloat16
    # pass where u came in bfloat16, whose values it holds exactly
    turned = jnp.einsum(
        "...a,an->...n", u.astype(f32), jnp.asarray(_turns(d)),
        precision=lax.Precision.HIGHEST if u.dtype == f32 else None)
    c = jnp.asarray(_weights(d) * scale)[:, None]
    return (turned.reshape(*u.shape[:-1], -1, d) * u.astype(f32)[..., None, :]
            * c).reshape(turned.shape)


def _power(s):
    """``(q . k)^degree`` from the products, where no expansion is needed.
    (A function of its own so that the benchmark's controls can plant
    another degree while a decoder is traced.)"""
    return s * s


def _quotient(num, den, eps: float):
    """``num / (den + eps)``, ``den`` one a row of ``num``."""
    return num / (den[..., None] + eps)


def log_gate(rt: Retention, gamma):
    """``log g`` from the gate's projection, float32."""
    import jax
    import jax.numpy as jnp

    return jax.nn.log_sigmoid(gamma.astype(jnp.float32) + rt.gate_offset)


def direct(forward_only: bool, tpu: bool, T: int, d: int) -> bool:
    """Whether the whole-sequence form of ``T`` positions of heads ``d``
    wide sums its quadratic form over the whole sequence in the kernel
    (:func:`_direct`) and not chunk by chunk through the state
    (:func:`chunked`'s scan), from static facts alone: no gradient will be
    asked (the kernel has no backward pass), the trace is for TPUs (the
    kernel compiles for nothing else), the lengths tile, and the sequence is
    no longer than :data:`CROSSOVER`.  The state at the sequence's end is
    then formed once, by the kernel of ``ops/retention_end_state.py`` where
    that one's own ``tiles`` takes the lengths (today wherever this rule
    does) and by :func:`end_state` where not."""
    from ompi_tpu.ops import retention_prefill

    return bool(forward_only and tpu and T <= CROSSOVER
                and retention_prefill.tiles(T, d))


def _blocks(y, Q: int):
    """(B, T, ...) -> (nc, B, Q, ...), the tail padded with zeros."""
    import jax.numpy as jnp

    B, T = y.shape[:2]
    nc = -(-T // Q)
    y = jnp.pad(y, [(0, 0), (0, nc * Q - T)] + [(0, 0)] * (y.ndim - 2))
    return jnp.moveaxis(y.reshape(B, nc, Q, *y.shape[2:]), 1, 0)


def _decays_to_end(logg, Q: int):
    """Each position's decay to the sequence's end, (nc, B, Q, G) float32
    over :func:`_blocks` of ``Q`` positions of logg (B, T, G): ``exp((c_end -
    c_j) + rest)``, the decays summed inside its block and ``rest`` the
    blocks' after it, so every exponent is at most zero and none is the
    difference of two sums over the sequence.  A padded position has log g
    = 0: it decays nothing."""
    import jax.numpy as jnp

    cs = jnp.cumsum(_blocks(logg.astype(jnp.float32), Q), axis=2)
    rest = jnp.cumsum(cs[::-1, :, -1:], axis=0)[::-1] - cs[:, :, -1:]
    return jnp.exp(cs[:, :, -1:] - cs + rest)


def end_state(k, v, logg, chunk: int):
    """The state after the last position of whole sequences from a zero
    state, formed once: ``S = sum_j exp(c_T - c_j) phi(k_j) v_j^T`` (B, G, D,
    d) and ``z`` alike (B, G, D), float32, from k, v (B, T, G, d) and the
    log decays logg (B, T, G).  :func:`chunked`'s arithmetic for a chunk's
    end over the whole sequence: ``chunk`` positions of ``phi(k)`` held at a
    time, each under its decay to the sequence's end
    (:func:`_decays_to_end`), so nothing is decayed between chunks.  The
    ``jax.numpy`` form, on any backend and at any length: a chunk's
    ``phi(k)`` is an array of the program.  Where the lengths tile a
    decoder's prefill on TPUs forms the same sums in
    ``ops/retention_end_state.py`` (:func:`_end_state_in_vmem`), and this is
    what the tests hold that kernel to."""
    import jax.numpy as jnp
    from jax import lax

    f32, cdt = jnp.float32, k.dtype
    B, T, G, d = k.shape
    Q, D = min(chunk, T), state_dim(d)
    # a padded position has k = 0 and log g = 0: it adds and decays nothing
    w = _decays_to_end(logg, Q)
    k, v = _blocks(k, Q), _blocks(v, Q)

    def one(state, block):
        S, z = state
        k, v, w = block
        pk = phi(k) * w[..., None]                      # (B, Q, G, D)
        return (S + jnp.einsum("bkgn,bkgv->bgnv", pk.astype(cdt), v,
                               preferred_element_type=f32),
                z + pk.sum(axis=1)), None

    state, _ = lax.scan(
        one, (jnp.zeros((B, G, D, d), f32), jnp.zeros((B, G, D), f32)),
        (k, v, w))
    return state


def _end_state_in_vmem(k, v, logg, chunk: int):
    """:func:`end_state`'s sums where ``retention_end_state.tiles`` takes
    the lengths, in that kernel (PR 73: a block's ``phi(k)`` is built and
    added into a head's ``S`` in VMEM and is no array of the program): under
    the same decays, :func:`_decays_to_end` over ``chunk`` positions, and
    with :func:`phi`'s constants asked of ``phi`` as the trace finds it,
    ``phi`` of a vector of ones."""
    import jax.numpy as jnp

    from ompi_tpu.ops.retention_end_state import retention_end_state

    B, T, G, d = k.shape
    w = jnp.moveaxis(_decays_to_end(logg, min(chunk, T)), 0, 1)
    return retention_end_state(k, v, w.reshape(B, -1, G)[:, :T],
                               phi(jnp.ones((d,), jnp.float32)))


def _direct(q, k, v, logg, chunk: int, eps: float):
    """:func:`chunked`'s results where :func:`direct` says so: position t
    reads every j <= t through ``exp(c_t - c_j) (q_t . k_j)^2 / d``, ``c``
    the decay's running sum over the whole sequence, in one kernel
    (``ops/retention_prefill.py``), and the state at the last position is
    formed once: in one kernel too where the lengths tile for it
    (:func:`_end_state_in_vmem`), by :func:`end_state` anywhere else.
    ``phi(q)`` is never formed."""
    from ompi_tpu.core.scopes import scope
    from ompi_tpu.ops import retention_end_state
    from ompi_tpu.ops.retention_prefill import retention_prefill

    B, T, H, d = q.shape
    with scope("retention.direct"):
        num, den = retention_prefill(q, k, v, logg, _power)
        y = _quotient(num, den, eps).reshape(B, T, H, d)
    with scope("retention.end_state"):
        at_end = (_end_state_in_vmem if retention_end_state.tiles(T, d)
                  else end_state)
        S, z = at_end(k, v, logg, chunk)
    return y, S, z


def chunked(q, k, v, logg, chunk: int, eps: float,
            forward_only: bool = False):
    """The whole-sequence form from a zero state, ``chunk`` positions at a
    time.  q: (B, T, H, d); k, v: (B, T, G, d), K/V head ``g`` serving the
    query heads ``g H/G .. (g + 1) H/G - 1``; logg: (B, T, G) float32, at
    most zero.  Returns y (B, T, H, d), the state after the last position
    S (B, G, D, d) and its normaliser z (B, G, D), all float32.

    Inside a chunk position t reads j <= t through ``exp(c_t - c_j)
    (q_t . k_j)^2 / d``, ``c`` the decay's running sum over the chunk; what
    came before the chunk through ``exp(c_t) phi(q_t)`` against ``S`` and
    ``z``; the state at the chunk's end is ``exp(c_end) S + sum_j exp(c_end
    - c_j) phi(k_j) v_j^T``.  A length that is no multiple of the chunk is
    padded with positions of k = 0 and log g = 0, which leave the state as
    it is.  The products run in q's type and add up in float32.

    ``forward_only`` (a decoder's prefill: no gradient will be asked): where
    :func:`direct` says so, the same results by :func:`_direct`, the sums
    over the whole sequence in one kernel and the state formed once at the
    end; anywhere else what follows."""
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.ops import _chip

    f32, cdt = jnp.float32, q.dtype
    B, T, H, d = q.shape
    if direct(forward_only, _chip._traced_for_tpus(), T, d):
        return _direct(q, k, v, logg, chunk, eps)
    G = k.shape[2]
    R, Q = H // G, min(chunk, T)
    nc = -(-T // Q)

    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def one(state, block):
        S, z = state
        q, k, v, lg = block
        q = q.reshape(B, Q, G, R, d)
        cs = jnp.cumsum(lg, axis=1)                     # (B, Q, G), inclusive
        # inside the chunk: the quadratic form, no expansion
        sc = jnp.einsum("bqgrd,bkgd->bgrqk", q, k, preferred_element_type=f32)
        at = jnp.moveaxis(cs, 1, -1)                    # (B, G, Q)
        seg = at[:, :, None, :, None] - at[:, :, None, None, :]
        a = _power(sc) * (1.0 / d) * jnp.exp(jnp.where(causal, seg, -jnp.inf))
        num = jnp.einsum("bgrqk,bkgv->bqgrv", a.astype(cdt), v,
                         preferred_element_type=f32)
        den = jnp.moveaxis(a.sum(-1), -1, 1)            # (B, Q, G, R)
        # what came before the chunk, through the state
        pq = phi(q, 1.0 / d).astype(cdt)                # (B, Q, G, R, D)
        seen = jnp.exp(cs)
        # (this one as float32 operands of the compute type's values, one
        # bfloat16 pass on a TPU: the CPU's runtime multiplies no bfloat16
        # pair into float32 in this order of axes, and with K/V heads moved
        # before positions first a prefill took 3.94 s for 2.96, PR 49)
        num = num + seen[..., None, None] * jnp.einsum(
            "bqgrn,bgnv->bqgrv", pq.astype(f32), S.astype(cdt).astype(f32),
            precision=lax.Precision.HIGHEST if cdt == f32 else None)
        den = den + seen[..., None] * jnp.einsum(
            "bqgrn,bgn->bqgr", pq, z.astype(cdt), preferred_element_type=f32)
        y = _quotient(num, den, eps)
        # the state at the chunk's end
        pk = phi(k) * jnp.exp(cs[:, -1:] - cs)[..., None]   # (B, Q, G, D)
        last = jnp.exp(cs[:, -1])                       # (B, G)
        S = S * last[..., None, None] + jnp.einsum(
            "bkgn,bkgv->bgnv", pk.astype(cdt), v, preferred_element_type=f32)
        z = z * last[..., None] + pk.sum(axis=1)
        return (S, z), y

    D = state_dim(d)
    (S, z), y = lax.scan(
        one, (jnp.zeros((B, G, D, d), f32), jnp.zeros((B, G, D), f32)),
        tuple(_blocks(t, Q) for t in (q, k, v, logg)))
    return jnp.moveaxis(y, 0, 1).reshape(B, nc * Q, H, d)[:, :T], S, z


def read(S, z, q, k, v, logg, eps: float):
    """What the query heads q (B, G, R, d) of each K/V head read at a new
    position with k, v (B, G, d) and logg (B, G), from the state the step
    starts from, ``S`` (B, G, D, d) and ``z`` (B, G, D) float32, read once
    for the R heads: ``S_t^T phi(q) = g_t S_{t-1}^T phi(q) + (phi(k_t) .
    phi(q)) v_t``, the normaliser alike, and the quotient.  Taken from the
    old state so that the read does not wait for the write and the new state
    exists in the carry alone.  ``S`` may be the layer where it lies, swept
    (:class:`InPlace`).  Returns y (B, G, R, d) float32."""
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    g = jnp.exp(logg.astype(f32))
    pk, pq = phi(k), phi(q, 1.0 / k.shape[-1])
    own = jnp.sum(pk[:, :, None] * pq, axis=-1)         # (B, G, R): a(t, t)
    if isinstance(S, InPlace):      # the kernel's sweep added them up
        sums = S.sums
    else:
        # a float32 product on the matrix unit: as a sum of products the
        # compiler first copies the layer's state out of the stack, two
        # passes over it more, and a step took 66 ms for 35 (PR 49, on the
        # chip)
        sums = jnp.einsum("bgnv,bgrn->bgrv", S, pq,
                          precision=lax.Precision.HIGHEST)
    num = (g[..., None, None] * sums
           + own[..., None] * v.astype(f32)[:, :, None])
    den = g[..., None] * jnp.sum(z[:, :, None] * pq, axis=-1) + own
    return _quotient(num, den, eps)


def write(S, z, k, v, logg):
    """The state after a new position: ``g S + phi(k) v^T`` and ``g z +
    phi(k)``, float32; of a layer where it lies (:class:`InPlace`), the
    layer with what its sweep is to add."""
    import jax.numpy as jnp

    f32 = jnp.float32
    g, pk, v = jnp.exp(logg.astype(f32)), phi(k), v.astype(f32)
    if isinstance(S, InPlace):
        return dataclasses.replace(S, adds=(g, pk, v)), z * g[..., None] + pk
    return (S * g[..., None, None] + pk[..., None] * v[..., None, :],
            z * g[..., None] + pk)


def update(S, z, q, k, v, logg, eps: float):
    """The recurrence once: :func:`read` and :func:`write`; y, S, z."""
    return (read(S, z, q, k, v, logg, eps), *write(S, z, k, v, logg))


def state_shapes(cfg, batch: int) -> tuple:
    """One layer's carried state for ``batch`` sequences: the K/V heads'
    matrix states ``(B, G, D, d)`` and their normalisers ``(B, G, D)``."""
    G, d = cfg.kv_heads, cfg.head_dim
    return (batch, G, state_dim(d), d), (batch, G, state_dim(d))


def carry(cfg, mesh, batch: int, t_max: int) -> list:
    """What a decoder carries for the core, zeros: every layer's
    :func:`state_shapes` stacked over layers (no ``t_max`` in them, and no
    K/V cache beside them) in ``state_dtype``.  A step updates layer ``l``'s
    in place."""
    import jax.numpy as jnp

    return [jnp.zeros((cfg.n_layers, *shape), cfg.retention.state_dtype)
            for shape in state_shapes(cfg, batch)]


def grows(cfg) -> tuple:
    """Of each of :func:`carry`'s stacks: whether it grows with the sequence.
    Neither does, so a decoder hands them from its prefill program to the
    generating one as they are (``decode._two_programs``)."""
    return (False, False)


def carried(cfg, mesh, collected, t_max: int, into=None, **group) -> list:
    """Every layer's state and normaliser after the last position of whole
    sequences (or a carry a prefill program filled), the next two of the
    iterator ``collected``, as :func:`carry`'s stacks (``block.written``)."""
    from ompi_tpu.models.block import written

    return [written(next(collected), t_max, stack, axis=None, **group)
            for stack in into or (None, None)]


@dataclasses.dataclass(frozen=True)
class InPlace:
    """Layer ``layer`` of the stacked matrix states where it lies: a cached
    step's ``S`` where the update is the kernel's one pass
    (``ops/retention_update.py``), in which nothing may slice the layer out
    of the stack (a slice handed to a kernel and written back is four
    passes more, not one fewer).  :func:`write` leaves what it adds in
    ``adds``, :meth:`swept` is the pass, and :func:`read` takes what the
    query heads read of the state before it from ``sums``."""
    stack: object               # (L, B, G, D, d)
    layer: object               # a traced int32
    adds: tuple = None          # (g, phi(k), v) of the position
    sums: object = None         # S^T phi(q), (B, G, R, d)

    # what an array in its place would say of itself: float32, as
    # :func:`_state_before` gives a layer
    shape = property(lambda self: self.stack.shape[1:])
    dtype = property(lambda self: np.dtype(np.float32))

    def swept(self, pq):
        """The kernel's pass for the query heads' ``pq`` (B, G, R, D): the
        stack after the write, and what they read of the state before it."""
        from ompi_tpu.ops.retention_update import retention_update

        sums, stack = retention_update(self.stack, self.layer, pq, *self.adds)
        return dataclasses.replace(self, stack=stack, sums=sums)


def _state_before(stack, layer):
    """A cached step's state of layer ``layer`` as the update starts from
    it, float32: an array, or the stack of matrix states itself where the
    update is the kernel's (``retention_update.block``: traced for TPUs, a
    float32 state of heads that tile), :class:`InPlace`."""
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.ops import _chip, retention_update

    if stack.ndim == 5 and retention_update.block(
            _chip._traced_for_tpus(), stack.dtype, *stack.shape[3:]):
        return InPlace(stack, layer)
    return lax.dynamic_index_in_dim(stack, layer,
                                    keepdims=False).astype(jnp.float32)


def core(cfg, q, k, v, logg, carry=None, forward_only: bool = False):
    """The core on a layer's rotated q (B, T, H, d) and k, its v (B, T, G,
    d) and ``log g`` (B, T, G).

    ``carry`` None: whole sequences from a zero state (:func:`chunked`,
    which is told whether a gradient may be asked: ``forward_only``); returns
    ``(y, (S, z))``, y (B, T, H, d) float32 and the layer's state after the
    last position as the carry stores it.  ``carry = ((S, z), layer)``: T == 1
    against layer ``layer`` of the stacks (:func:`carry`'s), read and written
    in place, by the kernel where :func:`_state_before` hands the matrix
    states over as they lie and by :func:`read`, a barrier and :func:`write`
    on a slice anywhere else; returns ``(y, [S, z])``, the stacks."""
    from jax import lax

    from ompi_tpu.core.scopes import scope

    rt = cfg.retention
    B, T, H, d = q.shape
    G = k.shape[2]
    if carry is None:
        with scope("attention"), scope("retention.scan"):
            # every argument by position: a control's wrapper of ``chunked``
            # hands what follows the gate on as it came
            y, S, z = chunked(q, k, v, logg, rt.chunk, rt.eps, forward_only)
        return y, (S.astype(rt.state_dtype), z.astype(rt.state_dtype))
    (S_c, z_c), layer = carry
    now = k[:, 0], v[:, 0], logg[:, 0]
    heads = q[:, 0].reshape(B, G, H // G, d)
    with scope("attention"), scope("retention.update"):
        S, z = _state_before(S_c, layer), _state_before(z_c, layer)
        if isinstance(S, InPlace):
            # one pass over the layer where it lies, which adds what
            # ``write`` says and sums what ``read`` reads
            S, z_new = write(S, z, *now)
            S = S.swept(phi(heads, 1.0 / d))
            y, S_c = read(S, z, heads, *now, rt.eps), S.stack
        else:
            y = read(S, z, heads, *now, rt.eps)
            # the read is done before the write begins: left to itself the
            # compiler writes first and keeps a copy of the old state to
            # read
            (S_c, z_c), y = lax.optimization_barrier(((S_c, z_c), y))
            S, z_new = write(_state_before(S_c, layer),
                             _state_before(z_c, layer), *now)
            S_c = lax.dynamic_update_slice(S_c, S.astype(S_c.dtype)[None],
                                           (layer, 0, 0, 0, 0))
    # the normaliser's write is the carry's write every decoder's programs
    # have under this name; the matrix state's is the update's own: the
    # kernel's, or a fusion the compiler makes of it with the decay
    with scope("kv_cache"):
        z_c = lax.dynamic_update_slice(z_c, z_new.astype(z_c.dtype)[None],
                                       (layer, 0, 0, 0))
    return y.reshape(B, 1, H, d), [S_c, z_c]
