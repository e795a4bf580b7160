"""The selective state-space mixer (Mamba-1) alone in a layer: the kind
"selective" of a layer plan (``models/plan.py``).

Its recurrence decays a channel *and* a state element, ``S_t = exp(dt_t A) *
S_{t-1} + (dt_t x_t) (x) B_t`` with ``A`` of ``(N, Di)`` and ``dt`` a channel,
so a chunk has no matrix form (``ssm.chunked_scan``'s is Mamba-2's, one decay
a head): a whole sequence is scanned a position at a time.  On any backend
that is :func:`scan`, a differentiable ``lax.scan`` (a trainer's, the CPU's);
a decoder's prefill traced for TPUs takes the pallas ``ops/selective_scan.py``
where the sizes tile (:func:`fused`), which keeps a block of the state in
registers and writes no ``(T, Di, N)`` temporary.

What a layer carries: the convolution's last ``d_conv - 1`` inputs ``(B,
d_conv - 1, Di)`` in the compute type and the state ``(B, N, Di)`` in
``state_dtype``, the state's element the leading axis so that a channel lies
on the lanes; neither grows.  The mixer also hands on its scan output ``y_t =
S_t C_t + D x_t`` before the gate (``hands``: a row of the plan may read it,
``LayerPlan.reads``), in the compute type.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Selective", "leaf_shapes", "buffers", "mixer", "scan", "fused",
           "hands", "reads", "POSITIONED"]

POSITIONED = False


@dataclasses.dataclass(frozen=True)
class Selective:
    """``d_inner`` channels over a state of ``d_state`` each, a causal
    depthwise convolution of ``d_conv`` taps with a bias, the step ``dt`` out
    of a projection of rank ``dt_rank``; ``state_dtype``: what the state is
    carried in."""
    d_inner: int
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 0
    state_dtype: str = "float32"


def hands(sz: Selective):
    """What a reader of this kind's rows gets: the scan's output of the same
    pass, which :func:`mixer` returns last."""
    return "output"


def reads(sz: Selective):
    return None


def _a_log(rng, shape):
    """``A = -(1 .. N)`` in every channel, the family's initializer."""
    import numpy as np

    n = shape[-2]
    return np.broadcast_to(np.log(np.arange(1, n + 1, dtype=np.float32))
                           [:, None], shape).copy()


def _dt_bias(rng, shape):
    """The inverse softplus of steps log-uniform in [1e-3, 1e-1]."""
    import numpy as np

    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), size=shape))
    return (dt + np.log(-np.expm1(-dt))).astype(np.float32)


def leaf_shapes(cfg, sz: Selective) -> dict:
    """One layer's leaves: name -> (shape, deviation of the program's own
    initializer, None for ones, or a draw ``(rng, shape)``)."""
    from ompi_tpu.models.ssm import _zeros

    D, Di, N, R = cfg.d_model, sz.d_inner, sz.d_state, sz.dt_rank
    return {
        "sel_in": ((D, 2 * Di), D ** -0.5),
        "sel_conv": ((sz.d_conv, Di), sz.d_conv ** -0.5),
        "sel_convb": ((Di,), _zeros),
        "sel_x": ((Di, R + 2 * N), Di ** -0.5),
        "sel_dt": ((R, Di), R ** -0.5),
        "sel_dtb": ((Di,), _dt_bias),
        "sel_alog": ((N, Di), _a_log),
        "sel_d": ((Di,), None),
        "sel_out": ((Di, D), Di ** -0.5 / max(1, 2 * cfg.n_layers) ** 0.5),
    }


def buffers(cfg, sz: Selective, batch: int, t_max: int) -> tuple:
    """What a decoder carries for one layer (``models/plan.py``'s form)."""
    return (((batch, sz.d_conv - 1, sz.d_inner), cfg.compute_dtype, None),
            ((batch, sz.d_state, sz.d_inner), sz.state_dtype, None))


def scan(x, dt, a, b, c):
    """The recurrence over whole sequences from a zero state, a position at a
    time.  x, dt: (B, T, Di) float32; a: (N, Di); b, c: (B, T, N).  Returns
    ``y`` (B, T, Di) with ``y_t = S_t c_t`` and the last state (B, N, Di)."""
    import jax.numpy as jnp
    from jax import lax

    def step(S, at):
        x_t, dt_t, b_t, c_t = at
        S = (jnp.exp(dt_t[:, None, :] * a) * S
             + (dt_t * x_t)[:, None, :] * b_t[:, :, None])
        return S, jnp.sum(S * c_t[:, :, None], axis=1)

    last, ys = lax.scan(
        step, jnp.zeros((x.shape[0], *a.shape), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(ys, 0, 1), last


def _conv_before(conv_c):
    """The convolution's last inputs as a cached step reads them (a
    benchmark's control plants a wrong one here while a decoder is traced;
    the same of :func:`_state_before` and :func:`_memory`)."""
    return conv_c


def _state_before(state_c):
    """The state a cached step starts from, float32."""
    import jax.numpy as jnp

    return state_c.astype(jnp.float32)


def _memory(y, skip, z):
    """What the mixer hands a reader: the scan's output ``y`` with the ``D
    x`` term ``skip`` in it, before the gate ``z``."""
    return y


def fused(sz: Selective, T: int, forward_only: bool) -> bool:
    """Whether a whole-sequence pass takes the pallas scan: static facts
    alone.  A decoder's prefill (the kernel has no backward pass), traced for
    TPUs, over sizes the kernel's blocks tile."""
    from ompi_tpu.ops import _chip

    if not (forward_only and _chip._traced_for_tpus()):
        return False
    from ompi_tpu.ops import selective_scan

    return selective_scan.tiles(T, sz.d_inner, sz.d_state)


def mixer(cfg, lp, h, carry=None, forward_only: bool = False):
    """One layer's mixer on the layer's input ``h`` (B, T, D): the norm, the
    mixer and the residual add of the branch times the plan's
    ``branch_factor``.

    ``carry`` None: whole sequences from a zero state; returns ``(h,
    conv_state, state, m)``, the convolution's last inputs, the state after
    the last position and the scan's output ``m`` (B, T, Di) before the gate.
    ``carry = (conv_c, state_c)``: T == 1 against this layer's own two
    buffers; returns ``(h, conv_c, state_c, m)``."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.models import transformer as tfm

    sz = cfg.plan.selective
    f32, cdt = jnp.float32, jnp.dtype(cfg.compute_dtype)
    B, T, _ = h.shape
    N, R, K = sz.d_state, sz.dt_rank, sz.d_conv
    with scope("ssm_proj"):
        # the norm is called through the module: a benchmark's control
        # plants a wrong one there while a decoder is traced
        u = tfm._norm(h, lp["ln1"], cfg.norm_eps,
                      lp.get("ln1b")).astype(cdt)
        x, z = jnp.split(jnp.einsum("btd,df->btf", u,
                                    lp["sel_in"].astype(cdt)), 2, axis=-1)
    with scope("ssm.conv"):
        taps = lp["sel_conv"].astype(f32)
        before = (jnp.zeros((B, K - 1, x.shape[-1]), cdt) if carry is None
                  else _conv_before(carry[0]).astype(cdt))
        padded = jnp.concatenate([before, x], axis=1)
        conv_out = padded[:, T:]
        x = jax.nn.silu(lp["sel_convb"].astype(f32) + sum(
            padded[:, k:k + T].astype(f32) * taps[k] for k in range(K)))
    with scope("ssm_proj"):
        r, b, c = jnp.split(
            jnp.einsum("btf,fr->btr", x.astype(cdt), lp["sel_x"].astype(cdt),
                       preferred_element_type=f32), [R, R + N], axis=-1)
        dt = jax.nn.softplus(
            jnp.einsum("btr,rf->btf", r.astype(cdt), lp["sel_dt"].astype(cdt),
                       preferred_element_type=f32)
            + lp["sel_dtb"].astype(f32))
        a = -jnp.exp(lp["sel_alog"].astype(f32))
    if carry is None:
        with scope("ssm.scan"):
            if fused(sz, T, forward_only):
                from ompi_tpu.ops.selective_scan import selective_scan

                y, state = selective_scan(x, dt, a, b, c)
            else:
                y, state = scan(x, dt, a, b, c)
            state = state.astype(sz.state_dtype)
    else:
        with scope("ssm.update"):
            S = _state_before(carry[1])
            dt1, x1 = dt[:, 0], x[:, 0]
            S = (jnp.exp(dt1[:, None, :] * a) * S
                 + (dt1 * x1)[:, None, :] * b[:, 0, :, None])
            y = jnp.sum(S * c[:, 0, :, None], axis=1)[:, None]
            state = S.astype(carry[1].dtype)
    with scope("ssm_proj"):
        skip = lp["sel_d"].astype(f32) * x
        y = y + skip
        m = _memory(y, skip, z).astype(cdt)
        s = jnp.einsum("btf,fd->btd",
                       (y * jax.nn.silu(z.astype(f32))).astype(cdt),
                       lp["sel_out"].astype(cdt))
        if cfg.plan.branch_factor != 1:
            s = s * cfg.plan.branch_factor
        return h + s, conv_out.astype(cdt), state, m
