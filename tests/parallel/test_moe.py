"""Dropless top-k routing (``parallel/moe.routed_moe``) against a loop over
experts in numpy, and what a routed configuration refuses."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

def _gelu_tanh(x):
    return 0.5 * x * (1 + np.tanh(np.sqrt(2 / np.pi) * (x + 0.044715 * x ** 3)))


def _routed_params(rng, D, F, E, gated, skewed):
    p = {"wg": rng.normal(0, D ** -0.5, size=(D, E)),
         "w1": rng.normal(0, D ** -0.5, size=(E, D, F)),
         "w2": rng.normal(0, F ** -0.5, size=(E, F, D))}
    if gated:
        p["w3"] = rng.normal(0, D ** -0.5, size=(E, D, F))
    if skewed:
        p["wg"][0, 0] = 4.0     # with x[..., 0] = 3: +12 on expert 0's logit
    return {k: v.astype(np.float32) for k, v in p.items()}


def _routed_oracle(x, p, k, gated, held=None, zero=0):
    """numpy, float64, a loop over experts: every token's k most probable
    outputs of the router, each weighted by its probability as it is;
    returns the layer's output and the rows each expert got.  ``held``
    (first, count): the loop is over those experts alone, whose matrices
    ``p`` holds.  ``zero``: the router's last outputs add the token itself."""
    D = x.shape[-1]
    xf = x.reshape(-1, D).astype(np.float64)
    p = {name: w.astype(np.float64) for name, w in p.items()}
    logits = xf @ p["wg"]
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    best = np.argsort(-probs, axis=-1, kind="stable")[:, :k]
    y, rows = np.zeros_like(xf), []
    first = held[0] if held else 0
    for e in range(p["w1"].shape[0]):
        mine = np.where((best == first + e).any(axis=-1))[0]
        rows.append(len(mine))
        h = xf[mine] @ p["w1"][e]
        if gated:
            h = h / (1 + np.exp(-h)) * (xf[mine] @ p["w3"][e])
        else:
            h = _gelu_tanh(h)
        y[mine] += probs[mine, first + e][:, None] * (h @ p["w2"][e])
    for e in range(probs.shape[-1] - zero, probs.shape[-1]):
        mine = np.where((best == e).any(axis=-1))[0]
        y[mine] += probs[mine, e][:, None] * xf[mine]
    return y.reshape(x.shape), rows


def _routed_jnp(x, p, k, gated, held=None, zero=0):
    """The oracle again in jax.numpy (every expert on every token, under a
    mask), for its gradient."""
    xf = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(xf @ p["wg"], axis=-1)
    kth = jnp.sort(probs, axis=-1)[:, -k, None]
    weight = jnp.where(probs >= kth, probs, 0.0)
    first, count = held or (0, p["w1"].shape[0])
    h = jnp.einsum("td,edf->etf", xf, p["w1"])
    if gated:
        h = jax.nn.silu(h) * jnp.einsum("td,edf->etf", xf, p["w3"])
    else:
        h = jax.nn.gelu(h)
    y = jnp.einsum("etf,efd,te->td", h, p["w2"],
                   weight[:, first:first + count])
    if zero:
        y = y + jnp.sum(weight[:, -zero:], axis=-1, keepdims=True) * xf
    return y.reshape(x.shape)


ROUTED = [pytest.param(8, 2, True, None, 0, 24, id="8x2-gated"),
          pytest.param(8, 2, False, None, 0, 24, id="8x2-gelu"),
          pytest.param(4, 1, False, None, 0, 24, id="4x1-gelu"),
          pytest.param(16, 4, True, None, 0, 24, id="16x4-gated"),
          pytest.param(64, 8, True, None, 0, 24, id="64x8-gated")]
# six and ten picks a token (cells 10 and 12, cell 13), every expert here or
# half of the router's, 42 tokens (no multiple of 16): the whole layout, and
# picks that do not fill a float32 tile's eight sublanes
PICKS = [pytest.param(16, 6, False, None, 0, 21, id="16x6-gelu"),
         pytest.param(16, 6, True, (0, 8), 0, 21, id="16x6-held8"),
         pytest.param(24, 10, True, None, 0, 21, id="24x10-gated"),
         pytest.param(24, 10, False, (0, 12), 0, 21, id="24x10-held12")]
# a device that holds 2 of a router's 64 outputs (16 of them identity
# experts): few enough of a wide router that ``routed_moe`` works through
# windows of the held picks (32 rows a window of the 384 routed)
WINDOWED = [pytest.param(64, 8, True, (0, 2), 0, 24, id="64x8-held2"),
            pytest.param(64, 8, True, (0, 2), 16, 24, id="64x8-held2-zero16")]


def _routed_case(E, gated, skewed, seed, held=None, zero=0, T=24):
    rng = np.random.default_rng(seed)
    B, D, F = 2, 32, 16
    x = rng.normal(size=(B, T, D)).astype(np.float32)
    if skewed:
        x[..., 0] = 3.0
    p = _routed_params(rng, D, F, E, gated, skewed)
    if held:    # the router stays E wide; the matrices are the held experts'
        p = {name: w if name == "wg" else w[held[0]:held[0] + held[1]]
             for name, w in p.items()}
    return x, p


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("skewed", [False, True], ids=["spread", "skewed"])
@pytest.mark.parametrize("E,k,gated,held,zero,T", ROUTED + PICKS + WINDOWED)
def test_routed_moe_equals_a_loop_over_experts(E, k, gated, held, zero, T,
                                               skewed, kernel):
    """No capacity: with a router that sends every token to expert 0 first,
    that expert gets all 48 rows (three and more tiles of 16) while others
    get none, and every token still has all k of its experts' results and
    nothing of the rows that fill a tile up.  Through the pallas kernel (in
    interpret mode here) and through XLA's ragged_dot, which the model
    runs off the TPU.  In windows too: the held picks of a spread router
    fit the first window of 32 rows; skewed, expert 0's 48 rows overflow
    it, a second window runs, and no pick is dropped.  And with six and ten
    picks a token, where half the router's experts are held here too (the
    whole layout still: some of every token's picks weigh nothing)."""
    from ompi_tpu.parallel.moe import _window_rows, routed_moe

    x, p = _routed_case(E, gated, skewed, seed=E + k, held=held, zero=zero,
                        T=T)
    want, rows = _routed_oracle(x, p, k, gated, held, zero)
    n = x.shape[0] * x.shape[1]
    if held is None:
        assert sum(rows) == n * k                       # nothing dropped
    if held is None or 2 * held[1] == E:                # the whole layout
        assert _window_rows(n * k, 16, held[1] if held else E, E) == n * k
    else:
        cap = _window_rows(n * k, 16, held[1], E)
        assert cap == 32 and (sum(rows) > cap) == skewed
    if skewed:
        assert rows[0] == n and min(rows) < 16
    got = jax.jit(lambda a, q: routed_moe(
        a, q, k, gated=gated, kernel=kernel, held=held, zero=zero))(x, p)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("E,k,gated,held,zero,T",
                         ROUTED[:4] + PICKS + WINDOWED)
def test_routed_moe_gradient(E, k, gated, held, zero, T, kernel):
    """Through the kernel's custom_vjp, the gathers and the float32 router,
    against the gradient of the masked dense form; and, in windows (the
    skewed router overflows the first), through the loop over them."""
    from ompi_tpu.parallel.moe import routed_moe

    x, p = _routed_case(E, gated, skewed=True, seed=7, held=held, zero=zero,
                        T=T)
    target = np.random.default_rng(1).normal(size=x.shape).astype(np.float32)

    def loss(layer, **how):
        return lambda a, q: ((layer(a, q, k, gated, held=held, zero=zero,
                                    **how) - target) ** 2).sum()

    got = jax.jit(jax.grad(loss(routed_moe, kernel=kernel),
                           argnums=(0, 1)))(x, p)
    want = jax.grad(loss(_routed_jnp), argnums=(0, 1))(x, p)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.abs(np.asarray(w)).max() > 0
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3,
                                   atol=2e-4 * np.abs(np.asarray(w)).max())


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("n,k", [(42, 6), (42, 10), (48, 8), (5, 1)])
def test_the_sum_of_a_tokens_picks_is_the_sum_over_one_gather(n, k, dtype):
    """The whole layout's sum, over rows gathered picks major, against the
    two lines it was until PR 71 (the rows gathered tokens major, widened,
    and a sum over the middle axis of picks): the same rows, the same
    weights, float32 both, so equal to the rounding of a sum taken in another
    order; every third weight is zero, as a pick's is whose expert is held
    elsewhere and whose slot is any."""
    from ompi_tpu.parallel.moe import _sum_of_picks

    rng = np.random.default_rng(n + k)
    D, rows = 256, n * k + 64
    out = jnp.asarray(rng.normal(size=(rows, D)), dtype)
    slot = jnp.asarray(rng.integers(0, rows, size=(n, k)), jnp.int32)
    gate = rng.uniform(size=(n, k)).astype(np.float32)
    gate.reshape(-1)[::3] = 0.0
    got = jax.jit(_sum_of_picks)(out, slot.T.reshape(-1), gate)
    was = out[slot.reshape(-1)].reshape(n, k, D).astype(jnp.float32)
    want = jnp.sum(was * gate[:, :, None], axis=1)
    assert got.dtype == jnp.float32 and got.shape == (n, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6 * k)


@pytest.mark.parametrize("held,windows", [
    pytest.param(None, 0, id="every-expert"),
    pytest.param((0, 32), 0, id="half"),        # Kimi-Linear's share
    pytest.param((0, 2), 12, id="2-of-64")])
def test_routed_moe_takes_windows_where_few_of_a_wide_router_are_held(
        held, windows):
    """The form is read off static shapes: a device that holds every expert,
    or half of them, lowers to the whole layout (its three grouped calls
    take ``n k + E tm`` rows, under no branch and no loop but
    ``searchsorted``'s own); one that holds 2 of 64 to a loop over windows
    of ``cap + E tm`` rows with the window's body under a branch."""
    from ompi_tpu.parallel.moe import routed_moe

    E, k, tm = 64, 8, 16
    x, p = _routed_case(E, True, False, seed=3, held=held)
    text = jax.jit(lambda a, q: routed_moe(a, q, k, gated=True, held=held)
                   ).lower(x, p).as_text()
    count = held[1] if held else E
    laid_out = (32 if windows else x.shape[0] * x.shape[1] * k) + count * tm
    # off the TPU a grouped call is ``ragged_dot``, lowered here to one
    # product of every held expert's matrix with every laid-out row
    calls = [line for line in text.splitlines()
             if "stablehlo.dot_general" in line and "HIGHEST" not in line]
    assert len(calls) == 3
    assert all(f"tensor<{count}x{laid_out}x" in line for line in calls)
    assert text.count("stablehlo.case") == (1 if windows else 0)
    assert text.count("stablehlo.while") == (2 if windows else 1)


def test_routed_moe_refuses_ep():
    """More than one expert a token over ep > 1 would be a ragged exchange."""
    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"dp": 1, "sp": 1, "tp": 1, "ep": 2},
                     devices=jax.devices()[:2])
    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=2, n_layers=1,
                                d_ff=16, seq=16, attention="xla",
                                moe_experts=4, moe_top_k=2, remat=False)
    with pytest.raises(ValueError, match="ragged"):
        jax.jit(tfm.make_loss_fn(cfg, mesh)).lower(
            tfm.init_params(cfg), np.zeros((2, 16), np.int32))


def test_a_routed_configuration_without_moe_top_k_is_refused_by_name():
    """``moe_experts`` without ``moe_top_k`` was the top-1 switch until PR
    75; it is now an error that names the field to set, raised while the
    program is traced."""
    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"dp": 1, "sp": 1, "tp": 1}, devices=jax.devices()[:1])
    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=2, n_layers=1,
                                d_ff=16, seq=16, attention="xla",
                                moe_experts=4, remat=False)
    with pytest.raises(ValueError, match=r"moe_top_k >= 1.*moe_top_k=0"):
        jax.jit(tfm.make_loss_fn(cfg, mesh)).lower(
            tfm.init_params(cfg), np.zeros((2, 16), np.int32))
