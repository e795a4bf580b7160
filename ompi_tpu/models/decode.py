"""Autoregressive decoding with a KV cache — the inference counterpart
of the train step, built from the same layer (``models/block.block``, one
function for the whole-sequence pass and for the cached step).

TPU-first shape: ONE compiled program per (prompt_len, max_new) pair —
prefill runs the training backbone (``collect_kv`` returns what every
layer's mixers hand over, in a single pass), then a ``lax.scan`` generates
tokens against a static-shape carry (no growing arrays, no recompilation per
token).  Where every mechanism says which of its buffers grow with the
sequence (``grows``: a layer plan, power retention) the decoder is two
programs, the prefill one of its own (:func:`_two_programs`).

The life of the carry: it is allocated once at its final length, and from
then on it is loop carry — of the token scan and, inside it, of a
``lax.fori_loop`` over the layer index, in which a layer writes its new
state in place and reads its own through a slice.  It is never the ``xs`` or
``ys`` of a scan: those are separate buffers, and a step would then copy
every layer's state out of the stack and back
(``tests/parallel/test_decode.py`` holds the compiled program to this).
What it holds is not this module's business: a list of stacks for each
mechanism the configuration has (``block.mechanisms``), which the
mechanism's module makes (``carry``), fills from what a whole-sequence pass
collected (``carried``) and reads and writes in a step; each module says
what it carries and why it is laid out so.

The prefill hands the carry over.  By default it is one pass over every
prompt whose collected states are padded to the carry's length.  With
``TransformerConfig.prefill_tokens`` (and for a hybrid block or a plan,
always) the carry is allocated first at its final size and the prompts are
prefilled a group of whole sequences at a time, each group writing its
states into it, so the pass's temporaries are a group's and not the batch's.

Sharding: batch over dp, heads over tp (the cache is
head-sharded exactly like the weights), the table's rows over tp where
``param_specs`` splits them: a rank looks up the tokens whose rows it
holds and a psum over tp completes them (``transformer._lookup``), and it
makes the logits of its own rows, which are gathered over tp
(``transformer._whole_vocab``), so greedy argmax, sampling and
``keep_logits`` see the full vocab.  Sequence parallelism is a
training-time layout — decode requires sp == 1.  Routed configurations
(``moe_top_k >= 1``) send each generated token through the same layer as
training and prefill; the routing has no capacity, so a token's experts and
their weights depend on that token alone, and the cached step and the full
forward agree at any batch, up to the order of summation.
"""

from __future__ import annotations

import contextlib
import functools

from ompi_tpu.models.transformer import (TransformerConfig, _head, _rmsnorm,
                                         layer_leaves, param_specs)
from ompi_tpu.parallel.moe import EXPERT_LEAVES

__all__ = ["make_decoder"]


def _prefill_group(batch: int, prompt_len: int, prefill_tokens: int) -> int:
    """Sequences one pass of the prefill holds: the most that divide the
    batch and stay within ``prefill_tokens`` tokens (0: the whole batch)."""
    if not prefill_tokens:
        return batch
    return max(g for g in range(1, batch + 1)
               if batch % g == 0 and (g == 1 or g * prompt_len
                                      <= prefill_tokens))


def make_decoder(cfg: TransformerConfig, mesh, max_new: int,
                 temperature: float = 0.0, top_k: int = 0,
                 keep_logits: int = 0):
    """jitted (params, prompt (B, Tp) int32[, seed]) → (B, Tp+max_new).

    Greedy decode by default: prefill through the training backbone (what
    each layer's mixers hand over collected), then ``max_new`` single-token
    steps over the static carry.  Requires sp == 1; dense, routed
    (dropless top-k) MoE, hybrid (``models/ssm.py``), indexed
    (``models/sparse_index.py``), planned (``models/plan.py``) and
    power-retention (``models/retention.py``) configs are supported (MoE
    routes each token through the same layer as training).

    The carry of the token scan and of the loop over layers inside it is a
    list of stacks for each of ``block.mechanisms(cfg)``, ``Tp + max_new``
    positions long (the mechanism's ``carry``; its module says what is in
    it).  The prefill runs in one pass and pads what it collected to that
    length; with ``cfg.prefill_tokens``, a hybrid block or a plan it runs a
    group of whole sequences at a time, each writing into the carry.

    ``keep_logits=n``: returns ``(tokens, logits)``, ``logits`` float32
    ``(n, max_new, vocab)``: what each generated token of the first ``n``
    sequences was picked from, the prefill's logits for the first and the
    cached step's after.  Needs dp == 1.

    The embedding and the head are read as :func:`transformer.param_specs`
    places them; where their rows are split over ``tp`` every rank still
    picks from the whole vocabulary (the module's docstring says how).

    ``temperature > 0`` switches to sampling (optionally truncated to
    the ``top_k`` highest logits); the returned callable then takes a
    third argument ``seed`` (int32 scalar).  Each step folds the
    position — and the dp coordinate, so data-parallel shards draw
    independent noise — into the key; tp ranks share the key and hence
    agree on every sampled token (their logits are identical).
    """
    from ompi_tpu.core import scopes

    from ompi_tpu.models import block as blk

    with scopes.host("build.decoder", program="decode"):
        # a carry whose every mechanism says which buffers grow can be
        # handed from one program to the next
        split = all(hasattr(mechanism, "grows")
                    for mechanism in blk.mechanisms(cfg))
        return (_two_programs if split else _one_program)(
            cfg, mesh, max_new, temperature, top_k, keep_logits)


def _halves(cfg: TransformerConfig, mesh, max_new: int,
            temperature: float, top_k: int, keep_logits: int):
    """A decoder's two halves, per device (under ``shard_map``), and the
    head both multiply by, ``unembedding(params)`` in the compute type:
    ``prefill(params, head, prompt, seed) -> (tok0, logits, stacks)``, the
    first token, the logits (B, V) it was picked from and the carry, and
    ``generate(params, head, prompt, seed, tok0, logits, stacks)``, what the
    decoder returns and the carry after the last step."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.models import block as blk
    from ompi_tpu.models import transformer as tfm

    for ax in ("dp", "sp", "tp"):
        if ax not in mesh.shape:
            raise ValueError(f"decode needs a mesh with dp/sp/tp axes "
                             f"(missing {ax!r}; have {tuple(mesh.shape)})")
    if int(mesh.shape["sp"]) != 1:
        raise ValueError("decode requires sp == 1 (sequence parallelism "
                         "is a training-time layout)")
    if keep_logits and int(mesh.shape["dp"]) != 1:
        raise ValueError(f"keep_logits={keep_logits} hands back the first "
                         f"sequences' logits whole and needs dp == 1; the "
                         f"mesh has dp={mesh.shape['dp']}")
    hy, mechanisms = cfg.hybrid, blk.mechanisms(cfg)
    for mechanism in mechanisms:
        mechanism.check_mesh(cfg, mesh)
    comm, cdt = tfm._mesh_comm(mesh), jnp.dtype(cfg.compute_dtype)
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if top_k and not temperature:
        raise ValueError("top_k needs temperature > 0")
    if top_k < 0 or top_k > cfg.vocab:
        raise ValueError(f"top_k must be in [0, vocab={cfg.vocab}], "
                         f"got {top_k}")

    def pick(logits, pos, seed):
        """Next token from (B, V) f32 logits."""
        if not temperature:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits / jnp.float32(temperature)
        if top_k:
            kth = lax.top_k(scaled, top_k)[0][..., -1:]
            scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(seed), pos),
            lax.axis_index("dp"))
        return jax.random.categorical(key, scaled,
                                      axis=-1).astype(jnp.int32)

    def carried(collected, t_max, stacks=None, **group):
        """What a whole-sequence pass collected, each mechanism's own taken
        off the front by its module, as the carry: written into ``stacks``
        (``group``: which sequences), or padded to ``t_max`` positions."""
        collected = iter(collected)
        return [mechanism.carried(cfg, mesh, collected, t_max, own, **group)
                for mechanism, own in zip(
                    mechanisms, stacks or [None] * len(mechanisms))]

    def prefill_in_groups(params, prompt):
        """The carry, filled a group of sequences at a time: (last hidden
        states (B, D), stacks)."""
        B, Tp = prompt.shape
        group = _prefill_group(B, Tp, cfg.prefill_tokens)
        stacks = [mechanism.carry(cfg, mesh, B, Tp + max_new)
                  for mechanism in mechanisms]

        def one(g, carry):
            last, stacks = carry
            rows = lax.dynamic_slice_in_dim(prompt, g * group, group)
            h, collected = tfm._local_backbone(
                cfg, comm, params, rows, collect_kv=True, forward_only=True)
            stacks = carried(collected, Tp + max_new, stacks, g=g,
                             group=group)
            return (lax.dynamic_update_slice(last, h[:, -1, :],
                                             (g * group, 0)), stacks)

        return lax.fori_loop(
            0, B // group, one, (jnp.zeros((B, cfg.d_model), cdt), stacks))

    def unembedding(params):
        return _head(cfg, params).astype(cdt)

    def prefill(params, head, prompt, seed):
        B, Tp = prompt.shape
        if keep_logits > B:
            raise ValueError(f"keep_logits={keep_logits} of {B} sequences")
        # ---- prefill: the training backbone, the mixers' states collected
        with scope("prefill"):
            if hy is None and cfg.plan is None and not cfg.prefill_tokens:
                h, collected = tfm._local_backbone(
                    cfg, comm, params, prompt, collect_kv=True,
                    forward_only=True)
                stacks = carried(collected, Tp + max_new)
                last = h[:, -1, :]
            else:
                last, stacks = prefill_in_groups(params, prompt)
            logits = tfm._whole_vocab(cfg, jnp.einsum(
                "bd,vd->bv", last, head, preferred_element_type=jnp.float32))
            tok0 = pick(logits, jnp.int32(Tp - 1), seed)          # (B,)
        return tok0, logits, stacks

    def generate(params, head, prompt, seed, tok0, logits, stacks):
        Tp = prompt.shape[1]
        layer_params = {k: params[k] for k in layer_leaves(cfg)}
        # the dropless experts' kernel reads its layer out of the stack
        whole = EXPERT_LEAVES if cfg.moe_top_k else ()

        def gen(carry, _):
            stacks, tok, pos = carry
            with scope("embed"):
                h = tfm._lookup(cfg, params["emb"], tok)[:, None, :]
                if hy is not None:
                    h = h * hy.embedding_multiplier
                elif cfg.plan is not None and cfg.plan.emb_factor != 1:
                    h = h * cfg.plan.emb_factor
                if cfg.plan is not None and cfg.plan.stream_dtype:
                    h = h.astype(cfg.plan.stream_dtype)

            # the whole stacked carry is this loop's carry too; as a
            # scan's xs and ys it would be sliced out and copied back
            def per_layer(layer, state):
                lp = {k: w if k in whole
                      else lax.dynamic_index_in_dim(w, layer, keepdims=False)
                      for k, w in layer_params.items()}
                return blk.block(cfg, comm, lp, state[0], pos[None],
                                 carry=(state[1], layer, pos))

            with scope("layers"):
                if cfg.plan is not None:    # a python loop over the plan
                    from ompi_tpu.models import plan

                    h, *own = plan.step(cfg, comm, layer_params, h,
                                        stacks[0], pos)
                    stacks = [own]
                else:
                    h, stacks = lax.fori_loop(0, cfg.n_layers, per_layer,
                                              (h, stacks))
            with scope("unembed"):
                if cfg.plan is not None:    # with LayerNorm its bias too
                    h = plan._last_norm(cfg, layer_params | {
                        "lnf": params["lnf"]}, h)
                else:
                    h = _rmsnorm(h, params["lnf"], cfg.norm_eps)
                if hy is not None:
                    h = h * hy.lm_head_multiplier
                elif cfg.plan is not None and cfg.plan.logit_divisor != 1:
                    h = h / cfg.plan.logit_divisor
                logits = tfm._whole_vocab(cfg, jnp.einsum(
                    "bd,vd->bv", h[:, 0, :], head,
                    preferred_element_type=jnp.float32))
            with scope("sample"):
                nxt = pick(logits, pos, seed)
            out = (nxt, logits[:keep_logits]) if keep_logits else nxt
            return (stacks, nxt, pos + 1), out

        # emit the PRODUCED token and scan max_new-1 steps: tok0 is known
        # from prefill, so the last single-token pass is not computed just
        # to be thrown away (the scope is around the scan, not inside
        # ``gen``: a copy XLA makes of the loop's carry is the step's too)
        with scope("decode.step"):
            (stacks, _tok, _pos), toks = lax.scan(
                gen, (stacks, tok0, jnp.int32(Tp)), None,
                length=max_new - 1)
        if keep_logits:
            toks, kept = toks
            kept = jnp.concatenate([logits[None, :keep_logits], kept],
                                   axis=0).swapaxes(0, 1)
        gen_toks = jnp.concatenate([tok0[None], toks], axis=0)  # (max_new, B)
        tokens = jnp.concatenate([prompt, gen_toks.swapaxes(0, 1)], axis=1)
        return ((tokens, kept) if keep_logits else tokens), stacks

    return unembedding, prefill, generate


def _greedy(decode, temperature: float, whole=None):
    """``decode`` as :func:`make_decoder` hands it out: a call of what comes
    back is a ``run.call`` span of the host's record (``scopes.run()``)
    around the dispatches of ``decode``'s programs, and nothing while it is
    traced into another program.  Where ``decode`` is one jitted function
    itself, ``whole`` is its program object and the call its one dispatch.
    The spans are opened in the frame that calls ``decode``, the one python
    function there was: set-up's seconds on the chip's host follow how deep
    the first call is made (``PERF.md`` section 7)."""
    import numpy as _np

    from ompi_tpu.core import scopes

    run = scopes.caller("decode")
    dispatch = contextlib.nullcontext if whole is None else whole.dispatch
    if temperature:
        def sampled(params, prompt, seed):
            with run.call(), dispatch():
                return decode(params, prompt, seed)

        return sampled

    def greedy(params, prompt):     # two arguments; the seed is inert
        with run.call(), dispatch():
            return decode(params, prompt, _np.int32(0))

    # a jit over the callable names its module after it (``Job.programs()``,
    # whose text tests pin): the name of the lambda this was
    greedy.__name__ = "<lambda>"
    return greedy


def _program(cfg: TransformerConfig, mesh, local, part: str, in_specs: tuple,
             out_specs, **options):
    """``local(params, *args)`` of one device under ``shard_map``, jitted
    with ``options``.  The function's name is the program's name in a
    profile and in the host's record (``scopes.startup()``): both halves of
    a plan's job are ``decode``, as the one program is, and ``part`` tells
    the objects apart in the record's ``calls``.  What comes back is the
    jitted function and its program object, whose ``dispatch()`` the caller
    opens around each call of it."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.core import scopes

    mapped = jax.shard_map(
        local, mesh=mesh, in_specs=(param_specs(P, cfg, mesh), *in_specs),
        out_specs=out_specs, check_vma=False)
    record = scopes.program("decode", part)

    @functools.partial(jax.jit, **options)
    def decode(params, *args):
        record.traced()
        return mapped(params, *args)

    return decode, record


def _one_program(cfg: TransformerConfig, mesh, max_new: int,
                 temperature: float, top_k: int, keep_logits: int):
    """:func:`make_decoder`: prefill and generation in one program."""
    from jax.sharding import PartitionSpec as P

    unembedding, prefill, generate = _halves(
        cfg, mesh, max_new, temperature, top_k, keep_logits)

    def local(params, prompt, seed):
        head = unembedding(params)
        return generate(params, head, prompt, seed,
                        *prefill(params, head, prompt, seed))[0]

    decode, whole = _program(
        cfg, mesh, local, "whole", (P("dp", None), P()),
        (P("dp", None), P()) if keep_logits else P("dp", None))
    return _greedy(decode, temperature, whole)


@functools.lru_cache(maxsize=8)
def _prefill_program(cfg: TransformerConfig, mesh, temperature: float,
                     top_k: int, keep_logits: int):
    """jitted (params, prompt (B, Tp), seed) -> (tokens (B, Tp+1), logits
    (keep_logits, 1, V), carry), with its program object
    (:func:`_program`): what a decoder of ``max_new=1`` returns, and
    the carry ``Tp`` positions long (every mechanism's buffers, in
    ``block.mechanisms``' order, flat).  One object
    for every ``max_new`` of a configuration on a mesh, so one executable:
    :func:`_two_programs` says why."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    unembedding, prefill, _ = _halves(cfg, mesh, 0, temperature, top_k,
                                      keep_logits)

    def local(params, prompt, seed):
        tok0, logits, stacks = prefill(
            params, unembedding(params), prompt, seed)
        return (jnp.concatenate([prompt, tok0[:, None]], axis=1),
                logits[:keep_logits, None],
                tuple(buffer for own in stacks for buffer in own))

    return _program(cfg, mesh, local, "prefill", (P("dp", None), P()),
                    (P("dp", None), P(), P(None, "dp")))


def _two_programs(cfg: TransformerConfig, mesh, max_new: int,
                  temperature: float, top_k: int, keep_logits: int):
    """:func:`make_decoder` of a configuration whose mechanisms say which of
    their buffers grow (a layer plan, power retention): the prefill a program
    of its own (:func:`_prefill_program`), and for ``max_new > 1`` a second
    that takes its carry over (donated: the states are updated in the buffers
    the prefill filled), lengthens the caches that grow to ``Tp + max_new``
    and generates.

    Every decoder of one configuration on one mesh starts from the same
    prefill executable, so what two of them make of the same prompts is the
    same bits, the first token too.  Two programs that each hold a prefill
    are compiled apart; on the chip a few of 384 first tokens then differed
    between ``max_new=1`` and ``max_new=128`` in every run (PR 45): a sum in
    another order turns a router's tie somewhere in a prompt, and the state
    remembers it; with power retention, whose logits have no router behind
    them, one run in three still had a first token whose two best logits lay
    within the two compilations' rounding of each other (PR 49).  A service
    that answers with the first token from one program and goes on from
    another cannot have that."""
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.core.scopes import scope
    from ompi_tpu.models import block as blk

    first, prefilled = _prefill_program(cfg, mesh, float(temperature), top_k,
                                        keep_logits)
    if max_new == 1:
        def decode(params, prompt, seed):
            with prefilled.dispatch():
                tokens, kept, _carry = first(params, prompt, seed)
            return (tokens, kept) if keep_logits else tokens

        return _greedy(decode, temperature)

    unembedding, _, generate = _halves(cfg, mesh, max_new, temperature,
                                       top_k, keep_logits)
    # a latent cache grows to Tp + max_new here, so its buffer is of no use
    # to this program's outputs; every other one is written where it lies
    mechanisms = blk.mechanisms(cfg)
    grows = tuple(grown for mechanism in mechanisms
                  for grown in mechanism.grows(cfg))

    def local(params, tokens, kept, seed, fixed, growing):
        fixed, growing = list(fixed), list(growing)
        carry = iter([(growing if g else fixed).pop(0) for g in grows])
        head, prompt = unembedding(params), tokens[:, :-1]
        tok0, logits = tokens[:, -1], kept[:, 0]
        with scope("prefill"):      # the prefill program's carry ends at Tp
            stacks = [mechanism.carried(cfg, mesh, carry,
                                        prompt.shape[1] + max_new)
                      for mechanism in mechanisms]
        out, stacks = generate(params, head, prompt, seed, tok0, logits,
                               stacks)
        return out, tuple(buffer for own in stacks for buffer in own)

    # the states come back so that each is written in the buffer it came
    # in: a donated buffer is reused for an output of its shape alone
    decode, generated = _program(
        cfg, mesh, local, "generate",
        (P("dp", None), P(), P(), P(None, "dp"), P(None, "dp")),
        ((P("dp", None), P()) if keep_logits else P("dp", None),
         P(None, "dp")), donate_argnums=4)

    def both(params, prompt, seed):
        with prefilled.dispatch():
            tokens, kept, carry = first(params, prompt, seed)
        with generated.dispatch():
            return decode(params, tokens, kept, seed,
                          [b for b, g in zip(carry, grows) if not g],
                          [b for b, g in zip(carry, grows) if g])[0]

    return _greedy(both, temperature)
