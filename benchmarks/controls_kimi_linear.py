#!/usr/bin/env python3
"""Planted faults of a layer plan's own: the delta rule's carried state, its
decay and its delta term, the carried convolution inputs, the latent's norm,
the shared expert, the router's selection bias, scale, score function and
the weights of the picks held elsewhere, in a decode cell whose
configuration has the plan of ``ompi_tpu/models/plan.py``, read by
``controls.read`` through the runner's own comparison.

    python3 benchmarks/controls_kimi_linear.py --workload <cell> \
        --seeds 1,2,3 [--faults sound,state_not_carried,...] [--tiny] \
        [--out FILE.jsonl] [--bench-dir DIR]

The arguments and the lines are those of ``benchmarks/controls.py``, whose
``sound`` and ``all_lower_precision`` this reads too, in the same process
and on the same job.  Its own:

``state_not_carried``        a cached step starts from a zero matrix state
                             (what it writes is never read): every step sees
                             its own token alone
``decay_dropped``            alpha = 1: the log decay zeroed, in the prefill
                             and in the steps
``delta_term_dropped``       ``u = beta v``: what the state already holds
                             under a key is not taken back, in both passes;
                             plain gated linear attention
``conv_state_not_carried``   a cached step's convolutions see zeros before
                             the new position
``latent_norm_dropped``      the latent is cached and read without its
                             RMSNorm, in both passes
``shared_expert_dropped``    the decoder built with ``moe_shared`` 0
``selection_bias_dropped``   the decoder built with ``moe_select_bias`` off:
                             the top-k of the scores themselves
``scale_dropped``            the decoder built with ``moe_scale`` 1
``softmax_for_sigmoid``      the decoder built with ``moe_score`` "softmax"
``renormalised_over_held``   a token's weights divided by the sum over the
                             picks this chip holds, not over all its picks
``state_in_bfloat16``        the decoder built with the matrix state
                             carried in bfloat16

The five ``decoder built`` ones change the configuration a decoder is built
from; the others are planted while a decoder is traced, by wrapping a
function the program calls (``kda.mixer``, ``kda.chunked``, ``kda.update``,
``transformer._rmsnorm``, ``moe.routed_moe``) for as long as the trace
takes: the wrapper hands the sound function other arguments, so the program
has nothing in it for the controls' sake.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import controls  # noqa: E402
from benchmarks.lib import cells  # noqa: E402

CONFIG_FAULTS = ("shared_expert_dropped", "selection_bias_dropped",
                 "scale_dropped", "softmax_for_sigmoid", "state_in_bfloat16")
TRACED_FAULTS = ("state_not_carried", "decay_dropped", "delta_term_dropped",
                 "conv_state_not_carried", "latent_norm_dropped",
                 "renormalised_over_held")
SHARED = ("sound", "all_lower_precision")
FAULTS = (*SHARED, *CONFIG_FAULTS, *TRACED_FAULTS)


def faulty_config(cfg, fault: str):
    """The program's configuration with ``fault`` in it."""
    changes = {
        "shared_expert_dropped": {"moe_shared": 0},
        "selection_bias_dropped": {"moe_select_bias": False},
        "scale_dropped": {"moe_scale": 1.0},
        "softmax_for_sigmoid": {"moe_score": "softmax"},
    }
    if fault == "state_in_bfloat16":
        return dataclasses.replace(cfg, plan=dataclasses.replace(
            cfg.plan, kda=dataclasses.replace(cfg.plan.kda,
                                              state_dtype="bfloat16")))
    return dataclasses.replace(cfg, **changes.get(fault, {}))


@contextlib.contextmanager
def planted(fault: str, latent: int = 0):
    """While a decoder is traced: the model with ``fault`` in it.  ``latent``:
    the width of the plan's latent (``kv_rank``), which no other normed
    vector of the model may have."""
    import jax.numpy as jnp

    from ompi_tpu.models import kda, transformer
    from ompi_tpu.parallel import moe

    mixer, chunked, update = kda.mixer, kda.chunked, kda.update
    rmsnorm, routed = transformer._rmsnorm, moe.routed_moe
    # k shrunk and v grown by as much: what a position writes, beta k v^T,
    # is as it was, and what it takes back, beta k k^T S, is EPS^2 of it
    EPS = 2.0 ** -10

    def conv_inputs_zeroed(cfg, lp, h, carry=None):
        if carry is not None:
            carry = (jnp.zeros_like(carry[0]), carry[1])
        return mixer(cfg, lp, h, carry)

    def latent_not_normed(x, scale, eps):
        """``_rmsnorm`` but for the latent, which is told by its width."""
        return x if x.shape[-1] == scale.shape[-1] == latent else rmsnorm(
            x, scale, eps)

    def over_held(x, params, top_k, held=None, **kwargs):
        """The sound layer's output a token, times (all its picks' scores)
        over (its held picks' scores): the weights as if divided by the
        held picks' sum.  Made from the sound routing, so the picks are the
        sound ones."""
        import jax

        out = routed(x, params, top_k, held=held, **kwargs)
        scores = jax.nn.sigmoid(jnp.einsum(
            "btd,de->bte", x.astype(jnp.float32),
            params["wg"].astype(jnp.float32), precision="highest"))
        at = jax.lax.top_k(scores + params["wgb"].astype(jnp.float32),
                           top_k)[1]
        picked = jnp.take_along_axis(scores, at, axis=-1)
        here = (at >= held[0]) & (at < held[0] + held[1])
        part = jnp.sum(jnp.where(here, picked, 0.0), axis=-1, keepdims=True)
        ratio = jnp.where(part > 0, picked.sum(-1, keepdims=True)
                          / jnp.maximum(part, 1e-9), 1.0)
        return (out.astype(jnp.float32) * ratio).astype(out.dtype)

    patches = {
        "state_not_carried": [(kda, "update", lambda state, *now: update(
            jnp.zeros_like(state), *now))],
        "conv_state_not_carried": [(kda, "mixer", conv_inputs_zeroed)],
        "decay_dropped": [
            (kda, "chunked", lambda q, k, v, g, beta, chunk: chunked(
                q, k, v, jnp.zeros_like(g), beta, chunk)),
            (kda, "update", lambda state, q, k, v, g, beta: update(
                state, q, k, v, jnp.zeros_like(g), beta))],
        "delta_term_dropped": [
            (kda, "chunked", lambda q, k, v, g, beta, chunk: chunked(
                q, k * EPS, v / EPS, g, beta, chunk)),
            (kda, "update", lambda state, q, k, v, g, beta: update(
                state, q, k * EPS, v / EPS, g, beta))],
        "latent_norm_dropped": [(transformer, "_rmsnorm",
                                 latent_not_normed)],
        "renormalised_over_held": [(moe, "routed_moe", over_held)],
    }.get(fault, [])
    sound = [(module, name, getattr(module, name))
             for module, name, _wrong in patches]
    for module, name, wrong in patches:
        setattr(module, name, wrong)
    try:
        yield
    finally:
        for module, name, was in sound:
            setattr(module, name, was)


class FaultyJob:
    """The cell's job with its two programs built wrong: from a faulty
    configuration, or traced, at their first call, with ``fault`` planted;
    kept for the next seed.  Everything else is the job's own."""

    def __init__(self, job, fault: str) -> None:
        if getattr(job.cfg, "plan", None) is None:
            raise KeyError(f"{fault}: {job.config['name']} has no layer plan")
        from jax.sharding import PartitionSpec as P

        from ompi_tpu.models.transformer import param_specs

        self._job = job
        cfg = faulty_config(job.cfg, fault)
        latent = cfg.plan.mla.kv_rank if cfg.plan.mla else 0
        if latent == cfg.d_model:
            raise ValueError("the latent is told from the stream by its "
                             f"width, and both are {latent} wide")
        # a configuration without a mechanism has no leaf for it either
        leaves = set(param_specs(P, cfg, job.mesh))
        # the decoders of one configuration on one mesh share their prefill
        # program (``decode._prefill_program``): this pair has one of its
        # own, traced with the fault in it, and no later pair finds it
        from ompi_tpu.models import decode

        decode._prefill_program.cache_clear()
        self.first, self.full = [
            self._program(fault, latent, leaves, job.make_decoder(
                cfg, job.mesh, max_new=n, **job.kept))
            for n in (1, job.max_new)]
        decode._prefill_program.cache_clear()

    @staticmethod
    def _program(fault, latent, leaves, decoder):
        def run(params, prompts):
            with planted(fault, latent):    # the first call traces; later ones do not
                return decoder({k: v for k, v in params.items()
                                if k in leaves}, prompts)
        return run

    def __getattr__(self, name):
        return getattr(self._job, name)


def run(workload: str, seeds: list[int], faults: list[str], small: bool,
        out: str | None = None, bench_dir: str = cells.BENCH_DIR):
    """``controls.run`` over this file's faults as well."""
    import jax

    cell = cells.resolve(workload, bench_dir)
    unknown = [f for f in faults if f not in FAULTS]
    if unknown:
        raise ValueError(f"no fault {unknown} (have: {', '.join(FAULTS)})")
    config, traffic = (controls.tiny(cell) if small
                       else (cell.config, cell.traffic))
    job = cell.runner.build(config, traffic, jax.devices()[:cell.chips])
    faulty: dict = {}
    readings = []
    for seed in seeds:
        for fault in faults:
            of = job
            if fault in CONFIG_FAULTS + TRACED_FAULTS:
                if fault not in faulty:
                    faulty[fault] = FaultyJob(job, fault)
                of = faulty[fault]
            reading = {"workload": workload,
                       **controls.read(of, cell.runner.verdict, fault, seed,
                                       {})}
            readings.append(reading)
            line = json.dumps(reading)
            print(line, flush=True)
            if out:
                with open(out, "a", encoding="utf-8") as f:
                    f.write(line + "\n")
    return readings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--tiny", action="store_true",
                    help="the configuration's tiny sizes, float32, any device")
    ap.add_argument("--out", metavar="FILE", help="append the lines here too")
    ap.add_argument("--bench-dir", metavar="DIR", default=cells.BENCH_DIR,
                    help="the benchmark directory the cell is resolved in")
    args = ap.parse_args(argv)

    import jax

    if not args.tiny:
        from ompi_tpu.core import enable_compile_cache

        if jax.devices()[0].platform != "tpu":
            print("the controls at the cell's own size need the TPU "
                  "(--tiny for the CPU)", file=sys.stderr)
            return 2
        enable_compile_cache()
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    run(args.workload, [int(s) for s in args.seeds.split(",")],
        args.faults.split(","), args.tiny, args.out, args.bench_dir)
    return 0


if __name__ == "__main__":
    sys.exit(main())
