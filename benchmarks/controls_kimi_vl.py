#!/usr/bin/env python3
"""Planted faults of rotary latent attention and of the router beside it: the
rotation, what is cached of the shared key, the cached step's position, the
rotated part of the scores, their scale, the latent's norm, the shared
experts, the router's scale, bias, renormalisation and score function, and
the latent cache's precision, in a decode cell whose configuration is built
by ``ompi_tpu.models.plan.mla_moe_config``, read by ``controls.read`` through
the runner's own comparison.

    python3 benchmarks/controls_kimi_vl.py --workload <cell> \
        --seeds 1,2,3 [--faults sound,rotation_dropped,...] [--tiny] \
        [--out FILE.jsonl] [--bench-dir DIR]

The arguments and the lines are those of ``benchmarks/controls.py``, whose
``sound`` and ``all_lower_precision`` this reads too, in the same process
and on the same job.  Its own:

``rotation_dropped``         neither the queries' rope part nor the shared
                             key is rotated, in the prefill and in the steps:
                             the NoPE form of the same weights
``shared_key_unrotated``     the queries are rotated and the shared key is
                             not: what is cached, and what both passes score
                             against, is ``k_r`` as projected
``step_position_off_by_one`` a cached step rotates its query and its key at
                             the position after its own; the prefill is sound
``rope_part_left_out``       the scores are ``q_n . k_n`` alone (the queries'
                             rope part zeroed), under the same scale
``scale_128``                scores times ``nope^-1/2`` and not ``(nope +
                             rope)^-1/2`` (the queries' projection grown by
                             the ratio's root)
``latent_norm_dropped``      the latent is cached and read without its
                             RMSNorm, in both passes
``one_shared_expert_dropped``    the decoder built with ``moe_shared`` of one
                             shared expert's width: the second's columns are
                             not read
``scale_dropped``            the decoder built with ``moe_scale`` 1
``selection_bias_dropped``   the decoder built with ``moe_select_bias`` off
``not_renormalised``         the decoder built with ``moe_norm_topk`` off
``softmax_for_sigmoid``      the decoder built with ``moe_score`` "softmax"
``latent_cache_lower_precision``    every cached row is rounded to 3
                             mantissa bits (``lax.reduce_precision``) as it
                             is written into the carry, by a prefill's pass
                             and by a step: the steps score against, and
                             sum, a rounded latent and shared key

The five ``decoder built`` ones change the configuration a decoder is built
from; the others are planted while a decoder is traced, by wrapping a
function the program calls (``mla.rotate``, ``mla.mixer``,
``lax.dynamic_update_slice``; the latent's norm by
``controls_kimi_linear.planted``) for as long as the trace takes: the
wrapper hands the sound function other arguments or rounds what it returns,
so the program has nothing in it for the controls' sake.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import controls, controls_kimi_linear  # noqa: E402
from benchmarks.lib import cells  # noqa: E402

CONFIG_FAULTS = ("one_shared_expert_dropped", "scale_dropped",
                 "selection_bias_dropped", "not_renormalised",
                 "softmax_for_sigmoid")
TRACED_FAULTS = ("rotation_dropped", "shared_key_unrotated",
                 "step_position_off_by_one", "rope_part_left_out",
                 "scale_128", "latent_norm_dropped",
                 "latent_cache_lower_precision")
SHARED = ("sound", "all_lower_precision")
FAULTS = (*SHARED, *CONFIG_FAULTS, *TRACED_FAULTS)
# a shared expert's leaves -> the axis of its width
SHARED_WIDTH_AXIS = {"sw1": 2, "sw3": 2, "sw2": 1}


def faulty_config(cfg, fault: str):
    """The program's configuration with ``fault`` in it."""
    changes = {
        "one_shared_expert_dropped": {"moe_shared": cfg.moe_shared // 2},
        "scale_dropped": {"moe_scale": 1.0},
        "selection_bias_dropped": {"moe_select_bias": False},
        "not_renormalised": {"moe_norm_topk": False},
        "softmax_for_sigmoid": {"moe_score": "softmax"},
    }
    return dataclasses.replace(cfg, **changes.get(fault, {}))


@contextlib.contextmanager
def planted(fault: str, latent: int = 0, cached: int = 0):
    """While a decoder is traced: the model with ``fault`` in it.  ``latent``:
    the width of the plan's latent (``kv_rank``), which no other normed
    vector of the model may have; ``cached``: the width of a cached row,
    which nothing else a decoder writes in place may have."""
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.models import mla

    rotate, mixer, write = mla.rotate, mla.mixer, lax.dynamic_update_slice

    def queries_alone(x, positions, theta):
        """The shared key (B, T, rope) as it is; a head's part rotated."""
        return x if x.ndim == 3 else rotate(x, positions, theta)

    def a_step_one_on(x, positions, theta):
        """A cached step hands one traced position; a prefill a range."""
        step = positions.shape == (1,)
        return rotate(x, positions + 1 if step else positions, theta)

    def keys_alone(x, positions, theta):
        return rotate(x, positions, theta) if x.ndim == 3 else x * 0

    def scores_over_nope(cfg, lp, h, carry=None):
        ml = cfg.plan.mla
        grown = ((ml.nope + ml.rope) / ml.nope) ** 0.5
        return mixer(cfg, {**lp, "mla_q": lp["mla_q"] * jnp.asarray(
            grown, lp["mla_q"].dtype)}, h, carry)

    def rows_rounded(operand, update, *start, **options):
        """``lax.dynamic_update_slice`` of a decoder's trace: a cached row is
        told by its width, and rounded as it is written."""
        if update.shape[-1] == cached:
            update = controls._lower(update)
        return write(operand, update, *start, **options)

    patches = {
        "rotation_dropped": [(mla, "rotate", lambda x, _at, _theta: x)],
        "shared_key_unrotated": [(mla, "rotate", queries_alone)],
        "step_position_off_by_one": [(mla, "rotate", a_step_one_on)],
        "rope_part_left_out": [(mla, "rotate", keys_alone)],
        "scale_128": [(mla, "mixer", scores_over_nope)],
        "latent_cache_lower_precision": [(lax, "dynamic_update_slice",
                                          rows_rounded)],
    }.get(fault, [])
    if fault == "latent_norm_dropped":      # the layer plan's own, as it is
        with controls_kimi_linear.planted(fault, latent):
            yield
        return
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
        plan = getattr(job.cfg, "plan", None)
        if plan is None or not getattr(plan.mla, "theta", 0):
            raise KeyError(f"{fault}: {job.config['name']} has no plan of "
                           f"rotary latent layers")
        from jax.sharding import PartitionSpec as P

        from ompi_tpu.models import decode
        from ompi_tpu.models.transformer import param_specs

        self._job = job
        cfg = faulty_config(job.cfg, fault)
        if plan.mla.kv_rank == cfg.d_model:
            raise ValueError("the latent is told from the stream by its "
                             f"width, and both are {cfg.d_model} wide")
        # a configuration without a mechanism has no leaf for it either
        leaves = set(param_specs(P, cfg, job.mesh))
        # the decoders of one configuration on one mesh share their prefill
        # program (``decode._prefill_program``): this pair has one of its
        # own, traced with the fault in it, and no later pair finds it
        decode._prefill_program.cache_clear()
        self.first, self.full = [
            self._program(fault, cfg, leaves, job.make_decoder(
                cfg, job.mesh, max_new=n, **job.kept))
            for n in (1, job.max_new)]
        decode._prefill_program.cache_clear()

    @staticmethod
    def _program(fault, cfg, leaves, decoder):
        from jax import lax

        def held(name, leaf):
            """A shared expert's leaf as wide as the configuration has it."""
            axis = SHARED_WIDTH_AXIS.get(name)
            if axis is None or leaf.shape[axis] == cfg.moe_shared:
                return leaf
            return lax.slice_in_dim(leaf, 0, cfg.moe_shared, axis=axis)

        def run(params, prompts):
            # the first call traces; later ones do not
            with planted(fault, cfg.plan.mla.kv_rank, cfg.plan.mla.cached):
                return decoder({k: held(k, v) for k, v in params.items()
                                if k in leaves}, prompts)
        return run

    def __getattr__(self, name):
        return getattr(self._job, name)


def run(workload: str, seeds: list[int], faults: list[str], small: bool,
        out: str | None = None, bench_dir: str = cells.BENCH_DIR, **traffic):
    """``controls.run`` over this file's faults as well.  ``traffic``: sizes
    of a tiny run other than ``controls.TINY_TRAFFIC``'s."""
    import jax

    cell = cells.resolve(workload, bench_dir)
    unknown = [f for f in faults if f not in FAULTS]
    if unknown:
        raise ValueError(f"no fault {unknown} (have: {', '.join(FAULTS)})")
    config, traffic = (controls.tiny(cell, **traffic) if small
                       else (cell.config, cell.traffic))
    job = cell.runner.build(config, traffic, jax.devices()[:cell.chips])
    readings = []
    # a fault at a time, every seed of it, and then its pair of programs is
    # let go: a dozen pairs held at once leave the chip no room to load the
    # next (4 GB of 16 were free at the thirteenth, PR 56)
    for fault in faults:
        of = (FaultyJob(job, fault) if fault in CONFIG_FAULTS + TRACED_FAULTS
              else job)
        for seed in seeds:
            reading = {"workload": workload,
                       **controls.read(of, cell.runner.verdict, fault, seed,
                                       {})}
            readings.append(reading)
            line = json.dumps(reading)
            print(line, flush=True)
            if out:
                with open(out, "a", encoding="utf-8") as f:
                    f.write(line + "\n")
        del of
        gc.collect()
        if not small and fault in CONFIG_FAULTS + TRACED_FAULTS:
            jax.clear_caches()      # the executables go with their functions
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
