#!/usr/bin/env python3
"""Planted faults of a block selection's and a lightning mixer's own, and of
the model's residual scale, in a decode cell whose configuration's plan has
the kinds of ``ompi_tpu/models/block_select.py`` and
``ompi_tpu/models/lightning.py``, read by ``controls.read`` through the
runner's own comparison.

    python3 benchmarks/controls_minicpm_sala.py --workload <cell> \
        --seeds 1,2,3 [--faults sound,selection_dropped,...] [--tiny] \
        [--out FILE.jsonl] [--bench-dir DIR]

The arguments and the lines are those of ``benchmarks/controls.py``, whose
``sound``, ``all_lower_precision``, ``attention_layer_off`` (the selected
layer's ``wo``) and ``ffn_layer_off`` (the middle layer's ``w2``) this reads
too, in the same process and on the same job.  Its own, of the selection:

``selection_dropped``        every block that starts at or before the query
                             is attended: dense attention, in both passes
``blocks_shifted``           each chosen block's successor is attended in
                             its place (the first block in the last one's)
``topk_halved``              the decoder built with half the ``topk``
``window_not_forced``        the window's blocks compete by their scores
``init_block_not_forced``    the leading blocks compete by their scores
``per_head_selection``       no sum over the group: a K/V head's blocks are
                             chosen by its first query head's scores alone
``pooled_keys_not_carried``  a cached step writes no pooled key: the
                             selection sees the prompt's kernels alone

of the lightning layers:

``state_not_carried``        a cached step starts from a zero state (what
                             it writes is never read)
``prefill_state_dropped``    the prefill hands over zero states; the steps
                             carry theirs
``decay_off``                lam = 1 in every head, in both passes
``rotary_off``               the decoder built with ``rope`` off
``output_gate_off``          the decoder built with ``gate`` off
``state_in_bfloat16``        the state rounded to bfloat16's mantissa where
                             it is handed on, by ``lax.reduce_precision``
                             (an explicit rounding the compiler cannot
                             remove), after the prefill and after every step

and of the model:

``residual_scale_off``       the decoder built with ``branch_scale`` 1

The four ``decoder built`` ones change the configuration a decoder is built
from; the others are planted while a decoder is traced, by wrapping a
function the program calls (``block_select.chosen``, ``forced``,
``group_sum``, ``written_pooled``; ``lightning.chunked``, ``update``,
``constants``) for as long as the trace takes: the wrapper hands the sound
function other arguments or changes what it returns, so the program has
nothing in it for the controls' sake.
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

CONFIG_FAULTS = ("topk_halved", "rotary_off", "output_gate_off",
                 "residual_scale_off")
TRACED_FAULTS = ("selection_dropped", "blocks_shifted", "window_not_forced",
                 "init_block_not_forced", "per_head_selection",
                 "pooled_keys_not_carried", "state_not_carried",
                 "prefill_state_dropped", "decay_off", "state_in_bfloat16")
SHARED = ("sound", "all_lower_precision", "attention_layer_off",
          "ffn_layer_off")
FAULTS = (*SHARED, *CONFIG_FAULTS, *TRACED_FAULTS)


def faulty_config(cfg, fault: str):
    """The program's configuration with ``fault`` in it."""
    pl = cfg.plan
    change = {
        "topk_halved": lambda: {"block_select": dataclasses.replace(
            pl.block_select, topk=pl.block_select.topk // 2)},
        "rotary_off": lambda: {"lightning": dataclasses.replace(
            pl.lightning, rope=False)},
        "output_gate_off": lambda: {"lightning": dataclasses.replace(
            pl.lightning, gate=False)},
        "residual_scale_off": lambda: {"branch_scale": 1.0},
    }.get(fault, dict)
    return dataclasses.replace(cfg, plan=dataclasses.replace(pl, **change()))


@contextlib.contextmanager
def planted(fault: str):
    """While a decoder is traced: the model with ``fault`` in it."""
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.models import block_select, lightning

    chosen, forced = block_select.chosen, block_select.forced
    chunked, update = lightning.chunked, lightning.update
    constants = lightning.constants

    def started(bs, scores, t):
        """Every block that starts at or before the query."""
        return jnp.broadcast_to(
            bs.block * jnp.arange(scores.shape[-1]) <= t[:, None],
            scores.shape)

    def rounded(state):
        return lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)

    def in_bfloat16(sound):
        def run(*args):
            y, state = sound(*args)
            return y, rounded(state)
        return run

    patches = {
        "selection_dropped": [(block_select, "chosen", started)],
        "blocks_shifted": [(block_select, "chosen", lambda bs, scores, t:
                            jnp.roll(chosen(bs, scores, t), 1, axis=-1)
                            & started(bs, scores, t))],
        "window_not_forced": [(block_select, "forced", lambda bs, t, n:
                               forced(dataclasses.replace(bs, window=0), t,
                                      n) & (jnp.arange(n) < bs.init_blocks))],
        "init_block_not_forced": [(block_select, "forced", lambda bs, t, n:
                                   forced(dataclasses.replace(
                                       bs, init_blocks=0), t, n))],
        "per_head_selection": [(block_select, "group_sum",
                                lambda p: p[:, :, 0])],
        "pooled_keys_not_carried": [(block_select, "written_pooled",
                                     lambda bs, rows, pooled, pos: pooled)],
        "state_not_carried": [(lightning, "update", lambda state, *now:
                               update(jnp.zeros_like(state), *now))],
        "prefill_state_dropped": [(lightning, "chunked", lambda *args: (
            lambda y, state: (y, jnp.zeros_like(state)))(*chunked(*args)))],
        "decay_off": [(lightning, "constants", lambda lt, layer: {
            k: v * 0 for k, v in constants(lt, layer).items()})],
        "state_in_bfloat16": [(lightning, "chunked", in_bfloat16(chunked)),
                              (lightning, "update", in_bfloat16(update))],
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
        plan = getattr(job.cfg, "plan", None)
        if plan is None or not (plan.lightning and plan.block_select):
            raise KeyError(f"{fault}: {job.config['name']} has no plan of "
                           f"lightning and block-selected layers")
        from jax.sharding import PartitionSpec as P

        from ompi_tpu.models.transformer import param_specs

        self._job = job
        cfg = faulty_config(job.cfg, fault)
        # a configuration without a mechanism has no leaf for it either
        leaves = set(param_specs(P, cfg, job.mesh))
        # the decoders of one configuration on one mesh share their prefill
        # program (``decode._prefill_program``): this pair has one of its
        # own, traced with the fault in it, and no later pair finds it
        from ompi_tpu.models import decode

        decode._prefill_program.cache_clear()
        self.first, self.full = [
            self._program(fault, leaves, job.make_decoder(
                cfg, job.mesh, max_new=n, **job.kept))
            for n in (1, job.max_new)]
        decode._prefill_program.cache_clear()

    @staticmethod
    def _program(fault, leaves, decoder):
        def run(params, prompts):
            with planted(fault):    # the first call traces; later ones do not
                return decoder({k: v for k, v in params.items()
                                if k in leaves}, prompts)
        return run

    def __getattr__(self, name):
        return getattr(self._job, name)


def run(workload: str, seeds: list[int], faults: list[str], small: bool,
        out: str | None = None, bench_dir: str = cells.BENCH_DIR,
        **traffic):
    """``controls.run`` over this file's faults as well; ``traffic``: what
    the tiny traffic is to differ in (a test's longer prompts)."""
    import jax

    cell = cells.resolve(workload, bench_dir)
    unknown = [f for f in faults if f not in FAULTS]
    if unknown:
        raise ValueError(f"no fault {unknown} (have: {', '.join(FAULTS)})")
    config, mix = (controls.tiny(cell, **traffic) if small
                   else (cell.config, cell.traffic))
    job = cell.runner.build(config, mix, jax.devices()[:cell.chips])
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
