#!/usr/bin/env python3
"""Planted faults of a model whose layer is one mixer alone: the state-space
mixer's output, its two states between steps and after the prefill and the
type its state is carried in, the router's selection bias, scale and
renormalisation, the experts' activation and the shared expert, and a rotary
embedding where the model has none, in a decode cell whose configuration is
built by ``ompi_tpu.models.plan.pattern_moe_config``, read by
``controls.read`` through the runner's own comparison; and the router's
counters over a whole batch, from the program's own routing.

    python3 benchmarks/controls_nemotron_h.py --workload <cell> \
        --seeds 1,2,3 [--faults sound,ssm_state_in_bfloat16,...] [--tiny] \
        [--out FILE.jsonl] [--bench-dir DIR]

The arguments and the lines are those of ``benchmarks/controls.py``, whose
``sound``, ``all_lower_precision``, ``attention_layer_off``, ``experts_off``,
``expert_layer_off`` and ``top_k_less_one`` this reads too, in the same
process and on the same job.  Its own:

``ssm_layer_off``            ``ssm_out`` of the middle Mamba layer zeroed
``shared_expert_off``        ``sw2`` of every routed layer zeroed
``ssm_state_not_carried``    every cached step's recurrence starts from a
                             zero state
``conv_state_off``           every cached step's convolution reads zeros for
                             its last inputs
``ssm_prefill_state_dropped``    the prefill hands over zero states
``ssm_state_in_bfloat16``    the decoder built with the heads' states carried
                             in bfloat16 between steps (the update itself
                             stays float32)
``selection_bias_dropped``   the decoder built with ``moe_select_bias`` off:
                             the top-k of the scores themselves
``scale_dropped``            the decoder built with ``moe_scale`` 1
``not_renormalised``         the decoder built with ``moe_norm_topk`` off: a
                             token's six scores weigh as they are
``relu_not_squared``         the experts and the shared expert put ``relu``
                             between their matrices, not its square
``rope_applied``             the decoder built with the attention layers'
                             queries and keys rotated at ``rope_theta``
``counters``                 nothing wrong: the sound programs traced with a
                             host callback behind every routed layer, which
                             hands back, a call, how many rows each held
                             expert got; the reading carries
                             ``moe_held_pick_share``,
                             ``moe_rows_a_held_expert`` and
                             ``moe_empty_group_share`` (the share of (step,
                             held expert) pairs without a row) over the
                             cached steps of the whole batch

The ``decoder built`` ones change the configuration a decoder is built from;
the others are planted while a decoder is traced, by wrapping a function the
program calls (``ssm._state_before``, ``ssm._conv_before``, the kind's
``mixer``, ``moe.ACTIVATIONS``, ``moe.routed_moe``) for as long as the trace
takes, so the program has nothing in it for the controls' sake.
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

from benchmarks import controls  # noqa: E402
from benchmarks.lib import cells  # noqa: E402

PARAM_FAULTS = {
    "ssm_layer_off": (("ssm_out",), ("ssm_out",), controls._zero_layer),
    "shared_expert_off": (("sw2",), ("sw2",), controls._zero)}
CONFIG_FAULTS = ("ssm_state_in_bfloat16", "selection_bias_dropped",
                 "scale_dropped", "not_renormalised", "rope_applied")
TRACED_FAULTS = ("ssm_state_not_carried", "conv_state_off",
                 "ssm_prefill_state_dropped", "relu_not_squared")
COUNTERS = "counters"
SHARED = ("sound", "all_lower_precision", "attention_layer_off",
          "experts_off", "expert_layer_off")
# those that run the job's own pair of programs first, then those that
# build a pair of their own
OWN_PROGRAMS = ("top_k_less_one", *CONFIG_FAULTS, *TRACED_FAULTS, COUNTERS)
FAULTS = (*SHARED, *PARAM_FAULTS, *OWN_PROGRAMS)

# ``controls.read`` plants a fault of the parameters by its name in this table
controls.PARAM_FAULTS.update(PARAM_FAULTS)

# what the ``counters`` callback was handed, a routed call each: (tokens,
# held picks, rows of each held expert)
_counted: list = []


def faulty_config(cfg, fault: str):
    """The program's configuration with ``fault`` in it."""
    pl = cfg.plan
    if fault == "ssm_state_in_bfloat16":
        return dataclasses.replace(cfg, plan=dataclasses.replace(
            pl, ssm=dataclasses.replace(pl.ssm, state_dtype="bfloat16")))
    if fault == "rope_applied":
        return dataclasses.replace(cfg, plan=dataclasses.replace(
            pl, attention=dataclasses.replace(pl.attention, rope=True)))
    changes = {"selection_bias_dropped": {"moe_select_bias": False},
               "scale_dropped": {"moe_scale": 1.0},
               "not_renormalised": {"moe_norm_topk": False}}
    return dataclasses.replace(cfg, **changes.get(fault, {}))


@contextlib.contextmanager
def planted(fault: str):
    """While a decoder is traced: the model with ``fault`` in it."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.models import ssm
    from ompi_tpu.parallel import moe

    mixer, routed = ssm.PLAN_KIND.mixer, moe.routed_moe

    def prefill_drops_its_states(cfg, lp, h, carry=None):
        out = mixer(cfg, lp, h, carry)
        if carry is not None:
            return out
        return (out[0], *(jnp.zeros_like(state) for state in out[1:]))

    def counted(x, params, top_k, held=None, **kwargs):
        first, count = held or (0, params["wg"].shape[-1])
        score = jax.nn.sigmoid(jnp.einsum(
            "btd,de->bte", x.astype(jnp.float32),
            params["wg"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        at = lax.top_k(score + params["wgb"].astype(jnp.float32), top_k)[1]
        rows = jnp.sum(at[..., None] == first + jnp.arange(count),
                       axis=(0, 1, 2))
        jax.debug.callback(
            lambda *got: _counted.append(tuple(int(g) if g.ndim == 0
                                               else g.tolist() for g in got)),
            jnp.int32(at.shape[0] * at.shape[1]), jnp.sum(rows), rows)
        return routed(x, params, top_k, held=held, **kwargs)

    patches = {
        "ssm_state_not_carried": [
            (ssm, "_state_before", lambda ssm_c, layer: jnp.zeros(
                ssm_c.shape, jnp.float32))],
        "conv_state_off": [
            (ssm, "_conv_before", lambda conv_c, layer: jnp.zeros_like(
                conv_c))],
        "ssm_prefill_state_dropped": [
            (ssm.PLAN_KIND, "mixer", prefill_drops_its_states)],
        "relu_not_squared": [
            (moe, "ACTIVATIONS", {**moe.ACTIVATIONS,
                                  "relu2": lambda x: jnp.maximum(x, 0)})],
        COUNTERS: [(moe, "routed_moe", counted)],
    }.get(fault, [])
    sound = [(holder, name, getattr(holder, name))
             for holder, name, _wrong in patches]
    for holder, name, wrong in patches:
        setattr(holder, name, wrong)
    try:
        yield
    finally:
        for holder, name, was in sound:
            setattr(holder, name, was)


def counters(batch: int, top_k: int) -> dict:
    """The shares from what the ``counters`` callback was handed since it was
    last read: over the calls of ``batch`` tokens (a cached step a layer; a
    prefill's pass holds more)."""
    import numpy as np

    steps = [c for c in _counted if c[0] == batch]
    _counted.clear()
    if not steps:
        return {}
    rows = np.asarray([c[2] for c in steps])
    return {"moe_held_pick_share":
            sum(c[1] for c in steps) / (len(steps) * batch * top_k),
            "moe_rows_a_held_expert": float(rows.mean()),
            "moe_empty_group_share": float((rows == 0).mean()),
            "routed_calls_counted": len(steps)}


class FaultyJob:
    """The cell's job with its two programs built wrong: from a faulty
    configuration, or traced, at their first call, with ``fault`` planted;
    kept for the next seed.  Everything else is the job's own."""

    def __init__(self, job, fault: str) -> None:
        plan = getattr(job.cfg, "plan", None)
        if plan is None or plan.ssm is None or plan.attention is None:
            raise KeyError(f"{fault}: {job.config['name']} has no plan of "
                           f"single-mixer layers")
        from jax.sharding import PartitionSpec as P

        from ompi_tpu.models import decode
        from ompi_tpu.models.transformer import param_specs

        self._job = job
        cfg = faulty_config(job.cfg, fault)
        # a configuration without a mechanism has no leaf for it either
        leaves = set(param_specs(P, cfg, job.mesh))
        # the decoders of one configuration on one mesh share their prefill
        # program (``decode._prefill_program``): this pair has one of its
        # own, traced with the fault in it, and no later pair finds it
        decode._prefill_program.cache_clear()
        self.first, self.full = [
            self._program(fault, leaves, job.make_decoder(
                cfg, job.mesh, max_new=n, **job.kept))
            for n in (1, job.max_new)]
        decode._prefill_program.cache_clear()

    @staticmethod
    def _program(fault, leaves, decoder):
        def run(params, prompts):
            # the first call traces; later ones do not
            with planted(fault):
                return decoder({k: v for k, v in params.items()
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
    readings, decoders = [], {}
    # a fault at a time, every seed of it, and then its pair of programs is
    # let go (``controls_kimi_vl.run`` says why)
    for fault in faults:
        of = (FaultyJob(job, fault) if fault in OWN_PROGRAMS[1:] else job)
        for seed in seeds:
            _counted.clear()
            reading = {"workload": workload,
                       **controls.read(of, cell.runner.verdict, fault, seed,
                                       decoders)}
            if fault == COUNTERS:
                jax.effects_barrier()
                reading.update(counters(job.batch, job.cfg.moe_top_k))
            readings.append(reading)
            line = json.dumps(reading)
            print(line, flush=True)
            if out:
                with open(out, "a", encoding="utf-8") as f:
                    f.write(line + "\n")
        del of
        decoders.clear()
        gc.collect()
        if not small and fault in OWN_PROGRAMS:
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
