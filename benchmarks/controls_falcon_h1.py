#!/usr/bin/env python3
"""Planted faults of a state-space mixer's own: the carried state and the
mixer's branch, in a decode cell whose configuration has the hybrid block of
``ompi_tpu/models/ssm.py``, read by ``controls.read`` through the runner's
own comparison.

    python3 benchmarks/controls_falcon_h1.py --workload <cell> --seeds 1,2,3 \
        [--faults sound,ssm_layer_off,...] [--tiny] [--out FILE.jsonl] \
        [--bench-dir DIR]

The arguments and the lines are those of ``benchmarks/controls.py``, whose
faults (``sound``, ``all_lower_precision``, ``attention_layer_off``,
``ffn_layer_off``) this reads too, in the same process and on the same job.
Its own:

``ssm_layer_off``               ``ssm_out`` of the middle layer zeroed
``ssm_state_not_carried``       every cached step's update starts from a
                                zero state (the state is written, never read)
``ssm_prefill_state_dropped``   the prefill hands over K/V and zero states:
                                the convolution's last inputs and the heads'
                                states of the prompt are lost
``conv_state_off``              every cached step's convolution reads zeros
                                for the inputs before its own

The last three are planted in the program while a decoder is traced, by
replacing the function of ``ompi_tpu.models.ssm`` that reads the state (or
the mixer's whole-sequence return) for as long as the trace takes.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import controls  # noqa: E402
from benchmarks.lib import cells  # noqa: E402

PARAM_FAULTS = {"ssm_layer_off": (("ssm_out",), ("ssm_out",),
                                  controls._zero_layer)}
STATE_FAULTS = ("ssm_state_not_carried", "ssm_prefill_state_dropped",
                "conv_state_off")
SHARED = ("sound", "all_lower_precision", "attention_layer_off",
          "ffn_layer_off")
FAULTS = (*SHARED, *PARAM_FAULTS, *STATE_FAULTS)

# ``controls.read`` plants a fault of the parameters by its name in this table
controls.PARAM_FAULTS.update(PARAM_FAULTS)


@contextlib.contextmanager
def planted(fault: str):
    """While a decoder is traced: the mixer with ``fault`` in it."""
    import jax.numpy as jnp

    from ompi_tpu.models import ssm

    mixer = ssm.mixer

    def prefill_drops_its_states(cfg, lp, u, carry=None):
        out = mixer(cfg, lp, u, carry)
        if carry is not None:
            return out
        return (out[0], *(jnp.zeros_like(state) for state in out[1:]))

    name, wrong = {
        "ssm_state_not_carried": (
            "_state_before", lambda ssm_c, layer: jnp.zeros(
                ssm_c.shape[1:], jnp.float32)),
        "conv_state_off": (
            "_conv_before", lambda conv_c, layer: jnp.zeros(
                conv_c.shape[1:], conv_c.dtype)),
        "ssm_prefill_state_dropped": ("mixer", prefill_drops_its_states),
    }[fault]
    sound = getattr(ssm, name)
    setattr(ssm, name, wrong)
    try:
        yield
    finally:
        setattr(ssm, name, sound)


class FaultyJob:
    """The cell's job with its two programs built wrong: traced, at their
    first call, with ``fault`` planted, and kept for the next seed.
    Everything else is the job's own."""

    def __init__(self, job, fault: str) -> None:
        if getattr(job.cfg, "hybrid", None) is None:
            raise KeyError(f"{fault}: {job.config['name']} has no mixer")
        self._job = job
        self.first, self.full = (
            self._program(fault, job.make_decoder(job.cfg, job.mesh,
                                                  max_new=n, **job.kept))
            for n in (1, job.max_new))

    @staticmethod
    def _program(fault, decoder):
        def run(params, prompts):
            with planted(fault):    # the first call traces; later ones do not
                return decoder(params, prompts)
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
            if fault in STATE_FAULTS:
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
