#!/usr/bin/env python3
"""Planted faults of a learned sparse attention's own: the selection, the
carried index keys, the per-head q/k norm and the renormalised routing
weights, in a decode cell whose configuration has the index of
``ompi_tpu/models/sparse_index.py``, read by ``controls.read`` through the
runner's own comparison.

    python3 benchmarks/controls_keye_vl2.py --workload <cell> --seeds 1,2,3 \
        [--faults sound,selection_dropped,...] [--tiny] [--out FILE.jsonl] \
        [--bench-dir DIR]

The arguments and the lines are those of ``benchmarks/controls.py``, whose
faults (``sound``, ``all_lower_precision``, ``attention_layer_off``) this
reads too, in the same process and on the same job.  Its own:

``selection_dropped``         attention over every earlier position (the
                              decoder built with ``topk`` past any length)
``topk_halved``               the decoder built with half the ``topk``
``weights_not_renormalised``  the decoder built with ``moe_norm_topk`` off:
                              a token's top-k probabilities as they are
``index_keys_not_carried``    the cached steps write no index key of their
                              own (a key of zeros, which is what the carry
                              holds before it is written): a step selects
                              among the prompt's keys, and the generated
                              positions' keys read as zero
``selection_shifted``         every index score read one position late, so
                              the selected set is the sound one shifted by
                              a position
``whole_projection_norm``     q and k normed over the whole projection, the
                              head's scale repeated, not over each head

The first three change the configuration a decoder is built from; the last
three are planted in the program while a decoder is traced, by replacing a
function of ``ompi_tpu.models`` for as long as the trace takes.
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

CONFIG_FAULTS = ("selection_dropped", "topk_halved",
                 "weights_not_renormalised")
TRACED_FAULTS = ("index_keys_not_carried", "selection_shifted",
                 "whole_projection_norm")
SHARED = ("sound", "all_lower_precision", "attention_layer_off")
FAULTS = (*SHARED, *CONFIG_FAULTS, *TRACED_FAULTS)


def faulty_config(cfg, fault: str):
    """The program's configuration with ``fault`` in it."""
    if fault == "selection_dropped":
        return dataclasses.replace(cfg, index=dataclasses.replace(
            cfg.index, topk=1 << 30))
    if fault == "topk_halved":
        return dataclasses.replace(cfg, index=dataclasses.replace(
            cfg.index, topk=cfg.index.topk // 2))
    if fault == "weights_not_renormalised":
        return dataclasses.replace(cfg, moe_norm_topk=False)
    return cfg


@contextlib.contextmanager
def planted(fault: str):
    """While a decoder is traced: the model with ``fault`` in it."""
    import jax.numpy as jnp

    from ompi_tpu.models import decode, sparse_index, transformer

    scores, project = sparse_index.scores, sparse_index.project

    def no_key_of_a_step(cfg, lp, x, positions):
        qi, ki, wi = project(cfg, lp, x, positions)
        return qi, (jnp.zeros_like(ki) if x.shape[1] == 1 else ki), wi

    def whole(cfg, x, scale, comm):
        return transformer._rmsnorm(
            x, jnp.tile(scale, x.shape[-1] // scale.shape[-1]), cfg.norm_eps)

    patches = {
        "index_keys_not_carried": [
            (sparse_index, "project", no_key_of_a_step)],
        "selection_shifted": [
            (sparse_index, "scores", lambda qi, wi, ki: jnp.roll(
                scores(qi, wi, ki), 1, axis=-1))],
        # ``block.mixer`` calls ``transformer``'s; a module that has taken
        # the name for its own (``decode`` has, and uses it nowhere) gets
        # the fault too for as long as it has
        "whole_projection_norm": [
            (module, "_qk_norm", whole) for module in (transformer, decode)
            if hasattr(module, "_qk_norm")],
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
        if getattr(job.cfg, "index", None) is None:
            raise KeyError(f"{fault}: {job.config['name']} has no index")
        self._job = job
        cfg = faulty_config(job.cfg, fault)
        self.first, self.full = (
            self._program(fault, job.make_decoder(cfg, job.mesh, max_new=n,
                                                  **job.kept))
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
