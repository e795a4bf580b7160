#!/usr/bin/env python3
"""Planted faults: a decode cell's own program on its own traffic with one
thing wrong, read by the runner's own comparison.

    python3 benchmarks/controls.py --workload <cell> --seeds 1,2,3 \
        --faults sound,all_lower_precision [--tiny] [--out FILE.jsonl] \
        [--bench-dir DIR]

A ``correct`` says as much as the faults it refuses.  For each seed and each
fault this draws the cell's seeded parameters, plants the fault (in the
parameters, in place on the device, so the cell needs no more memory than it
has; or in the decoder that is built), runs the cell's two programs once on
the cell's prompts, and hands the tokens to ``Job.compare`` with the *sound*
parameters of the same seed, drawn again: what ``run.py`` would have printed
under ``checks`` had the program had that fault, and ``Job.verdict``'s
``correct`` of it.  One JSON line a reading on stdout (and appended to
``--out``).  ``run.py`` reads nothing of this file; no measured window, no
metric.  ``--tiny`` runs the configuration's ``tiny`` sizes in float32 on
whatever device JAX has (the CPU tests); without it a TPU is asked for, as
``run.py`` asks.  ``--bench-dir`` names the benchmark directory the cell is
resolved in (``cells.resolve``'s; ``BENCHMARK.json`` beside it), so that a
cell of a copied benchmark is read before it is added.

Faults (one whose leaf the configuration lacks raises):

``sound``                    nothing wrong
``all_lower_precision``      every drawn matrix rounded to 3 mantissa bits
                             (``lax.reduce_precision``: the nearest stored
                             precision below bfloat16, as an explicit
                             rounding the compiler cannot remove)
``attention_layer_off``      ``wo`` of the middle layer zeroed
``ffn_layer_off``            ``w2`` of the middle layer zeroed
``expert_layer_off``         the same, of a routed configuration (``wg``)
``experts_off``              ``w2`` of every routed layer zeroed
``experts_lower_precision``  the experts' matrices at 3 mantissa bits
``top_k_less_one``           the decoder built with one expert a token fewer
``router_in_bfloat16``       the router's probabilities rounded to bfloat16
                             before the top-k (its only ``lax.top_k`` in a
                             greedy decoder), while the decoder is traced;
                             by ``lax.reduce_precision`` too: as a cast to
                             bfloat16 and back, which it was until PR 37,
                             it read the sound program's numbers bit for
                             bit on the chip (PERF.md section 6)
"""

from __future__ import annotations

import argparse
import contextlib
import copy
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.lib import cells, program  # noqa: E402

MANTISSA_BITS = 3       # fp8 e4m3 keeps as many; bfloat16 keeps 7
# ``--tiny``: 192 checked tokens, so that a fault of the precision, which
# moves a token in ten where the vocabulary is 128, shows in every seed
TINY_TRAFFIC = {"batch": 8, "prompt_len": 8, "max_new": 24,
                "reference_sequences": 8}
EXPERT_LEAVES = ("w1", "w2", "w3")


def _lower(x):
    from jax import lax

    return lax.reduce_precision(x, exponent_bits=8,
                                mantissa_bits=MANTISSA_BITS)


def _zero_layer(x):
    return x.at[x.shape[0] // 2].set(0)


def _zero(x):
    return x * 0


# fault -> (leaves the configuration must have, leaves changed or None for
# every drawn matrix, what is done to each)
PARAM_FAULTS = {
    "all_lower_precision": ((), None, _lower),
    "attention_layer_off": (("wo",), ("wo",), _zero_layer),
    "ffn_layer_off": (("w2",), ("w2",), _zero_layer),
    "expert_layer_off": (("wg", "w2"), ("w2",), _zero_layer),
    "experts_off": (("wg", "w2"), ("w2",), _zero),
    "experts_lower_precision": (("wg",), EXPERT_LEAVES, _lower),
}
DECODER_FAULTS = ("top_k_less_one", "router_in_bfloat16")
FAULTS = ("sound", *PARAM_FAULTS, *DECODER_FAULTS)


def plant(job, fault: str, params: dict) -> dict:
    """``params`` with ``fault`` planted, in place: the arrays handed in are
    donated."""
    import jax

    needs, leaves, change = PARAM_FAULTS[fault]
    table = program.param_table(job.reference, job.config, serving=True)
    missing = [leaf for leaf in needs if leaf not in table]
    if missing:
        raise KeyError(f"{fault}: {job.config['name']} has no leaf "
                       f"{missing}")
    if leaves is None:
        leaves = [leaf for leaf, (_dims, std) in table.items()
                  if std is not None]
    leaves = [leaf for leaf in leaves if leaf in params]
    some = {leaf: params.pop(leaf) for leaf in leaves}
    changed = jax.jit(lambda tree: {k: change(v) for k, v in tree.items()},
                      donate_argnums=0)(some)
    return {**params, **changed}


@contextlib.contextmanager
def _router_rounds_to_bfloat16():
    """While a decoder is traced: ``lax.top_k`` sees its operand rounded to
    bfloat16 (8 exponent bits, 7 of mantissa).  A greedy decoder picks by
    argmax, so the router's is the only top-k in its programs."""
    from jax import lax

    top_k = lax.top_k

    def rounded(operand, k, *args, **kwargs):
        return top_k(lax.reduce_precision(operand, exponent_bits=8,
                                          mantissa_bits=7), k,
                     *args, **kwargs)

    lax.top_k = rounded
    try:
        yield
    finally:
        lax.top_k = top_k


class FaultyDecoders:
    """The pair of programs (``max_new`` 1 and the cell's) of a decoder built
    wrong, compiled at first use and kept for the next seed."""

    def __init__(self, job, fault: str) -> None:
        if not getattr(job.cfg, "moe_top_k", 0):
            raise KeyError(f"{fault}: {job.config['name']} routes no token")
        cfg, self.tracing = job.cfg, contextlib.nullcontext
        if fault == "top_k_less_one":
            cfg = dataclasses.replace(cfg, moe_top_k=cfg.moe_top_k - 1)
        else:
            self.tracing = _router_rounds_to_bfloat16
        self.programs = [job.make_decoder(cfg, job.mesh, max_new=n,
                                          **job.kept)
                         for n in (1, job.max_new)]

    def __call__(self, params, prompts):
        with self.tracing():    # the first call traces; later ones do not
            return [decoder(params, prompts) for decoder in self.programs]


def read(job, verdict, fault: str, seed: int, decoders: dict) -> dict:
    """One reading: ``fault`` planted in the cell of ``job`` at ``seed``,
    judged by the runner's ``verdict``."""
    began = time.perf_counter()
    params, prompts = job.draw(seed)
    if fault in PARAM_FAULTS:
        params = plant(job, fault, params)
    if fault in DECODER_FAULTS:
        if fault not in decoders:
            decoders[fault] = FaultyDecoders(job, fault)
        one, out = decoders[fault](params, prompts)
    else:
        one, out = job.first(params, prompts), job.full(params, prompts)
    one, answer = job.tokens_of(one), job.tokens_of(out)
    logits = out[1] if job.kept else None
    if fault in PARAM_FAULTS:       # the reference gets the sound ones
        del params
        params, _ = job.draw(seed)
    checks = job.compare(params, prompts, one, answer, logits)
    checks["repeat_equal"] = True       # one job a reading: nothing to repeat
    return {"fault": fault, "seed": seed, "correct": verdict(checks),
            **checks, "seconds": round(time.perf_counter() - began, 1)}


def tiny(cell: cells.Cell, **traffic) -> tuple[dict, dict]:
    """The cell's configuration at its ``tiny`` sizes, float32 on both sides
    (what is left between program and reference is the order of summation),
    and its traffic at a size the CPU decodes in a second."""
    config = copy.deepcopy(program.tiny(cell.config))
    config["entry"]["options"]["compute_dtype"] = "float32"
    config["param_dtype"] = "float32"
    return config, {**cell.traffic, **TINY_TRAFFIC, **traffic}


def run(workload: str, seeds: list[int], faults: list[str], small: bool,
        out: str | None = None, bench_dir: str = cells.BENCH_DIR):
    """Every reading of ``faults`` x ``seeds`` in the cell ``workload`` of
    the benchmark under ``bench_dir``, as dicts; printed, and appended to
    ``out``, as they come."""
    import jax

    cell = cells.resolve(workload, bench_dir)
    if "prompt_len" not in cell.traffic:
        raise ValueError(f"{workload} decodes nothing: the controls are of "
                         f"decode cells")
    unknown = [f for f in faults if f not in FAULTS]
    if unknown:
        raise ValueError(f"no fault {unknown} (have: {', '.join(FAULTS)})")
    config, traffic = tiny(cell) if small else (cell.config, cell.traffic)
    job = cell.runner.build(config, traffic, jax.devices()[:cell.chips])
    decoders: dict = {}
    readings = []
    for seed in seeds:
        for fault in faults:
            reading = {"workload": workload,
                       **read(job, cell.runner.verdict, fault, seed,
                              decoders)}
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
