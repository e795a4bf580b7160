#!/usr/bin/env python3
"""Planted faults of power retention's own: the carried state and its
normaliser, the gate and its offset, the power and its feature map, which
K/V head a query head reads, the q/k norm and the rotary embedding, in a
decode cell whose configuration has the core of
``ompi_tpu/models/retention.py``, read by ``controls.read`` through the
runner's own comparison.

    python3 benchmarks/controls_brumby.py --workload <cell> \
        --seeds 1,2,3 [--faults sound,state_not_carried,...] [--tiny] \
        [--out FILE.jsonl] [--bench-dir DIR]

The arguments and the lines are those of ``benchmarks/controls.py``, whose
``sound`` and ``all_lower_precision`` this reads too, in the same process
and on the same job.  Its own:

``state_not_carried``        a cached step starts from a zero matrix state
                             and a zero normaliser (what it writes is never
                             read): every step sees its own token alone
``normaliser_not_carried``   ``z`` from zero each step, ``S`` carried
``normaliser_dropped``       ``y = S^T phi(q)``: no quotient, in both passes
``gate_dropped``             g = 1: the log decay zeroed, in both passes
``gate_offset_dropped``      the decoder built with ``gate_offset`` 0: g =
                             0.5 at a zero projection for 0.999
``degree_one``               ``q . k`` for its square, in the quadratic
                             form and through the feature map alike
``cross_terms_unscaled``     the sqrt 2 left out of ``phi``: what is read
                             through the state weighs the cross terms half,
                             what a chunk reads of itself stays exact
``group_state_mixed``        a query head reads the state of the K/V head
                             before its own, in both passes
``qk_norm_dropped``          the decoder built with ``qk_norm`` off
``rope_dropped``             q and k unrotated, in both passes
``state_in_bfloat16``        the decoder built with ``S`` and ``z`` carried
                             in bfloat16

The three ``decoder built`` ones change the configuration a decoder is built
from; the others are planted while a decoder is traced, by wrapping a
function the program calls (``retention.chunked``, ``retention.read``,
``retention.write``, ``retention.phi``, ``retention._power``,
``retention._quotient``, ``retention._state_before``, ``transformer._rope``)
for as long as the trace takes: the wrapper hands the sound function other
arguments, so the program has nothing in it for the controls' sake.
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

CONFIG_FAULTS = ("gate_offset_dropped", "qk_norm_dropped",
                 "state_in_bfloat16")
TRACED_FAULTS = ("state_not_carried", "normaliser_not_carried",
                 "normaliser_dropped", "gate_dropped", "degree_one",
                 "cross_terms_unscaled", "group_state_mixed", "rope_dropped")
SHARED = ("sound", "all_lower_precision")
FAULTS = (*SHARED, *CONFIG_FAULTS, *TRACED_FAULTS)


def faulty_config(cfg, fault: str):
    """The program's configuration with ``fault`` in it."""
    if fault == "qk_norm_dropped":
        return dataclasses.replace(cfg, qk_norm=False)
    changes = {"gate_offset_dropped": {"gate_offset": 0.0},
               "state_in_bfloat16": {"state_dtype": "bfloat16"}}
    return dataclasses.replace(cfg, retention=dataclasses.replace(
        cfg.retention, **changes.get(fault, {})))


@contextlib.contextmanager
def planted(fault: str):
    """While a decoder is traced: the model with ``fault`` in it."""
    import jax.numpy as jnp
    import numpy as np

    from ompi_tpu.models import retention, transformer

    chunked, read, write = retention.chunked, retention.read, retention.write
    phi, before = retention.phi, retention._state_before

    def zero(*widths):
        """``_state_before`` of a stack this many axes wide, as zeros."""
        return lambda stack, layer: (
            jnp.zeros_like(before(stack, layer)) if stack.ndim in widths
            else before(stack, layer))

    def first_power(u, scale=1.0):
        """``u`` itself where ``phi(u)`` is expected, zeros after it:
        ``phi(q) . phi(k) = q . k`` times the scale."""
        pad = [(0, 0)] * (u.ndim - 1) + [
            (0, retention.state_dim(u.shape[-1]) - u.shape[-1])]
        return jnp.pad(u.astype(jnp.float32) * scale, pad)

    def unscaled(u, scale=1.0):
        d = u.shape[-1]
        return phi(u, scale) / jnp.asarray(
            np.repeat(retention._weights(d), d))

    def turned(y):      # each K/V head's in the place of the next one's
        return jnp.roll(y, 1, axis=-2 if y.ndim == 4 else -1)

    patches = {
        "state_not_carried": [(retention, "_state_before", zero(4, 5))],
        "normaliser_not_carried": [(retention, "_state_before", zero(4))],
        "normaliser_dropped": [
            (retention, "_quotient", lambda num, den, eps: num)],
        "gate_dropped": [
            (retention, "chunked", lambda q, k, v, lg, *a: chunked(
                q, k, v, jnp.zeros_like(lg), *a)),
            (retention, "read", lambda S, z, q, k, v, lg, eps: read(
                S, z, q, k, v, jnp.zeros_like(lg), eps)),
            (retention, "write", lambda S, z, k, v, lg: write(
                S, z, k, v, jnp.zeros_like(lg)))],
        "degree_one": [(retention, "_power", lambda s: s),
                       (retention, "phi", first_power)],
        "cross_terms_unscaled": [(retention, "phi", unscaled)],
        # k, v and the gate turned by one K/V head, in both passes: the
        # state in place g is then head g - 1's, which head g's queries read
        "group_state_mixed": [
            (retention, "chunked", lambda q, k, v, lg, *a: chunked(
                q, turned(k), turned(v), turned(lg), *a)),
            (retention, "read", lambda S, z, q, k, v, lg, eps: read(
                S, z, q, jnp.roll(k, 1, 1), jnp.roll(v, 1, 1),
                jnp.roll(lg, 1, 1), eps)),
            (retention, "write", lambda S, z, k, v, lg: write(
                S, z, jnp.roll(k, 1, 1), jnp.roll(v, 1, 1),
                jnp.roll(lg, 1, 1)))],
        "rope_dropped": [(transformer, "_rope", lambda x, *_a, **_k: x)],
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
        if getattr(job.cfg, "retention", None) is None:
            raise KeyError(f"{fault}: {job.config['name']} has no power "
                           f"retention")
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
