#!/usr/bin/env python3
"""Planted faults of an indexed latent layer and of the group-limited router
beside it: the selection, its size, what the index's queries read, the
index's rotation, the scaled rotation's frequencies and its softmax scale,
the router's groups, bias and scale, the shared expert, in a decode cell
whose configuration is built by ``ompi_tpu.models.plan.mla_moe_config`` with
an index, read by ``controls.read`` through the runner's own comparison; and
the router's counters over the whole batch.

    python3 benchmarks/controls_deepseek_v32.py --workload <cell> \
        --seeds 1,2,3 [--faults sound,selection_dropped,...] [--tiny] \
        [--out FILE.jsonl] [--bench-dir DIR]

The arguments and the lines are those of ``benchmarks/controls.py``, whose
``sound`` and ``all_lower_precision`` this reads too, in the same process
and on the same job.  Its own:

``selection_dropped``        the decoder built with an ``index_topk`` no
                             cache reaches: every query reads every earlier
                             row, in the prefill and in the steps
``topk_halved``              the decoder built with half the ``index_topk``
``mscale_dropped``           the decoder built with ``mscale`` and
                             ``mscale_all_dim`` 0: the frequencies stay
                             scaled, the scores lose their ``m^2``
``group_limit_dropped``      the decoder built without groups: a token picks
                             its top 8 among all the router's outputs
``selection_bias_dropped``   the decoder built with ``moe_select_bias`` off
``scale_dropped``            the decoder built with ``moe_scale`` 1
``shared_expert_off``        the decoder built with ``moe_shared`` 0
``index_reads_layer_input``  the index's queries are projected from the
                             layer's normed input (its first ``q_lora_rank``
                             columns) and not from the query latent
``index_rotation_dropped``   neither the index's queries nor its key is
                             rotated
``yarn_dropped``             the rotation turns every pair by ``theta^(-2i /
                             rope)``, the published frequencies unscaled;
                             the scores keep their ``m^2``
``index_keys_not_carried``   a cached step writes zeros for its index key:
                             the steps after it score that position 0
``counters``                 the sound program, its router counted: a
                             reading's line also has ``moe_held_pick_share``
                             (the share of a cached step's picks that land on
                             the held experts) and ``moe_empty_group_share``
                             (the share of (step, layer, held expert) triples
                             without a row), over the whole batch, beside the
                             0.776 that the reference's ``counts`` take from
                             the shapes

The seven ``decoder built`` ones change the configuration a decoder is built
from; the others are planted while a decoder is traced, by wrapping a
function the program calls (``mla.rotate``, ``mla._index_rotation``,
``sparse_index.project``, ``moe.routed_moe``) for as long as the trace takes,
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

from benchmarks import controls  # noqa: E402
from benchmarks.lib import cells  # noqa: E402

CONFIG_FAULTS = ("selection_dropped", "topk_halved", "mscale_dropped",
                 "group_limit_dropped", "selection_bias_dropped",
                 "scale_dropped", "shared_expert_off")
TRACED_FAULTS = ("index_reads_layer_input", "index_rotation_dropped",
                 "yarn_dropped", "index_keys_not_carried")
COUNTERS = "counters"
SHARED = ("sound", "all_lower_precision")
FAULTS = (*SHARED, *CONFIG_FAULTS, *TRACED_FAULTS, COUNTERS)

_counted: list = []     # (tokens, picks held here, rows of each held expert)


def faulty_config(cfg, fault: str):
    """The program's configuration with ``fault`` in it."""
    ml = cfg.plan.mla

    def latent(**changes):
        return dataclasses.replace(cfg, plan=dataclasses.replace(
            cfg.plan, mla=dataclasses.replace(ml, **changes)))

    if fault == "selection_dropped":
        return latent(index=dataclasses.replace(ml.index, topk=1 << 30))
    if fault == "topk_halved":
        return latent(index=dataclasses.replace(ml.index,
                                                topk=ml.index.topk // 2))
    if fault == "mscale_dropped":
        return latent(yarn=dataclasses.replace(ml.yarn, mscale=0.0,
                                               mscale_all_dim=0.0))
    changes = {"group_limit_dropped": {"moe_groups": None},
               "selection_bias_dropped": {"moe_select_bias": False},
               "scale_dropped": {"moe_scale": 1.0},
               "shared_expert_off": {"moe_shared": 0}}
    return dataclasses.replace(cfg, **changes.get(fault, {}))


@contextlib.contextmanager
def planted(fault: str):
    """While a decoder is traced: the model with ``fault`` in it."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.models import mla, sparse_index
    from ompi_tpu.parallel import moe

    rotate, project, routed = mla.rotate, sparse_index.project, moe.routed_moe

    def queries_from_the_input(cfg, lp, x, positions, queries_from=None,
                               **own):
        return project(cfg, lp, x, positions,
                       queries_from=x[..., :queries_from.shape[-1]], **own)

    def no_key_of_a_step(cfg, lp, x, positions, **own):
        qi, ki, wi = project(cfg, lp, x, positions, **own)
        return qi, (jnp.zeros_like(ki) if x.shape[1] == 1 else ki), wi

    def picks(x, params, top_k, groups=None):
        """(B, T, k) picks as the sound layer makes them."""
        choice = jax.nn.sigmoid(jnp.einsum(
            "btd,de->bte", x.astype(jnp.float32),
            params["wg"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        choice = choice + params["wgb"].astype(jnp.float32)
        if groups is not None:
            choice = moe._within_groups(
                choice.reshape(-1, choice.shape[-1]),
                groups).reshape(choice.shape)
        return lax.top_k(choice, top_k)[1]

    def counted(x, params, top_k, held=None, **kwargs):
        first, count = held
        at = picks(x, params, top_k, kwargs.get("groups"))
        rows = jnp.sum(at[..., None] == first + jnp.arange(count),
                       axis=(0, 1, 2))
        jax.debug.callback(
            lambda *got: _counted.append(tuple(
                int(g) if g.ndim == 0 else g.tolist() for g in got)),
            jnp.int32(at.shape[0] * at.shape[1]), jnp.sum(rows), rows)
        return routed(x, params, top_k, held=held, **kwargs)

    patches = {
        "index_reads_layer_input": [
            (sparse_index, "project", queries_from_the_input)],
        "index_rotation_dropped": [
            (mla, "_index_rotation", lambda _ml: lambda y, _positions: y)],
        "yarn_dropped": [(mla, "rotate", lambda x, at, theta, *_scaled:
                          rotate(x, at, theta))],
        "index_keys_not_carried": [
            (sparse_index, "project", no_key_of_a_step)],
        COUNTERS: [(moe, "routed_moe", counted)],
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


def counters(batch: int, top_k: int) -> dict:
    """The shares from what the ``counters`` callback was handed since it was
    last read: over the calls of ``batch`` tokens (a cached step a routed
    layer; a prefill's pass holds more)."""
    import numpy as np

    steps = [c for c in _counted if c[0] == batch]
    _counted.clear()
    if not steps:
        return {}
    rows = np.asarray([c[2] for c in steps])
    return {"moe_held_pick_share":
            sum(c[1] for c in steps) / (len(steps) * batch * top_k),
            "moe_empty_group_share": float((rows == 0).mean()),
            "routed_calls_counted": len(steps)}


class FaultyJob:
    """The cell's job with its two programs built wrong: from a faulty
    configuration, or traced, at their first call, with ``fault`` planted;
    kept for the next seed.  Everything else is the job's own."""

    def __init__(self, job, fault: str) -> None:
        plan = getattr(job.cfg, "plan", None)
        if plan is None or getattr(plan.mla, "index", None) is None:
            raise KeyError(f"{fault}: {job.config['name']} has no plan of "
                           f"indexed latent layers")
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
    readings = []
    # a fault at a time, every seed of it, and then its pair of programs is
    # let go (``controls_kimi_vl.run`` says why)
    for fault in faults:
        of = job if fault in SHARED else FaultyJob(job, fault)
        for seed in seeds:
            _counted.clear()
            reading = {"workload": workload,
                       **controls.read(of, cell.runner.verdict, fault, seed,
                                       {})}
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
        gc.collect()
        if not small and fault not in SHARED:
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
