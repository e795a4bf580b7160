#!/usr/bin/env python3
"""Planted faults of the shortcut-connected layer: where its routed branch
reads and whether it lands, the second attention, the identity experts and
their weights, the router's selection bias and renormalisation, the weights of
the picks held elsewhere, the two latents' scale corrections and the query
latent's norm, in a decode cell whose configuration is built by
``ompi_tpu.models.plan.shortcut_moe_config``, read by ``controls.read``
through the runner's own comparison; and the router's counters over a whole
batch, from the program's own routing.

    python3 benchmarks/controls_longcat_flash.py --workload <cell> \
        --seeds 1,2,3 [--faults sound,shortcut_dropped,...] [--tiny] \
        [--out FILE.jsonl] [--bench-dir DIR]

The arguments and the lines are those of ``benchmarks/controls.py``, whose
``sound`` and ``all_lower_precision`` this reads too, in the same process
and on the same job.  Its own:

``shortcut_dropped``         the routed branch adds nothing where it lands: a
                             layer is its two attentions and two dense MLPs
``shortcut_reads_second_sublayer``    the decoder built with every branch
                             reading the second sublayer's normed
                             post-attention stream (an ordinary MoE beside
                             the second MLP, nothing across)
``second_attention_dropped`` the second sublayer's attention adds nothing
                             (its output projection zeroed as it is read)
``identity_experts_dropped`` the picks among the identity experts add
                             nothing
``identity_unweighted``      each such pick adds the token itself, not its
                             weight times the token
``selection_bias_dropped``   the decoder built with ``moe_select_bias`` off:
                             the top-k of the probabilities themselves
``renormalised``             the decoder built with ``moe_norm_topk`` on: a
                             token's twelve weights divided by their sum
``renormalised_over_held``   the held picks' weights divided by their sum
                             over the picks this chip holds, not left as the
                             probabilities they are
``scale_q_lora_dropped``     the decoder built with the query latent's
                             correction 1
``scale_kv_lora_dropped``    the decoder built with the latent's correction 1
``query_latent_norm_dropped``    the query latent is not normed (its scale
                             and its correction stay), in both passes
``counters``                 nothing wrong: the sound programs traced with a
                             host callback behind every routed layer, which
                             hands back, a call, how many picks were identity
                             experts and how many rows each held expert got;
                             the reading carries ``moe_identity_pick_share``,
                             ``moe_held_pick_share`` and
                             ``moe_empty_group_share`` (the share of (step,
                             held expert) pairs without a row) over the
                             cached steps of the whole batch

The five ``decoder built`` ones change the configuration a decoder is built
from; the others are planted while a decoder is traced, by wrapping a
function the program calls (``plan._mlp``, ``plan._mixer_leaves``,
``moe.routed_moe``, ``transformer._rmsnorm``) for as long as the trace takes:
the wrapper hands the sound function other arguments or adds to what it
returns, so the program has nothing in it for the controls' sake.
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

CONFIG_FAULTS = ("shortcut_reads_second_sublayer", "selection_bias_dropped",
                 "renormalised", "scale_q_lora_dropped",
                 "scale_kv_lora_dropped")
TRACED_FAULTS = ("shortcut_dropped", "second_attention_dropped",
                 "identity_experts_dropped", "identity_unweighted",
                 "renormalised_over_held", "query_latent_norm_dropped")
COUNTERS = "counters"
SHARED = ("sound", "all_lower_precision")
FAULTS = (*SHARED, *CONFIG_FAULTS, *TRACED_FAULTS, COUNTERS)

# what the ``counters`` callback was handed, a routed call each: (tokens,
# identity picks, held picks, rows of each held expert)
_counted: list = []


def faulty_config(cfg, fault: str):
    """The program's configuration with ``fault`` in it."""
    pl = cfg.plan
    if fault == "shortcut_reads_second_sublayer":
        return dataclasses.replace(cfg, plan=dataclasses.replace(
            pl, branches=tuple((kind, lands, lands)
                               for kind, _reads, lands in pl.branches)))
    if fault in ("scale_q_lora_dropped", "scale_kv_lora_dropped"):
        field = "q_scale" if "q_lora" in fault else "kv_scale"
        return dataclasses.replace(cfg, plan=dataclasses.replace(
            pl, mla=dataclasses.replace(pl.mla, **{field: 1.0})))
    changes = {"selection_bias_dropped": {"moe_select_bias": False},
               "renormalised": {"moe_norm_topk": True}}
    return dataclasses.replace(cfg, **changes.get(fault, {}))


@contextlib.contextmanager
def planted(fault: str, cfg=None):
    """While a decoder is traced: the model with ``fault`` in it.  ``cfg``:
    the program's configuration (the plan's second rows, the query latent's
    width, which no other normed vector of the model may have)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.models import plan, transformer
    from ompi_tpu.parallel import moe

    mlp, leaves_of = plan._mlp, plan._mixer_leaves
    routed, rmsnorm = moe.routed_moe, transformer._rmsnorm

    def branch_adds_nothing(cfg, comm, params, layer, kind, h, branch=False):
        return (jnp.zeros_like(h) if branch
                else mlp(cfg, comm, params, layer, kind, h))

    def second_attention_projects_to_nothing(cfg, params, layer, kind):
        lp = leaves_of(cfg, params, layer, kind)
        return {**lp, "wo": lp["wo"] * 0} if cfg.plan.second(layer) else lp

    def routing(x, params, top_k):
        """(probabilities (B, T, E), picks (B, T, k)) as the sound layer
        makes them."""
        prob = jax.nn.softmax(jnp.einsum(
            "btd,de->bte", x.astype(jnp.float32),
            params["wg"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST), axis=-1)
        biased = (prob + params["wgb"].astype(jnp.float32)
                  if "wgb" in params else prob)
        return prob, lax.top_k(biased, top_k)[1]

    def rerouted(x, params, top_k, held=None, zero=0, scale=1.0, **kwargs):
        """The layer put together from the sound function's held part (no
        identity experts: ``zero`` 0 under the same ``held``) and the
        identity picks' part made here, one of them wrong."""
        n_real = params["wg"].shape[-1] - zero
        held = held or (0, n_real)
        part = routed(x, params, top_k, held=held, zero=0, scale=scale,
                      **kwargs).astype(jnp.float32)
        prob, at = routing(x, params, top_k)
        gate = jnp.take_along_axis(prob, at, axis=-1) * scale
        is_zero = at >= n_real
        here = (at >= held[0]) & (at < held[0] + held[1])
        weight = jnp.sum(jnp.where(is_zero, gate, 0.0), axis=-1)
        if fault == "identity_experts_dropped":
            weight = weight * 0
        elif fault == "identity_unweighted":
            weight = jnp.sum(is_zero, axis=-1).astype(jnp.float32)
        else:       # renormalised_over_held
            over = jnp.sum(jnp.where(here, gate, 0.0), axis=-1) / scale
            part = part / jnp.where(over > 0, over, 1.0)[..., None]
        return (part + weight[..., None] * x.astype(jnp.float32)
                ).astype(x.dtype)

    def query_latent_not_normed(x, scale, eps):
        """``_rmsnorm`` but for the query latent, which is told by its
        width: its scale (the correction folded in) stays."""
        if x.shape[-1] == scale.shape[-1] == cfg.plan.mla.q_rank:
            return (x.astype(jnp.float32) * scale).astype(x.dtype)
        return rmsnorm(x, scale, eps)

    def counted(x, params, top_k, held=None, zero=0, scale=1.0, **kwargs):
        n_real = params["wg"].shape[-1] - zero
        first, count = held or (0, n_real)
        _prob, at = routing(x, params, top_k)
        rows = jnp.sum(at[..., None] == first + jnp.arange(count),
                       axis=(0, 1, 2))
        jax.debug.callback(
            lambda *got: _counted.append(tuple(int(g) if g.ndim == 0
                                               else g.tolist() for g in got)),
            jnp.int32(at.shape[0] * at.shape[1]), jnp.sum(at >= n_real),
            jnp.sum(rows), rows)
        return routed(x, params, top_k, held=held, zero=zero, scale=scale,
                      **kwargs)

    patches = {
        "shortcut_dropped": [(plan, "_mlp", branch_adds_nothing)],
        "second_attention_dropped": [
            (plan, "_mixer_leaves", second_attention_projects_to_nothing)],
        "identity_experts_dropped": [(moe, "routed_moe", rerouted)],
        "identity_unweighted": [(moe, "routed_moe", rerouted)],
        "renormalised_over_held": [(moe, "routed_moe", rerouted)],
        "query_latent_norm_dropped": [(transformer, "_rmsnorm",
                                       query_latent_not_normed)],
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
    """The three shares from what the ``counters`` callback was handed since
    it was last read: over the calls of ``batch`` tokens (a cached step a
    layer; a prefill's pass holds more)."""
    import numpy as np

    steps = [c for c in _counted if c[0] == batch]
    _counted.clear()
    if not steps:
        return {}
    picks = len(steps) * batch * top_k
    rows = np.asarray([c[3] for c in steps])
    return {"moe_identity_pick_share": sum(c[1] for c in steps) / picks,
            "moe_held_pick_share": sum(c[2] for c in steps) / picks,
            "moe_empty_group_share": float((rows == 0).mean()),
            "routed_calls_counted": len(steps)}


class FaultyJob:
    """The cell's job with its two programs built wrong: from a faulty
    configuration, or traced, at their first call, with ``fault`` planted;
    kept for the next seed.  Everything else is the job's own."""

    def __init__(self, job, fault: str) -> None:
        plan = getattr(job.cfg, "plan", None)
        if plan is None or not plan.branches:
            raise KeyError(f"{fault}: {job.config['name']} has no plan with "
                           f"a branch across its rows")
        from jax.sharding import PartitionSpec as P

        from ompi_tpu.models import decode
        from ompi_tpu.models.transformer import param_specs

        self._job = job
        cfg = faulty_config(job.cfg, fault)
        if cfg.plan.mla.q_rank in (cfg.d_model, cfg.plan.mla.kv_rank):
            raise ValueError("the query latent is told from the stream and "
                             "from the latent by its width")
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
        def run(params, prompts):
            # the first call traces; later ones do not
            with planted(fault, cfg):
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
