#!/usr/bin/env python3
"""Planted faults of a decoder-hybrid-decoder: the selective mixer's states
between steps and after the prefill, what it hands the gated memory units,
the differential heads' subtracted term, sub-norm and ``lam0``, the window's
extent and its ring, what a cross layer reads, the biases and the norm's
mean, in a decode cell whose configuration is built by
``ompi_tpu.models.plan.shared_state_config``, read by ``controls.read``
through the runner's own comparison.

    python3 benchmarks/controls_phi4_flash.py --workload <cell> \
        --seeds 1,2,3 [--faults sound,window_unbounded,...] [--tiny] \
        [--out FILE.jsonl] [--bench-dir DIR]

The arguments and the lines are those of ``benchmarks/controls.py``, whose
``sound``, ``all_lower_precision``, ``attention_layer_off`` (``wo`` of the
middle window layer) and ``ffn_layer_off`` this reads too, in the same process
and on the same job.  Its own:

``memory_unit_off``          ``gmu_out`` of the middle gated memory unit zeroed
``dt_bias_dropped``          ``sel_dtb`` of every Mamba layer zeroed
``attention_bias_dropped``   both projections' biases of every attending layer
                             zeroed
``differential_term_dropped``    ``lam`` = 0: one softmax, not two subtracted
``sub_norm_dropped``         no RMS norm of the subtracted context
``lambda_init_of_layer_0``   every layer's ``lam0`` is layer 0's, 0.2
``window_unbounded``         a window layer's prefill attends every earlier
                             position (a cached step cannot: the ring holds
                             ``sliding_window`` positions)
``window_one_short``         a window of ``sliding_window - 1`` keys, in the
                             prefill's band and in the ring's mask
``ring_not_wrapped``         a cached step past the window writes the ring's
                             last slot, not ``pos mod window``
``cross_reads_own_projection``   a cross layer attends K and V cut out of its
                             own input at its own positions, not the full
                             layer's cache
``memory_after_gate``        the memory handed on is ``y * silu(z)``
``memory_of_previous_step``  a whole-sequence pass hands position ``t`` the
                             memory of ``t - 1``; a cached step carries no
                             earlier memory to hand and stays sound, so in a
                             decoder the fault is in the first token's logits
                             alone
``skip_dropped_from_memory`` the memory handed on lacks the ``D x`` term
``ssm_state_not_carried``    every cached step's recurrence starts from a
                             zero state
``conv_state_off``           every cached step's convolution reads zeros for
                             its last inputs
``ssm_prefill_state_dropped``    the prefill hands over zero states
``layernorm_mean_kept``      every LayerNorm keeps the mean (an RMS norm with
                             a bias)
``stream_in_compute_type``   the configuration with ``residual_in_fp32`` false:
                             the program's own path that carries the residual
                             stream between rows in the compute type (the
                             precision below the one the file states; the
                             same program where that type is float32, as at
                             the tiny sizes)

The first three change the parameters, the last the configuration the decoders
are made of; the others are planted while a decoder
is traced, by wrapping a function the program calls (``differential._lambda``,
``_sub_norm``, ``_whole``, ``_ring_slot``, ``_ring_seen``, ``constants`` and
``mixer``; ``selective._memory``, ``_state_before``, ``_conv_before`` and
``mixer``; ``transformer._layernorm``) for as long as the trace takes, so the
program has nothing in it for the controls' sake.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import controls  # noqa: E402
from benchmarks.lib import cells  # noqa: E402

BIASES = ("wqkvb", "wob", "aqkvb", "aob", "xqb", "xob")
PARAM_FAULTS = {
    "memory_unit_off": (("gmu_out",), ("gmu_out",), controls._zero_layer),
    "dt_bias_dropped": (("sel_dtb",), ("sel_dtb",), controls._zero),
    "attention_bias_dropped": (BIASES, BIASES, controls._zero)}
TRACED_FAULTS = (
    "differential_term_dropped", "sub_norm_dropped", "lambda_init_of_layer_0",
    "window_unbounded", "window_one_short", "ring_not_wrapped",
    "cross_reads_own_projection", "memory_after_gate",
    "memory_of_previous_step", "skip_dropped_from_memory",
    "ssm_state_not_carried", "conv_state_off", "ssm_prefill_state_dropped",
    "layernorm_mean_kept")
CONFIG_FAULTS = ("stream_in_compute_type",)
SHARED = ("sound", "all_lower_precision", "attention_layer_off",
          "ffn_layer_off")
FAULTS = (*SHARED, *PARAM_FAULTS, *TRACED_FAULTS, *CONFIG_FAULTS)

# ``controls.read`` plants a fault of the parameters by its name in this table
controls.PARAM_FAULTS.update(PARAM_FAULTS)


@contextlib.contextmanager
def planted(fault: str):
    """While a decoder is traced: the model with ``fault`` in it."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.models import differential as diff
    from ompi_tpu.models import selective as sel
    from ompi_tpu.models import transformer as tfm

    whole, attend, constants = diff._whole, diff.mixer, diff.constants
    scanning = sel.mixer

    def own_input(cfg, lp, h, carry=None, forward_only=False, source=None):
        sz = lp["sizes"]
        if sz.cross:
            B, T, _ = h.shape
            width = sz.kv_heads * sz.head_dim
            source = tuple(h[..., lo:lo + width].reshape(
                B, T, sz.kv_heads, sz.head_dim) for lo in (0, width))
        return attend(cfg, lp, h, carry, forward_only=forward_only,
                      source=source)

    def one_short_ring(pos, window):
        # the slot after the one just written holds the oldest position
        oldest = (pos + 1) % window
        return ((jnp.arange(window)[None, :] <= pos)
                & ~((jnp.arange(window)[None, :] == oldest)
                    & (pos >= window - 1)))

    def previous_memory(cfg, lp, h, carry=None, forward_only=False):
        *out, m = scanning(cfg, lp, h, carry, forward_only)
        if carry is not None:       # a step carries no earlier memory
            return (*out, m)
        return (*out, jnp.pad(m, ((0, 0), (1, 0), (0, 0)))[:, :-1])

    def prefill_drops_its_states(cfg, lp, h, carry=None, forward_only=False):
        out = scanning(cfg, lp, h, carry, forward_only)
        if carry is not None:
            return out
        return (out[0], *(jnp.zeros_like(s) for s in out[1:-1]), out[-1])

    def mean_kept(x, scale, bias, eps=1e-5):
        xf = x.astype(jnp.float32)
        norm = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (norm * scale + bias).astype(x.dtype)

    patches = {
        "differential_term_dropped": [
            (diff, "_lambda", lambda rows, lam0: jnp.float32(0))],
        "sub_norm_dropped": [(diff, "_sub_norm", lambda o: o)],
        "lambda_init_of_layer_0": [
            (diff, "constants", lambda sz, layer: constants(sz, 0))],
        "window_unbounded": [
            (diff, "_whole", lambda q, k, v, window: whole(q, k, v, 0))],
        "window_one_short": [
            (diff, "_whole", lambda q, k, v, window: whole(
                q, k, v, window - 1 if window else 0)),
            (diff, "_ring_seen", one_short_ring)],
        "ring_not_wrapped": [
            (diff, "_ring_slot",
             lambda pos, window: jnp.minimum(pos, window - 1))],
        "cross_reads_own_projection": [(diff, "mixer", own_input)],
        "memory_after_gate": [
            (sel, "_memory", lambda y, skip, z: y * jax.nn.silu(
                z.astype(jnp.float32)))],
        "memory_of_previous_step": [(sel, "mixer", previous_memory)],
        "skip_dropped_from_memory": [
            (sel, "_memory", lambda y, skip, z: y - skip)],
        "ssm_state_not_carried": [
            (sel, "_state_before", lambda state_c: jnp.zeros(
                state_c.shape, jnp.float32))],
        "conv_state_off": [(sel, "_conv_before", jnp.zeros_like)],
        "ssm_prefill_state_dropped": [
            (sel, "mixer", prefill_drops_its_states)],
        "layernorm_mean_kept": [(tfm, "_layernorm", mean_kept)],
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


class FaultyJob:
    """The cell's job with its two programs traced, at their first call, with
    ``fault`` planted; kept for the next seed.  Everything else is the job's
    own."""

    def __init__(self, job, fault: str) -> None:
        plan = getattr(job.cfg, "plan", None)
        if plan is None or not plan.reads:
            raise KeyError(f"{fault}: {job.config['name']} has no plan whose "
                           f"rows read another row's state")
        from benchmarks.lib import program
        from ompi_tpu.models import decode

        self._job = job
        cfg = job.cfg
        if fault in CONFIG_FAULTS:      # the door's other branch
            cfg = program.program_config({**job.config,
                                          "residual_in_fp32": False})
        # the decoders of one configuration on one mesh share their prefill
        # program (``decode._prefill_program``): this pair has one of its
        # own, traced with the fault in it, and no later pair finds it
        decode._prefill_program.cache_clear()
        self.first, self.full = [
            self._program(fault, job.make_decoder(
                cfg, job.mesh, max_new=n, **job.kept))
            for n in (1, job.max_new)]
        decode._prefill_program.cache_clear()

    @staticmethod
    def _program(fault, decoder):
        def run(params, prompts):
            # the first call traces; later ones do not
            with planted(fault):
                return decoder(params, prompts)
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
        of = (FaultyJob(job, fault)
              if fault in TRACED_FAULTS + CONFIG_FAULTS else job)
        for seed in seeds:
            reading = {"workload": workload,
                       **controls.read(of, cell.runner.verdict, fault, seed,
                                       decoders)}
            readings.append(reading)
            line = json.dumps(reading)
            print(line, flush=True)
            if out:
                with open(out, "a", encoding="utf-8") as f:
                    f.write(line + "\n")
        del of
        decoders.clear()
        gc.collect()
        if not small and fault in TRACED_FAULTS + CONFIG_FAULTS:
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
