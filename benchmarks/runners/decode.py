"""Runner: batch generation jobs through ``make_decoder``, in a closed loop.

The decoder compiles one program per ``(prompt_len, max_new)`` and has no
batcher, so a job is one call: ``batch`` prompts of ``prompt_len`` tokens
in, ``max_new`` greedy tokens each out, read back to the host.  One sample
is a pair of jobs on the same prompts: ``first`` with ``max_new=1`` (prefill
and the first token) and ``full`` with the traffic file's ``max_new``.  The
difference of their medians is the time of ``max_new - 1`` cached steps with
prefill and dispatch cancelled (the method of ``bench.matrix_decode_
throughput``, at this cell's sizes and with medians).

``correct``: the window's last job returns the tokens the first one did;
both programs agree on the first token; and for ``reference_sequences`` of the sequences every
generated token's logit in the plain reference's full forward over prompt
plus continuation lies within ``DEFICIT_TOL`` of that position's maximum.
"""

from __future__ import annotations

import os
import statistics

import numpy as np

from benchmarks.lib import costs, program

# the benchmark directory this runner was found in: its configurations'
# references are found there too
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# In standard deviations of a position's reference logits.  The decoder
# multiplies in bfloat16 and keeps its cache in bfloat16; where it picks
# another token than the float32 reference, the two were a rounding apart.
# On the chip 2 of 3328 checked tokens were not the reference's argmax, each
# 0.009 deviations under it (13 seeds, the PR that added this file,
# PERF.md).  0.05 is five times that; a wrong token sits about four
# deviations down, and a path that drops a term or rounds to fewer bits
# than bfloat16 keeps moves logits by tenths.
DEFICIT_TOL = 0.05


def build(config: dict, traffic: dict, devices) -> "Job":
    return Job(config, traffic, devices)


class Job:
    def __init__(self, config: dict, traffic: dict, devices) -> None:
        self.config, self.traffic = config, traffic
        self.devices = list(devices)
        self.mesh = program.mesh(config, self.devices)
        self.cfg = program.program_config(config)
        self.reference = program.reference(config, BENCH_DIR)
        self.shape = self.reference.Shape.from_config(config)
        self.shardings = program.param_shardings(config, self.cfg, self.mesh)
        make_decoder = program.import_dotted(config["entry"]["decoder"])
        self.batch = traffic["batch"]
        self.prompt_len, self.max_new = traffic["prompt_len"], traffic["max_new"]
        self.first = make_decoder(self.cfg, self.mesh, max_new=1)
        self.full = make_decoder(self.cfg, self.mesh, max_new=self.max_new)

    def _prompt_sharding(self):
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        return NamedSharding(self.mesh, P("dp", None))

    def programs(self) -> dict:
        """name -> (jitted program, abstract arguments), for compiling at
        the real sizes for devices that are described and not attached."""
        import jax

        params = program.abstract_params(self.reference, self.config,
                                         self.shardings)
        prompt = jax.ShapeDtypeStruct((self.batch, self.prompt_len), np.int32,
                                      sharding=self._prompt_sharding())
        return {"decode_first": (jax.jit(self.first), (params, prompt)),
                "decode_full": (jax.jit(self.full), (params, prompt))}

    # ---- the run ---------------------------------------------------------

    def setup(self, seed: int, spans) -> None:
        import jax

        self.spans = spans
        with spans.span("setup.params"):
            self.params = jax.block_until_ready(
                program.init_params(self.reference, self.config,
                                    self.shardings, seed))
            self.n_params = costs.tree_count(self.params)
            prompts = np.random.default_rng(seed).integers(
                0, self.shape.vocab, size=(self.batch, self.prompt_len))
            self.prompts = jax.device_put(prompts.astype(np.int32),
                                          self._prompt_sharding())
        with spans.span("setup.warmup"):
            one = np.asarray(self.first(self.params, self.prompts))
            answer = np.asarray(self.full(self.params, self.prompts))
        self.answer = self.latest = answer
        n_ref = self.traffic["reference_sequences"]
        with spans.span("setup.reference"):
            deficits = np.asarray(self.reference.token_deficits(
                self.shape, self.params, answer[:n_ref], self.prompt_len))
        total = self.prompt_len + self.max_new
        self.checks = {
            "shape_ok": answer.shape == (self.batch, total)
            and one.shape == (self.batch, self.prompt_len + 1),
            "prompt_kept": bool(np.array_equal(answer[:, :self.prompt_len],
                                               prompts)),
            "first_token_equal": bool(np.array_equal(
                one[:, -1], answer[:, self.prompt_len])),
            "deficit_max": float(deficits.max()),
            "deficit_tol": DEFICIT_TOL,
            "tokens_not_reference_argmax": int((deficits > 0).sum()),
            "tokens_checked": int(deficits.size),
        }

    def _job(self, name: str, decoder) -> None:
        span = self.spans.span
        with span(name):
            with span("dispatch"):
                out = decoder(self.params, self.prompts)
            with span("readback"):
                self.latest = np.asarray(out)

    def sample(self) -> None:
        with self.spans.span("sample"):
            self._job("first", self.first)
            self._job("full", self.full)

    def close(self) -> None:
        pass

    # ---- the results -----------------------------------------------------

    def facts(self) -> dict:
        import jax.numpy as jnp

        counts = program.counts(self.reference, self.shape)
        return {
            "chips": len(self.devices),
            "n_params": self.n_params,
            "batch": self.batch, "max_new": self.max_new,
            "prompt_len": self.prompt_len,
            "counts": counts,
            "prefill_flops": costs.prefill_flops(
                counts["active_params"], counts["projection_params"],
                counts["attention_layers"], counts["attention_width"],
                self.batch, self.prompt_len),
            "decode_step_bytes": costs.decode_step_bytes(
                costs.tree_bytes(self.params), counts["attention_layers"],
                self.batch, self.prompt_len, self.max_new,
                counts["kv_elements"],
                jnp.dtype(self.config["kv_cache_dtype"]).itemsize,
                counts["state_elements"]),
        }

    def end_to_end(self, durations: dict[str, list[float]]) -> dict:
        first = statistics.median(durations["first"])
        full = statistics.median(durations["full"])
        return {"decode_tokens_per_s":
                self.batch * (self.max_new - 1) / (full - first),
                "ttft_ms": first * 1e3}

    def finish(self) -> dict:
        # the window's last job is a ``full`` one: greedy decoding of the
        # same prompts has to repeat token for token
        c = {**self.checks,
             "repeat_equal": bool(np.array_equal(self.latest, self.answer))}
        return {
            "correct": bool(c["shape_ok"] and c["prompt_kept"]
                            and c["repeat_equal"] and c["first_token_equal"]
                            and c["deficit_max"] <= DEFICIT_TOL),
            "failed": 0,
            "checks": c,
        }
