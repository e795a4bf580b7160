"""Runner: batch generation jobs through ``make_decoder``, in a closed loop.

The decoder compiles one program per ``(prompt_len, max_new)`` and has no
batcher, so a job is one call: ``batch`` prompts of ``prompt_len`` tokens
in, ``max_new`` greedy tokens each out, read back to the host.  One sample
is a pair of jobs on the same prompts: ``first`` with ``max_new=1`` (prefill
and the first token) and ``full`` with the traffic file's ``max_new``.  The
difference of their medians is the time of ``max_new - 1`` cached steps with
prefill and dispatch cancelled.  ``facts()`` counts a cached step's bytes as
the step reads them (``lib/costs.py``): the parameters at the narrower of
``param_dtype`` and ``entry.options.compute_dtype``, less what the
reference's ``counts`` call ``lookup_params``, and the live keys and values.

What a decoder may return.  ``entry.decoder(cfg, mesh, max_new=N)`` gives a
callable ``(params, prompts (B, Tp) int32) -> tokens (B, Tp + N) int32``.
Where the configuration's ``entry`` names, under ``decoder_logits``, a
keyword of that factory, both programs are built with ``<keyword> =
reference_sequences`` (the traffic file's) and return ``(tokens, logits)``:
``logits`` float32 ``(n, N, vocab)``, what each generated token of the first
``n`` sequences was picked from, written by the timed program itself in every
job.  The timed jobs read back the tokens alone; the warm-up job's logits
are read once, for the check.

``correct`` (``compare`` makes the numbers, ``verdict`` asks): the window's
last job returns the tokens the first one did, the prompts came back, and
both programs agree on the first token.  Then, of ``reference_sequences`` of
the sequences, against the plain reference's full float32 forward over prompt
plus the program's own continuation, handed the program's parameters:

- a decoder that hands back tokens alone: every generated token's reference
  logit lies within ``check.deficit_max`` deviations of that position's
  maximum; no more than ``check.mismatch_share`` of the tokens are not the
  reference's own argmax; and no checked sequence's commonest generated
  token is more than ``REPEAT_SHARE_TOL`` of it (a continuation of one
  token repeated is decided by a lead that no fault moves, so it cannot be
  judged);
- a decoder that hands back logits: per position ``e``, the rms over the
  vocabulary of ``z - r``, each centred, over the deviation of ``r`` (``z``
  the program's logits, ``r`` the reference's); the median of ``e`` within
  the configuration's ``check.logit_err_median`` (what every position shows:
  precision, a dropped or mis-scaled term), the share of positions with
  ``e`` over ``check.logit_err_position`` within ``check.positions_over``
  (room for the tie flips of a router, none for a flip at every position),
  and every checked token the argmax of the logits handed back; the
  deficits, the mismatches and ``repeat_share`` are printed beside them and
  not asked.

Every limit but ``REPEAT_SHARE_TOL`` is the configuration file's, under its
``check`` key, ``{"limit": x, "why": "..."}`` each, because the noise is the
model's own; a configuration that hands back logits without them is refused
at ``build``, one that hands back tokens when a run is set up.

``benchmarks/controls.py`` plants faults in a cell's program and reads them
through ``compare`` and ``verdict``: plant them before trusting a ``correct``.
"""

from __future__ import annotations

import os
import statistics

import numpy as np

from benchmarks.lib import costs, program

# the benchmark directory this runner was found in: its configurations'
# references are found there too
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# A continuation whose commonest token is more than half of it is decided
# by that token's lead over every other, which no fault of a term or of
# the precision moves: every control reads what the sound program reads.
# Such a run says nothing and is refused whatever its deficits.  Sound
# continuations of the benchmark's two decode cells read at most 0.039 (152
# seeds, PERF.md section 2).  A property of the statistic, not of a model.
REPEAT_SHARE_TOL = 0.5

# The limits a configuration's ``check`` gives, by what its decoder hands
# back.  Tokens: ``deficit_max``, in standard deviations of a position's
# reference logits (the decoder multiplies in bfloat16 and keeps its stream
# and its cache in bfloat16; where it picks another token than the float32
# reference, the two were a rounding apart), and ``mismatch_share``, the
# share of the checked tokens that may be another token than the reference's
# argmax: the worst token says how far one token was moved, the count how
# many were, and holds a fault that moves many tokens a little.
TOKEN_LIMITS = ("deficit_max", "mismatch_share")
LOGIT_LIMITS = ("logit_err_median", "logit_err_position", "positions_over")


def build(config: dict, traffic: dict, devices) -> "Job":
    return Job(config, traffic, devices)


def check_limits(config: dict, names: tuple[str, ...]) -> dict:
    """The limits ``names`` from the configuration file's ``check`` key, each
    ``{"limit": x, "why": "..."}``; refused where one is missing or has no
    reason.  They are the configuration's own because the noise is the
    model's own: set each from the chip, above the sound program's largest
    reading and below the controls' smallest (``benchmarks/controls.py``)."""
    check = config.get("check")
    if not isinstance(check, dict):
        raise ValueError(
            f"{config.get('name')}: the file has no 'check' key: the limits "
            f"its decoder is held to ({', '.join(names)}) are the "
            f"configuration's own")
    limits = {}
    for name in names:
        row = check.get(name)
        if (not isinstance(row, dict) or not str(row.get("why", "")).strip()
                or not isinstance(row.get("limit"), (int, float))):
            raise ValueError(
                f"{config.get('name')}: check.{name} has to be "
                f'{{"limit": <number>, "why": "<its reason>"}}')
        limits[name] = float(row["limit"])
    return limits


def repeat_share(generated: np.ndarray) -> float:
    """Of (n, N) generated tokens: the largest share any one token has of
    its own sequence."""
    return max(float(np.unique(row, return_counts=True)[1].max()) / row.size
               for row in generated)


def logit_errors(z, r) -> np.ndarray:
    """Per position, the rms over the vocabulary of ``z - r``, each centred
    on its own mean, over the deviation of ``r``: (n, N, V) float32 twice ->
    (n, N)."""
    import jax.numpy as jnp

    z = z - z.mean(axis=-1, keepdims=True)
    r = r - r.mean(axis=-1, keepdims=True)
    return np.asarray(jnp.sqrt(jnp.mean(jnp.square(z - r), axis=-1))
                      / r.std(axis=-1))


def verdict(checks: dict) -> bool:
    """``correct`` from the numbers of ``Job.compare`` and ``repeat_equal``."""
    c = checks
    held = bool(c["shape_ok"] and c["prompt_kept"] and c["repeat_equal"]
                and c["first_token_equal"])
    if "logit_err_median" in c:
        return bool(held and c["tokens_are_argmax"]
                    and c["logit_err_median"] <= c["logit_err_median_limit"]
                    and c["positions_over"] <= c["positions_over_limit"])
    return bool(held and c["deficit_max"] <= c["deficit_max_limit"]
                and c["mismatch_share"] <= c["mismatch_share_limit"]
                and c["repeat_share"] <= c["repeat_share_limit"])


class Job:
    def __init__(self, config: dict, traffic: dict, devices) -> None:
        self.config, self.traffic = config, traffic
        self.devices = list(devices)
        self.mesh = program.mesh(config, self.devices)
        self.cfg = program.program_config(config)
        self.reference = program.reference(config, BENCH_DIR)
        self.shape = self.reference.Shape.from_config(config)
        self.shardings = program.param_shardings(config, self.cfg, self.mesh)
        self.make_decoder = program.import_dotted(config["entry"]["decoder"])
        self.batch = traffic["batch"]
        self.prompt_len, self.max_new = traffic["prompt_len"], traffic["max_new"]
        # where the decoder hands its logits back: the keyword that asks it
        # to, and the limits they are held to
        keyword = config["entry"].get("decoder_logits")
        self.limits = check_limits(config, LOGIT_LIMITS) if keyword else None
        self.kept = ({keyword: traffic["reference_sequences"]}
                     if keyword else {})
        self.first = self.make_decoder(self.cfg, self.mesh, max_new=1,
                                       **self.kept)
        self.full = self.make_decoder(self.cfg, self.mesh,
                                      max_new=self.max_new, **self.kept)

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

    def draw(self, seed: int) -> tuple[dict, np.ndarray]:
        """The seeded parameters, on the devices, as they are drawn for
        serving, and the prompts (on the host)."""
        import jax

        params = jax.block_until_ready(
            program.init_params(self.reference, self.config, self.shardings,
                                seed, serving=True))
        prompts = np.random.default_rng(seed).integers(
            0, self.shape.vocab, size=(self.batch, self.prompt_len))
        return params, prompts.astype(np.int32)

    def held_to(self) -> dict:
        """The configuration's limits.  Those for logits are read when the
        job is built; those for tokens when a run first needs them, because a
        configuration that no cell decodes is built for its counts alone and
        has none."""
        if self.limits is None:
            self.limits = check_limits(self.config, TOKEN_LIMITS)
        return self.limits

    def tokens_of(self, out) -> np.ndarray:
        """A job's tokens on the host; logits, where the decoder hands them
        back, stay on the device."""
        return np.asarray(out[0] if self.kept else out)

    def setup(self, seed: int, spans) -> None:
        import jax

        self.spans = spans
        self.held_to()      # refused before any work, where it has none
        with spans.span("setup.params"):
            self.params, prompts = self.draw(seed)
            self.n_params = costs.tree_count(self.params)
            self.prompts = jax.device_put(prompts, self._prompt_sharding())
        with spans.span("setup.warmup"):
            one = self.tokens_of(self.first(self.params, self.prompts))
            out = self.full(self.params, self.prompts)
            answer = self.tokens_of(out)
        self.answer = self.latest = answer
        with spans.span("setup.reference"):
            self.checks = self.compare(self.params, prompts, one, answer,
                                       out[1] if self.kept else None)

    def compare(self, params: dict, prompts: np.ndarray, one: np.ndarray,
                answer: np.ndarray, logits=None) -> dict:
        """The numbers ``verdict`` asks, of the tokens ``one`` and ``answer``
        that the two programs returned for ``prompts`` (and of the logits the
        second handed back), against the plain reference handed ``params``."""
        n, start = self.traffic["reference_sequences"], self.prompt_len
        total, limits = start + self.max_new, self.held_to()
        checks = {
            "shape_ok": answer.shape == (self.batch, total)
            and one.shape == (self.batch, start + 1)
            and (logits is None or tuple(logits.shape)
                 == (n, self.max_new, self.shape.vocab)),
            "prompt_kept": bool(np.array_equal(answer[:, :start], prompts)),
            "first_token_equal": bool(np.array_equal(one[:, -1],
                                                     answer[:, start])),
        }
        if logits is None:
            deficits = np.asarray(self.reference.token_deficits(
                self.shape, params, answer[:n], start))
        else:
            import jax.numpy as jnp

            z = jnp.asarray(logits, jnp.float32)
            r = self.reference.logits(self.shape, params,
                                      answer[:n])[:, start - 1:-1]
            generated = jnp.asarray(answer[:n, start:])
            chosen = jnp.take_along_axis(r, generated[..., None], -1)[..., 0]
            deficits = np.asarray((r.max(-1) - chosen) / r.std(-1))
            errors = logit_errors(z, r)
            checks.update(
                tokens_are_argmax=bool(jnp.array_equal(z.argmax(-1),
                                                       generated)),
                logit_err_median=float(np.median(errors)),
                logit_err_median_limit=limits["logit_err_median"],
                logit_err_max=float(errors.max()),
                logit_err_position_limit=limits["logit_err_position"],
                positions_over=float(
                    (errors > limits["logit_err_position"]).mean()),
                positions_over_limit=limits["positions_over"])
        mismatches = int((deficits > 0).sum())
        checks.update(
            deficit_max=float(deficits.max()),
            tokens_not_reference_argmax=mismatches,
            tokens_checked=int(deficits.size),
            mismatch_share=mismatches / deficits.size,
            repeat_share=repeat_share(answer[:n, start:]),
            repeat_share_limit=REPEAT_SHARE_TOL)
        if logits is None:
            checks.update(
                deficit_max_limit=limits["deficit_max"],
                mismatch_share_limit=limits["mismatch_share"])
        return checks

    def _job(self, name: str, decoder) -> None:
        span = self.spans.span
        with span(name):
            with span("dispatch"):
                out = decoder(self.params, self.prompts)
            with span("readback"):
                self.latest = self.tokens_of(out)

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
        stored = self.config["param_dtype"]
        compute = self.config["entry"]["options"].get("compute_dtype", stored)
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
                costs.step_param_bytes(
                    self.n_params, counts["lookup_params"],
                    jnp.dtype(stored).itemsize, jnp.dtype(compute).itemsize),
                counts["attention_layers"],
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
        return {"correct": verdict(c), "failed": 0, "checks": c}
