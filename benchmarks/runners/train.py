"""Runner: a training job, as a closed loop of blocked optimizer steps.

One sample is what a training script does per step: take the next batch
from ``models.data.train_stream``, call the ``make_train_step`` program, and
wait for the loss.  The traffic file gives the batch, the sequence length,
the learning rate and how many samples a traced run records.

``correct``: the loss of the first step (initial parameters, first batch)
equals the plain reference's loss on the same batch and parameters; every
loss is finite; the last is below the first.
"""

from __future__ import annotations

import math
import os
import statistics

import numpy as np

from benchmarks.lib import costs, program

# the benchmark directory this runner was found in: its configurations'
# references are found there too
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The program multiplies in bfloat16 (8 bits of mantissa, float32
# accumulation) and the reference in float32 at "highest".  At the initial
# parameters the rounding of activations and weights is independent from
# token to token and averages out over the batch's 16k positions: on the
# chip the two losses differed by 8e-6 to 1.3e-4 relative over 25 seeds in
# the PR that added this file (PERF.md), and the four-chip and one-chip
# losses of PR 21 by 4e-5.  1e-3 is eight times the largest seen: room for
# a reordered reduction, none for a dropped term, a mis-scaled attention or
# a residual stream rounded to fewer bits than bfloat16 keeps.
LOSS_RTOL = 1e-3

CORPUS_PATTERN = 97     # distinct positions of the repeated pattern
CORPUS_REPEATS = 512


def corpus(seed: int, vocab: int) -> np.ndarray:
    """A seeded pattern of tokens, repeated: the next token follows from the
    last few, so a few steps already lower the loss.  Uniform random tokens
    could never fall below ln(vocab)."""
    pattern = np.random.default_rng(seed).integers(0, vocab,
                                                   size=CORPUS_PATTERN)
    return np.tile(pattern, CORPUS_REPEATS).astype(np.int32)


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
        make_step = program.import_dotted(config["entry"]["train_step"])
        self.step, self.init_opt = make_step(self.cfg, self.mesh,
                                             lr=traffic["lr"])
        self.batch, self.seq = traffic["batch"], traffic["seq"]
        self.stream = None
        self.losses: list = []

    # ---- what the job would compile, from shapes alone -------------------

    def programs(self) -> dict:
        """name -> (jitted program, abstract arguments), for compiling at
        the real sizes for devices that are described and not attached."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        params = program.abstract_params(self.reference, self.config,
                                         self.shardings)
        tokens = jax.ShapeDtypeStruct(
            (self.batch, self.seq), np.int32,
            sharding=NamedSharding(self.mesh, P("dp", None)))
        return {"train_step": (self.step, (params,
                                           self._abstract_state(params),
                                           tokens))}

    def _abstract_state(self, params: dict):
        """The optimizer state as shapes.  The program makes its state from
        arrays only, so its tree, types and partition specs are learned from
        the state it makes at the configuration's tiny sizes on CPU devices
        laid out as the cell's mesh; a leaf that mirrors a parameter takes
        the real parameter's shape."""
        import jax
        from jax.sharding import NamedSharding

        small = program.tiny(self.config)
        cpus = jax.devices("cpu")
        if len(cpus) < len(self.devices):
            raise RuntimeError(
                f"need {len(self.devices)} CPU devices to learn the "
                f"optimizer state's tree, have {len(cpus)}")
        small_mesh = program.mesh(small, cpus[:len(self.devices)])
        make_step = program.import_dotted(self.config["entry"]["train_step"])
        _, init = make_step(program.program_config(small), small_mesh,
                            lr=self.traffic["lr"])
        small_params = {
            name: np.zeros(dims, self.config["param_dtype"])
            for name, (dims, _std) in program.param_table(self.reference,
                                                          small).items()}

        def size_up(path, leaf):
            names = [p.key for p in path
                     if isinstance(p, jax.tree_util.DictKey)]
            name = names[-1] if names and names[-1] in params else None
            if name and leaf.shape == small_params[name].shape:
                dims = params[name].shape
            elif leaf.ndim == 0:
                dims = ()
            else:
                raise ValueError(f"optimizer state leaf {path} of shape "
                                 f"{leaf.shape} mirrors no parameter")
            return jax.ShapeDtypeStruct(
                dims, leaf.dtype,
                sharding=NamedSharding(self.mesh, leaf.sharding.spec))

        return jax.tree_util.tree_map_with_path(size_up, init(small_params))

    # ---- the run ---------------------------------------------------------

    def setup(self, seed: int, spans) -> None:
        import jax

        from ompi_tpu.models import data

        self.spans = spans
        with spans.span("setup.params"):
            params = jax.block_until_ready(
                program.init_params(self.reference, self.config,
                                    self.shardings, seed))
            self.n_params = costs.tree_count(params)
            self.stream = data.train_stream(
                data.ArraySource(corpus(seed, self.shape.vocab), seed=seed),
                self.mesh, self.batch, self.seq)
            first = next(self.stream)
        # before the optimizer state exists: the reference puts every
        # parameter and a block's float32 logits on one device
        with spans.span("setup.reference"):
            self.loss_reference = self._reference_loss(params, first)
        with spans.span("setup.warmup"):
            opt_state = self.init_opt(params)
            params, opt_state, loss = self.step(params, opt_state, first)
            self.loss_first = float(loss)
            # a second call, so that an input that changed placement in the
            # first compiles here and not in the window
            params, opt_state, loss = self.step(params, opt_state,
                                                next(self.stream))
            jax.block_until_ready(loss)
        self.params, self.opt_state = params, opt_state
        self.stream_warm = self.stream.stats()

    def _reference_loss(self, params: dict, tokens) -> float:
        import jax

        one = self.devices[0]
        return self.reference.loss(
            self.shape, jax.device_put(params, one),
            jax.device_put(np.asarray(tokens), one),
            block=self.traffic["reference_block"])

    def sample(self) -> None:
        span = self.spans.span
        with span("sample"):
            with span("data.next"):
                batch = next(self.stream)
            with span("step"):
                with span("dispatch"):
                    self.params, self.opt_state, loss = self.step(
                        self.params, self.opt_state, batch)
                with span("readback"):
                    loss.block_until_ready()
        self.losses.append(loss)

    def close(self) -> None:
        if self.stream is not None:
            self.stream.close()

    # ---- the results -----------------------------------------------------

    def facts(self) -> dict:
        counts = program.counts(self.reference, self.shape)
        stream = self.stream.stats()
        return {
            "chips": len(self.devices),
            "n_params": self.n_params,
            "tokens_per_sample": self.batch * self.seq,
            "counts": counts,
            "flops_per_token": costs.train_flops_per_token(
                counts["active_params"], counts["attention_layers"],
                counts["attention_width"], self.seq),
            # the input stream's own counters since warm-up
            "stream": {key: value - self.stream_warm[key]
                       for key, value in stream.items()},
        }

    def end_to_end(self, durations: dict[str, list[float]]) -> dict:
        per_sample = statistics.median(durations["sample"])
        return {"train_tokens_per_s": self.batch * self.seq / per_sample}

    def finish(self) -> dict:
        """``correct``, the numbers behind it, and the samples that failed
        on their value."""
        losses = [float(x) for x in self.losses]
        rel_err = (abs(self.loss_first - self.loss_reference)
                   / abs(self.loss_reference))
        not_finite = sum(not math.isfinite(x) for x in losses)
        fell = bool(losses) and losses[-1] < self.loss_first
        return {
            "correct": rel_err <= LOSS_RTOL and not_finite == 0 and fell,
            "failed": not_finite,
            "checks": {"loss_first": self.loss_first,
                       "loss_reference": self.loss_reference,
                       "loss_rel_err": rel_err, "loss_rtol": LOSS_RTOL,
                       "loss_last": losses[-1] if losses else None},
        }
