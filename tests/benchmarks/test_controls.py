"""What a decode cell's ``correct`` holds: the planted faults of
``benchmarks/controls.py`` at the configurations' ``tiny`` sizes, float32, on
the CPU, read through the runner's own ``compare`` and ``verdict``; the two
limits tokens alone are held to beside the worst deficit; a run driven with
the timed path broken underneath; and the train cells' seeded parameters,
which the serving draw leaves alone.  Agreement and control flow only: the
limits themselves were set on the chip (PERF.md section 2)."""

import copy
import hashlib
import json
import time

import jax
import numpy as np
import pytest

from benchmarks import controls
from benchmarks import run as bench_run
from benchmarks.lib import cells, program
from benchmarks.lib.compile_meter import CompileMeter
from benchmarks.lib.spans import Spans

BENCH = cells.load_benchmark()
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
DECODE = [w for w in WORKLOADS if "prompt_len" in cells.resolve(w).traffic]
TRAIN = [w for w in WORKLOADS if w not in DECODE]
ROUTED = [w for w in DECODE if "num_experts" in cells.resolve(w).config]
DENSE = [w for w in DECODE if w not in ROUTED]
WHICH = [0, 1]      # the first and the second seed of ``seeds_of``

# every matrix at 3 mantissa bits, an attending layer's output projection
# zeroed, a feed-forward layer's (every routed layer's) down projection zeroed
MUST_HOLD = ([(w, f) for w in DENSE for f in
              ("all_lower_precision", "attention_layer_off", "ffn_layer_off")]
             + [(w, f) for w in ROUTED for f in
                ("all_lower_precision", "attention_layer_off", "experts_off")])

_readings: dict = {}
_seeds: dict = {}


def seeds_of(workload: str) -> list[int]:
    """The first two seeds at which the cell's tiny configuration continues
    generically.  The draws are sized for the cells (PERF.md section 2): at a
    vocabulary of 128 a greedy continuation closes its cycle within a dozen
    tokens in most seeds, which ``repeat_share`` refuses, as it should."""
    if workload not in _seeds:
        sound = controls.run(workload, list(range(1, 41)), ["sound"],
                             small=True)
        _seeds[workload] = [r["seed"] for r in sound
                            if r["repeat_share"] <= 0.3][:2]
        assert len(_seeds[workload]) == 2, [r["repeat_share"] for r in sound]
    return _seeds[workload]


def readings(workload: str) -> dict:
    """(fault, seed) -> the reading, of every fault the cell's configuration
    has the leaves for; the cell's job is built, and each decoder compiled,
    once."""
    if workload not in _readings:
        faults = [f for f in controls.FAULTS if workload in ROUTED
                  or f in ("sound", "all_lower_precision",
                           "attention_layer_off", "ffn_layer_off")]
        _readings[workload] = {
            (r["fault"], r["seed"]): r
            for r in controls.run(workload, seeds_of(workload), faults,
                                  small=True)}
    return _readings[workload]


@pytest.mark.parametrize("which", WHICH)
@pytest.mark.parametrize("workload", DECODE)
def test_the_sound_program_is_correct(workload, which):
    r = readings(workload)["sound", seeds_of(workload)[which]]
    assert r["correct"] is True, r
    # float32 on both sides: the same argmax at every token
    assert r["tokens_not_reference_argmax"] == 0 and r["deficit_max"] < 1e-3
    assert r["tokens_checked"] == 8 * 24
    assert 1 / 24 <= r["repeat_share"] <= r["repeat_share_limit"] == 0.5
    check = cells.resolve(workload).config["check"]
    assert r["deficit_max_limit"] == check["deficit_max"]["limit"] <= 0.1
    assert r["mismatch_share_limit"] == check["mismatch_share"]["limit"]
    json.dumps(r)


@pytest.mark.parametrize("which", WHICH)
@pytest.mark.parametrize("workload,fault", MUST_HOLD)
def test_a_fault_that_must_hold_is_refused(workload, fault, which):
    r = readings(workload)[fault, seeds_of(workload)[which]]
    assert r["correct"] is False, r
    assert r["shape_ok"] and r["prompt_kept"] and r["first_token_equal"]
    assert (r["deficit_max"] > r["deficit_max_limit"]
            or r["mismatch_share"] > r["mismatch_share_limit"])


@pytest.mark.parametrize("workload", ROUTED)
@pytest.mark.parametrize("fault", ["expert_layer_off", "top_k_less_one",
                                   "experts_lower_precision",
                                   "router_in_bfloat16"])
def test_the_other_faults_are_planted_and_read(workload, fault):
    """Whether tokens hold them is the chip's to say (PERF.md); here each is
    planted, decodes, and is read.  One expert fewer and a routed layer off
    change what the program computes, so in float32 they show."""
    for seed in seeds_of(workload):
        r = readings(workload)[fault, seed]
        assert r["tokens_checked"] == 192 and r["shape_ok"], r
    if fault in ("expert_layer_off", "top_k_less_one"):
        assert any(readings(workload)[fault, seed]["deficit_max"] > 0
                   for seed in seeds_of(workload))


@pytest.mark.parametrize("workload", DENSE)
@pytest.mark.parametrize("fault", ["experts_off", "expert_layer_off",
                                   "experts_lower_precision",
                                   "top_k_less_one", "router_in_bfloat16"])
def test_a_fault_whose_leaf_the_configuration_lacks_raises(workload, fault):
    with pytest.raises(KeyError):
        controls.run(workload, [1], [fault], small=True)


def test_an_unknown_fault_or_a_train_cell_is_refused():
    with pytest.raises(ValueError, match="no fault"):
        controls.run(DECODE[0], [1], ["sonud"], small=True)
    with pytest.raises(ValueError, match="decodes nothing"):
        controls.run(TRAIN[0], [1], ["sound"], small=True)


def test_the_command_prints_one_line_a_reading(tmp_path, capsys):
    out = tmp_path / "deep" / "controls.jsonl"
    seed = seeds_of(DECODE[0])[0]
    assert controls.main(["--workload", DECODE[0], "--seeds", str(seed),
                          "--faults", "sound,ffn_layer_off", "--tiny",
                          "--out", str(out)]) == 0
    printed = [json.loads(line) for line in
               capsys.readouterr().out.strip().splitlines()]
    with open(out) as f:
        kept = [json.loads(line) for line in f]
    assert [r["fault"] for r in printed] == ["sound", "ffn_layer_off"]
    assert [r["correct"] for r in printed] == [True, False]
    assert all(r["workload"] == DECODE[0] and r["seed"] == seed
               for r in printed)
    assert {k: v for r in kept for k, v in r.items() if k != "seconds"} == {
        k: v for r in printed for k, v in r.items() if k != "seconds"}


# ---- the limits beside the worst deficit -----------------------------------

def sound_checks(workload: str) -> tuple:
    r = readings(workload)["sound", seeds_of(workload)[0]]
    return cells.resolve(workload).runner, {**r, "repeat_equal": True}


@pytest.mark.parametrize("workload", DECODE)
def test_a_continuation_of_one_repeated_token_is_refused(workload):
    """Whatever its deficits: the sound run's numbers with the commonest
    token at more than half of a sequence."""
    runner, checks = sound_checks(workload)
    assert runner.verdict(checks) is True
    tol = checks["repeat_share_limit"]
    assert runner.verdict({**checks, "repeat_share": tol}) is True
    assert runner.verdict({**checks, "repeat_share": tol + 1 / 128}) is False
    assert checks["deficit_max"] < 1e-3


def test_repeat_share_is_the_worst_sequences_commonest_token():
    runner = cells.resolve(DECODE[0]).runner
    rows = np.array([[1, 2, 3, 4, 5, 6, 7, 8], [9, 9, 3, 9, 9, 9, 1, 9]])
    assert runner.repeat_share(rows) == 6 / 8
    assert runner.repeat_share(rows[:1]) == 1 / 8
    assert runner.repeat_share(np.full((2, 16), 7)) == 1.0


@pytest.mark.parametrize("workload", DECODE)
def test_compare_reads_a_repeated_continuation(workload):
    """Through ``compare`` itself: the sound run's tokens with the first
    checked sequence's continuation overwritten by its first token."""
    cell = cells.resolve(workload)
    job = cell.runner.build(*controls.tiny(cell, reference_sequences=2),
                            jax.devices()[:1])
    start = job.prompt_len
    params, prompts = job.draw(seeds_of(workload)[0])
    one = job.tokens_of(job.first(params, prompts))
    answer = job.tokens_of(job.full(params, prompts)).copy()
    sound = job.compare(params, prompts, one, answer)
    assert cell.runner.verdict({**sound, "repeat_equal": True}) is True
    answer[0, start:] = answer[0, start]
    stuck = job.compare(params, prompts, one, answer)
    assert stuck["repeat_share"] == 1.0 and stuck["first_token_equal"]
    assert cell.runner.verdict({**stuck, "repeat_equal": True}) is False


@pytest.mark.parametrize("workload", DECODE)
def test_the_count_refuses_many_small_mismatches(workload):
    """More tokens off the reference's argmax than the limit allows, each
    of them inside the worst token's limit: refused by the count alone."""
    runner, checks = sound_checks(workload)
    tol, n = checks["mismatch_share_limit"], 256
    few, many = int(tol * n), int(tol * n) + 1
    under = {**checks, "deficit_max": 0.8 * checks["deficit_max_limit"],
             "tokens_checked": n}
    assert runner.verdict({**under, "tokens_not_reference_argmax": few,
                           "mismatch_share": few / n}) is True
    assert runner.verdict({**under, "tokens_not_reference_argmax": many,
                           "mismatch_share": many / n}) is False
    assert runner.verdict({**under, "mismatch_share": 0.0,
                           "deficit_max": 1.01 * checks["deficit_max_limit"]}
                          ) is False


@pytest.mark.parametrize("workload", DECODE)
@pytest.mark.parametrize("missing", ["check", "deficit_max", "mismatch_share",
                                     "why"])
def test_a_decode_configuration_without_its_limits_is_refused(workload,
                                                              missing):
    cell = cells.resolve(workload)
    config = copy.deepcopy(cell.config)
    if missing == "check":
        del config["check"]
    elif missing == "why":
        config["check"]["deficit_max"]["why"] = " "
    else:
        del config["check"][missing]
    job = cell.runner.build(program.tiny(config), cell.traffic,
                            jax.devices()[:1])
    with pytest.raises(ValueError, match="check"):
        job.setup(1, Spans())
    for row in cell.config["check"].values():
        assert len(row["why"]) > 80     # a reason, with its readings


@pytest.mark.parametrize("broken", ["shape_ok", "prompt_kept", "repeat_equal",
                                    "first_token_equal"])
def test_every_boolean_is_asked(broken):
    runner, checks = sound_checks(DECODE[0])
    assert runner.verdict({**checks, broken: False}) is False


# ---- a run with the timed path broken underneath ---------------------------

def decoder_that_alters_a_token(cfg, mesh, max_new, **kwargs):
    """The program's decoder with one generated token of every sequence
    altered where it is produced (``entry.decoder`` of the test below)."""
    from ompi_tpu.models.decode import make_decoder

    decode = make_decoder(cfg, mesh, max_new=max_new, **kwargs)

    def altered(params, prompts):
        tokens = decode(params, prompts)
        at = prompts.shape[1] + max_new // 2
        return tokens.at[:, at].set((tokens[:, at] + 1) % cfg.vocab)

    return altered


def decoder_that_forgets_its_cache(cfg, mesh, max_new, **kwargs):
    """Every generated token decoded from the last four tokens alone: a
    cached step that leaves out the rest of its context."""
    from ompi_tpu.models.decode import make_decoder

    decode = make_decoder(cfg, mesh, max_new=1, **kwargs)

    def forgetful(params, prompts):
        tokens = prompts
        for _ in range(max_new):
            last = decode(params, tokens[:, -4:])[:, -1:]
            tokens = jax.numpy.concatenate([tokens, last], axis=1)
        return tokens

    return forgetful


@pytest.mark.parametrize("workload", DECODE)
@pytest.mark.parametrize("broken", ["decoder_that_alters_a_token",
                                    "decoder_that_forgets_its_cache"])
def test_a_run_with_the_timed_path_broken_is_not_correct(workload, broken):
    """The harness's look for a chip skipped, the rest of a run driven:
    ``measure`` builds the job, sets it up, times its window, and the result
    line says ``correct: false``."""
    cell = cells.resolve(workload)
    cell.config, cell.traffic = controls.tiny(cell, reference_sequences=2)

    def measure():
        return bench_run.measure(
            cell, jax.devices()[:1], CompileMeter(), Spans(),
            seed=seeds_of(workload)[0],
            seconds=0.2, trace=False, peaks=None, t0=time.perf_counter())

    line = measure()
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    cell.config["entry"]["decoder"] = f"{__name__}.{broken}"
    line = measure()
    assert line["correct"] is False, line["checks"]
    assert line["attempted"] > 0 and list(line)[-1] == "checks"
    assert (line["checks"]["deficit_max"]
            > line["checks"]["deficit_max_limit"])


# ---- the train cells' draw is the parent's ---------------------------------

# sha256 of two leaves of ``program.init_params`` at the configurations'
# tiny sizes, seed 7, printed on the parent of the PR that gave the decode
# cells a serving draw (CPU box), before any edit
PARENT_LEAVES = {"emb": "ba3e47db965b1b03", "w2": "fb557aa18e21cc2b"}


@pytest.mark.parametrize("workload", TRAIN)
def test_the_train_cells_seeded_parameters_are_the_parents(workload):
    cell = cells.resolve(workload)
    config = program.tiny(cell.config)
    ref = program.reference(config)
    cfg = program.program_config(config)
    mesh = program.mesh(config, jax.devices()[:cell.chips])
    shardings = program.param_shardings(config, cfg, mesh)
    params = program.init_params(ref, config, shardings, 7)
    for leaf, want in PARENT_LEAVES.items():
        got = hashlib.sha256(np.asarray(params[leaf]).tobytes()).hexdigest()
        assert got[:16] == want, leaf
    # and the draw for serving is another: the decode cells' own
    serving = program.init_params(ref, config, shardings, 7, serving=True)
    assert not np.array_equal(serving["w2"], params["w2"])
    assert {k: v.shape for k, v in serving.items()} == {
        k: v.shape for k, v in params.items()}
