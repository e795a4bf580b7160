"""What a decode cell's ``correct`` holds: the planted faults of
``benchmarks/controls.py`` at the configurations' ``tiny`` sizes, float32, on
the CPU, read through the runner's own ``compare`` and ``verdict``; the limits
each kind of decoder is held to beside the worst reading; a run driven with
the timed path broken underneath; and the train cells' seeded parameters,
which the serving draw leaves alone.  Agreement and control flow only: the
limits themselves were set on the chip (PERF.md section 2).

Every test that is parametrised over the decode cells asks the cell what its
decoder hands back (``decode_cells.hands_back``) and holds it to that kind's
own readings, and runs over two sets of cases: every decode cell of
``BENCHMARK.json``, and ``DRY``, a cell added to a copy of the benchmark by
files and rows alone, whose decoder hands its logits back and whose ``check``
has the three limits for logits and none for tokens.  So a configuration that
names ``entry.decoder_logits`` passes these tests as it is added."""

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
from tests.benchmarks import decode_cells

BENCH = cells.load_benchmark()
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
DECODE = [w for w in WORKLOADS if "prompt_len" in cells.resolve(w).traffic]
TRAIN = [w for w in WORKLOADS if w not in DECODE]
# by what the reference counts, whatever the configuration calls its keys
ROUTED = [w for w in DECODE if decode_cells.routed(cells.resolve(w))]
DENSE = [w for w in DECODE if w not in ROUTED]
# the dry cell: the first routed configuration behind ``logits_decoder``
DRY = "dry-logits-cell"
CASES = DECODE + [DRY]
KIND = {**{w: decode_cells.hands_back(cells.resolve(w)) for w in DECODE},
        DRY: "logits"}
OF_TOKENS = [c for c in CASES if KIND[c] == "tokens"]
OF_LOGITS = [c for c in CASES if KIND[c] == "logits"]
KEYS = decode_cells.KEYS
WHICH = [0, 1]      # the first and the second seed of ``seeds_of``

# every matrix at 3 mantissa bits, an attending layer's output projection
# zeroed, a feed-forward layer's (every routed layer's) down projection zeroed
MUST_HOLD = ([(c, f) for c in DENSE for f in
              ("all_lower_precision", "attention_layer_off", "ffn_layer_off")]
             + [(c, f) for c in ROUTED + [DRY] for f in
                ("all_lower_precision", "attention_layer_off", "experts_off")])
DENSE_FAULTS = ("sound", "all_lower_precision", "attention_layer_off",
                "ffn_layer_off")

_dry: dict = {}
_readings: dict = {}
_seeds: dict = {}


@pytest.fixture(scope="module", autouse=True)
def dry_benchmark(tmp_path_factory):
    """The copy of the benchmark that ``DRY`` is a cell of, made once."""
    root = tmp_path_factory.mktemp("dry")
    bench_dir, before = decode_cells.copied_benchmark(root)
    workload = decode_cells.add_logits_cell(root, bench_dir, ROUTED[0])
    _dry.update(workload=workload, bench_dir=bench_dir, before=before)
    yield
    for kept in (_dry, _readings, _seeds):
        kept.clear()


def where(case: str) -> tuple[str, str]:
    """A case's workload and the benchmark directory it is resolved in."""
    if case == DRY:
        return _dry["workload"], _dry["bench_dir"]
    return case, cells.BENCH_DIR


def cell_of(case: str) -> cells.Cell:
    return cells.resolve(*where(case))


def run_controls(case: str, seeds, faults) -> list[dict]:
    workload, bench_dir = where(case)
    return controls.run(workload, seeds, faults, small=True,
                        bench_dir=bench_dir)


def seeds_of(case: str) -> list[int]:
    """The first two seeds at which the cell's tiny configuration continues
    generically.  The draws are sized for the cells (PERF.md section 2): at a
    vocabulary of 128 a greedy continuation closes its cycle within a dozen
    tokens in most seeds, which ``repeat_share`` refuses, as it should."""
    if case not in _seeds:
        sound = []
        for lo in range(1, 41, 10):     # ten at a time, until two are found
            sound += run_controls(case, list(range(lo, lo + 10)), ["sound"])
            found = [r["seed"] for r in sound if r["repeat_share"] <= 0.3][:2]
            if len(found) == 2:
                break
        assert len(found) == 2, [r["repeat_share"] for r in sound]
        _seeds[case] = found
    return _seeds[case]


def readings(case: str) -> dict:
    """(fault, seed) -> the reading, of every fault the cell's configuration
    has the leaves for; the cell's job is built, and each decoder compiled,
    once."""
    if case not in _readings:
        faults = DENSE_FAULTS if case in DENSE else controls.FAULTS
        _readings[case] = {
            (r["fault"], r["seed"]): r
            for r in run_controls(case, seeds_of(case), list(faults))}
    return _readings[case]


def refused_by_its_kind(case: str, r: dict) -> bool:
    """Whether one of the limits of the cell's own kind refuses ``r``."""
    if KIND[case] == "tokens":
        return (r["deficit_max"] > r["deficit_max_limit"]
                or r["mismatch_share"] > r["mismatch_share_limit"])
    return (r["logit_err_median"] > r["logit_err_median_limit"]
            or r["positions_over"] > r["positions_over_limit"]
            or not r["tokens_are_argmax"])


@pytest.mark.parametrize("which", WHICH)
@pytest.mark.parametrize("case", CASES)
def test_the_sound_program_is_correct(case, which):
    r = readings(case)["sound", seeds_of(case)[which]]
    assert r["correct"] is True, r
    # float32 on both sides: the same argmax at every token
    assert r["tokens_not_reference_argmax"] == 0 and r["deficit_max"] < 1e-3
    assert r["tokens_checked"] == 8 * 24
    assert 1 / 24 <= r["repeat_share"] <= r["repeat_share_limit"] == 0.5
    # the kind's own limits, printed beside its readings
    check = cell_of(case).config["check"]
    assert set(check) >= set(KEYS[KIND[case]])
    for key in KEYS[KIND[case]]:
        assert r[key + "_limit"] == check[key]["limit"]
    assert not refused_by_its_kind(case, r)
    if KIND[case] == "tokens":
        assert check["deficit_max"]["limit"] <= 0.1
        assert "logit_err_median" not in r
    else:
        assert r["logit_err_median"] <= r["logit_err_max"] < 1e-4
        assert r["positions_over"] == 0 and r["tokens_are_argmax"] is True
        assert "deficit_max_limit" not in r
    json.dumps(r)


@pytest.mark.parametrize("which", WHICH)
@pytest.mark.parametrize("case,fault", MUST_HOLD)
def test_a_fault_that_must_hold_is_refused(case, fault, which):
    r = readings(case)[fault, seeds_of(case)[which]]
    assert r["correct"] is False, r
    assert r["shape_ok"] and r["prompt_kept"] and r["first_token_equal"]
    assert refused_by_its_kind(case, r)


OTHER_FAULTS = ["expert_layer_off", "top_k_less_one",
                "experts_lower_precision", "router_in_bfloat16"]


@pytest.mark.parametrize("case", ROUTED + [DRY])
@pytest.mark.parametrize("fault", OTHER_FAULTS)
def test_the_other_faults_are_planted_and_read(case, fault):
    """Whether a cell's limits hold them is the chip's to say (PERF.md); here
    each is planted, decodes, is read, and what it read is printed.  One
    expert fewer and a routed layer off change what the program computes, so
    in float32 they show."""
    for seed in seeds_of(case):
        r = readings(case)[fault, seed]
        assert r["tokens_checked"] == 192 and r["shape_ok"], r
        print(json.dumps(r))
    if fault in ("expert_layer_off", "top_k_less_one"):
        assert any(readings(case)[fault, seed]["deficit_max"] > 0
                   for seed in seeds_of(case))


@pytest.mark.parametrize("case", [c for c in ROUTED + [DRY] if c in OF_LOGITS])
@pytest.mark.parametrize("fault", ["top_k_less_one", "expert_layer_off",
                                   "experts_off"])
def test_logits_hold_what_tokens_could_not(case, fault):
    """One expert of a token's fewer, a routed layer off, every routed layer
    off: in float32 at tiny sizes the median of the per-position error
    refuses each in every seed, where tokens on the chip held the first in
    one seed of three (PERF.md section 2)."""
    for seed in seeds_of(case):
        r = readings(case)[fault, seed]
        assert r["correct"] is False and r["tokens_are_argmax"], r
        assert r["logit_err_median"] > 5 * r["logit_err_median_limit"], r


@pytest.mark.parametrize("case", DENSE)
@pytest.mark.parametrize("fault", ["experts_off", "expert_layer_off",
                                   "experts_lower_precision",
                                   "top_k_less_one", "router_in_bfloat16"])
def test_a_fault_whose_leaf_the_configuration_lacks_raises(case, fault):
    with pytest.raises(KeyError):
        run_controls(case, [1], [fault])


def test_an_unknown_fault_or_a_train_cell_is_refused():
    with pytest.raises(ValueError, match="no fault"):
        controls.run(DECODE[0], [1], ["sonud"], small=True)
    with pytest.raises(ValueError, match="decodes nothing"):
        controls.run(TRAIN[0], [1], ["sound"], small=True)


@pytest.mark.parametrize("case", [DECODE[0], DRY])
def test_the_command_prints_one_line_a_reading(case, tmp_path, capsys):
    out = tmp_path / "deep" / "controls.jsonl"
    seed = seeds_of(case)[0]
    workload, bench_dir = where(case)
    fault = "ffn_layer_off" if case in DENSE else "experts_off"
    assert controls.main(["--workload", workload, "--seeds", str(seed),
                          "--faults", f"sound,{fault}", "--tiny",
                          "--out", str(out), "--bench-dir", bench_dir]) == 0
    printed = [json.loads(line) for line in
               capsys.readouterr().out.strip().splitlines()]
    with open(out) as f:
        kept = [json.loads(line) for line in f]
    assert [r["fault"] for r in printed] == ["sound", fault]
    assert [r["correct"] for r in printed] == [True, False]
    assert all(r["workload"] == workload and r["seed"] == seed
               for r in printed)
    assert {k: v for r in kept for k, v in r.items() if k != "seconds"} == {
        k: v for r in printed for k, v in r.items() if k != "seconds"}


# ---- the limits beside the worst reading ------------------------------------

def sound_checks(case: str) -> tuple:
    r = readings(case)["sound", seeds_of(case)[0]]
    return cell_of(case).runner, {**r, "repeat_equal": True}


def job_of(case: str, **check_limits):
    """The cell's job at tiny sizes with two checked sequences, its ``check``
    as the file gives it but for ``check_limits``."""
    cell = cell_of(case)
    config, traffic = controls.tiny(cell, reference_sequences=2)
    for key, limit in check_limits.items():
        config["check"][key]["limit"] = limit
    return cell.runner, cell.runner.build(config, traffic, jax.devices()[:1])


@pytest.mark.parametrize("case", OF_TOKENS)
def test_tokens_a_continuation_of_one_repeated_token_is_refused(case):
    """Whatever its deficits: the sound run's numbers with the commonest
    token at more than half of a sequence."""
    runner, checks = sound_checks(case)
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


@pytest.mark.parametrize("case", OF_TOKENS)
def test_tokens_compare_reads_a_repeated_continuation(case):
    """Through ``compare`` itself: the sound run's tokens with the first
    checked sequence's continuation overwritten by its first token."""
    runner, job = job_of(case)
    start = job.prompt_len
    params, prompts = job.draw(seeds_of(case)[0])
    one = job.tokens_of(job.first(params, prompts))
    answer = job.tokens_of(job.full(params, prompts)).copy()
    sound = job.compare(params, prompts, one, answer)
    assert runner.verdict({**sound, "repeat_equal": True}) is True
    answer[0, start:] = answer[0, start]
    stuck = job.compare(params, prompts, one, answer)
    assert stuck["repeat_share"] == 1.0 and stuck["first_token_equal"]
    assert runner.verdict({**stuck, "repeat_equal": True}) is False


@pytest.mark.parametrize("case", OF_LOGITS)
def test_logits_compare_reads_a_shift_and_a_spike(case):
    """Through ``compare`` and ``verdict``, on the sound run's tokens and
    logits: a shift of a tenth of a deviation at every position is refused by
    the median; a spike of three at one position in twenty (3 of these 48)
    leaves the median and is allowed or refused by the share of positions
    over, as the configuration's ``check.positions_over`` says; and a
    continuation of one repeated token, which the limits for tokens refuse,
    is printed and not asked."""
    runner, job = job_of(case)
    start = job.prompt_len
    params, prompts = job.draw(seeds_of(case)[0])
    one = job.tokens_of(job.first(params, prompts))
    out = job.full(params, prompts)
    answer, z = job.tokens_of(out), np.asarray(out[1])
    picked = answer[:2, start:]

    def judged(logits, of=job):
        checks = of.compare(params, prompts, one, answer, logits)
        return checks, runner.verdict({**checks, "repeat_equal": True})

    sound, correct = judged(z)
    assert correct is True and sound["logit_err_max"] < 1e-4, sound
    assert runner.verdict({**sound, "repeat_equal": True,
                           "repeat_share": 1.0}) is True
    shifted, correct = judged(decode_cells.faulty_logits("shifted", z, picked))
    assert correct is False and shifted["tokens_are_argmax"], shifted
    assert shifted["logit_err_median"] > 5 * shifted["logit_err_median_limit"]
    assert shifted["positions_over"] == 1
    spiky = decode_cells.faulty_logits("spiky", z, picked)
    allowed, correct = judged(spiky)
    assert correct is True and allowed["tokens_are_argmax"], allowed
    assert allowed["logit_err_median"] < 1e-3 < 1 < allowed["logit_err_max"]
    assert allowed["positions_over"] == 3 / 48 < allowed["positions_over_limit"]
    _runner, strict = job_of(case, positions_over=0.05)
    refused, correct = judged(spiky, strict)
    assert correct is False and refused["positions_over"] == 3 / 48
    assert refused["logit_err_median"] == allowed["logit_err_median"]


@pytest.mark.parametrize("case", OF_TOKENS)
def test_tokens_the_count_refuses_many_small_mismatches(case):
    """More tokens off the reference's argmax than the limit allows, each
    of them inside the worst token's limit: refused by the count alone."""
    runner, checks = sound_checks(case)
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


@pytest.mark.parametrize("case", OF_LOGITS)
def test_logits_the_share_refuses_many_positions_over(case):
    """More positions over the position limit than the share allows, the
    median inside its limit: refused by the share alone; and the median over
    its limit is refused with no position over."""
    runner, checks = sound_checks(case)
    tol, n = checks["positions_over_limit"], 256
    few, many = int(tol * n), int(tol * n) + 1
    under = {**checks,
             "logit_err_median": 0.8 * checks["logit_err_median_limit"]}
    assert runner.verdict({**under, "positions_over": few / n}) is True
    assert runner.verdict({**under, "positions_over": many / n}) is False
    assert runner.verdict(
        {**checks, "positions_over": 0.0, "logit_err_median":
         1.01 * checks["logit_err_median_limit"]}) is False
    # what the limits for tokens ask is printed here and not asked
    assert runner.verdict({**checks, "deficit_max": 9.0,
                           "mismatch_share": 1.0}) is True


@pytest.mark.parametrize("case,missing", [
    (c, m) for c in CASES for m in ("check", *KEYS[KIND[c]], "why")])
def test_a_decode_configuration_without_its_limits_is_refused(case, missing):
    """Where its kind is refused: a decoder that hands back logits when the
    job is built, one that hands back tokens when a run is set up (a
    configuration that no cell decodes is built for its counts alone)."""
    cell = cell_of(case)
    config = copy.deepcopy(cell.config)
    if missing == "check":
        del config["check"]
    elif missing == "why":
        config["check"][KEYS[KIND[case]][0]]["why"] = " "
    else:
        del config["check"][missing]

    def build():
        return cell.runner.build(program.tiny(config), cell.traffic,
                                 jax.devices()[:1])

    if KIND[case] == "logits":
        with pytest.raises(ValueError, match="check"):
            build()
    else:
        job = build()
        with pytest.raises(ValueError, match="check"):
            job.setup(1, Spans())
    if case != DRY:
        for row in cell.config["check"].values():
            assert len(row["why"]) > 80     # a reason, with its readings


@pytest.mark.parametrize("case,broken", [
    (c, b) for c in (DECODE[0], DRY)
    for b in ("shape_ok", "prompt_kept", "repeat_equal", "first_token_equal",
              *(("tokens_are_argmax",) if KIND[c] == "logits" else ()))])
def test_every_boolean_is_asked(case, broken):
    runner, checks = sound_checks(case)
    assert checks[broken] is True
    assert runner.verdict({**checks, broken: False}) is False


# ---- a run with the timed path broken underneath ---------------------------

@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("broken", ["decoder_that_alters_a_token",
                                    "decoder_that_forgets_its_cache"])
def test_a_run_with_the_timed_path_broken_is_not_correct(case, broken,
                                                         monkeypatch):
    """The harness's look for a chip skipped, the rest of a run driven:
    ``measure`` builds the job, sets it up, times its window, and the result
    line says ``correct: false``, by a reading of the cell's own kind.  The
    broken decoders (``decode_cells``) wrap the cell's own and pass on what it
    hands back beside its tokens."""
    cell = cell_of(case)
    cell.config, cell.traffic = controls.tiny(cell, reference_sequences=2)

    def measure():
        return bench_run.measure(
            cell, jax.devices()[:1], CompileMeter(), Spans(),
            seed=seeds_of(case)[0],
            seconds=0.2, trace=False, peaks=None, t0=time.perf_counter())

    line = measure()
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    monkeypatch.setattr(decode_cells, "BROKEN_INNER",
                        cell.config["entry"]["decoder"])
    cell.config["entry"]["decoder"] = f"{decode_cells.__name__}.{broken}"
    line = measure()
    c = line["checks"]
    assert line["correct"] is False, c
    assert line["attempted"] > 0 and list(line)[-1] == "checks"
    if KIND[case] == "tokens":
        assert c["deficit_max"] > c["deficit_max_limit"]
    elif broken == "decoder_that_alters_a_token":
        # the logits are the decoder's own: the altered token is not theirs
        assert c["tokens_are_argmax"] is False
    else:
        assert c["tokens_are_argmax"] is True
        assert c["logit_err_median"] > 5 * c["logit_err_median_limit"]


# ---- the dry cell was added, and nothing edited -----------------------------

def test_the_dry_cell_is_an_addition_of_files_and_rows_alone():
    """What every case above ran on: a decode cell whose configuration names
    ``entry.decoder_logits``, gives the three limits for logits and no limit
    for tokens, routes its tokens, and sits in a copy of the benchmark whose
    other files are byte for byte what they were."""
    cell = cell_of(DRY)
    assert decode_cells.hands_back(cell) == KIND[DRY] == "logits"
    assert set(cell.config["check"]) == set(decode_cells.LOGIT_KEYS)
    assert decode_cells.routed(cell, _dry["bench_dir"])
    assert cell.runner.__file__.startswith(_dry["bench_dir"])
    assert cell.config["param_dtype"] == "bfloat16"     # cut by ``tiny`` alone
    after = decode_cells.digest(_dry["bench_dir"])
    assert {k: after[k] for k in _dry["before"]} == _dry["before"]
    assert set(after) - set(_dry["before"]) == {
        f"configs/{decode_cells.LOGITS_FAMILY}.json"}


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
