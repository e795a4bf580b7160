"""The tests of a decode cell's ``correct`` that name no cell of
``BENCHMARK.json``, and ``controls_cases.py``'s tests over cells for ``DRY``,
the cell added to a copy of the benchmark by files and rows alone.  The
benchmark's own decode cells have a file each
(``test_cell_controls_<cell>.py``), so that the driver's ``--dist loadfile``
hands them to several workers."""

import jax
import numpy as np
import pytest

from benchmarks import controls
from benchmarks.lib import cells, program
from tests.benchmarks import controls_cases, decode_cells
from tests.benchmarks.controls_cases import (DECODE, DRY, KIND, TRAIN, _dry,
                                             cell_of)

globals().update(controls_cases.tests_of([DRY]))


def test_an_unknown_fault_or_a_train_cell_is_refused():
    with pytest.raises(ValueError, match="no fault"):
        controls.run(DECODE[0], [1], ["sonud"], small=True)
    with pytest.raises(ValueError, match="decodes nothing"):
        controls.run(TRAIN[0], [1], ["sound"], small=True)


def test_repeat_share_is_the_worst_sequences_commonest_token():
    runner = cells.resolve(DECODE[0]).runner
    rows = np.array([[1, 2, 3, 4, 5, 6, 7, 8], [9, 9, 3, 9, 9, 9, 1, 9]])
    assert runner.repeat_share(rows) == 6 / 8
    assert runner.repeat_share(rows[:1]) == 1 / 8
    assert runner.repeat_share(np.full((2, 16), 7)) == 1.0


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


# ---- the train cells' draw is what the reference's table states ------------

def _draws(workload):
    """(the reference's table, the tiny configuration, ``draw(seed,
    **how)``: the cell's parameters drawn as its runner draws them)."""
    cell = cells.resolve(workload)
    config = program.tiny(cell.config)
    ref = program.reference(config)
    cfg = program.program_config(config)
    mesh = program.mesh(config, jax.devices()[:cell.chips])
    shardings = program.param_shardings(config, cfg, mesh)

    def draw(seed, **how):
        return program.init_params(ref, config, shardings, seed, **how)

    return program.param_table(ref, config), config, draw


@pytest.mark.parametrize("workload", TRAIN)
def test_the_train_cells_seeded_parameters_are_the_parents(workload):
    """The draw is held by what it means and by no backend's bits: every leaf
    has the shape and type the configuration states and the mean and
    deviation the reference's table states for training, to what a sample of
    the leaf's size can show (five deviations of a mean of ``n`` draws,
    ``std / sqrt(n)``, and of their deviation, ``std / sqrt(2 n)``: under one
    leaf in a million).  One seed is one draw bit for bit, another seed is
    another.  (Until PR 74 two leaves' sha256 stood here, printed on one CPU
    box at one level of its compiler.)"""
    table, config, draw = _draws(workload)
    params = draw(7)
    assert set(params) == set(table)
    for leaf, (dims, std) in table.items():
        got = np.asarray(params[leaf])
        assert got.shape == tuple(dims), leaf
        assert got.dtype == np.dtype(config["param_dtype"]), leaf
        got = got.astype(np.float64)
        if std is None:
            assert (got == 1).all(), leaf
            continue
        assert abs(got.mean()) < 5 * std / got.size ** 0.5, leaf
        assert abs(got.std() / std - 1) < 5 / (2 * got.size) ** 0.5, leaf
    again, other = draw(7), draw(8)
    for leaf, (_dims, std) in table.items():
        assert np.array_equal(again[leaf], params[leaf]), leaf
        assert std is None or not np.array_equal(other[leaf],
                                                 params[leaf]), leaf
    # and the draw for serving is another: the decode cells' own
    serving = draw(7, serving=True)
    assert not np.array_equal(serving["w2"], params["w2"])
    assert {k: v.shape for k, v in serving.items()} == {
        k: v.shape for k, v in params.items()}
