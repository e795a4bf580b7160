"""Input pipeline (models/data.py): determinism, memmap windows,
dp-sharded prefetch feeding a real train step."""

import numpy as np

from ompi_tpu.models import data as data_mod
from ompi_tpu.models import transformer as tfm
from ompi_tpu.parallel.mesh import make_mesh


def test_array_source_deterministic_and_in_range():
    toks = np.arange(1000, dtype=np.int32) % 97
    src = data_mod.ArraySource(toks, seed=3)
    a = src.batch(step=5, batch=4, seq=16)
    b = src.batch(step=5, batch=4, seq=16)
    c = src.batch(step=6, batch=4, seq=16)
    np.testing.assert_array_equal(a, b)       # same (seed, step)
    assert (a != c).any()                     # next step differs
    assert a.shape == (4, 16) and a.dtype == np.int32
    assert a.min() >= 0 and a.max() < 97


def test_memmap_source_matches_array(tmp_path):
    toks = (np.arange(5000) % 251).astype(np.uint16)
    path = tmp_path / "corpus.bin"
    toks.tofile(path)
    mm = data_mod.MemmapSource(str(path), dtype=np.uint16, seed=1)
    arr = data_mod.ArraySource(toks, seed=1)
    np.testing.assert_array_equal(mm.batch(7, 3, 32), arr.batch(7, 3, 32))


def test_prefetch_preserves_order_and_shards():
    import jax
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh({"dp": 4, "sp": 1, "tp": 2})
    toks = (np.arange(4096) % 128).astype(np.int32)
    src = data_mod.ArraySource(toks, seed=0)
    stream = data_mod.train_stream(src, mesh, batch=8, seq=32)
    got = [next(stream) for _ in range(3)]
    for step, dev in enumerate(got):
        want = src.batch(step, 8, 32)
        np.testing.assert_array_equal(np.asarray(dev), want)
        # dp-sharded rows: each device holds batch/dp rows
        assert dev.sharding.shard_shape(dev.shape)[0] == 2
    assert isinstance(got[0], jax.Array)


def test_stream_feeds_train_step():
    mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2})
    cfg = tfm.TransformerConfig(
        vocab=128, d_model=64, n_heads=4, n_layers=2, d_ff=128, seq=32,
        attention="xla", compute_dtype="float32")
    params = tfm.init_params(cfg)
    step, init_opt = tfm.make_train_step(cfg, mesh, lr=1e-2)
    opt_state = init_opt(params)
    src = data_mod.ArraySource(
        (np.arange(2048) % cfg.vocab).astype(np.int32))
    stream = data_mod.train_stream(src, mesh, batch=4, seq=cfg.seq)
    losses = []
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, next(stream))
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses


def test_resume_reproduces_stream():
    src = data_mod.ArraySource(np.arange(999, dtype=np.int32), seed=9)
    first = list(zip(range(5), data_mod.batches(src, 2, 8)))
    resumed = data_mod.batches(src, 2, 8, start_step=3)
    np.testing.assert_array_equal(next(resumed), first[3][1])
    np.testing.assert_array_equal(next(resumed), first[4][1])


def test_prefetch_propagates_source_errors():
    """A failing source must raise at the consumer, not end the stream."""
    import pytest

    def bad():
        yield np.zeros((2, 4), np.int32)
        raise RuntimeError("corpus went away")

    stream = data_mod.prefetch(bad())
    next(stream)
    with pytest.raises(RuntimeError, match="corpus went away"):
        next(stream)


def test_prefetch_releases_worker_on_early_abandon():
    """A consumer that breaks out early must not leave the worker thread
    blocked on a full queue (it would pin `depth` device batches in HBM
    for the process lifetime)."""
    import threading
    import time

    produced = []

    def endless():
        i = 0
        while True:
            produced.append(i)
            yield np.full((2, 4), i, np.int32)
            i += 1

    before = threading.active_count()
    stream = data_mod.prefetch(endless(), depth=2)
    next(stream)
    stream.close()          # abandon with batches still queued
    deadline = time.time() + 5.0
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before, (
        "prefetch worker still alive after consumer closed the stream")
    # and the worker stopped producing (no unbounded growth after close)
    n = len(produced)
    time.sleep(0.3)
    assert len(produced) == n


def test_prefetch_close_before_first_next_releases_worker():
    """close() before any next() must still release the worker — a plain
    generator's finally never runs if the generator was never started."""
    import threading
    import time

    def endless():
        while True:
            yield np.zeros((2, 4), np.int32)

    before = threading.active_count()
    stream = data_mod.prefetch(endless(), depth=2)
    stream.close()                      # never consumed
    deadline = time.time() + 5.0
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before
    # closed stream reads as exhausted, not a hang
    import pytest

    with pytest.raises(StopIteration):
        next(stream)


def test_prefetch_counts_batches_and_starved_takes():
    """``stats()``: a take that finds the queue empty is starved (the
    consumer outran the worker); one that finds a batch waiting is not."""
    import threading
    import time

    gate = threading.Event()

    def slow_then_fast():
        yield np.zeros((2, 4), np.int32)
        gate.wait(timeout=10.0)         # the consumer has to wait here
        for i in range(3):
            yield np.full((2, 4), i, np.int32)

    stream = data_mod.prefetch(slow_then_fast(), depth=4)
    assert stream.stats() == {"batches": 0, "starved": 0, "wait_s": 0.0}
    deadline = time.time() + 5.0
    while stream.stats()["batches"] == 0 and time.time() < deadline:
        next(stream)
    threading.Timer(0.2, gate.set).start()
    next(stream)                        # the worker is held: queue empty
    starved = stream.stats()["starved"]
    assert starved >= 1 and stream.stats()["batches"] == 2
    time.sleep(0.5)                     # the worker fills the queue
    next(stream)
    counts = {k: v for k, v in stream.stats().items() if k != "wait_s"}
    assert counts == {"batches": 3, "starved": starved}
    next(stream)
    stream.close()
    assert stream.stats()["batches"] == 4


def test_prefetch_counts_the_seconds_it_waited_for_a_batch():
    """``stats()["wait_s"]``: the seconds ``next`` spent taking from the
    queue.  It grows by the worker's delay when the source is slower than
    the consumer and stays near 0 when batches are waiting."""
    import time

    def slow(n, delay):
        for i in range(n):
            time.sleep(delay)
            yield np.full((2, 4), i, np.int32)

    stream = data_mod.prefetch(slow(4, 0.1), depth=2)
    for _ in range(4):
        next(stream)
    starved = stream.stats()
    stream.close()
    assert starved["batches"] == 4 and starved["starved"] >= 3
    assert 0.25 < starved["wait_s"] < 5.0

    stream = data_mod.prefetch(slow(6, 0.0), depth=6)
    deadline = time.time() + 10.0
    time.sleep(0.3)                     # the worker fills the queue
    for _ in range(6):
        next(stream)
        assert time.time() < deadline
    fed = stream.stats()
    stream.close()
    assert fed["batches"] == 6 and fed["wait_s"] < 0.05


def test_stream_and_its_worker_are_host_spans():
    """``train_stream`` is a ``build.stream`` span and each batch its worker
    makes a ``data.produce`` span of ``core/scopes.host``."""
    import time

    from ompi_tpu.core import scopes

    scopes.reset()
    mesh = make_mesh({"dp": 4, "sp": 1, "tp": 2})
    src = data_mod.ArraySource(np.arange(1000, dtype=np.int32), seed=1)
    stream = data_mod.train_stream(src, mesh, batch=8, seq=4)
    for _ in range(3):
        next(stream)
    stream.close()
    deadline = time.time() + 5.0
    names = [s.name for s in scopes.records()]
    while names.count("data.produce") < 3 and time.time() < deadline:
        time.sleep(0.01)
        names = [s.name for s in scopes.records()]
    assert names.count("build.stream") == 1
    assert names.count("data.produce") >= 3
    produced = [s for s in scopes.records() if s.name == "data.produce"]
    assert all(s.parent is None for s in produced)  # the worker's thread
    scopes.reset()
