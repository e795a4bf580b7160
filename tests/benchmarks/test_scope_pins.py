"""The recorded traces' scope tables, pinned: how many keys each has and the
seconds of five of them, as the reader gave them while it still held a copy
of the vocabulary of its own (the parent of the PR that took the copy out;
that PR also compared the whole tables of both readers once, ``==``).  A
change to ``lib/scopes.py``, ``lib/xplane.py`` or ``lib/hlo_names.py`` that
moves a reading of the same events fails here.  CPU only: a reduction of
recorded events, no device metric of this run."""

import gzip
import os

import pytest

from benchmarks.lib import scopes, xplane

SCOPED = os.path.join(os.path.dirname(xplane.__file__), "testdata", "scoped")

PINS = {
    "pythia-1.4b-widths.decode-1k-128.events.json.gz": (44, {
        "scope/attention@decode.step": 0.011518594,
        "self/layers@decode.step": 0.049240301,
        "scope/prefill": 0.461266212,
        "scope/kv_cache@decode.step": 1.1874e-05,
        "unscoped": 0.006215793}),
    "pythia-6.9b-widths.train-2k-dp2tp2.events.json.gz": (28, {
        "phase/bwd": 0.24769383875,
        "phase/recompute": 0.048157415,
        "coll/allreduce.tp": 0.0321458335,
        "coll/grad_sync": 0.05346010225,
        "self/layers": 0.012625503}),
    "probe-train-step.v5e.xplane.pb.gz": (19, {
        "phase/fwd": 8.057e-05,
        "scope/attention@layers": 0.000121538,
        "self/layers": 5.569e-06,
        "scope/optimizer": 1.3761e-05,
        "unscoped": 1.162e-05}),
}


def test_every_recording_is_pinned():
    assert set(os.listdir(SCOPED)) == set(PINS)


@pytest.mark.parametrize("recording", sorted(PINS))
def test_a_recordings_scope_table_reads_what_it_read(recording, tmp_path):
    path = os.path.join(SCOPED, recording)
    if recording.endswith(".pb.gz"):
        profile = tmp_path / "recorded.xplane.pb"
        with gzip.open(path, "rb") as f:
            profile.write_bytes(f.read())
        events = xplane.read_events(str(profile))
    else:
        events = xplane.load_events(path)
    n_keys, seconds = PINS[recording]
    table = scopes.reduce_scopes(events)
    assert len(table) == n_keys, sorted(table)
    assert {key: table[key] for key in seconds} == seconds
