"""Live observability plane: metrics uplink (delta merge at tree hops,
jobid keying, push-period clamp), the histogram vector path (tagged
delta/absolute wire forms, element-wise merge_hop folds, Prometheus
histogram render, the straggler panel), the DVM scrape endpoint
(/metrics with per-job labels, /status with the FT event timeline),
the one-hop TAG_METRICS delivery semantics, and the FT event log."""

import json
import socket
import threading
import time
import urllib.request

import pytest

from ompi_tpu.core import dss
from ompi_tpu.core.config import var_registry
from ompi_tpu.mpi import trace
from ompi_tpu.runtime import ftevents, rml
from ompi_tpu.runtime.metrics import (AGG_HISTS, AGG_METRICS,
                                      MetricsAggregate, MetricsCollector,
                                      merge_hop, straggler_panel,
                                      vec_merge)


def _vec(marker: str, *pairs, total: int = 0) -> list:
    """A tagged test vector: (bucket, count) pairs + the trailing sum."""
    ints = [0] * trace.HIST_VLEN
    for bucket, count in pairs:
        ints[bucket] = count
    ints[trace.HIST_NBUCKETS] = total
    return [marker] + ints


# -- merge_hop: the per-hop fold -------------------------------------------

def test_merge_hop_midtree_delta_merge():
    """A mid-tree daemon folds a child's payload into its own pending
    delta: same rank's counters update (cumulative, last-writer-wins),
    other ranks ride along, the freshest timestamp wins."""
    pending = {7: {0: [100.0, {"a": 1, "b": 2}]}}
    # child hop: rank 0's newer reading + a new rank 2
    merge_hop(pending, {7: {0: [200.0, {"b": 5, "c": 9}],
                            2: [150.0, {"a": 4}]}})
    assert pending[7][0][0] == 200.0
    assert pending[7][0][1] == {"a": 1, "b": 5, "c": 9}
    assert pending[7][2][1] == {"a": 4}
    # an OLDER duplicate must not regress the timestamp
    merge_hop(pending, {7: {0: [50.0, {"b": 5}]}})
    assert pending[7][0][0] == 200.0


def test_merge_hop_keys_by_jobid():
    """Two jobs' ranks never mix — the per-job namespacing the
    multi-tenant DVM needs."""
    pending = {}
    merge_hop(pending, {7: {0: [1.0, {"x": 1}]}})
    merge_hop(pending, {8: {0: [1.0, {"x": 100}]}})
    assert pending[7][0][1] == {"x": 1}
    assert pending[8][0][1] == {"x": 100}
    assert set(pending) == {7, 8}


def test_merge_hop_ignores_garbage():
    pending = {}
    merge_hop(pending, None)
    merge_hop(pending, {"not-int-keyed": "nope"})
    merge_hop(pending, {7: {0: "not-a-row"}})
    assert pending == {}


# -- push-period var ---------------------------------------------------------

def test_push_period_clamp():
    old = var_registry.get("trace_metrics_push_period")
    try:
        var_registry.set("trace_metrics_push_period", 0.0)
        assert trace.push_period() == 0.0          # disabled
        var_registry.set("trace_metrics_push_period", 0.05)
        assert trace.push_period() == trace.PUSH_PERIOD_FLOOR  # clamped
        var_registry.set("trace_metrics_push_period", 2.5)
        assert trace.push_period() == 2.5          # honest above the floor
        var_registry.set("trace_metrics_push_period", -1.0)
        assert trace.push_period() == 0.0
    finally:
        var_registry.set("trace_metrics_push_period", old)


# -- MetricsCollector: rank datagrams + child payloads ----------------------

def test_collector_udp_roundtrip_and_drain():
    got = []
    col = MetricsCollector(period=30.0, send_fn=got.append)
    try:
        host, port = col.uri.rsplit(":", 1)
        sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        sock.sendto(dss.pack(("m1", 7, 0, 1, {"a": 3})),
                    (host, int(port)))
        deadline = time.monotonic() + 5.0
        payload = {}
        while time.monotonic() < deadline:
            payload = col.drain()
            if payload:
                break
            time.sleep(0.02)
        assert 7 in payload and 0 in payload[7], payload
        assert payload[7][0][1] == {"a": 3}
        # drain took it: nothing pending now
        assert col.drain() == {}
        # a child daemon's TAG_METRICS payload merges too
        col.on_child_payload({7: {1: [time.time(), {"b": 4}]}})
        assert col.drain()[7][1][1] == {"b": 4}
        sock.close()
    finally:
        col.close()


def test_collector_fences_stale_datagrams():
    col = MetricsCollector(period=30.0, send_fn=lambda p: None)
    try:
        host, port = col.uri.rsplit(":", 1)
        addr = (host, int(port))
        sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        sock.sendto(dss.pack(("m1", 7, 0, 9, {"a": 9})), addr)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with col._lock:
                if col._seq.get((7, 0), (0, 0.0))[0] == 9:
                    break
            time.sleep(0.02)
        # an out-of-order older datagram must not regress the counter
        sock.sendto(dss.pack(("m1", 7, 0, 5, {"a": 5})), addr)
        time.sleep(0.3)
        assert col.drain()[7][0][1] == {"a": 9}
        # a RESTARTED life's sequence starts over (push_n 1) — accepted
        sock.sendto(dss.pack(("m1", 7, 0, 1, {"a": 1})), addr)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            p = col.drain()
            if p:
                assert p[7][0][1] == {"a": 1}
                break
            time.sleep(0.02)
        else:
            pytest.fail("restarted-life datagram never accepted")
        # an EXPIRED fence is stale itself: a revived life whose first
        # two pushes were lost (push_n jumps to a mid-range number below
        # the dead life's high-water mark) must not be blacked out
        with col._lock:
            col._seq[(7, 0)] = (60, time.monotonic() - 11.0)
        sock.sendto(dss.pack(("m1", 7, 0, 12, {"a": 12})), addr)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            p = col.drain()
            if p:
                assert p[7][0][1] == {"a": 12}
                break
            time.sleep(0.02)
        else:
            pytest.fail("expired-fence datagram never accepted")
        # a bad-typed datagram (non-int rank) must not kill the thread
        sock.sendto(dss.pack(("m1", 7, "zero", 1, {"a": 1})), addr)
        time.sleep(0.2)
        sock.sendto(dss.pack(("m1", 8, 1, 1, {"b": 2})), addr)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            p = col.drain()
            if p:
                assert p[8][1][1] == {"b": 2}
                break
            time.sleep(0.02)
        else:
            pytest.fail("collector thread died on a garbage datagram")
        sock.close()
    finally:
        col.close()


# -- the histogram vector path -----------------------------------------------

def test_vec_merge_algebra():
    """delta∘delta adds, absolute subsumes older deltas, delta stacks
    onto absolute, absolute∘absolute takes the element-wise max."""
    d1 = _vec("d", (2, 1), total=100)
    d2 = _vec("d", (2, 2), (5, 1), total=300)
    out = vec_merge(d1, d2)
    assert out[0] == "d" and out[3] == 3 and out[6] == 1
    assert out[trace.HIST_NBUCKETS + 1] == 400
    a = _vec("a", (2, 10), total=5000)
    assert vec_merge(d1, a) == a                  # absolute subsumes
    out = vec_merge(a, d1)                        # increments stack on
    assert out[0] == "a" and out[3] == 11
    assert out[trace.HIST_NBUCKETS + 1] == 5100
    a2 = _vec("a", (2, 8), (4, 3), total=4000)
    out = vec_merge(a, a2)                        # reorder-safe max
    assert out[0] == "a" and out[3] == 10 and out[5] == 3
    assert out[trace.HIST_NBUCKETS + 1] == 5000
    # a length-skewed peer resolves to the newer vector, no corruption
    assert vec_merge(["d", 1, 2], d1) == d1


def test_merge_hop_folds_vectors_elementwise():
    """The per-hop fold a failed-send re-merge depends on: two pending
    payloads with deltas for the same series must ADD, not last-writer-
    win (dict.update would silently drop bucket increments)."""
    pending = {7: {0: [100.0, {"coll_dispatch_ns": _vec("d", (3, 2),
                                                        total=200),
                               "x": 5}]}}
    merge_hop(pending, {7: {0: [200.0, {"coll_dispatch_ns":
                                        _vec("d", (3, 1), total=90),
                                        "x": 9}]}})
    row = pending[7][0]
    assert row[0] == 200.0
    assert row[1]["x"] == 9                       # scalars: last writer
    assert row[1]["coll_dispatch_ns"][4] == 3     # vectors: element add
    assert row[1]["coll_dispatch_ns"][trace.HIST_NBUCKETS + 1] == 290


def test_pusher_rides_vector_deltas_and_full_heals():
    """First push: absolute vectors.  A record between pushes rides as
    a tagged delta carrying ONLY the increment; the reorder fence still
    drops stale datagrams ahead of the vector merge."""
    col = MetricsCollector(period=30.0, send_fn=lambda p: None)
    old = var_registry.get("trace_metrics_push_period")
    key = 'coll_dispatch_ns{slot="t",provider="shm",szb="4"}'
    try:
        var_registry.set("trace_metrics_push_period", 30.0)
        trace.hists.pop(key, None)
        trace.record_hist("coll_dispatch_ns", 5000,
                          labels='slot="t",provider="shm",szb="4"')
        pusher = trace.start_metrics_push(77, 0, uri=col.uri)
        assert pusher is not None
        try:
            pusher.push()                     # push 1: full → absolute
            deadline = time.monotonic() + 5.0
            vals = {}
            while time.monotonic() < deadline:
                p = col.drain()
                if p:
                    vals = p[77][0][1]
                    break
                time.sleep(0.02)
            assert key in vals, vals.keys()
            assert vals[key][0] == "a"
            b = trace.hist_bucket_index(5000)
            assert vals[key][1 + b] == 1
            # a new observation rides the next delta — increment only
            trace.record_hist("coll_dispatch_ns", 5000,
                              labels='slot="t",provider="shm",szb="4"')
            pusher.push()
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                p = col.drain()
                if p:
                    delta = p[77][0][1]
                    assert delta[key][0] == "d"
                    assert delta[key][1 + b] == 1, (
                        "delta must carry the increment, not the "
                        "cumulative count")
                    break
                time.sleep(0.02)
            else:
                pytest.fail("vector delta push never arrived")
        finally:
            trace.stop_metrics_push(flush=False)
    finally:
        var_registry.set("trace_metrics_push_period", old)
        trace.hists.pop(key, None)
        col.close()


def test_aggregate_renders_prometheus_histograms():
    """Real histogram exposition: cumulative le buckets ending at +Inf,
    _sum/_count, per-job element-wise bucket sums for AGG_HISTS, and a
    single # TYPE line per metric name."""
    agg = MetricsAggregate()
    b = trace.hist_bucket_index(5000)
    key = 'coll_dispatch_ns{slot="bcast",provider="shm",szb="10"}'
    agg.merge({7: {0: [time.time(), {key: _vec("a", (b, 3), (b + 2, 1),
                                              total=20000),
                                     "pml_zero_copy_sends_total": 2}],
                   1: [time.time(), {key: _vec("a", (b, 1),
                                               total=5000)}]}})
    text = agg.prometheus()
    le = str(1 << (trace.HIST_MIN_EXP + b))
    le_next = str(1 << (trace.HIST_MIN_EXP + b + 1))
    pre = 'job="7",rank="0",slot="bcast",provider="shm",szb="10"'
    assert (f'ompi_tpu_coll_dispatch_ns_bucket{{{pre},le="{le}"}} 3'
            in text)
    # cumulative: the next rung includes the lower one's count
    assert (f'ompi_tpu_coll_dispatch_ns_bucket{{{pre},le="{le_next}"}} 3'
            in text)
    assert f'ompi_tpu_coll_dispatch_ns_bucket{{{pre},le="+Inf"}} 4' in text
    assert f'ompi_tpu_coll_dispatch_ns_sum{{{pre}}} 20000' in text
    assert f'ompi_tpu_coll_dispatch_ns_count{{{pre}}} 4' in text
    assert "# TYPE ompi_tpu_coll_dispatch_ns histogram" in text
    # per-job element-wise sum across ranks, labels preserved
    jpre = 'job="7",slot="bcast",provider="shm",szb="10"'
    assert (f'ompi_tpu_job_coll_dispatch_ns_bucket{{{jpre},le="{le}"}} 4'
            in text)
    assert f'ompi_tpu_job_coll_dispatch_ns_sum{{{jpre}}} 25000' in text
    # one # TYPE line per metric name (scrapers reject duplicates)
    typed = [ln.split()[2] for ln in text.splitlines()
             if ln.startswith("# TYPE")]
    assert len(typed) == len(set(typed)), typed
    # scalars still render beside the vectors
    assert ('ompi_tpu_pml_zero_copy_sends_total{job="7",rank="0"} 2'
            in text)


def test_agg_hists_family_names_real_histograms():
    """Every AGG_HISTS entry must be a _HIST_SPECS histogram — the
    runtime half of the lint pvar-spec cross-check."""
    spec_names = {name for name, _u, _d in trace._HIST_SPECS}
    assert set(AGG_HISTS) <= spec_names, set(AGG_HISTS) - spec_names


# -- the straggler panel ------------------------------------------------------

def test_straggler_panel_names_the_slowest_rank():
    """A deliberately skewed 4-rank job: rank 2 is the slow one, so it
    barely waits while ranks 0/1/3 burn wait time on its flags — the
    panel must name rank 2 with the lowest wait share."""
    waits = {0: 9e9, 1: 8e9, 2: 0.4e9, 3: 8.5e9}
    pubs = {r: 1e8 for r in waits}
    panel = straggler_panel(waits, pubs, "arena_wait", window_s=30.0)
    assert panel["suspect"] == 2
    shares = {int(r): row["wait_share"]
              for r, row in panel["ranks"].items()}
    assert shares[2] == min(shares.values())
    assert abs(sum(shares.values()) - 1.0) < 0.01
    assert panel["skew"] is not None and panel["skew"] > 1.0
    assert panel["max_wait_ms"] == pytest.approx(9000.0)
    # degenerate cases: one rank / no data → no verdict
    assert straggler_panel({0: 5.0}, {}, "arena_wait", 1.0)["suspect"] \
        is None
    assert straggler_panel({}, {}, "arena_wait", 1.0) is None


def test_aggregate_straggler_from_synthetic_skewed_job():
    """End to end through the aggregate: skewed arena-wait vectors in,
    panel out — and the window baseline rotates instead of growing
    forever."""
    agg = MetricsAggregate()
    rows = {}
    for rank, wait_ns in ((0, 9_000_000_000), (1, 8_000_000_000),
                          (2, 400_000_000), (3, 8_500_000_000)):
        rows[rank] = [time.time(),
                      {"coll_arena_wait_ns": _vec("a", (20, 5),
                                                  total=wait_ns),
                       "coll_ppublish_ns": _vec("a", (5, 5),
                                                total=1_000_000)}]
    agg.merge({42: rows})
    panel = agg.straggler(42)
    assert panel is not None
    assert panel["signal"] == "arena_wait"
    assert panel["suspect"] == 2
    assert panel["ranks"]["2"]["wait_share"] == min(
        row["wait_share"] for row in panel["ranks"].values())
    # unknown job → None; a job with no vectors → None
    assert agg.straggler(4242) is None
    agg.merge({43: {0: [time.time(), {"x": 1}]}})
    assert agg.straggler(43) is None


def test_aggregate_straggler_falls_back_to_dispatch_signal():
    """Cross-host jobs have no arena: the panel keys on total coll
    dispatch time instead (same inversion — the last arriver spends
    the least time inside the collective)."""
    agg = MetricsAggregate()
    key = 'coll_dispatch_ns{slot="barrier",provider="host",szb="0"}'
    agg.merge({9: {0: [time.time(), {key: _vec("a", (12, 4),
                                               total=7_000_000_000)}],
                   1: [time.time(), {key: _vec("a", (12, 4),
                                               total=300_000_000)}]}})
    panel = agg.straggler(9)
    assert panel is not None
    assert panel["signal"] == "coll_dispatch"
    assert panel["suspect"] == 1


def test_aggregate_straggler_signal_flip_resets_baseline():
    """A dispatch-signal baseline must never be subtracted from
    arena-wait sums: when the signal flips (arena series appear after a
    cross-host phase), the panel starts a fresh window."""
    agg = MetricsAggregate()
    key = 'coll_dispatch_ns{slot="barrier",provider="host",szb="0"}'
    agg.merge({5: {0: [time.time(), {key: _vec("a", (12, 4),
                                               total=9_000_000_000)}],
                   1: [time.time(), {key: _vec("a", (12, 4),
                                               total=1_000_000_000)}]}})
    assert agg.straggler(5)["signal"] == "coll_dispatch"
    # arena series arrive: smaller sums than the dispatch baseline
    agg.merge({5: {0: [time.time(),
                       {"coll_arena_wait_ns": _vec("a", (15, 2),
                                               total=50_000_000)}],
                   1: [time.time(),
                       {"coll_arena_wait_ns": _vec("a", (15, 2),
                                               total=900_000_000)}]}})
    panel = agg.straggler(5)
    assert panel["signal"] == "arena_wait"
    # fresh window off the cumulative arena sums, not garbage deltas
    assert panel["suspect"] == 0
    assert panel["ranks"]["1"]["wait_share"] > \
        panel["ranks"]["0"]["wait_share"]


def test_aggregate_short_vector_does_not_break_scrape():
    """A version-skewed peer's stub vector (marker only / one int) must
    not 500 the whole /metrics page or crash the panel paths."""
    agg = MetricsAggregate()
    agg.merge({5: {0: [time.time(), {"coll_dispatch_ns": ["a"],
                                     "coll_pstart_ns": ["d", 7]}]}})
    text = agg.prometheus()          # no IndexError
    assert "_bucket" not in text     # stubs render nothing
    assert agg.straggler(5) is None
    assert agg.job_hist_quantiles(5, "coll_dispatch_ns", 0.99) == {}


def test_aggregate_job_eviction_prunes_straggler_baseline():
    agg = MetricsAggregate(max_jobs=1)
    now = time.time()
    agg.merge({1: {0: [now - 5.0,
                       {"coll_arena_wait_ns": _vec("a", (10, 1),
                                                   total=100)}]}})
    assert agg.straggler(1) is not None
    assert 1 in agg._strag_base
    agg.merge({2: {0: [now, {"a": 1}]}})     # evicts job 1
    assert set(agg.snapshot()) == {2}
    assert 1 not in agg._strag_base


def test_aggregate_rank_hist_quantile():
    agg = MetricsAggregate()
    b = trace.hist_bucket_index(50_000)
    key = 'coll_dispatch_ns{slot="allreduce",provider="shm",szb="10"}'
    agg.merge({7: {0: [time.time(), {key: _vec("a", (b, 100),
                                               total=5_000_000)}]}})
    p99 = agg.rank_hist_quantile(7, 0, "coll_dispatch_ns", 0.99)
    assert p99 is not None and 50_000 / 1.5 <= p99 <= 50_000 * 1.5
    assert agg.rank_hist_quantile(7, 3, "coll_dispatch_ns", 0.99) is None
    assert agg.rank_hist_quantile(8, 0, "coll_dispatch_ns", 0.99) is None


# -- rank pusher → collector end to end -------------------------------------

def test_pusher_delta_compresses_and_full_heals():
    col = MetricsCollector(period=30.0, send_fn=lambda p: None)
    old = var_registry.get("trace_metrics_push_period")
    try:
        var_registry.set("trace_metrics_push_period", 30.0)
        pusher = trace.start_metrics_push(7, 0, uri=col.uri)
        assert pusher is not None
        try:
            # first push: full snapshot
            pusher.push()
            deadline = time.monotonic() + 5.0
            vals = {}
            while time.monotonic() < deadline:
                p = col.drain()
                if p:
                    vals = p[7][0][1]
                    break
                time.sleep(0.02)
            assert "pml_zero_copy_sends_total" in vals
            # second push with nothing changed: delta is empty → no
            # datagram at all (the compression)
            pusher.push()
            time.sleep(0.3)
            assert col.drain() == {}
            # a counter bump rides the next delta — and ONLY the change
            trace.count("btl_shm_publish_total", 3)
            pusher.push()
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                p = col.drain()
                if p:
                    delta = p[7][0][1]
                    assert "btl_shm_publish_total" in delta
                    assert len(delta) < 5, (
                        "delta should carry only changed counters")
                    break
                time.sleep(0.02)
            else:
                pytest.fail("delta push never arrived")
        finally:
            trace.stop_metrics_push(flush=False)
    finally:
        var_registry.set("trace_metrics_push_period", old)
        col.close()


def test_collector_push_now_sends_pending_without_waiting_a_period():
    """What an orted does before it reports a rank's exit: the pending
    snapshot goes one hop up at once, so the exit report cannot overtake
    it; with nothing pending nothing is sent."""
    sent = []
    col = MetricsCollector(period=30.0, send_fn=sent.append)
    try:
        col.push_now()
        assert sent == []
        col.on_child_payload({7: {0: [1.0, {"x": 1}]}})
        col.push_now()
        assert sent == [{7: {0: [1.0, {"x": 1}]}}]
        assert col.drain() == {} and col.stats()["pushes_up"] == 1
    finally:
        col.close()


def test_start_metrics_push_disabled_without_uri_or_period():
    old = var_registry.get("trace_metrics_push_period")
    try:
        var_registry.set("trace_metrics_push_period", 1.0)
        assert trace.start_metrics_push(1, 0, uri=None) is None
        var_registry.set("trace_metrics_push_period", 0.0)
        assert trace.start_metrics_push(1, 0, uri="127.0.0.1:1") is None
    finally:
        var_registry.set("trace_metrics_push_period", old)
        trace.stop_metrics_push(flush=False)


# -- send_hop: delivered at the NEXT hop, not relayed to the root -----------

def test_send_hop_delivers_at_parent_hop():
    parent = rml.RmlNode(1)
    child = rml.RmlNode(3)          # tree parent of 3 is 1
    got = threading.Event()
    seen = []

    def handler(origin, payload):
        seen.append((origin, payload))
        got.set()

    parent.register_recv(rml.TAG_METRICS, handler)
    try:
        parent.dial_children([(3, child.uri)])
        assert child.wait_parent(5.0)
        child.send_hop(rml.TAG_METRICS, {7: {0: [1.0, {"a": 1}]}})
        assert got.wait(5.0), "hop message never delivered at the parent"
        assert seen[0][0] == 3
        assert seen[0][1] == {7: {0: [1.0, {"a": 1}]}}
    finally:
        child.close()
        parent.close()


def test_send_hop_at_root_delivers_locally():
    hnp = rml.RmlNode(0)
    seen = []
    hnp.register_recv(rml.TAG_METRICS, lambda o, p: seen.append(p))
    try:
        hnp.send_hop(rml.TAG_METRICS, {"x": 1})
        assert seen == [{"x": 1}]
    finally:
        hnp.close()


# -- MetricsAggregate: the scrape surface -----------------------------------

def test_aggregate_prometheus_labels_and_job_sums():
    agg = MetricsAggregate()
    agg.merge({7: {0: [time.time(), {"pml_zero_copy_sends_total": 5}],
                   1: [time.time(), {"pml_zero_copy_sends_total": 2}]},
               9: {0: [time.time(), {"pml_zero_copy_sends_total": 11}]}})
    text = agg.prometheus()
    assert 'ompi_tpu_pml_zero_copy_sends_total{job="7",rank="0"} 5' in text
    assert 'ompi_tpu_pml_zero_copy_sends_total{job="7",rank="1"} 2' in text
    assert 'ompi_tpu_pml_zero_copy_sends_total{job="9",rank="0"} 11' in text
    # the per-job aggregated family sums across ranks
    assert 'ompi_tpu_job_pml_zero_copy_sends_total{job="7"} 7' in text
    assert 'ompi_tpu_job_pml_zero_copy_sends_total{job="9"} 11' in text
    # TYPE lines present, counters typed as counters
    assert "# TYPE ompi_tpu_pml_zero_copy_sends_total counter" in text


def test_aggregate_ages_and_prune():
    agg = MetricsAggregate(max_jobs=2)
    now = time.time()
    agg.merge({1: {0: [now - 10.0, {"a": 1}]}})
    ages = agg.ages(1, now=now)
    assert ages[0] == pytest.approx(10.0, abs=0.5)
    # unknown job → empty
    assert agg.ages(99) == {}
    # prune keeps the freshest max_jobs
    agg.merge({2: {0: [now - 5.0, {"a": 1}]}})
    agg.merge({3: {0: [now, {"a": 1}]}})
    assert set(agg.snapshot()) == {2, 3}


def test_agg_metrics_family_names_real_counters():
    """Every AGG_METRICS entry must be a _COUNTER_SPECS counter — the
    runtime half of the lint pvar-spec cross-check."""
    spec_names = {name for name, _u, _d in trace._COUNTER_SPECS}
    assert set(AGG_METRICS) <= spec_names, \
        set(AGG_METRICS) - spec_names


# -- FT event timeline -------------------------------------------------------

def test_ftevents_record_snapshot_and_jobid_filter():
    log = ftevents.FtEventLog(capacity=64)
    log.record("detect", jobid=7, rank=2, lives=1, reason="exit 9")
    log.record("revive", jobid=7, rank=2, lives=2)
    log.record("detect", jobid=8, rank=0)
    log.record("daemon_lost", jobid=0, vpid=1)     # pre-job containment
    evs = log.snapshot(7)
    kinds = [e["kind"] for e in evs]
    # job 7's ladder + the jobid-0 containment event ride together;
    # job 8's detect does not
    assert kinds == ["detect", "revive", "daemon_lost"]
    assert evs[0]["rank"] == 2 and evs[0]["info"]["reason"] == "exit 9"
    assert evs[1]["lives"] == 2
    assert [e["kind"] for e in log.snapshot(8)] == ["detect",
                                                    "daemon_lost"]
    assert len(log.snapshot()) == 4
    # wall + monotonic stamps and a monotone seq
    assert evs[0]["wall"] <= evs[1]["wall"]
    assert evs[0]["seq"] < evs[1]["seq"]


def test_ftevents_ring_is_bounded():
    log = ftevents.FtEventLog(capacity=16)
    for i in range(100):
        log.record("detect", jobid=1, rank=i)
    assert log.total() == 100
    evs = log.snapshot()
    # the 16-event tail + ONE synthetic marker saying what fell off —
    # truncation is explicit, never silent
    assert len(evs) == 17
    assert evs[0]["kind"] == "truncated"
    assert evs[0]["info"]["dropped"] == 84
    assert all(e["kind"] != "truncated" for e in evs[1:])
    assert evs[-1]["rank"] == 99      # newest survive, oldest fall off


# -- the scrape endpoint, round trip ----------------------------------------

@pytest.fixture
def scrape_hnp(tmp_path):
    from ompi_tpu.runtime.dvm import DvmHnp

    hnp = DvmHnp(uri_path=str(tmp_path / "dvm.uri"))
    hnp._start_metrics_server(0)     # ephemeral port
    try:
        yield hnp
    finally:
        if hnp._http is not None:
            hnp._http.shutdown()
            hnp._http.server_close()   # release the listening socket


def _get(url: str) -> tuple[int, str]:
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, resp.read().decode()


def test_scrape_metrics_known_counter_under_job_label(scrape_hnp):
    scrape_hnp.metrics_agg.merge(
        {7: {0: [time.time(), {"pml_zero_copy_sends_total": 5}]}})
    status, body = _get(scrape_hnp.metrics_uri + "/metrics")
    assert status == 200
    assert ('ompi_tpu_pml_zero_copy_sends_total'
            '{job="7",rank="0"} 5') in body
    # DVM-level gauges and the HNP's own (unlabeled) pvars ride along
    assert "ompi_tpu_dvm_jobs_completed_total" in body
    assert "ompi_tpu_dvm_daemons" in body


def test_scrape_status_ft_timeline_and_metrics_age(scrape_hnp):
    jobid = 31337
    scrape_hnp.metrics_agg.merge(
        {jobid: {0: [time.time() - 2.0, {"a": 1}]}})
    ftevents.record("detect", jobid=jobid, rank=0, reason="seeded kill")
    ftevents.record("revive", jobid=jobid, rank=0, lives=2)
    status, body = _get(scrape_hnp.metrics_uri + "/status")
    assert status == 200
    doc = json.loads(body)
    assert "uptime_s" in doc and "daemons" in doc
    jobs = {j["jobid"]: j for j in doc["jobs"]}
    assert jobid in jobs
    job = jobs[jobid]
    kinds = [e["kind"] for e in job["ft_events"]]
    assert "detect" in kinds and "revive" in kinds
    assert job["metrics_age_s"]["0"] >= 1.0
    # the bound address was recorded for ephemeral-port clients
    with open(scrape_hnp.uri_path + ".metrics") as f:
        assert f.read().strip() == scrape_hnp.metrics_uri


def test_scrape_unknown_path_404(scrape_hnp):
    with pytest.raises(urllib.error.HTTPError):
        _get(scrape_hnp.metrics_uri + "/nope")


def test_scrape_metrics_no_duplicate_type_lines(scrape_hnp):
    """A real Prometheus scraper rejects a page with two # TYPE lines
    for one metric name (or split sample groups): the DVM's own pvar
    section must exclude names the aggregate already emitted."""
    scrape_hnp.metrics_agg.merge(
        {7: {0: [time.time(), {"pml_zero_copy_sends_total": 5,
                               "btl_shm_publish_total": 2}]}})
    _status, body = _get(scrape_hnp.metrics_uri + "/metrics")
    typed = [ln.split()[2] for ln in body.splitlines()
             if ln.startswith("# TYPE")]
    dupes = {t for t in typed if typed.count(t) > 1}
    assert not dupes, dupes
    # and no unlabeled second sample group for an aggregate-owned name
    zero_copy_lines = [ln for ln in body.splitlines()
                       if ln.startswith("ompi_tpu_pml_zero_copy")]
    assert all("{" in ln for ln in zero_copy_lines), zero_copy_lines


def test_ps_proc_rows_gain_lives_and_metrics_age(scrape_hnp):
    """--dvm-ps rows carry lives, the restarts budget and the
    last-metrics-age column sourced from the aggregate."""
    from types import SimpleNamespace

    from ompi_tpu.runtime.job import ProcState

    job = SimpleNamespace(jobid=7, procs=[SimpleNamespace(
        rank=0, state=ProcState.RUNNING,
        node=SimpleNamespace(name="sim000"), local_rank=0,
        lives=3, restarts=1, exit_code=None)])
    scrape_hnp.metrics_agg.merge(
        {7: {0: [time.time() - 4.0, {"a": 1}]}})
    rows = scrape_hnp._proc_rows(job, {})
    assert rows[0]["lives"] == 3
    assert rows[0]["restarts"] == 1
    assert rows[0]["restarts_budget_left"] == max(
        0, int(var_registry.get("errmgr_max_restarts")) - 1)
    assert rows[0]["metrics_age_s"] == pytest.approx(4.0, abs=1.0)


def test_scrape_status_straggler_panel_names_slowest_rank(scrape_hnp):
    """The acceptance gate: a deliberately skewed 4-rank job's /status
    names the slowest rank in the straggler panel."""
    jobid = 616
    rows = {}
    for rank, wait_ns in ((0, 9_000_000_000), (1, 8_000_000_000),
                          (2, 400_000_000), (3, 8_500_000_000)):
        rows[rank] = [time.time(),
                      {"coll_arena_wait_ns": _vec("a", (20, 5),
                                                  total=wait_ns)}]
    scrape_hnp.metrics_agg.merge({jobid: rows})
    _status, body = _get(scrape_hnp.metrics_uri + "/status")
    doc = json.loads(body)
    job = {j["jobid"]: j for j in doc["jobs"]}[jobid]
    panel = job["straggler"]
    assert panel["suspect"] == 2
    assert set(panel["ranks"]) == {"0", "1", "2", "3"}
    assert panel["ranks"]["2"]["wait_share"] == min(
        r["wait_share"] for r in panel["ranks"].values())


def test_scrape_metrics_histogram_series_round_trip(scrape_hnp):
    """/metrics serves parseable histogram series for pushed vectors
    (the CI obs-smoke grep, in-process form)."""
    key = 'coll_pstart_ns{kind="allreduce",provider="shm"}'
    scrape_hnp.metrics_agg.merge(
        {7: {0: [time.time(), {key: _vec("a", (8, 2), total=1000)}]}})
    _status, body = _get(scrape_hnp.metrics_uri + "/metrics")
    assert "# TYPE ompi_tpu_coll_pstart_ns histogram" in body
    assert 'ompi_tpu_coll_pstart_ns_bucket{job="7",rank="0",' in body
    assert 'le="+Inf"} 2' in body
    assert 'ompi_tpu_coll_pstart_ns_count{job="7",rank="0",' in body
    # still one # TYPE per name across the whole page (DVM pvars ride
    # below the aggregate)
    typed = [ln.split()[2] for ln in body.splitlines()
             if ln.startswith("# TYPE")]
    assert len(typed) == len(set(typed))


def test_ps_proc_rows_gain_coll_p99_column(scrape_hnp):
    """--dvm-ps rows carry the p99 collective latency sourced from the
    rank's pushed dispatch histogram."""
    from types import SimpleNamespace

    from ompi_tpu.runtime.job import ProcState

    job = SimpleNamespace(jobid=7, procs=[SimpleNamespace(
        rank=0, state=ProcState.RUNNING,
        node=SimpleNamespace(name="sim000"), local_rank=0,
        lives=1, restarts=0, exit_code=None)])
    b = trace.hist_bucket_index(100_000)
    key = 'coll_dispatch_ns{slot="allreduce",provider="shm",szb="10"}'
    scrape_hnp.metrics_agg.merge(
        {7: {0: [time.time(), {key: _vec("a", (b, 50),
                                         total=5_000_000)}]}})
    rows = scrape_hnp._proc_rows(job, {})
    assert "coll_p99_us" in rows[0]
    assert 100 / 1.5 <= rows[0]["coll_p99_us"] <= 100 * 1.5


# -- PMIx regcount (the barrier the chaos schedule keys on) -----------------

def test_regcount_counts_registered_lives():
    from ompi_tpu.runtime import pmix

    server = pmix.PMIxServer(size=2)
    try:
        assert pmix.query_regcount(server.uri) == 0
        c0 = pmix.PMIxClient(uri=server.uri, rank=0, size=2)
        assert pmix.query_regcount(server.uri) == 1
        c1 = pmix.PMIxClient(uri=server.uri, rank=1, size=2)
        assert pmix.query_regcount(server.uri) == 2
        assert c0.regcount() == 2
        # query_regcount is registration-free: the probes above must
        # not have inflated the barrier
        assert pmix.query_regcount(server.uri) == 2
        # the ready count tracks init-complete notices separately
        assert pmix.query_regstate(server.uri) == (2, 0, 0)
        c0.ready()
        assert pmix.query_regstate(server.uri) == (2, 0, 1)
        # a revive discards the current life's registration AND ready
        server.proc_revived(1, incarnation=2)
        assert pmix.query_regcount(server.uri) == 1
        c1.ready()            # the dead life's late notice still counts
        server.proc_revived(0, incarnation=2)
        assert pmix.query_regstate(server.uri) == (0, 0, 1)
        c0.finalize()
        c1.finalize()
    finally:
        server.close()


def test_query_regcount_unreachable_is_none():
    from ompi_tpu.runtime import pmix

    assert pmix.query_regcount("tcp://127.0.0.1:1") is None


def test_ps_proc_rows_gain_rejoins_column(scrape_hnp):
    """--dvm-ps rows carry the epoch-fenced coll-rejoin count sourced
    from the rank's pushed coll_rejoin_total pvar (absent while 0 —
    steady-state rows stay compact)."""
    from types import SimpleNamespace

    from ompi_tpu.runtime.job import ProcState

    job = SimpleNamespace(jobid=7, procs=[SimpleNamespace(
        rank=0, state=ProcState.RUNNING,
        node=SimpleNamespace(name="sim000"), local_rank=0,
        lives=2, restarts=0, exit_code=None)])
    scrape_hnp.metrics_agg.merge(
        {7: {0: [time.time(), {"coll_rejoin_total": 1}]}})
    rows = scrape_hnp._proc_rows(job, {})
    assert rows[0]["rejoins"] == 1
    # a rank that never rejoined shows no column at all
    scrape_hnp.metrics_agg.merge(
        {7: {1: [time.time(), {"coll_shm_fanin_total": 3}]}})
    job.procs.append(SimpleNamespace(
        rank=1, state=ProcState.RUNNING,
        node=SimpleNamespace(name="sim000"), local_rank=1,
        lives=1, restarts=0, exit_code=None))
    rows = scrape_hnp._proc_rows(job, {})
    assert "rejoins" not in rows[1]
