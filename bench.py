"""Benchmark driver — prints ONE JSON line on stdout.

Everything that needs the device runs in this one process: a TPU chip
belongs to one process at a time, so a parent that has touched JAX cannot
hand the chip to a child.  A run that finds no TPU exits non-zero before it
compiles anything, and a run in which any row failed exits non-zero after
printing the record.  There is no CPU mode: a number from a CPU run is not a
device metric.

Primary metric:

- **multi-device** (≥2 chips): MPI_Allreduce busbw over ICI (BASELINE.json
  north star) — float32 allreduce through the device path
  (DeviceCommunicator.allreduce → lax.psum), busbw = 2·(n-1)/n·bytes/time.
- **single chip**: flagship-model **MFU** — model FLOPs/step ÷ step time ÷
  chip peak FLOPs (bf16). ``vs_baseline`` is MFU as a fraction of the 40%
  MFU a well-tuned reference-class training stack reaches on this hardware
  class; tokens/s is carried alongside.

The full BASELINE.md config matrix (ring p50, 2D-mesh bcast/allgather,
7B-param reduce_scatter+allgather gradient harness, oshmem max-reduction /
circular-shift on the device path) runs after the primary metric; every
config emits a JSON row into ``BENCH_MATRIX.json``.

Run from the repo root (``python bench.py``); no install is needed.  All
diagnostics go to stderr; stdout carries exactly one JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

import numpy as np

_MATRIX_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_MATRIX.json")

# Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.
# A kind that is not here is an error: a default peak would print a
# utilization that means nothing.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM
    # at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bits_per_s": 1600e9},
}


def device_peaks(kind: str) -> dict:
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {kind!r}; add it to "
            f"bench.DEVICE_PEAKS with its source (known: "
            f"{sorted(DEVICE_PEAKS)})") from None


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_tpu():
    """The TPU devices this process sees, or exit non-zero."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"this measures a TPU and JAX found "
                 f"{devices[0].platform!r} ({devices[0].device_kind}); "
                 f"there is no CPU mode")
    return devices


# ---------------------------------------------------------------------------
# primary metrics
# ---------------------------------------------------------------------------

def bench_allreduce_busbw(devices) -> dict:
    import jax
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.mpi.device_comm import device_world
    from ompi_tpu.parallel.mesh import make_mesh

    n = len(devices)
    mesh = make_mesh(devices=devices)
    comm = device_world(mesh)
    per_device = 1 << 28          # 256 MiB per device
    x = _device_put(np.ones((n * (per_device // 4),), np.float32),
                    mesh, P("world"))

    # the allreduce runs INSIDE one compiled program (fori_loop over the
    # shard_map'd body, rescaled by 1/n so the carry stays finite) and
    # per-iter cost comes from the two-point slope, which cancels the
    # per-dispatch host cost
    scale = np.float32(1.0 / n)

    make = _loop_maker(lambda s: comm.allreduce(s) * scale, mesh,
                       P("world"), P("world"))
    shard_bytes = x.nbytes / n
    row = {
        "metric": f"MPI_Allreduce busbw over ICI ({n} chips, fp32)",
        "unit": "GiB/s",
        "vs_baseline": 1.0,  # reference publishes no number (BASELINE.md)
    }
    if n == 1:
        fn = make(1)
        jax.block_until_ready(fn(x))
        t0 = time.perf_counter()
        _ = float(jax.device_get(fn(x).ravel()[0]))
        dt = time.perf_counter() - t0
        row.update(value=0.0, dispatch_ms=round(dt * 1e3, 1),
                   note=_ONE_CHIP_NOTE)
        log(f"allreduce: {_ONE_CHIP_NOTE} ({dt*1e3:.0f}ms dispatch)")
        return row
    dt, extra = _slope_or_bound(make, x, *_LOOP_ITERS)
    busbw = 2 * (n - 1) / n * shard_bytes / dt
    log(f"allreduce {shard_bytes/2**20:.0f}MiB/dev over {n} devices: "
        f"{dt*1e3:.2f}ms/iter (slope) → busbw {busbw/2**30:.2f} GiB/s")
    row.update(value=round(busbw / 2**30, 3),
               iter_ms=round(dt * 1e3, 2), **extra)
    return row


def _device_put(x, mesh, spec):
    """Place a host array on the mesh BEFORE any timing loop — feeding
    numpy into a jitted fn pays a full H2D transfer per call, which
    swamps the collective being measured (round-2 verdict: the matrix
    reported 0.07 GiB/s on hardware that moves ~800)."""
    import jax
    from jax.sharding import NamedSharding

    return jax.device_put(x, NamedSharding(mesh, spec))


def _slope_time(make_fn, x, lo: int, hi: int, reps: int = 2):
    """Per-iteration seconds of an in-jit loop body via the two-point
    method: build the SAME program at two ``fori_loop`` trip counts, time
    one dispatch of each with a 1-element value readback as the fence,
    and take the slope — every per-dispatch constant (dispatch, readback)
    cancels.  ``make_fn(iters)`` must return a jitted callable whose
    output matches ``x``'s shape/sharding (a well-formed loop carry).

    Only meaningful when the loop body does real per-iteration work: a
    single-chip "collective" is the identity, XLA folds the whole loop
    away, and the slope is noise — callers keep single-dispatch timing
    for that case.
    """
    import jax

    f_lo, f_hi = make_fn(lo), make_fn(hi)

    def timed(f):
        out = f(x)
        _ = float(jax.device_get(out.ravel()[0]))  # compile + warm + fence
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = f(x)
            _ = float(jax.device_get(out.ravel()[0]))
            best = min(best, time.perf_counter() - t0)
        return best

    t_lo, t_hi = timed(f_lo), timed(f_hi)
    slope = (t_hi - t_lo) / (hi - lo)
    if slope <= 0 or (t_hi - t_lo) < 0.02 * t_lo:
        # collapsed slope: the extra iterations vanished into timing
        # noise (host contention, or the body optimized away).  Report
        # the honest upper bound — one dispatch amortized over its trip
        # count — rather than a nonsense near-zero per-iter cost.
        return None, t_lo, t_hi
    return slope, t_lo, t_hi


_SLOPE_COLLAPSED = ("two-point slope collapsed under timing noise; per-iter "
                    "cost is an upper bound (one dispatch / trip count, "
                    "dispatch overhead included)")


def _loop_maker(kernel, mesh, in_specs, out_specs):
    """make(iters) factory for the slope rows: ONE compiled program
    running ``iters`` trips of the shard_map'd kernel (carry must keep
    the input's shape/sharding)."""
    import jax

    def make(iters):
        body = jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)
        return jax.jit(lambda a: jax.lax.fori_loop(
            0, iters, lambda i, y: body(y), a))

    return make


def _slope_fields(t_lo: float, t_hi: float, lo: int, hi: int):
    """The shared slope-or-bound POLICY: per-iter seconds + row fields
    from two wall times.  Collapse threshold and the suspect contract
    live here only — both the loop-carry rows (via _slope_or_bound) and
    rows with other call signatures (decode) decide through this."""
    extra = {"wall_lo_s": round(t_lo, 3), "wall_hi_s": round(t_hi, 3)}
    dt = (t_hi - t_lo) / (hi - lo)
    if dt <= 0 or (t_hi - t_lo) < 0.02 * t_lo:
        extra["suspect"] = _SLOPE_COLLAPSED
        return t_hi / hi, extra
    return dt, extra


def _slope_or_bound(make_fn, x, lo: int, hi: int):
    """(per-iter seconds, extra-row-fields) — slope when clean, else the
    t_hi/hi upper bound with a ``suspect`` note."""
    _dt, t_lo, t_hi = _slope_time(make_fn, x, lo, hi)
    return _slope_fields(t_lo, t_hi, lo, hi)


_LOOP_ITERS = (4, 20)     # (lo, hi) trip counts of the slope rows


_ONE_CHIP_NOTE = ("single device — the collective degenerates to identity; "
                  "busbw is defined over ICI (needs >=2 chips), this row "
                  "times dispatch only; the hbm_copy row carries the "
                  "honest single-chip memory-bandwidth record")


# Any device-path row below this measures overhead, not the data plane
# (HBM ~800 GiB/s, single-chip "collectives" are copies).
_DEVICE_ROW_FLOOR_GIBPS = 10.0


def _flag_suspect(row: dict) -> dict:
    if (row.get("unit") == "GiB/s"
            and row.get("value", 0) < _DEVICE_ROW_FLOOR_GIBPS):
        row["suspect"] = ("below sanity floor "
                          f"({_DEVICE_ROW_FLOOR_GIBPS} GiB/s): likely "
                          "measuring dispatch/transfer, not the data plane")
    return row


def _count_params(params) -> int:
    import jax

    return int(sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(params)))


def _time_train_loop(cfg, mesh, tokens, chain: int, outer: int):
    """Time `outer` dispatches of a `chain`-step compiled train loop.

    All state lives on the mesh's devices with the step's own shardings
    (params/opt donated and fed back — feeding numpy in would time the
    H2D transfer) and the clock is closed by a VALUE readback of the last
    loss, which depends on every step.
    """
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.models import transformer as tfm

    params = tfm.shard_params(cfg, mesh, tfm.init_params(cfg))
    n_params = _count_params(params)
    loop, init_opt = tfm.make_train_loop(cfg, mesh, lr=1e-3, steps=chain)
    opt_state = init_opt(params)
    tokens = _device_put(tokens, mesh, P("dp", "sp"))
    params, opt_state, losses = loop(params, opt_state, tokens)  # compile
    _ = float(losses[-1])                                        # full sync
    t0 = time.perf_counter()
    for _ in range(outer):
        params, opt_state, losses = loop(params, opt_state, tokens)
    loss = float(losses[-1])                                     # fences all
    dt = (time.perf_counter() - t0) / (outer * chain)
    return dt, n_params, loss


def flagship_flops_per_token(cfg, n_params: int) -> int:
    """PaLM-style accounting: 6·N for the dense path + 12·L·D·S for
    attention.  Recomputed operations do not count."""
    return 6 * n_params + 12 * cfg.n_layers * cfg.d_model * cfg.seq


def bench_flagship_mfu(devices) -> dict:
    """Single-chip flagship train step → MFU.  The config is the one
    definition in models/transformer.py (FLAGSHIP): XLA dot-product
    attention, chunked cross-entropy, 32 steps chained in one program."""
    from ompi_tpu.models.transformer import FLAGSHIP, FLAGSHIP_BATCH
    from ompi_tpu.parallel.mesh import make_mesh

    kind = devices[0].device_kind
    peak = device_peaks(kind)["bf16_flops"]
    mesh = make_mesh({"dp": 1, "sp": 1, "tp": 1}, devices=devices[:1])
    cfg, chain, outer = FLAGSHIP, 32, 1
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab,
                          size=(FLAGSHIP_BATCH, cfg.seq)).astype(np.int32)

    dt, n_params, loss = _time_train_loop(cfg, mesh, tokens, chain, outer)
    n_tokens = tokens.size
    model_flops = flagship_flops_per_token(cfg, n_params) * n_tokens
    toks_per_s = n_tokens / dt
    mfu = model_flops / dt / peak
    log(f"bf16 train step: {dt*1e3:.1f}ms, {toks_per_s:,.0f} tok/s, "
        f"{n_params/1e6:.0f}M params, model {model_flops/1e9:.1f} GFLOP/step, "
        f"peak={peak}, MFU={mfu*100:.1f}% (loss {loss:.3f})")
    return {
        "metric": f"flagship transformer train-step MFU (1 chip {kind}, "
                  f"bf16, {n_params/1e6:.0f}M params, seq {cfg.seq})",
        "value": round(mfu * 100, 2),
        "unit": "% MFU",
        # no reference number published (BASELINE.md); 40% MFU is the
        # well-tuned-training-stack bar on this hardware class
        "vs_baseline": round(mfu / 0.40, 3),
        "tokens_per_s": round(toks_per_s, 1),
        "step_ms": round(dt * 1e3, 2),
        "params": n_params,
    }


# ---------------------------------------------------------------------------
# BASELINE.md config matrix → BENCH_MATRIX.json
# ---------------------------------------------------------------------------

def matrix_ring_latency() -> dict:
    """Config 1: 4-rank send/recv ring (host path, real sockets), p50 lap."""
    from tests.mpi.harness import run_ranks

    laps = 200
    msg = np.array([0], np.int32)

    def ring(comm):
        rank, size = comm.rank, comm.size
        nxt, prv = (rank + 1) % size, (rank - 1) % size
        times = []
        for i in range(20 + laps):
            if rank == 0:
                t0 = time.perf_counter()
                comm.send(msg, dest=nxt, tag=1)
                comm.recv(source=prv, tag=1)
                if i >= 20:
                    times.append(time.perf_counter() - t0)
            else:
                m = comm.recv(source=prv, tag=1)
                comm.send(m, dest=nxt, tag=1)
        return times

    results = run_ranks(4, ring, timeout=120.0)
    p50 = float(np.percentile(np.array(results[0]) * 1e6, 50))
    return {
        "metric": "ring_c 4-rank lap latency p50 (host path)",
        "value": round(p50, 1), "unit": "us", "vs_baseline": 1.0,
        "per_hop_us": round(p50 / 4, 2),
    }


def matrix_allreduce_sweep(devices) -> dict:
    """Config 2: OSU-style MPI_Allreduce size sweep — the device path
    (coll/xla → psum) per size, with the host path (coll/tuned algorithms
    over in-process ranks) alongside for the crossover picture."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.mpi.device_comm import device_world
    from ompi_tpu.parallel.mesh import make_mesh

    n = len(devices)
    mesh = make_mesh(devices=devices)
    comm = device_world(mesh)
    dev_rows = {}
    scale = np.float32(1.0 / n)
    sizes = (("4KiB", 1024), ("1MiB", 1 << 18), ("64MiB", 1 << 24))
    if n == 1:
        for label, _elems in sizes:
            dev_rows[label] = {"us": None, "note": _ONE_CHIP_NOTE}
        sizes = ()
    for label, elems in sizes:
        x = _device_put(np.ones((n * elems,), np.float32), mesh, P("world"))
        make = _loop_maker(lambda s: comm.allreduce(s) * scale, mesh,
                           P("world"), P("world"))
        lo, hi = _LOOP_ITERS
        if elems <= (1 << 18):  # small payloads: longer loops, less noise
            lo, hi = lo * 4, hi * 4
        dt, extra = _slope_or_bound(make, x, lo, hi)
        shard = elems * 4
        dev_rows[label] = {
            "us": round(dt * 1e6, 1),
            "busbw_gibps": round(2 * (n - 1) / n * shard / dt / 2**30, 3),
        }
        if "suspect" in extra:
            dev_rows[label]["suspect"] = extra["suspect"]

    # host path: 4 in-process ranks through coll/tuned's decision layer
    from tests.mpi.harness import run_ranks

    host_rows = {}
    for label, elems in (("4B", 1), ("4KiB", 1024), ("1MiB", 1 << 18)):
        payload = np.ones(elems, np.float32)
        iters = 30 if elems <= 1024 else 10

        def body(comm_):
            import time as _t

            comm_.allreduce(payload)          # warm routes
            t0 = _t.perf_counter()
            for _ in range(iters):
                comm_.allreduce(payload)
            return (_t.perf_counter() - t0) / iters

        dts = run_ranks(4, body, timeout=120.0)
        dt = max(dts)
        host_rows[label] = {"us": round(dt * 1e6, 1)}

    return {
        "metric": f"MPI_Allreduce sweep ({n} dev psum | 4-rank host tuned)",
        "value": dev_rows["64MiB"].get("busbw_gibps", 0.0), "unit": "GiB/s",
        "vs_baseline": 1.0,
        "device_path": dev_rows, "host_path_4rank": host_rows,
    }


def matrix_mesh_bcast_allgather(devices) -> dict:
    """Config 3: Bcast + Allgather over a 2D mesh, mixed dtypes."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.mpi.device_comm import DeviceCommunicator
    from ompi_tpu.parallel.mesh import make_mesh, mesh_shape_for

    n = len(devices)
    shape = mesh_shape_for(n, ["x", "y"])
    mesh = make_mesh(shape, devices=devices)
    comm = DeviceCommunicator(mesh, ("x", "y"))
    if n == 1:
        return {
            "metric": f"Bcast+Allgather 2D mesh {tuple(shape.values())}, "
                      "mixed dtypes",
            "value": 0.0, "unit": "GiB/s", "vs_baseline": 1.0,
            "note": _ONE_CHIP_NOTE,
        }
    nbytes = 0
    total_dt = 0.0
    suspect = None
    for dtype in (np.float32, np.bfloat16 if hasattr(np, "bfloat16")
                  else np.float16, np.int32):
        x = _device_put(
            np.ones((n * (1 << 22),), dtype=np.float32).astype(dtype),
            mesh, P(("x", "y")))
        shard_elems = x.shape[0] // n

        def kernel(s):
            # bcast + allgather, then slice this device's shard back out
            # so the loop carry keeps the input's shape/sharding
            b = comm.bcast(s, root=0)
            full = comm.allgather(b)
            return jax.lax.dynamic_slice_in_dim(
                full, comm.rank() * shard_elems, shard_elems)

        make = _loop_maker(kernel, mesh, P(("x", "y")), P(("x", "y")))
        dt, extra = _slope_or_bound(make, x, *_LOOP_ITERS)
        total_dt += dt
        nbytes += x.nbytes
        if "suspect" in extra:
            suspect = extra["suspect"]
    gbps = nbytes / total_dt / 2**30
    row = {
        "metric": f"Bcast+Allgather 2D mesh {tuple(shape.values())}, "
                  "mixed dtypes",
        "value": round(gbps, 3), "unit": "GiB/s", "vs_baseline": 1.0,
    }
    if suspect:
        row["suspect"] = suspect
    return row


def matrix_hbm_copy(devices) -> dict:
    """HBM-bandwidth calibration (the memory-side twin of matmul_peak's
    MXU row): slope-timed read+write sweep of one device's HBM.  This is
    the sanity floor for every bandwidth row — a single-chip self-put or
    degenerate collective can never beat it, and on one chip it is the
    honest 'what the memory system can do' record the n=1 matrix rows
    point at instead of timing dispatch."""
    import jax

    n_elems = 1 << 26
    x = jax.device_put(np.ones((n_elems,), np.float32), devices[0])
    nbytes = x.nbytes

    def make(iters):
        return jax.jit(lambda a: jax.lax.fori_loop(
            0, iters, lambda i, y: y + np.float32(1.0), a))

    dt, extra = _slope_or_bound(make, x, 8, 72)
    # each iteration reads the buffer and writes it back
    gbps = 2 * nbytes / dt / 2**30
    return {
        "metric": f"HBM read+write bandwidth ({nbytes >> 20}MiB fp32, "
                  f"1 device)",
        "value": round(gbps, 2), "unit": "GiB/s", "vs_baseline": 1.0,
        "per_iter_ms": round(dt * 1e3, 3), **extra,
    }


def matrix_grad_reduce_scatter(devices) -> dict:
    """Config 4: data-parallel gradient reduce_scatter + allgather on
    float32 buffers, sized to HBM (7B params when it fits)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.mpi.device_comm import device_world
    from ompi_tpu.parallel.mesh import make_mesh

    n = len(devices)
    limit = devices[0].memory_stats()["bytes_limit"]
    # grad shard + scattered output + slack must fit per device
    params = min(7_000_000_000, int(limit * 0.15 / 4) * n)
    params -= params % (n * 1024)
    mesh = make_mesh(devices=devices)
    x = _device_put(np.ones((params,), np.float32), mesh, P("world"))
    nbytes = x.nbytes

    scale = np.float32(1.0 / n)

    def kernel(s):
        scattered = jax.lax.psum_scatter(s, "world", tiled=True) * scale
        return jax.lax.all_gather(scattered, "world", tiled=True)

    make = _loop_maker(kernel, mesh, P("world"), P("world"))
    row = {
        "metric": f"grad reduce_scatter+allgather ({params/1e9:.2f}B fp32 "
                  f"params, {n} dev)",
        "unit": "GiB/s", "vs_baseline": 1.0, "params": params,
    }
    if n == 1:
        row.update(value=0.0, note=_ONE_CHIP_NOTE)
        return row
    dt, extra = _slope_or_bound(make, x, *_LOOP_ITERS)
    gbps = 2 * nbytes / dt / 2**30  # RS + AG each move ~the buffer once
    row.update(value=round(gbps, 3), step_ms=round(dt * 1e3, 2), **extra)
    return row


def matrix_oshmem_device(devices) -> dict:
    """Config 5: oshmem max-reduction + circular shift on the device path
    (symmetric-heap semantics: every device holds an identically-shaped
    shard; max_to_all = pmax, circular shift = ppermute)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.mpi.device_comm import device_world
    from ompi_tpu.mpi.op import MAX
    from ompi_tpu.parallel.mesh import make_mesh

    n = len(devices)
    mesh = make_mesh(devices=devices)
    comm = device_world(mesh)
    x = _device_put(np.arange(n * (1 << 22), dtype=np.float32),
                    mesh, P("world"))
    nbytes = x.nbytes

    def kernel(s):
        m = comm.allreduce(s, MAX)       # shmem_float_max_to_all
        return comm.shift(m, 1, axis="world")  # circular shift, 1 ICI hop

    make = _loop_maker(kernel, mesh, P("world"), P("world"))
    row = {
        "metric": f"oshmem max_to_all + circular shift ({n} dev, "
                  f"{nbytes/n/2**20:.0f}MiB/dev)",
        "unit": "GiB/s", "vs_baseline": 1.0,
    }
    if n == 1:
        row.update(value=0.0, note=_ONE_CHIP_NOTE)
        return row
    dt, extra = _slope_or_bound(make, x, *_LOOP_ITERS)
    row.update(value=round(nbytes / dt / 2**30, 3), **extra)
    return row


def matrix_shm_pingpong() -> dict:
    """Two real PROCESSES ping-ponging raw frames over the shm BTL rings
    — the deployment-shape same-host data-plane number (the reference's
    vader BTL benchmark shape), exercising the fused native frame engine
    (fastdss.ring_send/ring_recv) without GIL sharing between ranks."""
    import multiprocessing as mp

    def child(c2p, p2c, result_q):
        from ompi_tpu.mpi.btl_shm import ShmBTL

        frames = []
        btl = ShmBTL(1, lambda p, h, b: frames.append((h, b)))
        c2p.put(btl.address)
        peer_card = p2c.get()
        btl.connect(0, peer_card)
        # echo every frame back until the stop marker
        seen = 0
        deadline = time.perf_counter() + 60
        while time.perf_counter() < deadline:
            if len(frames) > seen:
                h, b = frames[seen]
                if h.get("t") == "stop":
                    break
                seen += 1
                btl.send(0, h, b)
            else:
                time.sleep(0)
        result_q.put(seen)
        btl.close()

    from ompi_tpu.mpi.btl_shm import ShmBTL

    ctx = mp.get_context("fork")
    c2p, p2c, result_q = ctx.Queue(), ctx.Queue(), ctx.Queue()
    proc = ctx.Process(target=child, args=(c2p, p2c, result_q),
                       daemon=True)
    proc.start()
    frames = []
    btl = ShmBTL(0, lambda p, h, b: frames.append((h, b)))
    peer_card = c2p.get(timeout=30)
    p2c.put(btl.address)
    btl.connect(1, peer_card)
    hdr = {"t": "eager", "tag": 1, "cid": 0, "seq": 0, "dt": "<i4",
           "elems": 16, "shp": [16]}
    payload = b"\x01" * 64
    laps = []
    warm, iters = 50, 400
    for i in range(warm + iters):
        target = len(frames) + 1   # BEFORE the send: the echo can land
        t0 = time.perf_counter()    # before this line otherwise
        btl.send(1, hdr, payload)
        deadline = t0 + 10
        while len(frames) < target and time.perf_counter() < deadline:
            time.sleep(0)   # yield: the poller thread appends frames
        if i >= warm:
            laps.append(time.perf_counter() - t0)
    btl.send(1, {"t": "stop"}, b"")
    echoed = result_q.get(timeout=30)
    proc.join(timeout=10)
    btl.close()
    p50 = float(np.percentile(np.array(laps) * 1e6, 50))
    return {
        "metric": "shm BTL 2-process ping-pong p50 (64B frames, fused "
                  "native ring)",
        "value": round(p50, 2), "unit": "us", "vs_baseline": 1.0,
        "one_way_us": round(p50 / 2, 2), "echoed": echoed,
    }


def matrix_shm_msgrate() -> dict:
    """Two real PROCESSES, PML-level small-message rate over the shm BTL
    — total CPU work per message (send prologue + C ring publish + fused
    drain + match + deliver).  On small hosts this is the honest
    same-host data-plane number: ping-pong latency there measures the
    scheduler, not the stack (1 core ⇒ every hop is a context switch)."""
    import multiprocessing as mp

    n_msgs = 20_000

    def child(c2p, p2c):
        from ompi_tpu.mpi.comm import Communicator
        from ompi_tpu.mpi.group import Group
        from ompi_tpu.mpi.pml import PmlOb1

        pml = PmlOb1(1)
        c2p.put(pml.address)
        peers = p2c.get()
        pml.set_peers(peers)
        comm = Communicator(Group(range(2)), cid=0, pml=pml,
                            my_world_rank=1)
        buf = np.zeros(16, np.int32)
        for _ in range(n_msgs):
            comm.recv(buf=buf, source=0, tag=1)
        comm.send(buf, dest=0, tag=2)   # ack closes the clock
        pml.close()

    from ompi_tpu.mpi.comm import Communicator
    from ompi_tpu.mpi.group import Group
    from ompi_tpu.mpi.pml import PmlOb1

    ctx = mp.get_context("fork")
    c2p, p2c = ctx.Queue(), ctx.Queue()
    proc = ctx.Process(target=child, args=(c2p, p2c), daemon=True)
    proc.start()
    pml = PmlOb1(0)
    try:
        peers = {0: pml.address, 1: c2p.get(timeout=30)}
        p2c.put(peers)
        pml.set_peers(peers)
        comm = Communicator(Group(range(2)), cid=0, pml=pml,
                            my_world_rank=0)
        msg = np.arange(16, dtype=np.int32)
        comm.send(msg, dest=1, tag=1)   # warm the route + ring
        t0 = time.perf_counter()
        for _ in range(n_msgs - 1):
            comm.send(msg, dest=1, tag=1)
        comm.recv(source=1, tag=2)
        dt = time.perf_counter() - t0
        proc.join(timeout=10)
    finally:
        pml.close()
    return {
        "metric": "shm PML 2-process message rate (64B, fused native "
                  "engine)",
        "value": round(n_msgs / dt),
        "unit": "msg/s", "vs_baseline": 1.0,
        "us_per_msg": round(dt / n_msgs * 1e6, 2),
        "n_cores": os.cpu_count(),
    }


def matrix_remote_dma(devices) -> dict:
    """One-sided put (pallas remote DMA, ≈ btl_put) — on ≥2 chips a true
    cross-chip put timing the single ICI path; on 1 chip the self-put
    degenerate form, which still exercises the kernel's TPU lowering."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.ops.remote_dma import window_put
    from ompi_tpu.parallel.mesh import make_mesh

    n = len(devices)
    mesh = make_mesh(devices=devices)
    elems = 1 << 24               # 64 MiB shards
    win = _device_put(np.zeros((n * elems,), np.float32), mesh, P("world"))
    val = _device_put(np.ones((n * elems,), np.float32), mesh, P("world"))
    src, dst = (0, 1) if n >= 2 else (0, 0)

    def body(w, v):
        return window_put(w, v, src=src, dst=dst, axis="world")

    sm = jax.shard_map(body, mesh=mesh,
                       in_specs=(P("world"), P("world")),
                       out_specs=P("world"), check_vma=False)

    # the put repeats INSIDE one compiled program; the two-point slope
    # cancels the per-dispatch host cost.  Unlike the collective
    # rows this is real per-iteration work even on 1 chip (the self-put
    # is an HBM copy into the window's dst shard), so the slope method
    # applies at any n.
    def make(iters):
        return jax.jit(lambda w: jax.lax.fori_loop(
            0, iters, lambda i, y: sm(y, val), w))

    dt, rdma_extra = _slope_or_bound(make, win, *_LOOP_ITERS)
    out = make(1)(win)
    nbytes = elems * 4
    ok = bool(np.asarray(out[dst * elems: dst * elems + 3] == 1.0).all())
    return {
        "metric": (f"one-sided put "
                   f"{f'{nbytes >> 20}MiB' if nbytes >= 1 << 20 else f'{nbytes >> 10}KiB'} "
                   f"{'chip0→chip1 (ICI RDMA)' if n >= 2 else 'self (1 chip)'}"),
        "value": round(nbytes / dt / 2**30, 3), "unit": "GiB/s",
        "vs_baseline": 1.0, "correct": ok, "n_devices": n, **rdma_extra,
    }


def matrix_decode_throughput(devices) -> dict:
    """Inference headline: greedy KV-cache decode tokens/s on one chip.

    Two decoders compiled at different ``max_new`` trip counts; the
    slope across them cancels BOTH the prefill pass and the dispatch
    round trip (the same two-point method as matmul_peak), leaving the
    steady-state per-token step cost of the cached decode loop."""
    import jax

    from ompi_tpu.models.decode import make_decoder
    from ompi_tpu.models.transformer import FLAGSHIP, FLAGSHIP_BATCH
    from ompi_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"dp": 1, "sp": 1, "tp": 1}, devices=devices[:1])
    cfg = FLAGSHIP                # 468M; KV room for 512 + 192 at batch 16
    batch, prompt_len, lo, hi = FLAGSHIP_BATCH, 512, 32, 192

    from ompi_tpu.models import transformer as tfm

    params = tfm.shard_params(cfg, mesh, tfm.init_params(cfg))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab,
                          size=(batch, prompt_len)).astype(np.int32)

    def timed(max_new: int) -> float:
        dec = make_decoder(cfg, mesh, max_new=max_new)
        out = dec(params, prompt)
        jax.block_until_ready(out)            # compile + warm
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            out = dec(params, prompt)
            _ = int(np.asarray(out[0, -1]))   # value-readback fence
            best = min(best, time.perf_counter() - t0)
        return best

    t_lo, t_hi = timed(lo), timed(hi)
    dt, extra = _slope_fields(t_lo, t_hi, lo, hi)
    row = {
        "metric": f"greedy KV-cache decode ({batch}x{prompt_len} prompt, "
                  f"1 chip)",
        "unit": "tokens/s", "vs_baseline": 1.0,
        "value": round(batch / dt, 1), **extra,
    }
    if "suspect" not in extra:
        row["ms_per_token"] = round(dt * 1e3, 3)
    return row


def matrix_flash_bwd_kernel(devices) -> dict:
    """Pallas flash-attention kernels, forward and both backwards: compile
    + run the gradient of a causal call."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu.ops.flash_attention import flash_attention

    b, t, h, d = 2, 512, 4, 128
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(b, t, h, d)),
                           jnp.bfloat16) for _ in range(3))

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))  # bind ONCE:
    # a fresh jit wrapper per call would re-trace and the timed run
    # would measure compilation, not the kernels
    grads = fn(q, k, v)
    jax.block_until_ready(grads)
    t0 = time.perf_counter()
    grads = fn(q, k, v)
    jax.block_until_ready(grads)
    dt = time.perf_counter() - t0
    finite = all(bool(np.isfinite(np.asarray(
        g, dtype=np.float32)).all()) for g in grads)
    return {
        "metric": f"flash bwd pallas kernels (seq {t})",
        "value": round(dt * 1e3, 2), "unit": "ms", "vs_baseline": 1.0,
        "grads_finite": finite,
    }


def matrix_tuned_crossovers(devices) -> dict:
    """Run the measured-crossover tuner (ompi_tpu.tools.tune) and ship the
    generated rules file next to coll/xla, so the decision layer's
    thresholds become measured numbers with provenance instead of
    guesses."""
    from ompi_tpu.tools.tune import DEFAULT_OUT, tune_device_colls

    text, table = tune_device_colls(devices, out_path=DEFAULT_OUT)
    rule_lines = [ln for ln in text.splitlines()
                  if ln and not ln.startswith("#")]
    return {
        "metric": f"measured coll crossovers ({len(devices)} dev)",
        "value": len(rule_lines), "unit": "rules", "vs_baseline": 1.0,
        "rules": rule_lines, "table_us": table,
        "shipped": DEFAULT_OUT,
    }


def run_matrix(devices) -> list[dict]:
    """Every row runs; a row that raises becomes an ``"error"`` row with
    its traceback on stderr, and ``main`` exits non-zero for it."""
    rows: list[dict] = []
    for name, fn in (
            ("ring_latency", matrix_ring_latency),
            ("shm_pingpong", matrix_shm_pingpong),
            ("shm_msgrate", matrix_shm_msgrate),
            ("hbm_copy", lambda: matrix_hbm_copy(devices)),
            ("allreduce_sweep", lambda: matrix_allreduce_sweep(devices)),
            ("mesh_bcast_allgather",
             lambda: matrix_mesh_bcast_allgather(devices)),
            ("grad_reduce_scatter",
             lambda: matrix_grad_reduce_scatter(devices)),
            ("oshmem_device", lambda: matrix_oshmem_device(devices)),
            ("remote_dma", lambda: matrix_remote_dma(devices)),
            ("decode_throughput",
             lambda: matrix_decode_throughput(devices)),
            ("flash_bwd_kernel",
             lambda: matrix_flash_bwd_kernel(devices)),
            ("tuned_crossovers",
             lambda: matrix_tuned_crossovers(devices))):
        t0 = time.perf_counter()
        try:
            row = fn()
        except Exception as e:  # noqa: BLE001 — the other rows still run;
            # main() turns any "error" row into a non-zero exit
            traceback.print_exc(file=sys.stderr)
            row = {"metric": name, "value": 0, "unit": "error",
                   "vs_baseline": 0, "error": f"{type(e).__name__}: {e}"}
        row["config"] = name
        row["wall_s"] = round(time.perf_counter() - t0, 2)
        _flag_suspect(row)
        log(f"matrix[{name}]: {json.dumps(row)}")
        rows.append(row)
    with open(_MATRIX_PATH, "w") as f:
        json.dump(rows, f, indent=1)
    log(f"matrix written to {_MATRIX_PATH}")
    return rows


def main() -> int:
    t_start = time.perf_counter()
    from ompi_tpu.core import enable_compile_cache

    devices = require_tpu()
    cache = enable_compile_cache()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"devices: {device}; compile cache: {cache}")
    if len(devices) >= 2:
        result = bench_allreduce_busbw(devices)
    else:
        result = bench_flagship_mfu(devices)
    result["device"] = device
    rows = run_matrix(devices)
    failed = [r["config"] for r in rows if r["unit"] == "error"]
    if failed:
        result["failed_rows"] = failed
    result["wall_s"] = round(time.perf_counter() - t_start, 1)
    # provenance: the transport-stack counter snapshot (pack-plan
    # classes, zero-copy vs packed sends, shm ring traffic) rides in the
    # record, so a row carries which fast paths its own run exercised
    from ompi_tpu.mpi import trace as _trace

    result["counters"] = _trace.counters_snapshot()
    print(json.dumps(result), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
