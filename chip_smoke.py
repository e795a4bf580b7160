#!/usr/bin/env python3
"""chip_smoke.py — drive the main path once on the TPU this process sees.

    python3 chip_smoke.py          # from the repo root; no install, no flags

One process and one mesh over every device JAX shows (dp×tp, as
examples/train.py builds it), at the full width of the flagship model
(models/transformer.FLAGSHIP), through the entry points a user calls:

- trainer: ``data.train_stream`` → ``make_train_step`` / ``make_train_loop``,
  loss finite and falling (1024 positions: the jnp path, by the rule); again
  at 2048 positions and the same tokens a step, where the rule takes the
  kernels: the step holds the three flash kernels and the rotary one, and
  starts from the same loss;
- kernels: flash forward and both backward kernels against
  ``local_attention(impl="jnp")`` and its autodiff;
  ``DeviceCommunicator.put`` / ``get``;
- decoder: ``make_decoder`` answers two prompt batches, greedy output equal
  on a repeat, first token equal to the argmax of ``make_forward``;
- MPI surface: ``ompi_tpu.init()``, ``bind_device(device_world())``,
  ``allreduce`` / ``allgather`` / ``bcast`` on committed device arrays;
- on several chips also: the loss on one chip against the loss on all of
  them, shards and memory on every device, ring attention over sp = all
  devices, and put/get with ``src != dst``.

It fails at the first phase that fails: nothing is caught, skipped or
replaced by a reference path.  It has no CPU mode: where JAX finds no TPU it
exits non-zero before compiling anything.  The phases are functions of a
``Size`` so that tests/test_chip_smoke.py can drive them tiny on the virtual
CPU mesh, where the suite's conftest puts pallas in interpret mode.

The line before it is ``run {...}``: what the host did while the phases' jobs
ran, by callable, by program object (a first call's rest, a later call that
compiled), the collector's passes and the longest jobs, each a call to the next
(``ompi_tpu/core/scopes.run()``).
The line before the last is ``startup {...}``: where the host's time went,
by span, by program, by program object and stage (``calls``) and by layer
kind and kernel traced (``trace``), with the spans the record could not keep
(``dropped``: 0 in a sound run) (``ompi_tpu/core/scopes.startup()``, which
also gives every phase's compile seconds and cache hits).  The last line of
stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import re
import sys
import time

import numpy as np

from ompi_tpu.models.transformer import (FLAGSHIP, FLAGSHIP_BATCH,
                                         TransformerConfig)

# bf16 storage rounds to 2^-8; every comparison against a reference is a
# max error over the reference's max magnitude
BF16_TOL = 3e-2
LOSS_RTOL = 2e-2


@dataclasses.dataclass(frozen=True)
class Size:
    cfg: TransformerConfig
    batch: int
    steps: int          # optimizer steps of each trainer phase
    prompt: int         # decoder prompt length
    new_tokens: int     # tokens the decoder generates
    ring_seq: int       # per-device sequence of the ring-attention step
    rows: int           # (rows, 128) f32 per device in collectives, put/get


FULL = Size(cfg=FLAGSHIP, batch=FLAGSHIP_BATCH, steps=4, prompt=512,
            new_tokens=32, ring_seq=1024, rows=1 << 15)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def compile_totals() -> dict:
    """JAX's own compile clock, from the program's record
    (``ompi_tpu/core/scopes.py``): ``programs`` sent to the backend since the
    first factory was called, ``backend_s`` there (compiling, or reading the
    persistent cache instead), and that cache's hits and misses."""
    from ompi_tpu.core import scopes

    return scopes.startup()["totals"]


def startup_summary(slowest: int = 5) -> dict:
    """The program's own account of the host side of this process
    (``scopes.startup()``: ``spans``, ``programs``, ``calls``, ``trace``,
    ``retraces``, ``totals``, ``records``, ``dropped``), with every program
    that is not the package's own summed but for the ``slowest`` few."""
    from ompi_tpu.core import scopes

    out = scopes.startup()
    others = out.pop("others")

    def seconds(row):
        return row["trace_s"] + row["lower_s"] + row["backend_s"]

    by_cost = sorted(others, key=lambda name: -seconds(others[name]))
    out["others"] = {"programs": len(others),
                     "seconds": sum(map(seconds, others.values())),
                     "slowest": {name: seconds(others[name])
                                 for name in by_cost[:slowest]}}
    return out


def run_summary(slowest: int = 5) -> dict:
    """The program's own account of the host side of its running jobs
    (``scopes.run()``: ``callables``, ``programs``, ``gc``, ``jobs``,
    ``records``, ``wrapped``), with ``jobs`` cut to the ``slowest``, each
    from one call's start to the next's."""
    from ompi_tpu.core import scopes

    out = scopes.run()
    out["jobs"] = sorted(out["jobs"],
                         key=lambda row: -row["wall_s"])[:slowest]
    return out


def model_mesh(devices):
    """dp×tp over ``devices`` (sp = 1), as examples/train.py builds it."""
    from ompi_tpu.parallel.mesh import make_mesh, mesh_shape_for

    shape = mesh_shape_for(len(devices), ["dp", "tp"])
    return make_mesh({"dp": shape["dp"], "sp": 1, "tp": shape["tp"]},
                     devices=devices)


@functools.lru_cache(maxsize=1)
def _host_params(cfg: TransformerConfig) -> dict:
    """Seeded random weights, made once (468M normals take seconds)."""
    from ompi_tpu.models import transformer as tfm

    return tfm.init_params(cfg)


def _corpus(vocab: int) -> np.ndarray:
    """97 distinct tokens, repeating: a few steps of learning which tokens
    occur already lower the loss on batches the model has not seen."""
    pattern = np.random.default_rng(0).integers(0, vocab, size=97)
    return np.tile(pattern, 512).astype(np.int32)


def _kernels(jitted, *args) -> int:
    """How many compiled pallas kernels the program holds.  Interpret mode
    lowers a kernel to plain HLO and callbacks, never to tpu_custom_call."""
    return jitted.lower(*args).as_text().count("tpu_custom_call")


def _kernel_names(jitted, *args) -> list[str]:
    """The names of the compiled pallas kernels the program holds, one
    entry a call."""
    return re.findall(r'kernel_name = "(\w+)"',
                      jitted.lower(*args).as_text())


def _rel_err(got, ref) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _timed(fn, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _warm_timed(fn, *args):
    """Seconds of a call after the one that compiled it."""
    import jax

    jax.block_until_ready(fn(*args))
    return _timed(fn, *args)


def _rank_rows(n: int, rows: int) -> np.ndarray:
    """(n·rows, 128) f32 whose r-th block of rows holds r + 1: device r's
    shard of an array sharded over a flat mesh."""
    ranks = np.repeat(np.arange(n, dtype=np.float32) + 1, rows)
    return np.broadcast_to(ranks[:, None], (n * rows, 128))


def _out_and_grads(fwd, mesh, spec):
    """jitted (q, k, v, g) → (fwd(q, k, v), dq, dk, dv) per device."""
    import jax

    def local(q, k, v, g):
        out, pull = jax.vjp(fwd, q, k, v)
        return (out, *pull(g))

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(spec,) * 4,
                                 out_specs=(spec,) * 4, check_vma=False))


def _on_every_device(tree, devices, what: str) -> None:
    import jax

    want = set(devices)
    for leaf in jax.tree_util.tree_leaves(tree):
        have = {s.device for s in leaf.addressable_shards}
        _check(have == want, f"{what}: a {leaf.shape} leaf lives on "
                             f"{len(have)} of {len(want)} devices")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_train(size: Size, devices, attention: str = "xla",
                seq: int = 0) -> dict:
    """A few optimizer steps on fresh batches from ``train_stream``; under
    the flagship's own ``attention`` word also a ``make_train_loop`` chain
    and, on several devices, the loss against one device's and the
    state's placement.  ``seq`` trains the same weights at another length,
    the same tokens a step (0 = the size's own)."""
    import jax

    from ompi_tpu.models import data
    from ompi_tpu.models import transformer as tfm

    cfg = dataclasses.replace(size.cfg, attention=attention,
                              seq=seq or size.cfg.seq)
    batch_size = size.batch * size.cfg.seq // cfg.seq
    mesh = model_mesh(devices)
    params = tfm.shard_params(cfg, mesh, _host_params(size.cfg))
    step, init_opt = tfm.make_train_step(cfg, mesh, lr=1e-3)
    opt_state = init_opt(params)
    stream = data.train_stream(data.ArraySource(_corpus(cfg.vocab)), mesh,
                               batch_size, cfg.seq)
    out: dict = {"losses": [], "step_s": []}
    try:
        batch = next(stream)
        out["kernels"] = _kernel_names(step, params, opt_state, batch)
        programs = []       # compiled so far, after each step
        for _ in range(size.steps):
            (params, opt_state, loss), dt = _timed(
                step, params, opt_state, batch)
            out["losses"].append(float(loss))
            out["step_s"].append(dt)
            programs.append(compile_totals()["programs"])
            batch = next(stream)
        _check(programs[-1] == programs[0],
               f"train[{attention}]: a step after the first compiled a "
               f"program: its inputs changed placement or shape")
        if attention == "xla":
            loop, _ = tfm.make_train_loop(cfg, mesh, lr=1e-3, steps=2)
            params, opt_state, chained = loop(params, opt_state, batch)
            out["losses"] += [float(x) for x in chained]
            batch = next(stream)
    finally:
        stream.close()
    losses = out["losses"]
    _check(all(np.isfinite(losses)), f"train[{attention}]: loss {losses}")
    _check(losses[-1] < losses[0],
           f"train[{attention}]: loss did not fall: {losses}")
    if attention == "xla" and len(devices) > 1:
        _on_every_device(params, devices, "parameters")
        _on_every_device(opt_state, devices, "optimizer state")
        # with the trained state live; the CPU backend reports no stats
        out["bytes_in_use"] = [(d.memory_stats() or {}).get("bytes_in_use")
                               for d in devices]
        one = model_mesh(devices[:1])
        here = float(jax.jit(tfm.make_loss_fn(cfg, mesh))(params, batch))
        there = float(jax.jit(tfm.make_loss_fn(cfg, one))(
            tfm.shard_params(cfg, one, params), np.asarray(batch)))
        _check(abs(here - there) <= LOSS_RTOL * abs(there),
               f"loss on {len(devices)} devices {here} vs on one {there}")
        out["loss_all_vs_one"] = (here, there)
    return out


def phase_flash(size: Size, devices) -> dict:
    """Flash forward and both backward kernels against the jnp path and its
    autodiff, at the model's attention shape, batch rows spread over the
    devices."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.parallel.attention import local_attention
    from ompi_tpu.parallel.mesh import make_mesh

    cfg = size.cfg
    mesh = make_mesh(devices=devices)
    shape = (size.batch, cfg.seq, cfg.n_heads, cfg.head_dim)
    rng = np.random.default_rng(2)
    q, k, v, g = (jax.device_put(
        jnp.asarray(rng.standard_normal(shape), jnp.bfloat16),
        NamedSharding(mesh, P("world"))) for _ in range(4))

    def out_and_grads(impl):
        return _out_and_grads(
            functools.partial(local_attention, causal=True, impl=impl),
            mesh, P("world"))

    ref = out_and_grads("jnp")(q, k, v, g)
    fn = out_and_grads("flash")
    kernels = _kernels(fn, q, k, v, g)
    got, run_s = _warm_timed(fn, q, k, v, g)
    errs = [_rel_err(a, b) for a, b in zip(got, ref)]
    _check(max(errs) < BF16_TOL,
           f"flash out/dq/dk/dv error {errs} at {shape}")
    return {"kernels": kernels, "errs": errs, "run_s": run_s}


def phase_decode(size: Size, devices) -> dict:
    import jax
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.models.decode import make_decoder
    from ompi_tpu.mpi.device_comm import DeviceCommunicator

    cfg = size.cfg
    mesh = model_mesh(devices)
    params = tfm.shard_params(cfg, mesh, _host_params(cfg))
    dec = make_decoder(cfg, mesh, max_new=size.new_tokens)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, size=(size.batch, size.prompt))
               .astype(np.int32) for _ in range(2)]
    answers = [np.asarray(dec(params, p)) for p in prompts]
    again, run_s = _timed(dec, params, prompts[0])
    for p, a in zip(prompts, answers):
        _check(a.shape == (size.batch, size.prompt + size.new_tokens),
               f"decode: answer shape {a.shape}")
        _check(np.array_equal(a[:, :size.prompt], p), "decode: prompt lost")
        _check(((a >= 0) & (a < cfg.vocab)).all(), "decode: token range")
    _check(np.array_equal(np.asarray(again), answers[0]),
           "decode: greedy output differs on a repeat call")
    logits = np.asarray(jax.jit(tfm.make_forward(cfg, mesh))(
        params, prompts[0])[:, -1, :])
    first = answers[0][:, size.prompt]
    picked = logits[np.arange(size.batch), first]
    _check((picked >= logits.max(axis=-1) - 1e-3 * logits.std()).all(),
           f"decode: first token {first} is not make_forward's argmax "
           f"{logits.argmax(axis=-1)}")
    out = {"run_s": run_s,
           "tokens_per_s": size.batch * size.new_tokens / run_s}
    if len(devices) > 1:
        # the cache is a value inside the decoder's one program; its
        # prefill is this pass of the shared backbone, heads over tp and
        # batch over dp
        comm = DeviceCommunicator(mesh, ("dp", "sp", "tp"))
        kv_spec = P(None, "dp", None, "tp", None)
        prefill = jax.jit(jax.shard_map(
            lambda p, t: tfm._local_backbone(cfg, comm, p, t,
                                             collect_kv=True)[1],
            mesh=mesh, in_specs=(tfm.param_specs(P, cfg, mesh),
                                 P("dp", "sp")),
            out_specs=(kv_spec, kv_spec), check_vma=False))
        _on_every_device(prefill(params, prompts[0]), devices, "KV cache")
    return out


def phase_mpi(size: Size, devices) -> dict:
    """The MPI surface on committed device arrays (coll/xla, driver mode)."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import ompi_tpu
    from ompi_tpu.mpi.device_comm import device_world
    from ompi_tpu.parallel.mesh import make_mesh

    n = len(devices)
    dc = device_world(make_mesh(devices=devices))
    host = _rank_rows(n, size.rows)
    comm = ompi_tpu.init()
    try:
        comm.bind_device(dc)
        x = jax.device_put(host, NamedSharding(dc.mesh, P("world")))
        summed, run_s = _warm_timed(comm.allreduce, x)
        _check((np.asarray(summed) == n * (n + 1) / 2).all(), "allreduce")
        gathered = np.asarray(comm.allgather(x))
        _check(gathered.shape == (n * n * size.rows, 128)
               and (gathered == np.tile(host, (n, 1))).all(), "allgather")
        _check((np.asarray(comm.bcast(x, root=n - 1)) == n).all(), "bcast")
    finally:
        comm.device = None
        ompi_tpu.finalize()
    return {"run_s": run_s}


def phase_dma(size: Size, devices) -> dict:
    """One-sided put and get.  On one chip only the self-put exists; on
    several, bytes cross the interconnect from device 0 to the last."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.mpi.device_comm import device_world
    from ompi_tpu.parallel.mesh import make_mesh

    n = len(devices)
    dc = device_world(make_mesh(devices=devices))
    src, dst = 0, n - 1
    sharded = NamedSharding(dc.mesh, P("world"))
    val = jax.device_put(_rank_rows(n, size.rows), sharded)
    win = jax.device_put(np.zeros((n * size.rows, 128), np.float32), sharded)

    def traced(fn, n_in):
        return jax.jit(jax.shard_map(
            fn, mesh=dc.mesh, in_specs=(P("world"),) * n_in,
            out_specs=P("world"), check_vma=False))

    put = traced(lambda w, v: dc.put(w, v, src, dst), 2)
    get = traced(lambda w: dc.get(w, src, dst), 1)
    kernels = _kernels(put, win, val) + _kernels(get, val)
    landed, run_s = _warm_timed(put, win, val)
    landed = np.asarray(landed).reshape(n, -1)
    _check((landed[dst] == src + 1).all(), f"put {src}->{dst}: wrong value")
    _check((np.delete(landed, dst, axis=0) == 0).all(),
           "put: a device other than the target was written")
    fetched = np.asarray(get(val)).reshape(n, -1)
    _check((fetched[dst] == src + 1).all(), f"get {src}->{dst}: wrong value")
    own = np.delete(np.arange(n, dtype=np.float32) + 1, dst)
    _check((np.delete(fetched, dst, axis=0) == own[:, None]).all(),
           "get: a device other than the origin changed")
    return {"kernels": kernels, "run_s": run_s, "src": src, "dst": dst}


def phase_ring(size: Size, devices, impl: str = "auto") -> dict:
    """One ring-attention step over sp = every device, forward and
    backward, against attention over the gathered K/V on the jnp path.
    ``impl`` as ``ring_attention`` takes it: on the chip the run asks for
    the kernels, whose k_offset is then a traced hop index."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.mpi.device_comm import DeviceCommunicator
    from ompi_tpu.parallel import attention as attn
    from ompi_tpu.parallel.mesh import make_mesh

    cfg, n = size.cfg, len(devices)
    mesh = make_mesh({"sp": n}, devices=devices)
    comm = DeviceCommunicator(mesh, ("sp",))
    t = size.ring_seq
    shape = (max(1, size.batch // 8), t * n, cfg.n_heads, cfg.head_dim)
    rng = np.random.default_rng(3)
    seq = P(None, "sp")
    q, k, v, g = (jax.device_put(
        jnp.asarray(rng.standard_normal(shape), jnp.bfloat16),
        NamedSharding(mesh, seq)) for _ in range(4))

    def gathered_jnp(q, k, v):
        k_all, v_all = (lax.all_gather(x, "sp", axis=1, tiled=True)
                        for x in (k, v))
        return attn.local_attention(q, k_all, v_all, causal=True,
                                    q_offset=lax.axis_index("sp") * t,
                                    impl="jnp")

    ring = _out_and_grads(
        lambda q, k, v: attn.ring_attention(comm, q, k, v, axis="sp",
                                            impl=impl),
        mesh, seq)
    kernels = _kernels(ring, q, k, v, g)
    got, run_s = _warm_timed(ring, q, k, v, g)
    ref = _out_and_grads(gathered_jnp, mesh, seq)(q, k, v, g)
    errs = [_rel_err(a, b) for a, b in zip(got, ref)]
    _check(max(errs) < BF16_TOL,
           f"ring sp={n} out/dq/dk/dv error {errs} at {shape}")
    hop = (shape[0], t, *shape[2:])     # what one device holds
    return {"kernels": kernels,
            "impl": attn.local_impl(impl, hop, hop, q.dtype,
                                    devices[0].platform),
            "errs": errs, "run_s": run_s, "sp": n}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def main() -> int:
    import importlib.metadata as md

    import jax

    from ompi_tpu.core import enable_compile_cache

    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    if d0.platform != "tpu":
        print(f"chip_smoke.py needs a TPU and JAX found {device}; it has "
              f"no CPU mode", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    print(f"device {device}; jax {jax.__version__}, jaxlib "
          f"{md.version('jaxlib')}, libtpu {md.version('libtpu')}, python "
          f"{sys.version.split()[0]}; compile cache {cache}", flush=True)

    def run(name, phase, *args, **kw):
        before = compile_totals()
        t0 = time.perf_counter()
        out = phase(FULL, devices, *args, **kw)
        since = {k: v - before[k] for k, v in compile_totals().items()}
        print(f"[{name}] wall {time.perf_counter() - t0:.1f}s, compile "
              f"{since['backend_s']:.1f}s (cache hits {since['cache_hits']}, "
              f"misses {since['cache_misses']}) {json.dumps(out)}",
              flush=True)
        return out

    train = run("train", phase_train)
    # the flagship's sequence is under the rule's 2048 keys: the jnp path
    _check(not train["kernels"], f"train holds {train['kernels']}")
    # twice the positions, the same tokens a step: the rule takes the
    # kernels, reached as a user reaches them, by shape.  The trainer, the
    # three kernels, the rotary kernel and the checkpoint policy that keeps
    # the forward kernel's results, together: the step holds all four and
    # starts from the loss the jnp path starts from (the same weights; at
    # random weights the loss does not know the length).  phase_flash holds
    # the kernels to the jnp path value for value.
    kern = run("train 2k", phase_train, attention="ulysses",
               seq=2 * FULL.cfg.seq)
    want = {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "rope"}
    _check(want <= set(kern["kernels"]),
           f"train 2k: the step holds {kern['kernels']}, not all of {want}")
    _check(np.isclose(kern["losses"][0], train["losses"][0], rtol=LOSS_RTOL),
           f"train 2k: first loss {kern['losses'][0]} vs "
           f"{train['losses'][0]} at {FULL.cfg.seq} positions")
    flash = run("flash kernels", phase_flash)
    compiled = {"train 2k": len(kern["kernels"]),
                "flash fwd, dq, dkv": flash["kernels"]}
    run("decode", phase_decode)
    run("mpi collectives", phase_mpi)
    compiled["put/get"] = run("put/get", phase_dma)["kernels"]
    if len(devices) > 1:
        ring = run("ring attention", phase_ring, impl="flash")
        compiled["ring attention"] = ring["kernels"]
        in_use = train["bytes_in_use"]
        _check(max(in_use) < 4 * min(in_use),
               f"trained state is not spread evenly: bytes_in_use {in_use}")
        print(f"peak_bytes_in_use "
              f"{[d.memory_stats()['peak_bytes_in_use'] for d in devices]}",
              flush=True)
    # no pallas kernel ran interpreted: each program that holds one lowered
    # it to a Mosaic custom call
    _check(all(n > 0 for n in compiled.values()),
           f"a pallas phase lowered no tpu_custom_call: {compiled}")
    print(f"compiled pallas kernels per program: {compiled}", flush=True)
    print(f"run {json.dumps(run_summary())}", flush=True)
    print(f"startup {json.dumps(startup_summary())}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
