"""What the tests that are parametrised over the decode cells ask a cell
before they ask anything else, and the dry cell they rehearse on.

A decode cell is compared on what its decoder hands back: tokens alone, or
tokens and the logits they were picked from (``entry.decoder_logits``;
``benchmarks/runners/decode.py`` says which limits each kind is held to).
``hands_back`` says which, and ``routed`` whether the configuration routes
its tokens, by the reference's ``counts`` and not by the name of a key.  So
a test written for "every decode cell" reads a cell of either kind, and a
configuration that names ``decoder_logits`` passes tier-1 as it is added.

``add_logits_cell`` adds such a cell to a copy of the benchmark, by files and
rows alone: a configuration the benchmark has, behind ``logits_decoder``,
held to the three limits for logits and to no limit for tokens.  The tests
run every assertion they make of the benchmark's own decode cells on it too.
From the root of a checkout,

    python3 -m tests.benchmarks.decode_cells DIR WORKLOAD

makes that copy under ``DIR`` at the configuration's real sizes and prints
the arguments that ``benchmarks/controls.py`` reads it with, on the chip.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import shutil
import sys

import numpy as np

from benchmarks.lib import cells, program

KEYWORD = "keep_logits"
LOGITS_FAMILY, LOGITS_CELL, LOGITS_MIX = ("logits-family", "logits-cell",
                                          "logits-mix")
# the keys of ``check`` that each kind of decoder is held to
TOKEN_KEYS = ("deficit_max", "mismatch_share")
LOGIT_KEYS = ("logit_err_median", "logit_err_position", "positions_over")
KEYS = {"tokens": TOKEN_KEYS, "logits": LOGIT_KEYS}

# what the dry addition gives as its ``check``: in float32 at tiny sizes the
# program's own forward sits within 1e-5 of the reference's
LOGIT_CHECK = {
    "logit_err_median": {"limit": 0.01, "why": "float32 on both sides"},
    "logit_err_position": {"limit": 0.05, "why": "float32 on both sides"},
    "positions_over": {"limit": 0.1, "why": "room for one position in ten"},
}


def hands_back(cell: cells.Cell) -> str:
    """``"logits"`` where the cell's configuration names the keyword that
    makes its decoder hand its logits back, else ``"tokens"``."""
    return "logits" if "decoder_logits" in cell.config["entry"] else "tokens"


def limit_keys(cell: cells.Cell) -> tuple[str, ...]:
    """The keys of ``check`` that the cell's kind is held to."""
    return KEYS[hands_back(cell)]


def routed(cell: cells.Cell, bench_dir: str = cells.BENCH_DIR):
    """The routed layers' shape as the cell's reference counts it, or None
    where no layer routes: whatever the configuration calls its keys."""
    ref = program.reference(cell.config, bench_dir)
    shape = ref.Shape.from_config(cell.config)
    return program.counts(ref, shape).get("routed")


# ---- a trace that is told it is for TPUs ------------------------------------

# the program's rule, by the name it has in whichever module holds it
TRACED_FOR_TPUS = "_traced_for_tpus"


def tell_it_is_traced_for_tpus(monkeypatch) -> None:
    """Every kind's mixer asks one function of the program whether its trace
    is under a mesh of TPUs before it takes a kernel.  Patched to say yes in
    every module of ``ompi_tpu.models`` and ``ompi_tpu.ops`` that defines it
    or has imported it, so a test says what it means and not where the
    function lives today: the program may move it among those modules."""
    import importlib
    import pkgutil

    patched = 0
    for package in ("ompi_tpu.models", "ompi_tpu.ops"):
        paths = importlib.import_module(package).__path__
        for info in pkgutil.iter_modules(paths, package + "."):
            module = importlib.import_module(info.name)
            if hasattr(module, TRACED_FOR_TPUS):
                monkeypatch.setattr(module, TRACED_FOR_TPUS, lambda: True)
                patched += 1
    assert patched, f"no module of the program has {TRACED_FOR_TPUS}"


# ---- a copy of the benchmark, and what was added to it ----------------------

def digest(top) -> dict[str, str]:
    """relative path -> sha256 of every file under ``top``."""
    out = {}
    for folder, _dirs, files in os.walk(top):
        for f in files:
            if f.endswith(".pyc"):
                continue
            path = os.path.join(folder, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def copied_benchmark(root) -> tuple[str, dict]:
    """The benchmark's directory copied under ``root``, and its digest."""
    bench_dir = os.path.join(str(root), "benchmarks")
    shutil.copytree(cells.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    return bench_dir, digest(bench_dir)


def add_logits_cell(root, bench_dir: str, base: str,
                    decoder: str = "logits_decoder", check=LOGIT_CHECK,
                    tiny: bool = False, traffic: dict | None = None) -> str:
    """To the copied benchmark under ``root``, by files and rows alone: the
    configuration of the decode cell ``base`` (at tiny sizes and float32
    where ``tiny``) with ``entry.decoder`` this file's factory ``decoder``,
    ``entry.decoder_logits`` its keyword, and of ``check`` the three limits
    for logits alone (no ``check`` where None); and a cell of it on ``base``'s
    own mix, or on ``traffic`` as a mix of its own.  Reports what ``base``
    reports.  Returns the cell's name."""
    bench = cells.load_benchmark()
    resolved = cells.resolve(base)
    config = json.loads(json.dumps(
        program.tiny(resolved.config) if tiny else resolved.config))
    config["name"] = LOGITS_FAMILY
    # this module's import path, also where it runs as ``__main__``
    config["entry"]["decoder"] = f"{__spec__.name}.{decoder}"
    config["entry"]["decoder_logits"] = KEYWORD
    if tiny:
        config["entry"]["options"]["compute_dtype"] = "float32"
    config.pop("check")     # the base's own, for tokens
    if check is not None:
        config["check"] = check
    file = f"configs/{LOGITS_FAMILY}.json"
    with open(os.path.join(bench_dir, file), "w", encoding="utf-8") as f:
        json.dump(config, f)
    row = next(w for w in bench["workloads"] if w["name"] == base)
    mix = row["traffic"]
    if traffic is not None:
        mix = LOGITS_MIX
        with open(os.path.join(bench_dir, "traffic", mix + ".json"), "w",
                  encoding="utf-8") as f:
            json.dump({**resolved.traffic, **traffic}, f)
    of_base = next(c for c in bench["configs"] if c["name"] == row["config"])
    bench["configs"].append({**of_base, "name": LOGITS_FAMILY,
                             "file": f"benchmarks/{file}"})
    bench["workloads"].append({"name": LOGITS_CELL, "config": LOGITS_FAMILY,
                               "traffic": mix, "chips": 1,
                               "why": "dry addition"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if base in m.get("workloads", []):
            m["workloads"].append(LOGITS_CELL)
    with open(os.path.join(str(root), "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(bench, f)
    return LOGITS_CELL


# ---- the dry cell's decoder --------------------------------------------------

def faulty_logits(fault: str, z: np.ndarray, picked: np.ndarray) -> np.ndarray:
    """``z`` (n, N, V) wrong in the way ``fault`` says, the token ``picked``
    (n, N) still leading at every position.  ``shifted``: every position, a
    tenth of a deviation; ``spiky``: one position in twenty, three."""
    z, rng = np.array(z, np.float32), np.random.default_rng(0)
    if fault == "shifted":
        z += 0.1 * z.std(-1, keepdims=True) * rng.normal(size=z.shape)
    elif fault == "spiky":
        for n, t in zip(*np.nonzero(np.arange(z[..., 0].size).reshape(
                z.shape[:2]) % 20 == 7)):
            z[n, t] += 3 * z[n, t].std() * rng.normal(size=z.shape[-1])
    else:
        raise ValueError(f"no fault {fault!r} of logits")
    np.put_along_axis(z, np.asarray(picked)[..., None],
                      z.max(-1, keepdims=True) + 1e-3, -1)
    return z


def logits_decoder(cfg, mesh, max_new, keep_logits=0, fault=""):
    """``entry.decoder`` of the dry addition: the program's decoder, wrapped
    so that with ``keep_logits=n`` it also returns float32 ``(n, max_new,
    vocab)`` logits, made with the program's own full forward over what it
    generated (not by its cached step), and with ``fault`` wrong in the way
    the name says."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.models.decode import make_decoder

    decode = make_decoder(cfg, mesh, max_new=max_new)
    if not keep_logits:
        return decode
    forward = jax.jit(tfm.make_forward(cfg, mesh))

    def run(params, prompts):
        tokens = decode(params, prompts)
        start = prompts.shape[1]
        z = np.array(forward(params, tokens[:keep_logits])[:, start - 1:-1],
                     np.float32)
        if fault in ("shifted", "spiky"):
            z = faulty_logits(fault, z, tokens[:keep_logits, start:])
        if fault == "wrong_token":
            tokens = tokens.at[0, start + 2].set(
                (tokens[0, start + 2] + 1) % cfg.vocab)
        return tokens, jnp.asarray(z)

    return run


# ``entry.decoder`` is a dotted path, so each fault has a name of its own
logits_decoder_shifted = functools.partial(logits_decoder, fault="shifted")
logits_decoder_spiky = functools.partial(logits_decoder, fault="spiky")
logits_decoder_wrong_token = functools.partial(logits_decoder,
                                               fault="wrong_token")


# ---- a run with the timed path broken underneath ----------------------------

# ``entry.decoder`` of the cell whose decoder the two below break: a test
# sets it (``monkeypatch.setattr``) beside the configuration's ``entry.decoder``
BROKEN_INNER = "ompi_tpu.models.decode.make_decoder"


def _split(out):
    """A job's tokens, and whatever the decoder handed back beside them."""
    return (out[0], tuple(out[1:])) if isinstance(out, tuple) else (out, ())


def _joined(tokens, rest):
    return (tokens, *rest) if rest else tokens


def decoder_that_alters_a_token(cfg, mesh, max_new, **kwargs):
    """The cell's decoder with one generated token of every sequence altered
    where it is produced; logits, where it hands them back, as they were."""
    decode = program.import_dotted(BROKEN_INNER)(cfg, mesh, max_new=max_new,
                                                 **kwargs)

    def altered(params, prompts):
        tokens, rest = _split(decode(params, prompts))
        at = prompts.shape[1] + max_new // 2
        return _joined(
            tokens.at[:, at].set((tokens[:, at] + 1) % cfg.vocab), rest)

    return altered


def decoder_that_forgets_its_cache(cfg, mesh, max_new, **kwargs):
    """Every generated token decoded from the last four tokens alone: a
    cached step that leaves out the rest of its context.  Logits, where the
    decoder hands them back, are those the four tokens gave."""
    import jax.numpy as jnp

    decode = program.import_dotted(BROKEN_INNER)(cfg, mesh, max_new=1,
                                                 **kwargs)

    def forgetful(params, prompts):
        tokens, kept = prompts, []
        for _ in range(max_new):
            last, rest = _split(decode(params, tokens[:, -4:]))
            tokens = jnp.concatenate([tokens, last[:, -1:]], axis=1)
            kept.append(rest)
        return _joined(tokens, tuple(jnp.concatenate(parts, axis=1)
                                     for parts in zip(*kept)))

    return forgetful


def main(argv: list[str]) -> int:
    root, base = argv
    os.makedirs(root, exist_ok=True)
    bench_dir, _before = copied_benchmark(root)
    cell = add_logits_cell(root, bench_dir, base)
    print(f"--bench-dir {bench_dir} --workload {cell}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
