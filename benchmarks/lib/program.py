"""From a configuration file to the program's own objects.

The file's ``entry`` names the program's callables by dotted path and maps
the published size keys onto the arguments of its configuration class, so a
model of another family that keeps these call signatures is a new file and
not new code.
"""

from __future__ import annotations

import importlib
import os

from benchmarks.lib import cells


def import_dotted(path: str):
    module, _, attr = path.rpartition(".")
    return getattr(importlib.import_module(module), attr)


def tiny(config: dict) -> dict:
    """The configuration at its ``tiny`` sizes: for tests on the CPU and for
    learning the tree of an optimizer state, never for a measurement."""
    return {**config, **config["tiny"]}


def program_config(config: dict):
    entry = config["entry"]
    sizes = {arg: config[key] for arg, key in entry["sizes"].items()}
    return import_dotted(entry["config"])(**sizes, **entry["options"])


def reference(config: dict, bench_dir: str = cells.BENCH_DIR):
    """The configuration's plain reference, ``reference/<name>.py`` under
    the benchmark directory the cell was resolved in: found by file, as
    runners and readers are, so a model of another family brings its own.

    A reference module has ``Shape.from_config(config)`` (with ``vocab``,
    ``d_model`` and ``n_layers``), ``param_init(shape, serving=False)``
    (``param_table`` below), ``logits``, ``loss``, ``token_deficits`` and
    ``counts(shape)``, which says what ``lib/costs.py`` may count of this
    family (``counts`` below has the keys).

    Keys a configuration file may carry beside its published ones and those
    every file has (``entry``, ``mesh``, ``reference``, ``tiny``, ...):
    ``check``, which a configuration that a cell decodes must give: the
    limits its decoder is held to, ``{"limit": x, "why": "..."}`` each; and
    ``entry.decoder_logits``, the keyword of ``entry.decoder`` that makes it
    hand back the logits of the first n sequences, whose limits ``check``
    then holds (the runner of a decode mix says in its docstring what a
    decoder may return, which limits it reads and what is asked)."""
    return cells.load_module(os.path.join(bench_dir, "reference",
                                          config["reference"] + ".py"))


def counts(ref, shape) -> dict:
    """``ref.counts(shape)`` with the keys a reference may leave out at
    their defaults; what the runners' ``facts()`` count from and carry.

    Every reference gives ``active_params`` (what one token multiplies; a
    lookup table that is not the projection counts nothing),
    ``projection_params`` (the output projection alone) and ``kv_elements``
    (one position's keys and values in one attending layer).  One whose
    layers differ may also give ``attention_layers`` (how many layers
    attend; default every layer), ``attention_width`` (the summed width of
    the query heads; default ``d_model``), ``state_elements`` (what one
    sequence holds, over all layers, of fixed-size state that a cached step
    reads, counted at ``kv_cache_dtype``; default 0), ``lookup_params`` (the
    elements of a table that a step only looks rows up in, an embedding that
    is not also the projection: a cached step's bytes leave it out; default
    0) and ``routed`` (``{"layers", "experts", "top_k", "d_model",
    "d_expert"}``: the routed layers' own shape, for the readers of their
    kernels; no default)."""
    return {"attention_layers": shape.n_layers,
            "attention_width": shape.d_model, "state_elements": 0,
            "lookup_params": 0, **ref.counts(shape)}


def mesh(config: dict, devices):
    from ompi_tpu.parallel.mesh import make_mesh

    return make_mesh(dict(config["mesh"]), devices=list(devices))


def param_shardings(config: dict, cfg, on_mesh) -> dict:
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    specs = import_dotted(config["entry"]["param_specs"])(P, cfg, on_mesh)
    return {name: NamedSharding(on_mesh, spec) for name, spec in specs.items()}


def param_table(ref, config: dict, serving: bool = False) -> dict:
    """Leaf name -> (shape, standard deviation or None), as the reference
    ``ref`` lays out the parameters of ``config``.  ``serving`` asks for the
    deviations the reference declares for greedy decoding (a decode runner
    does; a reference whose draw serves both gives one table): the shapes
    are the same either way."""
    return ref.param_init(ref.Shape.from_config(config), serving=serving)


def abstract_params(ref, config: dict, shardings: dict) -> dict:
    """The parameter tree as shapes, for compiling without arrays."""
    import jax

    return {name: jax.ShapeDtypeStruct(dims, config["param_dtype"],
                                       sharding=shardings[name])
            for name, (dims, _std) in param_table(ref, config).items()}


def init_params(ref, config: dict, shardings: dict, seed: int,
                serving: bool = False) -> dict:
    """Seeded random parameters made on the devices, already sharded, in one
    jitted call and in the type they are stored in: no host array and no
    transfer.  Leaf ``i`` in name order draws from ``fold_in(key(seed), i)``;
    with JAX's partitionable threefry the values do not depend on the mesh.
    ``serving``: at the deviations ``param_table`` gives for serving."""
    import jax
    import jax.numpy as jnp

    table = param_table(ref, config, serving)
    dtype = jnp.dtype(config["param_dtype"])

    def make(key):
        out = {}
        for i, (name, (dims, std)) in enumerate(sorted(table.items())):
            if std is None:
                out[name] = jnp.ones(dims, dtype)
            else:
                draw = jax.random.normal(jax.random.fold_in(key, i), dims,
                                         jnp.float32)
                out[name] = (draw * std).astype(dtype)
        return out

    return jax.jit(make, out_shardings=shardings)(jax.random.key(seed))
