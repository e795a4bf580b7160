"""ompi-tpu-info — dump frameworks, components, and config variables.

≈ ompi/tools/ompi_info: the introspection tool that lists every registered
framework, its components (with priorities), and every config variable with
its current value and source.
"""

from __future__ import annotations

import argparse
import importlib
import sys

from ompi_tpu.core.config import InfoLevel, var_registry
from ompi_tpu.core.mca import framework_registry

# Modules whose import registers frameworks/components/vars. Import errors are
# tolerated (e.g. jax-dependent modules on a host without accelerators).
_REGISTERING_MODULES = [
    "ompi_tpu.runtime.ras",
    "ompi_tpu.runtime.rmaps",
    "ompi_tpu.runtime.errmgr",
    "ompi_tpu.runtime.launcher",
    "ompi_tpu.runtime.notifier",
    "ompi_tpu.runtime.rtc",
    "ompi_tpu.runtime.plm",
    "ompi_tpu.runtime.metrics",       # metrics_agg_* fan-in valve vars
    "ompi_tpu.runtime.doctor",        # doctor_* capture-budget vars
    "ompi_tpu.mpi.coll",
    "ompi_tpu.mpi.coll.host",
    "ompi_tpu.mpi.coll.selfcoll",
    "ompi_tpu.mpi.coll.shm",
    "ompi_tpu.mpi.coll.xla",
    "ompi_tpu.mpi.pml",
    "ompi_tpu.mpi.op",
    "ompi_tpu.mpi.io",
    "ompi_tpu.mpi.btl_shm",
    "ompi_tpu.core.memchecker",
    "ompi_tpu.parallel.multihost",
    "ompi_tpu.shmem.api",
]


def load_all() -> list[str]:
    failures = []
    for mod in _REGISTERING_MODULES:
        try:
            importlib.import_module(mod)
        except Exception as e:
            failures.append(f"{mod}: {type(e).__name__}: {e}")
    return failures


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(prog="ompi-tpu-info")
    p.add_argument("--level", type=int, default=9,
                   help="max info level to show (1=user basic .. 9=dev all)")
    p.add_argument("--param", default=None,
                   help="show only variables whose name contains this string")
    args = p.parse_args(argv)

    failures = load_all()
    import ompi_tpu

    print(f"ompi_tpu version: {ompi_tpu.__version__}")
    print()
    print("Frameworks and components:")
    for name, fw in sorted(framework_registry.all().items()):
        comps = ", ".join(
            f"{c.NAME}(pri={c.PRIORITY})"
            for c in sorted(fw.components().values(), key=lambda c: -c.PRIORITY))
        print(f"  {name:<12} {fw.description or ''}")
        print(f"  {'':<12}   components: {comps or '(none)'}")
    print()
    print("Configuration variables (name = value [type, source]):")
    for var in var_registry.all_vars():
        if var.info_level > args.level:
            continue
        if args.param and args.param not in var.full_name:
            continue
        print(f"  {var.full_name} = {var.value!r} "
              f"[{var.vtype.value}, {var.source.name.lower()}]"
              + (f"  # {var.description}" if var.description else ""))
    from ompi_tpu.mpi.mpit import pvar_registry

    names = pvar_registry.names()
    if names:
        print()
        print("Performance variables (MPI_T pvars):")
        for n in names:
            pv = pvar_registry.lookup(n)
            print(f"  {n} [{pv.klass.value}"
                  + (f", {pv.unit}" if pv.unit else "") + "]"
                  + (f"  # {pv.description}" if pv.description else ""))
    if failures:
        print("\nmodules not loaded:", file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
