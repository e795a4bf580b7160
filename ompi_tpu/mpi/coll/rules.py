"""Dynamic collective-selection rules file.

≈ ompi/mca/coll/tuned/coll_tuned_dynamic_file.c — the reference lets admins
override the fixed decision tables with a file of measured crossover points,
keyed by communicator size and message size.  Same idea here with a
line-oriented format (the reference's positional integer format is tied to
its enum numbering; ours names algorithms):

    # collective  comm_size_min  msg_bytes_min  algorithm
    allreduce     0              0              recursive_doubling
    allreduce     0              10240          ring
    allreduce     16             1048576        segmented_ring

For a lookup (collective, comm_size, msg_bytes) the matching rule with the
largest (comm_size_min, msg_bytes_min) wins — i.e. rules refine from generic
to specific exactly like the reference's nested comm-size → msg-size tables.
Returns None when no rule matches (fall through to the fixed decision).
"""

from __future__ import annotations

import os
from typing import Optional

__all__ = ["RuleSet", "load_rules", "decide",
           "SHM_ALLREDUCE", "SHM_ALLREDUCE_ALGORITHMS"]

#: rules-file collective key selecting the coll/shm arena allreduce
#: fold strategy (coll/shm.decide_allreduce_algo's ladder reads it) —
#: e.g. ``shm_allreduce 0 1048576 segment_parallel``
SHM_ALLREDUCE = "shm_allreduce"
SHM_ALLREDUCE_ALGORITHMS = ("root_fold", "segment_parallel")


class RuleSet:
    def __init__(self, rules: list[tuple[str, int, int, str]]) -> None:
        # rules: (collective, comm_size_min, msg_bytes_min, algorithm)
        self._by_coll: dict[str, list[tuple[int, int, str]]] = {}
        for coll, cmin, mmin, alg in rules:
            self._by_coll.setdefault(coll, []).append((cmin, mmin, alg))
        for lst in self._by_coll.values():
            lst.sort()

    def lookup(self, coll: str, comm_size: int,
               msg_bytes: int) -> Optional[str]:
        best: Optional[tuple[int, int, str]] = None
        for cmin, mmin, alg in self._by_coll.get(coll, ()):
            if cmin <= comm_size and mmin <= msg_bytes:
                if best is None or (cmin, mmin) >= best[:2]:
                    best = (cmin, mmin, alg)
        return best[2] if best else None

    def __len__(self) -> int:
        return sum(len(v) for v in self._by_coll.values())


def parse(text: str, source: str = "<string>") -> RuleSet:
    rules = []
    for lineno, line in enumerate(text.splitlines(), 1):
        line = line.split("#", 1)[0].strip()
        if not line:
            continue
        fields = line.split()
        if len(fields) != 4:
            from ompi_tpu.mpi.constants import MPIException

            raise MPIException(
                f"{source}:{lineno}: expected "
                f"'collective comm_size_min msg_bytes_min algorithm', "
                f"got {line!r}")
        coll, cmin, mmin, alg = fields
        try:
            rules.append((coll, int(cmin), int(mmin), alg))
        except ValueError as e:
            from ompi_tpu.mpi.constants import MPIException

            raise MPIException(f"{source}:{lineno}: {e}") from e
    return RuleSet(rules)


_cache: dict[str, tuple[float, RuleSet]] = {}


def load_rules(path: str) -> RuleSet:
    """Parse a rules file, cached by mtime."""
    mtime = os.stat(path).st_mtime
    hit = _cache.get(path)
    if hit is not None and hit[0] == mtime:
        return hit[1]
    with open(path, encoding="utf-8") as f:
        rs = parse(f.read(), source=path)
    _cache[path] = (mtime, rs)
    return rs


def decide(coll: str, comm_size: int, msg_bytes: int, forced: str = "",
           path: str = "", valid: Optional[tuple] = None,
           forced_src: str = "forced var",
           load=None) -> tuple[Optional[str], str]:
    """The selection ladder every decision layer repeats, factored
    once: forced config var > rules-file hit > ``(None, "fixed")``
    (the caller applies its fixed default).  ``valid`` is the
    validation universe (None skips validation; an EMPTY tuple means
    nothing is valid, so any forced name raises — user tuning must
    fail loudly, not silently fall through).  ``forced_src`` labels
    the forced rung in traces/errors; ``load`` substitutes the
    caller's RuleSet cache for :func:`load_rules` (HostColl keeps its
    lock-guarded component cache).  Returns
    ``(algorithm | None, source)``."""
    if forced:
        alg: Optional[str] = forced
        src = forced_src
    elif path:
        alg = (load or load_rules)(path).lookup(coll, comm_size,
                                                msg_bytes)
        src = f"rules file {path}"
        if alg is None:
            return None, "fixed"
    else:
        return None, "fixed"
    if valid is not None and alg not in valid:
        from ompi_tpu.mpi.constants import MPIException

        raise MPIException(
            f"unknown {coll} algorithm {alg!r} (from {src}); "
            f"valid: {', '.join(valid)}")
    return alg, src
