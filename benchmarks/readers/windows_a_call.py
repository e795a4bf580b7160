"""Shared reader: the windows a routed call runs, from a traced run's
kernel events.

``parallel/moe.routed_moe`` routes a call's tokens once (one ``top_k`` under
``moe.route``) and then lays out, multiplies and sums a window of the picks
this device holds at a time: one window where they fit it, which is nearly
always, another for every overflow; where it takes the whole layout, that is
the one window.  Every window it runs calls the pallas kernel
``grouped_matmul`` once a matrix of an expert (``"matrices"``: three where
the experts are gated).  So, over the program runs whose operations lie
under the scope ``"root"`` (``prefill``, ``decode.step``):

    windows a call = kernel events / matrices / top_k sorts

``metrics/<metric>.json`` gives ``{"reader": "windows_a_call", "root":
"prefill", "matrices": 3}``.  ``layouts(run.events, root)`` says, besides,
how many kernel events there were at each layout's rows (the first
dimension of the event's result: ``bf16[2304,2048]`` is a window of 1792
rows beside 16 held experts' tiles of 32), which tells a window from the
whole layout.  A trace without such events (a program with no routed layer,
a run without a trace) reads as nothing."""

import collections
import re

KERNEL = re.compile(r"^%?grouped_matmul(\.\d+)? = \w+\[(\d+),")
ROUTED = re.compile(r"^%?sort(\.\d+)? ")


def _under(event, root: str) -> bool:
    return root in event.scope.split("/")


def layouts(events, root: str) -> dict[int, int]:
    """Kernel events under ``root`` by the rows of the layout they took."""
    found = collections.Counter()
    for e in events:
        named = KERNEL.match(e.name)
        if named and _under(e, root):
            found[int(named.group(2))] += 1
    return dict(found)


def read(run, spec):
    if run.trace is None:
        return None
    root = spec["root"]
    kernels = sum(layouts(run.events, root).values())
    calls = sum(1 for e in run.events if ROUTED.match(e.name)
                and _under(e, root) and "moe.route" in e.scope)
    if not kernels or not calls:
        return None
    return kernels / spec["matrices"] / calls
