"""Share of a memory roofline that a prefill's selective scans reach: the
least time the chip could take to move what a prompt's scans must move, over
the device time under the scope ``ssm.scan`` of the prefill that ``ttft_ms``
times, whatever implements the scan.

What must move (``cost_bytes``): for every sequence, position and scanning
layer the scan reads ``x`` and ``dt`` (``d_inner`` each) and ``B`` and ``C``
(``d_state`` each) and writes ``y`` (``d_inner``), at four bytes (the scan's
operands are float32); the state stays on the chip and its ``d_inner x
d_state`` updates a position count nothing here.  That work, about six
operations and one exponential a (channel, state element) and position, is
the **vector units'**, for which the chip publishes no peak: so the share is
of the memory roofline alone, reads low by design (the scan is bound by the
vector units, sixteen state elements a channel for every three words moved),
and cannot pass 100% while the scan runs under that scope.  It says how far a
scan is from the point where only a narrower operand type would help.

A run whose reference names no selective scan, or whose trace has no time
under the scope, reads as nothing.
"""

KEYS = ["scope/ssm.scan@prefill"]
SPAN = "first"      # one run of the prefill's program


def cost_bytes(batch: int, positions: int, layers: int, d_inner: int,
               d_state: int, itemsize: int = 4) -> float:
    """Bytes the scans of one prefill must move."""
    return (batch * positions * layers
            * (3 * d_inner + 2 * d_state) * itemsize)


def read(run):
    from benchmarks.lib import program, scopes

    config = run.config or {}
    if run.scopes is None or run.peaks is None or "reference" not in config:
        return None
    ref = program.reference(config)
    if not hasattr(ref, "selective_scan"):
        return None
    table = run.scopes_under(SPAN)
    took = scopes.seconds(table, KEYS)
    if not took or not table["executions"]:
        scopes.warn_missing("selective_scan_roofline", KEYS,
                            where=f" in the runs under the host span "
                                  f"{SPAN!r}")
        return None
    facts = run.facts
    shape = ref.selective_scan(ref.Shape.from_config(config))
    least = table["executions"] * cost_bytes(
        facts["batch"], facts["prompt_len"], shape["layers"],
        shape["d_inner"], shape["d_state"]) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / took
