"""Model FLOP/s utilization of the ``first`` job: the forward over every
prompt plus one token each (``lib/costs.prefill_flops``) over its median
time and the published bf16 peak.  The time holds dispatch and readback."""


def read(run):
    first = run.median("first")
    if first is None or run.peaks is None:
        return None
    return (100.0 * run.facts["prefill_flops"] / first
            / (run.facts["chips"] * run.peaks["bf16_flops"]))
