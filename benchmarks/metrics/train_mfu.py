"""Model FLOP/s utilization of training: tokens per second of the loop times
the operations forward and backward need per token (``lib/costs.py``,
recomputation excluded) over chips times the published bf16 peak.  Not a
kernel's roofline share."""


def read(run):
    sample = run.median("sample")
    if sample is None or run.peaks is None:
        return None
    f = run.facts
    achieved = f["tokens_per_sample"] / sample * f["flops_per_token"]
    return 100.0 * achieved / (f["chips"] * run.peaks["bf16_flops"])
