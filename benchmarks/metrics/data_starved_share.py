"""Share of the takes from ``models.data.train_stream`` since warm-up that
found the prefetch queue empty (the stream's own ``stats()``, carried by the
runner's ``facts()``): above zero, the host sets the pace."""


def read(run):
    stream = run.facts.get("stream")
    if not stream or not stream.get("batches"):
        return None
    return 100.0 * stream["starved"] / stream["batches"]
