"""Mean milliseconds a take from ``models.data.train_stream`` spent waiting
for the prefetch queue since warm-up, by the stream's own clock inside
``__next__`` (``stats()["wait_s"]`` over ``stats()["batches"]``, carried by
the runner's ``facts()``).  ``data_wait_ms`` is the same wait timed from
outside, as a median."""


def read(run):
    stream = run.facts.get("stream")
    if not stream or not stream.get("batches") or "wait_s" not in stream:
        return None
    return 1e3 * stream["wait_s"] / stream["batches"]
