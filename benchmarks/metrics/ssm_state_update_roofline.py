"""Share of its roofline that the cached steps' state update reaches, in a
model only some of whose layers hold a state: ``ssm_update_roofline.py``'s
metric (the same ``costs``, the same scope, ``ssm.update`` of the cached
steps, so it reads the same work whatever later implements the update),
counted over the layers that hold a state as the configuration's reference
gives them (``ssm_update(shape)``: the ``M`` layers of a plan, and a state's
heads, head width, size and carried type) and not over
``num_hidden_layers``, which would count 14 layers where 6 hold a state and
read 14/6 of the truth.

A run whose reference names no such state, or whose trace has no time under
the scope, reads as nothing.
"""

import os

HERE = os.path.dirname(os.path.abspath(__file__))
JOBS = "first"      # the job that is one run of one program


def read(run):
    from benchmarks.lib import cells, program, scopes

    if run.scopes is None or run.peaks is None or not run.config:
        return None
    ref = program.reference(run.config, os.path.dirname(HERE))
    if not hasattr(ref, "ssm_update"):
        return None
    base = cells.load_module(os.path.join(HERE, "ssm_update_roofline.py"))
    took = scopes.seconds(run.scopes_under(base.SPAN), base.KEYS)
    # a sample is one ``first`` job and one ``full`` job; ``first`` is one
    # program run, and ``full`` is two where the prefill is a program of its
    # own (``models/decode._two_programs``), so the jobs are counted there
    jobs = (run.scopes_under(JOBS) or {}).get("executions")
    if not took or not jobs:
        scopes.warn_missing("ssm_state_update_roofline", base.KEYS,
                            where=f" in the runs under the host span "
                                  f"{base.SPAN!r}")
        return None
    state = ref.ssm_update(ref.Shape.from_config(run.config))
    steps = jobs * (run.facts["max_new"] - 1)
    least = steps * base.least_seconds(
        run.facts["batch"], state["layers"],
        state["heads"] * state["head_dim"], state["d_state"],
        state["itemsize"], run.peaks)
    return 100.0 * least / took
