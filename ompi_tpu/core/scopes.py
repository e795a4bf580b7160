"""The names inside the device programs.

Every boundary the performance records talk about is a ``jax.named_scope``
from the one vocabulary below.  ``SCOPES`` is the only copy of it: the
reader of a profile, ``benchmarks/lib/scopes.py``, keeps no list of its own
and looks a name up in this tuple when it classifies an operation, so a name
added here is a scope there, a key of its table and a line of a traced run's
``breakdown.device_scopes``, with no edit under ``benchmarks/``; a metric
that reads it is a data file that names its keys (``PERF.md`` section 3 says
which metric reads which name).  A profile of any program built on this
package is thus read by layer instead of by ``fusion.461``.  A scope is HLO
metadata (``op_name``): it changes no computation and costs nothing when
the program runs.  JAX's own name stack already tells forward (``jvp``),
backward (``transpose``) and recomputation (``rematted_computation``)
apart, so none of them is a scope.

One caution: JAX's persistent compilation cache leaves metadata out of its
key.  A program that differs from a cached one only in where its scopes sit
is served the cached executable with the old names; use a fresh
``JAX_COMPILATION_CACHE_DIR`` after moving a scope.
"""

from __future__ import annotations

__all__ = ["SCOPES", "COLL", "scope", "coll"]

# plain lower-case words; a dot says which scope a name belongs under
SCOPES = (
    "embed",            # token lookup
    "layers",           # the loop over layers, its slicing and stacking
    "attn_proj",        # ln1, q/k/v projections, rope, the wo projection
    "attention",        # scores, mask, softmax, context
    "attention.ring",   # ... K/V blocks around the sp ring
    "attention.ulysses",    # ... resharded seq -> heads by all_to_all
    "attention.flash",  # ... the pallas kernels
    "ffn",              # ln2, the MLP, the residual
    "moe.route", "moe.dispatch", "moe.experts", "moe.combine",
    "ssm_proj",         # the mixer's scalings, in/out projections, gated norm
    "ssm.conv",         # ... its causal convolution (from a state, when cached)
    "ssm.scan",         # ... the chunked scan over a whole sequence
    "ssm.update",       # ... one cached step's recurrence: from reading the
                        # layer's state out of the carry to writing it back
    "loss",             # the unembed matmul and the cross entropy
    "optimizer",        # the optimizer's update and its application
    "prefill",          # backbone over the prompt, cache padding, first logits
    "decode.step",      # one cached token for the whole batch
    "kv_cache",         # ... writing the new K/V into the cache
    "unembed",          # ... the vocabulary matmul
    "sample",           # ... picking the next token
)

# ``coll.<method>.<axes>``: one collective call site, named by the
# DeviceCommunicator method (or its lax equivalent's method) and the mesh
# axes it runs over, joined by "-"
COLL = "coll"


def scope(name: str):
    """``with scope("attention"): ...`` around traced code."""
    import jax

    if name not in SCOPES:
        raise ValueError(f"{name!r} is not in the scope vocabulary {SCOPES}")
    return jax.named_scope(name)


def coll(method: str, axes):
    """``with coll("allreduce", "tp"): lax.psum(x, "tp")``."""
    import jax

    names = (axes,) if isinstance(axes, str) else tuple(axes)
    return jax.named_scope(f"{COLL}.{method}.{'-'.join(names)}")
