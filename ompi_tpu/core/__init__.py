"""Core substrate (≈ the reference's OPAL layer, opal/).

Single-process portability and plumbing: the component/plugin registry
(``mca``), the typed configuration-variable registry (``config``), structured
logging and aggregated user diagnostics (``output``), control-message
serialization (``dss``), and the buffer-location abstraction (``buffer``)
that threads device/host duality through the whole stack the way the
reference threads its CUDA convertor flag (opal/datatype/opal_convertor.h:43-59).
"""

import os as _os

__all__ = ["pkg_root", "enable_compile_cache"]


def pkg_root() -> str:
    """Directory CONTAINING the ompi_tpu package — what a child process
    needs on PYTHONPATH to import this framework (≈ plm_rsh prefixing its
    install dirs, plm_rsh_module.c).  One definition so local and remote
    launch paths cannot drift."""
    return _os.path.dirname(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Every entry point that compiles device programs calls this before its
    first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    follows it and no directory is set here.  Otherwise the cache lives at
    the fixed ``<checkout>/.jax_cache``: the path is part of the cache key,
    so it must not move between runs.  Programs that compile in under a
    second are cached too, so a second run of the same command compiles
    nothing.
    """
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = _os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _os.path.join(pkg_root(), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
