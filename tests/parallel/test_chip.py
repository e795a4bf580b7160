"""``ompi_tpu/ops/_chip.py``: the one question every choice of a kernel asks
("is this traced for TPUs") and the one home it has.

A cell's own programs are traced here at the cell's real sizes on this box's
CPU devices (``jax.make_jaxpr``: shapes alone, nothing is compiled and
nothing runs) with the one home patched to yes and to no, and each choosing
site is held to its kernel's name in, or out of, the program.  Agreement and
control flow only: nothing here is a time.
"""

import functools
import os
import re
import subprocess
import sys

import jax
import pytest
from jax.sharding import PartitionSpec as P

from ompi_tpu.ops import _chip

from tests.parallel.compiled import _pallas_calls

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MODELS = os.path.join(ROOT, "ompi_tpu", "models")

# site -> (the cell whose program reaches it, the kernel it takes on TPUs)
SITES = {
    "kda-step": ("kimi-linear-48b-a3b.decode-512-128-b384", "kda_update"),
    "ssm-step": ("granite-4.0-h-small.decode-512-128-b160", "ssm_update"),
    "ssm-prefill": ("nemotron-3-nano-30b-a3b.decode-1k-128-b256", "ssm_scan"),
    "latent-prefill": ("kimi-vl-a3b.decode-16k-256-b32", "latent_attention"),
    "latent-decode": ("kimi-vl-a3b.decode-16k-256-b32", "latent_decode"),
    "latent-indexed-prefill": ("deepseek-v3.2-exp.decode-16k-512-b8",
                               "masked_latent_attention"),
    "retention-prefill": ("brumby-14b-base.decode-2k-128-b48",
                          "retention_prefill"),
    "retention-step": ("brumby-14b-base.decode-2k-128-b48",
                       "retention_update"),
    "block-select-prefill": ("minicpm-sala.decode-16k-512-b24",
                             "masked_attention"),
    "block-select-step": ("minicpm-sala.decode-16k-512-b24",
                          "selected_attention"),
    # the two that asked a concrete mesh until PR 75 (a patch of
    # ``kda._traced_for_tpus`` did not reach them), and the carry's rows,
    # which ``sparse_index.row_shape`` lays out flat for the streaming step
    "sparse-index-attend": ("keye-vl-2.0-30b-a3b.decode-8k-128-b64",
                            "masked_attention"),
    "sparse-index-rows": ("keye-vl-2.0-30b-a3b.decode-8k-128-b64",
                          "selected_attention"),
    "routed-moe": ("olmoe-1b-7b.decode-1k-128", "grouped_matmul"),
    # PR 77: a plan's selective rows scan a prefill in one kernel, and its one
    # full differential row attends through the flash kernel
    "selective-prefill": ("phi-4-mini-flash-reasoning.decode-16k-256-b16",
                          "selective_scan"),
    "differential-prefill": ("phi-4-mini-flash-reasoning.decode-16k-256-b16",
                             "flash_fwd"),
}


@functools.lru_cache(maxsize=None)
def _kernels(workload: str, tpus: bool) -> frozenset:
    """The names of the pallas calls in ``workload``'s full decoder (a
    prefill, then cached steps) at the cell's real sizes, traced with the
    one home saying ``tpus``."""
    from benchmarks.lib import cells
    from ompi_tpu.models import decode

    cell = cells.resolve(workload)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(_chip, "_traced_for_tpus", lambda: tpus)
        # a prefill's program is kept by configuration and mesh: traced anew
        # under what this call says, and not kept for the next
        decode._prefill_program.cache_clear()
        job = cell.runner.build(cell.config, cell.traffic,
                                jax.devices()[:cell.chips])
        fn, args = job.programs()["decode_full"]
        jaxpr = jax.make_jaxpr(fn)(*args)
        decode._prefill_program.cache_clear()
    return frozenset(c.params["name"] for c in _pallas_calls(jaxpr.jaxpr))


@pytest.mark.parametrize("tpus", [True, False], ids=["on-tpus", "off-tpus"])
@pytest.mark.parametrize("site", SITES)
def test_every_choosing_site_follows_the_one_home(site, tpus):
    workload, kernel = SITES[site]
    names = _kernels(workload, tpus)
    assert (kernel in names) is tpus, (site, sorted(names))
    if not tpus:
        assert not names, sorted(names)


# ---- the question itself -------------------------------------------------------

def _asked_under(mesh) -> bool:
    seen = []

    def local(x):
        seen.append(_chip._traced_for_tpus())
        return x

    jax.make_jaxpr(jax.shard_map(local, mesh=mesh, in_specs=P(),
                                 out_specs=P(), check_vma=False))(
        jax.ShapeDtypeStruct((8,), "float32"))
    [said] = seen
    return said


def test_under_a_described_v5es_shard_map_it_is_traced_for_tpus(chip):
    """The mesh the trace is under decides, not this process's backend: a
    compile for a described chip takes the kernels on a CPU box."""
    assert jax.default_backend() == "cpu"
    assert _asked_under(jax.sharding.Mesh(chip[:1], ("dp",))) is True


def test_under_a_cpu_meshs_shard_map_it_is_not():
    assert _asked_under(jax.make_mesh((1,), ("dp",))) is False


def test_under_no_mesh_it_is_the_processs_backend(monkeypatch):
    assert _chip._traced_for_tpus() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _chip._traced_for_tpus() is True


# ---- the home --------------------------------------------------------------------

def test_the_home_imports_nothing_of_pallas():
    """``ops/_pallas.py``'s import is most of a second of set-up, which a
    cell that takes no kernel never pays: asking the question must not."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, ompi_tpu.ops._chip\n"
         "assert 'jax.experimental.pallas' not in sys.modules\n"
         "assert 'ompi_tpu.ops._pallas' not in sys.modules"],
        cwd=ROOT, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr


def test_the_models_ask_the_one_home_and_nothing_else():
    """No module of ``ompi_tpu/models`` asks a concrete mesh's platform or
    binds the question to a name of its own at import (one patch must flip
    every site); ``transformer._compiler_options`` runs outside any trace
    and picks compile options, not a kernel: its line is the one exception.
    One budget, one definition of the question, under ``ops``."""
    platform = re.compile(r"\.platform\s*==")
    bound = re.compile(r"import\s+.*\b_traced_for_tpus\b|"
                       r"=\s*_chip\._traced_for_tpus\b(?!\()")
    found = []
    for name in sorted(os.listdir(MODELS)):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(MODELS, name)) as f:
            found += [(name, line.strip()) for line in f
                      if platform.search(line) or bound.search(line)]
    assert found == [
        ("transformer.py", 'tpus = mesh.devices.flat[0].platform == "tpu"')]

    ops = os.path.join(ROOT, "ompi_tpu", "ops")
    defined = {"def _traced_for_tpus": [], "_VMEM_BUDGET_BYTES = ": []}
    for folder, _dirs, files in os.walk(os.path.join(ROOT, "ompi_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    text = f.read()
                for what in defined:
                    defined[what] += [os.path.join(folder, name)] * len(
                        re.findall("^" + re.escape(what), text, re.M))
    assert defined == {what: [os.path.join(ops, "_chip.py")]
                       for what in defined}
