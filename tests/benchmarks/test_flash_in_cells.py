"""Which cells run the flash kernels, and which are left exactly as they were.

The trainers (2048 keys a device) hold ``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv`` and the rotary kernel, and nothing as large as a score
matrix; the cells' ``test_cell_fits_<cell>.py`` compile them so, at their
real sizes, on demand.  The decoders' prefill (1024 keys) is under the
rule's threshold (``parallel/attention.local_impl``): their programs hold no
kernel of attention's, and the dense one's process never imports
``jax.experimental.pallas`` (0.8 s of a 6.6 s set-up; ledger, PR 27).  At
the tiny sizes on CPU devices no program holds a pallas call: an interpreted
kernel would compile scalar programs inside a timed window.  Programs are
lowered for a described v5e; nothing runs and no number is a measurement."""

import json
import os
import re
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")    # or libtpu logs to /tmp

import jax  # noqa: E402
import pytest  # noqa: E402

from benchmarks.lib import cells, program  # noqa: E402

ROOT = os.path.dirname(cells.BENCH_DIR)
KERNEL = re.compile(r'kernel_name = "(\w+)"')
# trainer cell -> the score matrix of one device that the kernels keep from
# existing
TRAINERS = {
    "pythia-1.4b-widths.train-2k": "8x16x2048x2048x",
    "pythia-6.9b-widths.train-2k-dp2tp2": "4x16x2048x2048x",
}
# decoder cell -> (kernels its two programs may hold, may it import pallas)
DECODERS = {
    "pythia-1.4b-widths.decode-1k-128": (set(), False),
    "olmoe-1b-7b.decode-1k-128": ({"grouped_matmul"}, True),
}
TINY_TRAFFIC = {"batch": 4, "seq": 32, "prompt_len": 16, "max_new": 8}

# what a process that serves a decoder cell does before its first run, as far
# as lowering: run.py's way to the job, for a chip that is only described
LOWER_A_DECODER = r"""
import json, re, sys
from jax.experimental import topologies
from benchmarks.lib import cells
chips = topologies.get_topology_desc(platform="tpu",
                                     topology_name="v5e:2x2").devices
cell = cells.resolve(sys.argv[1])
job = cell.runner.build(cell.config, cell.traffic, chips[:cell.chips])
out = {"programs": {}}
for name, (fn, args) in job.programs().items():
    text = fn.lower(*args).as_text()
    out["programs"][name] = {
        "custom_calls": text.count("tpu_custom_call"),
        "kernels": re.findall(r'kernel_name = "(\w+)"', text)}
out["pallas"] = "jax.experimental.pallas" in sys.modules
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def chips():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.mark.parametrize("workload", list(DECODERS))
def test_decoder_programs_stay_as_they_are(workload, chips):
    """In a fresh process, as a run of the cell is: ``decode_first`` and
    ``decode_full`` hold no kernel but the routed experts', and the dense
    decoder's process has not imported pallas by the time both are
    lowered."""
    allowed, may_import = DECODERS[workload]
    # the child describes a v5e while this process (and other workers'
    # files that compile for one) hold libtpu for theirs: without this it
    # aborts on /tmp/libtpu_lockfile
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
           "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}
    done = subprocess.run([sys.executable, "-c", LOWER_A_DECODER, workload],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    out = json.loads(done.stdout.rpartition("RESULT ")[2])
    assert set(out["programs"]) == {"decode_first", "decode_full"}
    for name, held in out["programs"].items():
        assert set(held["kernels"]) == allowed, f"{name}: {held}"
        assert held["custom_calls"] == len(held["kernels"]), f"{name}: {held}"
    assert out["pallas"] == may_import


@pytest.mark.parametrize("workload", list(TRAINERS))
def test_trainer_programs_hold_the_kernels_and_no_score_matrix(workload,
                                                               chips):
    from jax.experimental.pallas import tpu as pltpu

    cell = cells.resolve(workload)
    job = cell.runner.build(cell.config, cell.traffic, chips[:cell.chips])
    with pltpu.force_tpu_interpret_mode(None):   # for the chip, not the suite
        texts = {name: fn.lower(*args).as_text()
                 for name, (fn, args) in job.programs().items()}
    assert texts
    for name, text in texts.items():
        held = KERNEL.findall(text)
        # the layers are a scan, so once a program: the forward in the
        # forward pass only (the checkpoint policy keeps its results), each
        # backward kernel once; the rotary kernel, jitted, once a pass
        flash = sorted(k for k in held if k.startswith("flash_"))
        assert flash == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"], name
        assert set(held) - set(flash) == {"rope"}, f"{name}: {held}"
        assert TRAINERS[workload] not in text, name


@pytest.mark.parametrize("workload", list(TRAINERS) + list(DECODERS))
def test_tiny_cpu_programs_hold_no_pallas_call(workload):
    cell = cells.resolve(workload)
    traffic = {k: TINY_TRAFFIC.get(k, v) for k, v in cell.traffic.items()}
    job = cell.runner.build(program.tiny(cell.config), traffic,
                            jax.devices()[:cell.chips])
    for name, (fn, args) in job.programs().items():
        assert "pallas_call" not in str(fn.trace(*args).jaxpr), name
