"""chip_smoke.py's phases at a tiny size on the virtual CPU mesh, and the
no-fallback behaviour of the entry point that needs the chip.

The phases are the same functions ``python chip_smoke.py`` runs at the
flagship width on the TPU; here the suite's conftest has pallas in
interpret mode, so a phase reports 0 compiled kernels where the chip run
must report at least one.
"""

import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from benchmarks.lib.peaks import device_peaks  # noqa: E402

jax = pytest.importorskip("jax")

TINY = chip_smoke.Size(
    cfg=dataclasses.replace(chip_smoke.FLAGSHIP, vocab=512, d_model=128,
                            n_heads=4, n_layers=2, d_ff=256, seq=64,
                            ce_chunk=32,
                            # interpret-mode kernels are host callbacks,
                            # which jax.checkpoint cannot partial-eval
                            remat=None),
    batch=4, steps=3, prompt=16, new_tokens=4, ring_seq=32, rows=8)


@pytest.fixture(params=[1, 4], ids=["1dev", "4dev"])
def devices(request):
    return jax.devices()[:request.param]


@pytest.fixture(scope="module")
def xla_on_four():
    return chip_smoke.phase_train(TINY, jax.devices()[:4])


def test_train_phase_on_one_device():
    out = chip_smoke.phase_train(TINY, jax.devices()[:1])
    assert len(out["losses"]) == TINY.steps + 2      # steps, then the loop
    assert out["kernels"] == [] and "loss_all_vs_one" not in out


def test_train_phase_on_four_devices(xla_on_four):
    here, there = xla_on_four["loss_all_vs_one"]
    assert abs(here - there) <= chip_smoke.LOSS_RTOL * there
    assert xla_on_four["bytes_in_use"] == [None] * 4   # CPU: no stats


@pytest.mark.parametrize("word", ["flash", "ulysses"])
def test_train_phase_words_select_no_implementation(xla_on_four, word):
    """"flash" and "xla" mean what "ulysses" means: a layout over sp.  The
    local attention is chosen from shape and the mesh's platform, so on the
    CPU mesh every word runs the one program, kernel-free."""
    other = chip_smoke.phase_train(TINY, jax.devices()[:4], attention=word)
    assert other["kernels"] == []
    assert other["losses"] == xla_on_four["losses"][:TINY.steps]


@pytest.mark.parametrize("seq", [32, 128])
def test_train_phase_takes_another_length_at_the_same_tokens(seq):
    """On the chip the smoke trains once more at twice the flagship's
    positions, where the rule takes the kernels; here, on the CPU mesh, any
    length stays kernel-free."""
    out = chip_smoke.phase_train(TINY, jax.devices()[:1],
                                 attention="ulysses", seq=seq)
    assert out["kernels"] == [] and len(out["losses"]) == TINY.steps


def test_flash_phase(devices):
    out = chip_smoke.phase_flash(TINY, devices)
    assert max(out["errs"]) < chip_smoke.BF16_TOL


def test_decode_phase(devices):
    out = chip_smoke.phase_decode(TINY, devices)
    assert out["tokens_per_s"] > 0


def test_mpi_phase(devices):
    chip_smoke.phase_mpi(TINY, devices)


def test_dma_phase(devices):
    out = chip_smoke.phase_dma(TINY, devices)
    assert (out["src"] != out["dst"]) == (len(devices) > 1)


def test_ring_phase():
    out = chip_smoke.phase_ring(TINY, jax.devices()[:4])
    assert out["sp"] == 4 and out["impl"] == "jnp"   # auto, off the TPU
    assert out["kernels"] == 0


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""           # no record, no JSON line
    assert "no CPU mode" in proc.stderr


def test_peak_table_rejects_unknown_device_kind():
    assert device_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        device_peaks("cpu")


def test_compile_cache_follows_env_else_checkout(monkeypatch):
    from ompi_tpu.core import enable_compile_cache

    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == old   # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


def test_tpu_launcher_never_imports_jax():
    """``tpurun --tpu``: the launcher allocates and maps without touching
    jax (it would hold the chip its rank needs), gives the host one slot,
    and refuses a second rank."""
    code = (
        "import sys\n"
        "from ompi_tpu.runtime.launcher import launch\n"
        "rc = launch([sys.executable, '-c', 'print(\"rank ran\")'], np=1,"
        " want_tpu=True)\n"
        "assert 'jax' not in sys.modules, 'launcher imported jax'\n"
        "sys.exit(rc)\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "rank ran" in proc.stdout

    from ompi_tpu.runtime import ras
    from ompi_tpu.runtime.job import AppContext, Job

    one = ras.allocate(Job([AppContext(argv=["true"], np=1)]),
                       want_tpu=True)
    assert [n.slots for n in one.nodes] == [1]
    with pytest.raises(ValueError, match="one rank on this host"):
        ras.allocate(Job([AppContext(argv=["true"], np=2)]), want_tpu=True)


def test_flash_fails_off_tpu_outside_interpret_mode():
    """The kernels carry no interpret selection: outside JAX's interpret
    context a flash call on the CPU fails to lower."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    from ompi_tpu.ops import flash_attention

    q = jnp.ones((1, 64, 2, 32), jnp.float32)
    with pltpu.force_tpu_interpret_mode(None):
        with pytest.raises(Exception, match="[Ii]nterpret"):
            jax.block_until_ready(flash_attention(q, q, q))
    assert np.isfinite(np.asarray(flash_attention(q, q, q))).all()


def test_flash_refuses_a_sequence_the_compiler_would():
    """Whole-sequence K/V blocks: 32k x 128 bf16 compiled on the v5e and
    64k did not; the call says so before the compiler does."""
    import jax.numpy as jnp

    from ompi_tpu.ops import flash_attention

    ok = jax.ShapeDtypeStruct((1, 32768, 1, 128), jnp.bfloat16)
    big = jax.ShapeDtypeStruct((1, 65536, 1, 128), jnp.bfloat16)
    jax.eval_shape(flash_attention, ok, ok, ok)
    with pytest.raises(ValueError, match="holds it in VMEM as one block"):
        jax.eval_shape(flash_attention, big, big, big)
