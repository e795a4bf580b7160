"""The one rule that chooses a device's local attention
(``parallel/attention.local_impl`` and, for what a layout hands a device,
``layout_impl``): the pallas flash kernels or the jnp path, from shape and
the mesh's platform and nothing else.  Arithmetic on shapes; nothing runs."""

from types import SimpleNamespace as NS

import numpy as np
import pytest

from ompi_tpu.parallel.attention import layout_impl, local_impl

# what one device attends over in each cell of BENCHMARK.json, bfloat16
CELLS = {
    "pythia-1.4b-widths.train-2k": ((8, 2048, 16, 128), "flash"),
    "pythia-6.9b-widths.train-2k-dp2tp2": ((4, 2048, 16, 128), "flash"),
    "pythia-1.4b-widths.decode-1k-128": ((48, 1024, 16, 128), "jnp"),
    "olmoe-1b-7b.decode-1k-128": ((48, 1024, 16, 128), "jnp"),
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_rule_on_the_cells_shapes(cell):
    """The trainers' 2048 keys take the kernels on a TPU; the decoders'
    prefill of 1024 keeps the jnp path; a CPU mesh never takes them."""
    shape, on_tpu = CELLS[cell]
    assert local_impl("auto", shape, shape, "bfloat16", "tpu") == on_tpu
    assert local_impl("auto", shape, shape, "bfloat16", "cpu") == "jnp"
    assert local_impl("auto", shape, shape, "bfloat16") == "jnp"   # this box


@pytest.mark.parametrize("q_shape,k_shape,dtype,want", [
    ((1, 2048, 1, 128), (1, 2048, 1, 128), "bfloat16", "flash"),
    ((1, 1920, 1, 128), (1, 1920, 1, 128), "bfloat16", "jnp"),    # < 2048
    ((1, 128, 1, 128), (1, 4096, 1, 128), "bfloat16", "flash"),   # the keys
    ((1, 4096, 1, 128), (1, 1024, 1, 128), "bfloat16", "jnp"),    # count
    ((1, 2048, 1, 64), (1, 2048, 1, 64), "bfloat16", "flash"),
    ((1, 2048, 1, 256), (1, 2048, 1, 256), "bfloat16", "jnp"),    # not run yet
    ((1, 2100, 1, 128), (1, 2100, 1, 128), "bfloat16", "jnp"),    # no block
    ((1, 200, 1, 128), (1, 2048, 1, 128), "bfloat16", "jnp"),     # divides it
    ((1, 32768, 1, 128), (1, 32768, 1, 128), "bfloat16", "flash"),
    ((1, 32768, 1, 128), (1, 32768, 1, 128), "float32", "jnp"),   # VMEM block
    ((1, 65536, 1, 128), (1, 65536, 1, 128), "bfloat16", "jnp"),
    ((1, 65536, 1, 128), (1, 2048, 1, 128), "bfloat16", "jnp"),   # Q in dk/dv
], ids=lambda x: "x".join(map(str, x)) if isinstance(x, tuple) else x)
def test_auto_on_a_tpu_from_shape(q_shape, k_shape, dtype, want):
    assert local_impl("auto", q_shape, k_shape, dtype, "tpu") == want


@pytest.mark.parametrize("platform", [None, "cpu", "tpu"])
def test_a_named_path_is_taken_at_its_word(platform):
    """Whatever the shape: the kernels themselves refuse what they cannot
    tile (``test_flash.py``)."""
    small, odd = (1, 384, 2, 64), (1, 200, 2, 64)
    for shape in (small, odd):
        assert local_impl("jnp", shape, shape, "float32", platform) == "jnp"
        assert local_impl("flash", shape, shape, "float32",
                          platform) == "flash"
    with pytest.raises(ValueError, match="unknown attention impl"):
        local_impl("pallas", small, small, "float32", platform)


def _comm(platform, sp=4):
    mesh = NS(shape={"sp": sp}, devices=np.array([NS(platform=platform)] * sp))
    return NS(mesh=mesh, axes=("dp", "sp"))


@pytest.mark.parametrize("layout,t,want", [
    # 512 positions a device: 2048 after the ulysses re-shard over four
    # devices and as gathered keys, a ring hop's own piece stays 512
    ("ulysses", 512, "flash"), ("gathered", 512, "flash"),
    ("ring", 512, "jnp"), ("ring", 2048, "flash"),
    ("ulysses", 256, "jnp"), ("gathered", 256, "jnp"),
])
def test_layout_impl_is_the_rule_for_what_one_device_attends_over(
        layout, t, want):
    """The three layouts and the model's rotary embedding ask one function."""
    shape = (2, t, 8, 128)
    assert layout_impl(_comm("tpu"), layout, shape, shape, "bfloat16") == want
    assert layout_impl(_comm("cpu"), layout, shape, shape, "bfloat16") == "jnp"
    assert layout_impl(_comm("tpu"), layout, shape, shape, "bfloat16",
                       "sp", impl="jnp") == "jnp"
    assert layout_impl(_comm("cpu"), layout, shape, shape, "bfloat16",
                       impl="flash") == "flash"


def test_layout_impl_refuses_a_layout_it_does_not_know():
    shape = (2, 2048, 8, 128)
    with pytest.raises(ValueError, match="unknown attention layout"):
        layout_impl(_comm("tpu"), "striped", shape, shape, "bfloat16")
