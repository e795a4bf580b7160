"""One-sided device RMA: a DeviceWindow over the chip mesh.

Runs on the devices there are, and needs at least two (origin and target
differ).  From the repo root, on a multi-chip TPU host:

    python -m examples.osc_device_window

The kernel compiles for the TPU only; on a CPU mesh it runs under
``pltpu.force_tpu_interpret_mode()``, as the test suite does.

The put is NOT a collective: bytes cross the interconnect exactly once,
origin→target, through a pallas remote-DMA kernel — the osc/rdma
strategy on ICI.
"""

import numpy as np


def main() -> None:
    import jax

    from ompi_tpu.mpi.device_comm import device_world
    from ompi_tpu.mpi.osc import DeviceWindow
    from ompi_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(devices=jax.devices())
    comm = device_world(mesh)
    n = comm.size
    if n < 2:
        raise SystemExit("need >= 2 devices (origin and target differ)")
    print(f"{n}-device window over {jax.default_backend()}")

    win = DeviceWindow(comm, local_shape=(4, 128), dtype=np.float32)
    win.put(np.full((4, 128), 42.0, np.float32), origin=0, target=n - 1)
    win.fence()
    assert np.all(win.local(n - 1) == 42.0)
    assert np.all(win.local(0) == 0.0)
    fetched = win.get(origin=1, target=n - 1)
    assert np.all(fetched == 42.0)
    print(f"one-sided put landed on device {n - 1}; "
          f"one-sided get fetched it back: {fetched[0, 0]}")
    win.free()


if __name__ == "__main__":
    main()
