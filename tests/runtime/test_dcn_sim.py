"""Device collectives across simulated slices.

≈ SURVEY §5 row 78's testable half: two fake hosts stand in for two TPU
slices (the DCN boundary), the global mesh carries a ``dcn`` axis across
them, and sums over the sub-communicator of each axis execute through
jax.distributed: over ``dcn`` across the process boundary, over ``ici``
inside each process.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_PROG = r"""
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np
import jax.numpy as jnp
import ompi_tpu

comm = ompi_tpu.init()
from ompi_tpu.mpi.device_comm import DeviceCommunicator
from ompi_tpu.parallel import multihost

# 2 hosts x 2 local devices -> global mesh {dcn: 2, ici: 2}; the dcn axis
# spans the fake slice boundary (one row of devices per host process)
mesh = multihost.global_mesh({'dcn': 2, 'ici': 2})
world = DeviceCommunicator(mesh)

from jax.sharding import NamedSharding, PartitionSpec as P

spec = P(('dcn', 'ici'))
# device (d, i) holds the value 10*d + i
x = jax.jit(lambda: jnp.repeat(jnp.array([0., 1., 10., 11.]), 128)
            .reshape(4, 128), out_shardings=NamedSharding(mesh, spec))()
for axis, expect in (('dcn', [10., 12., 10., 12.]),
                     ('ici', [1., 1., 21., 21.]),
                     (None, [22., 22., 22., 22.])):
    sub = world.sub((axis,)) if axis else world
    fn = jax.jit(jax.shard_map(lambda s: sub.allreduce(s), mesh=mesh,
                               in_specs=spec, out_specs=spec,
                               check_vma=False))
    y = jax.jit(lambda a: a, out_shardings=NamedSharding(mesh, P()))(fn(x))
    got = np.asarray(y)
    assert np.array_equal(got, np.repeat(np.array(expect, np.float32), 128)
                          .reshape(4, 128)), (axis, got[:, 0])
print(f'rank {comm.rank}: sums over dcn, ici and both across slices ok')
ompi_tpu.finalize()
"""


def test_dcn_axis_routing_across_sim_slices():
    env = dict(os.environ)
    env.pop("OMPI_TPU_RANK", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    r = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-np", "2",
         "--plm", "sim", "--hosts", "2", "--",
         sys.executable, "-c", _PROG],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr + r.stdout
    for rank in (0, 1):
        assert (f"rank {rank}: sums over dcn, ici and both across slices ok"
                in r.stdout)
