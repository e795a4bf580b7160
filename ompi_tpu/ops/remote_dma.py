"""One-sided device put/get: pallas remote-DMA kernels over ICI.

≈ opal/mca/btl/btl.h:970 (btl_put), :1007 (btl_get), :1048 (atomics) —
the BTL's one-sided contract realized as TPU inter-chip RDMA
(``pltpu.make_async_remote_copy``) instead of a collective.  Every prior
device-path op in this framework is a *collective* (psum/ppermute over an
axis: all devices move bytes).  Here bytes move ONLY src→dst over ICI:
the other devices in the SPMD program run the same compiled kernel but
issue no traffic — the TPU-native analog of a vader-BTL put landing in a
peer's mapped segment while the rest of the node does nothing.

SPMD shape: XLA compiles one program for all devices, so "one-sided"
means *one-sided dataflow*, not one-sided control: every device enters
the kernel, the sender starts the DMA and awaits its send semaphore, the
receiver awaits its receive semaphore, everyone else falls through.

The ops are functional (windows are values): ``window_put`` returns the
new window, with only the destination device's shard changed.  They must
be called inside ``shard_map`` over the mesh axis (the same contract as
every DeviceCommunicator method); ``DeviceCommunicator.put/get`` wrap
them for driver mode.

The kernels are always compiled for the TPU.  Off-TPU they fail to
lower unless traced under ``pltpu.force_tpu_interpret_mode()`` (the TPU
interpret mode models cross-device DMA + semaphores on the host;
tests/conftest.py enters it for the virtual CPU mesh).

Every cross-device kernel opens with a handshake on the barrier
semaphore: a remote write may only start once its target has entered the
same kernel (its semaphores exist and the window's producer has
finished).  That is what ``collective_id`` is for; the kernels never
overlap, so they share one id.
"""

from __future__ import annotations

import functools

__all__ = ["window_put", "window_get", "fetch_bcast"]


def _pl():
    from ompi_tpu.ops._pallas import pl
    from ompi_tpu.ops._pallas import pltpu

    return pl, pltpu


_COLLECTIVE_ID = 0


def _params(cross_device: bool):
    _, pltpu = _pl()
    return pltpu.CompilerParams(
        has_side_effects=True,
        collective_id=_COLLECTIVE_ID if cross_device else None)


def _meet(my, a: int, b: int) -> None:
    """Devices ``a`` and ``b`` each wait until the other is in the kernel."""
    pl, pltpu = _pl()
    barrier = pltpu.get_barrier_semaphore()
    for me, peer in ((a, b), (b, a)):
        @pl.when(my == me)
        def _(peer=peer):
            pltpu.semaphore_signal(
                barrier, 1, device_id=peer,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            pltpu.semaphore_wait(barrier, 1)


def _put_kernel(src_ref, win_ref, out_ref, send_sem, recv_sem, *,
                src: int, dst: int, axis: str):
    """dst's out ← src's src_ref; every other device: out = own win.

    out_ref is input/output-aliased to win_ref, so "unchanged" costs
    nothing; only the landing shard is written remotely.
    """
    from jax import lax

    pl, pltpu = _pl()
    my = lax.axis_index(axis)
    if src == dst:  # degenerate self-put: local DMA on the one device
        @pl.when(my == src)
        def _self():
            copy = pltpu.make_async_copy(src_ref, out_ref, send_sem)
            copy.start()
            copy.wait()
        return
    _meet(my, src, dst)
    rdma = pltpu.make_async_remote_copy(
        src_ref=src_ref, dst_ref=out_ref, send_sem=send_sem,
        recv_sem=recv_sem, device_id=dst,
        device_id_type=pltpu.DeviceIdType.LOGICAL)

    @pl.when(my == src)
    def _send():
        rdma.start()
        rdma.wait_send()

    @pl.when(my == dst)
    def _recv():
        rdma.wait_recv()


def window_put(win, value, src: int, dst: int, axis: str):
    """One-sided put (inside shard_map): device ``src`` writes ``value``
    into device ``dst``'s window shard; returns the new window.  Bytes
    cross ICI once, src→dst — no collective dataflow.

    ≈ btl.h:970 mca_btl_base_module_put_fn_t with the window as the
    registered remote segment.
    """
    import jax

    from ompi_tpu.ops._pallas import pallas_call

    pl, pltpu = _pl()
    if win.shape != value.shape or win.dtype != value.dtype:
        raise ValueError(
            f"window_put: value {value.shape}/{value.dtype} must match the "
            f"window shard {win.shape}/{win.dtype}")
    return pallas_call(
        functools.partial(_put_kernel, src=src, dst=dst, axis=axis),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(win.shape, win.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        input_output_aliases={1: 0},      # win -> out
        compiler_params=_params(src != dst),
        name="remote_put",
    )(value, win)


def _get_kernel(win_ref, local_ref, out_ref, send_sem, recv_sem, *,
                src: int, dst: int, axis: str):
    """dst's out ← src's win; every other device: out = own local buf."""
    from jax import lax

    pl, pltpu = _pl()
    my = lax.axis_index(axis)
    if src == dst:
        @pl.when(my == src)
        def _self():
            copy = pltpu.make_async_copy(win_ref, out_ref, send_sem)
            copy.start()
            copy.wait()
        return
    _meet(my, src, dst)
    rdma = pltpu.make_async_remote_copy(
        src_ref=win_ref, dst_ref=out_ref, send_sem=send_sem,
        recv_sem=recv_sem, device_id=dst,
        device_id_type=pltpu.DeviceIdType.LOGICAL)

    @pl.when(my == src)
    def _serve():
        rdma.start()
        rdma.wait_send()

    @pl.when(my == dst)
    def _recv():
        rdma.wait_recv()


def window_get(win, src: int, dst: int, axis: str):
    """One-sided get (inside shard_map): device ``dst`` fetches device
    ``src``'s window shard; returns the fetched buffer (on every other
    device: its own window shard, via a local copy).

    The wire direction is identical to put — the serving device pushes —
    because ICI RDMA is sender-driven; the *semantics* are a get: the
    value read is ``src``'s window content, untouched.
    ≈ btl.h:1007 mca_btl_base_module_get_fn_t.
    """
    import jax

    from ompi_tpu.ops._pallas import pallas_call

    pl, pltpu = _pl()
    return pallas_call(
        functools.partial(_get_kernel, src=src, dst=dst, axis=axis),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(win.shape, win.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        input_output_aliases={1: 0},      # local buf -> out
        compiler_params=_params(src != dst),
        name="remote_get",
    )(win, win)


def _bcast_kernel(src_ref, out_ref, send_sem, recv_sem, *,
                  root: int, n: int, axis: str):
    """Root pushes its buffer to every other device, point-to-point —
    n-1 RDMAs from root, no tree, no psum.  The btl-put composition the
    reference builds its rdma-pipeline broadcasts from."""
    from jax import lax

    pl, pltpu = _pl()
    my = lax.axis_index(axis)
    barrier = pltpu.get_barrier_semaphore()

    @pl.when(my == root)
    def _serve():
        copy = pltpu.make_async_copy(src_ref, out_ref, send_sem)
        copy.start()
        copy.wait()
        pltpu.semaphore_wait(barrier, n - 1)   # every receiver is in
        for peer in range(n):
            if peer == root:
                continue
            rdma = pltpu.make_async_remote_copy(
                src_ref=src_ref, dst_ref=out_ref, send_sem=send_sem,
                recv_sem=recv_sem, device_id=peer,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            rdma.start()
            rdma.wait_send()

    @pl.when(my != root)
    def _recv():
        pltpu.semaphore_signal(
            barrier, 1, device_id=root,
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.make_async_remote_copy(
            src_ref=src_ref, dst_ref=out_ref, send_sem=send_sem,
            recv_sem=recv_sem, device_id=root,
            device_id_type=pltpu.DeviceIdType.LOGICAL).wait_recv()


def fetch_bcast(x, root: int, n: int, axis: str):
    """Root's buffer delivered to all n devices by explicit one-sided
    puts (demonstrates put composition; the production bcast stays on
    the coll/xla decision layer)."""
    import jax

    if n == 1:
        return x
    from ompi_tpu.ops._pallas import pallas_call

    pl, pltpu = _pl()
    return pallas_call(
        functools.partial(_bcast_kernel, root=root, n=n, axis=axis),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        compiler_params=_params(True),
        name="remote_bcast",
    )(x)
