"""Host sharding across a TPU device mesh.

The reference assigns hosts to worker pthreads by random shuffle
(reference: src/main/core/scheduler/scheduler.c:440-534) and synchronizes
rounds with 6 countdown-latch barriers (scheduler.c:124-129). Here hosts are
block-partitioned across a `jax.sharding.Mesh`; every engine state leaf is
sharded on its leading host dimension; the round barrier is `lax.pmin` and
cross-shard packet delivery rides XLA collectives over ICI (SURVEY.md §2.4
"Distributed communication backend").

Multi-slice: the mesh may be 2-D ("dcn", "hosts") — slices of chips joined
over the data-center network, the reference's never-finished multi-machine
master/slave design (master.c:414-416, work/message.c stub) done properly.
Hosts block-partition over both axes (dcn-major); every collective
(pmin barrier, bucketed all_to_all exchange) runs over the combined axis
tuple, so XLA routes intra-slice traffic over ICI and inter-slice traffic
over DCN.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

HOSTS_AXIS = "hosts"
DCN_AXIS = "dcn"


def make_mesh(n_devices: int | None = None, axis: str = HOSTS_AXIS,
              dcn_slices: int = 1) -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise RuntimeError(
                f"need {n_devices} devices, found {len(devs)} "
                f"(set --xla_force_host_platform_device_count for CPU testing)"
            )
        devs = devs[:n_devices]
    if dcn_slices > 1:
        n = len(devs)
        if n % dcn_slices:
            raise ValueError(
                f"{n} devices not divisible by {dcn_slices} DCN slices"
            )
        return Mesh(
            np.array(devs).reshape(dcn_slices, n // dcn_slices),
            (DCN_AXIS, axis),
        )
    return Mesh(np.array(devs), (axis,))


def hosts_axes(mesh: Mesh):
    """The axis name (1-D mesh) or axis-name tuple (multi-slice mesh)
    hosts are sharded over — valid anywhere an axis_name is accepted."""
    names = mesh.axis_names
    return names[0] if len(names) == 1 else tuple(names)


def state_specs(st, n_hosts_local: int, axis: str = HOSTS_AXIS):
    """PartitionSpec pytree for an EngineState: leaves with a leading
    per-shard host dim shard on `axis`; scalars (now, n_windows) replicate.
    The exchange double buffer (EngineState.xchg) is per-shard PRIVATE
    state — its leaves shard on `axis` unconditionally, never replicate,
    whatever their leading dim happens to equal."""

    def spec(leaf):
        if leaf.ndim >= 1 and leaf.shape[0] == n_hosts_local:
            return P(axis)
        return P()

    specs = jax.tree.map(spec, st)
    xchg = getattr(st, "xchg", None)
    if xchg is not None:
        import dataclasses as _dc

        specs = _dc.replace(
            specs,
            xchg=jax.tree.map(
                lambda leaf: P(axis) if leaf.ndim >= 1 else P(), xchg
            ),
        )
    return specs


def shard_jit(fn, mesh: Mesh, in_specs, out_specs, per: int, axes=None,
              donate: bool = False):
    """Jit `fn(*args, host0)` under `shard_map` over `mesh`: the one
    sharded call every mesh path builds on.

    `host0` is the shard's first global host id, its index along `axes`
    (default `hosts_axes(mesh)`) times `per` hosts per shard. `in_specs`
    and `out_specs` are shard_map's; a `P()` spec replicates an argument
    (a stop time, a key, a tuple of them). `donate` donates the first
    argument.
    """
    if axes is None:
        axes = hosts_axes(mesh)
    return jax.jit(
        jax.shard_map(
            lambda *args: fn(
                *args, jax.lax.axis_index(axes).astype(jnp.int32) * per
            ),
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        ),
        donate_argnums=0 if donate else (),
    )


def build_sharded(eng, init_fn, mesh: Mesh, n_hosts_local: int,
                  axis: str = HOSTS_AXIS):
    """Wrap an axis-aware Engine into sharded init/run/step callables.

    `eng` must have been built with axis_name=axis and per-shard host count
    n_hosts_local. Returns (init, run, step_window), all jitted over `mesh`:
    init() -> sharded EngineState; run(st, stop) / step_window(st, stop).

    run and step_window do not donate: this is the raw API, and callers
    (tests, smoke entries) reread their input state after the call. The
    managed path (sim.Simulation) donates: it tracks state ownership and
    can prove the input buffer is dead.
    """
    template = jax.eval_shape(init_fn, jnp.zeros((), jnp.int32))
    specs = state_specs(template, n_hosts_local, axis)
    init = shard_jit(init_fn, mesh, (), specs, n_hosts_local, axis)

    def _wrap(fn):
        return shard_jit(fn, mesh, (specs, P()), specs, n_hosts_local, axis)

    return init, _wrap(eng.run), _wrap(eng.step_window)
