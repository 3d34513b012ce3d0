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


def select_spmd(spmd: str = "auto") -> str:
    """Resolve an --spmd request to the executed path: "shard_map",
    "constraint" (jit + explicit shardings, GSPMD partitioning), or
    "pmap" (the legacy 1-D path). "auto" is shard_map."""
    if spmd not in ("auto", "shard_map", "constraint", "pmap"):
        raise ValueError(
            f"spmd must be auto|shard_map|constraint|pmap, got {spmd!r}"
        )
    return "shard_map" if spmd == "auto" else spmd


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


def pmap_call(fn, mesh: Mesh, specs, per: int, axes):
    """Run `fn(state, stop, host0)` data-parallel via `jax.pmap` — the
    legacy 1-D path, kept for soak comparison until ROADMAP C3 settles
    it.

    `specs` is the state's PartitionSpec pytree: leaves sharded on the
    mesh axis reshape [S*d0, ...] <-> [S, d0, ...] around the pmap
    (d0 = leading dim / S: host-dim leaves use `per`, the exchange
    buffer its own width); replicated leaves broadcast in and take
    device 0's copy out (the same contract shard_map's P() out_spec
    has).
    """
    if not isinstance(axes, str):
        raise NotImplementedError(
            "the pmap fallback is single-axis only: a multi-slice "
            "(dcn x hosts) mesh must run through the SPMD paths — build "
            "with spmd='auto' (selects "
            f"{select_spmd('auto')!r}) or spmd='constraint' instead of "
            "spmd='pmap'"
        )
    n = int(np.prod(mesh.devices.shape))
    mask = jax.tree.map(lambda sp: len(sp) > 0, specs)
    in_axes = jax.tree.map(lambda m: 0 if m else None, mask)

    def split(st):
        return jax.tree.map(
            lambda x, m: x.reshape((n, x.shape[0] // n) + x.shape[1:])
            if m else x,
            st, mask,
        )

    def join(st):
        return jax.tree.map(
            lambda x, m: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])
            if m else x,
            st, mask,
        )

    pf = jax.pmap(
        lambda st, stop: fn(
            st, stop, jax.lax.axis_index(axes).astype(jnp.int32) * per
        ),
        axis_name=axes,
        in_axes=(in_axes, None),
        out_axes=in_axes,
        devices=list(mesh.devices.flatten()),
    )

    def call(st, stop):
        return join(pf(split(st), stop))

    return call


def build_sharded(eng, init_fn, mesh: Mesh, n_hosts_local: int,
                  axis: str = HOSTS_AXIS, spmd: str = "auto"):
    """Wrap an axis-aware Engine into sharded init/run/step callables.

    `eng` must have been built with axis_name=axis and per-shard host count
    n_hosts_local. Returns (init, run, step_window), all jitted over `mesh`:
    init() -> sharded EngineState; run(st, stop) / step_window(st, stop).

    `spmd` picks the execution path (see `select_spmd`): "auto" resolves
    to shard_map, and "pmap" keeps the legacy 1-D path alive for soak
    comparison.
    """
    path = select_spmd(spmd)
    if path == "constraint":
        raise ValueError(
            "spmd='constraint' partitions a GLOBAL (axis_name=None) "
            "engine with GSPMD and cannot wrap this per-shard engine; "
            "build it via sim.build_simulation(..., spmd='constraint')"
        )

    def _host0():
        return jax.lax.axis_index(axis).astype(jnp.int32) * n_hosts_local

    template = jax.eval_shape(init_fn, jnp.zeros((), jnp.int32))
    specs = state_specs(template, n_hosts_local, axis)

    init = jax.jit(
        jax.shard_map(
            lambda: init_fn(_host0()),
            mesh=mesh,
            in_specs=(),
            out_specs=specs,
            check_vma=False,
        )
    )

    def _wrap(fn):
        if path == "pmap":
            return pmap_call(fn, mesh, specs, n_hosts_local, axis)
        # no donate_argnums here: this is the raw API and callers (tests,
        # smoke entries) legitimately reread their input state after the
        # call. The managed path (sim.Simulation) donates — it tracks
        # state ownership and can prove the input buffer is dead.
        return jax.jit(
            jax.shard_map(
                lambda s, t: fn(s, t, _host0()),
                mesh=mesh,
                in_specs=(specs, P()),
                out_specs=specs,
                check_vma=False,
            )
        )

    return init, _wrap(eng.run), _wrap(eng.step_window)
