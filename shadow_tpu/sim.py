"""Simulation assembly: parsed config -> engine + device state + run loop.

This is the TPU-era Master/Slave bootstrap (reference:
src/main/core/master.c:271-448 `_master_registerPlugins/_master_registerHosts`
-> slave_addNewVirtualHost -> host_new/host_setup -> scheduler_addHost):
load the topology, expand and attach hosts, register DNS names, size the
NICs, let the app model bind its sockets and schedule its process start
events, then compile everything into one Engine whose handler table is
[stack pipeline | TCP machinery | app kinds].

Where the reference walks XML into heap objects and pthread queues, this
builder walks the same config into struct-of-arrays device state; where
the reference's hosts are partitioned across worker threads by random
shuffle (scheduler.c:440-534), hosts here are block-partitioned across the
device mesh axis by dense gid.
"""

from __future__ import annotations

import dataclasses
import weakref
from typing import Any, Callable, Protocol

import jax
import jax.numpy as jnp
import numpy as np

from shadow_tpu.config import (
    HostInstance,
    ShadowConfig,
    expand_hosts,
    resolve_path,
)
from shadow_tpu.core.engine import Engine, EngineConfig
from shadow_tpu.core.events import Events
from shadow_tpu.core.timebase import MILLISECOND, SECOND, TIME_INVALID
from shadow_tpu.net.dns import DNS
from shadow_tpu.net.topology import Topology
from shadow_tpu.transport.stack import N_PKT_ARGS, SimHost, Stack, HostNet
from shadow_tpu.transport.tcp import TCP

DEFAULT_BANDWIDTH_KIB = 10240  # when neither host attr nor vertex attr set

# Virtual-CPU model: every executed event costs this many cycles on the
# host's configured CPU (the reference scales measured wall time by
# rawFrequency/virtualFrequency, cpu.c:56-107; with jitted handlers there
# is no wall time to measure, so a fixed per-event cycle budget stands in).
CPU_CYCLES_PER_EVENT = 10_000


@dataclasses.dataclass
class SimBuild:
    """Mutable build context handed to the app model.

    The app reads per-host process specs/arguments, resolves peer names
    through `dns`, binds listen sockets into `sockets`/`tcb`, and appends
    process start events (starttime semantics of the <process> element).

    `hosts` is the subset of hosts the *current* model owns (all hosts in
    a single-model simulation); per-host arrays must still be sized
    `n_hosts` = the full host count, indexed by `HostInstance.gid`.
    """

    cfg: ShadowConfig
    hosts: list[HostInstance]
    dns: DNS
    topo: Topology
    n_sockets: int
    sockets: Any  # SocketTable [H, S]
    tcb: Any  # transport.tcp.TCB [H, S] or None
    start_events: list[tuple[int, int, int, list[int]]] = dataclasses.field(
        default_factory=list
    )  # (time_ns, gid, kind_rel, args words)
    n_total: int = 0  # full host count (len(hosts) when single-model)
    kind_offset: int = 0  # current model's kind base relative to the apps'

    @property
    def n_hosts(self) -> int:
        return self.n_total or len(self.hosts)

    def resolve_gid(self, name: str) -> int:
        addr = self.dns.resolve_name(name)
        if addr is None:
            raise ValueError(f"unknown hostname in config: {name!r}")
        return addr.host_id

    def add_start_event(self, gid: int, time_s: float, kind_rel: int,
                        args: list[int] | None = None) -> None:
        self.start_events.append(
            (int(time_s * SECOND), gid, self.kind_offset + kind_rel,
             list(args or []))
        )


class AppModel(Protocol):
    """A jitted application compiled into the device step (the fast tier
    of SURVEY.md §7 step 6: the analog of a plugin binary is a handler
    table + static per-host config arrays)."""

    name: str
    needs_tcp: bool
    n_kinds: int

    def app_rows(self) -> int:
        """Emit rows the on_recv callback returns (for max_emit sizing)."""
        ...

    def handler_rows(self) -> int:
        """Max Emit rows any of the app's own kind handlers returns."""
        ...

    def build(self, b: SimBuild) -> tuple[Any, Callable, Callable | None]:
        """-> (app_state [H,...], make_handlers(stack, kind_base) ->
        [handlers], on_recv or None)."""
        ...


@dataclasses.dataclass
class Simulation:
    """A built, runnable simulation.

    With `mesh` set, hosts are block-partitioned over the 1-D "hosts" mesh
    axis (gid // per_shard = owning shard — the TPU-era version of the
    reference's host→thread assignment, scheduler.c:440-534) and run/step
    execute under shard_map: the window barrier is lax.pmin across shards
    and cross-shard packet delivery rides the engine's all_to_all exchange.
    """

    engine: Engine
    state0: Any  # EngineState
    stop_ns: int
    dns: DNS
    topo: Topology
    names: list[str]
    app: Any  # the AppModel instance
    stack: Stack
    mesh: Any = None  # jax.sharding.Mesh when sharded
    # requested SPMD lowering for the sharded paths: "auto" resolves via
    # parallel.mesh.select_spmd (shard_map on every supported jax;
    # "constraint" = jit + explicit NamedShardings over a GLOBAL engine,
    # GSPMD inserts the collectives; "pmap" = the legacy 1-D fallback,
    # kept alive for soak comparison). See `spmd_path` for the resolved
    # value and docs/12-Sharding.md for the selection matrix.
    spmd: str = "auto"
    pcap_gids: tuple = ()  # hosts with logpcap set
    pcap_dir: str = "shadow.pcap.d"  # from the pcapdir host attr
    kind_names: tuple = ()  # handler-kind names (object-counter labels)
    faults: Any = None  # CompiledFaults when the config schedules any
    # WindowProfiler (shadow_tpu.obs) when built with profiling on: the
    # jitted step phase is timed here (the un-jitted skeleton around it —
    # drains, pump, checkpoints — is timed by the CLI / process tier),
    # and summary() grows a "profile" key
    profiler: Any = None

    # queue-overflow handling (docs/9-Queue-Pressure.md): "drop" keeps
    # the historical counted-drop behavior (with strict_overflow's loud
    # RuntimeError), "strict" raises QueuePressureError at the first
    # drop, "spill"/"grow" run losslessly via the attached
    # PressureController (runtime.pressure) — run() then steps window by
    # window so the controller can harvest/refill at every boundary
    overflow: str = "drop"
    pressure: Any = None  # PressureController for spill/grow modes

    # the host permutation applied at build time (position i holds the
    # config host formerly known as gid host_order[i]): the locality
    # layout when `locality=True`, a checkpoint's stored order on
    # reshard-resume, None for plain config order. Recorded in v6
    # checkpoints so a resume on a DIFFERENT shard count can force the
    # writer's layout instead of recomputing a shard-count-dependent
    # locality_order (docs/13-Elastic-Recovery.md).
    host_order: tuple | None = None

    _jit_run: Any = None
    _jit_step: Any = None
    _jit_step_w: Any = None  # traced-window variant (--window auto)
    _owned: Any = None  # weak id-map of donation-safe states we produced

    @property
    def spmd_path(self) -> str | None:
        """The EXECUTED sharding path: None (single device), "shard_map",
        "constraint", or "pmap". This is what tests assert on — no
        jax.pmap runs unless this says so."""
        if self.mesh is None:
            return None
        from shadow_tpu.parallel.mesh import select_spmd

        return select_spmd(self.spmd)

    def _wrap(self, fn):
        """Jit `fn(state, stop, host0)`, under the selected SPMD path
        when sharded.

        The state argument is DONATED: the [H, C] queue arrays, staging
        buffers, and trace/spill rings alias the outputs instead of
        being copied on every call — which is once per *window* on the
        window-stepped paths (pressure boundaries, the process tier, the
        CLI heartbeat loop). Callers own the consequence: a state passed
        into run()/step_window() is consumed (its buffers are deleted),
        so `state0` is defended by copy in run()/step_window() and
        external callers must re-chain the returned state, never reuse
        the input. Donation changes only input/output aliasing, not the
        computation: `assert_zero_cost` HLO identities compare donated
        builds against donated builds and hold unchanged."""
        if self.mesh is None:
            return jax.jit(lambda st, stop: fn(st, stop, 0), donate_argnums=0)
        from jax.sharding import PartitionSpec as P

        from shadow_tpu.parallel.mesh import (
            hosts_axes, state_specs,
        )

        axes = hosts_axes(self.mesh)
        per = self.engine.cfg.n_hosts
        # state0 leaves are global-shaped; sharding splits the leading
        # host dim across the axis (or axis tuple for multi-slice)
        specs = state_specs(
            self.state0, per * self.engine.cfg.n_shards, axes
        )
        path = self.spmd_path

        if path == "pmap":
            from shadow_tpu.parallel.mesh import pmap_call

            # no donation on the pmap path: jax.pmap's donation is
            # per-device-buffer and interacts badly with the path's
            # reshape/stack plumbing; it is a soak path, not the perf
            # path
            return pmap_call(fn, self.mesh, specs, per, axes)

        if path == "constraint":
            # GSPMD path: the engine is GLOBAL (axis_name=None — it runs
            # no manual collectives), the state is pinned to the mesh by
            # explicit NamedShardings, and the partitioner inserts the
            # cross-device movement. Bit-identity with single-device is
            # structural: this IS the single-device program.
            from jax.sharding import NamedSharding

            shardings = jax.tree.map(
                lambda sp: NamedSharding(self.mesh, sp), specs
            )

            def constrained(st, stop):
                st = jax.lax.with_sharding_constraint(st, shardings)
                return fn(st, stop, 0)

            return jax.jit(
                constrained,
                in_shardings=(shardings, None),
                out_shardings=shardings,
                donate_argnums=0,
            )

        def sharded(st, stop):
            host0 = jax.lax.axis_index(axes).astype(jnp.int32) * per
            return fn(st, stop, host0)

        return jax.jit(
            jax.shard_map(
                sharded,
                mesh=self.mesh,
                in_specs=(specs, P()),
                out_specs=specs,
                check_vma=False,
            ),
            donate_argnums=0,
        )

    strict_overflow: bool = True

    def run(self, stop_ns: int | None = None, state=None):
        """Jit-run to the stop time; returns the final EngineState.

        The jitted callables are cached on the instance so repeated calls
        (the CLI's heartbeat loop, checkpoint-interval stepping) reuse one
        compiled executable instead of retracing.

        Queue overflow is loud by default: the reference's event heaps are
        unbounded (src/main/utility/priority_queue.c), so silently dropping
        events on a full fixed-capacity queue would corrupt simulation
        semantics mid-run. Set strict_overflow=False to accept counted
        drops instead (they remain visible in queues.drops).

        The jitted step DONATES its state input (see `_wrap`): a state
        passed via `state=` is consumed. `state0` itself is defended by
        a device-side copy so a Simulation stays re-runnable.
        """
        st = self._fresh_state(state)
        stop = jnp.int64(stop_ns if stop_ns is not None else self.stop_ns)
        if self.pressure is not None:
            # spill/grow: the controller must see every window boundary,
            # or an evicted event could miss the window it is due in —
            # so run window-stepped instead of one fused device loop.
            # The frontier probe and the controller's spill cursor fetch
            # share one batched device_get per window (the boundary's
            # idle probe would otherwise force a second round-trip).
            out = self._note_owned(st)
            stop_i = int(stop)
            now = int(jax.device_get(out.now))  # shadowlint: no-deadline=library run() path; the supervised CLI uses HeartbeatHarvest
            while now < stop_i:
                out = self.step_window(out, stop_i)
                now, wr = jax.device_get((out.now, out.queues.spill.wr))  # shadowlint: no-deadline=library run() path; the supervised CLI uses HeartbeatHarvest
                out = self._note_owned(
                    self.pressure.boundary(out, wr=np.asarray(wr))
                )
                now = int(now)
            return out
        if self._jit_run is None:
            object.__setattr__(self, "_jit_run", self._wrap(self.engine.run))
        if self.profiler is not None:
            with self.profiler.phase("step"):
                out = self._jit_run(st, stop)
                out.now.block_until_ready()  # shadowlint: no-deadline=library run() path; the supervised CLI uses HeartbeatHarvest
        else:
            out = self._jit_run(st, stop)
        out = self._note_owned(out)
        if self.overflow == "strict" or self.strict_overflow:
            drops = int(jax.device_get(out.queues.drops.sum()))  # shadowlint: no-deadline=library run() path; the supervised CLI uses HeartbeatHarvest
            if drops > 0:
                self.check_drops(drops, self.summary(out))
        return out

    def check_drops(self, drops: int, summary: dict | None = None):
        """Apply the loud-overflow contract to an already-fetched drop
        count. run() probes the count itself; the overlapped CLI loop
        reads it from its heartbeat-harvest bundle instead (the probe
        would be a second sync) and calls this with the fetched value."""
        if int(drops) <= 0:
            return
        if self.overflow == "strict":
            from shadow_tpu.runtime.pressure import QueuePressureError

            raise QueuePressureError(
                int(drops), self.engine.cfg.capacity, summary or {}
            )
        if self.strict_overflow:
            raise RuntimeError(
                f"event queue overflow: {int(drops)} events dropped "
                f"(per-host capacity {self.engine.cfg.capacity}); rerun "
                "with a larger --capacity, or set strict_overflow=False "
                "to accept counted drops"
            )

    def build_fleet(self, lanes: int, **overrides):
        """Batch `lanes` scenario variants of this simulation into one
        vmapped Fleet program — see the module-level `build_fleet`."""
        return build_fleet(self, lanes, **overrides)

    def dispatch(self, stop_ns: int, state, window_ns: int | None = None):
        """Asynchronously dispatch the next segment; returns the chained
        state WITHOUT any host<->device sync.

        The async half of the CLI's depth-1 dispatch-ahead: jax queues
        the computation on the backend and returns immediately, so the
        host can consume the previous heartbeat's fetched bundle while
        the device works. No profiler barrier (the CLI times the fetch
        wait instead), no overflow probe (`check_drops` runs on the
        harvest bundle's count). `window_ns` selects the traced-window
        step (one window per call — the adaptive controller decides
        between windows); None dispatches the fused run-to-stop loop.
        Pressure modes need run()'s window-boundary refills and are not
        dispatchable."""
        if self.pressure is not None:
            raise ValueError(
                "dispatch() cannot run spill/grow pressure modes; their "
                "reservoir refills are host-side window-boundary work — "
                "use run()"
            )
        st = self._fresh_state(state)
        stop = jnp.int64(stop_ns)
        if window_ns is None:
            if self._jit_run is None:
                object.__setattr__(
                    self, "_jit_run", self._wrap(self.engine.run)
                )
            return self._note_owned(self._jit_run(st, stop))
        self._ensure_step_w()
        return self._note_owned(
            self._jit_step_w(st, stop, jnp.int64(window_ns))
        )

    def _fresh_state(self, state):
        """Resolve the state argument for a donating jit call.

        Only states this Simulation itself produced (tracked weakly by
        identity) pass through to be donated in place — those are
        XLA-owned jit outputs, safe to alias. Everything else is copied
        first: `state0` so the Simulation stays re-runnable, and foreign
        states (checkpoint restores, test-built states) because
        `jnp.asarray` ZERO-COPIES aligned numpy arrays on CPU — donating
        such a leaf would let XLA write into (and alias outputs onto)
        memory numpy still owns, a use-after-free once the numpy side
        drops it. The copy is once per entry, never per window: chained
        step outputs are owned and flow through untouched."""
        if (
            state is not None
            and self._owned is not None
            and self._owned.get(id(state)) is state
        ):
            return state
        src = self.state0 if state is None else state
        return jax.tree.map(
            lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x, src
        )

    def _note_owned(self, state):
        """Mark `state` as a donation-safe product of this Simulation's
        own jits (see `_fresh_state`); returns it for chaining."""
        if self._owned is None:
            object.__setattr__(self, "_owned", weakref.WeakValueDictionary())
        self._owned[id(state)] = state
        return state

    def step_window(self, state, stop_ns: int | None = None,
                    window_ns: int | None = None):
        """Advance one window; the input state is consumed (donated).

        `window_ns` widens the conservative window bound past
        cfg.lookahead as a TRACED scalar — causally safe but with the
        --runahead timing tradeoff (core.engine._advance); the
        adaptive-window controller retunes it between windows with
        zero recompiles. None keeps the fixed cfg.lookahead bound, the
        byte-identical default lowering, and bit-identical results.
        """
        state = self._fresh_state(state)
        stop = jnp.int64(stop_ns if stop_ns is not None else self.stop_ns)
        if window_ns is None:
            if self._jit_step is None:
                object.__setattr__(
                    self, "_jit_step", self._wrap(self.engine.step_window)
                )
            args = (state, stop)
            jit_step = self._jit_step
        else:
            self._ensure_step_w()
            args = (state, stop, jnp.int64(window_ns))
            jit_step = self._jit_step_w
        if self.profiler is not None:
            with self.profiler.phase("step"):
                out = jit_step(*args)
                out.now.block_until_ready()  # shadowlint: no-deadline=library run() path; the supervised CLI uses HeartbeatHarvest
            return self._note_owned(out)
        return self._note_owned(jit_step(*args))

    def _ensure_step_w(self):
        """Build the traced-window step jit once (--window N / auto)."""
        if self._jit_step_w is not None:
            return
        if self.spmd_path == "pmap":
            raise ValueError(
                "adaptive windows (--window auto) need the shard_map or "
                "constraint SPMD path; the pmap fallback runs fixed "
                "windows only (selected spmd='pmap')"
            )
        if self.mesh is None:
            jsw = jax.jit(
                lambda st, stop, w: self.engine.step_window(
                    st, stop, 0, window=w
                ),
                donate_argnums=0,
            )
        else:
            jsw = self._wrap_windowed()
        object.__setattr__(self, "_jit_step_w", jsw)

    def _wrap_windowed(self):
        """shard_map wrapper for the traced-window step (mesh path)."""
        from jax.sharding import PartitionSpec as P

        from shadow_tpu.parallel.mesh import (
            hosts_axes, state_specs,
        )

        axes = hosts_axes(self.mesh)
        per = self.engine.cfg.n_hosts
        specs = state_specs(
            self.state0, per * self.engine.cfg.n_shards, axes
        )

        if self.spmd_path == "constraint":
            from jax.sharding import NamedSharding

            shardings = jax.tree.map(
                lambda sp: NamedSharding(self.mesh, sp), specs
            )

            def constrained(st, stop, w):
                st = jax.lax.with_sharding_constraint(st, shardings)
                return self.engine.step_window(st, stop, 0, window=w)

            return jax.jit(
                constrained,
                in_shardings=(shardings, None, None),
                out_shardings=shardings,
                donate_argnums=0,
            )

        def sharded(st, stop, w):
            host0 = jax.lax.axis_index(axes).astype(jnp.int32) * per
            return self.engine.step_window(st, stop, host0, window=w)

        return jax.jit(
            jax.shard_map(
                sharded,
                mesh=self.mesh,
                in_specs=(specs, P(), P()),
                out_specs=specs,
                check_vma=False,
            ),
            donate_argnums=0,
        )

    def summary(self, state) -> dict:
        """Host-side progress snapshot (frontier time, window count,
        executed events) — what the supervised run loop pets its
        watchdog with and the stall bundle records; see
        core.engine.state_summary. With a profiler attached, grows a
        "profile" key (wall-clock phase aggregates + occupancy —
        stripped from determinism diffs by tools/strip_log.py)."""
        from shadow_tpu.core.engine import state_summary

        out = state_summary(state)
        if self.profiler is not None:
            out["profile"] = self.profiler.summary()
        if self.pressure is not None:
            snap = self.pressure.snapshot(state)
            out["refilled"] = snap.get("refilled", 0)
            out["reservoir"] = snap.get("resident", 0)
            out["overdue"] = snap.get("overdue", 0)
        return out

    def metrics_refs(self, state) -> dict:
        """Device-array refs for the live-telemetry extras (net drops,
        fault drops, cross-shard traffic, socket byte totals) — the
        reductions `HeartbeatHarvest` embeds in its bundle under
        `--metrics`. Exposed here for the one-off fetch the CLI's
        --overflow grow re-template path does after rebuilding (the
        rebuilt harvest hasn't extracted yet at that boundary)."""
        from shadow_tpu.obs.metrics import metrics_device_refs

        return metrics_device_refs(state)


def _plugin_tokens(cfg: ShadowConfig, plugin_id: str) -> set[str]:
    """Registry-matchable name tokens for a plugin: its id plus its path
    basename, split on separators (the reference identifies plugins purely
    by id but test configs name them after their .so, e.g.
    'shadow-plugin-test-phold'). Whole-token matching keeps registry names
    like 'tor' from matching inside unrelated words ('monitor')."""
    import re

    spec = cfg.plugin_by_id(plugin_id)
    names = [plugin_id] + ([spec.path.rsplit("/", 1)[-1]] if spec else [])
    toks: set[str] = set()
    for n in names:
        toks.update(t for t in re.split(r"[^a-z0-9]+", n.lower()) if t)
    return toks


def resolve_app_models(
    cfg: ShadowConfig, registry: dict[str, Callable], hosts: list[HostInstance]
):
    """Map every host's processes to registered app models.

    Returns [(name, model_instance, owned_host_list)] in first-appearance
    order. A host whose processes span two different models is rejected
    (each host's state rows belong to exactly one model).
    """
    owner: dict[int, str] = {}
    order: list[str] = []
    for h in hosts:
        for p in h.spec.processes:
            toks = _plugin_tokens(cfg, p.plugin)
            for regname in registry:
                if regname in toks:
                    break
            else:
                raise ValueError(
                    f"no app model registered for plugin {p.plugin!r} "
                    f"(known: {sorted(registry)})"
                )
            if owner.setdefault(h.gid, regname) != regname:
                raise ValueError(
                    f"host {h.name!r} mixes app models "
                    f"{owner[h.gid]!r} and {regname!r}"
                )
            if regname not in order:
                order.append(regname)
    return [
        (name, registry[name](),
         [h for h in hosts if owner.get(h.gid) == name])
        for name in order
    ]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MultiApp:
    """Fused app state: every sub-model's [H]-leading state side by side,
    plus the per-host owning-model index for receive dispatch."""

    model_id: jax.Array  # i32[H]
    subs: tuple


class FusedModel:
    """Handler-table fusion of several app models (lifts the round-1
    one-model-per-simulation limit).

    Kinds are laid out [stack | model0 kinds | model1 kinds | ...]; each
    sub-model's handlers run against its own state slice (the rest of the
    MultiApp rides along untouched), and packet deliveries dispatch to the
    receiving host's owning model via lax.switch on model_id.
    """

    def __init__(self, parts):  # [(name, model, owned_hosts)]
        self.parts = parts
        self.name = "+".join(name for name, _, _ in parts)
        self.needs_tcp = any(m.needs_tcp for _, m, _ in parts)
        self.n_kinds = sum(m.n_kinds for _, m, _ in parts)

    def app_rows(self) -> int:
        return max(m.app_rows() for _, m, _ in self.parts)

    def handler_rows(self) -> int:
        return max(m.handler_rows() for _, m, _ in self.parts)

    def cpu_kind_cycles(self, n_kinds: int):
        """Sum the parts' per-(host, kind) cycle tables: a fused model
        must not silently drop a part's declared CPU charges (e.g. Tor
        relay crypto) — the accepted-but-ignored failure mode this
        codebase elsewhere hard-errors on. Each part's table is already
        host-masked (rows it doesn't own are zero), so summation is the
        exact composition."""
        total = None
        for _, m, _ in self.parts:
            if not hasattr(m, "cpu_kind_cycles"):
                continue
            cy = m.cpu_kind_cycles(n_kinds)
            if cy is None:
                continue
            total = cy if total is None else total + cy
        return total

    def build(self, b: SimBuild):
        n = b.n_hosts
        model_id = np.zeros((n,), np.int32)
        subs, makers, recvs = [], [], []
        offset = 0
        for i, (name, model, owned) in enumerate(self.parts):
            for h in owned:
                model_id[h.gid] = i
            sub_b_hosts = b.hosts
            b.hosts = owned
            b.kind_offset = offset
            state_i, make_i, recv_i = model.build(b)
            b.hosts = sub_b_hosts
            subs.append(state_i)
            makers.append(make_i)
            recvs.append(recv_i)
            offset += model.n_kinds
        b.kind_offset = 0
        self._recvs = recvs
        self._makers = makers
        state = MultiApp(
            model_id=jnp.asarray(model_id), subs=tuple(subs)
        )
        return state, self._make_handlers, self._on_recv

    def _sub_call(self, hs, i, fn, *args):
        """Run a sub-model callable against its own app-state slice."""
        hs_sub = dataclasses.replace(hs, app=hs.app.subs[i])
        out = fn(hs_sub, *args)
        hs2, em = out
        new_subs = tuple(
            hs2.app if j == i else hs.app.subs[j]
            for j in range(len(hs.app.subs))
        )
        hs2 = dataclasses.replace(
            hs2, app=MultiApp(model_id=hs.app.model_id, subs=new_subs)
        )
        return hs2, em

    def _make_handlers(self, stack, kind_base):
        rows = self.handler_rows()
        handlers = []
        offset = kind_base
        for i, ((name, model, _), make) in enumerate(
            zip(self.parts, self._makers)
        ):
            for fn in make(stack, offset):
                def wrapped(hs, ev, key, _i=i, _fn=fn):
                    hs2, em = self._sub_call(hs, _i, _fn, ev, key)
                    return hs2, em.pad_to(rows)
                handlers.append(wrapped)
            offset += model.n_kinds
        return handlers

    def _on_recv(self, hs, slot, pkt, now, key):
        rows = self.app_rows()
        branches = []
        for i, recv in enumerate(self._recvs):
            def mk(_i=i, _recv=recv):
                if _recv is None:
                    from shadow_tpu.core.engine import Emit

                    return lambda: (
                        hs, Emit.none(rows, N_PKT_ARGS)
                    )

                def br():
                    hs2, em = self._sub_call(
                        hs, _i, _recv, slot, pkt, now, key
                    )
                    return hs2, em.pad_to(rows)

                return br
            branches.append(mk())
        idx = jnp.clip(hs.app.model_id, 0, len(branches) - 1)
        return jax.lax.switch(idx, branches)


def build_simulation(
    cfg: ShadowConfig,
    registry: dict[str, Callable] | None = None,
    *,
    seed: int = 0,
    n_sockets: int = 8,
    capacity: int | None = None,
    app_model: Any = None,
    mesh: Any = None,
    tcp_cc: str = "reno",
    tcp_in_order: bool = True,
    tcp_wnd_words: int | None = None,
    rx_queue: str = "codel",
    qdisc: str = "fifo",
    interface_buffer: int = 1_024_000,
    tcp_child_slot_limit: int | None = None,
    locality: bool = False,
    runahead_ns: int | None = None,
    frontier: int = 0,
    fuse_rx: bool = True,
    burst_rx: bool = True,
    shape_bucket: bool = True,
    trace: int = 0,
    stats: int = 0,
    profiler: Any = None,
    overflow: str = "drop",
    spill_len: int = 0,
    spmd: str = "auto",
    host_order: Any = None,
) -> Simulation:
    """Config -> Simulation; pass a `jax.sharding.Mesh` (1-D "hosts" or
    2-D "dcn" x "hosts") to shard hosts.

    `spmd` selects the sharded lowering: "auto" resolves to shard_map
    (public or experimental — the engine's collective-free loop
    predicates make both safe), "constraint" builds ONE global engine
    and lets GSPMD partition it from explicit NamedShardings, "pmap"
    keeps the legacy 1-D fallback. See docs/12-Sharding.md.

    `locality=True` (sharded runs only) reorders hosts at build time so
    config-visible traffic partners share a shard, cutting cross-shard
    packet traffic (the static replacement for the reference's random
    host->thread shuffle + work stealing, scheduler.c:440-534,
    scheduler_policy_host_steal.c). Host gids and the `names` order then
    follow the locality layout, so single-vs-sharded comparisons must
    match hosts by NAME, not position.

    `host_order` (elastic resume, docs/13-Elastic-Recovery.md) forces an
    explicit host permutation instead of computing one: pass the order a
    v6 checkpoint was written under and the rebuilt gids match the
    checkpoint's leaves regardless of the new mesh's shard count. It
    overrides `locality` (the stored order already IS the writer's
    locality layout) and is legal on any mesh, including unsharded.

    `stats` (docs/15-Sim-Analytics.md) compiles the sim-time analytics
    plane into the window loop: device-side log2 histograms of event
    wait time, network latency, per-window host occupancy, queue fill,
    and frontier run length (`EngineState.splane`, harvested through
    the heartbeat bundle's single fetch). 0 (the default) is zero-cost:
    the lowered program is byte-identical to a stats-free build.

    `frontier` (docs/11-Performance.md, "Model-tier batching") selects
    the engine's third drain contract: per round each host's staged
    events sort once and a RUN of up to `frontier` equal-time same-kind
    events executes through a position fold that amortizes the chained
    drain's per-event bookkeeping. Results are bit-identical to
    `frontier=0` (the chained default). Requires a TCP stack with
    fuse_rx=True and a model that declares `frontier_safe` (every local
    emit scheduled at dt >= 1) — refused loudly otherwise.
    """
    from shadow_tpu.runtime.pressure import OVERFLOW_MODES

    if overflow not in OVERFLOW_MODES:
        raise ValueError(
            f"overflow must be one of {OVERFLOW_MODES}, got {overflow!r}"
        )
    if overflow in ("spill", "grow") and mesh is not None and (
        int(mesh.devices.size) > 1
    ):
        # the reservoir's window-boundary harvest would need a cross-
        # shard barrier protocol the controller doesn't speak yet; fail
        # loudly instead of silently losing events (repo-wide principle)
        raise ValueError(
            f"--overflow {overflow} is not supported on sharded meshes "
            "yet; use strict or drop (or run unsharded)"
        )
    if registry is None:
        registry = default_registry()
    topo = Topology.from_graphml(cfg.topology_source())
    hosts = expand_hosts(cfg)
    n_hosts = len(hosts)
    applied_order: tuple | None = None
    if host_order is not None:
        from shadow_tpu.parallel.partition import apply_order

        perm = [int(g) for g in host_order]
        if sorted(perm) != list(range(n_hosts)):
            raise ValueError(
                f"host_order must be a permutation of range({n_hosts}) — "
                "was the checkpoint written from the same config?"
            )
        hosts = apply_order(hosts, perm)
        applied_order = tuple(perm)
    elif locality and (mesh is None or int(mesh.devices.size) <= 1):
        # semantics-bearing options act or fail loudly (the repo-wide
        # config principle): locality without a multi-shard mesh would
        # silently change nothing
        raise ValueError("locality=True requires a multi-device mesh")
    elif locality and mesh is not None and int(mesh.devices.size) > 1:
        from shadow_tpu.parallel.partition import (
            apply_order,
            locality_order,
            traffic_edges_from_config,
        )

        edges = traffic_edges_from_config(hosts)
        perm = locality_order(
            n_hosts, edges, int(mesh.devices.size),
            dcn_slices=(mesh.devices.shape[0]
                        if mesh.devices.ndim == 2 else 1),
        )
        hosts = apply_order(hosts, perm)
        applied_order = tuple(perm)

    # -- shape bucketing: pad the host dimension to a standard ladder so
    # configs of nearby sizes COMPILE TO THE SAME XLA PROGRAM. Every
    # distinct (n_hosts, n_sockets, capacity, ...) tuple is otherwise a
    # fresh TPU compile of minutes; padded hosts are
    # inert (no processes, no events, default NICs), so they cost array
    # rows but no event traffic. The ladder doubles up to 1024 rows and
    # then steps by 1024 (bounded <=2x overhead below 1k hosts, <=10%
    # above), always honoring mesh divisibility.
    n_shards_req = int(mesh.devices.size) if mesh is not None else 1
    if shape_bucket:
        b_ = 16
        while b_ < n_hosts:
            b_ = b_ * 2 if b_ < 1024 else b_ + 1024
        if b_ % n_shards_req:
            b_ = ((b_ // n_shards_req) + 1) * n_shards_req
        n_hosts = max(b_, n_hosts)
    elif mesh is not None and n_hosts % n_shards_req:
        raise ValueError(
            f"{len(hosts)} hosts not divisible by mesh size "
            f"{n_shards_req} (enable shape_bucket to auto-pad)"
        )

    # -- attachment + DNS (master.c:307-345 registerHosts -> topology_attach,
    # dns_register)
    dns = DNS()
    host_vertex = []
    for h in hosts:
        s = h.spec
        v = topo.attach(
            ip_hint=s.iphint, citycode_hint=s.citycodehint,
            countrycode_hint=s.countrycodehint, geocode_hint=s.geocodehint,
            type_hint=s.typehint,
        )
        host_vertex.append(v)
        dns.register(h.gid, h.name, s.iphint or None)
    # bucket-padded rows attach to vertex 0; they originate no traffic
    host_vertex += [0] * (n_hosts - len(hosts))

    # -- NIC sizing: host attr overrides vertex attr (docs/3.1 host element)
    # defaults also give bucket-padded rows sane (never-exercised) NICs
    bw_up = np.full((n_hosts,), float(DEFAULT_BANDWIDTH_KIB), np.float64)
    bw_down = np.full((n_hosts,), float(DEFAULT_BANDWIDTH_KIB), np.float64)
    cpu_cost = np.zeros((n_hosts,), np.int64)
    cpu_khz = np.zeros((n_hosts,), np.int64)  # for per-kind model charges
    rcv_wnd_bytes = np.zeros((n_hosts,), np.int64)
    snd_buf_bytes = np.zeros((n_hosts,), np.int64)  # 0 = unlimited
    # NIC receive buffer bound (interfacebuffer host attr; reference
    # default 1024000 bytes, options.c:78 — CoDel acts long before a
    # megabyte of standing queue, so the default only bounds pathology)
    rx_buf = np.full((n_hosts,), interface_buffer, np.int64)
    pcap_mask = np.zeros((n_hosts,), bool)
    pcap_dirs: set[str] = set()
    proc_stop = np.full((n_hosts,), np.iinfo(np.int64).max, np.int64)
    for h, v in zip(hosts, host_vertex):
        vx = topo.vertices[v]
        s = h.spec
        bw_up[h.gid] = s.bandwidthup or vx.bandwidth_up_kib or DEFAULT_BANDWIDTH_KIB
        bw_down[h.gid] = (
            s.bandwidthdown or vx.bandwidth_down_kib or DEFAULT_BANDWIDTH_KIB
        )
        # semantics-bearing host attrs must act or fail loudly (round-1
        # accepted-and-ignored them, silently changing results)
        if s.cpufrequency:
            cpu_cost[h.gid] = CPU_CYCLES_PER_EVENT * 1_000_000 // s.cpufrequency
            cpu_khz[h.gid] = s.cpufrequency
        if s.socketrecvbuffer:
            rcv_wnd_bytes[h.gid] = s.socketrecvbuffer
        if s.socketsendbuffer:
            # bounded send buffer: bytes beyond the cap wait in the
            # TCB's app_pending and drain on ACK progress — the jitted
            # analog of the reference's blocking send against its
            # (autotuned) buffer, tcp.c:407-598
            snd_buf_bytes[h.gid] = s.socketsendbuffer
        if s.interfacebuffer:
            rx_buf[h.gid] = s.interfacebuffer
        if s.logpcap or s.pcapdir:
            pcap_mask[h.gid] = True
            if s.pcapdir:
                pcap_dirs.add(s.pcapdir)
        stops = {p.stoptime for p in s.processes if p.stoptime}
        if stops and not getattr(app_model, "owns_process_lifecycle", False):
            if len(s.processes) > 1 and (
                len(stops) > 1 or len(stops) < len(s.processes)
            ):
                # jitted app models collapse a host's processes into one
                # state row, so app-handler muting is per host; a partial
                # stop would silently kill the host's other processes
                # too. The process tier owns true per-process lifecycle
                # (each process is its own green thread) and opts out.
                raise ValueError(
                    f"host {h.name!r}: all processes on a host must share "
                    "one stoptime (per-process stop needs the real-binary "
                    "tier, whose processes are individual green threads)"
                )
            proc_stop[h.gid] = int(stops.pop() * SECOND)

    if app_model is not None:
        model = app_model
    else:
        parts = resolve_app_models(cfg, registry, hosts)
        model = parts[0][1] if len(parts) == 1 else FusedModel(parts)
    if snd_buf_bytes.any() and not model.needs_tcp:
        # semantics-bearing attrs act or fail loudly: without a TCP
        # stack there is no send buffer for the cap to bound
        raise ValueError(
            "socketsendbuffer is set but the app model "
            f"{model.name!r} runs no TCP stack; remove the attribute"
        )
    if capacity is None:
        # every in-flight packet occupies a destination queue slot, so a
        # TCP host must hold a full receive window (64*WND_WORDS segs)
        # plus timers/app events; non-TCP models need far less. The +64
        # headroom covers the fused rx path's earlier ACK clock (windows
        # open sooner, so bursts overlap slightly more in flight).
        from shadow_tpu.transport.tcp import WND_WORDS

        capacity = 64 * WND_WORDS * 2 + 64 if model.needs_tcp else 256
    net = HostNet.create(
        n_hosts, n_sockets, jnp.asarray(bw_up), jnp.asarray(bw_down),
        with_tcp=model.needs_tcp,
        rcv_wnd_bytes=rcv_wnd_bytes if rcv_wnd_bytes.any() else None,
        wnd_words=tcp_wnd_words,
        rx_buf_bytes=jnp.asarray(rx_buf),
        snd_buf_bytes=snd_buf_bytes if snd_buf_bytes.any() else None,
    )
    if pcap_mask.any():
        from shadow_tpu.utils.pcap import CaptureRing

        net = dataclasses.replace(
            net, cap=CaptureRing.create(jnp.asarray(pcap_mask))
        )

    b = SimBuild(
        cfg=cfg, hosts=hosts, dns=dns, topo=topo, n_sockets=n_sockets,
        sockets=net.sockets, tcb=net.tcb, n_total=n_hosts,
    )
    app_state, make_handlers, on_recv = model.build(b)
    net = dataclasses.replace(net, sockets=b.sockets, tcb=b.tcb)

    bootstrap_end = int(cfg.bootstraptime * SECOND)
    # config-driven sims get strict byte-stream delivery order (the
    # reference's apps read in-order streams); raw-engine users can still
    # build TCP(in_order=False) for on-arrival accounting.
    # qdisc 'rr' (options.c interface-qdisc): one segment per tx kick, so
    # contending connections strictly alternate through the shared NIC
    # virtual clock — round-robin at packet granularity. 'fifo' (default)
    # keeps burst transmission; admission follows the event total order,
    # which *is* packet-creation order (the reference's FIFO qdisc sorts
    # on a host-monotonic creation counter, packet.c:87-88; its single
    # exception — control packets stamped priority 0.0 to jump the
    # queue, tcp.c:844 — is immaterial here because pure ACKs ride
    # their own events through the same total order rather than a
    # shared tx backlog).
    if qdisc not in ("fifo", "rr"):
        raise ValueError(f"unknown qdisc {qdisc!r}")
    tcp_kw = dict(tx_burst=1, inline_budget=1) if qdisc == "rr" else {}
    # a restarted host has lost all connection state, so survivors'
    # segments to it must draw an RST (the kernel's answer to a segment
    # for no socket) rather than blackholing until RTO exhaustion
    have_crash_faults = any(
        f.type in ("crash", "churn") for f in cfg.faults
    )
    if have_crash_faults:
        tcp_kw["rst_on_unmatched"] = True
    tcp = (
        TCP(auto_close=False, cc=tcp_cc, in_order=tcp_in_order,
            child_slot_limit=tcp_child_slot_limit, **tcp_kw)
        if model.needs_tcp else None
    )
    # fuse_rx folds the per-packet ARRIVE->RX double event into one
    # (stack.py Stack docstring): output timing exact, state-read timing
    # early by the rx serialization delay, half the sequential depth in
    # the drain. On by default — the per-packet event pair is the
    # dominant chain in every TCP workload.
    stack = Stack(bootstrap_end=bootstrap_end, tcp=tcp, rx_queue=rx_queue,
                  fuse_rx=fuse_rx)

    if on_recv is None:
        def on_recv(hs, slot, pkt, now, key):  # noqa: F811
            from shadow_tpu.core.engine import Emit
            return hs, Emit.none(1, N_PKT_ARGS)

    # <process stoptime>: a stopped process's callbacks never run again
    # (the reference kills the plugin; its sockets keep the kernel-side
    # teardown going — here the stack/TCP handlers likewise continue)
    if (proc_stop < np.iinfo(np.int64).max).any():
        stop_arr = jnp.asarray(proc_stop)

        def _dead_select(hs, hs2, em, dead):
            hs_out = jax.tree.map(lambda a, b: jnp.where(dead, a, b), hs, hs2)
            return hs_out, dataclasses.replace(em, mask=em.mask & ~dead)

        def _mute_handler(fn):
            def wrapped(hs, ev, key):
                hs2, em = fn(hs, ev, key)
                return _dead_select(hs, hs2, em, ev.time >= stop_arr[ev.dst])

            return wrapped

        # recv-muting needs the lane's host id from the app state. A model
        # may declare it via a `lane_gid(app_state_slice)` method (the
        # AppModel-level contract); the fallback sniffs the conventional
        # `gid` field every bundled model carries. Fail at build time, not
        # trace time, when neither resolves.
        if hasattr(model, "lane_gid"):
            _lane_gid = model.lane_gid
        else:
            def _gid_resolvable(app):
                return hasattr(app, "gid") or any(
                    hasattr(sub, "gid") for sub in getattr(app, "subs", ())
                )

            if not _gid_resolvable(app_state):
                raise ValueError(
                    "process stoptime needs the app model to define "
                    "lane_gid(app_state) or carry a gid field "
                    f"(model {model.name!r} has neither)"
                )

            def _lane_gid(app):
                if hasattr(app, "gid"):
                    return app.gid
                for sub in app.subs:
                    if hasattr(sub, "gid"):
                        return sub.gid
                raise AssertionError  # unreachable: checked at build

        def _mute_recv(fn):
            def wrapped(hs, slot, pkt, now, key):
                hs2, em = fn(hs, slot, pkt, now, key)
                dead = now >= stop_arr[_lane_gid(hs.app)]
                return _dead_select(hs, hs2, em, dead)

            return wrapped

        make_inner = make_handlers

        def make_handlers(stack_, kind_base_):  # noqa: F811
            return [_mute_handler(fn) for fn in make_inner(stack_, kind_base_)]

        on_recv = _mute_recv(on_recv) if on_recv is not None else None

    base_handlers = stack.make_handlers(on_recv)
    kind_base = len(base_handlers)
    handlers = base_handlers + make_handlers(stack, kind_base)
    # handler-kind labels for the per-kind executed-event counters (the
    # reference's ObjectCounter type names, object_counter.h:13-27)
    kind_names = ["pkt_arrive", "pkt_rx"]
    if tcp is not None:
        kind_names += ["tcp_timer", "tcp_tx"]
    if isinstance(model, FusedModel):
        for name, sub, _ in model.parts:
            kind_names += [f"{name}.{i}" for i in range(sub.n_kinds)]
    else:
        kind_names += [f"{model.name}.{i}" for i in range(model.n_kinds)]
    if len(kind_names) != len(handlers):
        raise AssertionError(
            f"kind label table ({len(kind_names)}) out of sync with the "
            f"handler table ({len(handlers)}); update the names above "
            "alongside Stack.make_handlers/model kinds"
        )

    if tcp is not None:
        need = tcp.min_max_emit(model.app_rows())
    else:
        need = model.app_rows() + 1
    max_emit = max(need, model.handler_rows())

    # conservative window width: the topology's minimum path latency by
    # default, overridable by the user (the reference exposes the same
    # knob as --runahead / minTimeJump, options.c; master.c:133-159).
    # Wider than min latency is SAFE for causality — cross-host arrivals
    # are clamped up to the window barrier (engine._route), exactly the
    # reference's barrier clamp — it just coarsens packet timing by up
    # to the window width, the documented runahead tradeoff.
    if runahead_ns is not None:
        if runahead_ns < 1:
            raise ValueError(f"runahead must be >= 1 ns, got {runahead_ns}")
        lookahead = runahead_ns
    else:
        lookahead = max(int(topo.min_latency_ms * MILLISECOND), 1)
    spmd_path = None
    if mesh is not None:
        from shadow_tpu.parallel.mesh import hosts_axes, select_spmd

        spmd_path = select_spmd(spmd)
        n_shards = int(mesh.devices.size)
        if n_hosts % n_shards:
            raise ValueError(
                f"{n_hosts} hosts not divisible by mesh size {n_shards}"
            )
        per_shard = n_hosts // n_shards
        axis_name = hosts_axes(mesh)
        if spmd_path == "constraint":
            # GSPMD partitions ONE global program: the engine runs no
            # manual collectives (axis_name=None), sees every host, and
            # the mesh enters only through _wrap's NamedShardings
            n_shards, per_shard, axis_name = 1, n_hosts, None
    else:
        n_shards, per_shard, axis_name = 1, n_hosts, None
    # burst delivery (engine._burst_fold): contiguous same-flow TCP
    # arrivals staged in one sweep collapse into multi-segment events.
    # The chained drain's wall time is (busiest host's sequential event
    # count) x (full handler-pass cost), and steady-state TCP data
    # bursts dominate that count. Requires fuse_rx (the delivery runs
    # inside the arrival) and the TCP stack. Timing of absorbed
    # segments coarsens by at most one window; loss fidelity is exact
    # (reliability rolls happened at send time).
    burst = None
    if burst_rx and fuse_rx and tcp is not None and pcap_mask.any():
        # burst folding collapses contiguous same-flow arrivals into one
        # multi-segment event, so the capture ring would record one
        # merged frame where the reference writes N — silently coarser
        # pcaps. Capture fidelity wins over drain depth.
        import warnings

        warnings.warn(
            "burst_rx disabled: pcap capture is enabled and burst "
            "folding would merge captured segments (pass burst_rx=False "
            "to silence)",
            stacklevel=2,
        )
        burst_rx = False
    if burst_rx and fuse_rx and tcp is not None:
        from shadow_tpu.transport.stack import (
            A_ACK, A_AUX, A_DPORT, A_LEN, A_META, A_SACK0, A_SACK1,
            A_SEQ, A_SPORT, A_WND, F_FIN, F_RST, F_SYN, KIND_PKT_ARRIVE,
        )
        from shadow_tpu.host.sockets import PROTO_TCP
        from shadow_tpu.transport.tcp import MSS

        burst = (KIND_PKT_ARRIVE, A_SEQ, A_LEN, A_SPORT, A_DPORT, A_META,
                 int(PROTO_TCP), int(F_SYN | F_FIN | F_RST), int(MSS),
                 (A_ACK, A_WND, A_AUX, A_SACK0, A_SACK1))
    from shadow_tpu.transport.stack import A_LEN as _A_LEN

    # spill ring sizing: default 4x capacity of record slots absorbs the
    # worst bursts seen in the skew benchmarks with room to spare; the
    # ring reports (never hides) overflow via n_lost if undersized
    spill = 0
    if overflow in ("spill", "grow"):
        spill = int(spill_len) if spill_len > 0 else 4 * capacity
    # frontier drain eligibility: the run rule is only exact when every
    # LOCAL emit lands at dt >= 1 (engine._drain_window_frontier). The
    # unfused ARRIVE->RX re-emit violates it (dt can be 0 in bootstrap),
    # and a model with zero-valued pause/interval tables would too — so
    # the knob demands fuse_rx + an explicit model-side declaration.
    frontier_kinds = None
    if frontier:
        if tcp is None or not fuse_rx:
            raise ValueError(
                "frontier batching requires the TCP stack with "
                "fuse_rx=True (the unfused ARRIVE->RX re-emit can land "
                "at dt=0, breaking the run rule's dt >= 1 invariant)"
            )
        if not getattr(model, "frontier_safe", False):
            raise ValueError(
                f"model {model.name!r} does not declare frontier_safe "
                "(its local emit delays are not provably >= 1 ns for "
                "this config); run with frontier=0"
            )
        frontier_kinds = stack.frontier_kinds() + tuple(
            kind_base + int(i) for i in model.frontier_kinds()
        )
    ecfg = EngineConfig(
        n_hosts=per_shard, capacity=capacity, lookahead=lookahead,
        max_emit=max_emit, n_args=N_PKT_ARGS, seed=seed,
        axis_name=axis_name, n_shards=n_shards, burst=burst,
        trace=int(trace), trace_len_arg=int(_A_LEN),
        spill=spill, frontier=int(frontier), stats=int(stats),
    )
    network = topo.build_network(host_vertex)
    # per-KIND CPU charges: a model may declare cycle costs for specific
    # event kinds (e.g. Tor relay crypto per delivered segment); they
    # convert to virtual-CPU ns on hosts with a cpufrequency and stack on
    # the uniform per-event cost (the reference charges measured plugin
    # time per task, cpu.c:56-107 — per-kind tables are the jitted analog)
    cost_arg = cpu_cost
    if fuse_rx and cpu_cost.any():
        # the fused KIND_PKT_ARRIVE event executes the delivery too, so
        # it pays BOTH halves of the uniform per-event charge — keeping
        # CPU-model timing aligned with the unfused two-event pipeline.
        # (Remaining documented divergence: a packet dropped at the rx
        # queue still pays the delivery half here, where unfused mode
        # would never execute its KIND_PKT_RX event.)
        from shadow_tpu.transport.stack import KIND_PKT_ARRIVE

        cost_arg = np.broadcast_to(
            cpu_cost[:, None], (n_hosts, len(handlers))
        ).copy()
        cost_arg[:, KIND_PKT_ARRIVE] += cpu_cost
    if hasattr(model, "cpu_kind_cycles"):
        cycles = model.cpu_kind_cycles(len(handlers))
        if cycles is not None and cpu_khz.any():
            if fuse_rx:
                # deliveries execute inside KIND_PKT_ARRIVE when fused —
                # move any per-delivery charge (e.g. Tor relay crypto at
                # KIND_PKT_RX) onto the kind that actually runs, or the
                # CPU model would silently stop charging it
                from shadow_tpu.transport.stack import (
                    KIND_PKT_ARRIVE, KIND_PKT_RX,
                )

                cycles = np.array(cycles, copy=True)
                cycles[:, KIND_PKT_ARRIVE] += cycles[:, KIND_PKT_RX]
                cycles[:, KIND_PKT_RX] = 0
            extra_ns = np.where(
                cpu_khz[:, None] > 0,
                cycles * 1_000_000 // np.maximum(cpu_khz[:, None], 1),
                0,
            )
            base = (
                cost_arg if cost_arg.ndim == 2 else cpu_cost[:, None]
            )
            cost_arg = base + extra_ns
    hosts_state = SimHost(net=net, app=app_state)

    faults = None
    if cfg.faults:
        from shadow_tpu.faults import compile_faults

        name_by_gid = [""] * n_hosts
        for h in hosts:
            name_by_gid[h.gid] = h.name
        faults = compile_faults(cfg.faults, name_by_gid, n_hosts, seed)
    eng = Engine(
        ecfg, handlers, network,
        cpu_cost=jnp.asarray(cost_arg) if cost_arg.any() else None,
        faults=faults,
        # the initial hosts pytree doubles as the restart template: a
        # crashed-and-restarted host comes back with boot-fresh state
        # (listen sockets rebound, app state re-zeroed)
        fault_reset=hosts_state if faults is not None else None,
        frontier_kinds=frontier_kinds,
    )

    # -- initial events: process starts (slave.c:296-336 scheduling of
    # process start tasks at starttime)
    evs = b.start_events
    m = max(len(evs), 1)
    init = Events.empty((m,), n_args=N_PKT_ARGS)
    times = np.full((m,), TIME_INVALID, np.int64)
    dsts = np.zeros((m,), np.int32)
    seqs = np.zeros((m,), np.int32)
    kinds = np.zeros((m,), np.int32)
    argw = np.zeros((m, N_PKT_ARGS), np.int32)
    per_src_seq: dict[int, int] = {}
    for i, (t_ns, gid, kind_rel, args) in enumerate(evs):
        times[i] = t_ns
        dsts[i] = gid
        seqs[i] = per_src_seq.get(gid, 0)
        per_src_seq[gid] = seqs[i] + 1
        kinds[i] = kind_base + kind_rel
        for j, w in enumerate(args):
            argw[i, j] = w
    init = dataclasses.replace(
        init,
        time=jnp.asarray(times), dst=jnp.asarray(dsts),
        src=jnp.asarray(dsts), seq=jnp.asarray(seqs),
        kind=jnp.asarray(kinds), args=jnp.asarray(argw),
    )

    if mesh is None or spmd_path == "constraint":
        # constraint path: the global init IS the single-device init;
        # _wrap's in_shardings spread it over the mesh on first call
        st0 = eng.init_state(hosts_state, init)
    else:
        # build the initial state under shard_map: each shard slices its
        # host-state rows and keeps only its own initial events (the push
        # ignores out-of-shard destinations)
        from jax.sharding import PartitionSpec as P

        from shadow_tpu.parallel.mesh import (
            hosts_axes, state_specs,
        )

        axes = hosts_axes(mesh)
        hspecs = jax.tree.map(lambda _: P(axes), hosts_state)

        def init_shard(hslice):
            host0 = jax.lax.axis_index(axes).astype(jnp.int32) * per_shard
            return eng.init_state(hslice, init, host0)

        slice_shapes = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(
                (per_shard,) + l.shape[1:], l.dtype
            ),
            hosts_state,
        )
        template = jax.eval_shape(
            lambda hs: eng.init_state(hs, init, 0), slice_shapes
        )
        ospecs = state_specs(template, per_shard, axes)
        st0 = jax.jit(
            jax.shard_map(
                init_shard,
                mesh=mesh,
                in_specs=(hspecs,),
                out_specs=ospecs,
                check_vma=False,
            )
        )(hosts_state)
    if len(pcap_dirs) > 1:
        raise ValueError(
            f"hosts disagree on pcapdir ({sorted(pcap_dirs)}); captures "
            "share one directory per run"
        )
    pressure = None
    if overflow in ("spill", "grow"):
        from shadow_tpu.runtime.pressure import PressureController

        pressure = PressureController(
            n_hosts, capacity, lookahead, mode=overflow,
            n_args=N_PKT_ARGS,
        )
    return Simulation(
        engine=eng, state0=st0, stop_ns=int(cfg.stoptime * SECOND),
        dns=dns, topo=topo, names=[h.name for h in hosts], app=model,
        stack=stack, mesh=mesh, spmd=spmd,
        pcap_gids=tuple(int(g) for g in np.nonzero(pcap_mask)[0]),
        pcap_dir=(pcap_dirs.pop() if pcap_dirs else "shadow.pcap.d"),
        kind_names=tuple(kind_names),
        faults=faults,
        profiler=profiler,
        overflow=overflow,
        pressure=pressure,
        host_order=applied_order,
    )


def build_fleet(sim: Simulation, lanes: int, **overrides):
    """Batch `lanes` variants of a built scenario into one Fleet.

    Per-lane knobs (`seeds`, `faults`, `latency_scale`,
    `bandwidth_scale`, `state_override` — see runtime.fleet.FleetPlan)
    become traced inputs of ONE jitted vmapped window loop; static
    compile-time knobs (kernel/frontier/window/capacity/...) must stay
    uniform and are rejected with the reason. The fleet's stacked
    `[L, ...]` state donates through every segment exactly like the
    solo `Simulation` jits, and `HeartbeatHarvest` drives it through
    the same single-fetch path. docs/16-Scenario-Fleets.md has the
    lane-semantics table.
    """
    from shadow_tpu.runtime.fleet import build_fleet_from_engine

    if sim.mesh is not None:
        raise ValueError(
            "fleets vmap the single-device engine; a sharded base "
            "scenario is not supported — shard across fleet replicas "
            "instead (one fleet per device group)"
        )
    if sim.pressure is not None:
        raise ValueError(
            "fleets cannot run spill/grow pressure modes; their "
            "reservoir refills are host-side per-window work that "
            "cannot ride one fused vmapped program — use --overflow "
            "drop/strict for fleet runs"
        )
    fleet = build_fleet_from_engine(
        sim.engine, sim.state0, lanes, names=sim.names,
        stop_ns=sim.stop_ns, **overrides,
    )
    fleet.strict_overflow = sim.strict_overflow or sim.overflow == "strict"
    return fleet


def default_registry() -> dict[str, Callable]:
    from shadow_tpu.models.bitcoin import BitcoinModel
    from shadow_tpu.models.phold_net import PholdNetModel
    from shadow_tpu.models.tgen import TGenModel
    from shadow_tpu.models.tor import TorModel

    return {
        "tgen": TGenModel,
        "phold": PholdNetModel,
        "tor": TorModel,
        "bitcoin": BitcoinModel,
    }
