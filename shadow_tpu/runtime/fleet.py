"""Scenario fleets: vmap the whole engine over scenario parameters.

The zero-cost discipline makes the compiled window loop a pure function
of (state, RNG root key, fault-schedule arrays, topology tables) — so a
seed × fault × topology sweep does not need N compiles and N sequential
dispatches. A `Fleet` stacks L lane states into one `[L, ...]`-leading
pytree, binds the per-lane scenario knobs as traced inputs
(`Engine.bind_lane`), and drives the existing window loop through
`jax.vmap` as ONE jitted, donation-preserving program. Chained, batched,
and frontier drain contracts all ride along unchanged — they are just
the body of the vmapped `run`.

Lane semantics (docs/16-Scenario-Fleets.md):

- MAY vary per lane: RNG seed, fault schedule, a global latency scale
  (integer per-mille, applied before the window-barrier clamp), a NIC
  bandwidth scale (state-side, NIC-modelled hosts only), and arbitrary
  array-valued initial-state overrides (`state_override`).
- MUST be uniform: every static compile-time knob — frontier, window
  policy, capacity, host count, drain batch, trace/stats/spill depth.
  One fleet is one lowered program; sweeping a static knob means
  building separate fleets. Violations raise with the knob named.

Termination masking comes from JAX itself: vmapping `lax.while_loop`
runs the body while ANY lane's predicate holds and select-masks each
lane's carry with its own predicate, so a finished lane's windows are
no-ops (its `_next_time` is TIME_INVALID and its state stops updating)
while the fleet runs until the last lane stops.

Per-lane bit-identity (tests/test_fleet.py) rests on three facts:
`rng.root_key(seed)` traced vs static yields the same key values; a
padded fault schedule is values-neutral (`_T_INF` epoch sentinels are
never reached, `lat * LAT_UNIT // LAT_UNIT` is exact for integer
latencies, a pass probability of 1.0 never drops because uniforms live
in [0, 1)); and the per-event RNG is counter-based, so the extra fault
roll lanes consume no shared stream state.
"""

from __future__ import annotations

import dataclasses
import weakref
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from shadow_tpu.core import rng as srng
from shadow_tpu.faults.schedule import (
    LAT_UNIT,
    _T_INF,
    CompiledFaults,
    compile_faults,
)

# knobs that are compile-time constants of the one lowered program; named
# here so the rejection error can say WHY a per-lane value cannot exist
STATIC_KNOBS = (
    "frontier", "window", "capacity", "lookahead", "drain_batch", "n_hosts",
    "max_emit", "n_args", "trace", "stats", "spill", "batched", "overflow",
    "mesh", "n_shards", "stage_width", "route_bucket", "hot_hosts",
    "hot_weight", "msgs_per_host", "latency_ns", "mean_delay_ns",
)

LANE_KNOBS = ("seeds", "faults", "latency_scale", "bandwidth_scale",
              "state_override")


def check_lane_knobs(overrides: dict) -> None:
    """Reject overrides that are not per-lane-capable, loudly."""
    for k in overrides:
        if k in LANE_KNOBS:
            continue
        if k in STATIC_KNOBS:
            raise ValueError(
                f"per-lane {k!r} is a static compile-time knob: a fleet "
                "shares ONE lowered program, so it must be uniform "
                "across lanes — set it on the base scenario and build "
                "separate fleets to sweep it"
            )
        raise ValueError(
            f"unknown fleet override {k!r}; per-lane knobs are "
            f"{LANE_KNOBS}"
        )


@dataclasses.dataclass(frozen=True)
class FleetPlan:
    """Per-lane overrides for a scenario fleet.

    Every sequence field has exactly `lanes` entries (validated);
    `None` means "no override" — all lanes inherit the base scenario.

    seeds           per-lane RNG seeds (default: base cfg.seed for all).
    faults          per-lane fault-spec tuples (entry None/() = no
                    faults for that lane). Replaces, never merges with,
                    a base-scenario schedule.
    latency_scale   per-lane multiplier on every path latency, applied
                    as integer per-mille BEFORE the window-barrier
                    clamp (use `scaled_network` for an exact solo
                    equivalent).
    bandwidth_scale per-lane multiplier on NIC rates (state-side;
                    requires a NIC-modelled host tier).
    state_override  fn(lane, state0) -> state0 for arbitrary per-lane
                    array-valued model parameters.
    """

    lanes: int
    seeds: tuple | None = None
    faults: tuple | None = None
    latency_scale: tuple | None = None
    bandwidth_scale: tuple | None = None
    state_override: Callable[[int, Any], Any] | None = None

    def __post_init__(self):
        if self.lanes < 1:
            raise ValueError(f"a fleet needs >= 1 lane, got {self.lanes}")
        for nm in ("seeds", "faults", "latency_scale", "bandwidth_scale"):
            v = getattr(self, nm)
            if v is None:
                continue
            v = tuple(v)
            object.__setattr__(self, nm, v)
            if len(v) != self.lanes:
                raise ValueError(
                    f"plan.{nm} has {len(v)} entries for {self.lanes} "
                    "lanes"
                )
        if self.latency_scale is not None:
            for s in self.latency_scale:
                if s < 0:
                    raise ValueError(f"latency_scale {s} < 0")
        if self.bandwidth_scale is not None:
            for s in self.bandwidth_scale:
                if s <= 0:
                    raise ValueError(f"bandwidth_scale {s} <= 0")


class ScaledLatencyNetwork:
    """Scale a base topology's path latency by integer per-mille.

    The scale may be a traced scalar (a fleet lane bind). Integer
    fixed-point keeps the identity lane exact: `lat * LAT_UNIT //
    LAT_UNIT == lat` for every int64 latency, so a lane with scale 1.0
    lowers to different HLO but computes identical values — and a solo
    run wrapped in the same class reproduces a scaled lane bit-exactly.
    """

    def __init__(self, base, lat_milli):
        self._base = base
        self._lat_milli = lat_milli
        self.has_jitter = bool(getattr(base, "has_jitter", False))

    def route(self, src, dst):
        lat, rel, jit = self._base.route(src, dst)
        return lat * self._lat_milli // LAT_UNIT, rel, jit


def scaled_network(base, scale: float) -> ScaledLatencyNetwork:
    """The solo-run equivalent of a fleet lane's latency_scale."""
    return ScaledLatencyNetwork(base, jnp.int64(int(round(scale * LAT_UNIT))))


def _pad_faults(comp: list[CompiledFaults], tmax: int | None = None,
                gmax: int | None = None):
    """Pad per-lane CompiledFaults to one uniform shape and stack.

    Values-neutral by construction: epoch-time pads are `_T_INF` (never
    reached, so `epoch_of` is unchanged for real times), alive pads are
    True, latency pads are LAT_UNIT (1.0x), pass pads are 1.0, and
    bandwidth pads are 1.0. Returns ({bind arrays [L, ...]}, flags).

    `tmax`/`gmax` optionally force MINIMUM epoch/group padding targets:
    the serving plane pins them per program-cache class so every batch
    in the class binds identically-shaped fault arrays and reuses one
    compiled fleet program (shapes are static; a schedule exceeding the
    target simply widens it, which is a different equivalence class).
    """
    t_need = max(f.np_times.shape[0] for f in comp)
    g_need = max(int(f.lat_milli.shape[1]) for f in comp)
    tmax = t_need if tmax is None else max(int(tmax), t_need)
    gmax = g_need if gmax is None else max(int(gmax), g_need)
    hg = int(comp[0].alive.shape[1])
    times, alive, fgrp, lat, passp, bw = [], [], [], [], [], []
    for f in comp:
        t = int(f.np_times.shape[0])
        g = int(f.lat_milli.shape[1])
        times.append(np.concatenate(
            [np.asarray(f.np_times),
             np.full((tmax - t,), _T_INF, np.int64)]))
        alive.append(np.concatenate(
            [np.asarray(f.alive),
             np.ones((tmax - t, hg), bool)], axis=0))
        fgrp.append(np.asarray(f.fgrp))
        la = np.full((tmax, gmax, gmax), LAT_UNIT, np.int64)
        la[:t, :g, :g] = np.asarray(f.lat_milli)
        lat.append(la)
        pp = np.ones((tmax, gmax, gmax), np.float32)
        pp[:t, :g, :g] = np.asarray(f.passp)
        passp.append(pp)
        bw.append(np.concatenate(
            [np.asarray(f.bw_scale),
             np.ones((tmax - t, hg), np.float32)], axis=0))
    binds = {
        "f_times": jnp.asarray(np.stack(times)),
        "f_alive": jnp.asarray(np.stack(alive)),
        "f_fgrp": jnp.asarray(np.stack(fgrp)),
        "f_lat": jnp.asarray(np.stack(lat)),
        "f_passp": jnp.asarray(np.stack(passp)),
        "f_bw": jnp.asarray(np.stack(bw)),
    }
    flags = (
        any(f.has_crash for f in comp),
        any(f.has_link for f in comp),
        any(f.has_bw for f in comp),
    )
    return binds, flags


def _lane_sum(x):
    return x.sum(axis=tuple(range(1, x.ndim)))


def _lane_max(x):
    return x.max(axis=tuple(range(1, x.ndim)))


def lane_summary_refs(state) -> dict:
    """Per-lane device reductions over a stacked `[L, ...]` state,
    mirroring `core.engine.state_summary`'s keys exactly — each value
    is an `[L]` array. This is what the harvest fleet path embeds in
    its single-fetch bundle."""
    out = {
        "now_ns": state.now,
        "windows": state.stats.n_windows,
        "executed": _lane_sum(state.stats.n_executed),
        "sweeps": state.stats.n_sweeps,
        "queue_drops": _lane_sum(state.queues.drops),
    }
    ring = state.queues.spill
    if ring is not None:
        out["spilled"] = _lane_sum(ring.n_spilled)
        out["spill_lost"] = _lane_sum(ring.n_lost)
        out["fill_hwm"] = _lane_max(ring.fill_hwm)
    return out


def lane_summaries_from(fetched: dict) -> list[dict]:
    """Split fetched `[L]`-valued summary arrays into per-lane dicts —
    each bit-identical to the solo run's `state_summary`."""
    lanes = int(np.asarray(fetched["now_ns"]).shape[0])
    return [
        {k: int(np.asarray(v)[i]) for k, v in fetched.items()}
        for i in range(lanes)
    ]


def aggregate_summary(fetched: dict) -> dict:
    """One fleet-level progress dict from `[L]` summary arrays: clock
    is the SLOWEST lane (the fleet runs until the last lane stops),
    event totals sum, loop counters take the deepest lane."""
    out = {}
    for k, v in fetched.items():
        a = np.asarray(v)
        if k == "now_ns":
            out[k] = int(a.min())
        elif k in ("windows", "sweeps", "fill_hwm"):
            out[k] = int(a.max())
        else:
            out[k] = int(a.sum())
    return out


class Fleet:
    """L scenario lanes lowered as one donation-preserving program.

    Duck-types the slice of `Simulation` the harvest/CLI layers use
    (`state0`, `mesh`, `pressure`, `profiler`,
    `_fresh_state`, `_note_owned`, `dispatch`, `check_drops`), so
    `HeartbeatHarvest` drives a fleet exactly like a solo run.
    """

    mesh = None
    pressure = None
    profiler = None

    def __init__(self, engine, state0, plan: FleetPlan, *, names=None,
                 stop_ns: int = 0, strict_overflow: bool = True,
                 per_lane_stop: bool = False, fault_pad=None):
        if engine.cfg.axis_name is not None:
            raise ValueError(
                "fleets vmap the single-device engine; a sharded base "
                "scenario is not supported (shard across fleets instead)"
            )
        self.engine = engine
        self.plan = plan
        self.lanes = plan.lanes
        self.stop_ns = stop_ns
        self.names = list(names) if names is not None else None
        self.strict_overflow = strict_overflow
        self.overflow = "drop"
        # per_lane_stop: the stop time becomes a traced [L] input (one
        # lane axis more on the vmap), so every lane truncates its LAST
        # window at its OWN stop — exactly like its solo run. This is
        # what lets the serving plane pack requests with mixed stop
        # times into one launch and still return summaries bit-identical
        # to solo `Simulation.run` (a shared scalar stop would truncate
        # early lanes' windows at the fleet-wide stop instead).
        self.per_lane_stop = bool(per_lane_stop)
        # fault_pad: (tmax, gmax) minimum fault-array padding targets,
        # pinned per serving equivalence class (see `_pad_faults`)
        self._fault_pad = fault_pad
        self._base_state0 = state0

        self.seeds, self.state0, binds, self._fault_flags = \
            self._plan_inputs(plan)
        self.binds = binds

        lane_run, lane_step = self._make_lane_fns()
        # in_axes: state and binds carry the lane axis; stop (and the
        # traced window bound) are shared scalars — unless per_lane_stop
        # gives the stop its own lane axis
        s_ax = 0 if self.per_lane_stop else None
        self._batched_run = jax.vmap(lane_run, in_axes=(0, 0, s_ax))
        self._batched_step_w = jax.vmap(
            lane_step, in_axes=(0, 0, s_ax, None)
        )
        # donation mirrors Simulation._wrap: the [L, ...] state is the
        # only donated argument — binds are reused across every segment
        self._jit_run = jax.jit(self._batched_run, donate_argnums=0)
        self._jit_step_w = None
        self._owned = None

    def _plan_inputs(self, plan: FleetPlan):
        """Lower a FleetPlan to its traced launch inputs: per-lane
        seeds, the stacked `[L, ...]` initial state, the bind dict, and
        the static fault flags. Host-side numpy work only — nothing
        here compiles."""
        engine, state0 = self.engine, self._base_state0
        lanes = plan.lanes

        seeds = plan.seeds
        if seeds is None:
            seeds = tuple(engine.cfg.seed for _ in range(lanes))
        seeds = tuple(int(s) for s in seeds)

        # ---- per-lane initial states (host-side, once) ----------------
        lane_states = []
        for i in range(lanes):
            st = state0
            if plan.state_override is not None:
                st = plan.state_override(i, st)
            if plan.bandwidth_scale is not None:
                st = _scale_nic(st, plan.bandwidth_scale[i])
            lane_states.append(st)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *lane_states)

        # ---- lane binds: the traced per-lane scenario knobs ------------
        binds: dict[str, Any] = {
            "key": jnp.stack([srng.root_key(s) for s in seeds]),
        }
        fault_flags = None
        if plan.faults is not None and any(plan.faults):
            if engine.faults is not None:
                raise ValueError(
                    "the base scenario already compiles a fault "
                    "schedule; per-lane fault overrides REPLACE the "
                    "schedule — build the base without faults and give "
                    "every lane its own spec list"
                )
            hg = engine.cfg.n_hosts * engine.cfg.n_shards
            nm = self.names or [f"host{i}" for i in range(hg)]
            comp = [
                compile_faults(tuple(sp or ()), nm, hg, seeds[i])
                for i, sp in enumerate(plan.faults)
            ]
            pad = self._fault_pad or (None, None)
            fb, flags = _pad_faults(comp, pad[0], pad[1])
            if any(flags):
                binds.update(fb)
                fault_flags = flags
                if flags[0] or flags[2]:
                    # crash/bw epochs re-template host rows: bind each
                    # lane's own initial hosts as its reset template
                    binds["fault_reset"] = jax.tree.map(
                        lambda *xs: jnp.stack(xs),
                        *[s.hosts for s in lane_states],
                    )
        if plan.latency_scale is not None:
            binds["lat_milli"] = jnp.asarray(
                [int(round(s * LAT_UNIT)) for s in plan.latency_scale],
                jnp.int64,
            )
        return seeds, stacked, binds, fault_flags

    def make_inputs(self, plan: FleetPlan):
        """Launch inputs `(state0, binds)` for a NEW plan through the
        SAME compiled program — the warm-path entry the serving plane's
        program cache re-invokes per packed batch.

        The plan must be structurally compatible with the template this
        Fleet compiled: same lane count, same fault flags, and a bind
        pytree of identical structure/shapes (the fault pad targets
        pinned at build time make schedules of differing length land on
        one shape). Violations raise instead of silently retracing.
        The returned state is registered donation-safe (`_note_owned`),
        so `run`/`dispatch` consume it without a defensive copy.
        """
        if plan.lanes != self.lanes:
            raise ValueError(
                f"plan has {plan.lanes} lanes; this fleet compiled "
                f"{self.lanes} — pad short batches with inert lanes "
                "(inert_lane_state) instead of rebuilding"
            )
        _, state0, binds, flags = self._plan_inputs(plan)
        if flags != self._fault_flags:
            raise ValueError(
                f"fault flags {flags} do not match the compiled "
                f"template's {self._fault_flags}; fault-kind mix is a "
                "static knob of the lowered program — route this batch "
                "to its own equivalence class"
            )
        if (jax.tree.structure(binds) != jax.tree.structure(self.binds)
                or [x.shape for x in jax.tree.leaves(binds)]
                != [x.shape for x in jax.tree.leaves(self.binds)]):
            raise ValueError(
                "bind structure/shape mismatch vs the compiled "
                "template; the batch needs its own equivalence class"
            )
        return self._note_owned(state0), binds

    # -- lane binding -----------------------------------------------------

    def _make_lane_fns(self):
        base = self.engine
        binds = self.binds
        has_fault = "f_times" in binds
        has_reset = "fault_reset" in binds
        has_lat = "lat_milli" in binds
        flags = self._fault_flags
        hg = base.cfg.n_hosts * base.cfg.n_shards
        # host-side accounting copies are per-LANE concepts; the fleet's
        # tracker rows come from the summary bundle instead, so the
        # traced template carries a neutral stand-in
        np_times = np.zeros((1,), np.int64)
        np_alive = np.ones((1, hg), bool)

        def bind(b):
            kw: dict[str, Any] = {"base_key": b["key"]}
            if has_reset:
                kw["fault_reset"] = b["fault_reset"]
            if has_fault:
                kw["faults"] = CompiledFaults(
                    times=b["f_times"], alive=b["f_alive"],
                    fgrp=b["f_fgrp"], lat_milli=b["f_lat"],
                    passp=b["f_passp"], bw_scale=b["f_bw"],
                    has_crash=flags[0], has_link=flags[1],
                    has_bw=flags[2],
                    np_times=np_times, np_alive=np_alive,
                )
            if has_lat:
                kw["network"] = ScaledLatencyNetwork(
                    base.network, b["lat_milli"]
                )
            return base.bind_lane(**kw)

        def lane_run(st, b, stop):
            return bind(b).run(st, stop, 0)

        def lane_step(st, b, stop, window):
            return bind(b).step_window(st, stop, 0, window=window)

        return lane_run, lane_step

    # -- run / dispatch ---------------------------------------------------

    def run_fn(self) -> Callable:
        """`(stacked_state, stop) -> stacked_state` closing over the
        lane binds — the lowering surface hlo_audit and the donation
        census inspect."""
        return lambda st, stop: self._batched_run(st, self.binds, stop)

    def _stop_arg(self, stop_ns):
        """The traced stop input: a scalar, or — per_lane_stop — an
        `[L]` vector (a scalar broadcasts to every lane)."""
        if self.per_lane_stop:
            arr = jnp.asarray(stop_ns, jnp.int64)
            if arr.ndim == 0:
                arr = jnp.full((self.lanes,), arr, jnp.int64)
            if arr.shape != (self.lanes,):
                raise ValueError(
                    f"per-lane stop must be scalar or [{self.lanes}], "
                    f"got shape {arr.shape}"
                )
            return arr
        return jnp.int64(stop_ns)

    def run(self, stop_ns: int | None = None, state=None, *, binds=None):
        """Jit-run every lane to the stop time (finished lanes mask to
        no-ops); returns the stacked final state. The state input is
        donated — `state0` is defended by copy, like Simulation.run.
        `binds` optionally swaps in a fresh batch's lane knobs from
        `make_inputs` (the serving warm path); None uses the plan this
        fleet was built with."""
        st = self._fresh_state(state)
        stop = self._stop_arg(
            stop_ns if stop_ns is not None else self.stop_ns
        )
        b = self.binds if binds is None else binds
        out = self._note_owned(self._jit_run(st, b, stop))
        if self.strict_overflow:
            drops = int(jax.device_get(_lane_sum(out.queues.drops).sum()))  # shadowlint: no-deadline=library run() path; the fleet CLI uses HeartbeatHarvest
            if drops > 0:
                self.check_drops(drops, aggregate_summary(
                    jax.device_get(lane_summary_refs(out))))  # shadowlint: no-deadline=overflow error path
        return out

    def dispatch(self, stop_ns, state, window_ns: int | None = None,
                 *, binds=None):
        """Asynchronously dispatch the next fleet segment — the depth-1
        dispatch-ahead half of the CLI loop, no host<->device sync."""
        st = self._fresh_state(state)
        stop = self._stop_arg(stop_ns)
        b = self.binds if binds is None else binds
        if window_ns is None:
            return self._note_owned(self._jit_run(st, b, stop))
        if self._jit_step_w is None:
            self._jit_step_w = jax.jit(
                self._batched_step_w, donate_argnums=0
            )
        return self._note_owned(
            self._jit_step_w(st, b, stop, jnp.int64(window_ns))
        )

    def step_window(self, state, stop_ns=None,
                    window_ns: int | None = None, *, binds=None):
        """Advance every live lane one conservative window."""
        if window_ns is not None:
            return self.dispatch(
                stop_ns if stop_ns is not None else self.stop_ns,
                state, window_ns, binds=binds,
            )
        st = self._fresh_state(state)
        stop = self._stop_arg(
            stop_ns if stop_ns is not None else self.stop_ns
        )
        b = self.binds if binds is None else binds
        # fixed-window step: the lane step with the static default bound
        # (None keeps bit-identical results, like Simulation.step_window)
        if getattr(self, "_jit_step_fixed", None) is None:
            _, lane_step = self._make_lane_fns()
            self._jit_step_fixed = jax.jit(
                jax.vmap(
                    lambda s, bi, t: lane_step(s, bi, t, None),
                    in_axes=(0, 0, 0 if self.per_lane_stop else None),
                ),
                donate_argnums=0,
            )
        return self._note_owned(
            self._jit_step_fixed(st, b, stop)
        )

    # -- summaries --------------------------------------------------------

    def lane_summaries(self, state) -> list[dict]:
        """Per-lane summary dicts, bit-identical to L solo
        `state_summary` calls with the same seeds/faults."""
        return lane_summaries_from(
            jax.device_get(lane_summary_refs(state))  # shadowlint: no-deadline=diagnostic summary helper; not on the supervised loop
        )

    def summary(self, state) -> dict:
        """Fleet-aggregate progress dict (see `aggregate_summary`)."""
        return aggregate_summary(
            jax.device_get(lane_summary_refs(state))  # shadowlint: no-deadline=diagnostic summary helper; not on the supervised loop
        )

    def check_drops(self, drops: int, summary: dict | None = None):
        if int(drops) <= 0:
            return
        if self.strict_overflow:
            raise RuntimeError(
                f"event queue overflow: {int(drops)} events dropped "
                "across the fleet (per-host capacity "
                f"{self.engine.cfg.capacity}); rerun with a larger "
                "--capacity, or set strict_overflow=False to accept "
                "counted drops"
            )

    # -- donation-safe state ownership (mirrors Simulation) ---------------

    def _fresh_state(self, state):
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
        if self._owned is None:
            self._owned = weakref.WeakValueDictionary()
        self._owned[id(state)] = state
        return state

    def adopt_state(self, state):
        """Register an externally built [L, ...] state tree as owned by
        this fleet, so `step_window` may donate it without a defensive
        copy. The serving plane's snapshot-resume path loads a tree
        through `utils.checkpoint.load_checkpoint` (host numpy leaves)
        and adopts it in place of the `make_inputs` state.

        The copy below is load-bearing: on the CPU backend
        `jnp.asarray` can alias the caller's numpy buffer zero-copy,
        and donating an aliased buffer lets XLA write into memory it
        does not own (heap corruption, silently wrong resumed lanes).
        `jnp.array(..., copy=True)` forces a JAX-owned buffer that is
        safe to donate."""
        state = jax.tree.map(lambda x: jnp.array(x, copy=True), state)
        return self._note_owned(state)


def inert_lane_state(state):
    """A zero-event lane state: every queue slot emptied (time ==
    TIME_INVALID), everything else untouched.

    The window loop's predicate is `next_event < stop`, so an inert
    lane executes ZERO windows — its stats counters, drop counts, and
    queues stay exactly as initialized (all zero) and only `now` lands
    on the lane's stop. This is how the serving packer launches a
    partial batch (R live requests) through a program compiled at
    `max_lanes`: the L - R pad lanes ride along as provable no-ops
    instead of forcing a recompile per batch size (tests/test_serve.py
    pins the counters at exactly zero).
    """
    from shadow_tpu.core.timebase import TIME_INVALID

    q = state.queues
    q = dataclasses.replace(
        q, time=jnp.full_like(q.time, TIME_INVALID)
    )
    return dataclasses.replace(state, queues=q)


def lane_reshard(state, new_lanes: int) -> list:
    """Split an `[L, ...]` lane-stacked state tree into `L // new_lanes`
    sub-trees of `new_lanes` lanes each, slicing the leading (LANE) axis
    of every leaf. The serving plane's elastic migration uses this to
    turn one snapshot written at 8 lanes into two 4-lane resumable
    batches after a device loss halves the mesh
    (docs/17-Serving.md "Elasticity").

    Works on any pytree whose leaves all lead with the same lane axis —
    live fleet state, a loaded checkpoint tree, or the raw
    {leaf_path: array} dict of `utils.checkpoint.load_checkpoint_raw`.
    Refuses loudly (leaf named) on scalar leaves, leaves that disagree
    about L, and lane counts that do not divide evenly — a silent
    truncation here would drop in-flight requests.
    """
    leaves, treedef = jax.tree_util.tree_flatten_with_path(state)
    if not leaves:
        raise ValueError("lane_reshard: empty state tree")
    lanes = -1
    for path, leaf in leaves:
        shape = np.shape(leaf)
        if not shape:
            raise ValueError(
                "lane_reshard: leaf "
                f"{jax.tree_util.keystr(path)!r} is a scalar — every "
                "leaf of a lane-stacked tree must lead with the LANE "
                "axis"
            )
        if lanes < 0:
            lanes = int(shape[0])
        elif int(shape[0]) != lanes:
            raise ValueError(
                "lane_reshard: leaf "
                f"{jax.tree_util.keystr(path)!r} has leading dim "
                f"{int(shape[0])} but earlier leaves have {lanes} — "
                "this tree is not lane-stacked along a shared axis"
            )
    if new_lanes <= 0 or lanes % new_lanes != 0:
        raise ValueError(
            f"lane_reshard: cannot split {lanes} lanes into parts of "
            f"{new_lanes} — the part size must divide the lane count "
            "evenly (a remainder would strand in-flight lanes)"
        )
    parts = []
    for j in range(lanes // new_lanes):
        lo, hi = j * new_lanes, (j + 1) * new_lanes
        parts.append(jax.tree_util.tree_unflatten(
            treedef, [leaf[lo:hi] for _, leaf in leaves]
        ))
    return parts


def lane_merge(states: list):
    """Concatenate lane-stacked state trees along the LANE axis — the
    inverse of `lane_reshard`, used when a resize *grows* the mesh and
    a small snapshot must pad up to the new lane count with inert
    template lanes. Leaves come back as host numpy (the caller adopts
    them through `Fleet.adopt_state`, which re-copies onto device)."""
    if not states:
        raise ValueError("lane_merge: no states to merge")
    if len(states) == 1:
        return states[0]
    return jax.tree.map(
        lambda *xs: np.concatenate([np.asarray(x) for x in xs], axis=0),
        *states,
    )


def _scale_nic(state, scale: float):
    """Scale a lane's NIC rates in its initial state (bandwidth knob)."""
    hosts = state.hosts
    net = getattr(hosts, "net", None)
    if net is None or getattr(net, "nic_tx", None) is None:
        raise ValueError(
            "per-lane bandwidth_scale needs a NIC-modelled host tier "
            "(hosts.net.nic_tx); this scenario's hosts carry none — "
            "use latency_scale or a fault schedule instead"
        )

    def _scaled(nic):
        return dataclasses.replace(
            nic, rate=(nic.rate * scale).astype(nic.rate.dtype)
        )

    net = dataclasses.replace(
        net, nic_tx=_scaled(net.nic_tx), nic_rx=_scaled(net.nic_rx)
    )
    return dataclasses.replace(
        state, hosts=dataclasses.replace(hosts, net=net)
    )


def build_fleet_from_engine(engine, state0, lanes: int, *, names=None,
                            stop_ns: int = 0, **overrides) -> Fleet:
    """Build a Fleet over a raw (engine, initial_state) pair — the
    model-tier entry point (`phold.build` and friends). Per-lane knob
    names are validated against `LANE_KNOBS`; static compile-time knobs
    are rejected with the reason."""
    check_lane_knobs(overrides)
    plan = FleetPlan(lanes=lanes, **overrides)
    return Fleet(engine, state0, plan, names=names, stop_ns=stop_ns)
