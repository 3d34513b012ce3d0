"""The conservative-window simulation engine.

Reference semantics being reproduced (see SURVEY.md §3.1-3.3):

- Master computes conservative execution windows from the minimum
  cross-host latency and drives rounds (reference:
  src/main/core/master.c:133-159,450-480).
- Workers pop events below the window barrier per host and execute them
  (reference: src/main/core/worker.c:149-216,
  scheduler_policy_host_single.c:210-271).
- Cross-host sends roll reliability, add path latency, and are clamped up
  to the window barrier to preserve causality (reference:
  src/main/core/worker.c:243-304, scheduler_policy_host_single.c:180-184).

TPU-native re-expression: all hosts pop/execute/emit in lockstep as one
vmapped kernel over [H]-leading state arrays; the inner drain loop is a
`lax.while_loop`; the window barrier is a global min over per-host
next-event times (`lax.pmin` across the device mesh when sharded). One
"round" of the reference's pthread barrier dance is one iteration of the
outer while loop here — no locks, no threads, no barrier waits.

Drain algorithm (v3, chained): each outer iteration (sweep) moves every
host's frontier — its `drain_batch` earliest below-barrier events, a
prefix of the key-sorted queue rows — into a per-host STAGING buffer,
then an inner while_loop executes, per iteration, each host's minimum-key
staged event (vmapped) and appends the handler's routed emits back into
the staging buffer with one-hot masked writes (no sort, no scatter).
Because cross-host sends are clamped to the window barrier, an emitted
event is below the barrier iff it is LOCAL — so chains of local
follow-ups (packet arrival -> rx delivery -> tx kick) execute inside ONE
sweep in exact (time, src, seq) order, instead of costing one full
queue-push + re-sort sweep per cascade level (the v2 bottleneck: TCP
workloads measured ~2 events/sweep, ~48 sweeps/window). The sweep ends
when no staged event is below the barrier; leftovers (clamped remote
sends, far-future timers, high-water overflow) are flushed to the queues
in one push + cross-shard exchange per sweep. The reference's per-host
drain semantics (pop everything below the barrier,
scheduler_policy_host_single.c:210-271) are preserved exactly — the
per-host execution order is identical to v2's, which makes v3
bit-compatible with v2 — and the inner loop still needs no collectives,
so each shard drains with its own trip count and only the outer loop
synchronizes.
"""

from __future__ import annotations

import copy
import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from shadow_tpu.core import rng as srng
from shadow_tpu.core.events import (
    N_ARGS,
    EventQueue,
    Events,
    pack_srcseq,
    queue_push_counted,
)
from shadow_tpu.core.phases import phase
from shadow_tpu.core.timebase import TIME_INVALID

# Burst-fold length-word layout: low bits payload total, high bits the
# folded-run segment count. Every packer/unpacker (the fold below, the
# stack's Pkt decode and wire accounting, tcp's dup-ACK carrier) derives
# from these; the stage-width guard in EngineConfig enforces NSEG_MAX.
BURST_NSEG_SHIFT = 24
BURST_LEN_MASK = (1 << BURST_NSEG_SHIFT) - 1
BURST_NSEG_MAX = 127  # bits 24..30; bit 31 is the i32 sign


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Emit:
    """Up to K events emitted by one handler invocation (per host).

    dst is a *global* host id; dt is a non-negative delay relative to the
    executing event's time. local=True means a same-host scheduled task
    (worker_scheduleTask semantics: dst is forced to self, no routing);
    local=False means a network send that the engine routes — + path
    latency, reliability drop roll, barrier clamp (worker_sendPacket
    semantics) — including sends addressed to the sending host itself,
    which traverse the topology's self-loop exactly like the reference.
    """

    dst: jax.Array  # i32[K]
    dt: jax.Array  # i64[K]
    kind: jax.Array  # i32[K]
    args: jax.Array  # i32[K, N_ARGS]
    mask: jax.Array  # bool[K]
    local: jax.Array  # bool[K]

    @staticmethod
    def none(k: int, n_args: int = N_ARGS) -> "Emit":
        return Emit(
            dst=jnp.zeros((k,), jnp.int32),
            dt=jnp.zeros((k,), jnp.int64),
            kind=jnp.zeros((k,), jnp.int32),
            args=jnp.zeros((k, n_args), jnp.int32),
            mask=jnp.zeros((k,), bool),
            local=jnp.zeros((k,), bool),
        )

    @staticmethod
    def single(
        dst, dt, kind, args=None, mask=True, local=False, n_args: int = N_ARGS
    ) -> "Emit":
        a = jnp.zeros((1, n_args), jnp.int32)
        if args is not None:
            args = jnp.asarray(args, jnp.int32).reshape(1, -1)
            a = a.at[:, : args.shape[1]].set(args)
        return Emit(
            dst=jnp.asarray(dst, jnp.int32).reshape(1),
            dt=jnp.asarray(dt, jnp.int64).reshape(1),
            kind=jnp.asarray(kind, jnp.int32).reshape(1),
            args=a,
            mask=jnp.asarray(mask, bool).reshape(1),
            local=jnp.asarray(local, bool).reshape(1),
        )

    def pad_to(self, k: int) -> "Emit":
        cur = self.dst.shape[0]
        if cur == k:
            return self
        assert cur < k, f"handler emitted {cur} > max_emit {k}"
        return jax.tree.map(
            lambda a: jnp.concatenate(
                [a, jnp.zeros((k - cur,) + a.shape[1:], a.dtype)]
            ),
            self,
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Stats:
    """Per-host accounting (the reference's ObjectCounter/Tracker spirit:
    object_counter.c tracks new/free per object type; here every event
    kind gets an executed count, the struct-of-arrays analog)."""

    n_executed: jax.Array  # i64[H]
    n_emitted: jax.Array  # i64[H]
    n_net_dropped: jax.Array  # i64[H] packets lost to reliability rolls
    n_windows: jax.Array  # i64[] (replicated across shards)
    n_by_kind: jax.Array  # i64[H, NK] executed events per handler kind
    # scheduler self-profiling (the reference logs per-thread barrier
    # waits and push/pop idle time every run, scheduler.c:266-271;
    # the lockstep analogs are sweep and collective-round counts):
    n_sweeps: jax.Array  # i64[] outer drain iterations (queue merges)
    n_inner_steps: jax.Array  # i64[] sequential frontier positions run
    n_xchg_rounds: jax.Array  # i64[] cross-shard all_to_all rounds
    n_cross_shard: jax.Array  # i64[] packets delivered across shards
    # fault-injection attribution (every drop the chaos causes is
    # accounted somewhere: either the packet died on the wire or the
    # event died with its crashed host)
    n_fault_dropped: jax.Array  # i64[H] packets lost to fault overlays
    n_quarantined: jax.Array  # i64[H] events voided by host crashes
    # queue-merge self-profiling (`queue_push_counted`): per host, the
    # lanes its row read in overflow rounds and the events it admitted
    # in them. Per host so they shard like n_executed; how a push is
    # split into rounds depends on the shard's own pushes, so the
    # values differ between mesh shapes and drain contracts.
    n_merge_over_lanes: jax.Array  # i64[H]
    n_merge_over_events: jax.Array  # i64[H]

    @staticmethod
    def create(n_hosts: int, n_kinds: int = 1) -> "Stats":
        z = jnp.zeros((n_hosts,), jnp.int64)
        s = jnp.zeros((), jnp.int64)
        return Stats(
            z, z, z, s,
            jnp.zeros((n_hosts, n_kinds), jnp.int64),
            s, s, s, s, z, z, z, z,
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ExchangeBuf:
    """In-flight cross-shard events: the exchange double buffer.

    `bucket` holds the [S, R] result of the LAST all_to_all round of the
    previous flush — events destined for this shard that have been
    exchanged but not yet merged into its queue. Delivery is deferred to
    the next point the queue is actually read (the top of the next sweep
    body, or the next window's open), so the shard-local drain of window
    k overlaps the wire time of window k-1's exchange, and the window
    barrier pmin never waits on an all_to_all completing.

    `sent_min` is the min time of the events this shard SENT in that
    deferred round (i64 max when none). The global pmin over per-shard
    sent_min equals the global pmin over per-shard received mins — the
    all_to_all only permutes the same [S, R] blocks — so `_next_time`
    can fold the in-flight events into the barrier without a data
    dependence on the collective's result.

    Deferral is exact, not approximate: every delivery point sits in a
    gap where no other queue operation runs (cond/flag evaluations only
    read, and cross-window events are clamped >= the sending window's
    end so they can never change a drain flag), and `queue_push` is
    push-order-insensitive including its capacity drops — so the queue
    trajectory, drops included, is bit-identical to immediate delivery
    and therefore to the single-device run.
    """

    bucket: Events  # [S, R] received, undelivered cross-shard events
    # i64[1], not a scalar: per-shard private state must shard on the
    # mesh axis across the shard_map boundary (a scalar would be forced
    # into a replicated P() out_spec, which this value is not)
    sent_min: jax.Array  # i64[1] min time sent in the deferred round

    @staticmethod
    def create(n_shards: int, r: int, n_args: int = N_ARGS) -> "ExchangeBuf":
        return ExchangeBuf(
            bucket=Events.empty((n_shards, r), n_args=n_args),
            sent_min=jnp.full((1,), TIME_INVALID, jnp.int64),
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EngineState:
    """Complete simulation state for one shard: a pure pytree.

    Because state is a pytree of arrays, checkpoint/resume is trivial
    (serialize the pytree) — a capability the reference lacks entirely
    (SURVEY.md §5 "Checkpoint / resume: Absent").
    """

    now: jax.Array  # i64[] current window start (replicated)
    queues: EventQueue
    hosts: Any  # user pytree, every leaf [H, ...]
    src_seq: jax.Array  # i32[H] per-source sequence counters
    exec_cnt: jax.Array  # i32[H] per-host executed-event counters (RNG)
    stats: Stats
    cpu_free: jax.Array  # i64[H] virtual-CPU available-from time
    # last fault-schedule epoch whose transitions (crash wipes, restart
    # re-templating, bandwidth rescales) have been applied; always 0
    # when no fault schedule is configured
    fault_epoch: jax.Array  # i32[] (replicated)
    # device-side event-trace ring (shadow_tpu.obs.trace.TraceRing) or
    # None when EngineConfig.trace == 0 — None contributes zero pytree
    # leaves, keeping the compiled program and checkpoint layout
    # identical to a trace-free build
    trace: Any = None
    # in-flight cross-shard exchange buffer (ExchangeBuf) or None when
    # unsharded — None contributes zero pytree leaves, so single-device
    # programs and checkpoints are untouched by the sharded overlap
    xchg: Any = None
    # sim-time analytics histograms (shadow_tpu.obs.stats.StatPlane)
    # or None when EngineConfig.stats == 0 — None contributes zero
    # pytree leaves, same zero-cost discipline as `trace`
    splane: Any = None


def state_summary(state: EngineState) -> dict:
    """Cheap host-side progress snapshot of an EngineState.

    One batched device_get of a handful of scalars — safe to call at
    every window boundary. This is what the supervised-run layer
    (shadow_tpu/runtime/) pets its watchdog with and what the stall
    diagnostic bundle records as "last known progress": the frontier
    (clock) time, the window count, and the executed-event total.
    """
    now, windows, executed, sweeps, drops = jax.device_get((  # shadowlint: no-deadline=diagnostic summary helper; not on the supervised loop
        state.now, state.stats.n_windows, state.stats.n_executed.sum(),
        state.stats.n_sweeps, state.queues.drops.sum(),
    ))
    out = {
        "now_ns": int(now),
        "windows": int(windows),
        "executed": int(executed),
        "sweeps": int(sweeps),
        "queue_drops": int(drops),
    }
    ring = state.queues.spill
    if ring is not None:
        spilled, lost, hwm = jax.device_get((  # shadowlint: no-deadline=diagnostic summary helper; not on the supervised loop
            ring.n_spilled.sum(), ring.n_lost.sum(), ring.fill_hwm.max(),
        ))
        out["spilled"] = int(spilled)
        out["spill_lost"] = int(lost)
        out["fill_hwm"] = int(hwm)
    return out


# Handler signature: (host_state_slice, ev: Events scalar, key) ->
#                    (host_state_slice', Emit)
Handler = Callable[[Any, Events, jax.Array], tuple[Any, Emit]]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    n_hosts: int  # hosts on this shard
    capacity: int  # event queue slots per host
    lookahead: int  # conservative window width, ns (min cross-host latency)
    max_emit: int = 2  # K: max events emitted per handler invocation
    n_args: int = N_ARGS
    seed: int = 0
    axis_name: str | None = None  # mesh axis hosts are sharded over
    n_shards: int = 1  # static mesh axis size (1 when unsharded)
    drain_batch: int = 32  # B: frontier events extracted per host per sweep
    route_bucket: int = 0  # per-peer all_to_all bucket slots (0 = auto)
    stage_width: int = 0  # staging slots per host (0 = auto: B + 4K)
    # Device-side event tracing (shadow_tpu.obs.trace): records per host
    # the ring holds between drains. 0 (the default) compiles the trace
    # path away entirely — EngineState.trace is None (a leaf-free pytree
    # subtree), so the jitted program and the checkpoint leaf list are
    # identical to a trace-free build.
    trace: int = 0
    # args column holding the payload-length word for trace records
    # (A_LEN for the packet stack; harmless 0 for bare-engine models)
    trace_len_arg: int = 0
    # Overflow-spill ring slots per host (shadow_tpu.runtime.pressure):
    # queue evictions land in a per-host device ring that a host-side
    # reservoir harvests at window boundaries instead of being dropped.
    # 0 (the default) compiles the spill path away entirely —
    # EventQueue.spill is None (a leaf-free pytree subtree), so the
    # jitted program and the checkpoint leaf list are identical to a
    # spill-free build, the same zero-cost discipline as `trace`.
    spill: int = 0
    # Burst delivery: fold contiguous same-flow packet arrivals staged in
    # one sweep into a single multi-segment event — the chained drain's
    # sequential depth is the busiest host's event count, and TCP data
    # bursts are most of it. None disables. The tuple is a static
    # descriptor supplied by the stack layer:
    #   (kind, seq_arg, len_arg, sport_arg, dport_arg, meta_arg,
    #    proto, flags_excl_mask, mss, ctl_cols)
    # ctl_cols: arg indices whose folded value comes from the run's
    # LAST (highest-seq) member as one consistent snapshot — cumulative
    # ack, window advertisement, ts echo, and the SACK words, whose
    # bits are relative to their own segment's ack and must never be
    # paired with another segment's ack value.
    # Eligible events (matching kind/proto, none of the excluded flags,
    # 0 < len <= mss) that form a strictly seq-contiguous run of one
    # (src, sport, dport) flow collapse into the run head: its length
    # word becomes total_bytes | (n_segments << 24), its time the run's
    # earliest. PATH loss is exact (reliability was rolled per packet
    # at send time, before folding); receiver-side drop-tail and CoDel
    # verdicts coarsen to one per burst, and absorbed segments' timing
    # coarsens by at most the window width — the same tradeoff class as
    # Stack(fuse_rx=True). Dup-ACK counting is burst-exact: an ACK
    # answering a fold carries its segment count, so the peer's fast
    # retransmit fires at the same byte position as unfolded.
    burst: tuple | None = None
    # Frontier run batching: the THIRD drain contract, between the fully
    # chained path and the commutative batch_handler path. When > 0 (and
    # no batch_handler is installed) the window drain runs
    # `_drain_window_frontier`: per round each host's staged events are
    # key-sorted once and a RUN — the maximal prefix of equal-time,
    # same-kind events, capped at this many positions — executes through
    # a sequential position fold whose per-step cost is only the handler
    # pass + routing; the per-event staging bookkeeping the chained path
    # pays every step (min-key selection, rank-matched append, trace
    # append) amortizes to once per round. Results are BIT-IDENTICAL to
    # the chained drain (tests/test_model_batching.py pins state, emit
    # order, and trace records); only the sweep's sequential decomposition
    # changes, so stats.n_inner_steps counts fold positions as before but
    # reaches the same total along fewer synchronization points.
    # Soundness needs every LOCAL emit scheduled at dt >= 1 (the
    # transport/model tier declares this; sim.build_simulation refuses
    # configs that cannot) so in-round emits can never precede a run
    # member. 0 (the default) compiles the frontier path away entirely:
    # the lowered program is byte-identical to a knob-free build.
    frontier: int = 0
    # Sim-time analytics plane (shadow_tpu.obs.stats): when > 0 the
    # window loop streams log2 histograms of event wait time, network
    # latency, per-window host occupancy, queue fill at pop, and
    # frontier run length into device-array StatPlane leaves, across
    # all three drain contracts. 0 (the default) compiles the plane
    # away entirely — EngineState.splane is None (a leaf-free pytree
    # subtree), the same zero-cost discipline as `trace`/`spill`.
    stats: int = 0

    def __post_init__(self):
        # a window of width 0 can never drain an event: the compiled outer
        # loop would spin forever on-device with no Python escape. The
        # reference bounds runahead below by 1ms for the same reason
        # (master.c:133-159 minTimeJump floor).
        if self.lookahead < 1:
            raise ValueError(f"lookahead must be >= 1 ns, got {self.lookahead}")
        # a non-positive bucket can never send an event: the exchange loop
        # would spin forever on-device with no Python escape
        if self.route_bucket < 0:
            raise ValueError(
                f"route_bucket must be >= 0, got {self.route_bucket}"
            )
        if self.trace < 0:
            raise ValueError(f"trace must be >= 0, got {self.trace}")
        if self.spill < 0:
            raise ValueError(f"spill must be >= 0, got {self.spill}")
        if not 0 <= self.trace_len_arg < self.n_args:
            raise ValueError(
                f"trace_len_arg {self.trace_len_arg} outside "
                f"[0, {self.n_args})"
            )
        if self.burst is not None and self.eff_stage_width > BURST_NSEG_MAX:
            # the fold packs its run count into bits 24..30 of the
            # length word; a wider staging buffer could form runs that
            # silently overflow into the sign bit — refuse loudly
            raise ValueError(
                f"burst folding requires stage_width <= {BURST_NSEG_MAX} "
                f"(got {self.eff_stage_width}); shrink drain_batch/"
                "stage_width or disable burst"
            )
        if self.frontier < 0:
            raise ValueError(f"frontier must be >= 0, got {self.frontier}")
        if self.stats < 0:
            raise ValueError(f"stats must be >= 0, got {self.stats}")
        if self.stage_width and self.stage_width < self.eff_drain_batch + self.max_emit:
            # staging must hold a full frontier dump plus one handler's
            # emits, or the chained drain could stall with zero headroom
            raise ValueError(
                f"stage_width {self.stage_width} < drain_batch "
                f"{self.eff_drain_batch} + max_emit {self.max_emit}"
            )

    @property
    def eff_drain_batch(self) -> int:
        return max(1, min(self.drain_batch, self.capacity))

    @property
    def eff_stage_width(self) -> int:
        return self.stage_width or (self.eff_drain_batch + 4 * self.max_emit)


def _kind_cost(cpu_cost: jax.Array, kind: jax.Array) -> jax.Array:
    """Per-event cost from a [..., NK] cost table by event kind, as a
    one-hot select (computed-index gathers like take_along_axis are far
    slower than elementwise work on TPU at engine batch sizes)."""
    nk = cpu_cost.shape[-1]
    kidx = jnp.clip(kind, 0, nk - 1)
    onehot = kidx[..., None] == jnp.arange(nk, dtype=kind.dtype)
    # cpu_cost [H, NK] broadcasts against kidx [H, ...]: align trailing NK
    extra = kidx.ndim - (cpu_cost.ndim - 1)
    table = cpu_cost.reshape(
        cpu_cost.shape[:1] + (1,) * extra + cpu_cost.shape[1:]
    )
    return jnp.sum(jnp.where(onehot, table, 0), axis=-1)


def _select_rows(mask: jax.Array, new: Any, old: Any) -> Any:
    """Per-host select across two equal-structure pytrees ([H, ...] leaves)."""

    def sel(n, o):
        m = mask.reshape(mask.shape + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)

    return jax.tree.map(sel, new, old)


class Engine:
    """Builds jittable window-step / run functions over a handler table.

    `network.route(src_gid, dst_gid) -> (latency_ns i64, reliability f32,
    jitter_ns i64)` supplies the topology model (element-wise over
    arrays); a truthy `network.has_jitter` enables the per-packet jitter
    roll.
    """

    def __init__(self, cfg: EngineConfig, handlers: Sequence[Handler], network,
                 cpu_cost=None, batch_handler=None, faults=None,
                 fault_reset=None, frontier_kinds=None):
        """`cpu_cost`: optional per-event virtual-CPU nanoseconds, indexed
        by GLOBAL host id (the reference's per-host CPU model delays
        event execution while the virtual CPU is busy — cpu.c:56-107,
        event.c:75-84). Two shapes:
          i64[H_global]          — uniform cost per event, or
          i64[H_global, n_kinds] — per-KIND cost (the analog of the
        reference charging each task its measured execution time rather
        than a flat constant). Global indexing lets one engine closure
        serve every shard: each window gathers its own hosts' costs by
        gid. None or zeros disables the model with no overhead in
        results.

        `batch_handler`: optional commutative fast path. When set, the
        window drain executes each host's whole below-barrier frontier in
        ONE vmapped call instead of one sequential step per event:
        `batch_handler(host_state_slice, evs: Events with [B]-leading
        fields, keys[B]) -> (host_state_slice', Emit with [B, K] fields)`.
        Only valid when (a) the state transition commutes across the
        events of one window (order-insensitive folds like counters), and
        (b) handlers never emit local events below the window barrier —
        both hold for PHOLD-style models. Per-position RNG keys derive
        from (gid, exec_cnt + position), so results remain deterministic
        and sharding-independent.

        The CPU model composes with the batched drain at whole-frontier
        granularity: a host whose virtual CPU is busy past the barrier
        runs nothing this window, and each executed frontier advances
        cpu_free by the SUM of its events' costs — the batched analog of
        the reference's delay rounding (cpu.c:85-95 rounds accumulated
        delay to a precision grid rather than modeling each instant).

        `faults`: optional CompiledFaults (shadow_tpu.faults). Baked
        into the compiled step like `network` is — the schedule is
        constants, only the `fault_epoch` watermark is state. Crashed
        hosts stop executing (events quarantined), packets to/through
        faulted links or dead destinations drop with attribution, and
        epoch transitions wipe crashed hosts' queues and re-template
        their state rows from `fault_reset` (a global-shaped hosts
        pytree: the same initial SimHost the simulation was built with,
        so a restarted host comes back with fresh listening sockets).

        `frontier_kinds`: static tuple of event kinds allowed to form
        multi-position runs under the frontier drain (cfg.frontier > 0).
        Kinds outside the set still execute — one position per round, in
        exact chained order (the explicit in-host ordering fold) — they
        just never amortize. None (the default) allows every kind.
        Ignored when cfg.frontier == 0."""
        self.cfg = cfg
        self.handlers = tuple(handlers)
        self.network = network
        self.batch_handler = batch_handler
        self._frontier_kinds = (
            tuple(sorted({int(x) for x in frontier_kinds}))
            if frontier_kinds is not None else None
        )
        self._base_key = srng.root_key(cfg.seed)
        hg = cfg.n_hosts * cfg.n_shards
        nk = len(self.handlers)
        if cpu_cost is None:
            cpu_cost = jnp.zeros((hg, nk), jnp.int64)
        cpu_cost = jnp.asarray(cpu_cost, jnp.int64)
        if cpu_cost.shape not in ((hg,), (hg, nk)):
            raise ValueError(
                f"cpu_cost must be [H_global]={hg} or [H_global, "
                f"n_kinds]=({hg}, {nk}), got shape {cpu_cost.shape}"
            )
        if cpu_cost.ndim == 1:
            cpu_cost = jnp.broadcast_to(cpu_cost[:, None], (hg, nk))
        self.cpu_cost = cpu_cost
        # static fast path: with no CPU model (the default), skip every
        # cpu_free compare/update in the compiled step — profiled at ~20%
        # of the PHOLD sweep as a [H*B]-lane gather of an all-zeros table
        self._cpu_enabled = bool(jax.device_get((cpu_cost != 0).any()))  # shadowlint: no-deadline=build-time constant fetch; no collectives in flight
        # jitter rolls cost an extra uniform per emit row; skip them
        # entirely for jitter-free networks
        self._use_jitter = bool(getattr(network, "has_jitter", False))
        # device-side event tracing: a static flag like the CPU/jitter
        # paths — trace=0 builds carry no ring and compile no appends
        self._trace = cfg.trace > 0
        # sim-time analytics histograms: same static-flag discipline;
        # stats=0 builds carry no StatPlane and compile no observes
        self._stats = cfg.stats > 0
        # fault schedule: static sub-flags keep the no-fault (and
        # partial-fault) compiled programs free of dead overlay work
        self.faults = faults
        self.fault_reset = fault_reset
        self._f_crash = bool(faults is not None and faults.has_crash)
        self._f_link = bool(faults is not None and faults.has_link)
        self._f_bw = bool(faults is not None and faults.has_bw)
        if (self._f_crash or self._f_bw) and fault_reset is None:
            raise ValueError(
                "faults with crashes or bandwidth changes need a "
                "fault_reset template (the initial hosts pytree)"
            )
        # static all_to_all bucket width: ONE width for every exchange in
        # the program, because the deferred recv bucket is carried state
        # (ExchangeBuf) whose shape must agree across sweeps and across
        # the narrow/wide flush branches. Sized off the widest flat batch
        # either drain path pushes, with the same quarter-of-uniform
        # default the per-call sizing used.
        if cfg.axis_name is not None:
            if batch_handler is not None:
                m_ref = cfg.n_hosts * cfg.eff_drain_batch * cfg.max_emit
            else:
                m_ref = cfg.n_hosts * max(
                    cfg.eff_stage_width, cfg.eff_drain_batch + cfg.max_emit
                )
            self._xchg_r = cfg.route_bucket or max(
                16, -(-m_ref // cfg.n_shards) // 4
            )
        else:
            self._xchg_r = 0

    @property
    def xchg_width(self) -> int:
        """Slots per destination shard in each all_to_all round of the
        cross-shard exchange (the [S, R] bucket's R); 0 when unsharded."""
        return self._xchg_r

    # -- collectives (identity when unsharded) ------------------------------
    @phase("barrier")
    def _gmin(self, x):
        """Global min over the mesh. The TPU compiler lowers a 64-bit
        all-reduce only as a sum ("Supported lowering only of Sum all
        reduce", compiled for a described v5e:2x2, PR 21), so the i64
        barrier gathers every shard's value as two u32 words and reduces
        locally: one all_gather of 8 bytes per shard, bit-identical to
        a pmin."""
        ax = self.cfg.axis_name
        if ax is None:
            return x
        words = jax.lax.all_gather(
            jax.lax.bitcast_convert_type(x, jnp.uint32), ax)
        return jnp.min(jax.lax.bitcast_convert_type(words, x.dtype), axis=0)

    @phase("barrier")
    def _gany(self, x: jax.Array) -> jax.Array:
        if self.cfg.axis_name is not None:
            return jax.lax.psum(x.astype(jnp.int32), self.cfg.axis_name) > 0
        return x

    @phase("barrier")
    def _gsum(self, x: jax.Array) -> jax.Array:
        if self.cfg.axis_name is not None:
            return jax.lax.psum(x, self.cfg.axis_name)
        return x

    @phase("barrier")
    def _drain_flag(self, q: EventQueue, cpu_free, window_end) -> jax.Array:
        """True while any host (globally) still has an executable event
        below the window barrier. Computed in loop BODIES and threaded
        through the carry — never evaluated inside a while_loop cond —
        so the lowered predicate contains no collective (an older
        jax's shard_map leaked device 0's carry when a collective sat
        inside a cond; see docs/12-Sharding.md)."""
        nxt = q.min_time()
        if self._cpu_enabled:
            nxt = jnp.maximum(nxt, cpu_free)
        return self._gany(jnp.any(nxt < window_end))

    @phase("merge")
    def _push(self, q: EventQueue, stats: Stats, ev: Events,
              mask: jax.Array, host0):
        """`queue_push`, adding its overflow-round counters to stats."""
        q, lanes, events = queue_push_counted(q, ev, mask, host0)
        return q, dataclasses.replace(
            stats,
            n_merge_over_lanes=stats.n_merge_over_lanes + lanes,
            n_merge_over_events=stats.n_merge_over_events + events,
        )

    @phase("deliver")
    def _xchg_deliver(self, q: EventQueue, xchg, stats: Stats, host0):
        """Merge the in-flight exchange buffer into the local queue and
        return it emptied. The guard predicate is shard-local and both
        branches are collective-free, so per-shard divergence is safe
        under shard_map; the common no-cross-traffic case skips the
        queue merge entirely."""
        if xchg is None:
            return q, xchg, stats
        flat = xchg.bucket.flatten()
        valid = flat.time != TIME_INVALID
        q, stats = jax.lax.cond(
            jnp.any(valid),
            lambda qs: self._push(*qs, flat, valid, host0),
            lambda qs: qs,
            (q, stats),
        )
        return q, ExchangeBuf.create(
            self.cfg.n_shards, self._xchg_r, self.cfg.n_args
        ), stats

    def _route_sort(self, ev: Events, mask: jax.Array):
        """Group a flat batch [M] by destination shard in one stable sort.

        Every masked-in event whose destination lies on the mesh keys to
        its shard; the rest, and R rows of padding appended so that a
        round's slice of R rows never runs past the end
        (`_route_bucket`), key to S. Every field rides the sort as an
        operand, so nothing is gathered after it, and each destination's
        run keeps the batch's order.

        Returns (cols, start, count): `cols` the sorted fields, each
        [M + R]: time, dst, src, seq, kind, then one column per arg;
        `start`, `count` i32[S], where each destination's run begins and
        how many events it holds.
        """
        s, r = self.cfg.n_shards, self._xchg_r
        dshard = ev.dst // jnp.int32(self.cfg.n_hosts)
        key = jnp.where(mask & (dshard >= 0) & (dshard < s), dshard, s)
        fields = [ev.time, ev.dst, ev.src, ev.seq, ev.kind] + [
            ev.args[:, i] for i in range(self.cfg.n_args)]
        fills = [TIME_INVALID] + [0] * (len(fields) - 1)
        key, *cols = jax.lax.sort(
            [jnp.concatenate([x, jnp.full((r,), f, x.dtype)])
             for x, f in zip([key] + fields, [s] + fills)],
            num_keys=1, is_stable=True,
        )
        # S + 1 queries: one broadcast compare, not a binary-search loop
        bounds = jnp.searchsorted(
            key, jnp.arange(s + 1, dtype=key.dtype), side="left",
            method="compare_all",
        ).astype(jnp.int32)
        return cols, bounds[:s], bounds[1:] - bounds[:s]

    def _route_bucket(self, cols: list, start: jax.Array,
                      count: jax.Array, k: jax.Array) -> Events:
        """Round k's [S, R] bucket from `_route_sort`'s columns: row d
        holds ranks [kR, (k+1)R) of destination d's run, read as one
        contiguous slice per column; a slot past the run's end (so every
        slot of a row whose count is 0) is empty: time TIME_INVALID,
        every other field 0."""
        s, r = self.cfg.n_shards, self._xchg_r
        # a start past the batch only ever reads a fully masked slice;
        # capping it at the padding keeps every slice unclamped
        at = jnp.minimum(start + k * r, cols[0].shape[0] - r)
        at = [at[d] for d in range(s)]
        ok = (k * r + jnp.arange(r, dtype=jnp.int32))[None, :] < count[:, None]
        time, dst, src, seq, kind, *args = (
            jnp.where(ok, jnp.stack([jax.lax.dynamic_slice_in_dim(c, a, r)
                                     for a in at]), fill)
            for c, fill in zip(cols, [TIME_INVALID] + [0] * (len(cols) - 1))
        )
        return Events(time=time, dst=dst, src=src, seq=seq, kind=kind,
                      args=jnp.stack(args, axis=-1))

    @phase("exchange")
    def _exchange_push(self, q: EventQueue, xchg, stats: Stats, ev: Events,
                       mask: jax.Array, host0):
        """Push a flat routed batch, delivering cross-shard events by
        bucketed all_to_all.

        Hosts are block-partitioned over the mesh axis (gid // n_hosts is
        the owning shard). One stable sort groups the batch by
        destination shard (`_route_sort`); this shard's own run pushes
        directly. Round k sends ranks [kR, (k+1)R) of every other
        destination's run as contiguous slices of the sorted columns,
        one [S, R] bucket exchanged with `lax.all_to_all`
        (`_route_bucket`): no scatter and no gather. The rounds repeat
        until the longest run anywhere on the mesh has been sent, a
        count one `pmax` takes before the loop: lossless, and traffic
        scales with the cross-shard packet count rather than total
        packets (the TPU-native replacement for the reference's
        shared-memory scheduler_push across threads,
        scheduler.c:342-360; SURVEY.md §2.4).

        Each round's received bucket is NOT pushed in that round: it
        lands in `xchg` and is merged at the top of the NEXT round's
        body — and the final round's recv rides out in the returned
        ExchangeBuf to the next sweep or window (double buffering). The
        loop predicate reads a carried flag, computed in the body from
        the count taken before the loop (see `_drain_flag`). Returns
        (q, xchg, stats), stats counting the rounds, crossings and
        merges.
        """
        if self.cfg.axis_name is None:
            q, stats = self._push(q, stats, ev, mask, host0)
            return q, xchg, stats
        ax = self.cfg.axis_name
        # engine-level static bucket width (see __init__): a quarter of
        # the widest uniform-traffic case — small enough that lightly-
        # coupled shards don't pay Θ(batch) ICI traffic every iteration,
        # large enough that uniform workloads rarely need a second round
        # (overflow just loops, lossless)
        r = self._xchg_r
        my = jax.lax.axis_index(ax).astype(jnp.int32)
        dshard = ev.dst // jnp.int32(self.cfg.n_hosts)
        is_local = mask & (dshard == my)
        q, stats = self._push(q, stats, ev, is_local, host0)
        # the sort waits for the local push, so its [M] output is never
        # live beside the push's temporaries; the batch dies at the sort
        q, ev = jax.lax.optimization_barrier((q, ev))
        # this shard's own events key past every run, with those that go
        # nowhere: its row of every bucket stays empty
        cols, start, count = self._route_sort(ev, mask & ~is_local)
        longest = jax.lax.pmax(jnp.max(count), ax)

        def body(carry):
            _, k, q, xchg, stats = carry
            q, xchg, stats = self._xchg_deliver(q, xchg, stats, host0)
            bucket = self._route_bucket(cols, start, count, k)
            recv = jax.tree.map(
                lambda x: jax.lax.all_to_all(x, ax, split_axis=0, concat_axis=0),
                bucket,
            )
            # min over what this shard SENT (pre-exchange) — globally
            # pmin-equivalent to the receiver-side min, with no data
            # dependence on the collective's result
            xchg = ExchangeBuf(
                bucket=recv, sent_min=jnp.min(bucket.time).reshape((1,))
            )
            return (k + 1) * r < longest, k + 1, q, xchg, stats

        # global count (each shard only sees its own outbound packets;
        # the replicated stats scalar needs the psum'd total)
        n_cross = jax.lax.psum(jnp.sum(count, dtype=jnp.int64), ax)
        _, rounds, q, xchg, stats = jax.lax.while_loop(
            lambda carry: carry[0], body,
            (longest > 0, jnp.zeros((), jnp.int32), q, xchg, stats),
        )
        return q, xchg, dataclasses.replace(
            stats,
            n_xchg_rounds=stats.n_xchg_rounds + rounds,
            n_cross_shard=stats.n_cross_shard + n_cross,
        )

    # -- state construction -------------------------------------------------
    def _trace_slack(self) -> int:
        """Scratch columns past the trace ring's capacity: the widest
        single append either drain path performs, so full-ring overflow
        writes always land in the never-read zone (obs.trace docstring).
        """
        k = self.cfg.max_emit
        if self.batch_handler is not None:
            b = max(1, min(self.cfg.drain_batch, self.cfg.capacity))
            return b * (1 + k)
        if self.cfg.frontier > 0:
            # the frontier drain defers tracing to one append per round:
            # up to `u` positions of (1 exec + K emit) records each
            u = max(1, min(self.cfg.frontier, self.cfg.eff_stage_width))
            return u * (1 + k)
        return 1 + k

    def init_state(self, hosts: Any, initial: Events, host0: int | jax.Array = 0):
        cfg = self.cfg
        q = EventQueue.create(
            cfg.n_hosts, cfg.capacity, cfg.n_args, spill=cfg.spill
        )
        flat = initial.flatten()
        valid = flat.time != TIME_INVALID
        q, stats = self._push(
            q, Stats.create(cfg.n_hosts, len(self.handlers)), flat, valid,
            host0,
        )
        # start each source's sequence counter past any seq the initial
        # events consumed, so engine-emitted events never reuse a (src, seq)
        # pair — uniqueness is what makes the (time, src, seq) total order
        # deterministic (event.c:110-153)
        local_src = flat.src - jnp.asarray(host0, jnp.int32)
        seq0 = jnp.zeros((cfg.n_hosts,), jnp.int32).at[
            jnp.where(valid & (local_src >= 0) & (local_src < cfg.n_hosts),
                      local_src, cfg.n_hosts)
        ].max(flat.seq + 1, mode="drop")
        trace = None
        if self._trace:
            from shadow_tpu.obs.trace import TraceRing

            trace = TraceRing.create(
                cfg.n_hosts, cfg.trace, self._trace_slack()
            )
        xchg = None
        if cfg.axis_name is not None:
            xchg = ExchangeBuf.create(cfg.n_shards, self._xchg_r, cfg.n_args)
        splane = None
        if self._stats:
            from shadow_tpu.obs.stats import StatPlane

            splane = StatPlane.create(cfg.n_hosts)
        return EngineState(
            now=jnp.zeros((), jnp.int64),
            queues=q,
            hosts=hosts,
            src_seq=seq0,
            exec_cnt=jnp.zeros((cfg.n_hosts,), jnp.int32),
            stats=stats,
            cpu_free=jnp.zeros((cfg.n_hosts,), jnp.int64),
            fault_epoch=jnp.zeros((), jnp.int32),
            trace=trace,
            xchg=xchg,
            splane=splane,
        )

    # -- per-lane rebinding (scenario fleets) --------------------------------
    def bind_lane(self, *, base_key=None, faults=None, fault_reset=None,
                  network=None):
        """Shallow-copy this engine with per-lane scenario bindings.

        The fleet tier (runtime/fleet.py) calls this INSIDE a vmapped
        function, so every value may be a tracer: the RNG root key, the
        CompiledFaults arrays, and the network wrapper's scale become
        per-lane traced inputs instead of baked closure constants —
        the values the engine computes from them are identical either
        way (rng.root_key(seed) traced vs static yields the same key),
        which is what makes a fleet lane bit-identical to its solo run.
        The base engine object is never mutated, so its default (non-
        fleet) lowering stays byte-identical — the zero-cost pin.
        """
        eng = copy.copy(self)
        if base_key is not None:
            eng._base_key = base_key
        if fault_reset is not None:
            eng.fault_reset = fault_reset
        if faults is not None:
            eng.faults = faults
            eng._f_crash = bool(faults.has_crash)
            eng._f_link = bool(faults.has_link)
            eng._f_bw = bool(faults.has_bw)
            if (eng._f_crash or eng._f_bw) and eng.fault_reset is None:
                raise ValueError(
                    "faults with crashes or bandwidth changes need a "
                    "fault_reset template (the initial hosts pytree)"
                )
        if network is not None:
            eng.network = network
            eng._use_jitter = bool(getattr(network, "has_jitter", False))
        return eng

    # -- fault-schedule helpers ---------------------------------------------
    def _alive_slice(self, host0):
        """[H, T] per-shard liveness table (bool), sliced from the global
        [T, Hg] schedule. Constant per drain call; the per-event check is
        then a one-hot select over the tiny epoch axis."""
        f = self.faults
        return jax.lax.dynamic_slice_in_dim(
            f.alive, host0, self.cfg.n_hosts, axis=1
        ).T

    def _alive_at(self, al_sh, t):
        """bool liveness per host at time(s) t. `al_sh` is _alive_slice's
        [H, T]; t is [H] or [H, B] (leading host axis)."""
        f = self.faults
        e = f.epoch_of(t)  # [H] or [H, B]
        tt = f.times.shape[0]
        onehot = e[..., None] == jnp.arange(tt, dtype=jnp.int32)
        if t.ndim == 1:
            return jnp.any(onehot & al_sh, axis=-1)
        return jnp.any(onehot & al_sh[:, None, :], axis=-1)

    # -- shared emit routing -------------------------------------------------
    @phase("route")
    def _route(self, emit: Emit, base_time, gids, window_end, rkeys, emask,
               seq):
        """Route an [N, K] emit batch: local tasks keep their time;
        network sends add path latency (+jitter), roll reliability, and
        clamp to the window barrier (worker_sendPacket semantics,
        worker.c:243-304; self-addressed sends traverse the topology
        self-loop like any other packet).

        The fault schedule (when compiled in) overlays the lookup: path
        latency scales by the active epoch's [G, G] factor BEFORE the
        barrier clamp (so even scaled-to-zero latency stays causal),
        and an extra pass-probability roll — lane offset 2K, disjoint
        from the reliability (0) and jitter (K) lanes — plus a
        destination-liveness check at the ARRIVAL epoch drop packets
        with their own attribution counter.

        Returns (Events[N, K], final_mask, dropped, fdropped, t,
        is_local)."""
        n, k = emit.dst.shape
        self_gid = gids[:, None]
        is_local = emit.local
        dst = jnp.where(is_local, self_gid, emit.dst)
        dt = jnp.maximum(emit.dt, 0)
        lat, rel, jit = self.network.route(
            jnp.broadcast_to(self_gid, (n, k)), dst
        )

        def rolls(offset):
            # one fused elementwise threefry pass over all [N, K] lanes
            return srng.uniform_lanes(rkeys, k, offset)

        t = base_time[:, None] + dt
        if self._f_link:
            f = self.faults
            hg = f.fgrp.shape[0]
            dstc = jnp.clip(dst, 0, hg - 1)
            gs = f.fgrp[jnp.broadcast_to(jnp.clip(self_gid, 0, hg - 1),
                                         (n, k))]
            gd = f.fgrp[dstc]
            e_s = f.epoch_of(t)  # link state is read at SEND time
            lat = lat * f.lat_milli[e_s, gs, gd] // 1000
        if self._use_jitter:
            # seeded symmetric latency noise, per packet (the reference
            # carries per-edge jitter attrs, topology.c:101-105; paths
            # accumulate them like latency)
            uj = rolls(k)
            lat = jnp.maximum(
                lat + ((uj * 2.0 - 1.0) * jit.astype(jnp.float32)).astype(
                    jnp.int64
                ),
                0,
            )
        t_remote = jnp.maximum(t + lat, window_end)

        u = rolls(0)
        dropped = (~is_local) & (u >= rel) & emask
        fdropped = jnp.zeros_like(dropped)
        if self._f_link:
            u2 = rolls(2 * k)
            fdropped = u2 >= f.passp[e_s, gs, gd]
        if self._f_crash:
            f = self.faults
            hg = f.fgrp.shape[0]
            dstc = jnp.clip(dst, 0, hg - 1)
            # a packet addressed to a host that is dead when it ARRIVES
            # is lost — the NIC it would land on does not exist
            e_a = f.epoch_of(t_remote)
            fdropped = fdropped | ~f.alive[e_a, dstc]
        if self._f_link or self._f_crash:
            fdropped = fdropped & (~is_local) & emask & ~dropped
        t = jnp.where(is_local, t, t_remote)
        final_mask = emask & ~dropped & ~fdropped

        out = Events(
            time=jnp.where(final_mask, t, TIME_INVALID),
            dst=dst,
            src=jnp.broadcast_to(self_gid, (n, k)).astype(jnp.int32),
            seq=seq,
            kind=emit.kind,
            args=emit.args,
        )
        return out, final_mask, dropped, fdropped, t, is_local

    # -- execute one frontier position across all hosts ---------------------
    def _execute_step(self, hosts, src_seq, exec_cnt, stats, ev: Events,
                      active: jax.Array, window_end: jax.Array,
                      gids: jax.Array, trace=None, splane=None):
        """Run handlers for one event per host (masked), route the emits.

        Returns (hosts', src_seq', exec_cnt', stats', routed Events[H, K],
        final_mask[H, K], trace', splane'). `trace` passes through
        untouched (None) unless tracing is compiled in, in which case
        one append records the executed event plus every non-local
        emit; `splane` likewise accumulates the wait/net histograms
        only when the stats plane is compiled in.
        """
        cfg = self.cfg
        h, k = cfg.n_hosts, cfg.max_emit

        def per_host(hs, e, key):
            branches = tuple(
                (lambda fn: lambda: _pad(fn(hs, e, key), k))(fn) for fn in self.handlers
            )

            def _pad(res, kk):
                hs2, em = res
                return hs2, em.pad_to(kk)

            idx = jnp.clip(e.kind, 0, len(branches) - 1)
            return jax.lax.switch(idx, branches)

        with phase("handler"):
            hkeys, rkeys = srng.event_keys(self._base_key, gids, exec_cnt)
            hosts2, emit = jax.vmap(per_host)(hosts, ev, hkeys)
            hosts = _select_rows(active, hosts2, hosts)
            emask = emit.mask & active[:, None]

        with phase("route"):
            # per-source sequence numbers, dense over the masked emits so
            # the numbering is independent of K padding (event.c:110-153
            # tie-break)
            inc = emask.astype(jnp.int32)
            within = jnp.cumsum(inc, axis=1) - inc
            seq = src_seq[:, None] + within
            src_seq = src_seq + jnp.sum(inc, axis=1, dtype=jnp.int32)
            out, final_mask, dropped, fdropped, _t, _is_local = self._route(
                emit, ev.time, gids, window_end, rkeys, emask, seq
            )

        with phase("observe"):
            if self._stats and splane is not None:
                # every delivered emit executes at its routed time _t, so
                # _t - now IS exec-minus-enqueue sim time; the non-local
                # subset is the send->exec network latency
                delta = _t - ev.time[:, None]
                splane = splane.observe("wait", delta, final_mask)
                splane = splane.observe("net", delta, final_mask & ~_is_local)

            if self._trace and trace is not None:
                from shadow_tpu.obs.trace import (
                    OP_DROP, OP_EXEC, OP_FDROP, OP_SEND, trace_append,
                )

                la = cfg.trace_len_arg
                # one width-(1+K) append: the executed event (op EXEC, on the
                # executing host's row) + its non-local emits (op SEND, or
                # DROP/FDROP with the loss attribution, on the source row at
                # emission time — the matching EXEC on the destination row is
                # the arrival, and (src, seq) ties the pair into a flow)
                op_send = jnp.where(
                    dropped, OP_DROP,
                    jnp.where(fdropped, OP_FDROP, OP_SEND),
                ).astype(jnp.int32)
                col = lambda a: a[:, None]
                trace = trace_append(
                    trace, cfg.trace,
                    time=jnp.concatenate(
                        [col(ev.time), jnp.broadcast_to(col(ev.time), (h, k))], 1
                    ),
                    src=jnp.concatenate([col(ev.src), out.src], 1),
                    dst=jnp.concatenate([col(ev.dst), out.dst], 1),
                    kind=jnp.concatenate([col(ev.kind), out.kind], 1),
                    plen=jnp.concatenate(
                        [ev.args[:, la:la + 1], out.args[:, :, la]], 1
                    ),
                    seq=jnp.concatenate([col(ev.seq), out.seq], 1),
                    op=jnp.concatenate(
                        [jnp.full((h, 1), OP_EXEC, jnp.int32), op_send], 1
                    ),
                    mask=jnp.concatenate(
                        [col(active), emask & ~_is_local], 1
                    ),
                )

        with phase("handler"):
            exec_cnt = exec_cnt + active.astype(jnp.int32)
            stats = dataclasses.replace(
                stats,
                n_executed=stats.n_executed + active,
                n_emitted=stats.n_emitted + jnp.sum(inc, axis=1, dtype=jnp.int64),
                n_net_dropped=stats.n_net_dropped + jnp.sum(dropped, axis=1, dtype=jnp.int64),
                n_fault_dropped=stats.n_fault_dropped
                + jnp.sum(fdropped, axis=1, dtype=jnp.int64),
                n_by_kind=stats.n_by_kind + (
                    jax.nn.one_hot(
                        jnp.clip(ev.kind, 0, len(self.handlers) - 1),
                        len(self.handlers), dtype=jnp.int64,
                    )
                    * active[:, None]
                ),
            )
        return (hosts, src_seq, exec_cnt, stats, out, final_mask, trace,
                splane)

    # -- commutative fast path: whole frontiers in one vmapped call ---------
    def _drain_window_batched(self, st: EngineState, window_end, host0):
        """Window drain for batch_handler engines: every below-barrier
        frontier event executes in a single [H, B]-shaped handler call
        per sweep — no sequential inner loop. Valid only under the
        batch_handler contract (commutative state folds, no local
        below-barrier emits); per-position keys keep determinism."""
        cfg = self.cfg
        h, k, c = cfg.n_hosts, cfg.max_emit, cfg.capacity
        b = max(1, min(cfg.drain_batch, c))
        gids = host0 + jnp.arange(h, dtype=jnp.int32)
        cpu_cost = self.cpu_cost[gids]  # [H, NK]
        al_sh = self._alive_slice(host0) if self._f_crash else None

        def outer_cond(carry):
            # carried flag: the psum/any deciding another sweep runs at
            # the END of the body (`_drain_flag`), never in this cond —
            # collective-free predicates keep the sharded lowering
            # independent of how a predicate is replicated per shard
            # (see docs/12-Sharding.md)
            return carry[0]

        def outer_body(carry):
            (_, q, xchg, hosts, src_seq, exec_cnt, stats, cpu_free, trace,
             splane) = carry
            # merge window k-1's in-flight exchange before reading the
            # frontier: the gap since the sending sweep's push contains
            # no queue operation, so deferred delivery is bit-identical
            q, xchg, stats = self._xchg_deliver(q, xchg, stats, host0)
            with phase("select"):
                bt = q.time[:, :b]
                # a host whose virtual CPU is busy past the barrier runs
                # nothing this window (whole-frontier granularity)
                bvalid = bt < window_end  # a prefix: rows are key-sorted
                if self._cpu_enabled:
                    bvalid = bvalid & (cpu_free[:, None] < window_end)
            with phase("observe"):
                if self._stats and splane is not None:
                    # queue fill at pop, pre-clear (chained-drain semantics:
                    # hosts popping at least one event this sweep)
                    splane = splane.observe(
                        "qfill",
                        jnp.sum(q.time != TIME_INVALID, axis=1,
                                dtype=jnp.int64),
                        jnp.any(bvalid, axis=1),
                    )
            with phase("select"):
                # crashed hosts consume (quarantine) their frontier without
                # executing it: rows still clear below, handlers see
                # TIME_INVALID
                if self._f_crash:
                    run = bvalid & self._alive_at(
                        al_sh, jnp.where(bvalid, bt, 0)
                    )
                else:
                    run = bvalid
            with phase("pop"):
                evs = Events(
                    time=jnp.where(run, bt, TIME_INVALID),
                    dst=jnp.broadcast_to(gids[:, None], (h, b)),
                    src=q.src[:, :b],
                    seq=q.seq[:, :b],
                    kind=q.kind[:, :b],
                    args=q.args[:, :b],
                )
            with phase("handler"):
                cnts = exec_cnt[:, None] + jnp.arange(b, dtype=jnp.int32)[None, :]
                hk, rk = srng.event_keys(
                    self._base_key,
                    jnp.broadcast_to(gids[:, None], (h, b)).reshape(-1),
                    cnts.reshape(-1),
                )
                hk = hk.reshape((h, b, 2))

                hosts2, emit = jax.vmap(self.batch_handler)(hosts, evs, hk)
                # n_exec counts the CLEARED prefix (and RNG positions) —
                # quarantined events consume both; n_run counts executions
                n_exec = jnp.sum(bvalid, axis=1, dtype=jnp.int32)
                n_run = jnp.sum(run, axis=1, dtype=jnp.int32)
                hosts = _select_rows(n_run > 0, hosts2, hosts)
                emask = emit.mask & run[:, :, None]

            with phase("route"):
                # dense per-source sequence numbers across the [B, K] lanes
                inc = emask.astype(jnp.int32).reshape(h, b * k)
                within = jnp.cumsum(inc, axis=1) - inc
                seq = (src_seq[:, None] + within).reshape(h, b, k)
                src_seq = src_seq + jnp.sum(inc, axis=1, dtype=jnp.int32)

                flat = lambda a: a.reshape((h * b,) + a.shape[2:])
                em_flat = jax.tree.map(flat, emit)
                out, final_mask, dropped, fdropped, _t, _loc = self._route(
                    em_flat,
                    evs.time.reshape(-1),
                    jnp.broadcast_to(gids[:, None], (h, b)).reshape(-1),
                    window_end,
                    rk,
                    flat(emask),
                    flat(seq),
                )

            with phase("observe"):
                if self._stats and splane is not None:
                    delta = (_t - evs.time.reshape(-1)[:, None]).reshape(
                        h, b * k
                    )
                    fm = final_mask.reshape(h, b * k)
                    splane = splane.observe("wait", delta, fm)
                    splane = splane.observe(
                        "net", delta, fm & ~_loc.reshape(h, b * k)
                    )

                if self._trace and trace is not None:
                    from shadow_tpu.obs.trace import (
                        OP_DROP, OP_EXEC, OP_FDROP, OP_SEND, trace_append,
                    )

                    la = cfg.trace_len_arg
                    # one width-(B + B*K) append per sweep: the executed
                    # frontier (EXEC rows) + every non-local emit
                    # (SEND/DROP/FDROP rows) — same semantics as the chained
                    # path's per-step append in _execute_step
                    wide = lambda a: a.reshape(h, b * k)  # [H*B, K] -> [H, BK]
                    op_send = jnp.where(
                        dropped, OP_DROP,
                        jnp.where(fdropped, OP_FDROP, OP_SEND),
                    ).astype(jnp.int32)
                    send_t = jnp.broadcast_to(
                        evs.time[:, :, None], (h, b, k)
                    ).reshape(h, b * k)
                    trace = trace_append(
                        trace, cfg.trace,
                        time=jnp.concatenate([evs.time, send_t], 1),
                        src=jnp.concatenate([evs.src, wide(out.src)], 1),
                        dst=jnp.concatenate([evs.dst, wide(out.dst)], 1),
                        kind=jnp.concatenate([evs.kind, wide(out.kind)], 1),
                        plen=jnp.concatenate(
                            [evs.args[:, :, la],
                             out.args[:, :, la].reshape(h, b * k)], 1
                        ),
                        seq=jnp.concatenate([evs.seq, wide(out.seq)], 1),
                        op=jnp.concatenate(
                            [jnp.full((h, b), OP_EXEC, jnp.int32),
                             wide(op_send)], 1
                        ),
                        mask=jnp.concatenate(
                            [run, wide(flat(emask) & ~_loc)], 1
                        ),
                    )

            with phase("handler"):
                exec_cnt = exec_cnt + n_exec
                stats2 = dataclasses.replace(
                    stats,
                    n_executed=stats.n_executed + n_run,
                    n_emitted=stats.n_emitted
                    + jnp.sum(inc, axis=1, dtype=jnp.int64),
                    n_net_dropped=stats.n_net_dropped
                    + jnp.sum(
                        dropped.reshape(h, b * k), axis=1, dtype=jnp.int64
                    ),
                    n_fault_dropped=stats.n_fault_dropped
                    + jnp.sum(
                        fdropped.reshape(h, b * k), axis=1, dtype=jnp.int64
                    ),
                    n_quarantined=stats.n_quarantined
                    + jnp.sum(bvalid & ~run, axis=1, dtype=jnp.int64),
                    n_by_kind=stats.n_by_kind + jnp.sum(
                        jax.nn.one_hot(
                            jnp.clip(evs.kind, 0, len(self.handlers) - 1),
                            len(self.handlers), dtype=jnp.int64,
                        )
                        * run[:, :, None],
                        axis=1,
                    ),
                )
            with phase("append"):
                if self._cpu_enabled:
                    # virtual-CPU charge: the frontier's summed per-kind
                    # costs advance cpu_free past its last executed event.
                    # One-hot select, not take_along_axis: a computed-index
                    # gather here measured ~20% of the whole sweep on TPU
                    ev_cost = _kind_cost(cpu_cost, evs.kind)
                    total_cost = jnp.sum(
                        jnp.where(run, ev_cost, 0), axis=1
                    )
                    t_last = jnp.max(jnp.where(run, bt, 0), axis=1)
                    cpu_free = jnp.where(
                        total_cost > 0,
                        jnp.maximum(cpu_free, t_last) + total_cost,
                        cpu_free,
                    )

            with phase("pop"):
                cleared = jnp.arange(c, dtype=jnp.int32)[None, :] < n_exec[:, None]
                q = dataclasses.replace(
                    q, time=jnp.where(cleared, TIME_INVALID, q.time)
                )
            q, xchg, stats2 = self._exchange_push(
                q, xchg, stats2, out.flatten(), final_mask.reshape(-1), host0
            )
            stats2 = dataclasses.replace(stats2, n_sweeps=stats2.n_sweeps + 1)
            more = self._drain_flag(q, cpu_free, window_end)
            return (more, q, xchg, hosts, src_seq, exec_cnt, stats2,
                    cpu_free, trace, splane)

        carry = (self._drain_flag(st.queues, st.cpu_free, window_end),
                 st.queues, st.xchg, st.hosts, st.src_seq, st.exec_cnt,
                 st.stats, st.cpu_free, st.trace, st.splane)
        (_, q, xchg, hosts, src_seq, exec_cnt, stats, cpu_free,
         trace, splane) = jax.lax.while_loop(outer_cond, outer_body, carry)
        if self._cpu_enabled:
            # the barrier's sent_min shortcut cannot see a destination
            # host's busy CPU; flush in-flight events before `_next_time`
            # runs so the max(min_time, cpu_free) defer stays exact
            q, xchg, stats = self._xchg_deliver(q, xchg, stats, host0)
        if self._stats and splane is not None:
            occ = stats.n_executed - st.stats.n_executed
            splane = splane.observe("occ", occ, occ > 0)
        return dataclasses.replace(
            st,
            queues=q,
            hosts=hosts,
            src_seq=src_seq,
            exec_cnt=exec_cnt,
            stats=dataclasses.replace(stats, n_windows=stats.n_windows + 1),
            cpu_free=cpu_free,
            trace=trace,
            xchg=xchg,
            splane=splane,
        )

    # -- staging-buffer helpers (chained drain) ------------------------------
    def _burst_fold(self, stage: Events) -> Events:
        """Collapse contiguous same-flow arrival runs in [H, SW] staging.

        Sort each host's staged events by (flow key, tcp seq); a run of
        eligible events whose seqs chain by +1 (every segment before the
        last full-MSS) folds into its head: length word = total |
        (count << 24), time = run min. Absorbed slots clear. All work is
        one lax.sort plus [H, SW, SW] masked reductions — no scatter.
        Slot order afterwards is arbitrary, which staging permits
        (_stage_min selects by content, _stage_append by free rank).
        """
        (kind, seq_a, len_a, sport_a, dport_a, meta_a, proto, flags_x,
         mss, ctl_cols) = self.cfg.burst
        t = stage.time
        h, sw = t.shape
        meta = stage.args[:, :, meta_a]
        ln = stage.args[:, :, len_a]
        elig = (
            (t != TIME_INVALID)
            & (stage.kind == kind)
            & ((meta & 0x3) == proto)
            & ((meta & flags_x) == 0)
            & (ln > 0) & (ln <= mss)
        )
        i64max = jnp.iinfo(jnp.int64).max
        slot = jnp.arange(sw, dtype=jnp.int64)[None, :]
        flow = (
            (stage.src.astype(jnp.int64) << 32)
            | (stage.args[:, :, sport_a].astype(jnp.int64) << 16)
            | stage.args[:, :, dport_a].astype(jnp.int64)
        )
        k1 = jnp.where(elig, flow, i64max - sw + slot)  # inelig: stable tail
        k2 = jnp.where(elig, stage.args[:, :, seq_a].astype(jnp.int64), 0)
        cols = jax.lax.sort(
            (k1, k2, t, stage.dst, stage.src, stage.seq, stage.kind,
             *[stage.args[:, :, i] for i in range(stage.args.shape[2])]),
            dimension=1, num_keys=2,
        )
        k1, k2, t2, dst2, src2, seq2, kind2, *acols = cols
        args2 = jnp.stack(acols, axis=-1)
        ln2 = args2[:, :, len_a]
        elig2 = k1 < (i64max - sw)  # eligibility survives the sort via k1
        prev = lambda a, fill: jnp.concatenate(
            [jnp.full_like(a[:, :1], fill), a[:, :-1]], axis=1
        )
        contig = (
            elig2
            & prev(elig2, False)
            & (k1 == prev(k1, -1))
            & (k2 == prev(k2, i64max) + 1)
            & (prev(ln2, 0) == mss)  # only a run's LAST segment may be short
        )
        start = elig2 & ~contig
        run = jnp.cumsum(start.astype(jnp.int32), axis=1)  # run id per slot
        same = (
            (run[:, :, None] == run[:, None, :])
            & elig2[:, :, None] & elig2[:, None, :]
        )  # [H, SW, SW]
        count = jnp.sum(same, axis=2, dtype=jnp.int32)
        total = jnp.sum(
            jnp.where(same, ln2[:, None, :], 0), axis=2, dtype=ln2.dtype
        )
        tmin = jnp.min(
            jnp.where(same, t2[:, None, :], i64max), axis=2
        )
        # count is uniform across a run's members, so membership in a
        # folded (>1 segment) run is a direct test
        folded_head = start & (count > 1)
        absorbed = elig2 & contig & (count > 1)
        args2 = args2.at[:, :, len_a].set(
            jnp.where(
                folded_head, total | (count << BURST_NSEG_SHIFT), ln2
            )
        )
        # the head takes the run's LAST member's piggybacked control
        # words as ONE consistent snapshot: the freshest cumulative
        # ack/window/ts, and the SACK words that are only meaningful
        # relative to that same segment's ack
        idx2 = jnp.arange(sw, dtype=jnp.int32)[None, None, :]
        endpos = jnp.max(
            jnp.where(same, idx2, -1), axis=2
        )  # [H, SW] index of each run's last member
        at_end = idx2 == endpos[:, :, None]  # [H, SW, SW] one-hot
        for col in ctl_cols:
            v = args2[:, :, col]
            vend = jnp.sum(
                jnp.where(at_end & same, v[:, None, :], 0),
                axis=2, dtype=v.dtype,
            )
            args2 = args2.at[:, :, col].set(
                jnp.where(folded_head, vend, v)
            )
        return Events(
            time=jnp.where(
                absorbed, TIME_INVALID, jnp.where(folded_head, tmin, t2)
            ),
            dst=dst2, src=src2, seq=seq2, kind=kind2, args=args2,
        )

    @staticmethod
    @phase("select")
    def _stage_min(stage: Events):
        """Per host, the minimum-(time, src, seq) staged event.

        Returns (ev: Events with [H] fields, mss i64[H] the packed
        (src, seq) key of that event — the total-order guard consumes
        it, onehot bool[H, S] selecting its slot, valid_cnt i32[H]).
        Empty rows yield time=TIME_INVALID. All elementwise/reduction
        work — computed-index gathers and scatters serialize on TPU,
        one-hot select is VPU-cheap.
        """
        t = stage.time
        s = t.shape[1]
        i64max = jnp.iinfo(jnp.int64).max
        mt = jnp.min(t, axis=1)  # [H]
        cand = t == mt[:, None]
        ss = pack_srcseq(stage.src, stage.seq)
        ssm = jnp.where(cand, ss, i64max)
        mss = jnp.min(ssm, axis=1)
        sel = cand & (ssm == mss[:, None])
        first = jnp.argmax(sel, axis=1)  # (time, src, seq) is unique
        onehot = jnp.arange(s, dtype=jnp.int32)[None, :] == first[:, None]
        # dtype pinned: a bare int32 jnp.sum promotes to int64 under x64,
        # which would leak wider event fields into every handler trace
        pick32 = lambda a: jnp.sum(
            jnp.where(onehot, a, 0), axis=1, dtype=a.dtype
        )
        ev = Events(
            time=mt,
            dst=pick32(stage.dst),
            src=pick32(stage.src),
            seq=pick32(stage.seq),
            kind=pick32(stage.kind),
            args=jnp.sum(
                jnp.where(onehot[:, :, None], stage.args, 0), axis=1,
                dtype=stage.args.dtype,
            ),
        )
        valid_cnt = jnp.sum(t != TIME_INVALID, axis=1, dtype=jnp.int32)
        return ev, mss, onehot, valid_cnt

    @staticmethod
    @phase("append")
    def _stage_append(stage: Events, out: Events):
        """Append a routed [H, K] emit batch into each host's free staging
        slots by RANK MATCHING: the j-th valid emit lands in the j-th
        free slot (two cumsum rank scans + one [H, S, K] compare), all
        elementwise — no sort, no scatter. The earlier implementation
        sorted [H, S+K] x 16 operands per inner step, which profiled as
        the drain's dominant per-iteration traffic at 1k hosts. The
        caller's high-water gate guarantees at least K free slots, so
        every valid emit matches exactly one slot. Slot arrangement is
        irrelevant: _stage_min selects by content key.
        """
        free = stage.time == TIME_INVALID  # [H, S]
        fr = jnp.cumsum(free.astype(jnp.int32), axis=1) - free
        valid = out.time != TIME_INVALID  # [H, K]
        er = jnp.cumsum(valid.astype(jnp.int32), axis=1) - valid
        match = (
            (fr[:, :, None] == er[:, None, :])
            & free[:, :, None]
            & valid[:, None, :]
        )  # [H, S, K]; at most one True per (row, slot) and per emit
        hit = jnp.any(match, axis=2)

        def put(cur, new):  # [H, S](, A) <- [H, K](, A)
            zero = jnp.zeros((), new.dtype)
            if cur.ndim == 2:
                sel = jnp.sum(
                    jnp.where(match, new[:, None, :], zero), axis=2,
                    dtype=new.dtype,
                )
                return jnp.where(hit, sel, cur)
            sel = jnp.sum(
                jnp.where(match[..., None], new[:, None, :, :], zero),
                axis=2, dtype=new.dtype,
            )
            return jnp.where(hit[..., None], sel, cur)

        return Events(
            time=put(stage.time, out.time),
            dst=put(stage.dst, out.dst),
            src=put(stage.src, out.src),
            seq=put(stage.seq, out.seq),
            kind=put(stage.kind, out.kind),
            args=put(stage.args, out.args),
        )

    # -- window = drain all events below the barrier ------------------------
    def _drain_window(self, st: EngineState, window_end, host0):
        if self.batch_handler is not None:
            return self._drain_window_batched(st, window_end, host0)
        if self.cfg.frontier > 0:
            return self._drain_window_frontier(st, window_end, host0)
        cfg = self.cfg
        h, k, c = cfg.n_hosts, cfg.max_emit, cfg.capacity
        b = cfg.eff_drain_batch
        sw = max(cfg.eff_stage_width, b + k)
        gids = host0 + jnp.arange(h, dtype=jnp.int32)
        cpu_cost = self.cpu_cost[gids]  # [H, NK] this shard's costs
        al_sh = self._alive_slice(host0) if self._f_crash else None

        def outer_cond(carry):
            # carried flag (computed by `_drain_flag` in the body): a
            # host's next executable instant is its earliest event or,
            # if later, when its virtual CPU frees up (cpu.c semantics).
            # The psum lives in the body, never in this predicate — the
            # structural rule SL108 pins (docs/12-Sharding.md)
            return carry[0]

        def outer_body(carry):
            (_, q, xchg, hosts, src_seq, exec_cnt, stats, cpu_free, trace,
             splane) = carry
            # merge the previous sweep's in-flight exchange before the
            # frontier read: no queue op ran since its sending push, so
            # the deferred merge is bit-identical to an immediate one
            q, xchg, stats = self._xchg_deliver(q, xchg, stats, host0)

            with phase("pop"):
                # 1. move the frontier into staging: queue rows are sorted by
                # (time, src, seq) with empties last (events.py invariant), so
                # each host's b earliest below-barrier events are its first b
                # columns, and clearing them is a prefix compare — no scatter.
                bvalid = q.time[:, :b] < window_end  # a prefix of each row
                ndump = jnp.sum(bvalid, axis=1, dtype=jnp.int32)
                with phase("observe"):
                    if self._stats and splane is not None:
                        # queue fill at pop: how full each popping host's queue
                        # is the moment its frontier dumps (pre-clear)
                        splane = splane.observe(
                            "qfill",
                            jnp.sum(q.time != TIME_INVALID, axis=1,
                                    dtype=jnp.int64),
                            ndump > 0,
                        )
                pad = ((0, 0), (0, sw - b))
                stage = Events(
                    time=jnp.pad(
                        jnp.where(bvalid, q.time[:, :b], TIME_INVALID),
                        pad, constant_values=TIME_INVALID,
                    ),
                    dst=jnp.pad(jnp.broadcast_to(gids[:, None], (h, b)), pad),
                    src=jnp.pad(q.src[:, :b], pad),
                    seq=jnp.pad(q.seq[:, :b], pad),
                    kind=jnp.pad(q.kind[:, :b], pad),
                    args=jnp.pad(q.args[:, :b], (*pad, (0, 0))),
                )
                cleared = jnp.arange(c, dtype=jnp.int32)[None, :] < ndump[:, None]
                q = dataclasses.replace(
                    q, time=jnp.where(cleared, TIME_INVALID, q.time)
                )
                if cfg.burst is not None:
                    # the dump is each host's earliest-b prefix, so every
                    # staged event precedes the queue head: folding inside
                    # it can never violate the head guard below
                    stage = self._burst_fold(stage)

                # queue-head guard: the first UN-dumped event's key, per host
                # (rows keep a sorted tail after the prefix clear, so it sits
                # at column ndump; i64max when the row is exhausted). A staged
                # event may only execute while its key precedes this — an
                # event beyond the b-column dump could still be due first, and
                # executing around it would break the (time, src, seq) total
                # order. The queue is untouched mid-sweep, so this is constant
                # per sweep.
                i64max = jnp.iinfo(jnp.int64).max
                headsel = (
                    jnp.arange(c, dtype=jnp.int32)[None, :] == ndump[:, None]
                )
                qh_t = jnp.min(jnp.where(headsel, q.time, i64max), axis=1)
                qh_ss = jnp.min(
                    jnp.where(
                        headsel & (q.time != TIME_INVALID),
                        pack_srcseq(q.src, q.seq), i64max,
                    ),
                    axis=1,
                )

            def precede_q(ev_t, ev_ss):
                return (ev_t < qh_t) | ((ev_t == qh_t) & (ev_ss < qh_ss))

            def can_run(sm, cpu_free):
                """Any host with a below-barrier staged event that precedes
                the un-dumped queue head, CPU permitting, with append
                headroom for one more handler invocation. `sm` is a
                precomputed _stage_min result — it is carried through the
                loop so each iteration pays the [H, S] min-key selection
                exactly once."""
                with phase("select"):
                    ev, mss, _oh, cnt = sm
                    mt = ev.time
                    eff = jnp.maximum(mt, cpu_free) if self._cpu_enabled else mt
                    return jnp.any(
                        (eff < window_end) & precede_q(mt, mss) & (cnt + k <= sw)
                    )

            # 2. chained execution: per iteration every host runs its
            # minimum staged event; emits append back into staging, so
            # same-window local follow-up chains run without another
            # sweep. Remote sends are barrier-clamped, hence never
            # below-barrier — they park in staging until the flush.
            def inner_cond(ic):
                return ic[0]

            def inner_body(ic):
                (_, sm, stage, hosts, src_seq, exec_cnt, stats, cpu_free,
                 trace, splane) = ic
                with phase("select"):
                    ev, mss, onehot, cnt = sm
                    ev_t = ev.time
                    eff_t = (
                        jnp.maximum(ev_t, cpu_free) if self._cpu_enabled else ev_t
                    )
                    active = (
                        (ev_t != TIME_INVALID)
                        & (eff_t < window_end)
                        & precede_q(ev_t, mss)
                        & (cnt + k <= sw)  # high-water: leftovers flush
                    )
                    # a crashed host consumes its due events without running
                    # them (quarantine): the slot still clears below — via
                    # `active` — so the drain makes progress, but the handler
                    # never fires and no emits escape the dead host
                    if self._f_crash:
                        alv = self._alive_at(al_sh, eff_t)
                        runm = active & alv
                        stats = dataclasses.replace(
                            stats,
                            n_quarantined=stats.n_quarantined
                            + (active & ~alv),
                        )
                    else:
                        runm = active
                    stage = dataclasses.replace(
                        stage,
                        time=jnp.where(
                            onehot & active[:, None], TIME_INVALID, stage.time
                        ),
                    )
                    ev = dataclasses.replace(
                        ev,
                        time=jnp.where(runm, eff_t, TIME_INVALID),
                        dst=gids,
                    )
                (hosts, src_seq, exec_cnt, stats, out, _fmask, trace,
                 splane) = self._execute_step(
                    hosts, src_seq, exec_cnt, stats, ev, runm,
                    window_end, gids, trace, splane,
                )
                with phase("append"):
                    if self._cpu_enabled:
                        ev_cost = _kind_cost(cpu_cost, ev.kind)
                        if self.cfg.burst is not None:
                            # a folded arrival stands for nseg segments: the
                            # virtual CPU pays per segment, not per event.
                            # Zero-payload count carriers (dup ACKs) are one
                            # packet; their count is protocol bookkeeping.
                            bkind, _sq, blen = self.cfg.burst[:3]
                            lw = ev.args[:, blen]
                            nseg = jnp.where(
                                (lw & BURST_LEN_MASK) > 0,
                                jnp.maximum(lw >> BURST_NSEG_SHIFT, 1), 1,
                            )
                            ev_cost = ev_cost * jnp.where(
                                ev.kind == bkind, nseg.astype(ev_cost.dtype), 1
                            )
                        cpu_free = jnp.where(
                            runm & (ev_cost > 0), eff_t + ev_cost,
                            cpu_free,
                        )
                    stage = self._stage_append(stage, out)
                    stats = dataclasses.replace(
                        stats, n_inner_steps=stats.n_inner_steps + 1
                    )
                sm2 = self._stage_min(stage)
                return (can_run(sm2, cpu_free), sm2, stage, hosts, src_seq,
                        exec_cnt, stats, cpu_free, trace, splane)

            sm0 = self._stage_min(stage)
            (_, _, stage, hosts, src_seq, exec_cnt, stats, cpu_free,
             trace, splane) = jax.lax.while_loop(
                inner_cond,
                inner_body,
                (can_run(sm0, cpu_free), sm0, stage, hosts, src_seq,
                 exec_cnt, stats, cpu_free, trace, splane),
            )

            with phase("flush"):
                # 3. flush staging leftovers (clamped remote sends, far-future
                # locals, high-water overflow) in one push + exchange. A
                # row-wise key sort compacts valid entries to a prefix; the
                # common case pushes only a narrow column slice (staged
                # leftovers are few), with a full-width fallback when any
                # host's count exceeds it — exact either way.
                skey = pack_srcseq(stage.src, stage.seq)
                t2, _ss2, dst2, src2, seq2, kind2, *acols = jax.lax.sort(
                    (stage.time, skey, stage.dst, stage.src, stage.seq,
                     stage.kind,
                     *[stage.args[:, :, i] for i in range(cfg.n_args)]),
                    dimension=1, num_keys=2,
                )
                stage = Events(
                    time=t2, dst=dst2, src=src2, seq=seq2, kind=kind2,
                    args=jnp.stack(acols, axis=-1),
                )
                w1 = min(sw, 16)
                maxcnt = jnp.max(
                    jnp.sum(stage.time != TIME_INVALID, axis=1, dtype=jnp.int32)
                )

                def push_narrow(args):
                    q, xchg, stats, stage = args
                    sl = jax.tree.map(lambda a: a[:, :w1], stage)
                    flat = sl.flatten()
                    return self._exchange_push(
                        q, xchg, stats, flat, flat.time != TIME_INVALID, host0
                    )

                def push_full(args):
                    q, xchg, stats, stage = args
                    flat = stage.flatten()
                    return self._exchange_push(
                        q, xchg, stats, flat, flat.time != TIME_INVALID, host0
                    )

                if w1 == sw:
                    q, xchg, stats = push_full((q, xchg, stats, stage))
                elif cfg.axis_name is not None:
                    # sharded: the exchange's collectives must run under a
                    # shard-uniform program, and maxcnt differs per shard —
                    # make the branch choice global. The ExchangeBuf's one
                    # static engine-level width is what lets both branches
                    # return the same carried-buffer shape.
                    go_wide = self._gany(maxcnt > w1)
                    q, xchg, stats = jax.lax.cond(
                        go_wide, push_full, push_narrow,
                        (q, xchg, stats, stage),
                    )
                else:
                    q, xchg, stats = jax.lax.cond(
                        maxcnt > w1, push_full, push_narrow,
                        (q, xchg, stats, stage),
                    )
                stats = dataclasses.replace(stats, n_sweeps=stats.n_sweeps + 1)
            more = self._drain_flag(q, cpu_free, window_end)
            return (more, q, xchg, hosts, src_seq, exec_cnt, stats,
                    cpu_free, trace, splane)

        carry = (self._drain_flag(st.queues, st.cpu_free, window_end),
                 st.queues, st.xchg, st.hosts, st.src_seq, st.exec_cnt,
                 st.stats, st.cpu_free, st.trace, st.splane)
        (_, q, xchg, hosts, src_seq, exec_cnt, stats, cpu_free,
         trace, splane) = jax.lax.while_loop(outer_cond, outer_body, carry)
        if self._cpu_enabled:
            # sent_min cannot see a destination's busy CPU: flush the
            # in-flight buffer before `_next_time`'s cpu_free defer runs
            q, xchg, stats = self._xchg_deliver(q, xchg, stats, host0)
        # each shard's inner loop trips independently; fold this window's
        # delta across shards so the counter stays replicated-consistent
        with phase("barrier"):
            inner = st.stats.n_inner_steps + self._gsum(
                stats.n_inner_steps - st.stats.n_inner_steps
            )
        with phase("observe"):
            if self._stats and splane is not None:
                # per-window occupancy: events each host executed this
                # window (hosts that ran nothing contribute no sample)
                occ = stats.n_executed - st.stats.n_executed
                splane = splane.observe("occ", occ, occ > 0)
        return dataclasses.replace(
            st,
            queues=q,
            hosts=hosts,
            src_seq=src_seq,
            exec_cnt=exec_cnt,
            stats=dataclasses.replace(
                stats, n_windows=stats.n_windows + 1, n_inner_steps=inner
            ),
            cpu_free=cpu_free,
            trace=trace,
            xchg=xchg,
            splane=splane,
        )

    # -- frontier drain: kind-partitioned runs, per-round bookkeeping --------
    def _drain_window_frontier(self, st: EngineState, window_end, host0):
        """The third drain contract (cfg.frontier > 0): bit-identical to
        the chained drain, amortized bookkeeping.

        Per round, each host's staging is key-sorted ONCE so the
        executable events form a column prefix; a sequential position
        fold (a while_loop capped at `u` positions with global early
        exit) then executes, per host, the maximal prefix RUN of
        equal-time same-kind events — per position it pays only the
        vmapped handler pass + routing. The per-event staging work the
        chained path repeats every step — the [H, S] min-key selection,
        the [H, S, K] rank-matched append, the trace-ring append —
        happens once per ROUND: executed slots clear as a prefix compare
        on the sorted buffer, every position's routed emits land in one
        deferred `_stage_append`, and tracing is one wide append whose
        per-host record order (position-major, exec then emits) matches
        the chained per-step appends record for record.

        Why the run rule is exact: run members share one time t, and
        every in-round LOCAL emit is scheduled at >= t+1 (the dt >= 1
        invariant the transport/model tier declares; remote emits are
        barrier-clamped >= window_end), so no emit can precede a
        remaining run member in (time, src, seq) order — the sorted
        column j IS the host's minimum staged event when position j
        executes, exactly what the chained drain would have selected.
        Per-host stall conditions (CPU busy past the barrier, queue-head
        guard, append headroom) are evaluated per position with the same
        accounting the chained path uses, and they are monotone within a
        sweep, so both paths stop each host at the same event. The
        same-kind rule partitions each round by handler kind ("every
        kind runs once per round"); kinds outside `frontier_kinds`
        execute one position per round — the explicit in-host ordering
        fold for kinds that want visible sequential granularity.
        """
        cfg = self.cfg
        h, k, c = cfg.n_hosts, cfg.max_emit, cfg.capacity
        b = cfg.eff_drain_batch
        sw = max(cfg.eff_stage_width, b + k)
        u = max(1, min(cfg.frontier, sw))
        gids = host0 + jnp.arange(h, dtype=jnp.int32)
        cpu_cost = self.cpu_cost[gids]  # [H, NK] this shard's costs
        al_sh = self._alive_slice(host0) if self._f_crash else None
        fk = self._frontier_kinds
        use_tr = self._trace and st.trace is not None
        if use_tr:
            from shadow_tpu.obs.trace import (
                OP_DROP, OP_EXEC, OP_FDROP, OP_SEND, trace_append,
            )
        la = cfg.trace_len_arg
        i64max = jnp.iinfo(jnp.int64).max

        def per_host(hs, e, key):
            branches = tuple(
                (lambda fn: lambda: _pad(fn(hs, e, key), k))(fn)
                for fn in self.handlers
            )

            def _pad(res, kk):
                hs2, em = res
                return hs2, em.pad_to(kk)

            idx = jnp.clip(e.kind, 0, len(branches) - 1)
            return jax.lax.switch(idx, branches)

        def outer_cond(carry):
            # carried flag (see the chained drain): the psum/any runs in
            # the body, never in this predicate
            return carry[0]

        def outer_body(carry):
            (_, q, xchg, hosts, src_seq, exec_cnt, stats, cpu_free, trace,
             splane) = carry
            q, xchg, stats = self._xchg_deliver(q, xchg, stats, host0)

            with phase("pop"):
                # 1. frontier dump into staging — identical to the chained
                # drain (same prefix clear, same optional burst fold)
                bvalid = q.time[:, :b] < window_end
                ndump = jnp.sum(bvalid, axis=1, dtype=jnp.int32)
                with phase("observe"):
                    if self._stats and splane is not None:
                        # same pre-clear observation point as the chained drain,
                        # so qfill histograms are bit-identical across contracts
                        splane = splane.observe(
                            "qfill",
                            jnp.sum(q.time != TIME_INVALID, axis=1,
                                    dtype=jnp.int64),
                            ndump > 0,
                        )
                pad = ((0, 0), (0, sw - b))
                stage = Events(
                    time=jnp.pad(
                        jnp.where(bvalid, q.time[:, :b], TIME_INVALID),
                        pad, constant_values=TIME_INVALID,
                    ),
                    dst=jnp.pad(jnp.broadcast_to(gids[:, None], (h, b)), pad),
                    src=jnp.pad(q.src[:, :b], pad),
                    seq=jnp.pad(q.seq[:, :b], pad),
                    kind=jnp.pad(q.kind[:, :b], pad),
                    args=jnp.pad(q.args[:, :b], (*pad, (0, 0))),
                )
                cleared = jnp.arange(c, dtype=jnp.int32)[None, :] < ndump[:, None]
                q = dataclasses.replace(
                    q, time=jnp.where(cleared, TIME_INVALID, q.time)
                )
                if cfg.burst is not None:
                    stage = self._burst_fold(stage)

                # queue-head guard — identical to the chained drain
                headsel = (
                    jnp.arange(c, dtype=jnp.int32)[None, :] == ndump[:, None]
                )
                qh_t = jnp.min(jnp.where(headsel, q.time, i64max), axis=1)
                qh_ss = jnp.min(
                    jnp.where(
                        headsel & (q.time != TIME_INVALID),
                        pack_srcseq(q.src, q.seq), i64max,
                    ),
                    axis=1,
                )

            def precede_q(ev_t, ev_ss):
                return (ev_t < qh_t) | ((ev_t == qh_t) & (ev_ss < qh_ss))

            def can_run(sm, cpu_free):
                with phase("select"):
                    ev, mss, _oh, cnt = sm
                    mt = ev.time
                    eff = jnp.maximum(mt, cpu_free) if self._cpu_enabled else mt
                    return jnp.any(
                        (eff < window_end) & precede_q(mt, mss) & (cnt + k <= sw)
                    )

            # 2. rounds: sort once, execute a run, bookkeep once
            def round_cond(rc):
                return rc[0]

            def round_body(rc):
                (_, stage, hosts, src_seq, exec_cnt, stats, cpu_free,
                 trace, splane) = rc
                with phase("select"):
                    skey = pack_srcseq(stage.src, stage.seq)
                    t2, ss2, dst2, src2, seq2, kind2, *acols = jax.lax.sort(
                        (stage.time, skey, stage.dst, stage.src, stage.seq,
                         stage.kind,
                         *[stage.args[:, :, i] for i in range(cfg.n_args)]),
                        dimension=1, num_keys=2,
                    )
                    args2 = jnp.stack(acols, axis=-1)
                    cnt0 = jnp.sum(
                        t2 != TIME_INVALID, axis=1, dtype=jnp.int32
                    )
                    t0 = t2[:, 0]
                    kind0 = kind2[:, 0]
                    if fk is not None:
                        allowed0 = jnp.zeros((h,), bool)
                        for kk in fk:
                            allowed0 = allowed0 | (kind0 == kk)
                    uidx = jnp.arange(u, dtype=jnp.int32)

                def pos_cond(pc):
                    return pc[0]

                def pos_body(pc):
                    (_, j, still, hosts, src_seq, exec_cnt, stats,
                     cpu_free, cnt, nact, outbuf, trbuf, splane) = pc
                    with phase("select"):
                        col = lambda a: jax.lax.dynamic_index_in_dim(
                            a, j, axis=1, keepdims=False
                        )
                        ev_t = col(t2)
                        ev_ss = col(ss2)
                        e_src = col(src2)
                        e_seq = col(seq2)
                        e_kind = col(kind2)
                        e_args = col(args2)
                        eff_t = (
                            jnp.maximum(ev_t, cpu_free)
                            if self._cpu_enabled else ev_t
                        )
                        member = (ev_t == t0) & (e_kind == kind0)
                        if fk is not None:
                            member = member & (allowed0 | (j == 0))
                        active = (
                            still & member
                            & (ev_t != TIME_INVALID)
                            & (eff_t < window_end)
                            & precede_q(ev_t, ev_ss)
                            & (cnt + k <= sw)
                        )
                        if self._f_crash:
                            alv = self._alive_at(al_sh, eff_t)
                            runm = active & alv
                            stats = dataclasses.replace(
                                stats,
                                n_quarantined=stats.n_quarantined
                                + (active & ~alv),
                            )
                        else:
                            runm = active
                        ev = Events(
                            time=jnp.where(runm, eff_t, TIME_INVALID),
                            dst=gids, src=e_src, seq=e_seq, kind=e_kind,
                            args=e_args,
                        )
                    with phase("handler"):
                        hkeys, rkeys = srng.event_keys(
                            self._base_key, gids, exec_cnt
                        )
                        hosts2, emit = jax.vmap(per_host)(hosts, ev, hkeys)
                        hosts = _select_rows(runm, hosts2, hosts)
                        emask = emit.mask & runm[:, None]
                    with phase("route"):
                        inc = emask.astype(jnp.int32)
                        within = jnp.cumsum(inc, axis=1) - inc
                        seq = src_seq[:, None] + within
                        src_seq = src_seq + jnp.sum(inc, axis=1, dtype=jnp.int32)
                        out, final_mask, dropped, fdropped, _t, _is_local = (
                            self._route(
                                emit, ev.time, gids, window_end, rkeys, emask,
                                seq,
                            )
                        )
                    with phase("observe"):
                        if self._stats and splane is not None:
                            # same observation as _execute_step's, so the
                            # wait/net histograms are bit-identical to the
                            # chained drain's
                            delta = _t - ev.time[:, None]
                            splane = splane.observe("wait", delta, final_mask)
                            splane = splane.observe(
                                "net", delta, final_mask & ~_is_local
                            )
                    with phase("append"):
                        if self._cpu_enabled:
                            ev_cost = _kind_cost(cpu_cost, ev.kind)
                            if cfg.burst is not None:
                                bkind, _sq, blen = cfg.burst[:3]
                                lw = ev.args[:, blen]
                                nseg = jnp.where(
                                    (lw & BURST_LEN_MASK) > 0,
                                    jnp.maximum(lw >> BURST_NSEG_SHIFT, 1), 1,
                                )
                                ev_cost = ev_cost * jnp.where(
                                    ev.kind == bkind,
                                    nseg.astype(ev_cost.dtype), 1,
                                )
                            cpu_free = jnp.where(
                                runm & (ev_cost > 0), eff_t + ev_cost, cpu_free
                            )
                    with phase("handler"):
                        exec_cnt = exec_cnt + runm.astype(jnp.int32)
                        stats = dataclasses.replace(
                            stats,
                            n_executed=stats.n_executed + runm,
                            n_emitted=stats.n_emitted
                            + jnp.sum(inc, axis=1, dtype=jnp.int64),
                            n_net_dropped=stats.n_net_dropped
                            + jnp.sum(dropped, axis=1, dtype=jnp.int64),
                            n_fault_dropped=stats.n_fault_dropped
                            + jnp.sum(fdropped, axis=1, dtype=jnp.int64),
                            n_by_kind=stats.n_by_kind + (
                                jax.nn.one_hot(
                                    jnp.clip(
                                        ev.kind, 0, len(self.handlers) - 1
                                    ),
                                    len(self.handlers), dtype=jnp.int64,
                                )
                                * runm[:, None]
                            ),
                        )
                    with phase("append"):
                        cnt = (
                            cnt - active.astype(jnp.int32)
                            + jnp.sum(final_mask, axis=1, dtype=jnp.int32)
                        )
                        nact = nact + active.astype(jnp.int32)

                        def buf_put(buf, v):
                            m = (uidx == j).reshape(
                                (1, u) + (1,) * (buf.ndim - 2)
                            )
                            return jnp.where(m, v[:, None], buf)

                        outbuf = Events(
                            time=buf_put(outbuf.time, out.time),
                            dst=buf_put(outbuf.dst, out.dst),
                            src=buf_put(outbuf.src, out.src),
                            seq=buf_put(outbuf.seq, out.seq),
                            kind=buf_put(outbuf.kind, out.kind),
                            args=buf_put(outbuf.args, out.args),
                        )
                    with phase("observe"):
                        if use_tr:
                            ecol = lambda a: a[:, None]
                            op_send = jnp.where(
                                dropped, OP_DROP,
                                jnp.where(fdropped, OP_FDROP, OP_SEND),
                            ).astype(jnp.int32)
                            row = (
                                jnp.concatenate(
                                    [ecol(ev.time),
                                     jnp.broadcast_to(ecol(ev.time), (h, k))],
                                    1,
                                ),
                                jnp.concatenate([ecol(ev.src), out.src], 1),
                                jnp.concatenate([ecol(ev.dst), out.dst], 1),
                                jnp.concatenate([ecol(ev.kind), out.kind], 1),
                                jnp.concatenate(
                                    [ev.args[:, la:la + 1],
                                     out.args[:, :, la]], 1
                                ),
                                jnp.concatenate([ecol(ev.seq), out.seq], 1),
                                jnp.concatenate(
                                    [jnp.full((h, 1), OP_EXEC, jnp.int32),
                                     op_send], 1
                                ),
                                jnp.concatenate(
                                    [ecol(runm), emask & ~_is_local], 1
                                ),
                            )
                            trbuf = tuple(
                                buf_put(bb, vv) for bb, vv in zip(trbuf, row)
                            )
                    with phase("select"):
                        go = jnp.any(active) & (j + 1 < u)
                    return (go, j + 1, active, hosts, src_seq, exec_cnt,
                            stats, cpu_free, cnt, nact, outbuf, trbuf,
                            splane)

                with phase("append"):
                    outbuf0 = Events(
                        time=jnp.full((h, u, k), TIME_INVALID, jnp.int64),
                        dst=jnp.zeros((h, u, k), jnp.int32),
                        src=jnp.zeros((h, u, k), jnp.int32),
                        seq=jnp.zeros((h, u, k), jnp.int32),
                        kind=jnp.zeros((h, u, k), jnp.int32),
                        args=jnp.zeros((h, u, k, cfg.n_args), jnp.int32),
                    )
                    trbuf0 = None
                    if use_tr:
                        z32 = jnp.zeros((h, u, 1 + k), jnp.int32)
                        trbuf0 = (
                            jnp.zeros((h, u, 1 + k), jnp.int64),
                            z32, z32, z32, z32, z32, z32,
                            jnp.zeros((h, u, 1 + k), bool),
                        )
                (_, jn, _still, hosts, src_seq, exec_cnt, stats, cpu_free,
                 _cnt, nact, outbuf, trbuf, splane) = jax.lax.while_loop(
                    pos_cond, pos_body,
                    (jnp.asarray(True), jnp.zeros((), jnp.int32),
                     jnp.ones((h,), bool), hosts, src_seq, exec_cnt,
                     stats, cpu_free, cnt0, jnp.zeros((h,), jnp.int32),
                     outbuf0, trbuf0, splane),
                )
                with phase("observe"):
                    if self._stats and splane is not None:
                        # frontier run length: how many positions each host
                        # actually executed this round — the quantity that
                        # decides whether the per-round sort amortizes
                        splane = splane.observe("runlen", nact, nact > 0)

                with phase("append"):
                    # 3. per-round bookkeeping: prefix-clear the executed
                    # columns, one deferred append of every position's routed
                    # emits (headroom is guaranteed — the per-position gate
                    # kept cnt + K <= SW with the exact chained accounting),
                    # one wide trace append in chained record order
                    colmask = (
                        jnp.arange(sw, dtype=jnp.int32)[None, :] < nact[:, None]
                    )
                    stage = Events(
                        time=jnp.where(colmask, TIME_INVALID, t2),
                        dst=dst2, src=src2, seq=seq2, kind=kind2, args=args2,
                    )
                    stage = self._stage_append(
                        stage,
                        Events(
                            time=outbuf.time.reshape(h, u * k),
                            dst=outbuf.dst.reshape(h, u * k),
                            src=outbuf.src.reshape(h, u * k),
                            seq=outbuf.seq.reshape(h, u * k),
                            kind=outbuf.kind.reshape(h, u * k),
                            args=outbuf.args.reshape(h, u * k, cfg.n_args),
                        ),
                    )
                    with phase("observe"):
                        if use_tr:
                            w = u * (1 + k)
                            rs = lambda a: a.reshape(h, w)
                            trace = trace_append(
                                trace, cfg.trace,
                                time=rs(trbuf[0]), src=rs(trbuf[1]),
                                dst=rs(trbuf[2]), kind=rs(trbuf[3]),
                                plen=rs(trbuf[4]), seq=rs(trbuf[5]),
                                op=rs(trbuf[6]), mask=rs(trbuf[7]),
                            )
                    stats = dataclasses.replace(
                        stats,
                        n_inner_steps=stats.n_inner_steps
                        + jn.astype(jnp.int64),
                    )
                sm2 = self._stage_min(stage)
                return (can_run(sm2, cpu_free), stage, hosts, src_seq,
                        exec_cnt, stats, cpu_free, trace, splane)

            sm0 = self._stage_min(stage)
            (_, stage, hosts, src_seq, exec_cnt, stats, cpu_free,
             trace, splane) = jax.lax.while_loop(
                round_cond, round_body,
                (can_run(sm0, cpu_free), stage, hosts, src_seq, exec_cnt,
                 stats, cpu_free, trace, splane),
            )

            with phase("flush"):
                # 4. flush staging leftovers — identical to the chained drain
                skey = pack_srcseq(stage.src, stage.seq)
                t2, _ss2, dst2, src2, seq2, kind2, *acols = jax.lax.sort(
                    (stage.time, skey, stage.dst, stage.src, stage.seq,
                     stage.kind,
                     *[stage.args[:, :, i] for i in range(cfg.n_args)]),
                    dimension=1, num_keys=2,
                )
                stage = Events(
                    time=t2, dst=dst2, src=src2, seq=seq2, kind=kind2,
                    args=jnp.stack(acols, axis=-1),
                )
                w1 = min(sw, 16)
                maxcnt = jnp.max(
                    jnp.sum(stage.time != TIME_INVALID, axis=1, dtype=jnp.int32)
                )

                def push_narrow(args):
                    q, xchg, stats, stage = args
                    sl = jax.tree.map(lambda a: a[:, :w1], stage)
                    flat = sl.flatten()
                    return self._exchange_push(
                        q, xchg, stats, flat, flat.time != TIME_INVALID, host0
                    )

                def push_full(args):
                    q, xchg, stats, stage = args
                    flat = stage.flatten()
                    return self._exchange_push(
                        q, xchg, stats, flat, flat.time != TIME_INVALID, host0
                    )

                if w1 == sw:
                    q, xchg, stats = push_full((q, xchg, stats, stage))
                elif cfg.axis_name is not None:
                    go_wide = self._gany(maxcnt > w1)
                    q, xchg, stats = jax.lax.cond(
                        go_wide, push_full, push_narrow,
                        (q, xchg, stats, stage),
                    )
                else:
                    q, xchg, stats = jax.lax.cond(
                        maxcnt > w1, push_full, push_narrow,
                        (q, xchg, stats, stage),
                    )
                stats = dataclasses.replace(stats, n_sweeps=stats.n_sweeps + 1)
            more = self._drain_flag(q, cpu_free, window_end)
            return (more, q, xchg, hosts, src_seq, exec_cnt, stats,
                    cpu_free, trace, splane)

        carry = (self._drain_flag(st.queues, st.cpu_free, window_end),
                 st.queues, st.xchg, st.hosts, st.src_seq, st.exec_cnt,
                 st.stats, st.cpu_free, st.trace, st.splane)
        (_, q, xchg, hosts, src_seq, exec_cnt, stats, cpu_free,
         trace, splane) = jax.lax.while_loop(outer_cond, outer_body, carry)
        if self._cpu_enabled:
            q, xchg, stats = self._xchg_deliver(q, xchg, stats, host0)
        with phase("barrier"):
            inner = st.stats.n_inner_steps + self._gsum(
                stats.n_inner_steps - st.stats.n_inner_steps
            )
        with phase("observe"):
            if self._stats and splane is not None:
                occ = stats.n_executed - st.stats.n_executed
                splane = splane.observe("occ", occ, occ > 0)
        return dataclasses.replace(
            st,
            queues=q,
            hosts=hosts,
            src_seq=src_seq,
            exec_cnt=exec_cnt,
            stats=dataclasses.replace(
                stats, n_windows=stats.n_windows + 1, n_inner_steps=inner
            ),
            cpu_free=cpu_free,
            trace=trace,
            xchg=xchg,
            splane=splane,
        )

    @phase("barrier")
    def _next_time(self, st: EngineState) -> jax.Array:
        """Global earliest executable time (one reduction + one pmin):
        per host the earliest pending event, deferred to when its virtual
        CPU frees up (empty queues stay at TIME_INVALID = i64 max).

        Sharded, the barrier also folds in `xchg.sent_min` — the min
        time of events still in flight in the exchange double buffer —
        through the SENDER-side copy, so the pmin never carries a data
        dependence on an all_to_all completing (ExchangeBuf docstring).
        """
        nxt = st.queues.min_time()
        if self._cpu_enabled:
            nxt = jnp.maximum(nxt, st.cpu_free)
        m = jnp.min(nxt)
        if st.xchg is not None:
            m = jnp.minimum(m, st.xchg.sent_min[0])
        return self._gmin(m)

    @phase("fault")
    def _apply_fault_epoch(self, st: EngineState, nxt, host0) -> EngineState:
        """Apply fault-schedule transitions entered since the last window.

        Window starts are globally synchronized (pmin barrier), so every
        shard applies the same transitions at the same sim time — the
        epoch watermark keeps this exact across checkpoint/restore too.
        For hosts dead at any newly-entered epoch: wipe their queues
        (counted as quarantined — a crash voids pending work) and
        re-template their state rows from `fault_reset`, which is what a
        restart is — fresh listening sockets, zeroed app state, while
        `src_seq`/`exec_cnt` stay monotone so (src, seq) uniqueness and
        RNG streams survive the reboot. Bandwidth epochs rescale NIC
        rates from the template's configured values. Runs under lax.cond:
        a window with no epoch change pays one scalar compare."""
        f = self.faults
        h = self.cfg.n_hosts
        tt = f.times.shape[0]
        e = f.epoch_of(nxt)

        def apply(st):
            idx = jnp.arange(tt, dtype=jnp.int32)
            gap = (idx > st.fault_epoch) & (idx <= e)
            if self._f_crash or self._f_bw:
                tmpl = jax.tree.map(
                    lambda a: jax.lax.dynamic_slice_in_dim(
                        a, host0, h, axis=0
                    ),
                    self.fault_reset,
                )
            hosts, q, stats = st.hosts, st.queues, st.stats
            if self._f_crash:
                al_sh = jax.lax.dynamic_slice_in_dim(
                    f.alive, host0, h, axis=1
                )
                reset = jnp.any(gap[:, None] & ~al_sh, axis=0)  # [H]
                wiped = jnp.sum(
                    reset[:, None] & (q.time != TIME_INVALID),
                    axis=1, dtype=jnp.int64,
                )
                q = dataclasses.replace(
                    q, time=jnp.where(reset[:, None], TIME_INVALID, q.time)
                )
                hosts = _select_rows(reset, tmpl, hosts)
                stats = dataclasses.replace(
                    stats, n_quarantined=stats.n_quarantined + wiped
                )
            if self._f_bw:
                bw_t = jax.lax.dynamic_slice_in_dim(
                    f.bw_scale, host0, h, axis=1
                )  # [T, H]
                bw_e = jnp.sum(
                    jnp.where((idx == e)[:, None], bw_t, 0.0), axis=0
                )
                net = hosts.net
                hosts = dataclasses.replace(
                    hosts,
                    net=dataclasses.replace(
                        net,
                        nic_tx=dataclasses.replace(
                            net.nic_tx, rate=tmpl.net.nic_tx.rate * bw_e
                        ),
                        nic_rx=dataclasses.replace(
                            net.nic_rx, rate=tmpl.net.nic_rx.rate * bw_e
                        ),
                    ),
                )
            return dataclasses.replace(
                st, queues=q, hosts=hosts, stats=stats,
                fault_epoch=e.astype(jnp.int32),
            )

        return jax.lax.cond(e != st.fault_epoch, apply, lambda s: s, st)

    def _advance(self, st: EngineState, nxt, stop, host0,
                 window=None) -> EngineState:
        """Open the window [nxt, min(nxt+window, stop)) and drain it.

        `window` defaults to the static conservative bound
        (cfg.lookahead). A *wider* traced bound stays causally safe but
        is NOT bit-identical to the default: `_route` clamps cross-host
        arrivals up to the window barrier (t_remote = max(t + lat,
        window_end)), so a barrier farther out defers those arrivals
        with it — cross-host packet timing coarsens by up to the extra
        width. That is exactly the documented `--runahead` tradeoff,
        except the bound here is a traced scalar: adaptive window
        sizing retunes it between windows with zero recompiles, where
        --runahead bakes a constant into the program. Same-host events
        inside the window keep their exact (time, src, seq) order
        regardless of width. A narrower bound than lookahead is legal
        too (it just wastes barriers). Runs that must be bit-identical
        use the default fixed bound (`--window` absent).
        """
        if window is None:
            window = self.cfg.lookahead
        with phase("barrier"):
            window_end = jnp.minimum(nxt + window, stop)
        if st.xchg is not None:
            # open of window k: merge window k-1's in-flight exchange.
            # Must precede the fault-epoch wipe (an immediate push would
            # have) and the drain's initial flag, whose barrier these
            # events may now be below.
            q, xchg, stats = self._xchg_deliver(st.queues, st.xchg, st.stats,
                                                host0)
            st = dataclasses.replace(st, queues=q, xchg=xchg, stats=stats)
        if self._f_crash or self._f_link or self._f_bw:
            # link-only schedules advance just the epoch watermark (one
            # scalar compare per window): keeping the watermark current
            # for EVERY fault kind is what lets a fleet lane's state
            # match its solo run leaf-for-leaf whatever mix of fault
            # kinds its sibling lanes compiled in
            st = self._apply_fault_epoch(st, nxt, host0)
        st = self._drain_window(st, window_end, host0)
        return dataclasses.replace(st, now=window_end)

    def step_window(self, st: EngineState, stop, host0=0,
                    window=None) -> EngineState:
        """Advance one conservative window (jittable; no-op when finished).

        `window` optionally widens the window bound past cfg.lookahead
        as a traced i64 scalar (see `_advance`); None keeps the static
        default and the default lowering byte-identical."""
        host0 = jnp.asarray(host0, jnp.int32)
        stop = jnp.asarray(stop, jnp.int64)
        nxt = self._next_time(st)

        def done(st):
            # no event below stop remains: land on stop so callers looping
            # "while now < stop: step_window" terminate. Flush any
            # in-flight exchange so the final queues match a run whose
            # deliveries were immediate (i.e. the single-device run).
            q, xchg, stats = self._xchg_deliver(st.queues, st.xchg, st.stats,
                                                host0)
            return dataclasses.replace(st, queues=q, xchg=xchg, stats=stats,
                                       now=stop)

        return jax.lax.cond(
            nxt < stop,
            lambda s: self._advance(s, nxt, stop, host0, window),
            done,
            st,
        )

    def run(self, st: EngineState, stop, host0=0) -> EngineState:
        """Run until no pending event is earlier than `stop` (jittable).

        This is the whole of master_run/slave_run/worker_run collapsed into
        one compiled loop: window barrier = global pmin, round = outer
        iteration, event execution = vmapped sweeps. The next-event time is
        threaded through the carry so each window costs exactly one global
        reduction + pmin collective.
        """
        host0 = jnp.asarray(host0, jnp.int32)
        stop = jnp.asarray(stop, jnp.int64)

        def cond(carry):
            _, nxt = carry
            return nxt < stop

        def body(carry):
            st, nxt = carry
            st = self._advance(st, nxt, stop, host0)
            return st, self._next_time(st)

        st, _ = jax.lax.while_loop(cond, body, (st, self._next_time(st)))
        if st.xchg is not None:
            # flush the last window's in-flight exchange: every remaining
            # event is >= stop, but it must sit in the queues (not the
            # double buffer) for the final state to match single-device
            q, xchg, stats = self._xchg_deliver(st.queues, st.xchg, st.stats,
                                                host0)
            st = dataclasses.replace(st, queues=q, xchg=xchg, stats=stats)
        return dataclasses.replace(st, now=stop)


class ConstantNetwork:
    """Uniform complete-graph network: fixed latency, fixed reliability.

    Mirrors the single-PoI topologies the reference's tests embed (e.g.
    src/test/phold/phold.test.shadow.config.xml: one vertex, 50ms self-loop).
    """

    def __init__(self, latency_ns: int, reliability: float = 1.0,
                 jitter_ns: int = 0):
        self.latency_ns = latency_ns
        self.reliability = reliability
        self.jitter_ns = jitter_ns
        self.has_jitter = jitter_ns > 0

    def route(self, src, dst):
        shape = jnp.broadcast_shapes(src.shape, dst.shape)
        return (
            jnp.full(shape, self.latency_ns, jnp.int64),
            jnp.full(shape, self.reliability, jnp.float32),
            jnp.full(shape, self.jitter_ns, jnp.int64),
        )
