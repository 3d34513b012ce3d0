#!/usr/bin/env python
"""Benchmark: PHOLD events/sec on the device engine vs a pure-Python DES.

PHOLD is the reference's own performance harness
(reference: src/test/phold/test_phold.c, SURVEY.md §6): a closed population
of messages bouncing between hosts through a 50ms-latency topology. The
metric is executed events per wall-clock second; `vs_baseline` is the ratio
against a single-threaded heapq discrete-event loop running the identical
workload (the classic CPU DES architecture the reference's serial scheduler
policy embodies — scheduler_policy_global_single.c).

Prints ONE JSON line per completed stage, each a complete result superset
of the previous, so the *last* line is always the richest result available
when the process ends — even if an external budget kills it mid-stage:

  1. primary PHOLD (batched drain) — the headline metric, printed the
     moment it lands;
  2. + skewed-target PHOLD;
  3. + 1k-host Tor circuits (BASELINE config 3 shape);
  4. + 1k-node Bitcoin gossip (BASELINE config 5 shape).

One process per chip: the parent runs only the pure-Python baseline
and never touches JAX; every chip stage, the primary included, runs in
a child process that holds the chip alone. A chip stage whose first
device is not a TPU fails; it never measures the CPU instead. The
CPU-only gates (--perf-smoke, --fleet*, --serve-*, --chaos-worker,
--multichip-worker, --metrics-smoke-worker) pin JAX_PLATFORMS=cpu and
report no device metric.

Compilation is cached persistently (shadow_tpu.utils.compile_cache:
JAX_COMPILATION_CACHE_DIR if set, else .jax_cache in the checkout), so
re-runs on the same machine skip straight to execution. A wall-clock
budget (BENCH_BUDGET_S, default 840s) governs the secondary stages:
each runs only if enough budget remains.
"""

import heapq
import json
import math
import os
import random
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))

N_HOSTS = 4096
MSGS_PER_HOST = 8
CAPACITY = 64
STOP_SIM_SECONDS = 20
SEED = 1234
LATENCY_S = 0.050
MEAN_DELAY_S = 0.010

_T0 = time.monotonic()
_BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", 840))


def _remaining() -> float:
    return _BUDGET_S - (time.monotonic() - _T0)


def _chip_stage() -> None:
    """Start of every chip stage: the persistent compile cache on, and
    a first device that is not a TPU ends the stage with an error."""
    from shadow_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: this stage measures the TPU; the first "
                         f"device is {dev.platform!r} ({dev.device_kind})")


def _cpu_stage(*, cache: bool = True) -> None:
    """Start of every CPU-only gate: JAX (and every child it starts)
    pinned to the CPU. `cache=False` turns the persistent compile cache
    off, for stages whose measurement is the cold compile itself."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if cache:
        from shadow_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
    else:
        jax.config.update("jax_enable_compilation_cache", False)


def python_baseline_rate(
    n_hosts=N_HOSTS, msgs_per_host=MSGS_PER_HOST, n_events=300_000, repeats=3
) -> float:
    """Single-threaded heapq PHOLD at the same scale as the device run.

    Same host count, initial population, latency (applied to every send,
    self-addressed included — matching the engine), and delay law. The rate
    is measured over a fixed event count (per-event cost is horizon-
    independent); median of `repeats` runs to damp scheduler noise.
    """
    rates = []
    for rep in range(repeats):
        rng = random.Random(SEED + rep)
        q = []
        for h in range(n_hosts):
            for m in range(msgs_per_host):
                heapq.heappush(q, ((h % 16 + 1) * 1e-3, h, m, h))
        t0 = time.perf_counter()
        executed = 0
        seq = n_hosts * msgs_per_host
        while executed < n_events:
            t, dst, _, _ = heapq.heappop(q)
            executed += 1
            peer = rng.randrange(n_hosts)
            dt = rng.expovariate(1.0 / MEAN_DELAY_S)
            heapq.heappush(q, (t + dt + LATENCY_S, peer, seq, dst))
            seq += 1
        rates.append(executed / (time.perf_counter() - t0))
    return sorted(rates)[len(rates) // 2]


def tpu_rate(stop_s: int, *, hot_hosts=0, hot_weight=0.0, capacity=CAPACITY,
             batched=True, overflow="drop"):
    _chip_stage()
    import jax
    import jax.numpy as jnp

    from shadow_tpu.core.timebase import SECOND, seconds
    from shadow_tpu.models import phold

    from shadow_tpu.obs import WindowProfiler

    prof = WindowProfiler()
    with prof.phase("build"):
        eng, init = phold.build(
            N_HOSTS,
            capacity=capacity,
            latency_ns=seconds(LATENCY_S),
            mean_delay_ns=seconds(MEAN_DELAY_S),
            msgs_per_host=MSGS_PER_HOST,
            seed=SEED,
            hot_hosts=hot_hosts,
            hot_weight=hot_weight,
            batched=batched,
            spill=4 * capacity if overflow == "spill" else 0,
        )
        if overflow == "spill":
            # window-stepped with host boundary harvest/refill: the spill
            # run pays host round trips per window, which is exactly the
            # overhead the skew_spill_* numbers exist to measure
            from shadow_tpu.runtime.pressure import PressureController

            step = jax.jit(eng.step_window)

            class _SpillRunner:
                def __init__(self):
                    self.ctrl = None

                def __call__(self, st, stop):
                    self.ctrl = PressureController(
                        N_HOSTS, capacity, eng.cfg.lookahead,
                        n_args=phold.N_PHOLD_ARGS,
                    )
                    h0 = jnp.asarray(0, jnp.int32)
                    while int(jax.device_get(st.now)) < int(stop):
                        st = step(st, stop, h0)
                        st = self.ctrl.boundary(st)
                    return st

            run = _SpillRunner()
        else:
            run = jax.jit(eng.run)

        # compile + warm-up on a short horizon
        st = init()
        jax.block_until_ready(run(st, jnp.int64(1 * SECOND)))

    # the device_get inside the timed region pins the measurement to
    # materialized values
    st = init()
    t0 = time.perf_counter()
    with prof.phase("step"):
        st = run(st, jnp.int64(stop_s * SECOND))
        executed = int(jax.device_get(st.stats.n_executed).sum())
    wall = time.perf_counter() - t0
    sweeps = int(st.stats.n_sweeps)
    dev = jax.devices()[0]
    pressure = {}
    if overflow == "spill":
        snap = run.ctrl.snapshot(st)
        pressure = {
            "spilled": snap["spilled"],
            "refilled": snap["refilled"],
            "spill_lost": snap["spill_lost"],
            "overdue": snap["overdue"],
        }
    return {
        "overflow": overflow,
        **pressure,
        "events": executed,
        "wall_s": wall,
        "events_per_s": executed / wall,
        "sim_s_per_wall_s": stop_s / wall,
        "windows": int(st.stats.n_windows),
        # scheduler self-profiling (scheduler.c:266-271 analog): sweeps
        # are the unit of fixed overhead (sort + merge + push); high
        # events/sweep is what the batched drain buys
        "sweeps": sweeps,
        "events_per_sweep": round(executed / max(sweeps, 1), 1),
        "drops": int(jax.device_get(st.queues.drops).sum()),
        "device": str(dev.device_kind),
        "n_hosts": N_HOSTS,
        "drain": "batched" if batched else "sequential",
        # per-phase wall breakdown (obs.WindowProfiler): how much of the
        # stage went to build+compile vs measured device execution
        "profile": {
            name: round(p["total_s"], 3)
            for name, p in prof.summary()["phases"].items()
        },
    }


# tor tiers, SMALLEST first: the 76-host shape lands a number before the
# climb to 304, 1020, and 10000 hosts (BASELINE configs 3-4). Each
# tier's compile lands in the persistent cache, so a later run on the
# same machine reloads it. Tier 3 is the north-star shape itself: 10k
# hosts (3000 relays + 6700 torperf clients + 300 servers, BASELINE
# config 4 / the 2018-ccs-tmodel framing).
TOR_TIERS = ((4, 60, 4), (30, 204, 10), (110, 660, 30), (1000, 6700, 300))


def _stamp(msg: str) -> None:
    """Stage timestamps on stderr (compile vs run vs hang forensics)."""
    print(f"bench[{time.monotonic() - _T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def tor_worker():
    """Secondary metric: Tor-circuit workload (BASELINE configs 3-4) at
    the BENCH_TOR_TIER size. The relay-crypto CPU model (cycles per
    forwarded segment, models/tor.py RELAY_CYCLES_PER_BYTE) is ON by
    default — reference hosts always pay CPU (cpu.c:56-107) — so tor_*
    is the honest headline; BENCH_TOR_CPU=0 reports the model-off
    variant under tor_nocpu_* for the side-by-side. Tier 3 reports under
    tor10k_* (the north-star shape must have its own stable keys)."""
    _chip_stage()
    import jax

    from shadow_tpu.config import parse_config
    from shadow_tpu.core.timebase import MILLISECOND, SECOND
    from shadow_tpu.examples import tor_example
    from shadow_tpu.sim import build_simulation

    with_cpu = os.environ.get("BENCH_TOR_CPU", "1") != "0"
    # one tier per process: each child holds the chip alone
    tier_idx = int(os.environ.get("BENCH_TOR_TIER", 0)) % len(TOR_TIERS)
    relays, clients, servers = TOR_TIERS[tier_idx]
    # measured horizon shrinks with tier size so every tier's timed run
    # fits a per-round budget. Every tier reaches past t=8: clients
    # start staggered at 3 + i%5 s (examples.py), so the window covers
    # the steady state torperf-style baselines report rather than the
    # rampup idle (r05 first attempts measured 0-20% of clients live).
    # BENCH_TOR_STOP_S, not BENCH_STOP_S: main() exports the latter for
    # the PHOLD workers, which would silently preempt the tier tuple
    stop_s = (20, 10, 10, 10)[tier_idx]
    stop_s = int(os.environ.get("BENCH_TOR_STOP_S", stop_s))
    _stamp(f"tor tier {relays}/{clients}/{servers} cpu={with_cpu}: building")
    t_start = time.perf_counter()
    cfg = parse_config(tor_example(
        n_relays_per_class=relays, n_clients=clients,
        n_servers=servers, filesize="64KiB", count=2, stoptime=stop_s,
        relay_cpu_ghz=3.0 if with_cpu else 0.0,
    ))
    runahead_ms = float(os.environ.get("BENCH_RUNAHEAD_MS", 0))
    # BENCH_FRONTIER > 0 selects the engine's frontier drain (the third
    # drain contract, docs/11-Performance.md "Model-tier batching"):
    # bit-identical results, per-event bookkeeping amortized per round
    frontier = int(os.environ.get("BENCH_FRONTIER", 0))
    sim = build_simulation(
        cfg, seed=1,
        # 32 sockets cover the worst role (a server carries ~23 conns:
        # clients/servers + listener); the socket tables are the
        # handler pass's dominant state traffic, so width is wall time
        n_sockets=int(os.environ.get("BENCH_TOR_NSOCK", 32)),
        capacity=768,
        runahead_ns=(
            int(runahead_ms * MILLISECOND) if runahead_ms > 0 else None
        ),
        frontier=frontier,
    )
    drain_b = int(os.environ.get("BENCH_DRAIN_B", 0))
    if drain_b:
        import dataclasses as _dc
        sim.engine.cfg = _dc.replace(sim.engine.cfg, drain_batch=drain_b)
    sim.strict_overflow = False
    build_s = time.perf_counter() - t_start
    _stamp("build done; compiling + warm-up run")
    jax.block_until_ready(sim.run(SECOND).now)
    compile_s = time.perf_counter() - t_start - build_s
    _stamp("compiled; timed run")
    t0 = time.perf_counter()
    st = sim.run(stop_s * SECOND)
    # the device fetches stay inside the timed region; sums are
    # padding-safe (bucket pad rows stay 0 — see btc_worker's
    # best_min note for the min-reduction trap)
    n_streams = int(jax.device_get(st.hosts.app.streams_done).sum())
    relayed = int(jax.device_get(st.hosts.app.relayed_bytes).sum())
    # scheduler self-profiling (scheduler.c:266-271 analog): the r04
    # verdict's ask — sweeps/windows/inner-steps make the per-sweep
    # fixed cost attributable instead of guessed at
    n_events = int(jax.device_get(st.stats.n_executed).sum())
    sweeps = int(jax.device_get(st.stats.n_sweeps))
    inner = int(jax.device_get(st.stats.n_inner_steps))
    windows = int(jax.device_get(st.stats.n_windows))
    wall = time.perf_counter() - t0
    _stamp(f"timed run done in {wall:.2f}s")
    pre = ("tor_" if with_cpu else "tor_nocpu_")
    if tier_idx == 3:
        pre = "tor10k_"
    print(json.dumps({
        f"{pre}hosts": len(sim.names),
        f"{pre}sim_s_per_wall_s": round(stop_s / max(wall, 1e-9), 3),
        f"{pre}streams_done": n_streams,
        f"{pre}relayed_mib": relayed >> 20,
        f"{pre}events": n_events,
        f"{pre}windows": windows,
        f"{pre}sweeps": sweeps,
        f"{pre}inner_steps": inner,
        f"{pre}events_per_sweep": round(n_events / max(sweeps, 1), 2),
        f"{pre}cpu_model": with_cpu,
        f"{pre}frontier": frontier,
        f"{pre}runahead_ms": runahead_ms,
        f"{pre}profile": {
            "build_s": round(build_s, 2),
            "compile_s": round(compile_s, 2),
            "run_s": round(wall, 2),
        },
    }))


def tor_analytics_worker():
    """Instrumented (NOT timed) tor run for the tor_rt analytics row:
    frontier drain with --stats histograms and the event trace on, so
    the stage can report p50/p95 frontier run length (the direct
    measurement of the PR 13 TPU bet) and the critical-path depth/width
    profile (the sequential ceiling no amount of vmap width can beat).
    Kept separate from the timed legs: stats/trace change the compiled
    program, and the timed headline must stay a clean price-of-
    bookkeeping measurement."""
    _chip_stage()
    import jax

    from shadow_tpu.config import parse_config
    from shadow_tpu.core.timebase import SECOND
    from shadow_tpu.examples import tor_example
    from shadow_tpu.obs.stats import stats_device_refs, summarize
    from shadow_tpu.obs.trace import TraceDrain
    from shadow_tpu.sim import build_simulation
    from shadow_tpu.tools.critical_path import analyze

    tier_idx = int(os.environ.get("BENCH_TOR_TIER", 0)) % len(TOR_TIERS)
    relays, clients, servers = TOR_TIERS[tier_idx]
    # a short horizon suffices: run-length and dependency-shape
    # statistics stabilize within a few steady-state seconds
    stop_s = int(os.environ.get("BENCH_ANALYTICS_STOP_S", 6))
    frontier = int(os.environ.get("BENCH_FRONTIER", 16))
    trace_n = int(os.environ.get("BENCH_TRACE", 4096))
    _stamp(f"tor analytics tier {relays}/{clients}/{servers} "
           f"frontier={frontier} trace={trace_n}: building")
    cfg = parse_config(tor_example(
        n_relays_per_class=relays, n_clients=clients,
        n_servers=servers, filesize="64KiB", count=2, stoptime=stop_s,
        relay_cpu_ghz=3.0,
    ))
    sim = build_simulation(
        cfg, seed=1,
        n_sockets=int(os.environ.get("BENCH_TOR_NSOCK", 32)),
        capacity=768, frontier=frontier, stats=1, trace=trace_n,
    )
    sim.strict_overflow = False
    td = TraceDrain(trace_n, names=sim.names,
                    kind_names=list(sim.kind_names))
    _stamp("build done; instrumented run, trace drained per sim-second")
    # drain the trace ring once per sim-second so it cannot overrun
    stop_ns = stop_s * SECOND
    st = sim.run(SECOND)
    st = td.drain_state(st)
    k = 2 * SECOND
    while k <= stop_ns:
        st = sim.run(k, state=st)
        st = td.drain_state(st)
        k += SECOND
    jax.block_until_ready(st.now)
    stats = summarize(jax.device_get(stats_device_refs(st.splane)))
    meta = {"names": sim.names, "kind_names": list(sim.kind_names)}
    report = analyze(td.records(), meta)
    _stamp(f"analytics done: {report['execs']} execs, "
           f"depth {report['depth']}")
    rl = stats["runlen"]
    print(json.dumps({
        "tora_hosts": len(sim.names),
        "tora_stop_s": stop_s,
        "tora_frontier": frontier,
        "tora_runlen_count": rl["count"],
        "tora_runlen_p50": rl["p50"],
        "tora_runlen_p95": rl["p95"],
        "tora_runlen_mean": round(rl["mean"], 2),
        "tora_wait_p50_ns": stats["wait"]["p50"],
        "tora_wait_p95_ns": stats["wait"]["p95"],
        "tora_critical_depth": report["depth"],
        "tora_width_mean": report["width_mean"],
        "tora_width_max": report["width_max"],
        "tora_execs": report["execs"],
        "tora_flows": report["flows"],
        "tora_trace_lost": td.lost,
    }))


def tor_churn_worker():
    """Secondary metric: the Tor workload under relay churn — a fifth of
    the relays crash and restart on a 20 s cycle (the dynamic-overlay
    scenario the reference cannot express; its packetloss is frozen at
    topology load). Reports surviving-stream throughput plus the fault
    attribution counters, so the churn run is checked for both liveness
    (streams still finish) and accounting (every drop attributed)."""
    _chip_stage()
    import jax

    from shadow_tpu.config import parse_config
    from shadow_tpu.core.timebase import SECOND
    from shadow_tpu.examples import tor_churn_example
    from shadow_tpu.sim import build_simulation

    relays, clients, servers = TOR_TIERS[0]
    stop_s = int(os.environ.get("BENCH_TOR_STOP_S", 30))
    _stamp(f"tor churn {relays}/{clients}/{servers}: building")
    cfg = parse_config(tor_churn_example(
        n_relays_per_class=relays, n_clients=clients, n_servers=servers,
        filesize="64KiB", count=2, stoptime=stop_s,
        churn_frac=0.3, churn_period=15.0, churn_downtime=4.0,
        churn_start=6.0,
    ))
    sim = build_simulation(cfg, seed=1, n_sockets=32, capacity=768)
    sim.strict_overflow = False
    _stamp("build done; compiling + warm-up run")
    jax.block_until_ready(sim.run(SECOND).now)
    _stamp("compiled; timed run")
    t0 = time.perf_counter()
    st = sim.run(stop_s * SECOND)
    n_streams = int(jax.device_get(st.hosts.app.streams_done).sum())
    n_events = int(jax.device_get(st.stats.n_executed).sum())
    fault_drops = int(jax.device_get(st.stats.n_fault_dropped).sum())
    quarantined = int(jax.device_get(st.stats.n_quarantined).sum())
    wall = time.perf_counter() - t0
    _stamp(f"timed churn run done in {wall:.2f}s")
    print(json.dumps({
        "torchurn_hosts": len(sim.names),
        "torchurn_sim_s_per_wall_s": round(stop_s / max(wall, 1e-9), 3),
        "torchurn_streams_done": n_streams,
        "torchurn_events": n_events,
        "torchurn_fault_drops": fault_drops,
        "torchurn_quarantined": quarantined,
    }))


def tgen_worker():
    """Secondary metric: the pure-TCP TGen transfer workload (BASELINE
    configs 1-2 shape scaled to BENCH_TGEN_PAIRS client/server pairs).
    No relay crypto, no CPU model: this isolates the transport + model
    tier the frontier drain batches, so the tgen_* chained-vs-frontier
    pair prices the drain contract itself rather than the tor relay
    pipeline on top of it. Same knobs as tor_worker: BENCH_FRONTIER
    selects the frontier drain, BENCH_RUNAHEAD_MS widens windows."""
    _chip_stage()
    import jax

    from shadow_tpu.config import parse_config
    from shadow_tpu.core.timebase import MILLISECOND, SECOND
    from shadow_tpu.examples import tgen_example
    from shadow_tpu.sim import build_simulation

    n_pairs = int(os.environ.get("BENCH_TGEN_PAIRS", 256))
    stop_s = int(os.environ.get("BENCH_TGEN_STOP_S", 10))
    runahead_ms = float(os.environ.get("BENCH_RUNAHEAD_MS", 0))
    frontier = int(os.environ.get("BENCH_FRONTIER", 0))
    _stamp(f"tgen {n_pairs} pairs: building")
    t_start = time.perf_counter()
    cfg = parse_config(tgen_example(
        n_pairs=n_pairs, sendsize="16KiB", recvsize="64KiB", count=4,
        stoptime=stop_s,
    ))
    sim = build_simulation(
        cfg, seed=1, n_sockets=8, capacity=768,
        runahead_ns=(
            int(runahead_ms * MILLISECOND) if runahead_ms > 0 else None
        ),
        frontier=frontier,
    )
    sim.strict_overflow = False
    build_s = time.perf_counter() - t_start
    _stamp("tgen build done; compiling + warm-up run")
    jax.block_until_ready(sim.run(SECOND).now)
    compile_s = time.perf_counter() - t_start - build_s
    _stamp("tgen compiled; timed run")
    t0 = time.perf_counter()
    st = sim.run(stop_s * SECOND)
    n_streams = int(jax.device_get(st.hosts.app.streams_done).sum())
    n_events = int(jax.device_get(st.stats.n_executed).sum())
    sweeps = int(jax.device_get(st.stats.n_sweeps))
    inner = int(jax.device_get(st.stats.n_inner_steps))
    windows = int(jax.device_get(st.stats.n_windows))
    wall = time.perf_counter() - t0
    _stamp(f"tgen timed run done in {wall:.2f}s")
    print(json.dumps({
        "tgen_hosts": len(sim.names),
        "tgen_sim_s_per_wall_s": round(stop_s / max(wall, 1e-9), 3),
        "tgen_streams_done": n_streams,
        "tgen_events": n_events,
        "tgen_windows": windows,
        "tgen_sweeps": sweeps,
        "tgen_inner_steps": inner,
        "tgen_events_per_sweep": round(n_events / max(sweeps, 1), 2),
        "tgen_frontier": frontier,
        "tgen_runahead_ms": runahead_ms,
        "tgen_profile": {
            "build_s": round(build_s, 2),
            "compile_s": round(compile_s, 2),
            "run_s": round(wall, 2),
        },
    }))


def btc_worker():
    """Secondary metric: Bitcoin gossip (BASELINE config 5 shape)."""
    _chip_stage()
    import jax

    from shadow_tpu.config import parse_config
    from shadow_tpu.core.timebase import SECOND
    from shadow_tpu.examples import bitcoin_example
    from shadow_tpu.sim import build_simulation

    cfg = parse_config(bitcoin_example(
        n_nodes=1000, blocks=2, blocksize="256KiB", interval=30,
    ))
    sim = build_simulation(cfg, seed=1, n_sockets=16, capacity=768)
    sim.strict_overflow = False
    stop_s = int(cfg.stoptime)
    _stamp("btc build done; compiling + warm-up run")
    jax.block_until_ready(sim.run(SECOND).now)
    _stamp("btc compiled; timed run")
    t0 = time.perf_counter()
    st = sim.run(stop_s * SECOND)
    # slice to the REAL hosts before reducing: shape bucketing pads
    # 1000 nodes to 1024 rows, and the inert pad rows hold best=0
    # forever — an unsliced min() reported "blocks_everywhere: 0" for
    # two rounds while every actual node held every block. Min-type
    # reductions are the only ones padding can poison (sums see 0s).
    best = jax.device_get(st.hosts.app.best)[: len(sim.names)]
    best_min = int(best.min())
    wall = time.perf_counter() - t0
    _stamp(f"btc timed run done in {wall:.2f}s")
    print(json.dumps({
        "btc_nodes": len(sim.names),
        "btc_sim_s_per_wall_s": round(stop_s / wall, 3),
        "btc_blocks_everywhere": best_min,
    }))


FAILED_STAGES: list[str] = []


def run_secondary(flag: str, nominal_timeout: int = 600) -> dict:
    """Run one stage in a child process, which holds the chip alone
    (the parent never touches JAX). The child reuses the persistent
    compilation cache. The timeout is the smaller of the nominal value
    and the remaining bench budget; with under a minute left the stage
    is skipped. A failed stage is named on stderr, counted in
    FAILED_STAGES, and returns {}."""
    import subprocess

    timeout = min(nominal_timeout, _remaining() - 30)
    if timeout < 60:
        print(f"bench: skipping {flag} (budget exhausted)", file=sys.stderr)
        return {}
    try:
        res = subprocess.run(
            [sys.executable, __file__, flag],
            capture_output=True, text=True, timeout=timeout,
        )
        if res.returncode == 0:
            for line in reversed(res.stdout.strip().splitlines()):
                try:
                    return json.loads(line)
                except json.JSONDecodeError:
                    continue
        err = f"rc={res.returncode}\n{res.stderr}"
    except subprocess.TimeoutExpired:
        err = f"timed out after {timeout:.0f}s"
    FAILED_STAGES.append(flag)
    print(f"bench worker {flag} failed: "
          + "\n".join(err.strip().splitlines()[-12:]), file=sys.stderr)
    return {}


def phold_worker():
    stop_s = int(os.environ.get("BENCH_STOP_S", STOP_SIM_SECONDS))
    r = tpu_rate(stop_s)
    print(json.dumps(r))


def phold_big_worker():
    """PHOLD at 16384 hosts (the BASELINE north star's >=10k-host scale
    on one chip): 4x the primary's host count at the same per-host
    message population. events/s rises with host count (more parallel
    lanes amortize the per-sweep sort); sim-s/wall-s falls because event
    density per sim-second scales with hosts. Both are reported."""
    stop_s = min(int(os.environ.get("BENCH_STOP_S", STOP_SIM_SECONDS)), 20)
    global N_HOSTS
    N_HOSTS = 16384
    r = tpu_rate(stop_s, capacity=64)
    print(json.dumps({f"phold16k_{k}": v for k, v in r.items()}))


def skew_worker():
    stop_s = min(int(os.environ.get("BENCH_STOP_S", STOP_SIM_SECONDS)), 10)
    # hot-spot variant: 1.5% of hosts receive 30% of traffic (the skewed
    # workload of reference test_phold.c:36-52 weighted targets); larger
    # queues absorb the hot hosts' backlog. Run BOTH overflow modes at
    # the same capacity: skew_* is the historical lossy-drop number
    # (skew_lossy flags any silent loss), skew_spill_* prices the
    # lossless spill path on the identical workload
    out = {}
    for mode, pre in (("drop", "skew_"), ("spill", "skew_spill_")):
        r = tpu_rate(stop_s, hot_hosts=64, hot_weight=0.3, capacity=256,
                     overflow=mode)
        out.update({f"{pre}{k}": v for k, v in r.items()})
        out[f"{pre}lossy"] = r["drops"] > 0
        print(json.dumps(out), flush=True)


# -- scenario fleets (docs/16-Scenario-Fleets.md) ---------------------
# The fleet bench is a CPU measurement by contract: what it prices is
# compile amortization + batched dispatch for seed sweeps, and both are
# program-structure effects, not silicon effects. The horizon is short
# on purpose — a sweep's scenarios are typically many and short, which
# is exactly the regime where N sequential compiles dominate the bill.
FLEET_LANES = 64
FLEET_HOSTS = 256
FLEET_STOP_S = 1


def fleet_rate(lanes: int, stop_s: int, *, n_hosts: int = FLEET_HOSTS):
    """One fleet-vs-sequential measurement, compile included on BOTH
    sides. The persistent compile cache is turned off first: every solo
    seed is its own XLA program (the root key is a
    baked constant), so a warm cache would hand the sequential side the
    exact amortization the fleet earns by construction and the ratio
    would be meaningless.

    Sequential = what a seed sweep costs today: per seed, a fresh
    `phold.build` + `jax.jit(eng.run)` + run. The fleet runs FIRST, so
    any one-time XLA/LLVM warm-up lands on the fleet's clock — the
    reported speedup is the conservative one. Every measured lane's
    final state is compared leaf-for-leaf against its solo run, so the
    bit-identity acceptance pin rides inside the measurement."""
    _cpu_stage(cache=False)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from shadow_tpu.core.timebase import SECOND, seconds
    from shadow_tpu.models import phold

    build_kw = dict(
        capacity=CAPACITY, latency_ns=seconds(LATENCY_S),
        mean_delay_ns=seconds(MEAN_DELAY_S), msgs_per_host=MSGS_PER_HOST,
        batched=True,
    )
    seeds = tuple(range(SEED, SEED + lanes))
    stop = jnp.int64(stop_s * SECOND)

    # fleet: ONE lowered program — build + compile + run on the clock
    t0 = time.perf_counter()
    fleet = phold.build_fleet(n_hosts, lanes, seeds=seeds, seed=SEED,
                              **build_kw)
    fst = fleet.run(stop)
    fleet_events = int(jax.device_get(fst.stats.n_executed).sum())
    fleet_wall = time.perf_counter() - t0
    flat_f = [np.asarray(x) for x in
              jax.tree_util.tree_leaves(jax.device_get(fst))]

    # sequential: the same seeds, one full build+jit+compile+run each.
    # fresh init states alias broadcasted buffers; per-leaf copies make
    # them donation-safe (same defence as perf_smoke)
    fresh = lambda init: jax.tree.map(
        lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x, init()
    )
    seq_walls: list[float] = []
    seq_events = 0
    identical = True
    for lane, s in enumerate(seeds):
        if _remaining() < 45:  # budget guard: extrapolate the tail
            break
        t0 = time.perf_counter()
        seng, sinit = phold.build(n_hosts, seed=s, **build_kw)
        run = jax.jit(seng.run, donate_argnums=0)
        sst = run(fresh(sinit), stop)
        seq_events += int(jax.device_get(sst.stats.n_executed).sum())
        seq_walls.append(time.perf_counter() - t0)
        flat_s = jax.tree_util.tree_leaves(jax.device_get(sst))
        identical = identical and all(
            bool((a[lane] == np.asarray(b)).all())
            for a, b in zip(flat_f, flat_s)
        )
    measured = len(seq_walls)
    seq_wall = sum(seq_walls)
    if 0 < measured < lanes:
        seq_wall = seq_wall / measured * lanes
    return {
        "fleet_lanes": lanes,
        "fleet_hosts": n_hosts,
        "fleet_stop_s": stop_s,
        "fleet_device": str(jax.devices()[0].device_kind),
        "fleet_wall_s": round(fleet_wall, 3),
        "fleet_events": fleet_events,
        "fleet_events_per_s": round(fleet_events / fleet_wall, 1),
        "fleet_scenarios_per_s": round(lanes / fleet_wall, 3),
        "fleet_windows": int(jax.device_get(fst.stats.n_windows).max()),
        "fleet_seq_measured": measured,
        "fleet_seq_extrapolated": measured < lanes,
        "fleet_seq_wall_s": round(seq_wall, 3),
        "fleet_seq_events": seq_events,
        "fleet_seq_scenarios_per_s": (
            round(lanes / seq_wall, 3) if seq_wall else 0.0),
        "fleet_speedup_x": (
            round(seq_wall / fleet_wall, 2) if fleet_wall else 0.0),
        "fleet_bit_identical": bool(identical and measured > 0),
    }


def fleet_worker():
    """`bench.py --fleet`: the 64-lane scenario-fleet headline — one
    vmapped program vs the same 64 seeds run sequentially, compile
    included on both sides (BENCH_r08.json acceptance: >= 5x). Override
    the shape with BENCH_FLEET_LANES / BENCH_FLEET_STOP_S."""
    lanes = int(os.environ.get("BENCH_FLEET_LANES", FLEET_LANES))
    stop_s = int(os.environ.get("BENCH_FLEET_STOP_S", FLEET_STOP_S))
    r = fleet_rate(lanes, stop_s)
    print(json.dumps(r), flush=True)
    if r["fleet_speedup_x"] < 5.0:
        print(f"fleet: x{r['fleet_speedup_x']:.2f} is below the 5x "
              "acceptance line (compile amortization should dominate "
              "at this horizon)", file=sys.stderr, flush=True)
    if not r["fleet_bit_identical"]:
        print("fleet: per-lane final states DIVERGED from the solo "
              "runs — the speedup is meaningless", file=sys.stderr)
        sys.exit(1)


def fleet_smoke_worker():
    """`bench.py --fleet-smoke` (measure_all.sh fleet_smoke stage): an
    8-lane PHOLD fleet vs the same 8 scenarios sequentially — the
    lane-equals-solo bit-identity gate (lane 0 included, every measured
    lane checked) plus the wall-clock ratio on stderr. Exit 1 when
    identity fails or the sequential side was budget-truncated."""
    r = fleet_rate(8, FLEET_STOP_S)
    ok = bool(r["fleet_bit_identical"]) and not r["fleet_seq_extrapolated"]
    r["fleet_smoke_ok"] = ok
    print(json.dumps(r), flush=True)
    print(f"fleet_smoke: {r['fleet_seq_wall_s']:.1f}s sequential vs "
          f"{r['fleet_wall_s']:.1f}s fleet -> x{r['fleet_speedup_x']:.2f}; "
          f"lane bit-identity "
          f"{'pass' if r['fleet_bit_identical'] else 'FAIL'}",
          file=sys.stderr, flush=True)
    if not ok:
        sys.exit(1)


def serve_smoke_worker():
    """`bench.py --serve-smoke` (measure_all.sh serve_smoke stage, the PR 16
    acceptance): the resident-service warm-cache headline, in-process.

    A SimService (max_lanes=4) takes two 8-request waves of the
    serve_client's deterministic mixed stream (two equivalence classes:
    a plain seed sweep and a crash-fault class with varied stops).
    Wave 1 is COLD — each class's first launch traces + compiles its
    fleet program; wave 2 is WARM — same classes, so every launch is a
    program-cache hit re-invoking the compiled fleet through
    `make_inputs`. The persistent compile cache is turned off first: a
    warm one would hand the cold side the exact
    amortization the program cache earns and the ratio would be
    meaningless. Acceptance: warm wave >= 5x faster than cold on CPU.

    Bit-identity rides inside the measurement: one request per class
    from the WARM wave (the cache-hit path, where a packing bug would
    hide) is checked against `solo_reference` — exact dict equality."""
    _cpu_stage(cache=False)

    from shadow_tpu.serve.service import SimService, solo_reference
    from shadow_tpu.tools.serve_client import request_docs

    docs = request_docs(16, mix="mixed", hosts=8, stop_s=0.5)
    svc = SimService(max_lanes=4, pack_deadline_ms=250,
                     beat_windows=16).start()

    def wave(wave_docs):
        t0 = time.perf_counter()
        rids = [svc.submit(d)["request_id"] for d in wave_docs]
        pending = set(rids)
        deadline = time.monotonic() + max(_remaining(), 60)
        while pending:
            if time.monotonic() > deadline:
                raise TimeoutError(f"{len(pending)} requests pending")
            for rid in list(pending):
                if svc.result(rid)["status"] in ("done", "error"):
                    pending.discard(rid)
            time.sleep(0.05)
        return time.perf_counter() - t0, {r: svc.result(r) for r in rids}

    try:
        # each 8-request wave fills BOTH classes (4 plain + 4 fault) at
        # max_lanes=4, so it dispatches as exactly two full launches
        cold_wall, cold = wave(docs[:8])
        warm_wall, warm = wave(docs[8:])
    finally:
        svc.drain()
    recs = {**cold, **warm}
    errors = [r for r in recs.values() if r["status"] != "done"]

    # bit-identity spot check: one warm request per class
    by_class = {}
    for d, (rid, r) in zip(docs[8:], sorted(warm.items())):
        by_class.setdefault(r["class"], (d, r))
    identical = all(r["summary"] == solo_reference(d)
                    for d, r in by_class.values())

    t = svc.metrics.totals()
    snap = svc.cache.snapshot()
    r = {
        "serve_requests": len(recs),
        "serve_errors": len(errors),
        "serve_classes": len({x["class"] for x in recs.values()}),
        "serve_max_lanes": 4,
        "serve_launches": int(t["shadow_tpu_serve_launches"]),
        "serve_packed_launches": int(
            t["shadow_tpu_serve_packed_launches"]),
        "serve_max_lanes_packed": max(
            (x["lanes_packed"] for x in recs.values()
             if x["status"] == "done"), default=0),
        "serve_cache_hits": snap["hits"],
        "serve_cache_misses": snap["misses"],
        "serve_cold_wall_s": round(cold_wall, 3),
        "serve_warm_wall_s": round(warm_wall, 3),
        "serve_warm_speedup_x": (round(cold_wall / warm_wall, 2)
                                 if warm_wall else 0.0),
        "serve_bit_identical": bool(identical),
    }
    ok = (not errors and identical
          and r["serve_warm_speedup_x"] >= 5.0
          and r["serve_packed_launches"] >= 1)
    r["serve_smoke_ok"] = ok
    print(json.dumps(r), flush=True)
    print(f"serve_smoke: cold {cold_wall:.1f}s vs warm {warm_wall:.1f}s "
          f"-> x{r['serve_warm_speedup_x']:.2f} "
          f"(acceptance 5x); bit-identity "
          f"{'pass' if identical else 'FAIL'}; "
          f"{r['serve_packed_launches']} packed launches",
          file=sys.stderr, flush=True)
    if not ok:
        sys.exit(1)


def serve_chaos_worker():
    """`bench.py --serve-chaos` (measure_all.sh serve_chaos stage,
    docs/17-Serving.md "Failure semantics"): failure-domain acceptance
    for the resident service, against a REAL serve subprocess.

    One `SHADOW_TPU_SERVE_CHAOS` spec drives the whole scenario:
    `raise:beat=2` (in-process retry resumes from the beat-1 snapshot),
    `kill:beat=4` (SIGKILL mid-batch; the harness relaunches serve and
    `resume_pending_batch` picks the batch up from the beat-3 snapshot
    under the ORIGINAL request ids — the restart MTTR number), and
    `poison:seed=905` (wave B: bisection isolates the poison request).
    The one-shot marker files live next to the snapshot, so the raise
    and kill injectors stay fired across the relaunch while the poison
    keeps firing — exactly what bisection needs.

    Acceptance: every non-poison request completes `done` with a
    summary that diffs EXACTLY (tools/diff_runs, drift count 0) against
    its `solo_reference`; wave-A records carry `resumed_from_beat` in
    (0, beats) — windows re-executed strictly fewer than completed; the
    poison request alone is `status:"error"`; the drained serve exits 0."""
    import re as _re
    import shutil
    import signal
    import subprocess
    import tempfile
    import urllib.error
    import urllib.request

    _cpu_stage()

    from shadow_tpu.serve.service import solo_reference
    from shadow_tpu.tools.diff_runs import diff_files
    from shadow_tpu.tools.serve_client import request_docs

    work = tempfile.mkdtemp(prefix="shadow_tpu_serve_chaos_")
    snap = os.path.join(work, "snap.npz")
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["SHADOW_TPU_SERVE_CHAOS"] = (
        "raise:beat=2;kill:beat=4;poison:seed=905")
    argv = [sys.executable, "-m", "shadow_tpu", "serve",
            "--port", "0", "--max-lanes", "4",
            "--pack-deadline-ms", "600000", "--beat-windows", "2",
            "--snapshot-beats", "1", "--snapshot-path", snap,
            "--launch-retries", "1",
            "--queue-file", os.path.join(work, "queue.json"),
            "--diag-dir", work]

    def _spawn(tag: str):
        """Start serve, tail its stderr for the listening line, return
        (proc, base_url, stderr_path)."""
        err_path = os.path.join(work, f"{tag}.err")
        err_f = open(err_path, "wb")
        proc = subprocess.Popen(argv, cwd=_REPO, env=env,
                                stdout=subprocess.DEVNULL, stderr=err_f)
        deadline = time.monotonic() + max(min(_remaining(), 300), 60)
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"serve ({tag}) died rc={proc.returncode} before "
                    f"listening; stderr: {open(err_path).read()[-2000:]}")
            m = _re.search(r"listening http://([\d.]+):(\d+)/",
                           open(err_path).read())
            if m:
                return proc, f"http://{m.group(1)}:{m.group(2)}", err_path
            time.sleep(0.1)
        raise TimeoutError(f"serve ({tag}) never printed a listening line")

    def _http(url, data=None):
        req = urllib.request.Request(
            url, data=data,
            headers={"Content-Type": "application/json"} if data else {})
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                return resp.status, resp.read().decode("utf-8")
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode("utf-8")

    def _submit(base, doc):
        code, body = _http(base + "/submit",
                           json.dumps(doc).encode("utf-8"))
        if code != 200:
            raise RuntimeError(f"/submit -> {code}: {body}")
        return json.loads(body)["request_id"]

    def _poll(proc, base, rids, *, allow_death=False):
        """Poll until every rid is terminal. Returns (records, died):
        records is None when the process died first (the SIGKILL leg)."""
        recs = {}
        deadline = time.monotonic() + max(min(_remaining(), 600), 120)
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                if allow_death:
                    return None, True
                raise RuntimeError(
                    f"serve died rc={proc.returncode} mid-wave")
            done = True
            for rid in rids:
                try:
                    code, body = _http(f"{base}/result/{rid}")
                except OSError:
                    # connection reset mid-request: serve is dying (the
                    # SIGKILL leg) or busy — the next iteration's
                    # proc.poll() decides which
                    done = False
                    break
                rec = json.loads(body)
                recs[rid] = rec
                if rec.get("status") not in ("done", "error", "timeout"):
                    done = False
            if done:
                return recs, False
            time.sleep(0.2)
        raise TimeoutError(f"wave never finished: "
                           f"{ {r: recs.get(r, {}).get('status') for r in rids} }")

    # one equivalence class throughout: 4-lane full packs dispatch
    # immediately despite the effectively-infinite pack deadline
    wave_a = request_docs(4, mix="plain", hosts=8, stop_s=0.5, seed0=901)
    wave_b = request_docs(3, mix="plain", hosts=8, stop_s=0.5, seed0=911)
    poison = request_docs(1, mix="plain", hosts=8, stop_s=0.5,
                          seed0=905)[0]

    def _diff_drift(rec, doc) -> int:
        a = os.path.join(work, f"rec_{rec['request_id']}.json")
        b = os.path.join(work, f"solo_{doc['seed']}.json")
        with open(a, "w") as f:
            json.dump(rec, f)
        with open(b, "w") as f:
            json.dump(solo_reference(doc), f)
        return len(diff_files(a, b, rtol=0.1))

    out: dict = {}
    proc2 = None
    try:
        # -- wave A: raise at beat 2 (in-process retry), then SIGKILL
        #    at beat 4 mid-batch, relaunch, resume, complete ----------
        _stamp("serve_chaos: wave A (raise -> SIGKILL -> resume)")
        proc1, base1, _ = _spawn("serve1")
        rids_a = [_submit(base1, d) for d in wave_a]
        recs, died = _poll(proc1, base1, rids_a, allow_death=True)
        t_death = time.monotonic()
        proc1.wait()
        out["serve_chaos_killed_rc"] = proc1.returncode
        if not died:
            raise RuntimeError("kill:beat=4 never fired — wave A "
                               "finished on the first serve instance")

        proc2, base2, _ = _spawn("serve2")
        out["serve_chaos_restart_mttr_s"] = round(
            time.monotonic() - t_death, 3)
        recs, _ = _poll(proc2, base2, rids_a)
        out["serve_chaos_recovery_wall_s"] = round(
            time.monotonic() - t_death, 3)

        resumed = [r.get("resumed_from_beat") for r in recs.values()]
        drift_a = sum(_diff_drift(recs[rid], d)
                      for rid, d in zip(rids_a, wave_a)
                      if recs[rid]["status"] == "done")
        out.update({
            "serve_chaos_wave_a_done": sum(
                1 for r in recs.values() if r["status"] == "done"),
            "serve_chaos_resumed_from_beat": resumed[0],
            "serve_chaos_drift_a": drift_a,
        })
        wave_a_ok = (
            out["serve_chaos_wave_a_done"] == 4 and drift_a == 0
            and all(isinstance(b, int) and 0 < b < r["beats"]
                    for b, r in zip(resumed, recs.values())))

        # -- wave B: poison request -> bisection isolates it ----------
        _stamp("serve_chaos: wave B (poison -> bisection)")
        rids_b = [_submit(base2, d) for d in wave_b]
        rid_p = _submit(base2, poison)
        recs_b, _ = _poll(proc2, base2, rids_b + [rid_p])
        drift_b = sum(_diff_drift(recs_b[rid], d)
                      for rid, d in zip(rids_b, wave_b)
                      if recs_b[rid]["status"] == "done")
        poison_rec = recs_b[rid_p]
        out.update({
            "serve_chaos_wave_b_done": sum(
                1 for r in rids_b if recs_b[r]["status"] == "done"),
            "serve_chaos_poison_isolated": bool(
                poison_rec["status"] == "error"
                and "poison seed 905" in poison_rec.get("error", "")),
            "serve_chaos_drift_b": drift_b,
        })

        # counters from the live scrape: the injectors, the retry, the
        # resume, and the two bisection levels all actually happened
        _, metrics = _http(base2 + "/metrics")

        def _counter(name):
            m = _re.search(rf"^{name}_total ([\d.e+]+)$", metrics,
                           _re.MULTILINE)
            return int(float(m.group(1))) if m else -1

        out.update({
            "serve_chaos_bisections": _counter(
                "shadow_tpu_serve_bisections"),
            "serve_chaos_resumes": _counter("shadow_tpu_serve_resumes"),
            "serve_chaos_launch_retries": _counter(
                "shadow_tpu_serve_launch_retries"),
        })

        proc2.send_signal(signal.SIGTERM)
        out["serve_chaos_drain_rc"] = proc2.wait(timeout=60)
        proc2 = None

        ok = bool(
            wave_a_ok
            and out["serve_chaos_wave_b_done"] == 3 and drift_b == 0
            and out["serve_chaos_poison_isolated"]
            and out["serve_chaos_bisections"] >= 2
            and out["serve_chaos_resumes"] >= 1
            and out["serve_chaos_launch_retries"] >= 1
            and out["serve_chaos_drain_rc"] == 0)
        out["serve_chaos_ok"] = ok
        print(json.dumps(out), flush=True)
        print(f"serve_chaos: restart MTTR "
              f"{out['serve_chaos_restart_mttr_s']}s, resumed from beat "
              f"{out['serve_chaos_resumed_from_beat']}, "
              f"{out['serve_chaos_bisections']} bisections, drift "
              f"{drift_a}+{drift_b} -> {'ok' if ok else 'FAIL'}",
              file=sys.stderr, flush=True)
        if not ok:
            sys.exit(1)
        shutil.rmtree(work, ignore_errors=True)
    finally:
        if proc2 is not None and proc2.poll() is None:
            proc2.kill()
        if os.path.isdir(work):  # kept on failure, for the stderr tails
            print(f"serve_chaos: artifacts kept at {work}",
                  file=sys.stderr, flush=True)


def serve_elastic_worker():
    """`bench.py --serve-elastic` (measure_all.sh serve_elastic stage,
    BENCH_r11.json, docs/17-Serving.md "Elasticity"): live lane-batch
    migration acceptance against a REAL `shadow_tpu serve --retry 2`
    subprocess — the full cross-process story, wrapper included.

    Wave 1 packs 8 requests at --max-lanes 8; `devloss:beat=2` makes
    the child exit EXIT_PEER_LOST=77 with the beat-1 snapshot on disk.
    The --retry wrapper halves --max-lanes to 4 (next_retry_argv) and
    relaunches; `resume_pending_batch` migrates the 8-lane snapshot
    into two 4-lane parts and finishes the batch under the ORIGINAL
    request ids — the migration-MTTR numbers. Wave 2 runs 4 longer
    requests at the shrunken width; `resize:beat=7,lanes=8` grows the
    mesh back IN PROCESS mid-batch. Acceptance: every request of both
    waves completes `done` with a summary that diffs EXACTLY
    (tools/diff_runs, drift 0) against its solo_reference; wave-1
    records carry resumed_from_beat in (0, beats); /healthz reports
    degraded_capacity at max_lanes 4 after the shrink and full width 8
    (no degraded flag) after the grow; /metrics carries
    serve_migrations_total >= 2 plus the serve_mesh_generation gauge;
    and a SIGTERM aimed at the WRAPPER is forwarded to the child,
    which drains to exit 0 and yields the wrapper's retry report
    (attempts 2, recoveries 1, one mttr_s sample)."""
    import re as _re
    import shutil
    import signal
    import subprocess
    import tempfile
    import urllib.error
    import urllib.request

    _cpu_stage()

    from shadow_tpu.serve.service import solo_reference
    from shadow_tpu.tools.diff_runs import diff_files
    from shadow_tpu.tools.serve_client import request_docs

    work = tempfile.mkdtemp(prefix="shadow_tpu_serve_elastic_")
    snap = os.path.join(work, "snap.npz")
    err_path = os.path.join(work, "elastic.err")
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["SHADOW_TPU_SERVE_CHAOS"] = (
        "devloss:beat=2;resize:beat=7,lanes=8")
    # --max-lanes must be spelled out for next_retry_argv to halve it
    argv = [sys.executable, "-m", "shadow_tpu", "serve", "--retry", "2",
            "--port", "0", "--max-lanes", "8",
            "--pack-deadline-ms", "600000", "--beat-windows", "2",
            "--snapshot-beats", "1", "--snapshot-path", snap,
            "--launch-retries", "1",
            "--queue-file", os.path.join(work, "queue.json"),
            "--diag-dir", work]

    def _bases():
        """Every base URL the (re)launched children have announced, in
        order — with --port 0 each relaunch binds a fresh port, so the
        LAST listening line is the live instance."""
        try:
            text = open(err_path).read()
        except OSError:
            return []
        return [f"http://{h}:{p}" for h, p in
                _re.findall(r"listening http://([\d.]+):(\d+)/", text)]

    def _wait(proc, pred, what, budget=300):
        deadline = time.monotonic() + max(min(_remaining(), budget), 60)
        while time.monotonic() < deadline:
            got = pred()
            if got:
                return got
            if proc.poll() is not None:
                raise RuntimeError(
                    f"serve wrapper died rc={proc.returncode} before "
                    f"{what}; stderr: {open(err_path).read()[-2000:]}")
            time.sleep(0.1)
        raise TimeoutError(f"serve_elastic: {what} never happened; "
                           f"stderr: {open(err_path).read()[-2000:]}")

    def _http(url, data=None):
        req = urllib.request.Request(
            url, data=data,
            headers={"Content-Type": "application/json"} if data else {})
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                return resp.status, resp.read().decode("utf-8")
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode("utf-8")

    def _submit(base, doc):
        code, body = _http(base + "/submit",
                           json.dumps(doc).encode("utf-8"))
        if code != 200:
            raise RuntimeError(f"/submit -> {code}: {body}")
        return json.loads(body)["request_id"]

    def _poll(proc, base, rids):
        recs = {}
        deadline = time.monotonic() + max(min(_remaining(), 600), 120)
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"serve wrapper died rc={proc.returncode} mid-wave;"
                    f" stderr: {open(err_path).read()[-2000:]}")
            done = True
            for rid in rids:
                try:
                    code, body = _http(f"{base}/result/{rid}")
                except OSError:
                    done = False  # restart window: refused / reset
                    break
                rec = json.loads(body)
                recs[rid] = rec
                if rec.get("status") not in ("done", "error", "timeout"):
                    done = False
            if done:
                return recs
            time.sleep(0.2)
        raise TimeoutError(f"wave never finished: "
                           f"{ {r: recs.get(r, {}).get('status') for r in rids} }")

    def _diff_drift(rec, doc) -> int:
        a = os.path.join(work, f"rec_{rec['request_id']}.json")
        b = os.path.join(work, f"solo_{doc['seed']}.json")
        with open(a, "w") as f:
            json.dump(rec, f)
        with open(b, "w") as f:
            json.dump(solo_reference(doc), f)
        return len(diff_files(a, b, rtol=0.1))

    # one equivalence class per wave: full packs dispatch immediately
    # despite the effectively-infinite pack deadline. Wave 2 runs 9
    # beats (stop 0.9s at 2x50ms windows/beat) so resize:beat=7 fires
    # mid-batch; wave 1's 5-beat requests never reach it.
    wave_1 = request_docs(8, mix="plain", hosts=8, stop_s=0.5, seed0=921)
    wave_2 = request_docs(4, mix="plain", hosts=8, stop_s=0.9, seed0=941)

    out: dict = {}
    proc = None
    try:
        # -- wave 1: 8-lane pack -> devloss@2 -> exit 77 -> wrapper
        #    relaunch at 4 lanes -> split-migrate -> complete ----------
        _stamp("serve_elastic: wave 1 (devloss -> shrink migration)")
        err_f = open(err_path, "wb")
        proc = subprocess.Popen(argv, cwd=_REPO, env=env,
                                stdout=subprocess.DEVNULL, stderr=err_f)
        base1 = _wait(proc, lambda: (_bases() or [None])[0],
                      "first listening line")
        rids_1 = [_submit(base1, d) for d in wave_1]
        _wait(proc, lambda: "exited 77" in open(err_path).read(),
              "devloss exit 77")
        t_death = time.monotonic()
        base2 = _wait(proc, lambda: (_bases()[1:2] or [None])[0],
                      "relaunch listening line")
        out["serve_elastic_relaunch_mttr_s"] = round(
            time.monotonic() - t_death, 3)
        recs = _poll(proc, base2, rids_1)
        out["serve_elastic_migration_mttr_s"] = round(
            time.monotonic() - t_death, 3)

        resumed = [r.get("resumed_from_beat") for r in recs.values()]
        drift_1 = sum(_diff_drift(recs[rid], d)
                      for rid, d in zip(rids_1, wave_1)
                      if recs[rid]["status"] == "done")
        _, hz = _http(base2 + "/healthz")
        hz = json.loads(hz)
        out.update({
            "serve_elastic_wave_1_done": sum(
                1 for r in recs.values() if r["status"] == "done"),
            "serve_elastic_resumed_from_beat": resumed[0],
            "serve_elastic_drift_1": drift_1,
            "serve_elastic_shrunk_lanes": hz.get("max_lanes"),
            "serve_elastic_degraded": bool(hz.get("degraded_capacity")),
        })
        wave_1_ok = (
            out["serve_elastic_wave_1_done"] == 8 and drift_1 == 0
            and all(isinstance(b, int) and 0 < b < r["beats"]
                    for b, r in zip(resumed, recs.values()))
            and hz.get("max_lanes") == 4
            and hz.get("degraded_capacity") is True
            and hz.get("mesh_generation", 0) >= 1)

        # -- wave 2: resize@7 grows the mesh back in process ----------
        _stamp("serve_elastic: wave 2 (in-process resize grow)")
        rids_2 = [_submit(base2, d) for d in wave_2]
        recs_2 = _poll(proc, base2, rids_2)
        drift_2 = sum(_diff_drift(recs_2[rid], d)
                      for rid, d in zip(rids_2, wave_2)
                      if recs_2[rid]["status"] == "done")
        _, hz2 = _http(base2 + "/healthz")
        hz2 = json.loads(hz2)
        out.update({
            "serve_elastic_wave_2_done": sum(
                1 for r in recs_2.values() if r["status"] == "done"),
            "serve_elastic_drift_2": drift_2,
            "serve_elastic_grown_lanes": hz2.get("max_lanes"),
        })
        wave_2_ok = (
            out["serve_elastic_wave_2_done"] == 4 and drift_2 == 0
            and hz2.get("max_lanes") == 8
            and not hz2.get("degraded_capacity"))

        # counters from the live scrape: both migrations (the shrink
        # split and the in-process grow) actually happened
        _, metrics = _http(base2 + "/metrics")

        def _counter(name):
            m = _re.search(rf"^{name}_total ([\d.e+]+)$", metrics,
                           _re.MULTILINE)
            return int(float(m.group(1))) if m else -1

        def _gauge(name):
            m = _re.search(rf"^{name} ([\d.e+]+)$", metrics,
                           _re.MULTILINE)
            return int(float(m.group(1))) if m else -1

        out.update({
            "serve_elastic_migrations": _counter(
                "shadow_tpu_serve_migrations"),
            "serve_elastic_resumes": _counter("shadow_tpu_serve_resumes"),
            "serve_elastic_mesh_generation": _gauge(
                "shadow_tpu_serve_mesh_generation"),
        })

        # SIGTERM the WRAPPER: run_with_retry forwards to the child's
        # process group, the child drains, the wrapper reports
        proc.send_signal(signal.SIGTERM)
        out["serve_elastic_drain_rc"] = proc.wait(timeout=60)
        proc = None
        m = _re.search(r"shadow_tpu: retry report (\{.*\})",
                       open(err_path).read())
        report = json.loads(m.group(1)) if m else {}
        out["serve_elastic_retry_report"] = report

        ok = bool(
            wave_1_ok and wave_2_ok
            and out["serve_elastic_migrations"] >= 2
            and out["serve_elastic_resumes"] >= 1
            and out["serve_elastic_mesh_generation"] >= 1
            and out["serve_elastic_drain_rc"] == 0
            and report.get("attempts") == 2
            and report.get("recoveries") == 1
            and report.get("exit_history", [None])[0] == 77
            and len(report.get("mttr_s", [])) == 1)
        out["serve_elastic_ok"] = ok
        print(json.dumps(out), flush=True)
        print(f"serve_elastic: relaunch MTTR "
              f"{out['serve_elastic_relaunch_mttr_s']}s, migration wall "
              f"{out['serve_elastic_migration_mttr_s']}s, resumed from "
              f"beat {out['serve_elastic_resumed_from_beat']}, "
              f"{out['serve_elastic_migrations']} migrations, drift "
              f"{drift_1}+{drift_2} -> {'ok' if ok else 'FAIL'}",
              file=sys.stderr, flush=True)
        if not ok:
            sys.exit(1)
        shutil.rmtree(work, ignore_errors=True)
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
        if os.path.isdir(work):  # kept on failure, for the stderr tail
            print(f"serve_elastic: artifacts kept at {work}",
                  file=sys.stderr, flush=True)


def multichip_worker():
    """Weak-scaling PHOLD over an 8-device mesh — MULTICHIP_r*.json
    carries data now, not just a smoke bit.

    Three stages, each printed as a JSON superset the moment it lands
    (same contract as the other workers):

      1. bit-identity: a small sharded PHOLD (8 shards) vs the
         single-device engine at the same total host count — the
         determinism contract, recorded as pass/fail;
      2. mid tier: 16k hosts/device x 8 = 131072 hosts;
      3. the 1M-host tier: 128k hosts/device x 8 = 1048576 hosts
         (ROADMAP "millions of users" north star shape), budget
         permitting.

    The final superset is also written to the next MULTICHIP_r*.json.
    On CPU the 8 devices are forced (virtual); events/s then measures
    the sharded program's single-core throughput — the weak-scaling
    *shape* (per-shard host count, collective structure) is identical
    to the real-chip run."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    _cpu_stage()
    import jax
    import jax.numpy as jnp

    from shadow_tpu.core.timebase import SECOND, seconds
    from shadow_tpu.models import phold
    from shadow_tpu.obs import WindowProfiler
    from shadow_tpu.parallel import mesh as pmesh

    n_dev = 8
    out = {
        "mc_devices": n_dev,
        "mc_device": str(jax.devices()[0].device_kind),
        # the path actually executed (tests pin that no jax.pmap runs
        # unless this says "pmap")
        "mc_spmd_path": pmesh.select_spmd("auto"),
    }
    prof = WindowProfiler()

    def sharded(per, **kw):
        eng, init = phold.build(
            per, axis_name=pmesh.HOSTS_AXIS, n_shards=n_dev, **kw)
        m = pmesh.make_mesh(n_dev)
        return pmesh.build_sharded(eng, init, m, per)

    # -- 1. bit-identity, small shape --------------------------------
    with prof.phase("identity"):
        kw = dict(seed=SEED, capacity=32, msgs_per_host=4)
        eng1, init1 = phold.build(64, **kw)
        st1 = jax.jit(eng1.run)(init1(), jnp.int64(SECOND))
        initN, runN, _ = sharded(8, **kw)
        stN = runN(initN(), jnp.int64(SECOND))
        out["mc_bit_identical"] = bool(
            st1.hosts.n_received.tolist() == stN.hosts.n_received.tolist()
            and st1.src_seq.tolist() == stN.src_seq.tolist()
            and (st1.queues.time.sort(axis=1)
                 == stN.queues.time.sort(axis=1)).all()
        )
    print(json.dumps(out), flush=True)

    # -- 2./3. weak scaling ------------------------------------------
    def tier(tag, per, stop_ns, msgs):
        with prof.phase(f"{tag}_build"):
            initN, runN, _ = sharded(
                per, seed=SEED, capacity=16, msgs_per_host=msgs,
                latency_ns=seconds(LATENCY_S),
                mean_delay_ns=seconds(MEAN_DELAY_S))
            st = initN()
            # warm the compile on a short horizon
            jax.block_until_ready(runN(st, jnp.int64(stop_ns // 8)))
        st = initN()
        t0 = time.perf_counter()
        with prof.phase(f"{tag}_step"):
            st = runN(st, jnp.int64(stop_ns))
            executed = int(jax.device_get(st.stats.n_executed).sum())
        wall = time.perf_counter() - t0
        out.update({
            f"{tag}_hosts_per_shard": per,
            f"{tag}_n_hosts": per * n_dev,
            f"{tag}_events": executed,
            f"{tag}_wall_s": round(wall, 3),
            f"{tag}_events_per_s": round(executed / wall, 1),
            f"{tag}_windows": int(st.stats.n_windows),
            f"{tag}_cross_shard_events": int(
                jax.device_get(st.stats.n_cross_shard).sum()),
        })
        out["mc_profile"] = {
            name: round(p["total_s"], 3)
            for name, p in prof.summary()["phases"].items()
        }
        print(json.dumps(out), flush=True)

    stop_ns = int(float(os.environ.get("MULTICHIP_STOP_S", "0.5")) * SECOND)
    tier("mc_mid", 16384, stop_ns, 2)
    if _remaining() > 120:
        tier("mc_1m", 131072, stop_ns, 1)
    else:
        print("bench: skipping 1M tier (budget exhausted)", file=sys.stderr)

    # land the superset in the next MULTICHIP_r*.json
    import glob
    import re as _re

    nums = [int(m.group(1)) for p in
            glob.glob(os.path.join(_REPO, "MULTICHIP_r*.json"))
            if (m := _re.search(r"MULTICHIP_r(\d+)\.json$", p))]
    path = os.path.join(
        _REPO, f"MULTICHIP_r{max(nums, default=0) + 1:02d}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    _stamp(f"multichip results -> {path}")


# 16-host PHOLD through a single 50ms self-edge: small enough that every
# chaos attempt (compile included, warm cache) fits the smoke budget,
# busy enough that every window carries cross-shard traffic on an
# 8-shard mesh — the shape the reshard-on-resume path must survive.
CHAOS_CFG = """<shadow stoptime="10">
  <topology>
    <![CDATA[<graphml xmlns="http://graphml.graphdrawing.org/xmlns">
      <key attr.name="latency" attr.type="double" for="edge" id="d3" />
      <key attr.name="bandwidthup" attr.type="int" for="node" id="d2" />
      <key attr.name="bandwidthdown" attr.type="int" for="node" id="d1" />
      <graph edgedefault="undirected">
        <node id="poi-1">
          <data key="d1">2048</data>
          <data key="d2">2048</data>
        </node>
        <edge source="poi-1" target="poi-1">
          <data key="d3">50.0</data>
        </edge>
      </graph>
    </graphml>]]>
  </topology>
  <plugin id="phold" path="shadow-plugin-test-phold.so" />
  <host id="peer" quantity="16">
    <process plugin="phold" starttime="1" arguments="basename=peer quantity=16 load=4" />
  </host>
</shadow>
"""

# the summary keys that must be bit-identical across a recovery; wall
# times and cross_shard_packets (mesh-dependent telemetry) are excluded
CHAOS_CMP_KEYS = ("events", "windows", "net_dropped", "queue_drops",
                  "fault_dropped", "quarantined_events", "sweeps",
                  "rx_bytes", "tx_bytes", "events_by_kind")


def chaos_worker():
    """Chaos acceptance for the elastic-recovery subsystem
    (measure_all.sh chaos_smoke stage, docs/13-Elastic-Recovery.md).

    Two scenarios on a forced 8-device CPU mesh, both wrapped in
    `runtime.supervisor.run_with_retry` and both asserted bit-identical
    to an unsharded baseline of the same config:

      1. preemption — SIGKILL the worker right after its first
         checkpoint lands; the relaunch resumes on the same mesh;
      2. peer loss — SHADOW_TPU_CHAOS_HANG_S wedges a harvest fetch
         past --collective-timeout, the collective watchdog exits 77
         with a per-shard bundle, and the relaunch resumes on a HALVED
         mesh (8 -> 4) from the same checkpoint.

    Reports mc_chaos_* (recoveries, MTTR, exit history, bit-identity)
    and merges them into the newest MULTICHIP_r*.json so the multichip
    record carries the recovery numbers next to the scaling numbers."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    _cpu_stage()
    import glob
    import re as _re
    import shutil
    import signal
    import subprocess
    import tempfile
    import threading

    from shadow_tpu.runtime.supervisor import EXIT_PEER_LOST, run_with_retry

    work = tempfile.mkdtemp(prefix="shadow_tpu_chaos_")
    cfg = os.path.join(work, "cfg.xml")
    with open(cfg, "w") as f:
        f.write(CHAOS_CFG)
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    base_argv = [sys.executable, "-m", "shadow_tpu", "--overflow", "drop",
                 "--seed", "1", cfg]

    def _last_json(path: str) -> dict:
        try:
            with open(path) as f:
                lines = f.read().strip().splitlines()
        except OSError:
            return {}
        for line in reversed(lines):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
        return {}

    def _sig(summary: dict) -> dict:
        return {k: summary.get(k) for k in CHAOS_CMP_KEYS}

    def _retry_run(tag: str, extra_argv: list, *, hang_s: float = 0.0,
                   on_spawn=None) -> tuple[dict, dict]:
        """One run_with_retry supervision with child stdout routed to a
        file (the worker's own stdout carries only the JSON protocol);
        returns (report, final summary signature)."""
        stdout_path = os.path.join(work, f"{tag}.out")
        env2 = dict(env)
        if hang_s > 0:
            env2["SHADOW_TPU_CHAOS_HANG_S"] = str(hang_s)
        with open(stdout_path, "ab") as out_f:
            report = run_with_retry(
                base_argv + extra_argv, retries=2, backoff_s=0.2,
                on_spawn=on_spawn,
                _popen=lambda a, **kw: subprocess.Popen(
                    a, cwd=_REPO, env=env2, stdout=out_f, **kw),
            )
        return report, _sig(_last_json(stdout_path))

    out: dict = {}
    try:
        _stamp("chaos: baseline unsharded run")
        base_out = os.path.join(work, "base.out")
        with open(base_out, "wb") as f:
            base_rc = subprocess.run(
                base_argv, cwd=_REPO, env=env, stdout=f).returncode
        baseline = _sig(_last_json(base_out))
        out["mc_chaos_baseline_rc"] = base_rc

        # -- 1. preemption: SIGKILL after the first checkpoint ---------
        _stamp("chaos: SIGKILL-after-checkpoint run")
        ck_a = os.path.join(work, "ck_a.npz")
        victim: list = []

        def _kill_after_ckpt():
            while not victim:
                time.sleep(0.05)
            p = victim[0]
            while p.poll() is None and not os.path.exists(ck_a):
                time.sleep(0.1)
            if p.poll() is None:
                time.sleep(0.3)  # into the next window, mid-flight
                try:
                    os.kill(p.pid, signal.SIGKILL)
                except OSError:
                    pass

        threading.Thread(target=_kill_after_ckpt, daemon=True).start()
        rep_a, sig_a = _retry_run(
            "kill", ["--mesh", "8", "--checkpoint-interval", "4",
                     "--checkpoint-path", ck_a, "--diag-dir", work],
            on_spawn=lambda p: victim.append(p) if not victim else None,
        )
        out.update({
            "mc_chaos_ok": bool(
                base_rc == 0 and rep_a["exit_code"] == 0
                and rep_a["recoveries"] >= 1 and sig_a == baseline),
            "mc_chaos_recoveries": rep_a["recoveries"],
            "mc_chaos_mttr_s": (rep_a["mttr_s"] or [None])[0],
            "mc_chaos_exit_history": rep_a["exit_history"],
        })
        print(json.dumps(out), flush=True)

        # -- 2. peer loss: wedged collective -> 77 -> shrunken mesh ----
        if _remaining() > 120:
            _stamp("chaos: collective-stall (exit 77) run")
            ck_b = os.path.join(work, "ck_b.npz")
            rep_b, sig_b = _retry_run(
                "peerlost",
                ["--mesh", "8", "--collective-timeout", "5",
                 "--checkpoint-interval", "4",
                 "--checkpoint-path", ck_b, "--diag-dir", work],
                hang_s=60.0,
            )
            bundles = glob.glob(os.path.join(work, "*.peerlost.*.json"))
            out.update({
                "mc_chaos_peerlost_ok": bool(
                    rep_b["exit_code"] == 0
                    and EXIT_PEER_LOST in rep_b["exit_history"]
                    and bundles and sig_b == baseline),
                "mc_chaos_peerlost_mttr_s": (rep_b["mttr_s"] or [None])[0],
                "mc_chaos_peerlost_exit_history": rep_b["exit_history"],
                "mc_chaos_peerlost_bundles": len(bundles),
            })
            print(json.dumps(out), flush=True)
        else:
            print("bench: skipping peer-loss scenario (budget exhausted)",
                  file=sys.stderr)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    # merge into the newest MULTICHIP_r*.json (create one if the
    # multichip stage hasn't run on this machine yet): the recovery
    # numbers belong next to the scaling numbers they qualify
    paths = [(int(m.group(1)), p) for p in
             glob.glob(os.path.join(_REPO, "MULTICHIP_r*.json"))
             if (m := _re.search(r"MULTICHIP_r(\d+)\.json$", p))]
    if paths:
        _, path = max(paths)
        try:
            with open(path) as f:
                merged = json.load(f)
        except (OSError, json.JSONDecodeError):
            merged = {}
        merged.update(out)
    else:
        path = os.path.join(_REPO, "MULTICHIP_r01.json")
        merged = out
    with open(path, "w") as f:
        json.dump(merged, f, indent=2)
        f.write("\n")
    _stamp(f"chaos results -> {path}")


def metrics_smoke_worker():
    """Live-telemetry acceptance (measure_all.sh metrics_smoke stage,
    docs/14-Telemetry.md): one slow supervised run with `--metrics-port
    0`, scraped while it runs and again after its summary prints.

    Gates, each recorded in the JSON superset and fatal on failure:

      1. exporter determinism — two mid-run scrapes with no heartbeat
         between them are byte-identical;
      2. OpenMetrics syntax — every scrape passes
         `obs.metrics.validate_openmetrics` (the same checker behind
         tools/check_openmetrics.py);
      3. /healthz answers 200 with status "ok" on a clean run;
      4. reconciliation — the final scrape's counter samples equal the
         end-of-run summary JSON exactly (events, drops, bytes, ...).

    SHADOW_TPU_METRICS_LINGER_S keeps the endpoint alive after the
    summary lands so gate 4 scrapes the *finalized* registry."""
    import re as _re
    import subprocess
    import urllib.request

    _cpu_stage()
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["SHADOW_TPU_METRICS_LINGER_S"] = "20"

    from shadow_tpu.obs.metrics import validate_openmetrics

    argv = [sys.executable, "-m", "shadow_tpu", "--test",
            "--stoptime", "30", "--heartbeat-frequency", "2",
            "--seed", "1", "--metrics-port", "0"]
    out: dict = {}
    proc = subprocess.Popen(argv, cwd=_REPO, env=env,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)

    def _fail(msg: str):
        proc.kill()
        out["metrics_smoke_ok"] = False
        out["metrics_smoke_error"] = msg
        print(json.dumps(out), flush=True)
        print(f"metrics_smoke: {msg}", file=sys.stderr)
        sys.exit(1)

    # the serving line appears on stderr once jax import + build finish
    port = None
    stderr_lines: list[str] = []
    deadline = time.monotonic() + min(300.0, max(_remaining() - 60, 60.0))
    while time.monotonic() < deadline:
        line = proc.stderr.readline()
        if not line:
            if proc.poll() is not None:
                break
            continue
        stderr_lines.append(line)
        m = _re.search(r"metrics: serving http://[\d.]+:(\d+)/metrics", line)
        if m:
            port = int(m.group(1))
            break
    if port is None:
        _fail("server line never appeared: "
              + "".join(stderr_lines[-5:]).strip())
    out["metrics_smoke_port"] = port
    _stamp(f"metrics_smoke: scraping port {port}")

    def _get(path: str) -> tuple[int, str]:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10) as r:
            return r.status, r.read().decode()

    def _samples(text: str) -> dict[str, float]:
        vals = {}
        for ln in text.splitlines():
            if not ln or ln.startswith("#"):
                continue
            name_lbl, _, v = ln.rpartition(" ")
            vals[name_lbl] = float(v)
        return vals

    # 1./2. determinism + syntax, mid-run: a heartbeat may land between
    # two scrapes (that is real state change, not nondeterminism), so
    # hunt for one byte-identical consecutive pair
    identical = False
    for _ in range(5):
        a, b = _get("/metrics")[1], _get("/metrics")[1]
        if a == b:
            identical = True
            break
    out["metrics_smoke_deterministic"] = identical
    problems = validate_openmetrics(b)
    out["metrics_smoke_openmetrics_violations"] = len(problems)
    status, health_body = _get("/healthz")
    health = json.loads(health_body)
    out["metrics_smoke_healthz"] = health.get("status")
    if not identical:
        _fail("two no-heartbeat scrapes never matched byte-for-byte")
    if problems:
        _fail("openmetrics violations: " + "; ".join(problems[:4]))
    if status != 200 or health.get("status") != "ok":
        _fail(f"/healthz {status} {health_body.strip()}")

    # 4. follow stdout to the summary line, then scrape the *finalized*
    # registry inside the SHADOW_TPU_METRICS_LINGER_S window — the same
    # "scrape after the run's last heartbeat" a shell harness would do
    import threading

    threading.Thread(target=proc.stderr.read, daemon=True).start()
    stdout_lines: list[str] = []
    summary: dict = {}
    deadline = time.monotonic() + max(_remaining() - 30, 60)
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        stdout_lines.append(line)
        if line.startswith("{"):
            try:
                cand = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "events" in cand:
                summary = cand
                break
    if not summary:
        proc.kill()
        _fail("summary line never appeared on stdout")
    final_text = _get("/metrics")[1]
    out["metrics_smoke_final_violations"] = len(
        validate_openmetrics(final_text))
    final = _samples(final_text)
    proc.stdout.read()  # drain until the linger window ends the process
    rc = proc.wait(timeout=60)
    out["metrics_smoke_rc"] = rc

    recon_ok = rc == 0 and not out["metrics_smoke_final_violations"]
    for key in ("events", "windows", "queue_drops", "net_dropped",
                "fault_dropped", "quarantined_events",
                "cross_shard_packets", "rx_bytes", "tx_bytes"):
        want = int(summary.get(key, 0))
        got = final.get(f"shadow_tpu_{key}_total")
        if got is None or int(got) != want:
            recon_ok = False
            out[f"metrics_smoke_mismatch_{key}"] = [want, got]
    # the [metrics] heartbeat rows are the same registry logged in-band;
    # the last row must agree with the scrape (exporter vs tracker)
    from shadow_tpu.tools.parse_shadow import parse_lines

    met = parse_lines(stdout_lines)["metrics"]
    rows_ok = bool(met["ticks"]) and all(
        met[k][-1] == int(final.get(f"shadow_tpu_{k}_total", -1))
        for k in ("events", "queue_drops", "rx_bytes", "tx_bytes")
    )
    # mid-run scrape must never exceed the final totals (counters only
    # move forward)
    monotone_ok = all(
        _samples(b).get(s, 0) <= final.get(s, 0)
        for s in ("shadow_tpu_events_total", "shadow_tpu_rx_bytes_total")
    )
    out["metrics_smoke_reconciled"] = recon_ok
    out["metrics_smoke_rows_match_scrape"] = rows_ok
    out["metrics_smoke_monotonic"] = monotone_ok
    out["metrics_smoke_events"] = int(summary.get("events", 0))
    out["metrics_smoke_ok"] = recon_ok and rows_ok and monotone_ok
    print(json.dumps(out), flush=True)
    if not out["metrics_smoke_ok"]:
        print("metrics_smoke: reconciliation failed", file=sys.stderr)
        sys.exit(1)


def perf_smoke():
    """CPU PHOLD floor gate (measure_all.sh perf_smoke stage): a small
    fixed-shape PHOLD on the CPU backend, compared against the
    checked-in PERF_FLOOR.json. Exits 1 when events/s lands below 70%
    of the floor — the cheap no-TPU lane that catches hot-path
    regressions (together with the lint + hlo_audit stages) before a
    device bench runs. The floor is per-machine-class, deliberately
    loose; update it consciously with PERF_SMOKE_UPDATE=1."""
    _cpu_stage()
    import jax
    import jax.numpy as jnp

    from shadow_tpu.core.timebase import SECOND, seconds
    from shadow_tpu.models import phold

    n_hosts, stop_s = 256, 4
    eng, init = phold.build(
        n_hosts, capacity=CAPACITY, latency_ns=seconds(LATENCY_S),
        mean_delay_ns=seconds(MEAN_DELAY_S), msgs_per_host=MSGS_PER_HOST,
        seed=SEED, batched=True,
    )
    run = jax.jit(eng.run, donate_argnums=0)
    # fresh init states alias buffers across leaves (broadcasted
    # zeros); one per-leaf copy makes them donation-safe
    fresh = lambda: jax.tree.map(
        lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x, init()
    )
    jax.block_until_ready(run(fresh(), jnp.int64(1 * SECOND)))  # compile
    t0 = time.perf_counter()
    st = run(fresh(), jnp.int64(stop_s * SECOND))
    executed = int(jax.device_get(st.stats.n_executed).sum())
    wall = time.perf_counter() - t0
    rate = executed / wall

    # TCP-workload floor: a small tgen config under the FRONTIER drain
    # (the TCP model tier's hot path since the model-tier batching PR).
    # PHOLD gates the commutative batched drain; this gates the
    # transport/handler pass + the frontier bookkeeping, which PHOLD's
    # stateless handler never touches.
    from shadow_tpu.config import parse_config
    from shadow_tpu.examples import tgen_example
    from shadow_tpu.sim import build_simulation

    tcp_pairs, tcp_stop_s = 16, 10
    cfg = parse_config(tgen_example(n_pairs=tcp_pairs, stoptime=tcp_stop_s))
    sim = build_simulation(cfg, seed=1, n_sockets=8, frontier=8)
    sim.strict_overflow = False
    tst = sim.run(1 * SECOND)  # compile
    jax.block_until_ready(tst.now)
    t0 = time.perf_counter()
    tst = sim.run(tcp_stop_s * SECOND)
    tcp_executed = int(jax.device_get(tst.stats.n_executed).sum())
    tcp_wall = time.perf_counter() - t0
    tcp_rate = tcp_executed / tcp_wall

    # Fleet floor: an 8-lane seed-sweep fleet over the same PHOLD shape
    # (docs/16-Scenario-Fleets.md). Gates the vmapped window loop's
    # throughput — a structural regression in the batched program (an
    # extra scatter, a broken termination mask) lands here as events/s,
    # without paying the full bench.py --fleet comparison. Warm-cache
    # like the other two floors: this prices execution, not compile.
    fleet_lanes = 8
    fleet = phold.build_fleet(
        n_hosts, fleet_lanes, seeds=tuple(range(SEED, SEED + fleet_lanes)),
        capacity=CAPACITY, latency_ns=seconds(LATENCY_S),
        mean_delay_ns=seconds(MEAN_DELAY_S), msgs_per_host=MSGS_PER_HOST,
        seed=SEED, batched=True,
    )
    jax.block_until_ready(fleet.run(jnp.int64(1 * SECOND)).now)  # compile
    t0 = time.perf_counter()
    fst = fleet.run(jnp.int64(stop_s * SECOND))
    fleet_executed = int(jax.device_get(fst.stats.n_executed).sum())
    fleet_wall = time.perf_counter() - t0
    fleet_rate_v = fleet_executed / fleet_wall

    floor_path = os.path.join(_REPO, "PERF_FLOOR.json")
    try:
        with open(floor_path) as f:
            floor = json.load(f)
    except (OSError, json.JSONDecodeError):
        floor = {}
    if os.environ.get("PERF_SMOKE_UPDATE") == "1":
        # update measured floors in place — unrelated keys survive so
        # the two gates can be re-floored independently
        floor.update({
            "phold_cpu_events_per_s": round(rate, 1),
            "n_hosts": n_hosts, "stop_s": stop_s,
            "msgs_per_host": MSGS_PER_HOST, "capacity": CAPACITY,
            "tgen_cpu_events_per_s": round(tcp_rate, 1),
            "tgen_pairs": tcp_pairs, "tgen_stop_s": tcp_stop_s,
            "tgen_frontier": 8,
            "fleet_cpu_events_per_s": round(fleet_rate_v, 1),
            "fleet_lanes": fleet_lanes,
        })
        with open(floor_path, "w") as f:
            json.dump(floor, f, indent=2)
            f.write("\n")
    fl = float(floor.get("phold_cpu_events_per_s", 0.0))
    tcp_fl = float(floor.get("tgen_cpu_events_per_s", 0.0))
    fleet_fl = float(floor.get("fleet_cpu_events_per_s", 0.0))
    ok = fl <= 0 or rate >= 0.7 * fl
    tcp_ok = tcp_fl <= 0 or tcp_rate >= 0.7 * tcp_fl
    fleet_ok = fleet_fl <= 0 or fleet_rate_v >= 0.7 * fleet_fl
    print(json.dumps({
        "perf_smoke_events_per_s": round(rate, 1),
        "perf_smoke_floor": fl,
        "perf_smoke_events": executed,
        "perf_smoke_wall_s": round(wall, 3),
        "perf_smoke_tgen_events_per_s": round(tcp_rate, 1),
        "perf_smoke_tgen_floor": tcp_fl,
        "perf_smoke_tgen_events": tcp_executed,
        "perf_smoke_tgen_wall_s": round(tcp_wall, 3),
        "perf_smoke_fleet_events_per_s": round(fleet_rate_v, 1),
        "perf_smoke_fleet_floor": fleet_fl,
        "perf_smoke_fleet_events": fleet_executed,
        "perf_smoke_fleet_wall_s": round(fleet_wall, 3),
        "perf_smoke_ok": ok and tcp_ok and fleet_ok,
    }), flush=True)
    if not ok:
        print(f"perf_smoke: {rate:.0f} events/s is below 70% of the "
              f"PERF_FLOOR.json floor {fl:.0f} — hot-path regression",
              file=sys.stderr)
    if not tcp_ok:
        print(f"perf_smoke: tgen {tcp_rate:.0f} events/s is below 70% "
              f"of the PERF_FLOOR.json floor {tcp_fl:.0f} — TCP/frontier "
              f"hot-path regression", file=sys.stderr)
    if not fleet_ok:
        print(f"perf_smoke: fleet {fleet_rate_v:.0f} events/s is below "
              f"70% of the PERF_FLOOR.json floor {fleet_fl:.0f} — "
              f"vmapped window-loop regression", file=sys.stderr)
    if not (ok and tcp_ok and fleet_ok):
        sys.exit(1)


def previous_tor_record() -> tuple[str, dict]:
    """(label, parsed) of the newest checked-in BENCH_r*.json whose
    parsed dict carries tor_* keys — the anchor the tor_rt stage prints
    its regression delta against. ("", {}) when none exists."""
    import glob
    import re

    best = ("", {}, -1)
    for path in glob.glob(os.path.join(_REPO, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        n = int(m.group(1))
        try:
            with open(path) as f:
                parsed = json.load(f).get("parsed") or {}
        except (OSError, json.JSONDecodeError):
            continue
        if float(parsed.get("tor_sim_s_per_wall_s", 0.0)) > 0 and n > best[2]:
            best = (f"r{n:02d}", parsed, n)
    return best[0], best[1]


def tor_rt():
    """tor_rt stage (measure_all.sh): the real-time-factor report for
    the TCP model tier. Runs tor (BENCH_TOR_TIER, default the 1020-host
    tier) and tgen (BENCH_TGEN_PAIRS) each twice in fresh subprocesses
    — chained drain, then the frontier drain with the runahead widener
    (BENCH_FRONTIER/BENCH_RUNAHEAD_MS, defaults 16/100) — and prints
    one JSON dict with sim-s/wall-s + events/sweep for all four runs,
    each worker's per-phase build/compile/run profile, and the
    regression delta vs the newest BENCH_r*.json tor record. The two
    drains are bit-identical by contract (tests/test_model_batching.py)
    so the pair is a pure price-of-bookkeeping measurement."""
    tier = os.environ.get("BENCH_TOR_TIER", "2")
    frontier = os.environ.get("BENCH_FRONTIER", "16")
    runahead = os.environ.get("BENCH_RUNAHEAD_MS", "100")
    tmo = int(os.environ.get("BENCH_TOR_RT_TIMEOUT", 2400))
    out = {"tier": int(tier), "frontier": int(frontier),
           "runahead_ms": float(runahead)}

    def _run(flag: str, pre: str, tag: str, env: dict) -> dict:
        for k in ("BENCH_FRONTIER", "BENCH_RUNAHEAD_MS"):
            os.environ.pop(k, None)
        os.environ.update(env)
        r = run_secondary(flag, nominal_timeout=tmo)
        sub = {k[len(pre):]: v for k, v in r.items() if k.startswith(pre)}
        if sub:
            out[tag] = sub
            print(json.dumps({"tor_rt": out}), flush=True)
        return sub

    os.environ["BENCH_TOR_TIER"] = tier
    tor_ch = _run("--tor-worker", "tor_", "tor_chained", {})
    tor_fr = _run("--tor-worker", "tor_", "tor_frontier",
                  {"BENCH_FRONTIER": frontier, "BENCH_RUNAHEAD_MS": runahead})
    tgen_ch = _run("--tgen-worker", "tgen_", "tgen_chained", {})
    tgen_fr = _run("--tgen-worker", "tgen_", "tgen_frontier",
                   {"BENCH_FRONTIER": frontier,
                    "BENCH_RUNAHEAD_MS": runahead})
    # the analytics row: same tier, frontier drain, --stats histograms
    # + trace on (untimed — instrumentation changes the program, so it
    # never contaminates the four timed legs above)
    ana = _run("--tor-analytics-worker", "tora_", "tor_analytics",
               {"BENCH_FRONTIER": frontier})
    if ana:
        depth = int(ana.get("critical_depth", 0))
        execs = int(ana.get("execs", 0))
        print(f"tor_rt: frontier run length p50/p95 = "
              f"{ana.get('runlen_p50', 0):.0f}/"
              f"{ana.get('runlen_p95', 0):.0f} positions "
              f"(mean {ana.get('runlen_mean', 0)}), critical-path "
              f"depth {depth} over {execs} events -> lockstep ceiling "
              f"{execs / max(depth, 1):.1f} events/sweep",
              file=sys.stderr, flush=True)

    prev_label, prev = previous_tor_record()
    if prev_label and tor_fr:
        out["prev_bench"] = prev_label
        pv = float(prev.get("tor_sim_s_per_wall_s", 0.0))
        pe = float(prev.get("tor_events_per_sweep", 0.0))
        nv = float(tor_fr.get("sim_s_per_wall_s", 0.0))
        ne = float(tor_fr.get("events_per_sweep", 0.0))
        if pv > 0 and nv > 0:
            out["tor_delta_pct"] = round((nv - pv) / pv * 100.0, 1)
            print(f"tor_rt: {pv:.3f} -> {nv:.3f} sim-s/wall-s, "
                  f"{out['tor_delta_pct']:+.1f}% vs {prev_label}",
                  file=sys.stderr, flush=True)
        if pe > 0 and ne > 0:
            out["tor_events_per_sweep_x"] = round(ne / pe, 2)
            print(f"tor_rt: {pe:.1f} -> {ne:.1f} events/sweep, "
                  f"x{out['tor_events_per_sweep_x']:.2f} vs {prev_label}",
                  file=sys.stderr, flush=True)
    if tor_ch and tor_fr:
        cv = float(tor_ch.get("sim_s_per_wall_s", 0.0))
        nv = float(tor_fr.get("sim_s_per_wall_s", 0.0))
        if cv > 0 and nv > 0:
            out["tor_frontier_x"] = round(nv / cv, 2)
    if tgen_ch and tgen_fr:
        cv = float(tgen_ch.get("sim_s_per_wall_s", 0.0))
        nv = float(tgen_fr.get("sim_s_per_wall_s", 0.0))
        if cv > 0 and nv > 0:
            out["tgen_frontier_x"] = round(nv / cv, 2)
    print(json.dumps({"tor_rt": out}), flush=True)


def previous_bench(device: str) -> tuple[str, float]:
    """(label, events/s) of the newest checked-in BENCH_r*.json with a
    parsed primary PHOLD number measured on `device` — the regression
    anchor every new record embeds and prints its delta against.
    ("", 0.0) when none exists."""
    import glob
    import re

    best = ("", 0.0, -1)
    for path in glob.glob(os.path.join(_REPO, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        n = int(m.group(1))
        try:
            with open(path) as f:
                parsed = json.load(f).get("parsed") or {}
            value = float(parsed.get("value", 0.0))
        except (OSError, json.JSONDecodeError, ValueError):
            continue
        if value > 0 and parsed.get("device") == device and n > best[2]:
            best = (f"r{n:02d}", value, n)
    return best[0], best[1]


def _fmt_rate(v: float) -> str:
    return f"{v / 1e6:.1f}M" if v >= 1e6 else f"{v / 1e3:.0f}k"


def print_delta(out: dict) -> None:
    """One glanceable regression line on stderr:
    `phold: 11.9M -> 14.2M events/s, +19.3% vs r05`."""
    prev_label, prev = out.get("prev_bench", ""), out.get("prev_events_per_s", 0.0)
    now = out.get("value", 0.0)
    if not prev or not now:
        return
    pct = (now - prev) / prev * 100.0
    print(f"phold: {_fmt_rate(prev)} -> {_fmt_rate(now)} events/s, "
          f"{pct:+.1f}% vs {prev_label}", file=sys.stderr, flush=True)


def main():
    for flag, fn in (("--tor-worker", tor_worker),
                     ("--tor-analytics-worker", tor_analytics_worker),
                     ("--tor-churn-worker", tor_churn_worker),
                     ("--tgen-worker", tgen_worker),
                     ("--tor-rt", tor_rt),
                     ("--btc-worker", btc_worker),
                     ("--phold-worker", phold_worker),
                     ("--phold-big-worker", phold_big_worker),
                     ("--fleet", fleet_worker),
                     ("--fleet-smoke", fleet_smoke_worker),
                     ("--serve-smoke", serve_smoke_worker),
                     ("--serve-chaos", serve_chaos_worker),
                     ("--serve-elastic", serve_elastic_worker),
                     ("--perf-smoke", perf_smoke),
                     ("--multichip-worker", multichip_worker),
                     ("--chaos-worker", chaos_worker),
                     ("--metrics-smoke-worker", metrics_smoke_worker),
                     ("--skew-worker", skew_worker)):
        if flag in sys.argv:
            fn()
            if FAILED_STAGES:
                sys.exit(1)
            return
    stop_s = int(sys.argv[1]) if len(sys.argv) > 1 else STOP_SIM_SECONDS
    os.environ["BENCH_STOP_S"] = str(stop_s)
    py_rate = python_baseline_rate()

    # the primary runs in a child like every other chip stage: the
    # parent never touches JAX, so each child can hold the chip. A
    # failed primary ends the bench non-zero with no result line.
    r = run_secondary("--phold-worker", nominal_timeout=900)
    if not r:
        sys.exit(1)
    out = {
        "metric": "phold_events_per_sec",
        "value": round(r["events_per_s"], 1),
        "unit": "events/s",
        "vs_baseline": round(r["events_per_s"] / py_rate, 3),
        "baseline_python_events_per_sec": round(py_rate, 1),
        "sim_s_per_wall_s": round(r["sim_s_per_wall_s"], 3),
        "n_hosts": r["n_hosts"],
        "events": r["events"],
        "wall_s": round(r["wall_s"], 3),
        "windows": r["windows"],
        "drops": r["drops"],
        "drain": r["drain"],
        "device": r["device"],
        "profile": r.get("profile", {}),
    }
    prev_label, prev_rate = previous_bench(r["device"])
    if prev_label:
        out["prev_bench"] = prev_label
        out["prev_events_per_s"] = prev_rate
    print(json.dumps(out), flush=True)
    print_delta(out)

    # secondaries enrich the result; every stage re-prints the full dict
    # so the last line is always a complete superset. Ordering is
    # breadth-first: the two fast tor tiers, then the OTHER workload
    # families, and only then the 1020-host tor tier, so its long timed
    # run cannot starve btc/phold16k/skew of budget. Tiers climb
    # smallest-first across FRESH subprocesses; each success overwrites
    # the tor_* keys, so the final dict carries the LARGEST tier that
    # ran.
    os.environ.pop("BENCH_TOR_CPU", None)  # default: CPU model ON (tor_*)
    tor_ok = False
    for tier in (0, 1):
        os.environ["BENCH_TOR_TIER"] = str(tier)
        rt = run_secondary("--tor-worker",
                           nominal_timeout=420 if tier == 0 else 600)
        if not rt:
            break
        tor_ok = True
        out.update(rt)
        print(json.dumps(out), flush=True)
    if tor_ok:
        # the CPU-model-off variant at the smallest tier: the with/without
        # pair, now with the honest (CPU on) number as the headline
        # (r03/r04 verdict item 8)
        os.environ["BENCH_TOR_TIER"] = "0"
        os.environ["BENCH_TOR_CPU"] = "0"
        rc = run_secondary("--tor-worker", nominal_timeout=420)
        os.environ.pop("BENCH_TOR_CPU", None)
        if rc:
            out.update(rc)
            print(json.dumps(out), flush=True)
    if tor_ok:
        # churn variant at the smallest tier: liveness + drop attribution
        # under relay crash/restart cycles
        rch = run_secondary("--tor-churn-worker", nominal_timeout=420)
        if rch:
            out.update(rch)
            print(json.dumps(out), flush=True)
    rb = run_secondary("--btc-worker")
    if rb:
        out.update(rb)
        print(json.dumps(out), flush=True)
    rbig = run_secondary("--phold-big-worker")
    if rbig:
        out.update(rbig)
        print(json.dumps(out), flush=True)
    rs = run_secondary("--skew-worker")
    if rs:
        out.update({
            "skew_events_per_s": round(rs.get("skew_events_per_s", 0.0), 1),
            "skew_sim_s_per_wall_s": round(
                rs.get("skew_sim_s_per_wall_s", 0.0), 3
            ),
            "skew_drops": rs.get("skew_drops", -1),
            "skew_lossy": rs.get("skew_lossy", True),
            # lossless-mode pricing on the identical skew workload
            "skew_spill_events_per_s": round(
                rs.get("skew_spill_events_per_s", 0.0), 1
            ),
            "skew_spill_drops": rs.get("skew_spill_drops", -1),
            "skew_spill_spilled": rs.get("skew_spill_spilled", 0),
            "skew_spill_refilled": rs.get("skew_spill_refilled", 0),
            "skew_spill_lossy": rs.get("skew_spill_lossy", True),
        })
        print(json.dumps(out), flush=True)
    if tor_ok:
        # the 1020-host tier, then the 10k north-star shape, with
        # whatever budget remains
        for tier, tmo in (("2", 2400), ("3", 3000)):
            os.environ["BENCH_TOR_TIER"] = tier
            rt2 = run_secondary("--tor-worker", nominal_timeout=tmo)
            if rt2:
                out.update(rt2)
                print(json.dumps(out), flush=True)
    if FAILED_STAGES:
        sys.exit(1)


if __name__ == "__main__":
    main()
