"""CLI entry: `python -m shadow_tpu [options] shadow.config.xml`.

Mirrors the reference's command surface (reference:
src/main/core/support/options.c option table; src/main/core/main.c:735
main_runShadow): config-file driven, `--test` for the built-in example
(examples.c), seed / heartbeat-frequency / log-level flags. Flags tied to
pthread scheduling (--workers, --scheduler-policy) have no TPU meaning and
are accepted-but-ignored with a note, so existing scripts keep working.

The run loop is the Master round loop (master.c:400-480) at CLI
granularity: jit-compiled window batches between heartbeat prints, then a
final summary line with event/window counts and rates.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from shadow_tpu import __version__
from shadow_tpu.config import parse_config
from shadow_tpu.core.timebase import MILLISECOND, SECOND
from shadow_tpu.examples import example_config
from shadow_tpu.sim import build_simulation
from shadow_tpu.utils.compile_cache import enable_compile_cache


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="shadow_tpu",
        description="TPU-native discrete-event network simulator",
    )
    p.add_argument("config", nargs="?", help="shadow.config.xml path")
    p.add_argument("--test", action="store_true",
                   help="run the built-in example config (examples.c)")
    p.add_argument("--seed", "-s", type=int, default=1,
                   help="random seed (options.c --seed)")
    p.add_argument("--stoptime", type=float, default=None,
                   help="override the config's stoptime (seconds)")
    p.add_argument("--bootstrap-end", type=float, default=None,
                   help="override bootstraptime (unlimited-bw phase end)")
    p.add_argument("--heartbeat-frequency", type=float, default=60.0,
                   help="sim seconds between heartbeat lines "
                        "(options.c --heartbeat-frequency)")
    p.add_argument("--sockets", type=int, default=8,
                   help="socket slots per host")
    p.add_argument("--capacity", type=int, default=None,
                   help="event-queue slots per host (default: sized to "
                        "hold a full TCP receive window in flight)")
    p.add_argument("--allow-queue-overflow", action="store_true",
                   help="legacy alias for --overflow drop with counted, "
                        "non-fatal drops")
    p.add_argument("--overflow", default=None,
                   choices=["spill", "strict", "grow", "drop"],
                   help="event-queue overflow handling "
                        "(docs/9-Queue-Pressure.md): 'spill' (default) is "
                        "lossless — evicted events land in a device ring "
                        "and a host reservoir re-inserts them at window "
                        "boundaries; 'strict' aborts with exit 76 and a "
                        "diagnostic bundle at the first would-be drop; "
                        "'grow' spills and doubles --capacity at the first "
                        "sign of pressure; 'drop' keeps the historical "
                        "lossy counted behavior (sharded meshes default "
                        "to drop: spill is unsharded-only for now)")
    p.add_argument("--log-level", "-l", default="message",
                   choices=["error", "critical", "warning", "message",
                            "info", "debug"])
    p.add_argument("--tcp-congestion-control", default="reno",
                   choices=["reno", "cubic", "aimd"],
                   help="congestion-control algorithm for all TCP "
                        "connections (options.c --tcp-congestion-control)")
    p.add_argument("--interface-qdisc", default="fifo",
                   choices=["fifo", "rr"],
                   help="socket send scheduling: creation-order bursts or "
                        "per-packet round-robin (options.c interface-qdisc)")
    p.add_argument("--interface-buffer", type=int, default=1_024_000,
                   help="NIC receive buffer bytes, drop-tail "
                        "(options.c:132; interfacebuffer host attr "
                        "overrides per host)")
    p.add_argument("--router-queue", default="codel",
                   choices=["codel", "static", "single"],
                   help="upstream router queue manager "
                        "(router.c:50-55 QUEUE_MANAGER_*)")
    p.add_argument("--locality", action="store_true",
                   help="reorder hosts at build time so config-visible "
                        "traffic partners share a shard (sharded runs; "
                        "replaces the reference's random host shuffle + "
                        "work stealing)")
    p.add_argument("--mesh", type=int, default=0,
                   help="shard hosts over N devices (0 = single device; "
                        "the TPU-era --workers)")
    p.add_argument("--dcn-slices", type=int, default=1,
                   help="arrange the mesh as M slices joined over DCN "
                        "(multi-slice; the reference's unfinished "
                        "multi-machine design, master.c:414-416)")
    p.add_argument("--runahead", type=float, default=None,
                   help="override the conservative window width in "
                        "MILLISECONDS (options.c --runahead minTimeJump; "
                        "default: the topology's minimum path latency). "
                        "Wider windows mean fewer barriers but coarser "
                        "cross-host packet timing: arrivals inside a "
                        "window are deferred to its end")
    p.add_argument("--window", default=None, metavar="N|auto",
                   help="conservative-window width as a TRACED scalar: a "
                        "number is a fixed width in milliseconds, 'auto' "
                        "lets a deterministic host-side controller retune "
                        "the width between windows (no recompiles; "
                        "docs/11-Performance.md). Like --runahead, widths "
                        "past the topology's minimum latency coarsen "
                        "cross-host packet timing; leave the flag off for "
                        "bit-identical default results")
    p.add_argument("--workers", "-w", type=int, default=None,
                   help="ignored (pthread-era flag; kept for compatibility)")
    p.add_argument("--scheduler-policy", "-p", default=None,
                   help="ignored (pthread-era flag; kept for compatibility)")
    p.add_argument("--fault", action="append", default=[],
                   metavar="SPEC",
                   help="append a fault to the schedule; repeatable. SPEC "
                        "is 'TYPE key=value ...', e.g. "
                        "'crash hosts=relay* start=30 end=45' or 'churn "
                        "hosts=relay* start=10 end=60 period=20 downtime=5 "
                        "frac=0.2' (same attrs as the config's <fault> "
                        "element; see docs/6-Fault-Injection.md)")
    p.add_argument("--fleet", default=None, metavar="SPEC",
                   help="run L scenario lanes of this config as ONE "
                        "vmapped program (docs/16-Scenario-Fleets.md). "
                        "SPEC is space-separated 'lanes=L [seed=a:b] "
                        "[fault-file=PATH] [latency-scale=x,y,...]': "
                        "seed=a:b gives lanes seeds a..b-1 (default: "
                        "--seed for every lane); fault-file holds one "
                        "lane per line of ';'-separated fault DSL specs "
                        "(blank line = no faults for that lane); "
                        "latency-scale lists one multiplier per lane. "
                        "Per-lane heartbeat progress prints as [fleet] "
                        "rows; the summary JSON grows a per-lane "
                        "'lanes' list")
    p.add_argument("--port", type=int, default=0, metavar="PORT",
                   help="serve mode: HTTP port for the request plane "
                        "(0 = kernel-assigned ephemeral port, printed "
                        "to stderr). Only with the 'serve' subcommand "
                        "(docs/17-Serving.md)")
    p.add_argument("--max-lanes", type=int, default=8, metavar="L",
                   help="serve mode: fleet lanes per launch — every "
                        "cached program compiles at exactly L lanes; "
                        "short batches pad with inert lanes")
    p.add_argument("--pack-deadline-ms", type=float, default=50.0,
                   metavar="MS",
                   help="serve mode: max time a queued request waits "
                        "for lane-mates before its class launches "
                        "partially packed (deadline-or-full dispatch)")
    p.add_argument("--max-cached-programs", type=int, default=4,
                   metavar="N",
                   help="serve mode: compiled fleet programs kept warm; "
                        "LRU eviction past N (docs/17-Serving.md)")
    p.add_argument("--queue-file", default="shadow_tpu.queue.json",
                   help="serve mode: pending requests persist here on "
                        "graceful SIGTERM drain and reload on the next "
                        "start")
    p.add_argument("--beat-windows", type=int, default=32, metavar="N",
                   help="serve mode: simulation windows per progress "
                        "heartbeat (one single-fetch harvest per beat)")
    p.add_argument("--snapshot-beats", type=int, default=0, metavar="N",
                   help="serve mode: persist the in-flight batch (fleet "
                        "state + manifest) to --snapshot-path every N "
                        "beats; a failed or crashed launch resumes from "
                        "the last snapshot instead of window 0 (0=off; "
                        "docs/17-Serving.md 'Failure semantics')")
    p.add_argument("--snapshot-path",
                   default="shadow_tpu.serve.snapshot.npz",
                   help="serve mode: beat-snapshot file (checkpoint v7 "
                        "with a serve-batch manifest header)")
    p.add_argument("--launch-retries", type=int, default=1, metavar="N",
                   help="serve mode: retries per launch (exponential "
                        "backoff, resuming from the newest snapshot); "
                        "once exhausted a multi-request batch bisects "
                        "to isolate the poison request")
    p.add_argument("--launch-deadline-s", type=float, default=0.0,
                   metavar="S",
                   help="serve mode: per-beat wall deadline — a wedged "
                        "launch aborts the process with the retryable "
                        "stall exit (75) and a diagnostic bundle, so an "
                        "outer --retry relaunch resumes the batch from "
                        "its snapshot (0=off)")
    p.add_argument("--result-ttl-s", type=float, default=0.0, metavar="S",
                   help="serve mode: evict terminal (done/error/timeout) "
                        "result records not polled for S seconds (0 = "
                        "no TTL; queued/running records never evict)")
    p.add_argument("--max-results", type=int, default=65536, metavar="N",
                   help="serve mode: LRU cap on retained terminal result "
                        "records")
    p.add_argument("--degraded-after", type=int, default=3, metavar="N",
                   help="serve mode: consecutive terminal launch "
                        "failures before /healthz reports degraded and "
                        "/submit returns 503 (a later success recovers)")
    p.add_argument("--trace-requests", type=int, default=0, metavar="N",
                   help="serve mode: record request-scoped spans for "
                        "the most recent N requests and serve each span "
                        "tree at GET /trace/<id> (0 = tracing off; "
                        "docs/18-Serve-Tracing.md)")
    p.add_argument("--ledger-file", default=None, metavar="JSONL",
                   help="serve mode: append every trace span/event to "
                        "this JSONL flight ledger (implies tracing; "
                        "flushed per record, so tools/serve_report and "
                        "the merged tools/export_trace view work on "
                        "dead servers)")
    p.add_argument("--checkpoint-interval", type=float, default=0.0,
                   help="write a checkpoint every N sim seconds (0=off). "
                        "Independent of the interval, SIGINT/SIGTERM "
                        "checkpoint-then-exit and SIGUSR1 writes an "
                        "on-demand checkpoint (docs/7-Supervised-Runs.md)")
    p.add_argument("--checkpoint-path", default="shadow_tpu.ckpt.npz",
                   help="checkpoint file path (rotated each write; see "
                        "--checkpoint-keep)")
    p.add_argument("--checkpoint-keep", type=int, default=1, metavar="N",
                   help="checkpoint generations to retain: PATH newest, "
                        "PATH.1..PATH.N-1 older (default 1 = overwrite)")
    p.add_argument("--resume", default=None, metavar="PATH|auto",
                   help="resume from a checkpoint written by the same "
                        "config; 'auto' picks the newest CRC-verified "
                        "candidate of --checkpoint-path (generations, "
                        "the .emergency crash file, complete shard "
                        "sets), falling back past corrupt ones; "
                        "'auto-if-any' (the --retry relaunch mode) "
                        "starts fresh instead of erroring when nothing "
                        "checkpoint-like exists yet")
    p.add_argument("--watchdog", type=float, default=0.0, metavar="SECONDS",
                   help="per-window wall-clock deadline over the jitted "
                        "step and the proc-tier syscall exchange: on "
                        "stall, dump all thread stacks + a diagnostic "
                        "bundle into --diag-dir and exit 75 instead of "
                        "hanging (0=off; allow for one cold XLA compile "
                        "inside the first window)")
    p.add_argument("--collective-timeout", type=float, default=0.0,
                   metavar="SECONDS",
                   help="per-window deadline over the sharded step's "
                        "collectives and the heartbeat harvest's "
                        "device_get — the two sites a dead mesh peer "
                        "wedges forever: on expiry, dump a per-shard "
                        "diagnostic bundle into --diag-dir and exit 77 "
                        "(EXIT_PEER_LOST) so a --retry wrapper can "
                        "relaunch on a shrunken mesh "
                        "(docs/13-Elastic-Recovery.md; 0=off)")
    p.add_argument("--retry", type=int, default=0, metavar="N",
                   help="supervise the run in a child process and "
                        "relaunch it up to N times after transient "
                        "failures (stall 75, peer-lost 77, signal "
                        "deaths), resuming from the newest valid "
                        "checkpoint with exponential backoff; a "
                        "peer-lost relaunch halves --mesh "
                        "(docs/13-Elastic-Recovery.md)")
    p.add_argument("--retry-backoff", type=float, default=1.0,
                   metavar="SECONDS",
                   help="base of the --retry exponential backoff "
                        "(SECONDS, 2*SECONDS, 4*SECONDS, ...)")
    p.add_argument("--validate", type=int, default=0, metavar="K",
                   help="check EngineState invariants every K engine "
                        "windows, off the jitted path (monotonic clock, "
                        "sorted queue rows, non-negative counters, NaN "
                        "scan); exit 70 naming the offending leaf on "
                        "violation (0=off)")
    p.add_argument("--diag-dir", default=".",
                   help="directory for watchdog stall bundles and stack "
                        "dumps")
    p.add_argument("--trace", nargs="?", const=2048, type=int, default=0,
                   metavar="N",
                   help="device-side event tracing: record every executed "
                        "event and routed send into a per-host ring of N "
                        "records (bare --trace = 2048), drained at "
                        "heartbeat boundaries and written to --trace-out; "
                        "export to Chrome trace-event JSON with "
                        "tools/export_trace.py (docs/8-Tracing-Profiling.md)")
    p.add_argument("--trace-out", default="shadow_tpu.trace.npz",
                   metavar="PATH",
                   help="trace output file (.npz of record arrays + meta)")
    p.add_argument("--profile", action="store_true",
                   help="wall-clock-time the run loop's phases (build, "
                        "jitted step, host drain, shim pump, checkpoint) "
                        "plus per-window occupancy; adds a 'profile' key "
                        "to the summary line and per-phase tracks to the "
                        "exported trace")
    p.add_argument("--metrics", action="store_true",
                   help="live telemetry registry: fold the heartbeat "
                        "harvest's counters into an OpenMetrics-renderable "
                        "registry and emit a [metrics] heartbeat section "
                        "(docs/14-Telemetry.md). Rides the existing "
                        "single-fetch harvest bundle — no extra device "
                        "round-trips; off, the compiled program is "
                        "byte-identical")
    p.add_argument("--stats", action="store_true",
                   help="sim-time analytics plane: device-side log2 "
                        "histograms of event wait time, network latency, "
                        "per-window host occupancy, queue fill at pop, "
                        "and frontier run length, accumulated inside the "
                        "jitted window loop and harvested through the "
                        "single-fetch heartbeat bundle; emits a [stats] "
                        "heartbeat section and OpenMetrics histogram "
                        "families (docs/15-Sim-Analytics.md). Off, the "
                        "compiled program is byte-identical")
    p.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                   help="serve /metrics (OpenMetrics), /healthz, and "
                        "/summary.json on 127.0.0.1:PORT from a background "
                        "thread (0 = ephemeral port, printed to stderr); "
                        "implies --metrics")
    p.add_argument("--xprof", default=None, metavar="START:STOP",
                   help="capture a device profiler trace "
                        "(jax.profiler.start_trace/stop_trace) across the "
                        "window segments between sim seconds START and "
                        "STOP, into --xprof-dir; the exported event trace "
                        "references the directory so Perfetto can show "
                        "sim-time tracks and device traces side by side "
                        "(docs/14-Telemetry.md)")
    p.add_argument("--xprof-dir", default="shadow_tpu_xprof",
                   metavar="DIR",
                   help="output directory for the --xprof trace")
    p.add_argument("--show-build-info", action="store_true")
    return p


def _make_observability(cfg, sim, args, trace=None, metrics=None):
    """Logger + tracker honoring the config's per-host loglevel and
    heartbeatloginfo attrs (tracker.c:433-561; shadow_logger.c:102-121)."""
    from shadow_tpu.config import expand_hosts
    from shadow_tpu.utils.logger import ShadowLogger
    from shadow_tpu.utils.tracker import Tracker

    logger = ShadowLogger(default_level=args.log_level)
    info_of: dict[str, tuple[str, ...]] = {}
    level_of: dict[str, str] = {}
    for h in expand_hosts(cfg):
        if h.spec.loglevel:
            logger.set_host_level(h.name, h.spec.loglevel)
        if h.spec.heartbeatloginfo:
            info_of[h.name] = tuple(
                p.strip() for p in h.spec.heartbeatloginfo.split(",")
                if p.strip()
            )
        if h.spec.heartbeatloglevel:
            level_of[h.name] = h.spec.heartbeatloglevel
    tracker = Tracker(
        sim.names, logger, log_info=("node",), info_of=info_of,
        level_of=level_of, faults=sim.faults, trace=trace,
        pressure=sim.pressure, metrics=metrics,
    )
    return logger, tracker


def _make_profiler(args):
    """WindowProfiler when --profile, else None — plus a phase context
    factory for the call sites: the profiler's timed phase, or without
    --profile only the phase's span on the XLA profiler's timeline."""
    from shadow_tpu.obs.profiler import WindowProfiler, host_phase

    if not args.profile:
        return None, host_phase
    prof = WindowProfiler()
    return prof, prof.phase


def _strip_retry_flags(argv: list[str]) -> list[str]:
    """The child relaunch command must not recurse into its own retry
    loop — one supervisor owns the run."""
    out, skip = [], False
    for a in argv:
        if skip:
            skip = False
            continue
        if a in ("--retry", "--retry-backoff"):
            skip = True
            continue
        if a.startswith("--retry=") or a.startswith("--retry-backoff="):
            continue
        out.append(a)
    return out


def _parse_fleet_spec(spec: str, base_seed: int) -> dict:
    """'lanes=L [seed=a:b] [fault-file=PATH] [latency-scale=x,...]' ->
    build_fleet overrides. Raises ValueError with the offending token."""
    kv = {}
    for tok in spec.split():
        k, sep, v = tok.partition("=")
        if not sep:
            raise ValueError(f"expected key=value, got {tok!r}")
        if k in kv:
            raise ValueError(f"duplicate key {k!r}")
        kv[k] = v
    unknown = set(kv) - {"lanes", "seed", "fault-file", "latency-scale"}
    if unknown:
        raise ValueError(
            f"unknown key(s) {sorted(unknown)}; valid keys are lanes, "
            "seed, fault-file, latency-scale"
        )
    if "lanes" not in kv:
        raise ValueError("lanes=L is required")
    lanes = int(kv["lanes"])
    out: dict = {"lanes": lanes}
    if "seed" in kv:
        a, sep, b = kv["seed"].partition(":")
        if not sep:
            raise ValueError(
                f"seed wants a range a:b (one seed per lane), got "
                f"{kv['seed']!r}"
            )
        seeds = tuple(range(int(a), int(b)))
        if len(seeds) != lanes:
            raise ValueError(
                f"seed range {kv['seed']} has {len(seeds)} seeds for "
                f"{lanes} lanes"
            )
        out["seeds"] = seeds
    else:
        out["seeds"] = tuple(base_seed for _ in range(lanes))
    if "fault-file" in kv:
        from shadow_tpu.faults import parse_fault_dsl

        with open(kv["fault-file"]) as f:
            lines = f.read().splitlines()
        lines = [ln for ln in lines if not ln.lstrip().startswith("#")]
        if len(lines) != lanes:
            raise ValueError(
                f"fault-file {kv['fault-file']} has {len(lines)} lane "
                f"lines for {lanes} lanes (blank line = no faults)"
            )
        out["faults"] = tuple(
            tuple(parse_fault_dsl(s) for s in ln.split(";") if s.strip())
            or None
            for ln in lines
        )
    if "latency-scale" in kv:
        scales = tuple(float(s) for s in kv["latency-scale"].split(","))
        if len(scales) != lanes:
            raise ValueError(
                f"latency-scale lists {len(scales)} values for {lanes} "
                "lanes"
            )
        out["latency_scale"] = scales
    return out


def _run_fleet(args, cfg, sim, t0: float) -> int:
    """The --fleet run path: L lanes of one scenario as ONE vmapped
    donating program, driven segment-by-segment through the single-fetch
    harvest with per-lane [fleet] heartbeat rows. Deliberately leaner
    than the solo loop: the per-scenario observability and recovery
    planes (tracker/trace/pcap/metrics/checkpoints) stay solo-only."""
    import math

    from shadow_tpu.runtime.harvest import HeartbeatHarvest
    from shadow_tpu.sim import build_fleet
    from shadow_tpu.utils.tracker import FLEET_HEADER

    if args.window == "auto":
        print("error: --window auto cannot drive a fleet: the adaptive "
              "WindowController is a single host-side policy and cannot "
              "track per-lane queue fill — use a fixed '--window N' "
              "(milliseconds, uniform across lanes) or leave --window "
              "off for bit-identical default windows", file=sys.stderr)
        return 2
    for on, name in (
        (args.mesh, "--mesh"),
        (args.trace, "--trace"),
        (args.stats, "--stats"),
        (args.resume, "--resume"),
        (args.checkpoint_interval, "--checkpoint-interval"),
        (args.metrics, "--metrics"),
        (args.metrics_port is not None, "--metrics-port"),
        (args.xprof, "--xprof"),
        (args.profile, "--profile"),
    ):
        if on:
            print(f"error: {name} is per-scenario and cannot ride a "
                  "fleet run; drop it (or run the lanes solo)",
                  file=sys.stderr)
            return 2
    window_fixed_ns = None
    if args.window is not None:
        try:
            window_fixed_ns = int(float(args.window) * MILLISECOND)
        except ValueError:
            print(f"error: --window must be a width in ms (or absent) "
                  f"under --fleet, got {args.window!r}", file=sys.stderr)
            return 2
        if window_fixed_ns < sim.engine.cfg.lookahead:
            print(f"error: --window {args.window} is narrower than the "
                  f"conservative lookahead ({sim.engine.cfg.lookahead} "
                  "ns); it would only add barriers", file=sys.stderr)
            return 2
    try:
        fspec = _parse_fleet_spec(args.fleet, args.seed)
    except (ValueError, OSError) as e:
        print(f"error: --fleet: {e}", file=sys.stderr)
        return 2
    lanes = fspec.pop("lanes")
    try:
        fleet = build_fleet(sim, lanes, **fspec)
    except ValueError as e:
        print(f"error: --fleet: {e}", file=sys.stderr)
        return 2
    harvest = HeartbeatHarvest(fleet)
    stop_s = cfg.stoptime
    hb = args.heartbeat_frequency
    print(f"shadow_tpu {__version__} fleet: {lanes} lanes x "
          f"{len(sim.names)} hosts, stoptime {stop_s:.0f}s, one vmapped "
          f"program, backend {jax.default_backend()}", file=sys.stderr)
    # heartbeat rows ride stdout like the solo tracker's (ShadowLogger's
    # default stream): `shadow_tpu ... | parse_shadow -` works unchanged
    print(FLEET_HEADER, flush=True)
    t1 = time.perf_counter()
    sim_s = 0.0
    next_hb = hb if hb > 0 else float("inf")
    st = None
    last_events = [0] * lanes
    fetched = None
    while sim_s < stop_s:
        nxt = min(next_hb, stop_s)
        stop_i = int(nxt * SECOND)
        if window_fixed_ns is not None:
            # traced fixed-width windows: one clock probe per window,
            # on the SLOWEST lane (the fleet's segment barrier)
            while True:
                st = fleet.dispatch(stop_i, st, window_ns=window_fixed_ns)
                if int(jax.device_get(st.now.min())) >= stop_i:  # shadowlint: no-deadline=fleet window probe; single-device path has no collectives
                    break
        else:
            st = fleet.dispatch(stop_i, st)
        st, bundle = harvest.extract(st, full=True)
        fetched = harvest.fetch(bundle)
        sim_s = nxt
        next_hb = (math.floor(sim_s / hb) + 1) * hb if hb > 0 else (
            float("inf"))
        rows = harvest.lane_summaries_from(fetched)
        t_s = int(sim_s)
        for i, row in enumerate(rows):
            delta = row["executed"] - last_events[i]
            last_events[i] = row["executed"]
            fill = float(fetched["fill"][i])
            print("[shadow-heartbeat] [fleet] "
                  f"{t_s},{i},{fleet.seeds[i]},"
                  f"{row['now_ns'] // 1_000_000_000},{row['windows']},"
                  f"{row['executed']},{delta},{row['queue_drops']},"
                  f"{fill:.4f}", flush=True)
        agg = harvest.summary_from(fetched)
        fleet.check_drops(agg["queue_drops"], agg)
    wall = time.perf_counter() - t1
    rows = harvest.lane_summaries_from(fetched)
    total_events = sum(r["executed"] for r in rows)
    summary = {
        "fleet_lanes": lanes,
        "hosts": len(sim.names),
        "sim_seconds": stop_s,
        "wall_seconds": round(wall, 3),
        "build_seconds": round(t1 - t0, 3),
        "events": total_events,
        "events_per_sec": round(total_events / max(wall, 1e-9), 1),
        "scenarios_per_sec": round(lanes / max(wall, 1e-9), 3),
        "sim_s_per_wall_s": round(stop_s / max(wall, 1e-9), 3),
        "windows": max(r["windows"] for r in rows),
        "queue_drops": sum(r["queue_drops"] for r in rows),
        "seeds": list(fleet.seeds),
        "lanes": rows,
    }
    print(json.dumps(summary), flush=True)
    return 0


def _run_serve(args) -> int:
    """`shadow_tpu serve`: the resident scenario service
    (docs/17-Serving.md). The main thread owns the signal plane; the
    launch worker and the HTTP handler threads do the work. SIGTERM /
    SIGINT trigger the graceful drain — finish the launch in flight,
    persist the pending queue to --queue-file, exit 0. SIGHUP is the
    operator mesh resize: it reads the new lane count from
    `<snapshot-path>.resize` and migrates the in-flight batch at the
    next beat boundary (docs/17-Serving.md "Elasticity")."""
    import signal as _signal

    from shadow_tpu.runtime.supervisor import Supervisor
    from shadow_tpu.serve.http import ServeServer
    from shadow_tpu.serve.service import SimService

    # a relaunch under `--retry` (the elastic outer loop) seeds the mesh
    # generation, so /healthz reports the churn from the first beat
    _attempt = os.environ.get("SHADOW_TPU_RETRY_ATTEMPT")
    generation = int(_attempt) if _attempt and _attempt.isdigit() else 0

    tracer = None
    if args.trace_requests > 0 or args.ledger_file:
        from shadow_tpu.obs.servetrace import ServeTracer

        tracer = ServeTracer(
            max_requests=args.trace_requests or 4096,
            ledger_file=args.ledger_file,
            ledger_meta={"max_lanes": args.max_lanes,
                         "beat_windows": args.beat_windows},
        )
    svc = SimService(
        max_lanes=args.max_lanes,
        pack_deadline_ms=args.pack_deadline_ms,
        max_cached_programs=args.max_cached_programs,
        beat_windows=args.beat_windows,
        queue_file=args.queue_file,
        snapshot_beats=args.snapshot_beats,
        snapshot_path=args.snapshot_path,
        launch_retries=args.launch_retries,
        launch_deadline_s=args.launch_deadline_s,
        result_ttl_s=args.result_ttl_s,
        max_results=args.max_results,
        degraded_after=args.degraded_after,
        diag_dir=args.diag_dir,
        tracer=tracer,
        generation=generation,
    )

    def _on_sighup(_signum, _frame):
        ctl = (args.snapshot_path or "shadow_tpu.serve") + ".resize"
        try:
            with open(ctl) as f:
                lanes = int(f.read().strip())
            os.remove(ctl)
        except (OSError, ValueError) as e:
            print(f"serve: SIGHUP resize ignored — no usable lane "
                  f"count in {ctl!r} ({type(e).__name__}: {e})",
                  file=sys.stderr, flush=True)
            return
        print(f"serve: SIGHUP resize -> {lanes} lane(s)",
              file=sys.stderr, flush=True)
        try:
            svc.resize(lanes)
        except ValueError as e:
            print(f"serve: SIGHUP resize rejected: {e}",
                  file=sys.stderr, flush=True)

    _signal.signal(_signal.SIGHUP, _on_sighup)
    with Supervisor(label="shadow_tpu-serve") as sup:
        # resume BEFORE reloading the drained queue: the crashed batch
        # must reach the worker ahead of any re-packed queue traffic,
        # or a completing queue batch would clear its snapshot
        svc.resume_pending_batch()
        restored = svc.load_queue()
        if restored:
            print(f"serve: restored {restored} pending request(s) from "
                  f"{args.queue_file}", file=sys.stderr, flush=True)
        svc.start()
        srv = ServeServer(svc, port=args.port).start()
        try:
            while not sup.stop_requested:
                time.sleep(0.2)
        finally:
            srv.close()
            report = svc.drain()
            print(f"serve: drained — {report['persisted']} pending "
                  f"request(s) persisted to {report['queue_file']}",
                  file=sys.stderr, flush=True)
            if tracer is not None:
                tracer.close()
            sup.mark_drained()
    return sup.exit_code()


def main(argv=None) -> int:
    args = make_parser().parse_args(argv)
    enable_compile_cache()  # run and serve alike
    if args.show_build_info:
        print(f"shadow_tpu {__version__} (jax {jax.__version__}, "
              f"backend {jax.default_backend()})")
        return 0
    if args.retry > 0:
        # elastic outer loop (docs/13-Elastic-Recovery.md): run the real
        # driver as a child in its own process group; on stall (75),
        # peer-lost (77), or a signal death, reap the child's whole
        # group, back off exponentially, and relaunch with --resume auto
        # — on a halved --mesh after a lost peer. A `serve` child is
        # elastic through its own flags instead: no --resume, a halved
        # --max-lanes on peer-lost, and --snapshot-path/--queue-file
        # ride along so resume_pending_batch migrates the batch
        from shadow_tpu.runtime import run_with_retry

        child = [sys.executable, "-m", "shadow_tpu"] + _strip_retry_flags(
            list(argv) if argv is not None else sys.argv[1:])
        report = run_with_retry(child, retries=args.retry,
                                backoff_s=args.retry_backoff)
        print("shadow_tpu: retry report "
              + json.dumps({k: report[k] for k in
                            ("attempts", "recoveries", "exit_code",
                             "exit_history", "mttr_s")}),
              file=sys.stderr, flush=True)
        return int(report["exit_code"])
    if args.workers is not None or args.scheduler_policy is not None:
        print("note: --workers/--scheduler-policy are pthread-era flags; "
              "parallelism is the device mesh here", file=sys.stderr)

    if args.config == "serve":
        # resident scenario service — no config file; scenarios arrive
        # as requests over the HTTP plane (docs/17-Serving.md)
        return _run_serve(args)
    if args.test:
        cfg = parse_config(example_config())
    elif args.config:
        cfg = parse_config(args.config)
    else:
        print("error: a config file (or --test) is required", file=sys.stderr)
        return 2
    if args.stoptime is not None:
        cfg = dataclasses.replace(cfg, stoptime=args.stoptime)
    if args.bootstrap_end is not None:
        cfg = dataclasses.replace(cfg, bootstraptime=args.bootstrap_end)
    if args.fault:
        # CLI faults append to the config's schedule BEFORE the config
        # digest below: a fault schedule changes every event total, so a
        # checkpoint must be tied to it like any other build input
        from shadow_tpu.faults import parse_fault_dsl

        cfg = dataclasses.replace(
            cfg,
            faults=cfg.faults + tuple(
                parse_fault_dsl(s) for s in args.fault
            ),
        )

    # overflow-mode resolution: lossless spill is the default, but the
    # sharded engine doesn't speak the reservoir's boundary protocol yet,
    # so meshes quietly keep the historical counted-drop behavior unless
    # the user explicitly asks for a lossless mode (then we fail loudly
    # in build_simulation rather than silently losing events)
    overflow = args.overflow
    if args.allow_queue_overflow:
        if overflow not in (None, "drop"):
            print("error: --allow-queue-overflow conflicts with "
                  f"--overflow {overflow}", file=sys.stderr)
            return 2
        overflow = "drop"
    if overflow is None:
        overflow = "drop" if args.mesh else "spill"

    # configs whose plugins are real shared objects run on the process
    # tier: native green threads + window-batched syscall exchange (the
    # reference's plugin execution path, process.c)
    import os

    def _is_shim_plugin(p) -> bool:
        from shadow_tpu.config import resolve_path

        path = resolve_path(p.path, cfg.base_dir)
        return path.endswith(".so") and os.path.exists(path)

    if any(_is_shim_plugin(p) for p in cfg.plugins):
        from shadow_tpu.proc import ProcessTier

        if not all(_is_shim_plugin(p) for p in cfg.plugins):
            print(
                "error: configs cannot mix native .so plugins with modeled "
                "plugins yet; make every plugin a .so or none",
                file=sys.stderr,
            )
            return 2
        if args.metrics or args.metrics_port is not None or args.xprof:
            print("note: --metrics/--metrics-port/--xprof are device-tier "
                  "flags (they ride the heartbeat harvest); the process "
                  "tier ignores them", file=sys.stderr)
        unsupported = []
        if args.resume:
            unsupported.append("--resume")
        if args.checkpoint_interval:
            unsupported.append("--checkpoint-interval")
        if unsupported:
            print(
                "error: the process tier (native .so plugins) does not "
                f"support {', '.join(unsupported)} yet; native endpoint "
                "streams are not captured in device checkpoints",
                file=sys.stderr,
            )
            return 2

        from shadow_tpu.runtime import Supervisor

        t0 = time.perf_counter()
        tier_mesh = None
        if args.mesh:
            from shadow_tpu.parallel.mesh import make_mesh

            tier_mesh = make_mesh(args.mesh, dcn_slices=args.dcn_slices)
        prof, _phase = _make_profiler(args)
        with _phase("build"):
            tier = ProcessTier(
                cfg, seed=args.seed, n_sockets=args.sockets,
                capacity=args.capacity,
                strict_overflow=not args.allow_queue_overflow,
                tcp_cc=args.tcp_congestion_control,
                rx_queue=args.router_queue, qdisc=args.interface_qdisc,
                interface_buffer=args.interface_buffer, mesh=tier_mesh,
                locality=args.locality, trace=args.trace, profiler=prof,
                overflow=overflow,
            )
        sup = Supervisor(
            watchdog_timeout=args.watchdog, diag_dir=args.diag_dir,
            label="shadow_tpu.proc",
            info=lambda: {
                "tier": "process",
                "live_pids": tier.live_pids(),
                "exit_codes": {str(k): v for k, v in tier.exit_codes.items()},
            },
        )
        from shadow_tpu.runtime import EXIT_PRESSURE
        from shadow_tpu.runtime.pressure import (
            QueuePressureError, pressure_bundle,
        )

        try:
            with sup:
                st = tier.run(supervisor=sup)
            wall = time.perf_counter() - t0
        except QueuePressureError as e:
            path = pressure_bundle(e, diag_dir=args.diag_dir,
                                   label="shadow_tpu.proc")
            print(f"shadow_tpu: QUEUE PRESSURE under --overflow strict: "
                  f"{e}\ndiagnostic bundle -> {path}", file=sys.stderr)
            return EXIT_PRESSURE
        finally:
            # abnormal exits (stall abort is os._exit and skips this, but
            # signals/exceptions land here) still surface the plugin log
            # lines collected so far and close the shim runtime
            for t_ns, pid, msg in tier.logs:
                print(f"[{t_ns / SECOND:.6f}] [pid {pid}] {msg}")
            tier.close()
        summary = {
            "hosts": len(tier.sim.names),
            "sim_seconds": cfg.stoptime,
            "wall_seconds": round(wall, 3),
            "processes": len(tier.pid_host),
            "exit_codes": tier.exit_codes,
            "rx_bytes": int(jax.device_get(  # shadowlint: no-deadline=post-run proc-tier summary; the pump already drained
                st.hosts.net.sockets.rx_bytes.sum()
            )),
            "queue_drops": int(jax.device_get(st.queues.drops.sum())),  # shadowlint: no-deadline=post-run proc-tier summary; the pump already drained
        }
        if args.trace and st.trace is not None:
            from shadow_tpu.obs import TraceDrain

            tdrain = TraceDrain(
                args.trace, names=tier.sim.names,
                kind_names=list(tier.sim.kind_names),
            )
            tdrain.drain(st.trace)
            tdrain.save(
                args.trace_out,
                profile=prof.export() if prof is not None else None,
                extra_meta={"seed": args.seed, "tier": "process"},
            )
            summary["trace"] = {
                "records": tdrain.n_records, "lost": tdrain.lost,
                "truncated": tdrain.truncated, "file": args.trace_out,
            }
            print(f"event trace: {tdrain.n_records} records -> "
                  f"{args.trace_out}", file=sys.stderr)
        if prof is not None:
            summary["profile"] = prof.summary()
        print(json.dumps(summary))
        if sup.stop_requested:
            print(f"interrupted by signal {sup.stop_signum}; the process "
                  "tier has no checkpoint to write", file=sys.stderr)
            return sup.exit_code()
        return 0 if all(c == 0 for c in tier.exit_codes.values()) else 1

    # --xprof parse before the expensive build: a malformed span should
    # fail in milliseconds, not after compilation
    xprof_span = None
    if args.xprof:
        try:
            a, sep, b = args.xprof.partition(":")
            if not sep:
                raise ValueError("missing ':'")
            xprof_span = (float(a), float(b))
        except ValueError:
            print(f"error: --xprof must be START:STOP in sim seconds, "
                  f"got {args.xprof!r}", file=sys.stderr)
            return 2
        if xprof_span[0] < 0 or xprof_span[1] <= xprof_span[0]:
            print(f"error: --xprof needs 0 <= START < STOP, got "
                  f"{args.xprof!r}", file=sys.stderr)
            return 2
    xprof_active = False
    xprof_done = False

    t0 = time.perf_counter()
    mesh = None
    if args.dcn_slices > 1 and not args.mesh:
        print("error: --dcn-slices needs --mesh N (total devices across "
              "all slices)", file=sys.stderr)
        return 2
    if args.mesh:
        from shadow_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(args.mesh, dcn_slices=args.dcn_slices)
    prof, _phase = _make_profiler(args)

    # -- resolve the resume source BEFORE building: a v6 checkpoint
    # records the host permutation it was written under, and the rebuild
    # must force that exact layout — recomputing locality_order against
    # a different shard count would scramble gids relative to the
    # checkpoint's leaves (docs/13-Elastic-Recovery.md)
    resume_src = None  # a path, or a list of shard-set member paths
    ckpt_info: dict = {}
    if args.resume:
        from shadow_tpu.utils import find_resume_checkpoint
        from shadow_tpu.utils.checkpoint import read_header_info

        resume_src = args.resume
        if resume_src in ("auto", "auto-if-any"):
            try:
                found = find_resume_checkpoint(args.checkpoint_path)
            except ValueError as e:
                print(f"error: --resume auto: {e}", file=sys.stderr)
                return 2
            if found is None:
                if resume_src == "auto-if-any":
                    # the --retry relaunch path: a worker that died
                    # before its first checkpoint restarts from zero
                    print("shadow_tpu: --resume auto-if-any: no "
                          "checkpoint yet; starting fresh",
                          file=sys.stderr)
                    found = (None, {}, [])
                else:
                    print("error: --resume auto: no checkpoint "
                          f"generations at {args.checkpoint_path}",
                          file=sys.stderr)
                    return 2
            resume_src, _auto_meta, skipped = found
            for p, reason in skipped:
                print(f"warning: --resume auto: skipping {p}: {reason}",
                      file=sys.stderr)
        if resume_src is None:
            ckpt_info = {}
        else:
            try:
                ckpt_info = read_header_info(
                    resume_src
                    if isinstance(resume_src, str) else resume_src[0]
                )
            except ValueError as e:
                print(f"error: --resume: {e}", file=sys.stderr)
                return 2
            ckpt_mesh = ckpt_info.get("mesh") or {}
            if ckpt_mesh.get("n_shards") not in (None, args.mesh or 1):
                print(f"shadow_tpu: resharding: checkpoint written at "
                      f"{ckpt_mesh['n_shards']} shard(s), resuming at "
                      f"{args.mesh or 1}", file=sys.stderr)
    resume_host_order = (ckpt_info.get("mesh") or {}).get("host_order")

    def _build(capacity):
        # one closure for the initial build AND the --overflow grow
        # re-template (doubled capacity, everything else identical)
        return build_simulation(
            cfg, seed=args.seed, n_sockets=args.sockets,
            capacity=capacity,
            mesh=mesh, tcp_cc=args.tcp_congestion_control,
            rx_queue=args.router_queue, qdisc=args.interface_qdisc,
            interface_buffer=args.interface_buffer, locality=args.locality,
            runahead_ns=(
                int(args.runahead * MILLISECOND)
                if args.runahead is not None else None
            ),
            trace=args.trace, stats=int(args.stats), profiler=prof,
            overflow=overflow,
            host_order=resume_host_order,
        )

    with _phase("build"):
        sim = _build(args.capacity)
    if args.allow_queue_overflow:
        sim.strict_overflow = False
    if args.fleet:
        return _run_fleet(args, cfg, sim, t0)
    tdrain = None
    if args.trace:
        from shadow_tpu.obs import TraceDrain

        tdrain = TraceDrain(
            args.trace, names=sim.names, kind_names=list(sim.kind_names)
        )
        if sim.pressure is not None:
            # spill/refill are host-side moments: the controller injects
            # synthetic OP_SPILL/OP_REFILL rows into the same drain
            sim.pressure.attach_trace(
                tdrain, len_arg=sim.engine.cfg.trace_len_arg
            )
        print(f"event trace: {args.trace} records/host/interval -> "
              f"{args.trace_out}", file=sys.stderr)
    n_hosts = len(sim.names)
    print(f"shadow_tpu {__version__}: {n_hosts} hosts, "
          f"{sim.topo.n_vertices} topology vertices, "
          f"stoptime {cfg.stoptime:.0f}s, backend {jax.default_backend()}"
          + (f", mesh {args.mesh}" if args.mesh else ""),
          file=sys.stderr)

    # digest ties a checkpoint to the exact build inputs: resuming under a
    # different config or seed would pass structural checks yet silently
    # break the bit-exact-resume guarantee. Hash *content*, not paths:
    # topology via its resolved source text, config minus base_dir — so
    # moving an identical config+checkpoint elsewhere still resumes, while
    # editing the referenced GraphML is caught
    import hashlib

    cfg_digest = hashlib.sha256(
        repr(
            (
                # stoptime excluded: resuming toward a later stop is the
                # normal use; it never affects per-event determinism
                dataclasses.replace(cfg, base_dir="", stoptime=0.0),
                cfg.topology_source(),
                args.seed,
                args.sockets,
                args.capacity,
                args.tcp_congestion_control,
                args.interface_qdisc,
                args.interface_buffer,
                args.router_queue,
            )
        ).encode()
    ).hexdigest()[:16]

    st = sim.state0
    sim_s = 0.0
    if args.resume and resume_src is not None:
        from shadow_tpu.utils import load_checkpoint, load_shard_set

        if isinstance(resume_src, list):
            try:
                st, meta = load_shard_set(resume_src, sim.state0)
            except ValueError as e:
                print(f"error: --resume: {e}", file=sys.stderr)
                return 2
            resume_name = f"{len(resume_src)}-member shard set"
            extras: dict = {}
        else:
            try:
                # reshard=True: leaves are matched by path, so a
                # checkpoint written at S shards restores onto this
                # build's S' — the exchange buffer (the only mesh-shaped
                # state) was verified empty or the load refuses
                st, meta = load_checkpoint(resume_src, sim.state0,
                                           reshard=True)
            except ValueError as e:
                print(f"error: --resume: {e}", file=sys.stderr)
                return 2
            resume_name = resume_src
            from shadow_tpu.utils.checkpoint import read_extra

            extras = read_extra(resume_src)
        parked = int(np.size(extras.get("reservoir_time", ())))
        if sim.pressure is not None:
            # mid-pressure resume: the reservoir rides the checkpoint's
            # extra section; restoring it keeps --resume bit-exact even
            # with events parked off-device at the write
            if extras:
                sim.pressure.restore(extras)
        elif parked:
            # no controller to re-seat the parked events — dropping them
            # silently would break the lossless contract. The sharded
            # build refuses spill/grow, so this also catches resuming a
            # mid-pressure checkpoint onto a mesh.
            print(f"error: checkpoint holds {parked} events parked in the "
                  "pressure reservoir but this run has no controller to "
                  "re-seat them; resume unsharded with --overflow spill "
                  "(or grow), reach a pressure-free window boundary, then "
                  "reshard", file=sys.stderr)
            return 2
        if meta.get("seed") is not None and meta["seed"] != args.seed:
            print(f"error: checkpoint was written with --seed {meta['seed']}"
                  f" but this run uses --seed {args.seed}; resume would not "
                  "be bit-exact", file=sys.stderr)
            return 2
        if meta.get("config_digest") not in (None, cfg_digest):
            print("error: checkpoint config digest "
                  f"{meta['config_digest']} != this build's {cfg_digest}; "
                  "it was written from a different config", file=sys.stderr)
            return 2
        sim_s = float(jax.device_get(st.now)) / SECOND  # shadowlint: no-deadline=one-shot resume fetch before the loop starts
        print(f"resumed from {resume_name} at sim time {sim_s:.3f}s "
              f"(meta: {meta})", file=sys.stderr)
    stop_s = cfg.stoptime
    # independent sim-time cadences; the run loop steps to whichever event
    # (heartbeat print, checkpoint write, stoptime) comes next. Cadences
    # are absolute interval multiples, so an interrupted+resumed run emits
    # heartbeats/checkpoints at the same sim times as an uninterrupted one
    import math

    hb = args.heartbeat_frequency
    ck = args.checkpoint_interval
    next_hb = (math.floor(sim_s / hb) + 1) * hb if hb > 0 else float("inf")
    next_ckpt = (math.floor(sim_s / ck) + 1) * ck if ck > 0 else float("inf")

    # -- live telemetry plane (docs/14-Telemetry.md): flight recorder
    # (always on — it's two bounded deques, and abnormal exits ship it),
    # /healthz state machine, and — under --metrics — the registry the
    # harvest bundle populates and the tracker's [metrics] row reads
    from shadow_tpu.obs.metrics import (
        FlightRecorder, HealthState, MetricsRegistry,
    )

    metrics_on = args.metrics or args.metrics_port is not None
    recorder = FlightRecorder()
    health = HealthState()
    _retry_attempt = os.environ.get("SHADOW_TPU_RETRY_ATTEMPT")
    if _retry_attempt:
        # run_with_retry marks relaunched children; a run that needed a
        # relaunch reports degraded even though it is making progress
        health.relaunch(int(_retry_attempt))
    registry = None
    if metrics_on:
        registry = MetricsRegistry(version=__version__,
                                   n_shards=args.mesh or 1)
    server = None
    if args.metrics_port is not None:
        from shadow_tpu.obs.server import MetricsServer

        try:
            server = MetricsServer(registry, health, recorder,
                                   port=args.metrics_port).start()
        except OSError as e:
            print(f"error: --metrics-port {args.metrics_port}: {e}",
                  file=sys.stderr)
            return 2

    def _close_metrics():
        # SHADOW_TPU_METRICS_LINGER_S keeps the endpoints up briefly
        # after the summary line so a scraping harness can take its
        # final reconciliation scrape
        if server is None:
            return
        linger_s = float(
            os.environ.get("SHADOW_TPU_METRICS_LINGER_S") or 0)
        if linger_s > 0:
            time.sleep(linger_s)
        server.close()

    logger, tracker = _make_observability(cfg, sim, args, trace=tdrain,
                                          metrics=registry)
    drain = None
    if sim.pcap_gids:
        from shadow_tpu.utils.pcap import CaptureDrain

        drain = CaptureDrain(
            [sim.names[g] for g in sim.pcap_gids], sim.pcap_gids,
            sim.pcap_dir, dns=sim.dns,
        )
        print(f"pcap capture: {len(sim.pcap_gids)} hosts -> {sim.pcap_dir}/",
              file=sys.stderr)
    from shadow_tpu.runtime import EXIT_INVARIANT, EXIT_PRESSURE, Supervisor
    from shadow_tpu.runtime.invariants import InvariantViolation, validate
    from shadow_tpu.runtime.pressure import (
        QueuePressureError, pressure_bundle,
    )
    from shadow_tpu.utils import save_checkpoint
    from shadow_tpu.utils.tracker import SupervisorHeartbeat

    sup = Supervisor(
        watchdog_timeout=args.watchdog, diag_dir=args.diag_dir,
        info=lambda: {"tier": "device",
                      "checkpoint_path": args.checkpoint_path,
                      "config_digest": cfg_digest,
                      "flight_recorder": recorder.snapshot()},
    )
    sup_hb = SupervisorHeartbeat(logger, watchdog=sup.watchdog)

    # --collective-timeout: the second deadline (exit 77, not 75) over
    # the two sites a dead mesh peer wedges forever — the sharded step's
    # collectives and the harvest device_get. Its bundle carries the
    # per-shard map so the post-mortem can name which shard went dark.
    cwd = None
    last_summary: dict = {}
    if args.collective_timeout > 0:
        from shadow_tpu.runtime import EXIT_PEER_LOST, Watchdog

        _n_shards = int(mesh.devices.size) if mesh is not None else 1
        _per = n_hosts // _n_shards

        def _peer_info():
            return {
                "tier": "device",
                "mesh_shards": _n_shards,
                "dcn_slices": args.dcn_slices,
                "per_shard_hosts": _per,
                "shards": [
                    {"shard": s, "hosts": [s * _per, (s + 1) * _per],
                     "device": str(d)}
                    for s, d in enumerate(
                        mesh.devices.flat if mesh is not None
                        else jax.devices()[:1])
                ],
                "checkpoint_path": args.checkpoint_path,
                "config_digest": cfg_digest,
                "last_summary": dict(last_summary),
                "flight_recorder": recorder.snapshot(),
            }

        cwd = Watchdog(
            args.collective_timeout, diag_dir=args.diag_dir,
            label="shadow_tpu", kind="peerlost",
            exit_code=EXIT_PEER_LOST, info=_peer_info,
            compile_grace=True,
        )

    # chaos-harness stall injector (tests/test_elastic.py): wedge
    # the next harvest fetch for N seconds, exactly what a lost peer's
    # never-completing collective looks like from this process. A marker
    # file next to the checkpoint makes the injection one-shot across
    # --retry relaunches (children inherit the env var), so a wrapped
    # run fails once, then recovers clean.
    _chaos_hang_s = float(os.environ.get("SHADOW_TPU_CHAOS_HANG_S") or 0)
    if _chaos_hang_s > 0:
        _chaos_marker = args.checkpoint_path + ".chaos"
        try:
            os.close(os.open(
                _chaos_marker, os.O_CREAT | os.O_EXCL | os.O_WRONLY
            ))
        except FileExistsError:
            _chaos_hang_s = 0.0

    # --window: traced-scalar window widths (fixed N ms or adaptive)
    wctl = None
    window_fixed_ns = None
    if args.window is not None:
        if sim.pressure is not None:
            print("error: --window needs --overflow drop or strict (the "
                  "spill reservoir's boundary harvest steps the static "
                  "window)", file=sys.stderr)
            return 2
        if args.window == "auto":
            from shadow_tpu.runtime.adaptive import WindowController

            wctl = WindowController(
                sim.engine.cfg.lookahead, n_hosts=len(sim.names)
            )
        else:
            try:
                window_fixed_ns = int(float(args.window) * MILLISECOND)
            except ValueError:
                print(f"error: --window must be a width in ms or 'auto', "
                      f"got {args.window!r}", file=sys.stderr)
                return 2
            if window_fixed_ns < sim.engine.cfg.lookahead:
                print(f"error: --window {args.window} is narrower than "
                      f"the conservative lookahead "
                      f"({sim.engine.cfg.lookahead} ns); it would only "
                      "add barriers", file=sys.stderr)
                return 2

    # single-sync heartbeat harvest + depth-1 dispatch-ahead: every
    # segment boundary costs ONE batched device_get, and the previous
    # heartbeat's host-side formatting runs while the device computes
    # the next segment (docs/11-Performance.md)
    from shadow_tpu.runtime.harvest import HeartbeatHarvest

    harvest = HeartbeatHarvest(sim, tracker=tracker, tdrain=tdrain,
                               pcap=drain, metrics=registry)
    pending_hb = None  # (fetched bundle, sim_ns, summary) to consume

    def consume_hb():
        # host-side half of a heartbeat, deferred so it overlaps the
        # next dispatched segment
        nonlocal pending_hb
        if pending_hb is None:
            return
        fetched, hb_ns, hb_summary = pending_hb
        pending_hb = None
        with _phase("drain"):
            harvest.consume(fetched, hb_ns)
            sup_hb.beat(hb_ns, hb_summary)
            logger.flush()

    def write_checkpoint(path=None, **extra_meta):
        # emergency checkpoints go to an explicit side path, NOT into
        # the rotation: a crashing run must never push the last known
        # good generation off the retention horizon
        with _phase("checkpoint"):
            save_checkpoint(
                path or args.checkpoint_path, st,
                meta={"sim_seconds": sim_s, "seed": args.seed,
                      "config_digest": cfg_digest, **extra_meta},
                keep=1 if path else args.checkpoint_keep,
                extra=(sim.pressure.serialize()
                       if sim.pressure is not None else None),
                # v6 mesh identity: what a reshard-resume needs to force
                # this build's host layout onto a different shard count
                mesh_info={
                    "n_shards": (int(sim.mesh.devices.size)
                                 if sim.mesh is not None else 1),
                    "dcn_slices": (
                        int(sim.mesh.devices.shape[0])
                        if sim.mesh is not None
                        and sim.mesh.devices.ndim == 2 else 1),
                    "host_order": (list(sim.host_order)
                                   if sim.host_order is not None else None),
                },
            )
        sup_hb.checkpoint_written()
        recorder.record_event("checkpoint", sim_seconds=sim_s,
                              path=path or args.checkpoint_path)
        if cwd is not None and cwd_armed:
            # checkpoint IO is a legitimate pause; don't let it eat the
            # next window's collective deadline
            cwd.pet(site="checkpoint")

    last_validated_windows = 0
    prev_validated_now = None
    prev_validated_drops = None
    # the collective watchdog arms only after the FIRST window
    # completes: that window's fetch blocks on JIT lowering and
    # compile, whose wall time is unbounded and says nothing about
    # peer health (the coarse --watchdog covers a wedged compile);
    # every later window is pure execution, where a missed deadline
    # really does mean a lost peer
    cwd_armed = False
    t1 = time.perf_counter()
    try:
        with sup:
            while sim_s < stop_s:
                if xprof_span is not None and not xprof_done:
                    # span edges are segment boundaries (joined into
                    # `nxt` below), so start/stop bracket whole window
                    # segments; both edges pet the collective watchdog —
                    # profiler IO is a legitimate pause, not a lost peer
                    if xprof_active and sim_s >= xprof_span[1]:
                        jax.profiler.stop_trace()
                        xprof_active, xprof_done = False, True
                        recorder.record_event("xprof-stop",
                                              sim_seconds=sim_s)
                        print(f"xprof: capture stopped at sim "
                              f"{sim_s:.3f}s -> {args.xprof_dir}",
                              file=sys.stderr)
                        if cwd is not None and cwd_armed:
                            cwd.pet(site="xprof-stop")
                    elif not xprof_active and sim_s >= xprof_span[0]:
                        jax.profiler.start_trace(args.xprof_dir)
                        xprof_active = True
                        recorder.record_event("xprof-start",
                                              sim_seconds=sim_s,
                                              dir=args.xprof_dir)
                        print(f"xprof: capturing device trace from sim "
                              f"{sim_s:.3f}s -> {args.xprof_dir}",
                              file=sys.stderr)
                        if cwd is not None and cwd_armed:
                            cwd.pet(site="xprof-start")
                nxt = min(next_hb, next_ckpt, stop_s)
                if xprof_span is not None and not xprof_done:
                    edge = (xprof_span[1] if xprof_active
                            else xprof_span[0])
                    if edge > sim_s:
                        nxt = min(nxt, edge)
                stop_i = int(nxt * SECOND)
                full_hb = nxt >= next_hb
                if cwd is not None and cwd_armed:
                    cwd.pet(site="dispatch", sim_seconds=sim_s)
                # -- advance to `nxt`: async dispatch on the overlap
                # path (the fetch below is the segment's only sync);
                # pressure modes keep run()'s synchronous window loop
                # (host-side reservoir refills at every boundary)
                if sim.pressure is not None:
                    st = sim.run(stop_i, state=st)
                elif wctl is not None or window_fixed_ns is not None:
                    # traced-bound windows, one probe per window; in
                    # auto mode the probe also feeds the controller
                    while True:
                        w = (wctl.window_ns if wctl is not None
                             else window_fixed_ns)
                        with _phase("step"):
                            st = sim.dispatch(stop_i, st, window_ns=w)
                        if wctl is not None:
                            from shadow_tpu.core.timebase import (
                                TIME_INVALID,
                            )

                            now_a, ex_a, dr_a, fill_a = jax.device_get((  # shadowlint: no-deadline=window probe; the collective watchdog is petted right after
                                st.now, st.stats.n_executed.sum(),
                                st.queues.drops.sum(),
                                jnp.mean(
                                    (st.queues.time != TIME_INVALID)
                                    .astype(jnp.float32)
                                ),
                            ))
                            wctl.update(int(ex_a), int(dr_a),
                                        float(fill_a))
                            now_i = int(now_a)
                        else:
                            now_i = int(jax.device_get(st.now))  # shadowlint: no-deadline=window probe; the collective watchdog is petted right after
                        if cwd is not None and cwd_armed:
                            # each probe is a completed blocking site;
                            # re-arm the collective deadline per window
                            cwd.pet(site="window-probe", now_ns=now_i)
                        if now_i >= stop_i:
                            break
                else:
                    st = sim.dispatch(stop_i, st)
                # queue the harvest extraction behind the segment, then
                # consume the PREVIOUS heartbeat's fetched bundle while
                # the device works (the dispatch-ahead overlap)
                st, bundle = harvest.extract(st, full=full_hb)
                consume_hb()
                if _chaos_hang_s > 0 and (cwd is None or cwd_armed):
                    # fire only once the collective deadline is armed
                    # (never during the first, compiling window)
                    _hang, _chaos_hang_s = _chaos_hang_s, 0.0
                    print(f"shadow_tpu: CHAOS: wedging the harvest fetch "
                          f"for {_hang:.1f}s", file=sys.stderr, flush=True)
                    time.sleep(_hang)
                with _phase("step"):
                    fetched = harvest.fetch(bundle)
                if cwd is not None:
                    if cwd_armed:
                        cwd.pet(site="harvest.fetch", sim_seconds=nxt)
                    else:
                        cwd.start()
                        cwd_armed = True
                sim_s = nxt
                if sim.pressure is not None and sim.pressure.grow_wanted:
                    # --overflow grow: rebuild the engine at doubled
                    # capacity, carry the live state across through the
                    # checkpoint transfer path, keep the SAME controller
                    # (reservoir + counters survive; the tracker holds a
                    # reference to it), then refill into the new room
                    from shadow_tpu.utils.checkpoint import transfer_state

                    ctrl = sim.pressure
                    new_cap = sim.engine.cfg.capacity * 2
                    print(f"shadow_tpu: queue pressure under --overflow "
                          f"grow: re-templating at --capacity {new_cap} "
                          f"(sim {sim_s:.3f}s)", file=sys.stderr)
                    with _phase("build"):
                        sim = _build(new_cap)
                    st = transfer_state(st, sim.state0)
                    ctrl.capacity = new_cap
                    ctrl.grow_wanted = False
                    sim.pressure = ctrl
                    st = ctrl.boundary(st)
                    # the harvest's jits close over the old engine;
                    # rebind and take the summary synchronously from
                    # the re-templated state
                    harvest.rebind(sim)
                    summary_now = sim.summary(st)
                    recorder.record_event("grow-retemplate",
                                          sim_seconds=sim_s,
                                          capacity=new_cap)
                    # the rebuilt harvest hasn't extracted yet at this
                    # boundary; take the telemetry extras in a one-off
                    # fetch from the re-templated state
                    metrics_extras = (
                        jax.device_get(sim.metrics_refs(st))  # shadowlint: no-deadline=one-shot grow re-template fetch; the next segment's harvest resumes the overlap
                        if metrics_on else None
                    )
                else:
                    summary_now = harvest.summary_from(fetched)
                    metrics_extras = fetched.get("metrics")
                    if sim.pressure is None:
                        # run()'s loud-overflow probe, from the already-
                        # fetched bundle (spill/grow never count drops)
                        sim.check_drops(summary_now["queue_drops"],
                                        summary_now)
                # the stall margin BEFORE the pet resets the deadline —
                # this is how close the segment came to exit 75
                stall_margin = (sup.watchdog.margin_s()
                                if sup.watchdog is not None else None)
                sup.pet(sim_seconds=sim_s, **summary_now)
                last_summary.update(summary_now, sim_seconds=sim_s)
                sup_hb.observe_margin()
                recorder.record_heartbeat(int(sim_s * SECOND),
                                          summary_now)
                if stall_margin is not None and health.observe_margin(
                        stall_margin, args.watchdog):
                    recorder.record_event(
                        "watchdog-near-miss", sim_seconds=sim_s,
                        margin_s=round(stall_margin, 3))
                if health.code() == 0 and (
                        summary_now.get("spilled", 0)
                        or summary_now.get("queue_drops", 0)):
                    health.pressure_event()
                    recorder.record_event(
                        "pressure", sim_seconds=sim_s,
                        spilled=int(summary_now.get("spilled", 0)),
                        queue_drops=int(
                            summary_now.get("queue_drops", 0)))
                if metrics_on:
                    registry.ingest(summary_now, extras=metrics_extras,
                                    fill=float(fetched["fill"]))
                    if "stats" in fetched:
                        registry.ingest_stats(fetched["stats"])
                    registry.observe(
                        watchdog_margin_s=stall_margin,
                        checkpoints=sup_hb.checkpoints_written,
                        health=health, profiler=prof)
                if args.validate > 0 and (
                    summary_now["windows"] - last_validated_windows
                    >= args.validate
                ):
                    prev_validated_now = validate(
                        st, prev_now=prev_validated_now,
                        prev_drops=prev_validated_drops,
                        pressure=sim.pressure,
                    )
                    prev_validated_drops = jax.device_get(st.queues.drops)  # shadowlint: no-deadline=validator fetch between pets on the supervised loop
                    last_validated_windows = summary_now["windows"]
                if prof is not None:
                    prof.observe(
                        summary_now, queue_fill=float(fetched["fill"]),
                        stall_margin_s=(
                            sup.watchdog.margin_s()
                            if sup.watchdog is not None else None
                        ),
                    )
                if full_hb:
                    # defer the host-side half (trace/pcap decode, the
                    # tracker's section formatting) to overlap the next
                    # dispatched segment; the extraction jit already
                    # reset the trace ring on device
                    pending_hb = (fetched, int(sim_s * SECOND),
                                  summary_now)
                    next_hb += hb
                if sup.take_checkpoint_request():  # SIGUSR1
                    write_checkpoint(on_demand=True)
                    print("checkpoint written on SIGUSR1 -> "
                          f"{args.checkpoint_path} (sim {sim_s:.3f}s)",
                          file=sys.stderr)
                if sup.stop_requested:
                    # graceful shutdown: checkpoint regardless of
                    # --checkpoint-interval, then exit 128+signum
                    write_checkpoint(interrupted=sup.stop_signum)
                    break
                if sim_s >= next_ckpt:
                    write_checkpoint()
                    next_ckpt += ck
            # the final segment's heartbeat has no next dispatch to
            # overlap with; consume it before the summary
            consume_hb()
    except InvariantViolation as e:
        # deliberately NO checkpoint here: the state just failed its own
        # consistency checks, and writing it would rotate a known-good
        # generation out in favor of a corrupt one — but it DOES get a
        # diagnostic bundle now, with the flight-recorder ring: the
        # heartbeats leading up to a corruption are the post-mortem
        from shadow_tpu.runtime import write_diagnostic_bundle

        health.fail(EXIT_INVARIANT)
        path = write_diagnostic_bundle(
            args.diag_dir, "shadow_tpu", "invariant",
            {"reason": str(e), "sim_seconds": sim_s,
             "exit_code": EXIT_INVARIANT,
             "flight_recorder": recorder.snapshot()},
        )
        print(f"shadow_tpu: INVARIANT VIOLATION at sim {sim_s:.3f}s\n{e}"
              f"\ndiagnostic bundle -> {path}",
              file=sys.stderr)
        _close_metrics()
        return EXIT_INVARIANT
    except QueuePressureError as e:
        # --overflow strict: the state is healthy (nothing was actually
        # lost — the run stopped at the first would-be drop), but the
        # campaign's no-loss contract is broken; leave a machine-readable
        # bundle and the distinct exit code instead of a stack trace
        health.fail(EXIT_PRESSURE)
        path = pressure_bundle(e, diag_dir=args.diag_dir,
                               label="shadow_tpu",
                               extra={"flight_recorder":
                                      recorder.snapshot()})
        print(f"shadow_tpu: QUEUE PRESSURE at sim {sim_s:.3f}s under "
              f"--overflow strict: {e}\ndiagnostic bundle -> {path}",
              file=sys.stderr)
        _close_metrics()
        return EXIT_PRESSURE
    except BaseException as e:
        # unhandled driver failure: best-effort emergency checkpoint of
        # the last completed window batch, then re-raise — diagnosis
        # must never mask the original error
        try:
            epath = args.checkpoint_path + ".emergency"
            write_checkpoint(path=epath, emergency=repr(e)[:200])
            print(f"emergency checkpoint -> {epath} (sim {sim_s:.3f}s)",
                  file=sys.stderr)
        except Exception as e2:
            print(f"emergency checkpoint failed: {e2!r}", file=sys.stderr)
        raise
    finally:
        # interrupted and failed runs keep their observability output:
        # flush buffered log lines, close every pcap writer, and write
        # the trace file so captures are valid up to the last drain.
        # A deferred heartbeat bundle holds drained trace records whose
        # device ring was already reset — consume it first or they're lost
        if cwd is not None and cwd_armed:
            cwd.stop()
        if xprof_active:
            # interrupted/failed runs keep the partial device capture
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            xprof_active = False
        try:
            consume_hb()
        except Exception:
            pass
        logger.flush()
        if drain is not None:
            try:
                drain.drain(st.hosts.net.cap)
            except Exception:
                pass
            drain.close()
            if drain.lost:
                print(f"pcap: {drain.lost} records lost to ring overrun "
                      "(raise --heartbeat-frequency cadence)",
                      file=sys.stderr)
        if tdrain is not None:
            try:
                st = tdrain.drain_state(st)
            except Exception:
                pass
            tdrain.save(
                args.trace_out,
                profile=prof.export() if prof is not None else None,
                extra_meta={
                    "seed": args.seed, "tier": "device",
                    # the exported Chrome trace references the device
                    # capture so Perfetto shows both side by side
                    **({"xprof_dir": args.xprof_dir}
                       if xprof_span is not None else {}),
                },
            )
            print(f"event trace: {tdrain.n_records} records -> "
                  f"{args.trace_out}"
                  + (f" ({tdrain.lost} lost to ring overrun; raise "
                     "--trace N or the heartbeat cadence)"
                     if tdrain.lost else ""),
                  file=sys.stderr)
    wall = time.perf_counter() - t1
    if sup.stop_requested:
        print(f"interrupted by signal {sup.stop_signum}: checkpoint at "
              f"{args.checkpoint_path} (sim {sim_s:.3f}s of {stop_s:.0f}s); "
              "resume with --resume auto", file=sys.stderr)
        _close_metrics()
        return sup.exit_code()

    stats = st.stats
    executed = int(jax.device_get(stats.n_executed.sum()))  # shadowlint: no-deadline=post-loop summary; watchdogs released, state materialized
    summary = {
        "hosts": n_hosts,
        "sim_seconds": stop_s,
        "wall_seconds": round(wall, 3),
        "build_seconds": round(t1 - t0, 3),
        "events": executed,
        "windows": int(jax.device_get(stats.n_windows)),  # shadowlint: no-deadline=post-loop summary; watchdogs released, state materialized
        "events_per_sec": round(executed / max(wall, 1e-9), 1),
        "sim_s_per_wall_s": round(stop_s / max(wall, 1e-9), 3),
        "net_dropped": int(jax.device_get(stats.n_net_dropped.sum())),  # shadowlint: no-deadline=post-loop summary; watchdogs released, state materialized
        "queue_drops": int(jax.device_get(st.queues.drops.sum())),  # shadowlint: no-deadline=post-loop summary; watchdogs released, state materialized
        "fault_dropped": int(jax.device_get(stats.n_fault_dropped.sum())),  # shadowlint: no-deadline=post-loop summary; watchdogs released, state materialized
        "quarantined_events": int(
            jax.device_get(stats.n_quarantined.sum())  # shadowlint: no-deadline=post-loop summary; watchdogs released, state materialized
        ),
        # scheduler self-profiling (scheduler.c:266-271 analog)
        "sweeps": int(jax.device_get(stats.n_sweeps)),  # shadowlint: no-deadline=post-loop summary; watchdogs released, state materialized
        # queue-merge overflow rounds: lanes read against events admitted
        "merge_over_lanes": int(jax.device_get(stats.n_merge_over_lanes.sum())),  # shadowlint: no-deadline=post-loop summary; watchdogs released, state materialized
        "merge_over_events": int(jax.device_get(stats.n_merge_over_events.sum())),  # shadowlint: no-deadline=post-loop summary; watchdogs released, state materialized
        "cross_shard_packets": int(jax.device_get(stats.n_cross_shard)),  # shadowlint: no-deadline=post-loop summary; watchdogs released, state materialized
        "rx_bytes": int(
            jax.device_get(st.hosts.net.sockets.rx_bytes.sum())  # shadowlint: no-deadline=post-loop summary; watchdogs released, state materialized
        ),
        "tx_bytes": int(
            jax.device_get(st.hosts.net.sockets.tx_bytes.sum())  # shadowlint: no-deadline=post-loop summary; watchdogs released, state materialized
        ),
        # the reference's ObjectCounter shutdown report
        # (object_counter.c; slave.c:237-241)
        "events_by_kind": {
            name: int(n)
            for name, n in zip(
                sim.kind_names,
                jax.device_get(stats.n_by_kind.sum(axis=0)),  # shadowlint: no-deadline=post-loop summary; watchdogs released, state materialized
            )
        },
    }
    if sim.pressure is not None:
        summary["pressure"] = sim.pressure.snapshot(st)
        summary["capacity"] = int(sim.engine.cfg.capacity)
    if drain is not None:
        # packet-lifecycle class counts from the capture rings (the
        # PDS_* stage tallies of packet.h:20-40)
        summary["packet_stages"] = {
            k: v for k, v in drain.stage_counts.items() if v
        }
    if tdrain is not None:
        summary["trace"] = {
            "records": tdrain.n_records, "lost": tdrain.lost,
            "truncated": tdrain.truncated, "file": args.trace_out,
        }
    if prof is not None:
        summary["profile"] = prof.summary()
    if st.splane is not None:
        from shadow_tpu.obs.stats import (
            FAMILY_KEYS, stats_device_refs, summarize,
        )

        stats_fetched = jax.device_get(stats_device_refs(st.splane))  # shadowlint: no-deadline=post-loop summary; watchdogs released, state materialized
        final_stats = summarize(stats_fetched)
        summary["stats"] = {
            k: {"count": final_stats[k]["count"],
                "sum": final_stats[k]["sum"],
                "p50": final_stats[k]["p50"],
                "p95": final_stats[k]["p95"]}
            for k in FAMILY_KEYS
        }
        if metrics_on:
            # align the last scrape's histogram families with the
            # printed totals, like registry.finalize below
            registry.ingest_stats(stats_fetched)
    if xprof_span is not None:
        summary["xprof"] = {"dir": args.xprof_dir,
                            "start": xprof_span[0],
                            "stop": xprof_span[1],
                            "completed": xprof_done}
    if metrics_on:
        # align the registry with the printed totals (the post-loop
        # fetches above are authoritative — they see the final state
        # after the trace drain), so the last scrape reconciles exactly
        registry.finalize(summary)
        registry.observe(checkpoints=sup_hb.checkpoints_written,
                         health=health, profiler=prof)
    print(json.dumps(summary), flush=True)
    _close_metrics()
    return 0


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
