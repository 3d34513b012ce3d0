#!/usr/bin/env python
"""Chip smoke: the simulator's main path once, on the TPU, through the
entry points a user calls, at the sizes the benchmark runs.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # only the sharded path, on four

One process holds the chip: nothing here starts a child. Every phase
first builds and compiles its programs, all phases at once in threads
(XLA compiles release the GIL, and a cold TPU compile takes minutes);
then the phases run their checks one after another. The CLI phase has
no separate compile step and runs in the main thread during that
set-up, so its wall seconds share the host with the other compiles. Each prints one
JSON line with its checks, its compile seconds (set-up, overlapped with
the other phases') and its wall seconds. Every phase runs even after
one fails; any failure ends the script non-zero with no `ok` line. The
last line of a passing run is exactly
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.

One chip:
- phold: 4096 hosts, capacity 64, 8 msgs/host (the bench headline
  shape) through `phold.build` + `jax.jit(eng.run)`; the final state
  must equal, leaf for leaf, the same run on the CPU device.
- tor1020: BASELINE config 3 (~1k hosts, relay CPU model on) through
  `build_simulation` + `sim.run`, past client start: streams complete,
  bytes are relayed, no queue drops, the invariant guard passes.
- tor76: the 76-host tier on the TPU and on the CPU device; the two
  summaries must be equal.
- cli: `shadow_tpu.cli.main(["--test"])` in process: 3342336 bytes each
  way and 820 events.
- serve: an in-process SimService packs 4 PHOLD requests of one class
  into 2 launches; every summary equals `solo_reference(doc)` and the
  second launch is a program-cache hit.

Four chips: PHOLD at 16384 hosts and the `--test` TGen config, each over
`make_mesh(4)` with shard_map, against the same run on one device of
that machine: the state spans 4 devices and the results are identical.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time
import traceback

SECOND = 1_000_000_000
MILLISECOND = 1_000_000

# Sizes. Tests and rehearsals on the CPU shrink them; the chip runs these.
SIZES = {
    "phold_hosts": 4096,
    "phold_stop_s": 5,
    "tor_big": (110, 660, 30),  # relays per class, clients, servers
    "tor_small": (4, 60, 4),
    "tor_stop_s": 10,  # clients start at 3-7 s
    "serve_hosts": 64,
    "sharded_phold_hosts": 16384,
    "sharded_phold_stop_s": 2,
}


class SmokeFailure(RuntimeError):
    pass


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _timed(fn, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _parallel(*thunks):
    """Run thunks in threads and return their results in order. XLA
    compiles release the GIL, so independent programs compile side by
    side on the host's cores instead of one after another."""
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=len(thunks)) as ex:
        futs = [ex.submit(t) for t in thunks]
    return [f.result() for f in futs]


def _on(device, thunk):
    """`thunk` with `device` as the default device (a thread-local
    setting, so it holds inside `_parallel`)."""
    import jax

    def run():
        with jax.default_device(device):
            return thunk()
    return run


def _differing_leaves(a, b) -> list[str]:
    import jax
    import numpy as np

    la = jax.tree_util.tree_leaves_with_path(jax.device_get(a))
    lb = jax.tree_util.tree_leaves_with_path(jax.device_get(b))
    _check(len(la) == len(lb), f"{len(la)} leaves vs {len(lb)}")
    return [jax.tree_util.keystr(p) for (p, x), (_, y) in zip(la, lb)
            if not np.array_equal(np.asarray(x), np.asarray(y))]


def _phold_kw():
    return dict(capacity=64, msgs_per_host=8, seed=1234,
                latency_ns=50 * MILLISECOND, mean_delay_ns=10 * MILLISECOND)


def _phold_compiled(n, **kw):
    """(run, init, compile_s) for a PHOLD engine on the default device;
    the stop-0 call compiles the program and executes nothing."""
    import jax
    import jax.numpy as jnp

    from shadow_tpu.models import phold

    eng, init = phold.build(n, **_phold_kw(), **kw)
    run = jax.jit(eng.run)
    _, compile_s = _timed(run, init(), jnp.int64(0))
    return run, init, compile_s


# -- one chip ----------------------------------------------------------
# Each phase is (prepare, check): every prepare (builds and compiles) runs
# at once in its own thread; the checks then run one after another, so
# their wall seconds are not shared with any other phase.


def prepare_phold(cpu):
    n = SIZES["phold_hosts"]
    tpu, ref = _parallel(
        lambda: _phold_compiled(n, batched=True),
        _on(cpu, lambda: _phold_compiled(n, batched=True)))
    return {"tpu": tpu, "cpu": ref, "cpu_device": cpu,
            "compile_s": tpu[2], "cpu_compile_s": ref[2]}


def check_phold(ctx):
    import jax
    import jax.numpy as jnp

    stop = jnp.int64(SIZES["phold_stop_s"] * SECOND)
    run, init, _ = ctx["tpu"]
    tpu, wall_s = _timed(run, init(), stop)
    run_c, init_c, _ = ctx["cpu"]
    with jax.default_device(ctx["cpu_device"]):
        ref, cpu_s = _timed(run_c, init_c(), stop)
    events = int(jax.device_get(tpu.stats.n_executed).sum())
    diff = _differing_leaves(tpu, ref)
    _check(not diff, f"TPU and CPU final states differ in {diff}")
    _check(events > 0, "no event executed")
    return {"hosts": SIZES["phold_hosts"], "sim_s": SIZES["phold_stop_s"],
            "events": events,
            "drops": int(jax.device_get(tpu.queues.drops).sum()),
            "tpu_equals_cpu": True, "cpu_compile_s": ctx["cpu_compile_s"],
            "cpu_wall_s": cpu_s, "compile_s": ctx["compile_s"],
            "wall_s": wall_s}


def _tor_sim(tier):
    from shadow_tpu.config import parse_config
    from shadow_tpu.examples import tor_example
    from shadow_tpu.sim import build_simulation

    relays, clients, servers = tier
    cfg = parse_config(tor_example(
        n_relays_per_class=relays, n_clients=clients, n_servers=servers,
        filesize="64KiB", count=2, stoptime=SIZES["tor_stop_s"],
        relay_cpu_ghz=3.0))
    return build_simulation(cfg, seed=1, n_sockets=32, capacity=768)


def _tor_compiled(tier):
    t0 = time.perf_counter()
    sim = _tor_sim(tier)
    build_s = time.perf_counter() - t0
    _, compile_s = _timed(sim.run, 0)
    return sim, build_s, compile_s


def prepare_tor1020(cpu):
    sim, build_s, compile_s = _tor_compiled(SIZES["tor_big"])
    return {"sim": sim, "build_s": build_s, "compile_s": compile_s}


def check_tor1020(ctx):
    import jax

    from shadow_tpu.runtime.invariants import validate

    sim = ctx["sim"]
    st, wall_s = _timed(sim.run, SIZES["tor_stop_s"] * SECOND)
    validate(st)
    streams = int(jax.device_get(st.hosts.app.streams_done).sum())
    relayed = int(jax.device_get(st.hosts.app.relayed_bytes).sum())
    drops = int(jax.device_get(st.queues.drops).sum())
    _check(streams > 0, "no Tor stream completed")
    _check(relayed > 0, "no byte relayed")
    _check(drops == 0, f"{drops} queue drops")
    return {"hosts": len(sim.names), "sim_s": SIZES["tor_stop_s"],
            "streams_done": streams, "relayed_bytes": relayed,
            "events": int(jax.device_get(st.stats.n_executed).sum()),
            "queue_drops": drops, "invariants": "pass",
            "build_s": ctx["build_s"], "compile_s": ctx["compile_s"],
            "wall_s": wall_s}


def prepare_tor76(cpu):
    tier = SIZES["tor_small"]
    tpu, ref = _parallel(lambda: _tor_compiled(tier),
                         _on(cpu, lambda: _tor_compiled(tier)))
    return {"sim": tpu[0], "compile_s": tpu[2], "sim_c": ref[0],
            "cpu_compile_s": ref[2], "cpu_device": cpu}


def check_tor76(ctx):
    import jax

    stop = SIZES["tor_stop_s"] * SECOND
    sim, sim_c = ctx["sim"], ctx["sim_c"]
    st, wall_s = _timed(sim.run, stop)
    with jax.default_device(ctx["cpu_device"]):
        st_c, cpu_s = _timed(sim_c.run, stop)
    tpu, ref = sim.summary(st), sim_c.summary(st_c)
    diff = _differing_leaves(st, st_c)
    _check(tpu == ref and not diff,
           f"TPU summary {tpu} vs CPU {ref}; final states differ in {diff}")
    streams = int(jax.device_get(st.hosts.app.streams_done).sum())
    _check(streams > 0, "no Tor stream completed")
    return {"hosts": len(sim.names), "sim_s": SIZES["tor_stop_s"],
            "events": tpu["executed"], "streams_done": streams,
            "tpu_equals_cpu": True, "cpu_compile_s": ctx["cpu_compile_s"],
            "cpu_wall_s": cpu_s, "compile_s": ctx["compile_s"],
            "wall_s": wall_s}


def _last_summary(text: str) -> dict:
    for line in reversed(text.strip().splitlines()):
        if line.startswith("{"):
            doc = json.loads(line)
            if "events" in doc:
                return doc
    raise SmokeFailure("the CLI printed no summary line")


def check_cli(ctx):
    from shadow_tpu.cli import main as cli_main

    buf = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(buf):
        rc = cli_main(["--test", "--profile"])
    wall_s = time.perf_counter() - t0
    s = _last_summary(buf.getvalue())
    _check(rc == 0, f"cli exit {rc}")
    _check(s["rx_bytes"] == s["tx_bytes"] == 3342336,
           f"rx {s['rx_bytes']} tx {s['tx_bytes']}, want 3342336 each")
    _check(s["events"] == 820, f"{s['events']} events, want 820")
    return {"events": s["events"], "rx_bytes": s["rx_bytes"],
            "tx_bytes": s["tx_bytes"],
            # the first window's step compiles the program
            "compile_s": s["profile"]["phases"]["step"]["max_s"],
            "wall_s": wall_s}


def _serve_docs():
    return [{"model": "phold", "seed": 100 + i, "stop_s": 1.0,
             "params": {"hosts": SIZES["serve_hosts"], "capacity": 64,
                        "msgs_per_host": 8}} for i in range(4)]


def prepare_serve(cpu):
    """The solo references: each seed is its own program (the root key
    is a baked constant), so they compile here, beside the others."""
    from shadow_tpu.serve.service import solo_reference

    return {"refs": _parallel(*[lambda d=d: solo_reference(d)
                                for d in _serve_docs()])}


def check_serve(ctx):
    from shadow_tpu.serve.service import SimService

    docs = _serve_docs()
    svc = SimService(max_lanes=2, pack_deadline_ms=2000,
                     beat_windows=16).start()
    t0 = time.perf_counter()
    try:
        rids = [svc.submit(d)["request_id"] for d in docs]
        deadline = time.monotonic() + 900
        while not all(svc.result(r)["status"] in ("done", "error", "timeout")
                      for r in rids):
            _check(time.monotonic() < deadline, "serve requests pending")
            time.sleep(0.05)
    finally:
        svc.drain()
    wall_s = time.perf_counter() - t0
    recs = [svc.result(r) for r in rids]
    _check(all(r["status"] == "done" for r in recs),
           f"statuses {[r['status'] for r in recs]}")
    for ref, r in zip(ctx["refs"], recs):
        _check(r["summary"] == ref,
               f"{r['request_id']} differs from its solo reference")
    launches = sorted({r["launch"] for r in recs})
    hit = {r["launch"]: r["cache_hit"] for r in recs}
    _check(len(launches) == 2 and not hit[launches[0]] and hit[launches[1]],
           f"launch/cache-hit pattern {hit}, want a miss then a hit")
    cold = max(r["wall_ms"] for r in recs if r["launch"] == launches[0])
    warm = max(r["wall_ms"] for r in recs if r["launch"] == launches[1])
    return {"requests": len(recs), "launches": len(launches),
            "equal_solo": True, "second_launch_cache_hit": True,
            "cold_launch_s": cold / 1e3, "warm_launch_s": warm / 1e3,
            "wall_s": wall_s}


# -- four chips --------------------------------------------------------


def _spans(leaf, n: int) -> bool:
    return len(leaf.sharding.device_set) == n


def prepare_sharded_phold(cpu):
    from shadow_tpu.parallel import mesh as pmesh

    n = SIZES["sharded_phold_hosts"]
    per = n // 4

    def sharded():
        import jax.numpy as jnp

        from shadow_tpu.models import phold

        engN, initN = phold.build(per, axis_name=pmesh.HOSTS_AXIS,
                                  n_shards=4, **_phold_kw())
        init, run, _ = pmesh.build_sharded(engN, initN, pmesh.make_mesh(4),
                                           per)
        _, compile_s = _timed(run, init(), jnp.int64(0))
        return run, init, compile_s

    shard, one = _parallel(sharded, lambda: _phold_compiled(n))
    return {"sharded": shard, "one": one, "compile_s": shard[2],
            "one_device_compile_s": one[2]}


def check_sharded_phold(ctx):
    import jax
    import jax.numpy as jnp

    from shadow_tpu.parallel import mesh as pmesh

    stop = jnp.int64(SIZES["sharded_phold_stop_s"] * SECOND)
    run, init, _ = ctx["sharded"]
    stN, wall_s = _timed(run, init(), stop)
    _check(_spans(stN.queues.time, 4), "sharded queue is not on 4 devices")
    run1, init1, _ = ctx["one"]
    st1, wall1_s = _timed(run1, init1(), stop)
    _check(_spans(st1.queues.time, 1), "one-device queue is not on 1 device")
    diff = _differing_leaves((st1.hosts, st1.src_seq, st1.now),
                             (stN.hosts, stN.src_seq, stN.now))
    _check(not diff, f"sharded and one-device states differ in {diff}")
    # queue rows equal as multisets (slot order may differ)
    _check(bool((st1.queues.time.sort(axis=1)
                 == stN.queues.time.sort(axis=1)).all()),
           "sharded and one-device queues differ")
    ev1 = int(jax.device_get(st1.stats.n_executed).sum())
    evN = int(jax.device_get(stN.stats.n_executed).sum())
    _check(ev1 == evN > 0, f"events {ev1} vs {evN}")
    return {"hosts": SIZES["sharded_phold_hosts"], "shards": 4,
            "spmd": pmesh.select_spmd("auto"), "events": evN,
            "equals_one_device": True,
            "one_device_compile_s": ctx["one_device_compile_s"],
            "one_device_wall_s": wall1_s,
            "compile_s": ctx["compile_s"], "wall_s": wall_s}


def prepare_sharded_tgen(cpu):
    from shadow_tpu.config import parse_config
    from shadow_tpu.examples import example_config
    from shadow_tpu.parallel.mesh import make_mesh
    from shadow_tpu.sim import build_simulation

    cfg = parse_config(example_config())

    def compiled(**kw):
        sim = build_simulation(cfg, seed=1, **kw)
        return sim, _timed(sim.run, 0)[1]

    shard, one = _parallel(
        lambda: compiled(mesh=make_mesh(4), spmd="auto"), compiled)
    return {"simN": shard[0], "sim1": one[0], "compile_s": shard[1],
            "one_device_compile_s": one[1]}


def check_sharded_tgen(ctx):
    import jax

    simN, sim1 = ctx["simN"], ctx["sim1"]
    stN, wall_s = _timed(simN.run)
    _check(_spans(stN.queues.time, 4), "sharded queue is not on 4 devices")
    st1, wall1_s = _timed(sim1.run)
    a, b = sim1.summary(st1), simN.summary(stN)
    _check(a == b, f"one-device summary {a} != sharded {b}")
    diff = _differing_leaves(st1.hosts, stN.hosts)
    _check(not diff, f"sharded and one-device hosts differ in {diff}")
    socks = stN.hosts.net.sockets
    rx, tx = (int(jax.device_get(x.sum()))
              for x in (socks.rx_bytes, socks.tx_bytes))
    _check(rx == tx == 3342336 and b["executed"] == 820,
           f"rx {rx} tx {tx} events {b['executed']}")
    return {"hosts": len(simN.names), "shards": 4, "spmd": simN.spmd_path,
            "events": b["executed"], "rx_bytes": rx, "tx_bytes": tx,
            "equals_one_device": True,
            "one_device_compile_s": ctx["one_device_compile_s"],
            "one_device_wall_s": wall1_s,
            "compile_s": ctx["compile_s"], "wall_s": wall_s}


ONE_CHIP = (("phold", prepare_phold, check_phold),
            ("tor1020", prepare_tor1020, check_tor1020),
            ("tor76", prepare_tor76, check_tor76),
            ("cli", None, check_cli),
            ("serve", prepare_serve, check_serve))
FOUR_CHIPS = (("sharded_phold", prepare_sharded_phold, check_sharded_phold),
              ("sharded_tgen", prepare_sharded_tgen, check_sharded_tgen))


def device_gate(chips: int, platform: str = "tpu"):
    """The first device must be a TPU and there must be `chips` of them;
    there is no CPU fallback. Returns the device list."""
    import jax

    devs = jax.devices()
    if devs[0].platform != platform or len(devs) < chips:
        raise SmokeFailure(
            f"chip_smoke needs {chips} {platform} device(s); JAX found "
            f"{len(devs)} {devs[0].platform} ({devs[0].device_kind})")
    return devs


def main(argv=None, platform: str = "tpu") -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded path and its one-device "
                         "comparison")
    args = ap.parse_args(argv)

    # the CPU device is the reference the TPU results are compared with
    plats = os.environ.get("JAX_PLATFORMS")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    from shadow_tpu.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    try:
        devs = device_gate(args.chips, platform)
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    import jax

    cpu = jax.devices("cpu")[0]
    print(json.dumps({"compile_cache": cache}), flush=True)
    phases = FOUR_CHIPS if args.chips == 4 else ONE_CHIP

    def prepared(prep):
        try:
            return prep(cpu), None
        except Exception as e:  # reported with its phase below
            return None, e

    def checked(name, check, ctx, err):
        t0 = time.perf_counter()
        try:
            if err is not None:
                raise err
            return {"phase": name, "ok": True, **check(ctx)}
        except Exception as e:
            traceback.print_exc()
            return {"phase": name, "ok": False,
                    "error": f"{type(e).__name__}: {e}"[:4000],
                    "wall_s": time.perf_counter() - t0}

    # Every prepare runs in its own thread. A phase without one (the CLI,
    # whose signal handlers need the main thread) runs its check here in
    # the meantime, so its compile overlaps the others' too.
    from concurrent.futures import ThreadPoolExecutor

    t0 = time.perf_counter()
    lines = {}
    with ThreadPoolExecutor(max_workers=len(phases)) as ex:
        futs = {name: ex.submit(prepared, prep)
                for name, prep, _ in phases if prep is not None}
        for name, prep, check in phases:
            if prep is None:
                lines[name] = {**checked(name, check, {}, None),
                               "during_setup": True}
        ctxs = {name: f.result() for name, f in futs.items()}
    print(json.dumps({"setup": "every phase built and compiled at once",
                      "wall_s": time.perf_counter() - t0}), flush=True)
    failed = []
    for name, prep, check in phases:
        line = lines.get(name) or checked(name, check, *ctxs[name])
        print(json.dumps(line), flush=True)
        if not line["ok"]:
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
