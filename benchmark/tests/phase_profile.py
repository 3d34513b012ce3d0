"""A cell's device time by engine phase, and what tracing costs (on the chip).

    python3 -m benchmark.tests.phase_profile WORKLOAD SEED OUT_DIR [SECONDS]

Sets the cell up as the harness does, then runs three windows of
SECONDS (default 3) in one process: untraced, traced, untraced. Prints
one JSON line: chunks per second of each window (the middle one is the
tracing-on cost), `trace_reduce`'s record with `scopes` added
(`scope_reduce`), and the top device ops by self time with the phase
each counts under. It writes the window program's optimized HLO, op
metadata included, to OUT_DIR/<WORKLOAD>.hlo.txt.gz, and puts in the
line the sha256 of that text with the metadata, the debug tables and
the numbering of instruction names taken out (`canonical_hlo`): two
builds whose programs differ only in metadata print the same hash.
"""

from __future__ import annotations

import gzip
import hashlib
import importlib
import json
import os
import re
import shutil
import sys
import tempfile
import time

from benchmark import harness, scope_reduce, trace_reduce
from benchmark.models import MS

_META = re.compile(r",? metadata=\{[^}]*\}")
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_NAME = re.compile(r"%[\w.\-]+")


def canonical_hlo(text: str) -> str:
    """HLO text without op metadata, without the debug tables, and with
    every `%name` renumbered in order of first appearance."""
    out, skip = [], False
    for line in _META.sub("", text).split("\n"):
        if line in _TABLES:
            skip = True
        elif skip and not line:
            skip = False
            continue
        if not skip:
            out.append(line)
    seen: dict[str, str] = {}
    return _NAME.sub(lambda m: seen.setdefault(m.group(0), f"%v{len(seen)}"),
                     "\n".join(out))


def window_hlo(built, state, stop_ns: int) -> str:
    """The optimized HLO of the family's window program, op metadata
    included: its jitted `run` (a free variable of `built.advance`) is
    lowered with the arguments `advance` would pass, and compiled (a
    persistent-cache hit where the run already compiled it)."""
    cells = dict(zip(built.advance.__code__.co_freevars,
                     built.advance.__closure__))
    run = cells["run"].cell_contents
    cells["run"].cell_contents = run.lower
    try:
        lowered = built.advance(state, stop_ns)
    finally:
        cells["run"].cell_contents = run
    return lowered.compile().as_text()


def top_ops(path: str, spans) -> list:
    """[name, phase, seconds] of the top device ops by self time."""
    names, _ = trace_reduce.read_planes(path)
    labelled = scope_reduce.read_ops(path)
    lo = min(s for n, s, e in spans if n == "chunk")
    hi = max(e for n, s, e in spans if n in ("chunk", "fetch"))
    by: dict[str, int] = {}
    for dev, evs in names.items():
        keyed = [(f"{n}\t{lb}", max(s, lo), min(e, hi))
                 for (n, s, e), (lb, _, _) in zip(evs, labelled[dev])
                 if min(e, hi) > max(s, lo)]
        for k, ns in trace_reduce.self_times(keyed).items():
            by[k] = by.get(k, 0) + ns
    top = sorted(by.items(), key=lambda kv: -kv[1])[:2 * trace_reduce.TOP]
    return [[*k.split("\t"), ns / 1e9] for k, ns in top]


def profile(workload: str, seed: int, out_dir: str, seconds: float, *,
            platform: str = "tpu", overrides: dict | None = None) -> dict:
    """The JSON line's record. `platform` and `overrides` (config keys)
    exist for the CPU rehearsal in test_scope_reduce.py."""
    import jax

    from shadow_tpu.utils.compile_cache import enable_compile_cache

    spec = harness.load_spec()
    cell = next(w for w in spec["workloads"] if w["name"] == workload)
    config = dict(harness.load_json("configs", cell["config"]),
                  **(overrides or {}))
    traffic = harness.load_json("traffic", cell["traffic"])
    family = importlib.import_module(f"benchmark.models.{config['family']}")
    devices = harness.accelerators(cell["chips"], platform)
    enable_compile_cache()
    t0 = time.perf_counter()
    built = family.build(config, traffic, seed, devices)
    built.state = built.advance(built.state, 0)
    jax.block_until_ready(built.counters(built.state))
    chunk_ns = int(traffic["chunk_ms"]) * MS
    stop = int(traffic.get("warmup_ms", 0)) * MS + chunk_ns
    built.state = built.advance(built.state, stop)
    jax.block_until_ready(built.counters(built.state))
    rec = {"workload": workload, "seed": seed,
           "setup_s": time.perf_counter() - t0, "chunks_per_s": []}
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        for traced in (False, True, False):
            start = stop
            built.state, stop, wall, _, _ = harness.run_window(
                built, start, chunk_ns, seconds, tmp if traced else None)
            rec["chunks_per_s"].append((stop - start) / chunk_ns / wall)
        path = trace_reduce.find(tmp)
        rec["trace"] = scope_reduce.reduce_file(path)
        if rec["trace"] is not None:
            rec["shares_pct"] = {k: scope_reduce.share(rec, v)
                                 for k, v in scope_reduce.SHARES.items()}
            rec["top_ops"] = top_ops(path, trace_reduce.read_planes(path)[1])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    text = window_hlo(built, built.state, stop + chunk_ns)
    os.makedirs(out_dir, exist_ok=True)
    with gzip.open(os.path.join(out_dir, f"{workload}.hlo.txt.gz"), "wt") as f:
        f.write(text)
    rec["hlo_sha256"] = hashlib.sha256(canonical_hlo(text).encode()).hexdigest()
    return rec


def main(argv) -> int:
    seconds = float(argv[3]) if len(argv) > 3 else harness.TRACE_S
    print(json.dumps(profile(argv[0], int(argv[1]), argv[2], seconds)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
