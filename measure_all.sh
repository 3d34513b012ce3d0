#!/bin/bash
# Sequential bench measurement + compile banking on the real chip.
# Each worker runs in its own process; stdout JSON accumulates in
# measure_results.jsonl, stage stamps in measure_stamps.log.
cd /root/repo
R=measure_results.jsonl
S=measure_stamps.log
: > "$R"; : > "$S"
run() { # run <name> <timeout_s> <worker-flag> [ENV=VAL ...]
  local name=$1 tmo=$2 flag=$3; shift 3
  echo "=== $name start $(date +%H:%M:%S)" >> "$S"
  echo "{\"stage\": \"$name\"}" >> "$R"
  timeout "$tmo" env "$@" python bench.py "$flag" >> "$R" 2>> "$S"
  echo "=== $name exit=$? $(date +%H:%M:%S)" >> "$S"
}
run tor0      1500 --tor-worker      BENCH_TOR_TIER=0
run tor1      1800 --tor-worker      BENCH_TOR_TIER=1
run tor2      2400 --tor-worker      BENCH_TOR_TIER=2
run tor3      3600 --tor-worker      BENCH_TOR_TIER=3
run tor0nocpu 1500 --tor-worker      BENCH_TOR_TIER=0 BENCH_TOR_CPU=0
# real-time-factor stage for the TCP model tier: tor (1020-host tier)
# and tgen, each chained vs frontier drain (+100 ms runahead), with
# per-phase profiles and the delta vs the newest BENCH_r* tor record
# (docs/11-Performance.md "Model-tier batching")
run tor_rt    7200 --tor-rt          BENCH_TOR_TIER=2 BENCH_FRONTIER=16 \
  BENCH_RUNAHEAD_MS=100 BENCH_TOR_RT_TIMEOUT=1800
run btc       1800 --btc-worker
run phold     900  --phold-worker    BENCH_STOP_S=20
run phold16k  1200 --phold-big-worker BENCH_STOP_S=20
run skew      900  --skew-worker
# weak-scaling multichip bench on a forced 8-device CPU mesh: sharded
# events/s, per-shard host count, and the bit-identity-vs-single-device
# pass/fail; the worker also writes the superset to MULTICHIP_r*.json
run multichip 2400 --multichip-worker JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 BENCH_BUDGET_S=2300
# chaos smoke: the elastic-recovery acceptance (docs/13) — SIGKILL a
# checkpointing 8-shard worker mid-window and wedge another one's
# collective past --collective-timeout; both runs must recover through
# the --retry path to a bit-identical summary. Results (recoveries,
# MTTR, exit histories) merge into the newest MULTICHIP_r*.json.
run chaos_smoke 900 --chaos-worker JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 BENCH_BUDGET_S=840
# fast observability smoke: a short traced+profiled run through the CLI
# plus the Chrome-trace exporter; only the summary JSON line joins $R
# (stderr notes and heartbeat lines go to the stamp log)
echo "=== trace_smoke start $(date +%H:%M:%S)" >> "$S"
echo "{\"stage\": \"trace_smoke\"}" >> "$R"
timeout 600 python -m shadow_tpu --test --stoptime 5 \
  --heartbeat-frequency 2 --trace 4096 --profile \
  --trace-out measure_trace.npz > measure_trace.out 2>> "$S" \
  && tail -n 1 measure_trace.out >> "$R" \
  && timeout 120 python -m shadow_tpu.tools.export_trace \
       measure_trace.npz -o measure_trace.json 2>> "$S"
echo "=== trace_smoke exit=$? $(date +%H:%M:%S)" >> "$S"
# queue-pressure smoke: the skewed example workload through the CLI under
# all four --overflow modes at a deliberately small capacity. drop stays
# lossy (counted), spill/grow must end with queue_drops 0, strict must
# exit 76. Only the summary JSON lines join $R.
for mode in drop spill grow strict; do
  echo "=== pressure_smoke_$mode start $(date +%H:%M:%S)" >> "$S"
  echo "{\"stage\": \"pressure_smoke_$mode\"}" >> "$R"
  timeout 600 python -m shadow_tpu --test --stoptime 5 \
    --heartbeat-frequency 2 --capacity 8 --overflow "$mode" \
    > measure_pressure.out 2>> "$S"
  rc=$?
  tail -n 1 measure_pressure.out >> "$R"
  echo "=== pressure_smoke_$mode exit=$rc $(date +%H:%M:%S)" >> "$S"
  if [ "$mode" = strict ] && [ "$rc" -ne 76 ] && [ "$rc" -ne 0 ]; then
    echo "pressure_smoke_strict: unexpected exit $rc" >> "$S"
  fi
done
# metrics smoke: the live-telemetry acceptance (docs/14-Telemetry.md) —
# a slow supervised run with --metrics-port 0, scraped mid-run (two
# no-heartbeat scrapes byte-identical, OpenMetrics syntax clean via
# tools/check_openmetrics, /healthz ok) and again after the summary
# lands inside the SHADOW_TPU_METRICS_LINGER_S window; the final scrape
# must equal the run summary and the in-band [metrics] rows exactly.
run metrics_smoke 900 --metrics-smoke-worker JAX_PLATFORMS=cpu \
  BENCH_BUDGET_S=840
# sim-analytics smoke (docs/15-Sim-Analytics.md): three gates in one
# stage — (1) a stats=0 build lowers byte-identically to a build that
# never heard of the stat plane (the shared assert_zero_cost pin), (2)
# a real --stats CLI run's cumulative [stats] heartbeat rows reconcile
# exactly with its end-of-run summary histograms, and (3) the
# OpenMetrics histogram exposition rebuilt from that run's final row
# passes tools/check_openmetrics (monotone le, mandatory +Inf,
# _count/_sum reconciliation). One JSON line joins $R.
echo "=== stats_smoke start $(date +%H:%M:%S)" >> "$S"
echo "{\"stage\": \"stats_smoke\"}" >> "$R"
timeout 900 env JAX_PLATFORMS=cpu python - >> "$R" 2>> "$S" <<'PYEOF'
import json, subprocess, sys, tempfile
import jax.numpy as jnp
from shadow_tpu.analysis.hlo_audit import assert_zero_cost
from shadow_tpu.core.timebase import SECOND
from shadow_tpu.models import phold
from shadow_tpu.obs.metrics import MetricsRegistry
from shadow_tpu.obs.stats import FAMILY_KEYS, parse_hist
from shadow_tpu.tools.parse_shadow import parse_lines

# gate 1: --stats off is byte-identical to a stats-naive build
eng0, i0 = phold.build(8, seed=3, capacity=32, msgs_per_host=2)
engz, iz = phold.build(8, seed=3, capacity=32, msgs_per_host=2, stats=0)
engs, i1 = phold.build(8, seed=3, capacity=32, msgs_per_host=2, stats=1)
assert_zero_cost((eng0, i0()), (engz, iz()), (engs, i1()),
                 jnp.int64(SECOND), get_subtree=lambda st: st.splane)

# gate 2: a --stats run's [stats] rows reconcile with the summary
run = subprocess.run(
    [sys.executable, "-m", "shadow_tpu", "--test", "--stoptime", "6",
     "--heartbeat-frequency", "3", "--stats"],
    capture_output=True, text=True, timeout=600)
assert run.returncode == 0, run.stderr[-2000:]
summary = next(json.loads(ln) for ln in
               reversed(run.stdout.strip().splitlines())
               if ln.startswith("{"))
rows = parse_lines(run.stdout.splitlines())["stats"]
assert rows["ticks"], "no [stats] heartbeat rows"
for fam in FAMILY_KEYS:
    assert rows[f"{fam}_count"][-1] == summary["stats"][fam]["count"], fam
    assert rows[f"{fam}_sum"][-1] == summary["stats"][fam]["sum"], fam

# gate 3: the histogram exposition from the final row validates
reg = MetricsRegistry(version="smoke")
reg.ingest_stats({
    **{f"{k}_bucket": parse_hist("|".join(
        f"{i}:{c}" for i, c in sorted(rows[f"{k}_hist"][-1].items(),
                                      key=lambda kv: int(kv[0]))))
       for k in FAMILY_KEYS},
    **{f"{k}_sum": rows[f"{k}_sum"][-1] for k in FAMILY_KEYS},
})
with tempfile.NamedTemporaryFile(
        "w", suffix=".metrics", delete=False) as f:
    f.write(reg.render())
chk = subprocess.run(
    [sys.executable, "-m", "shadow_tpu.tools.check_openmetrics",
     f.name], capture_output=True, text=True)
assert chk.returncode == 0, chk.stdout

print(json.dumps({
    "stats_zero_cost": True,
    "stats_rows": len(rows["ticks"]),
    "stats_reconcile": True,
    "openmetrics": chk.stderr.strip(),
    "wait_count": summary["stats"]["wait"]["count"],
    "wait_p95_ns": summary["stats"]["wait"]["p95"],
}))
PYEOF
echo "=== stats_smoke exit=$? $(date +%H:%M:%S)" >> "$S"
# scenario-fleet smoke (docs/16-Scenario-Fleets.md): an 8-lane PHOLD
# fleet vs the same 8 scenarios run sequentially, compile included on
# both sides with the persistent cache off — every measured lane (lane 0
# included) must be bit-identical to its solo run, and the sequential-
# vs-fleet wall-clock ratio prints to the stamp log. Exit 1 on an
# identity failure or a budget-truncated sequential side.
run fleet_smoke 900 --fleet-smoke JAX_PLATFORMS=cpu BENCH_BUDGET_S=840
# resident-service smoke (docs/17-Serving.md): a real `shadow_tpu serve`
# subprocess takes the serve_client's 16-request mixed stream (two
# equivalence classes). Four gates in one stage: (a) every served
# summary diffs EXACTLY against its solo_reference via tools/diff_runs
# (the served-record classify path), (b) >= 1 launch packed >= 2 lanes,
# (c) the /metrics scrape passes tools/check_openmetrics and carries the
# serve families, (d) SIGTERM with 2 undispatched requests queued ->
# graceful drain, exit 0, queue persisted as re-submittable JSON. The
# warm/cold ratio itself is bench.py --serve-smoke.
echo "=== serve_smoke start $(date +%H:%M:%S)" >> "$S"
echo "{\"stage\": \"serve_smoke\"}" >> "$R"
timeout 900 env JAX_PLATFORMS=cpu python - >> "$R" 2>> "$S" <<'PYEOF'
import json, os, re, shutil, signal, subprocess, sys, time

from shadow_tpu.serve.service import solo_reference
from shadow_tpu.tools import diff_runs
from shadow_tpu.tools.serve_client import request_docs, run_load

QF = "measure_serve_queue.json"
DIR = "measure_served"
for p in (QF, DIR):
    (shutil.rmtree if os.path.isdir(p) else
     lambda q: os.path.exists(q) and os.remove(q))(p)

# a 10-min pack deadline: the 16-request stream dispatches purely via
# full classes (8 per class / max-lanes 4), and the 2 extra requests
# submitted afterwards stay QUEUED for the drain-persistence gate
srv = subprocess.Popen(
    [sys.executable, "-m", "shadow_tpu", "serve", "--port", "0",
     "--max-lanes", "4", "--pack-deadline-ms", "600000",
     "--queue-file", QF],
    stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
port = None
t0 = time.monotonic()
for line in srv.stderr:
    m = re.search(r"listening http://[^:]+:(\d+)/", line)
    if m:
        port = int(m.group(1))
        break
    if time.monotonic() - t0 > 120:
        break
assert port, "server never printed its listening line"
url = f"http://127.0.0.1:{port}"

docs = request_docs(16, mix="mixed", hosts=8, stop_s=0.5)
report = run_load(url, docs, out_dir=DIR, timeout_s=600)
assert report["errors"] == 0, report

# gate (a): every served record diffs exactly against its solo run
# through tools/diff_runs' served-artifact path (rids are submit order)
os.makedirs("measure_solo", exist_ok=True)
drift = []
for i, doc in enumerate(docs):
    rid = f"r{i:06d}"
    solo = os.path.join("measure_solo", f"{rid}.json")
    with open(solo, "w") as f:
        json.dump(solo_reference(doc), f, sort_keys=True)
    entries = diff_runs.diff_files(
        os.path.join(DIR, f"{rid}.json"), solo, rtol=0.0)
    drift += [{**e, "rid": rid} for e in entries]
assert not drift, f"served summaries drifted from solo runs: {drift[:4]}"

# gate (b): >= 1 multi-lane packed launch
assert report["max_lanes_packed"] >= 2, report

# gate (c): the /metrics scrape is valid OpenMetrics + serve families
import urllib.request
scrape = urllib.request.urlopen(f"{url}/metrics", timeout=10).read()
with open("measure_serve.metrics", "wb") as f:
    f.write(scrape)
chk = subprocess.run(
    [sys.executable, "-m", "shadow_tpu.tools.check_openmetrics",
     "measure_serve.metrics"], capture_output=True, text=True)
assert chk.returncode == 0, chk.stdout
for fam in ("shadow_tpu_serve_requests_total",
            "shadow_tpu_serve_packed_launches_total",
            "shadow_tpu_serve_cache_hits_total",
            "shadow_tpu_serve_request_latency_ns_count"):
    assert fam.encode() in scrape, f"missing serve family {fam}"

# gate (d): SIGTERM with 2 queued requests -> drain, exit 0, persist
extra = request_docs(2, mix="mixed", hosts=8, stop_s=0.5, seed0=900)
for doc in extra:
    body = json.dumps(doc).encode()
    urllib.request.urlopen(
        urllib.request.Request(url + "/submit", data=body), timeout=10)
srv.send_signal(signal.SIGTERM)
rc = srv.wait(timeout=120)
assert rc == 0, f"drain exit code {rc} != 0"
with open(QF) as f:
    pending = json.load(f)["pending"]
assert len(pending) == 2, pending
assert [p["seed"] for p in pending] == [d["seed"] for d in extra]

print(json.dumps({
    "serve_bit_identical": True, "serve_diffed": len(docs),
    "serve_requests_per_sec": report["requests_per_sec"],
    "serve_p50_ms": report["p50_ms"], "serve_p95_ms": report["p95_ms"],
    "serve_max_lanes_packed": report["max_lanes_packed"],
    "serve_launches": report["launches"],
    "serve_cache_hits_seen": report["cache_hits_seen"],
    "serve_openmetrics": chk.stderr.strip(),
    "serve_drain_exit": rc, "serve_queue_persisted": len(pending),
}))
PYEOF
echo "=== serve_smoke exit=$? $(date +%H:%M:%S)" >> "$S"
# serve chaos: failure-domain acceptance for the resident service
# (docs/17-Serving.md "Failure semantics") against a real serve
# subprocess with SHADOW_TPU_SERVE_CHAOS armed — injected exception at
# beat 2 (in-process retry from the beat snapshot), SIGKILL mid-batch
# at beat 4 (harness relaunch, resume_pending_batch under the original
# request ids, restart MTTR), then a poison request that bisection
# isolates. Every non-poison result must diff EXACTLY against its
# solo_reference via tools/diff_runs, and the recovered records must
# show resumed_from_beat < beats (windows re-executed < completed).
run serve_chaos 900 --serve-chaos JAX_PLATFORMS=cpu BENCH_BUDGET_S=840
# serve-trace acceptance (docs/18-Serve-Tracing.md): a traced real
# `shadow_tpu serve` subprocess (--trace-requests + --ledger-file) runs
# a packed 4-lane class with one chaos-injected retry
# (SHADOW_TPU_SERVE_CHAOS raise:beat=2, resume from the beat-1
# snapshot). Four gates: (a) every request's /trace span tree is
# complete (submit/queue_wait/pack_wait/retry/result + launch beats)
# and its queue+pack+run+retry decomposition tiles the recorded
# wall_ms, (b) the flight ledger round-trips through tools/serve_report
# with the retry/resume accounted, (c) the /metrics scrape carries
# per-class histogram exemplars and still passes check_openmetrics,
# (d) the merged tools/export_trace --serve-ledger view is one valid
# Chrome trace with serve wall (pid 2) + lane sim-time (pid 3) tracks
# and balanced flow arrows.
echo "=== serve_trace start $(date +%H:%M:%S)" >> "$S"
echo "{\"stage\": \"serve_trace\"}" >> "$R"
timeout 900 env JAX_PLATFORMS=cpu \
  SHADOW_TPU_SERVE_CHAOS="raise:beat=2" \
  python - >> "$R" 2>> "$S" <<'PYEOF'
import glob, json, os, re, shutil, signal, subprocess, sys, time
import urllib.request

from shadow_tpu.obs.servetrace import decompose, load_ledger
from shadow_tpu.tools.serve_client import request_docs, run_load
from shadow_tpu.tools.serve_report import reduce_ledger

LEDGER = "measure_serve_ledger.jsonl"
SNAP = "measure_serve_trace.snapshot.npz"
QF = "measure_serve_trace_queue.json"
DIR = "measure_served_trace"
shutil.rmtree(DIR, ignore_errors=True)
for p in [LEDGER, SNAP, QF] + glob.glob("serve_chaos.*.fired"):
    os.path.exists(p) and os.remove(p)

srv = subprocess.Popen(
    [sys.executable, "-m", "shadow_tpu", "serve", "--port", "0",
     "--max-lanes", "4", "--pack-deadline-ms", "600000",
     "--beat-windows", "2", "--snapshot-beats", "1",
     "--snapshot-path", SNAP, "--launch-retries", "1",
     "--queue-file", QF, "--trace-requests", "1024",
     "--ledger-file", LEDGER],
    stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
port = None
t0 = time.monotonic()
for line in srv.stderr:
    m = re.search(r"listening http://[^:]+:(\d+)/", line)
    if m:
        port = int(m.group(1))
        break
    if time.monotonic() - t0 > 120:
        break
assert port, "server never printed its listening line"
url = f"http://127.0.0.1:{port}"

docs = request_docs(4, mix="plain", hosts=8, stop_s=0.5)
report = run_load(url, docs, out_dir=DIR, timeout_s=600)
assert report["errors"] == 0, report
assert report.get("traced") == 4, report

# gate (a): span-tree completeness + the wall-time tiling acceptance
slack_ms = 50.0
for i in range(4):
    rid = f"r{i:06d}"
    with open(os.path.join(DIR, f"{rid}.trace.json")) as f:
        tree = json.load(f)
    names = [s["name"] for s in tree["spans"]]
    for required in ("submit", "queue_wait", "pack_wait", "retry",
                     "result"):
        assert required in names, (rid, required, names)
    launch_names = {s["name"] for ln in tree["launches"]
                    for s in ln["spans"]}
    assert {"cache", "pack", "beat", "confirm"} <= launch_names
    assert any(s["name"] == "resume" for ln in tree["launches"]
               for s in ln["spans"]), rid
    d = decompose(tree)
    assert d["status"] == "done" and d["total_ms"], (rid, d)
    accounted = (d["queue_wait_ms"] + d["pack_wait_ms"] + d["run_ms"]
                 + d["retry_ms"])
    assert accounted <= d["total_ms"] + slack_ms, (rid, d)
    assert accounted >= 0.5 * d["total_ms"] - slack_ms, (rid, d)

# gate (c): per-class exemplars in a valid scrape
scrape = urllib.request.urlopen(f"{url}/metrics", timeout=10).read()
with open("measure_serve_trace.metrics", "wb") as f:
    f.write(scrape)
chk = subprocess.run(
    [sys.executable, "-m", "shadow_tpu.tools.check_openmetrics",
     "measure_serve_trace.metrics"], capture_output=True, text=True)
assert chk.returncode == 0, chk.stdout
for fam in ("shadow_tpu_serve_queue_wait_ns_bucket",
            "shadow_tpu_serve_pack_wait_ns_bucket",
            "shadow_tpu_serve_beat_wall_ns_bucket"):
    assert fam.encode() in scrape, f"missing per-class family {fam}"
assert b" # {trace_id=" in scrape, "no exemplars rendered"

srv.send_signal(signal.SIGTERM)
rc = srv.wait(timeout=120)
assert rc == 0, f"drain exit code {rc} != 0"

# gate (b): ledger -> serve_report round-trip, retry/resume accounted
rep = subprocess.run(
    [sys.executable, "-m", "shadow_tpu.tools.serve_report", LEDGER],
    capture_output=True, text=True)
assert rep.returncode == 0, rep.stderr
cli_report = json.loads(rep.stdout)
header, records = load_ledger(LEDGER)
assert reduce_ledger(header, records) == cli_report
assert cli_report["requests"] == 4, cli_report
assert cli_report["retries"] == 1, cli_report
assert cli_report["chaos_injections"] == 1, cli_report
assert cli_report["pack_efficiency"] == 1.0, cli_report

# gate (d): the merged Chrome-trace view loads and is flow-balanced
from shadow_tpu.tools.export_trace import export
stats = export(None, "measure_serve_trace.json", ledger_path=LEDGER)
with open("measure_serve_trace.json") as f:
    doc = json.load(f)
evs = doc["traceEvents"]
assert {e["ph"] for e in evs} <= {"M", "i", "s", "f", "X"}
assert {2, 3} <= {e["pid"] for e in evs}
starts = [e for e in evs if e["ph"] == "s"]
ends = [e for e in evs if e["ph"] == "f"]
assert len(starts) == len(ends) > 0

print(json.dumps({
    "serve_trace_requests": 4, "serve_trace_tiled": True,
    "serve_trace_retries": cli_report["retries"],
    "serve_trace_ledger_records": len(records),
    "serve_trace_openmetrics": chk.stderr.strip(),
    "serve_trace_merged_events": stats["events"],
    "serve_trace_flows": stats["flows"],
    "serve_trace_drain_exit": rc,
}))
PYEOF
echo "=== serve_trace exit=$? $(date +%H:%M:%S)" >> "$S"
# serve elasticity: live lane-batch migration acceptance
# (docs/17-Serving.md "Elasticity") against a real
# `shadow_tpu serve --retry 2` subprocess. Wave 1: 8 requests packed at
# --max-lanes 8, devloss:beat=2 exits the child 77 (peer-lost), the
# retry wrapper relaunches at the halved width and resume_pending_batch
# splits the 8-lane snapshot into two 4-lane parts that finish under
# the ORIGINAL request ids (migration MTTR). Wave 2: 4 longer requests
# at the shrunken width, resize:beat=7,lanes=8 grows the mesh back in
# process mid-batch. Gates: both waves drift-0 vs solo_reference via
# tools/diff_runs, /healthz walks the degraded->restored capacity arc,
# /metrics carries serve_migrations_total >= 2 and the
# serve_mesh_generation gauge, and one SIGTERM at the wrapper drains
# child + wrapper to exit 0 with the retry report (attempts=2,
# recoveries=1, mttr_s) on stderr.
run serve_elastic 900 --serve-elastic JAX_PLATFORMS=cpu BENCH_BUDGET_S=840
# perf smoke: a small CPU-backend PHOLD, a small tgen TCP workload
# under the frontier drain, and an 8-lane PHOLD fleet, each against its
# checked-in PERF_FLOOR.json floor — fails (exit 1) when any of the
# three events/s numbers regresses more than 30%.
# Together with the lint + hlo_audit stage below this is the no-TPU
# regression lane; refresh the floors deliberately with
# `PERF_SMOKE_UPDATE=1 python bench.py --perf-smoke`.
echo "=== perf_smoke start $(date +%H:%M:%S)" >> "$S"
echo "{\"stage\": \"perf_smoke\"}" >> "$R"
timeout 900 env JAX_PLATFORMS=cpu python bench.py --perf-smoke \
  >> "$R" 2>> "$S"
echo "=== perf_smoke exit=$? $(date +%H:%M:%S)" >> "$S"
# static-analysis gate: shadowlint over the package plus the HLO
# contract audit of every model config. The CLI's JSON report is the
# stage's $R line; a nonzero exit means new findings or a budget breach.
echo "=== lint start $(date +%H:%M:%S)" >> "$S"
echo "{\"stage\": \"lint\"}" >> "$R"
# the forced 8-device count lets the phold_sharded contract lower (it
# skips, not fails, when fewer devices are present)
timeout 1200 env JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python -m shadow_tpu.tools.lint \
  --hlo-audit all --output measure_lint.json 2>> "$S" \
  && cat measure_lint.json >> "$R"
echo "=== lint exit=$? $(date +%H:%M:%S)" >> "$S"
# dataflow audit: the compiled-program gate — donation/aliasing over
# every production window-loop jit, peak-live estimates vs the
# checked-in MEM_BUDGETS.json, and the harvest host-transfer census
# ("exactly one fetch per segment"). Refresh budgets deliberately with
# `python -m shadow_tpu.tools.lint --mem-audit --update-baseline`.
echo "=== dataflow_audit start $(date +%H:%M:%S)" >> "$S"
echo "{\"stage\": \"dataflow_audit\"}" >> "$R"
timeout 1200 env JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python -m shadow_tpu.tools.lint \
  --donation-audit --mem-audit --output measure_dataflow.json 2>> "$S" \
  && cat measure_dataflow.json >> "$R"
echo "=== dataflow_audit exit=$? $(date +%H:%M:%S)" >> "$S"
# TPU-readiness gate: tile padding waste, layout churn, hot-loop
# gather/scatter placement, merge-kernel VMEM fit, and the roofline
# drain economics — every lowering checked against the committed
# TPU_READINESS.json (new waste/churn/VMEM or a predicted-floor drop
# fails the stage). Refresh deliberately with
# `python -m shadow_tpu.tools.lint --tpu-audit all --update-baseline`.
echo "=== tpu_readiness start $(date +%H:%M:%S)" >> "$S"
echo "{\"stage\": \"tpu_readiness\"}" >> "$R"
timeout 1200 env JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python -m shadow_tpu.tools.lint \
  --tpu-audit all --output measure_tpu_readiness.json 2>> "$S" \
  && cat measure_tpu_readiness.json >> "$R"
echo "=== tpu_readiness exit=$? $(date +%H:%M:%S)" >> "$S"
# sanitizer smoke: interposer + driver as one ASan/UBSan executable
# (the dlmopen plugin path cannot host a sanitized DSO — see
# shadow_tpu/proc/native.py SANITIZE_FLAGS)
echo "=== asan_smoke start $(date +%H:%M:%S)" >> "$S"
echo "{\"stage\": \"asan_smoke\"}" >> "$R"
timeout 300 python -c '
import json
from shadow_tpu.proc import native
r = native.sanitizer_smoke()
print(json.dumps({"ok": r["ok"], "returncode": r["returncode"]}))
raise SystemExit(0 if r["ok"] else 1)
' >> "$R" 2>> "$S"
echo "=== asan_smoke exit=$? $(date +%H:%M:%S)" >> "$S"
echo ALL_DONE >> "$S"
