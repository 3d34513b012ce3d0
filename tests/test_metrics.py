"""Live telemetry plane (shadow_tpu/obs/metrics.py + server.py): the
metrics registry, OpenMetrics exporter, flight recorder, health state
machine, and their CLI wiring (docs/14-Telemetry.md).

The contracts under test: the exporter is deterministic between ingests, syntactically valid
OpenMetrics, and reconciles exactly with the tracker's [metrics]
heartbeat rows and the end-of-run summary — single-shard and on the
forced 8-device mesh. With --metrics off, the harvest extraction must
lower byte-identically (the zero-cost pin, via the shared auditor
helper). Forced pressure exits must ship the flight-recorder ring in
their diagnostic bundle.
"""

import glob
import io
import json
import textwrap
import urllib.error
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.config import parse_config
from shadow_tpu.obs.metrics import (
    METRICS_HEADER,
    SPECS,
    FlightRecorder,
    HealthState,
    MetricsRegistry,
    validate_openmetrics,
)
from shadow_tpu.obs.server import MetricsServer
from shadow_tpu.sim import build_simulation
from shadow_tpu.tools.parse_shadow import parse_lines

TOPO = """<graphml xmlns="http://graphml.graphdrawing.org/xmlns">
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
</graphml>"""

# 16 PHOLD hosts through one 50ms self-edge: small enough to run in
# seconds on the CPU backend, busy enough that an 8-shard mesh carries
# cross-shard traffic every window (the chaos-smoke shape)
PHOLD_CFG = textwrap.dedent(f"""\
<shadow stoptime="6">
  <topology><![CDATA[{TOPO}]]></topology>
  <plugin id="phold" path="shadow-plugin-test-phold.so" />
  <host id="peer" quantity="16">
    <process plugin="phold" starttime="1"
      arguments="basename=peer quantity=16 load=4" />
  </host>
</shadow>
""")


# ------------------------------------------------------------- registry


def test_specs_are_a_complete_catalog():
    names = [s.name for s in SPECS]
    assert len(names) == len(set(names))
    for s in SPECS:
        assert s.name.startswith("shadow_tpu_")
        assert s.kind in ("counter", "gauge")
        assert s.help and s.source  # provenance is part of the contract


def test_registry_ingest_is_cumulative_not_additive():
    reg = MetricsRegistry(version="1.2.3", n_shards=4)
    reg.ingest({"now_ns": 5_000_000_000, "executed": 10, "windows": 2,
                "sweeps": 3, "queue_drops": 1},
               extras={"rx_bytes": 100, "tx_bytes": 90, "net_dropped": 0,
                       "fault_dropped": 0, "quarantined": 0,
                       "cross_shard": 7},
               fill=0.5)
    reg.ingest({"now_ns": 10_000_000_000, "executed": 25},
               extras={"rx_bytes": 250}, fill=0.25)
    t = reg.totals()
    # harvest counters are already cumulative device sums: the second
    # ingest REPLACES, it must not add (25, not 35)
    assert t["shadow_tpu_events"] == 25
    assert t["shadow_tpu_rx_bytes"] == 250
    assert t["shadow_tpu_cross_shard_packets"] == 7
    assert t["shadow_tpu_sim_seconds"] == 10
    assert t["shadow_tpu_queue_fill"] == 0.25
    assert t["shadow_tpu_heartbeats"] == 2
    assert t["shadow_tpu_shards"] == 4


def test_registry_finalize_aligns_with_summary():
    reg = MetricsRegistry()
    reg.ingest({"executed": 10, "now_ns": 1_000_000_000})
    reg.finalize({"events": 42, "windows": 6, "rx_bytes": 1024,
                  "sim_seconds": 9.0,
                  "pressure": {"spilled": 5, "refilled": 5, "resident": 0}})
    t = reg.totals()
    assert t["shadow_tpu_events"] == 42
    assert t["shadow_tpu_windows"] == 6
    assert t["shadow_tpu_rx_bytes"] == 1024
    assert t["shadow_tpu_sim_seconds"] == 9
    assert t["shadow_tpu_spilled"] == 5
    assert t["shadow_tpu_pressure_refills"] == 5


def test_metrics_row_matches_header_shape():
    cols = METRICS_HEADER.rsplit("] ", 1)[1].split(",")
    reg = MetricsRegistry()
    reg.ingest({"executed": 7}, extras={"rx_bytes": 64, "tx_bytes": 64},
               fill=0.125)
    row = reg.metrics_row(30)
    parts = row.split(",")
    assert len(parts) == len(cols)
    assert parts[0] == "30"
    assert parts[cols.index("events")] == "7"
    assert parts[cols.index("rx-bytes")] == "64"
    assert float(parts[cols.index("queue-fill")]) == 0.125
    # integers render bare so the CSV reconciles with int() parsing
    assert "." not in parts[cols.index("events")]


def test_observe_folds_host_side_sources():
    class _Prof:
        def summary(self):
            return {"phases": {"drain": {"count": 4, "total_s": 0.5},
                               "pump": {"count": 4, "total_s": 0.25}}}

    reg = MetricsRegistry()
    h = HealthState()
    h.pressure_event()
    reg.observe(watchdog_margin_s=12.5, checkpoints=3, health=h,
                profiler=_Prof())
    t = reg.totals()
    assert t["shadow_tpu_watchdog_margin_seconds"] == 12.5
    assert t["shadow_tpu_checkpoints"] == 3
    assert t["shadow_tpu_health"] == 1
    assert t["shadow_tpu_phase_seconds{phase=drain}"] == 0.5
    text = reg.render()
    assert 'shadow_tpu_phase_seconds_total{phase="drain"} 0.5' in text
    assert 'shadow_tpu_phase_calls_total{phase="pump"} 4' in text


# ------------------------------------------------------------- exporter


def test_render_is_deterministic_and_valid():
    reg = MetricsRegistry(version="0.1.0")
    reg.ingest({"executed": 123, "now_ns": 2_500_000_000},
               extras={"rx_bytes": 8192}, fill=0.75)
    a, b = reg.render(), reg.render()
    assert a == b  # no scrape-varying state in the exposition
    assert validate_openmetrics(a) == []
    assert a.endswith("# EOF\n")
    assert "shadow_tpu_events_total 123" in a
    assert 'shadow_tpu_build_info{version="0.1.0"} 1' in a
    # every declared family renders its TYPE/HELP pair
    for s in SPECS:
        assert f"# TYPE {s.name} {s.kind}" in a
        assert f"# HELP {s.name} " in a


def test_validate_openmetrics_catches_malformations():
    assert validate_openmetrics("shadow_tpu_x 1\n")  # no TYPE, no EOF
    bad_counter = ("# TYPE f counter\n# HELP f h\nf 1\n# EOF\n")
    assert any("_total" in e for e in validate_openmetrics(bad_counter))
    bad_gauge = ("# TYPE g gauge\n# HELP g h\ng_total 1\n# EOF\n")
    assert any("must not" in e for e in validate_openmetrics(bad_gauge))
    dup = ("# TYPE f counter\n# HELP f h\nf_total 1\nf_total 2\n# EOF\n")
    assert any("duplicate" in e for e in validate_openmetrics(dup))
    no_eof = "# TYPE g gauge\n# HELP g h\ng 1\n"
    assert any("EOF" in e for e in validate_openmetrics(no_eof))
    ok = "# TYPE g gauge\n# HELP g h\ng{a=\"b\"} 1.5\n# EOF\n"
    assert validate_openmetrics(ok) == []


# --------------------------------------------------------------- health


def test_health_state_machine():
    h = HealthState()
    assert h.code() == 0 and h.http_status() == 200
    assert h.snapshot() == {"status": "ok", "causes": [],
                            "exit_code": None}
    # a comfortable margin is not a near-miss
    assert h.observe_margin(9.0, timeout_s=10.0) is False
    assert h.code() == 0
    # under NEAR_MISS_FRAC of the deadline degrades (sticky) — still 200
    assert h.observe_margin(2.0, timeout_s=10.0) is True
    assert h.code() == 1 and h.http_status() == 200
    h.pressure_event()
    h.relaunch(2)
    snap = h.snapshot()
    assert snap["status"] == "degraded"
    assert snap["causes"] == ["watchdog-near-miss", "pressure",
                              "retry-relaunch-2"]
    # an abnormal exit code chosen -> failed, 503
    h.fail(76)
    assert h.code() == 2 and h.http_status() == 503
    assert h.snapshot()["exit_code"] == 76


def test_health_no_watchdog_never_degrades():
    h = HealthState()
    assert h.observe_margin(0.0, timeout_s=0.0) is False
    assert h.code() == 0


# ------------------------------------------------------ flight recorder


def test_flight_recorder_is_a_bounded_json_ring():
    fr = FlightRecorder(capacity=4)
    for i in range(10):
        fr.record_heartbeat(i * 1_000_000_000,
                            {"executed": np.int64(i * 5), "windows": i,
                             "profile": {"dropped": "nested"}})
    fr.record_event("checkpoint", sim_seconds=3.0, path=object())
    snap = fr.snapshot()
    assert snap["capacity"] == 4
    assert len(snap["heartbeats"]) == 4  # ring keeps only the last K
    assert snap["heartbeats"][-1]["executed"] == 45
    assert snap["heartbeats"][-1]["sim_seconds"] == 9.0
    assert "profile" not in snap["heartbeats"][-1]
    assert snap["events"][0]["kind"] == "checkpoint"
    assert "path" not in snap["events"][0]  # non-scalars are dropped
    json.dumps(snap)  # numpy scalars were converted: bundle-safe


# ---------------------------------------------------------- HTTP server


def _get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10) as r:
            return r.status, r.read().decode(), r.headers.get_content_type()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), e.headers.get_content_type()


def test_server_endpoints():
    reg = MetricsRegistry(version="0.1.0")
    reg.ingest({"executed": 11, "now_ns": 1_000_000_000})
    health = HealthState()
    fr = FlightRecorder()
    fr.record_event("xprof-start", sim_seconds=1.0)
    stream = io.StringIO()
    srv = MetricsServer(reg, health, fr, port=0, _stream=stream).start()
    try:
        assert f":{srv.port}/metrics" in stream.getvalue()
        st, a, ct = _get(srv.port, "/metrics")
        _, b, _ = _get(srv.port, "/metrics")
        assert st == 200 and a == b  # scrape determinism over HTTP
        assert ct == "application/openmetrics-text"
        assert validate_openmetrics(a) == []
        assert "shadow_tpu_events_total 11" in a

        st, body, ct = _get(srv.port, "/healthz")
        assert st == 200 and ct == "application/json"
        assert json.loads(body)["status"] == "ok"

        st, body, _ = _get(srv.port, "/summary.json")
        s = json.loads(body)
        assert st == 200
        assert s["totals"]["shadow_tpu_events"] == 11
        assert s["health"]["status"] == "ok"
        assert s["flight_recorder"]["events"] == 1
        assert s["scrapes"]["metrics"] == 2

        assert _get(srv.port, "/nope")[0] == 404

        # exit-code-aware: a failure flips /healthz to 503; /metrics
        # keeps serving the final counters for the post-mortem scrape
        health.fail(70)
        st, body, _ = _get(srv.port, "/healthz")
        assert st == 503 and json.loads(body)["exit_code"] == 70
        assert _get(srv.port, "/metrics")[0] == 200
    finally:
        srv.close()
    # closed: the port no longer answers
    with pytest.raises((urllib.error.URLError, ConnectionError, OSError)):
        urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=2)


# ------------------------------------------------------------- zero cost


def test_metrics_off_is_zero_cost():
    """With --metrics off, the harvest extraction lowers byte-identically
    to a build that never heard of the telemetry plane; on, it gains the
    extras reductions (non-vacuity). Checked through the shared auditor
    helper on the real extraction jits."""
    from shadow_tpu.analysis.hlo_audit import assert_zero_cost
    from shadow_tpu.runtime.harvest import HeartbeatHarvest

    cfg = parse_config(PHOLD_CFG)
    sim_b = build_simulation(cfg, seed=3)
    sim_off = build_simulation(cfg, seed=3)
    sim_on = build_simulation(cfg, seed=3)

    def extract_fn(sim, metrics):
        h = HeartbeatHarvest(sim, metrics=metrics)
        f = h._build(True)
        return lambda st, stop, f=f: f(st)  # auditor passes (state, stop)

    assert_zero_cost(
        (extract_fn(sim_b, None), sim_b.state0),
        (extract_fn(sim_off, None), sim_off.state0),
        (extract_fn(sim_on, MetricsRegistry()), sim_on.state0),
        jnp.int64(0),
    )


# ------------------------------------------------------------ CLI wiring


def _run_cli(capsys, argv):
    from shadow_tpu.cli import main

    rc = main(argv)
    out = capsys.readouterr().out
    summary = {}
    for line in reversed(out.strip().splitlines()):
        if line.startswith("{"):
            summary = json.loads(line)
            break
    return rc, out, summary


@pytest.mark.slow  # ~14s CLI run; the in-process registry/OpenMetrics
# reconciliation pins stay in tier-1
def test_cli_metrics_rows_reconcile_with_summary(capsys):
    rc, out, summary = _run_cli(capsys, [
        "--test", "--stoptime", "8", "--heartbeat-frequency", "4",
        "--metrics",
    ])
    assert rc == 0
    assert METRICS_HEADER in out
    met = parse_lines(out.splitlines())["metrics"]
    assert len(met["ticks"]) >= 2
    assert met["heartbeats"] == sorted(met["heartbeats"])  # monotone
    # the last [metrics] row IS the registry the exporter serves; it
    # must equal the end-of-run summary exactly
    for key in ("events", "queue_drops", "net_dropped", "fault_dropped",
                "cross_shard_packets", "rx_bytes", "tx_bytes"):
        assert met[key][-1] == int(summary[key]), key
    assert summary["rx_bytes"] > 0


def test_cli_without_metrics_emits_no_metrics_section(capsys):
    rc, out, _ = _run_cli(capsys, [
        "--test", "--stoptime", "4", "--heartbeat-frequency", "2",
    ])
    assert rc == 0
    assert "[metrics" not in out


def test_sharded_metrics_reconcile_with_single_shard(tmp_path, capsys):
    """The acceptance reconciliation on a forced multi-shard mesh: the
    registry's totals on --mesh 8 equal the single-device run's — every
    exported reduction is a global sum, so sharding must not change a
    single counter (cross_shard_packets excepted: it measures the mesh
    itself)."""
    cfg = tmp_path / "phold.xml"
    cfg.write_text(PHOLD_CFG)
    rc1, out1, sum1 = _run_cli(capsys, [
        str(cfg), "--metrics", "--heartbeat-frequency", "3",
        "--overflow", "drop", "--seed", "1",
    ])
    rc8, out8, sum8 = _run_cli(capsys, [
        str(cfg), "--metrics", "--heartbeat-frequency", "3",
        "--overflow", "drop", "--seed", "1", "--mesh", "8",
    ])
    assert rc1 == 0 and rc8 == 0
    for key in ("events", "windows", "queue_drops", "net_dropped",
                "fault_dropped", "rx_bytes", "tx_bytes"):
        assert int(sum1[key]) == int(sum8[key]), key
    m1 = parse_lines(out1.splitlines())["metrics"]
    m8 = parse_lines(out8.splitlines())["metrics"]
    assert m1["ticks"] and m8["ticks"]
    # exporter-vs-exporter: the final cumulative rows agree too
    for key in ("events", "queue_drops", "rx_bytes", "tx_bytes"):
        assert m1[key][-1] == m8[key][-1] == int(sum1[key]), key
    assert sum8["cross_shard_packets"] > 0  # the mesh actually exchanged


def test_exit76_bundle_ships_flight_recorder(tmp_path):
    from shadow_tpu.cli import main
    from shadow_tpu.runtime import EXIT_PRESSURE

    rc = main([
        "--test", "--stoptime", "4", "--capacity", "4",
        "--overflow", "strict", "--heartbeat-frequency", "0.2",
        "--diag-dir", str(tmp_path),
    ])
    assert rc == EXIT_PRESSURE == 76
    bundles = glob.glob(str(tmp_path / "*.pressure.*.json"))
    assert len(bundles) == 1
    with open(bundles[0]) as f:
        b = json.load(f)
    fr = b["flight_recorder"]
    # the black box ships its own recent history: at least the last 8
    # heartbeat summaries leading into the trip
    assert len(fr["heartbeats"]) >= 8
    sims = [hb["sim_seconds"] for hb in fr["heartbeats"]]
    assert sims == sorted(sims)
    assert all("executed" in hb for hb in fr["heartbeats"])


def test_xprof_flag_validation():
    from shadow_tpu.cli import main

    assert main(["--test", "--stoptime", "1", "--xprof", "nonsense"]) == 2
    assert main(["--test", "--stoptime", "1", "--xprof", "5:2"]) == 2
    assert main(["--test", "--stoptime", "1", "--xprof", "3:3"]) == 2


# ------------------------------------------------------- parser & plots


def test_parse_lines_tolerates_interleaved_sections():
    lines = [
        "x [shadow-heartbeat] [metrics] 20,50,0,0,0,0,900,900,0.5,2",
        "x [shadow-heartbeat] [node] 20,a,0,0,0,0,0,0,0,0,0,30,0,0",
        "x [shadow-heartbeat] [supervisor] 20,4,1.0,10.0,,1",
        # an earlier tick arriving later (resumed / concatenated logs)
        "x [shadow-heartbeat] [metrics] 10,20,0,0,0,0,400,400,0.25,1",
        "x [shadow-heartbeat] [node] 10,a,0,0,0,0,0,0,0,0,0,20,0,0",
        "x [shadow-heartbeat] [supervisor] 10,2,1.0,10.0,,0",
    ]
    stats = parse_lines(lines)
    assert stats["metrics"]["ticks"] == [10, 20]
    assert stats["metrics"]["events"] == [20, 50]
    assert stats["metrics"]["queue_fill"] == [0.25, 0.5]
    assert stats["metrics"]["heartbeats"] == [1, 2]
    assert stats["nodes"]["a"]["ticks"] == [10, 20]
    assert stats["nodes"]["a"]["events_executed"] == [20, 30]
    assert stats["supervisor"]["ticks"] == [10, 20]
    assert stats["supervisor"]["checkpoints_written"] == [0, 1]


def test_plot_shadow_metrics_figure_is_conditional(tmp_path):
    from shadow_tpu.tools.plot_shadow import make_figures

    node = {"ticks": [10, 20], "events_executed": [20, 30],
            **{f: [0, 0] for f in (
                "bytes_payload_recv", "bytes_payload_send",
                "bytes_wire_recv", "bytes_wire_send",
                "packets_recv", "packets_send",
                "bytes_header_recv", "bytes_header_send",
                "retrans_segments", "queue_drops", "tail_drops")}}
    base = {"nodes": {"a": node}}
    assert len(make_figures(dict(base), str(tmp_path), "png")) == 4
    with_metrics = dict(base)
    with_metrics["metrics"] = {
        "ticks": [10, 20], "events": [20, 50], "queue_drops": [0, 0],
        "net_dropped": [0, 0], "fault_dropped": [0, 0],
        "cross_shard_packets": [0, 0], "rx_bytes": [400, 900],
        "tx_bytes": [400, 900], "queue_fill": [0.25, 0.5],
        "heartbeats": [1, 2],
    }
    paths = make_figures(with_metrics, str(tmp_path), "png")
    assert len(paths) == 5
    assert any(p.endswith("shadow_tpu.metrics.png") for p in paths)
