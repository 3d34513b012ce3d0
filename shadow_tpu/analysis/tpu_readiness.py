"""TPU-readiness auditor over the lowered production programs.

hlo_audit pins op *counts* and memory.py pins HBM *liveness*; neither
says whether the programs will perform on real silicon. This module
walks every production lowering (all hlo_audit contract configs —
chained, `_frontier`, `_fleet`, `_sharded`, the serve warm-path fleet
step — plus the harvest extraction jits) and computes the three
static signals ROADMAP item 1's campaign needs before chip time:

- **tile report**: per-op (sublane, 128)-tile padding waste by dtype
  (`analysis.chips` geometry: f32/i64-as-2xi32 (8,128), bf16
  (16,128), i8 (32,128)). A shape like [H, 3] wastes 125/128 of
  every vector register; the report names the worst offenders with
  their line and region path.
- **layout-churn census**: transpose / reshape / bitcast_convert
  instances and bytes, split hot (inside a `while` body) vs total —
  each hot churn op is a relayout between every round.
- **placement report**: gather / scatter / dynamic_slice /
  dynamic_update_slice relative to the window `while` body, hot ones
  flagged with their region path (`Module.ops_with_path`) — the ops
  whose TPU lowering quality decides the drain's round time.

Findings land in the checked-in `analysis/TPU_READINESS.json`
baseline: waste %, churn counts, hot-op counts, and the cost model's
predicted events/s floors (`analysis.costmodel`). The audit fails on
regressions against the baseline (more waste, new hot ops, a floor
dropping below tolerance) and on a CPU cost-model prediction that
disagrees with BENCH_r07's measured chained-vs-frontier direction;
improvements land silently and show up in `--diff`. Refresh deliberately with
``python -m shadow_tpu.tools.lint --tpu-audit all --update-baseline``.
"""

from __future__ import annotations

import json
import os
from typing import Iterable

from shadow_tpu.analysis import costmodel, hlo_graph
from shadow_tpu.analysis.chips import chip as chip_row
from shadow_tpu.analysis.costmodel import parse_tensor

BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "TPU_READINESS.json")

# Tolerances: waste may drift this many percentage points before the
# audit fires (tiny-build shapes make the % jumpy at the margin);
# predicted floors may drop to this fraction of the baseline (the
# cost model's own error bars are wider than its round-to-round
# jitter — see docs/10-Static-Analysis.md).
WASTE_TOL_PCT = 0.5
FLOOR_TOL = 0.8

# The data-movement ops whose hot-loop placement the report pins.
PLACEMENT_OPS = ("gather", "scatter", "dynamic_slice",
                 "dynamic_update_slice")
CHURN_OPS = ("transpose", "reshape", "bitcast_convert")

# The harvest extraction jits ride along with the contract configs:
# same parser, same tile math, no roofline (no window loop inside).
EXTRA_CONFIGS = ("harvest_full", "harvest_light")


def ready_configs() -> list[str]:
    from shadow_tpu.analysis import hlo_audit

    return sorted(hlo_audit.CONTRACTS) + list(EXTRA_CONFIGS)


# ------------------------------------------------------------ lowering


def lower_config(name: str) -> tuple[str, int]:
    """(lowered text, host rows) for one config. Host rows feed the
    cost model's linear scale-up."""
    if name in EXTRA_CONFIGS:
        from shadow_tpu.analysis.donation import _sim_tiny
        from shadow_tpu.runtime.harvest import HeartbeatHarvest

        sim = _sim_tiny()
        h = HeartbeatHarvest(sim)
        text = h._build(name == "harvest_full").lower(
            sim.state0).as_text()
        return text, _host_rows(sim.state0)

    from shadow_tpu.analysis import hlo_audit

    run, state, stop = hlo_audit._build(name)
    return hlo_audit.lower_text(run, state, stop), _host_rows(state)


def _host_rows(state) -> int:
    """Host-row count of a build's queue arrays ([H, C] solo,
    [L, H, C] fleet) — the axis the cost model scales linearly."""
    try:
        return int(state.queues.time.shape[-2])
    except AttributeError:
        return 1


# ------------------------------------------------------------- reports


def tile_report(module: hlo_graph.Module, *, chip_name: str = "v5e",
                worst: int = 5) -> dict:
    """Logical vs tile-padded bytes over every reachable op result.
    Geometry is identical across the TPU rows (the sublane map depends
    only on element width), so one report serves all three."""
    c = chip_row(chip_name)
    logical = padded = 0
    by_dtype: dict[str, dict] = {}
    offenders: list[tuple[int, dict]] = []
    for op, path in module.ops_with_path():
        if op.dialect not in ("stablehlo", "mhlo", "chlo"):
            continue
        for t in op.result_types:
            parsed = parse_tensor(t)
            if parsed is None:
                continue
            dims, dtype = parsed
            eb = hlo_graph.dtype_bytes(dtype)
            if not eb:
                continue
            lb = costmodel._elems(dims) * eb
            pb = c.padded_bytes(dims, eb)
            logical += lb
            padded += pb
            d = by_dtype.setdefault(
                dtype, {"logical_bytes": 0, "padded_bytes": 0})
            d["logical_bytes"] += lb
            d["padded_bytes"] += pb
            if pb > lb:
                offenders.append((pb - lb, {
                    "op": op.short, "line": op.line, "type": t.strip(),
                    "waste_bytes": pb - lb, "path": path,
                }))
    offenders.sort(key=lambda x: (-x[0], x[1]["line"]))
    for d in by_dtype.values():
        d["waste_pct"] = _waste_pct(d["logical_bytes"],
                                    d["padded_bytes"])
    return {
        "logical_bytes": logical,
        "padded_bytes": padded,
        "waste_pct": _waste_pct(logical, padded),
        "by_dtype": {k: by_dtype[k] for k in sorted(by_dtype)},
        "worst": [o for _, o in offenders[:worst]],
    }


def _waste_pct(logical: int, padded: int) -> float:
    return round(100.0 * (padded - logical) / padded, 2) if padded else 0.0


def churn_report(module: hlo_graph.Module) -> dict:
    """Layout-churn census: relayout ops, hot (inside any while body)
    vs total, with the bytes they move."""
    out = {k: {"count": 0, "hot": 0, "bytes": 0} for k in CHURN_OPS}
    for op, path in module.ops_with_path():
        if op.short not in out:
            continue
        rec = out[op.short]
        rec["count"] += 1
        rec["bytes"] += op.result_bytes()
        if _is_hot(path):
            rec["hot"] += 1
    return out


def placement_report(module: hlo_graph.Module, *, flag: int = 8) -> dict:
    """Gather/scatter/dynamic-slice placement relative to the window
    while body; hot instances carry their region path."""
    out = {k: {"count": 0, "hot": 0, "flagged": []}
           for k in PLACEMENT_OPS}
    for op, path in module.ops_with_path():
        if op.short not in out:
            continue
        rec = out[op.short]
        rec["count"] += 1
        if _is_hot(path):
            rec["hot"] += 1
            if len(rec["flagged"]) < flag:
                rec["flagged"].append(
                    {"line": op.line, "path": path,
                     "type": (op.result_types[0].strip()
                              if op.result_types else "")})
    return out


def _is_hot(path: str) -> bool:
    return "while@" in path and ".do" in path


# ------------------------------------------------------------ auditing


def audit_config(name: str) -> dict:
    """Full readiness report for one config."""
    text, rows = lower_config(name)
    module = hlo_graph.parse_module(text)
    return {
        "hosts": rows,
        "tile": tile_report(module),
        "churn": churn_report(module),
        "placement": placement_report(module),
        "_module": module,  # stripped before serialization
    }


def audit_all(names: Iterable[str] | None = None) -> dict:
    """Audit every config + the drain economics, checked against the
    checked-in baseline. Structure mirrors hlo_audit.audit_all: each
    entry carries ok/violations; `drain_economics` carries the cost
    model's predictions and the BENCH_r07 direction check."""
    names = list(names) if names else ready_configs()
    baseline = load_baseline()
    out: dict = {}
    modules: dict[str, hlo_graph.Module] = {}
    hosts: dict[str, int] = {}
    for name in names:
        try:
            rep = audit_config(name)
        except RuntimeError as e:
            # the sharded config needs 8 devices; skipped, not failed
            out[name] = {"ok": True, "skipped": str(e),
                         "violations": []}
            continue
        modules[name] = rep.pop("_module")
        hosts[name] = rep["hosts"]
        bl = baseline.get("configs", {}).get(name)
        violations = check_config(name, rep, bl)
        out[name] = {"ok": not violations, "violations": violations,
                     **rep}

    econ = costmodel.drain_report(modules, hosts)
    evio: list[str] = []
    for model, rec in econ.items():
        if rec.get("cpu_agrees_with_bench") is False:
            evio.append(
                f"drain_economics: {model} cost model predicts "
                f"`{rec['winner']['cpu']}` wins under CPU parameters "
                f"but BENCH_r07 measured "
                f"`{rec['measured_cpu_winner']}` — recalibrate "
                f"analysis/chips.py before trusting the TPU ranking")
    # predicted floors ride on the drain-pair configs
    for model, (cfg_c, cfg_f) in costmodel.DRAIN_PAIRS.items():
        rec = econ.get(model)
        if rec is None:
            continue
        for drain, cfg in (("chained", cfg_c), ("frontier", cfg_f)):
            if cfg not in out or "skipped" in out[cfg]:
                continue
            floors = {cn: rec["per_chip"][cn][drain]["events_per_s"]
                      for cn in rec["per_chip"]}
            out[cfg]["floors"] = floors
            bl = baseline.get("configs", {}).get(cfg, {})
            for cn, v in (bl.get("floors") or {}).items():
                got = floors.get(cn)
                if got is not None and got < v * FLOOR_TOL:
                    out[cfg]["violations"].append(
                        f"{cfg}: predicted {cn} floor {got:.1f} "
                        f"events/s fell below {FLOOR_TOL:.0%} of the "
                        f"baseline {v:.1f} — the drain round got "
                        f"statically slower; investigate or re-pin "
                        f"with --update-baseline")
                    out[cfg]["ok"] = False
    out["drain_economics"] = {"ok": not evio, "violations": evio,
                              **econ}
    return out


def check_config(name: str, rep: dict, bl: dict | None) -> list[str]:
    """Baseline regressions for one config's report; [] means clean."""
    if bl is None:
        return [f"{name}: no entry in TPU_READINESS.json — pin it with "
                f"--tpu-audit all --update-baseline"]
    v: list[str] = []
    waste, bwaste = rep["tile"]["waste_pct"], bl["tile"]["waste_pct"]
    if waste > bwaste + WASTE_TOL_PCT:
        v.append(f"{name}: tile padding waste {waste}% exceeds "
                 f"baseline {bwaste}% — a padded-to-waste shape "
                 f"entered the lowering (see tile.worst)")
    for op_name, rec in rep["churn"].items():
        brec = bl["churn"].get(op_name, {"count": 0, "hot": 0})
        for k in ("count", "hot"):
            if rec[k] > brec[k]:
                v.append(f"{name}: {rec[k]}x {op_name} "
                         f"({k}) exceeds baseline {brec[k]} — layout "
                         f"churn crept into the lowering")
    for op_name, rec in rep["placement"].items():
        bhot = bl["hot_ops"].get(op_name, 0)
        if rec["hot"] > bhot:
            v.append(f"{name}: {rec['hot']}x hot-loop {op_name} "
                     f"exceeds baseline {bhot} — a new {op_name} "
                     f"entered the window while body "
                     f"(placement.{op_name}.flagged has the paths)")
    return v


# ------------------------------------------------------------- baseline


def load_baseline(path: str = BASELINE_PATH) -> dict:
    if not os.path.exists(path):
        return {}
    with open(path, "r", encoding="utf-8") as fh:
        return json.load(fh)


def save_baseline(results: dict, path: str = BASELINE_PATH) -> dict:
    """Distill an audit_all result into the checked-in baseline (the
    enforced numbers only — worst-offender lists and per-chip detail
    stay in the full report)."""
    configs: dict[str, dict] = {}
    for name, rep in results.items():
        if name == "drain_economics" or "skipped" in rep \
                or "tile" not in rep:
            continue
        entry = {
            "tile": {"logical_bytes": rep["tile"]["logical_bytes"],
                     "padded_bytes": rep["tile"]["padded_bytes"],
                     "waste_pct": rep["tile"]["waste_pct"]},
            "churn": {k: {"count": r["count"], "hot": r["hot"]}
                      for k, r in rep["churn"].items()},
            "hot_ops": {k: r["hot"]
                        for k, r in rep["placement"].items()},
        }
        if "floors" in rep:
            entry["floors"] = rep["floors"]
        configs[name] = entry
    econ = results.get("drain_economics", {})
    winners = {m: rec.get("winner", {})
               for m, rec in econ.items()
               if isinstance(rec, dict) and "winner" in rec}
    data = {
        "version": 1,
        "comment": "TPU-readiness baseline (tile waste / layout churn "
                   "/ hot-loop placement / predicted events-per-s floors) over the lowered "
                   "production programs; regenerate with `python -m "
                   "shadow_tpu.tools.lint --tpu-audit all "
                   "--update-baseline`",
        "configs": {k: configs[k] for k in sorted(configs)},
        "winners": winners,
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(data, fh, indent=1)
        fh.write("\n")
    return data


def report_json(results: dict) -> dict:
    """The audit result with only JSON-safe content (drop nothing
    today — modules are already stripped in audit_all)."""
    return results
