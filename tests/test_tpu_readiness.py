"""TPU-readiness auditor + roofline cost model (analysis/chips.py,
costmodel.py, tpu_readiness.py).

Two pinned regression injections (the acceptance contract): a
padded-to-waste shape must move the tile report past its baseline, and
an extra gather inside the window while body must trip the placement
check with its region path. Plus the cost model's ground-truth anchor: under
the CPU chip row its chained-vs-frontier prediction must agree in
direction with BENCH_r07's measured wall times for tor and tgen.
"""

import json

import pytest

from shadow_tpu.analysis import costmodel as C
from shadow_tpu.analysis import hlo_graph as G
from shadow_tpu.analysis import tpu_readiness as T
from shadow_tpu.analysis.chips import chip


# ------------------------------------------------------------ tile math


def test_tile_geometry_per_dtype():
    v5e = chip("v5e")
    assert v5e.tile(4) == (8, 128)
    assert v5e.tile(2) == (16, 128)
    assert v5e.tile(1) == (32, 128)
    # i64 is emulated as two i32 words: 4-byte geometry, 8-byte payload
    assert v5e.tile(8) == (8, 128)


def test_padded_dims_and_bytes():
    v5e = chip("v5e")
    # last dim to the lane, second-to-last to the sublane
    assert v5e.padded_dims([8, 3], 8) == [8, 128]
    assert v5e.padded_dims([5, 200], 4) == [8, 256]
    # leading dims never pad
    assert v5e.padded_dims([3, 8, 128], 4) == [3, 8, 128]
    # rank-1 / rank-0 occupy a full tile of lanes
    assert v5e.padded_dims([5], 4) == [8, 128]
    assert v5e.padded_dims([], 4) == [8, 128]
    assert v5e.padded_bytes([8, 3], 8) == 8 * 128 * 8
    # the CPU row is identity: no tiling, no waste
    cpu = chip("cpu")
    assert cpu.padded_dims([8, 3], 8) == [8, 3]
    assert cpu.padded_bytes([5], 4) == 20


def test_parse_tensor():
    assert C.parse_tensor("tensor<8x32xi64>") == ([8, 32], "i64")
    assert C.parse_tensor("tensor<i1>") == ([], "i1")
    assert C.parse_tensor(
        "tensor<8xi64, #stablehlo.type_extensions<bounds = [4]>>") \
        == ([8], "i64")
    assert C.parse_tensor("tensor<?x4xi32>") is None
    assert C.parse_tensor("!stablehlo.token") is None


# ----------------------------------------------------- synthetic modules


def _module(body_ops: str) -> G.Module:
    """A window-shaped module: one while whose body is `body_ops`."""
    return G.parse_module(
        'module @m {\n'
        '  func.func public @main(%arg0: tensor<i64>, '
        '%arg1: tensor<8x32xi64>) -> tensor<i64> {\n'
        '    %0 = stablehlo.while(%iterArg = %arg0) : tensor<i64>\n'
        '     cond {\n'
        '      %1 = stablehlo.compare  LT, %iterArg, %iterArg : '
        '(tensor<i64>, tensor<i64>) -> tensor<i1>\n'
        '      stablehlo.return %1 : tensor<i1>\n'
        '    } do {\n'
        + body_ops +
        '      stablehlo.return %iterArg : tensor<i64>\n'
        '    }\n'
        '    return %0 : tensor<i64>\n'
        '  }\n'
        '}\n')


_SORT = ('      %s = "stablehlo.sort"(%arg1) <{dimension = 1 : i64}> ({\n'
         '      ^bb0(%a: tensor<i64>, %b: tensor<i64>):\n'
         '        %c = stablehlo.compare  LT, %a, %b : '
         '(tensor<i64>, tensor<i64>) -> tensor<i1>\n'
         '        stablehlo.return %c : tensor<i1>\n'
         '      }) : (tensor<8x32xi64>) -> tensor<8x32xi64>\n')

_GATHER = ('      %g = "stablehlo.gather"(%arg1, %iterArg) : '
           '(tensor<8x32xi64>, tensor<i64>) -> tensor<32xi64>\n')


def test_tile_report_flags_padded_to_waste_shape():
    # a [8,3] i64 tensor wastes 125/128 of every vector register; the
    # report names it as the worst offender with its hot-loop path
    good = T.tile_report(_module(
        '      %1 = stablehlo.add %arg1, %arg1 : tensor<8x32xi64>\n'))
    bad = T.tile_report(_module(
        '      %1 = stablehlo.add %arg1, %arg1 : tensor<8x32xi64>\n'
        '      %2 = stablehlo.abs %1 : tensor<8x3xi64>\n'))
    assert bad["waste_pct"] > good["waste_pct"]
    assert any(o["type"] == "tensor<8x3xi64>" and "while@" in o["path"]
               for o in bad["worst"])
    assert "i64" in bad["by_dtype"]


def test_injected_waste_regression_trips_baseline():
    rep = {"tile": {"logical_bytes": 100, "padded_bytes": 1000,
                    "waste_pct": 90.0},
           "churn": {}, "placement": {}}
    bl = {"tile": {"logical_bytes": 100, "padded_bytes": 500,
                   "waste_pct": 80.0},
          "churn": {}, "hot_ops": {}}
    v = T.check_config("phold", rep, bl)
    assert len(v) == 1 and "tile padding waste" in v[0]
    # within tolerance: silent
    rep["tile"]["waste_pct"] = 80.0 + T.WASTE_TOL_PCT
    assert T.check_config("phold", rep, bl) == []


def test_hot_loop_gather_flagged_with_region_path():
    m = _module(_GATHER)
    rep = T.placement_report(m)
    assert rep["gather"]["count"] == 1
    assert rep["gather"]["hot"] == 1
    (flag,) = rep["gather"]["flagged"]
    assert "while@" in flag["path"] and ".do" in flag["path"]
    # the same gather OUTSIDE the loop is counted but not hot
    m2 = G.parse_module(
        'module @m { func.func public @main(%arg1: tensor<8x32xi64>, '
        '%i: tensor<i64>) -> tensor<32xi64> {\n'
        '  %g = "stablehlo.gather"(%arg1, %i) : '
        '(tensor<8x32xi64>, tensor<i64>) -> tensor<32xi64>\n'
        '  return %g : tensor<32xi64>\n'
        '} }')
    rep2 = T.placement_report(m2)
    assert rep2["gather"]["count"] == 1 and rep2["gather"]["hot"] == 0


def test_injected_hot_gather_trips_baseline():
    full = {
        "tile": {"logical_bytes": 1, "padded_bytes": 1, "waste_pct": 0.0},
        "churn": T.churn_report(_module(_GATHER)),
        "placement": T.placement_report(_module(_GATHER)),
    }
    bl = {"tile": full["tile"],
          "churn": {k: {"count": v["count"], "hot": v["hot"]}
                    for k, v in full["churn"].items()},
          "hot_ops": {"gather": 0, "scatter": 0, "dynamic_slice": 0,
                      "dynamic_update_slice": 0}}
    v = T.check_config("tor", full, bl)
    assert len(v) == 1 and "hot-loop gather" in v[0]


def test_churn_census_hot_vs_total():
    m = _module(
        '      %1 = stablehlo.reshape %arg1 : (tensor<8x32xi64>) -> '
        'tensor<256xi64>\n'
        '      %2 = stablehlo.transpose %arg1, dims = [1, 0] : '
        '(tensor<8x32xi64>) -> tensor<32x8xi64>\n')
    rep = T.churn_report(m)
    assert rep["reshape"]["count"] == 1 and rep["reshape"]["hot"] == 1
    assert rep["transpose"]["hot"] == 1
    assert rep["reshape"]["bytes"] == 256 * 8
    # a baseline pinned at zero churn trips on both
    bl = {"tile": {"waste_pct": 0.0},
          "churn": {k: {"count": 0, "hot": 0} for k in T.CHURN_OPS},
          "hot_ops": {}}
    rep_full = {"tile": {"waste_pct": 0.0}, "churn": rep,
                "placement": {}}
    v = T.check_config("x", rep_full, bl)
    assert any("reshape" in s for s in v) \
        and any("transpose" in s for s in v)


# ----------------------------------------------------------- cost model


def test_round_time_bound_classification():
    v5e = chip("v5e")
    sorty = {"bytes": 10, "vpu_flops": 10, "sort_compares": int(1e12),
             "mxu_flops": 0}
    hbmy = {"bytes": int(1e12), "vpu_flops": 10, "sort_compares": 10,
            "mxu_flops": 0}
    assert C.round_time_s(sorty, v5e)["bound"] == "sort"
    assert C.round_time_s(hbmy, v5e)["bound"] == "hbm"
    # overhead floors the round even when counts are tiny
    t = C.round_time_s({"bytes": 1, "vpu_flops": 1, "sort_compares": 0,
                        "mxu_flops": 0}, v5e)
    assert t["round_us"] >= v5e.round_overhead_us


def test_price_region_sort_formula():
    m = _module(_SORT)
    op, func = C.innermost_while(m)
    assert op is not None
    body = next(r for r in op.regions if r.label == "do")
    counts = C.price_region(body, C._type_env(func), chip("cpu"))
    # rows * n * ceil(log2 n) per operand column: 8 * 32 * 5
    assert counts["sort_compares"] == 8 * 32 * 5
    assert counts["bytes"] > 0


def test_drain_winner_follows_sort_throughput():
    # frontier does twice the sorting per round AND advances fewer
    # events per round: on the scalar-sort CPU row chained must win
    chained = _module(_SORT)
    frontier = _module(_SORT + _SORT.replace("%s", "%s2"))
    bench = {"tor": {
        "hosts": 8,
        "chained": {"events": 1000, "inner_steps": 100, "run_s": 10.0},
        "frontier": {"events": 1000, "inner_steps": 200, "run_s": 20.0},
    }}
    rep = C.drain_report(
        {"tor": chained, "tor_frontier": frontier},
        {"tor": 8, "tor_frontier": 8}, bench=bench)
    assert rep["tor"]["winner"]["cpu"] == "chained"
    assert rep["tor"]["cpu_agrees_with_bench"] is True
    assert rep["tor"]["per_chip"]["cpu"]["chained"]["events_per_s"] > 0


def test_cpu_prediction_agrees_with_bench_r07():
    # the acceptance anchor: the checked-in baseline's CPU-row winner
    # must match BENCH_r07's measured direction for BOTH models
    bl = T.load_baseline()
    assert bl, "analysis/TPU_READINESS.json must be committed"
    bench = C.bench_drain_metadata()
    for model in ("tor", "tgen"):
        measured = ("chained"
                    if bench[model]["chained"]["run_s"]
                    <= bench[model]["frontier"]["run_s"] else "frontier")
        assert bl["winners"][model]["cpu"] == measured, model


def test_bench_metadata_parses_r07():
    bench = C.bench_drain_metadata()
    for model in ("tor", "tgen"):
        assert set(bench[model]) == {"hosts", "chained", "frontier"}
        assert bench[model]["chained"]["events"] > 0
    # missing file falls back to the pinned numbers
    fb = C.bench_drain_metadata("/nonexistent/bench.json")
    assert fb["tor"]["hosts"] == 1020


# ------------------------------------------------------ baseline + audit


def test_baseline_has_every_contract_config():
    from shadow_tpu.analysis import hlo_audit as H

    bl = T.load_baseline()
    expected = set(H.CONTRACTS) | set(T.EXTRA_CONFIGS)
    assert expected <= set(bl["configs"])
    for name, entry in bl["configs"].items():
        assert {"tile", "churn", "hot_ops"} <= set(entry), name


def test_missing_config_fails_check():
    v = T.check_config("phold", {"tile": {"waste_pct": 0.0},
                                 "churn": {}, "placement": {}}, None)
    assert len(v) == 1 and "no entry" in v[0]


def test_floor_drop_trips_audit_rule():
    # floors are enforced in audit_all; the rule itself: a predicted
    # events/s below FLOOR_TOL x baseline is a violation
    assert T.FLOOR_TOL < 1.0


def test_save_baseline_roundtrip(tmp_path):
    results = {
        "phold": {
            "ok": True, "violations": [], "hosts": 8,
            "tile": {"logical_bytes": 10, "padded_bytes": 20,
                     "waste_pct": 50.0, "by_dtype": {}, "worst": []},
            "churn": {k: {"count": 0, "hot": 0, "bytes": 0}
                      for k in T.CHURN_OPS},
            "placement": {k: {"count": 0, "hot": 0, "flagged": []}
                          for k in T.PLACEMENT_OPS},
            "floors": {"cpu": 100.0},
        },
        "skipped_cfg": {"ok": True, "skipped": "no devices",
                        "violations": []},
        "drain_economics": {"ok": True, "violations": []},
    }
    path = str(tmp_path / "bl.json")
    data = T.save_baseline(results, path)
    assert set(data["configs"]) == {"phold"}  # skipped configs stay out
    loaded = T.load_baseline(path)
    assert loaded["configs"]["phold"]["floors"] == {"cpu": 100.0}
    # a clean re-audit against its own distilled baseline passes
    assert T.check_config("phold", results["phold"],
                          loaded["configs"]["phold"]) == []


def test_audit_config_real_phold():
    # one real lowering end-to-end: the phold engine's window loop
    # parses and the committed baseline accepts the result
    rep = T.audit_config("phold")
    rep.pop("_module")
    assert rep["hosts"] == 8
    assert rep["placement"]["scatter"]["hot"] == 0  # ROADMAP invariant
    bl = T.load_baseline()
    assert T.check_config("phold", rep, bl["configs"]["phold"]) == []
