"""StableHLO graph parser (shadow_tpu/analysis/hlo_graph.py).

Two layers of round-trip: a synthetic module exercising every grammar
form the parser claims (while cond/do regions, generic-form ops with
^bb0 block args, func.call reachability, quoted custom_call targets,
tuple-element uses), and the real lowered programs the audits run on
(unsharded phold, sharded phold with GSPMD markers, the harvest
extraction jit). Byte accounting is pinned per dtype and cross-checked
against the compiled module's own memory analysis.
"""

import jax
import jax.numpy as jnp
import pytest

from shadow_tpu.analysis import hlo_audit as H
from shadow_tpu.analysis import hlo_graph as G


# ------------------------------------------------------------ byte math


def test_dtype_bytes_engine_dtypes():
    # every dtype the engine's pytrees carry, plus the narrow/wide ends
    assert G.dtype_bytes("i1") == 1
    assert G.dtype_bytes("pred") == 1
    assert G.dtype_bytes("i8") == 1
    assert G.dtype_bytes("i16") == 2
    assert G.dtype_bytes("i32") == 4
    assert G.dtype_bytes("i64") == 8
    assert G.dtype_bytes("ui8") == 1
    assert G.dtype_bytes("ui32") == 4
    assert G.dtype_bytes("ui64") == 8
    assert G.dtype_bytes("f16") == 2
    assert G.dtype_bytes("bf16") == 2
    assert G.dtype_bytes("f32") == 4
    assert G.dtype_bytes("f64") == 8
    assert G.dtype_bytes("c64") == 8
    assert G.dtype_bytes("c128") == 16


def test_bytes_of_type():
    assert G.bytes_of_type("tensor<i64>") == 8
    assert G.bytes_of_type("tensor<8x32xi32>") == 8 * 32 * 4
    assert G.bytes_of_type("tensor<4x0xi64>") == 0
    assert G.bytes_of_type("tensor<8xi1>") == 8
    # encoding attributes after the comma don't change the payload
    assert G.bytes_of_type(
        "tensor<8xi64, #stablehlo.type_extensions<bounds = [4]>>") == 64
    # non-tensor types carry no buffer
    assert G.bytes_of_type("!stablehlo.token") == 0


# ---------------------------------------------------- synthetic module


_SYNTH = """\
module @jit_run attributes {mhlo.num_partitions = 1 : i32} {
  func.func public @main(%arg0: tensor<i64>, %arg1: tensor<8x4xi64>) -> (tensor<i64> {jax.result_info = ".now"}, tensor<8x4xi64>) {
    %c = stablehlo.constant dense<0> : tensor<i64>
    %0:2 = stablehlo.while(%iterArg = %arg0, %iterArg_0 = %arg1) : tensor<i64>, tensor<8x4xi64>
     cond {
      %1 = stablehlo.compare  LT, %iterArg, %c : (tensor<i64>, tensor<i64>) -> tensor<i1>
      stablehlo.return %1 : tensor<i1>
    } do {
      %1 = stablehlo.add %iterArg, %c : tensor<i64>
      %2 = func.call @helper(%iterArg_0) : (tensor<8x4xi64>) -> tensor<8x4xi64>
      %3 = stablehlo.custom_call @"annotate_device_placement"(%2) {has_side_effect = true} : (tensor<8x4xi64>) -> tensor<8x4xi64>
      stablehlo.return %1, %3 : tensor<i64>, tensor<8x4xi64>
    }
    return %0#0, %0#1 : tensor<i64>, tensor<8x4xi64>
  }
  func.func private @helper(%arg0: tensor<8x4xi64>) -> tensor<8x4xi64> {
    %0 = "stablehlo.sort"(%arg0) <{dimension = 1 : i64}> ({
    ^bb0(%arg2: tensor<i64>, %arg3: tensor<i64>):
      %1 = stablehlo.compare  LT, %arg2, %arg3 : (tensor<i64>, tensor<i64>) -> tensor<i1>
      stablehlo.return %1 : tensor<i1>
    }) : (tensor<8x4xi64>) -> tensor<8x4xi64>
    return %0 : tensor<8x4xi64>
  }
  func.func private @dead(%arg0: tensor<f32>) -> tensor<f32> {
    %0 = stablehlo.negate %arg0 : tensor<f32>
    return %0 : tensor<f32>
  }
}
"""


@pytest.fixture(scope="module")
def synth():
    return G.parse_module(_SYNTH)


def test_funcs_and_entry(synth):
    assert set(synth.funcs) == {"main", "helper", "dead"}
    assert synth.entry.name == "main"
    assert synth.entry.visibility == "public"
    assert synth.funcs["helper"].visibility == "private"
    # entry signature: names, types, and jax.result_info leaf paths
    assert [n for n, _t, _a in synth.entry.args] == ["%arg0", "%arg1"]
    assert synth.entry.arg_bytes() == 8 + 8 * 4 * 8
    assert ".now" in synth.entry.result_infos


def test_reachability_excludes_dead_funcs(synth):
    names = {f.name for f in synth.reachable_funcs()}
    assert names == {"main", "helper"}  # @dead is parsed but unreached
    hist = synth.histogram()
    assert "negate" not in hist  # dead-func ops don't count
    assert G.parse_module(_SYNTH).histogram(
        reachable_only=False)["negate"] == 1


def test_histogram_counts_op_instances_once(synth):
    hist = synth.histogram()
    assert hist["while"] == 1
    assert hist["sort"] == 1  # reached through func.call @helper
    assert hist["custom_call"] == 1
    assert hist["add"] == 1
    # compare appears in the while cond AND the sort comparator
    assert hist["compare"] == 2
    # stablehlo.return is a dialect op (3 region terminators here);
    # func.call / func.return are structural and never counted
    assert hist["return"] == 3
    assert "call" not in hist


def test_region_nesting_and_carry(synth):
    (w,) = synth.find_ops("while")
    assert [r.label for r in w.regions] == ["cond", "do"]
    # both while regions see the iterArg carry as block args
    for r in w.regions:
        assert [n for n, _t in r.block_args] == ["%iterArg", "%iterArg_0"]
        assert [t for _n, t in r.block_args] == \
            ["tensor<i64>", "tensor<8x4xi64>"]
    (s,) = synth.find_ops("sort")
    assert len(s.regions) == 1
    assert [n for n, _t in s.regions[0].block_args] == ["%arg2", "%arg3"]
    assert s.result_types == ["tensor<8x4xi64>"]
    assert s.result_bytes() == 8 * 4 * 8


def test_quoted_custom_call_target(synth):
    # the quoted form `custom_call @"..."` the old regex missed
    assert synth.custom_call_targets() == ["annotate_device_placement"]


def test_tuple_element_uses(synth):
    ret = [op for op in synth.entry.body.ops if op.short == "return"][0]
    assert ret.operands == ["%0"]  # %0#0 / %0#1 resolve to base %0


def test_loose_text_toplevel():
    # bare op lines (no func wrapper) land in an implicit public func —
    # the audit_text fixtures depend on this
    m = G.parse_module("stablehlo.sort ...\nstablehlo.scatter ...\n")
    assert m.entry is not None
    assert m.histogram() == {"sort": 1, "scatter": 1}


# -------------------------------------------------------- real programs


def test_roundtrip_unsharded_phold():
    run, state, stop = H._build("phold")
    m = G.parse_module(H.lower_text(run, state, stop))
    leaves = jax.tree_util.tree_leaves(state)
    # entry args = every state leaf + stop, byte-exact
    assert len(m.entry.args) == len(leaves) + 1
    assert m.entry.arg_bytes() == sum(x.nbytes for x in leaves) + 8
    hist = m.histogram()
    assert hist["while"] >= 1 and hist["sort"] >= 1
    assert hist.get("scatter", 0) == 0  # the phold contract, structurally
    # the window loop's body is where the work is
    assert sum(1 for _ in m.while_body_ops()) > 0


def test_roundtrip_sharded_phold_gspmd():
    try:
        run, state, stop = H._build("phold_sharded")
    except RuntimeError as e:
        pytest.skip(str(e))
    m = G.parse_module(H.lower_text(run, state, stop))
    # Shardy sharding markers present (jax 0.9 prints no GSPMD
    # custom_call @Sharding)...
    assert "sdy.manual_computation" in m.sharding_markers()
    targets = set(m.custom_call_targets())
    allow = set(H.CONTRACTS["phold_sharded"].custom_call_allow)
    assert targets <= allow  # ...and any custom_call on the allowlist
    hist = m.histogram()
    # the sharded contract, structurally: counts come from the
    # reachable graph (shmap_body and its callees), not regex text;
    # the exchange cuts its route buckets as slices, with no scatter
    assert hist["all_to_all"] == 12 and hist.get("scatter", 0) == 0


def test_roundtrip_harvest_program():
    from shadow_tpu.analysis import donation as D
    from shadow_tpu.runtime.harvest import HeartbeatHarvest

    sim = D._sim_tiny()
    h = HeartbeatHarvest(sim)
    text = h._build(True).lower(sim.state0).as_text()
    m = G.parse_module(text)
    assert m.entry is not None and len(m.entry.result_infos) > 0
    hist = m.histogram()
    for op in ("infeed", "outfeed", "send", "recv"):
        assert hist.get(op, 0) == 0  # extraction never crosses to host


# ------------------------------------------------- adversarial fixtures
# Fuzz-style texts pinning the parser the whole TPU-readiness tentpole
# stands on: nesting depth, strings that contain the grammar's own
# delimiters, dense<...> literals inside attributes, zero-result ops.


_DEEP = """\
module @deep {
  func.func public @main(%arg0: tensor<i64>, %arg1: tensor<4x4xi64>) -> tensor<i64> {
    %0 = stablehlo.while(%iterArg = %arg0) : tensor<i64>
     cond {
      %1 = stablehlo.compare  LT, %iterArg, %iterArg : (tensor<i64>, tensor<i64>) -> tensor<i1>
      stablehlo.return %1 : tensor<i1>
    } do {
      %1 = stablehlo.while(%iterArg_0 = %iterArg) : tensor<i64>
       cond {
        %2 = stablehlo.compare  LT, %iterArg_0, %iterArg_0 : (tensor<i64>, tensor<i64>) -> tensor<i1>
        stablehlo.return %2 : tensor<i1>
      } do {
        %2 = "stablehlo.if"(%iterArg_0) ({
          %3 = stablehlo.while(%iterArg_1 = %iterArg_0) : tensor<i64>
           cond {
            %4 = stablehlo.compare  LT, %iterArg_1, %iterArg_1 : (tensor<i64>, tensor<i64>) -> tensor<i1>
            stablehlo.return %4 : tensor<i1>
          } do {
            %4 = "stablehlo.gather"(%arg1, %iterArg_1) : (tensor<4x4xi64>, tensor<i64>) -> tensor<i64>
            stablehlo.return %4 : tensor<i64>
          }
          stablehlo.return %3 : tensor<i64>
        }, {
          stablehlo.return %iterArg_0 : tensor<i64>
        }) : (tensor<i64>) -> tensor<i64>
        stablehlo.return %2 : tensor<i64>
      }
      stablehlo.return %1 : tensor<i64>
    }
    return %0 : tensor<i64>
  }
}
"""


def test_deeply_nested_regions():
    m = G.parse_module(_DEEP)
    hist = m.histogram()
    assert hist["while"] == 3
    assert hist["if"] == 1
    assert hist["gather"] == 1
    # the gather sits three while bodies down; its region path names
    # every enclosing op, innermost last
    paths = {op.short: path for op, path in m.ops_with_path()}
    gp = paths["gather"]
    assert gp.startswith("main/")
    assert gp.count("while@") == 3 and gp.count(".do") == 3
    assert "if@" in gp


def test_quoted_and_escaped_attr_strings():
    # attribute strings carrying the grammar's own delimiters — braces,
    # parens, an escaped quote — must not unbalance region tracking
    m = G.parse_module(
        'module @q {\n'
        '  func.func public @main(%arg0: tensor<4xi64>) -> tensor<4xi64> {\n'
        '    %0 = stablehlo.custom_call @"weird\\"target{(" (%arg0)\n'
        '      {backend_config = "a { b } ) \\" c", api_version = 2 : i32}\n'
        '      : (tensor<4xi64>) -> tensor<4xi64>\n'
        '    %1 = stablehlo.add %0, %arg0 : tensor<4xi64>\n'
        '    return %1 : tensor<4xi64>\n'
        '  }\n'
        '}\n')
    hist = m.histogram()
    assert hist["custom_call"] == 1
    assert hist["add"] == 1  # the braces inside strings didn't eat it
    assert m.entry is not None and m.entry.name == "main"
    assert m.custom_call_targets() == ['weird\\"target{(']


def test_dense_literals_inside_tensor_encodings():
    # dense<...> payloads show up both as constant initializers and
    # inside encoding attrs; byte accounting must key off dims x dtype
    # and ignore the rest
    m = G.parse_module(
        'module @d {\n'
        '  func.func public @main(%arg0: tensor<8xi64, #stablehlo.type_extensions<bounds = [4]>>) -> tensor<2x2xi32> {\n'
        '    %c = stablehlo.constant dense<[[1, 2], [3, 4]]> : tensor<2x2xi32>\n'
        '    %0 = stablehlo.add %c, %c : tensor<2x2xi32>\n'
        '    return %0 : tensor<2x2xi32>\n'
        '  }\n'
        '}\n')
    assert m.histogram()["constant"] == 1
    assert m.entry.arg_bytes() == 8 * 8  # encoding attr ignored
    (c,) = m.find_ops("constant")
    assert c.result_bytes() == 2 * 2 * 4
    assert G.bytes_of_type(
        "tensor<8xi64, #stablehlo.type_extensions<bounds = [4]>>") == 64


def test_zero_result_ops():
    # side-effect-only ops bind no SSA result; the parser must keep
    # walking (and the op must still count and carry its operands)
    m = G.parse_module(
        'module @z {\n'
        '  func.func public @main(%arg0: tensor<4xi64>) -> tensor<4xi64> {\n'
        '    stablehlo.custom_call @sink(%arg0) {has_side_effect = true} : (tensor<4xi64>) -> ()\n'
        '    "stablehlo.optimization_barrier"() : () -> ()\n'
        '    %0 = stablehlo.add %arg0, %arg0 : tensor<4xi64>\n'
        '    return %0 : tensor<4xi64>\n'
        '  }\n'
        '}\n')
    hist = m.histogram()
    assert hist["custom_call"] == 1
    assert hist["optimization_barrier"] == 1
    assert hist["add"] == 1
    (cc,) = m.find_ops("custom_call")
    assert cc.n_results == 0 and cc.result_bytes() == 0
    assert cc.operands == ["%arg0"]
    assert "sink" in m.custom_call_targets()
