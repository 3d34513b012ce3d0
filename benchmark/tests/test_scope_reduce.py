"""The reduction by phase (`scope_reduce.py`): its labels, its sums on
synthetic intervals and on two small traces recorded on the chip
(`record_trace.py`, PHOLD at 256 hosts, TPU v5 lite): one from before
the program named its phases, one after (`phold256_scoped.xplane.pb`),
and the chip script `phase_profile.py` rehearsed on the CPU."""

from __future__ import annotations

import gzip
import os
import re

import pytest

from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
OLD = os.path.join(DATA, "phold256.xplane.pb")
SCOPED = os.path.join(DATA, "phold256_scoped.xplane.pb")


def test_phases_are_the_programs():
    from shadow_tpu.core.phases import PHASES

    assert sr.PHASES == PHASES
    assert {p for ps in sr.SHARES.values() for p in ps} <= set(PHASES)


@pytest.mark.parametrize("name, tf_op, want", [
    ("%fusion.1 = s32[8] fusion(...)",
     "jit(run)/while/body/flush/cond/branch_0_fun/merge/gather:", "merge"),
    ("%fusion.2 = s32[8] fusion(...)", "jit(run)/while/body/flush/sort:", "flush"),
    ("%while.3 = (s32[]) while((s32[]) %t), condition=%c, body=%b",
     "jit(run)/while/body/select/while:", "control"),
    ("%conditional.4 = s32[] conditional(pred[] %p, ...)",
     "jit(run)/while/body/flush/cond:", "control"),
    # the primitive (the last component) is never taken for a phase
    ("%select.5 = s32[8] select(...)", "jit(run)/while/body/select", "unscoped"),
    ("%fusion.6 = s32[8] fusion(...)", "jit(jit_handler)/sort:", "unscoped"),
    ("%fusion.7 = s32[8] fusion(...)", "", "unscoped"),
])
def test_label_is_the_innermost_phase(name, tf_op, want):
    assert sr._label(name, tf_op) == want


def test_scopes_tile_the_busy_time():
    ops = {"/device:TPU:0": [("control", 0, 100), ("merge", 10, 40),
                             ("flush", 50, 90), ("merge", 60, 70)],
           "/device:TPU:1": [("handler", -5, 20), ("unscoped", 30, 60)]}
    spans = [("chunk", 0, 80), ("fetch", 80, 100)]
    busy = tr.reduce({d: [("x", s, e) for _, s, e in evs]
                      for d, evs in ops.items()}, spans)["busy_s"]
    out = sr.reduce(ops, spans)
    assert set(out) == set(sr.KEYS)
    assert {k: round(v * 1e9, 3) for k, v in out.items() if v} == {
        "control": 15.0, "merge": 20.0, "flush": 15.0, "handler": 10.0,
        "unscoped": 15.0}
    assert sum(out.values()) == pytest.approx(busy)
    assert sr.reduce({}, spans) is None
    assert sr.reduce(ops, [("build", 0, 1)]) is None


@pytest.mark.parametrize("metric, phases", sorted(sr.SHARES.items()))
def test_share_readers(metric, phases):
    scopes = dict.fromkeys(sr.KEYS, 0.0)
    for i, p in enumerate(phases):
        scopes[p] = 0.1 * (i + 1)
    scopes["unscoped"] = 0.05
    busy = sum(scopes.values())
    rec = {"trace": {"busy_s": busy, "window_s": 2.0, "scopes": scopes}}
    want = 100.0 * sum(0.1 * (i + 1) for i in range(len(phases))) / busy
    assert sr.share(rec, phases) == pytest.approx(want)
    assert sr.share({"trace": {"busy_s": busy, "window_s": 2.0}}, phases) is None
    assert sr.share({}, phases) is None
    assert sr.share({"trace": None}, phases) is None


@pytest.mark.skipif(not os.path.exists(OLD), reason="no chip trace")
def test_old_trace_reads_as_before():
    ops, spans = tr.read_planes(OLD)
    old = tr.reduce(ops, spans)
    out = sr.reduce_file(OLD)
    assert {k: v for k, v in out.items() if k != "scopes"} == old
    # the reduction's numbers on this trace before phases were named
    assert old["busy_s"] == 0.070392102 and old["window_s"] == 0.106105522
    assert old["device_ops"][0] == ["fusion.141", 0.003660458]
    scopes = out["scopes"]
    assert sum(scopes.values()) == pytest.approx(old["busy_s"], rel=1e-9)
    assert not any(scopes[p] for p in sr.PHASES)
    assert 0 < scopes["control"] < 0.02 * old["busy_s"]


@pytest.mark.skipif(not os.path.exists(SCOPED), reason="no scoped chip trace")
def test_scoped_trace():
    out = sr.reduce_file(SCOPED)
    scopes = out["scopes"]
    assert sum(scopes.values()) == pytest.approx(out["busy_s"], rel=1e-3)
    for p in ("merge", "pop", "select", "handler", "flush"):
        assert scopes[p] > 0, p
    assert scopes["unscoped"] < 0.02 * out["busy_s"]
    rec = {"trace": out}
    shares = {k: sr.share(rec, v) for k, v in sr.SHARES.items()}
    assert all(0 < v < 100 for v in shares.values())
    assert sum(shares.values()) < 100


def test_phase_profile_rehearsal(tmp_path):
    """The chip script at a tiny size on the CPU: three windows, no
    device planes to reduce, the window program's HLO with its phases."""
    from benchmark.tests import phase_profile as pp

    rec = pp.profile("phold_4k.uniform", 2**31 + 7, str(tmp_path), 0.3,
                     platform="cpu", overrides={"n_hosts": 256})
    assert len(rec["chunks_per_s"]) == 3 and min(rec["chunks_per_s"]) > 0
    assert rec["trace"] is None  # the CPU has no device plane
    with gzip.open(tmp_path / "phold_4k.uniform.hlo.txt.gz", "rt") as f:
        text = f.read()
    names = {p for m in re.finditer(r'op_name="([^"]*)"', text)
             for p in m.group(1).split("/")[:-1]}
    assert {"pop", "select", "handler", "route", "append", "flush",
            "merge", "barrier"} <= names
    assert "metadata" not in pp.canonical_hlo(text)
    assert len(rec["hlo_sha256"]) == 64


def test_canonical_hlo_ignores_metadata_and_numbering():
    from benchmark.tests.phase_profile import canonical_hlo

    a = ("HloModule m\n\nFileNames\n1 \"a.py\"\n\nENTRY %main {\n"
         "  %add.7 = s32[] add(%p.1, %p.1), metadata={op_name=\"pop/add\"}\n}")
    b = ("HloModule m\n\nENTRY %main {\n"
         "  %add.3 = s32[] add(%p.2, %p.2)\n}")
    c = ("HloModule m\n\nENTRY %main {\n"
         "  %add.3 = s32[] multiply(%p.2, %p.2)\n}")
    assert canonical_hlo(a) == canonical_hlo(b) != canonical_hlo(c)
