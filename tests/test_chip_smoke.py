"""chip_smoke.py, the script the chip runs, driven here on the CPU at a
tiny size: every phase's code path and check runs, so a broken entry
point fails in the suite instead of on the chip."""

import json

import pytest

import chip_smoke as cs

TINY = dict(phold_hosts=64, phold_stop_s=2, tor_big=(2, 10, 2),
            tor_small=(2, 10, 2), tor_stop_s=8, serve_hosts=8,
            sharded_phold_hosts=64, sharded_phold_stop_s=1)


@pytest.mark.parametrize("chips", [1, 4])
def test_every_phase_passes_at_tiny_size(chips, monkeypatch, capsys):
    monkeypatch.setattr(cs, "SIZES", {**cs.SIZES, **TINY})
    rc = cs.main(["--chips", str(chips)], platform="cpu")
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert rc == 0, [x for x in lines if not x.get("ok", True)]
    names = {x["phase"] for x in lines if "phase" in x}
    want = cs.FOUR_CHIPS if chips == 4 else cs.ONE_CHIP
    assert names == {name for name, _, _ in want}
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 8}}


def test_no_result_without_the_chip(capsys):
    assert cs.main([]) == 2  # the suite's devices are CPUs
    assert '"ok"' not in capsys.readouterr().out
