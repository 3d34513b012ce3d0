"""The window loop's phase names (shadow_tpu/core/phases.py) and the host
phase spans, as a profiler capture sees them.

- Each drain contract's compiled window program carries every phase it
  runs in its ops' `op_name` metadata: the chained drain (PHOLD, with
  the trace ring and the StatPlane), the batched drain, the frontier
  drain (TGen under a crash schedule), and the sharded chained drain.
- The benchmark's copy of the phase names (benchmark/scope_reduce.py)
  is the program's.
- An `--xprof` capture holds the run loop's host phases (`step`) on the
  same timeline as the device ops, without `--profile`.
"""

import glob
import re

import jax
import jax.numpy as jnp
import pytest

from shadow_tpu.core.phases import PHASES, phase

CORE = {"pop", "select", "handler", "route", "merge", "barrier"}


def _chained():
    from shadow_tpu.models import phold

    eng, init = phold.build(8, seed=3, capacity=32, msgs_per_host=2,
                            trace=64, stats=1)
    return eng.run, init(), jnp.int64(2_000_000_000)


def _batched():
    from shadow_tpu.models import phold

    eng, init = phold.build(8, seed=3, capacity=32, msgs_per_host=2,
                            batched=True)
    return eng.run, init(), jnp.int64(2_000_000_000)


def _frontier():
    from shadow_tpu.config import parse_config
    from shadow_tpu.sim import build_simulation
    from tests.test_faults import echo_config

    sim = build_simulation(
        parse_config(echo_config('<fault type="crash" hosts="server" '
                                 'start="5"/>', count=2, stoptime=10)),
        seed=42, frontier=8, stats=1)
    return sim.engine.run, sim.state0, jnp.int64(sim.stop_ns)


def _sharded():
    from shadow_tpu.analysis import hlo_audit

    return hlo_audit._build("phold_sharded")


CONTRACTS = {
    "chained": (_chained, CORE | {"append", "flush", "observe"}),
    "batched": (_batched, CORE),
    "frontier": (_frontier, CORE | {"append", "flush", "fault", "observe"}),
    "sharded": (_sharded, CORE | {"append", "flush", "deliver", "exchange"}),
}


def _phases_in(text: str) -> set[str]:
    """The phases a compiled program's ops count under: the innermost
    phase on each op_name (its last component is the primitive)."""
    out = set()
    for m in re.finditer(r'op_name="([^"]*)"', text):
        out.update([p for p in m.group(1).split("/")[:-1] if p in PHASES][-1:])
    return out


@pytest.mark.parametrize("contract", sorted(CONTRACTS))
def test_compiled_window_program_names_its_phases(contract):
    build, want = CONTRACTS[contract]
    run, state, stop = build()
    text = jax.jit(run).lower(state, stop).compile().as_text()  # shadowlint: no-donate=compiled for inspection only
    assert _phases_in(text) == want


def test_benchmark_reads_the_programs_phases():
    from benchmark.scope_reduce import PHASES as BENCH_PHASES

    assert BENCH_PHASES == PHASES
    assert len(set(PHASES)) == len(PHASES)


def test_phase_refuses_an_unknown_name():
    with pytest.raises(ValueError, match="unknown phase"):
        phase("sort")


def test_xprof_capture_holds_host_step_spans(tmp_path):
    from jax.profiler import ProfileData

    from shadow_tpu.cli import main

    rc = main(["--test", "--stoptime", "3", "--heartbeat-frequency", "1",
               "--xprof", "1:2", "--xprof-dir", str(tmp_path)])
    assert rc == 0
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert "step" in names
