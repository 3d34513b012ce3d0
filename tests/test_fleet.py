"""Scenario fleets: the vmapped engine's headline pins (ISSUE 15).

A `Fleet` runs L scenario lanes of one compiled window loop as ONE
jitted vmapped program. The contract this file pins, lane by lane:

- bit-identity: lane k's final state tree AND summary equal a solo run
  built the native way (Engine with that lane's seed / compiled fault
  schedule / scaled network) — for seed sweeps, mixed fault schedules,
  and latency scalings in the SAME fleet;
- no bleed: a lane with no faults inside a faulted fleet matches the
  fault-free solo run exactly (the padded schedules are values-neutral);
- zero cost: building a fleet leaves the unbatched engine's lowered
  program byte-identical (assert_zero_cost), and the fleet program's op
  histogram is lane-count-independent (L=1 vs L=4);
- donation: the production fleet jit aliases every donated leaf of the
  stacked [L, ...] carry (no per-window copy of the fleet state);
- census: a fleet heartbeat segment performs exactly ONE jax.device_get;
- CLI: `--window auto` + `--fleet` is rejected with an actionable error
  before any lane compiles.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.core.engine import Engine
from shadow_tpu.core.engine import state_summary
from shadow_tpu.core.timebase import SECOND
from shadow_tpu.faults import parse_fault_dsl
from shadow_tpu.faults.schedule import compile_faults
from shadow_tpu.models import phold
from shadow_tpu.runtime.fleet import (
    FleetPlan,
    build_fleet_from_engine,
    check_lane_knobs,
    scaled_network,
)

N = 8  # hosts
STOP = 3 * SECOND
NAMES = [f"host{i}" for i in range(N)]

CRASH = parse_fault_dsl("crash hosts=host3 start=1 end=2")
LOSSY = parse_fault_dsl("loss src=host1 dst=host5 loss=0.5 start=1 end=2")


def _phold(seed):
    return phold.build(N, seed=seed, capacity=64, msgs_per_host=2)


def _solo_final(seed, faults=(), scale=None):
    """The native solo build for one lane's scenario: faults compiled
    into the Engine constructor (NOT bind_lane — the comparison must
    cross implementations), latency scaling via scaled_network."""
    eng, init = _phold(seed)
    st0 = init()
    if faults or scale is not None:
        net = (scaled_network(eng.network, scale)
               if scale is not None else eng.network)
        comp = None
        reset = None
        if faults:
            comp = compile_faults(tuple(faults), NAMES, N, seed)
            if comp.has_crash or comp.has_bw:
                reset = st0.hosts
        eng = Engine(eng.cfg, eng.handlers, net,
                     batch_handler=eng.batch_handler,
                     faults=comp, fault_reset=reset)
    return jax.device_get(jax.jit(eng.run)(st0, jnp.int64(STOP)))


def _lane(state, k):
    return jax.tree.map(lambda x: np.asarray(x)[k], state)


def _assert_tree_equal(a, b, label):
    la = jax.tree.leaves(a)
    lb = jax.tree.leaves(b)
    assert len(la) == len(lb), label
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=label)


# ----------------------------------------------------------- bit-identity


@pytest.fixture(scope="module")
def mixed_fleet():
    """4 lanes exercising every per-lane knob class at once: a plain
    lane, a crash lane, a loss lane, and a latency-scaled lane."""
    eng, init = _phold(0)
    fleet = build_fleet_from_engine(
        eng, init(), 4,
        seeds=(11, 12, 13, 14),
        faults=(None, (CRASH,), (LOSSY,), None),
        latency_scale=(1.0, 1.0, 1.0, 1.7),
    )
    final = jax.device_get(fleet.run(STOP))
    return fleet, final


@pytest.mark.slow  # three fresh solo compiles; the tier-1 smoke lane keeps the
# mixed-fault identity pin + every guard test under the 870s budget
def test_seed_sweep_lanes_bit_identical_to_solo():
    eng, init = _phold(0)
    fleet = build_fleet_from_engine(eng, init(), 3, seeds=(5, 6, 7))
    final = jax.device_get(fleet.run(STOP))
    sums = fleet.lane_summaries(final)
    for k, seed in enumerate((5, 6, 7)):
        solo = _solo_final(seed)
        _assert_tree_equal(_lane(final, k), solo, f"lane {k} state")
        assert sums[k] == state_summary(solo), f"lane {k} summary"
    # the sweep actually varied: different seeds, different trajectories
    assert len({s["executed"] for s in sums}) > 1


@pytest.mark.slow  # fleet compile + four solo compiles via the module fixture;
# the full lane (`-m slow`) keeps this acceptance pin while tier-1 holds 870s
def test_mixed_fault_fleet_lanes_bit_identical_to_solo(mixed_fleet):
    fleet, final = mixed_fleet
    cases = [(11, (), None), (12, (CRASH,), None),
             (13, (LOSSY,), None), (14, (), 1.7)]
    sums = fleet.lane_summaries(final)
    for k, (seed, faults, scale) in enumerate(cases):
        solo = _solo_final(seed, faults=faults, scale=scale)
        _assert_tree_equal(_lane(final, k), solo, f"lane {k} state")
        assert sums[k] == state_summary(solo), f"lane {k} summary"


@pytest.mark.slow  # rides the same compile-heavy fixture + two solo runs
def test_fault_schedules_do_not_bleed_across_lanes(mixed_fleet):
    # lane 0 rides a fleet whose siblings compiled crash+loss overlays;
    # its state must equal the NO-fault solo run — the padded schedule
    # rows are values-neutral, not merely approximately so
    fleet, final = mixed_fleet
    solo = _solo_final(11)
    _assert_tree_equal(_lane(final, 0), solo, "no-fault lane")
    # and the crash lane visibly diverges from its fault-free twin
    crashed = fleet.lane_summaries(final)[1]
    assert crashed != state_summary(_solo_final(12))


# -------------------------------------------------------------- zero cost


@pytest.mark.slow  # four full lowerings; the tier-1 smoke lane keeps the
# mixed-fault identity pin + every guard test under the 870s budget
def test_fleet_off_is_zero_cost_and_histogram_lane_count_independent():
    from shadow_tpu.analysis.hlo_audit import (
        assert_zero_cost,
        lower_text,
        ops_histogram,
    )

    eng_b, init_b = _phold(3)
    st_b = init_b()
    eng_o, init_o = _phold(3)
    st_o = init_o()
    # building a fleet must leave the base engine untouched: the solo
    # lowering stays byte-identical (the off build feeds a Fleet first)
    fleet1 = build_fleet_from_engine(eng_o, st_o, 1, seeds=(3,))
    fleet2 = build_fleet_from_engine(eng_o, st_o, 2, seeds=(3, 4))
    fleet4 = build_fleet_from_engine(eng_o, st_o, 4, seeds=(3, 4, 5, 6))
    stop = jnp.int64(STOP)
    texts = assert_zero_cost(
        (eng_b, st_b), (eng_o, st_o), (fleet1.run_fn(), fleet1.state0),
        stop,
    )
    # lane-count independence: the L=2 and L=4 programs differ only in
    # the batch dimension's EXTENT — same ops, same counts. (L=1 elides
    # a few size-1 broadcasts, so it is compared on the heavy ops.)
    h2 = ops_histogram(lower_text(fleet2.run_fn(), fleet2.state0, stop))
    h4 = ops_histogram(lower_text(fleet4.run_fn(), fleet4.state0, stop))
    assert h2 == h4
    # and batching adds no scatter and no extra sorts/loops over the
    # solo program (vmap rewrites two dynamic slices into batched
    # gathers — bounded structural overhead, not per-lane bookkeeping)
    h1 = ops_histogram(lower_text(fleet1.run_fn(), fleet1.state0, stop))
    h_solo = ops_histogram(texts["base"])
    for op in ("scatter", "sort", "while"):
        assert h1.get(op, 0) == h2.get(op, 0) == h_solo.get(op, 0), op
    assert h2.get("scatter", 0) == 0
    assert h2.get("gather", 0) - h_solo.get("gather", 0) <= 2


# --------------------------------------------------------------- donation


@pytest.mark.slow  # compiles the production fleet jit; the tier-1 smoke lane keeps the
# mixed-fault identity pin + every guard test under the 870s budget
def test_fleet_jit_donates_the_stacked_carry():
    from shadow_tpu.analysis.donation import audit_jit

    eng, init = _phold(3)
    fleet = build_fleet_from_engine(eng, init(), 4, seeds=(0, 1, 2, 3))
    rep = audit_jit(fleet._jit_run,
                    (fleet.state0, fleet.binds, jnp.int64(STOP)),
                    "fleet_run")
    assert rep["ok"], rep["violations"]
    assert rep["donated_leaves"] == rep["aliased_leaves"] > 0
    assert rep["transfers"] == {}


# ----------------------------------------------------------- harvest path


@pytest.mark.slow  # fleet + harvest compile; the tier-1 smoke lane keeps the
# mixed-fault identity pin + every guard test under the 870s budget
def test_fleet_heartbeat_segment_fetches_exactly_once(monkeypatch):
    from shadow_tpu.runtime.harvest import HeartbeatHarvest

    eng, init = _phold(0)
    fleet = build_fleet_from_engine(eng, init(), 2, seeds=(1, 2))
    harvest = HeartbeatHarvest(fleet)
    st = fleet.dispatch(STOP, None)
    st, bundle = harvest.extract(st, full=True)
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: calls.append(1) or real(x))
    fetched = harvest.fetch(bundle)
    assert len(calls) == 1
    rows = harvest.lane_summaries_from(fetched)
    agg = harvest.summary_from(fetched)
    assert len(rows) == 2
    assert agg["executed"] == sum(r["executed"] for r in rows)
    assert agg["now_ns"] == min(r["now_ns"] for r in rows)
    # the per-lane rows match L solo runs (same seeds, no faults)
    for k, seed in enumerate((1, 2)):
        assert rows[k] == state_summary(_solo_final(seed))


def test_fleet_harvest_rejects_per_scenario_consumers():
    from shadow_tpu.runtime.harvest import HeartbeatHarvest

    eng, init = _phold(0)
    fleet = build_fleet_from_engine(eng, init(), 2, seeds=(1, 2))
    h = HeartbeatHarvest(fleet, tracker=object())
    with pytest.raises(ValueError, match="per-scenario"):
        h._build(True)


# ------------------------------------------------------------- validation


def test_static_knobs_rejected_with_reason():
    with pytest.raises(ValueError, match="static compile-time knob"):
        check_lane_knobs({"capacity": (32, 64)})
    with pytest.raises(ValueError, match="unknown fleet override"):
        check_lane_knobs({"sseeds": (1, 2)})
    with pytest.raises(ValueError, match="entries for 3 lanes"):
        FleetPlan(lanes=3, seeds=(1, 2))


def test_sharded_base_rejected():
    eng, init = phold.build(2, seed=0, capacity=16, axis_name="hosts",
                            n_shards=2)
    with pytest.raises(ValueError, match="single-device engine"):
        build_fleet_from_engine(eng, None, 2)


def test_cli_rejects_window_auto_with_fleet(capsys):
    from shadow_tpu.cli import main

    rc = main(["--test", "--stoptime", "1", "--overflow", "drop",
               "--fleet", "lanes=2 seed=0:2", "--window", "auto"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "--window auto cannot drive a fleet" in err
    assert "--window N" in err  # the actionable remedy


# ---------------------------------------------------------------- tools


FLEET_LOG = """\
[shadow-heartbeat] [fleet-header] time-seconds,lane,seed,now-seconds,\
windows,events,events-delta,queue-drops,fill
[shadow-heartbeat] [fleet] 1,0,11,1,10,100,100,0,0.1000
[shadow-heartbeat] [fleet] 1,1,12,1,10,90,90,0,0.0900
[shadow-heartbeat] [fleet] 2,0,11,2,20,220,120,0,0.1100
[shadow-heartbeat] [fleet] 2,1,12,2,20,200,110,0,0.0800
"""


def test_parse_shadow_learns_fleet_rows():
    from shadow_tpu.tools.parse_shadow import parse_lines

    stats = parse_lines(FLEET_LOG.splitlines())
    assert set(stats["fleet"]) == {"0", "1"}
    lane0 = stats["fleet"]["0"]
    assert lane0["ticks"] == [1, 2]
    assert lane0["seed"] == [11, 11]
    assert lane0["events"] == [100, 220]
    assert lane0["events_delta"] == [100, 120]
    assert lane0["fill"] == [0.1, 0.11]


def test_diff_runs_fleet_logs_diff_lane_by_lane(tmp_path):
    from shadow_tpu.tools import diff_runs

    a = tmp_path / "a.log"
    a.write_text(FLEET_LOG)
    b = tmp_path / "b.log"
    b.write_text(FLEET_LOG.replace("20,200,110,0", "20,201,111,0"))
    assert diff_runs.main([str(a), str(a)]) == 0
    entries = diff_runs.diff_files(str(a), str(b), rtol=0.0)
    keys = {e["key"] for e in entries}
    # only lane 1's sim keys drift; lane 0 stays clean
    assert keys == {"fleet:1.events", "fleet:1.events-delta"}


def test_cli_rejects_per_scenario_flags_and_bad_specs(capsys):
    from shadow_tpu.cli import main

    rc = main(["--test", "--stoptime", "1", "--overflow", "drop",
               "--fleet", "lanes=2 seed=0:2", "--metrics"])
    assert rc == 2
    assert "per-scenario" in capsys.readouterr().err
    rc = main(["--test", "--stoptime", "1", "--overflow", "drop",
               "--fleet", "lanes=2 seed=0:5"])
    assert rc == 2
    assert "2 lanes" in capsys.readouterr().err


def test_phold_build_fleet_convenience_defaults():
    # the model-level sweep entry point: seeds default to base seed .. base seed + L - 1
    fleet = phold.build_fleet(N, 3, seed=7, capacity=64, msgs_per_host=2)
    assert fleet.lanes == 3
    assert tuple(int(s) for s in fleet.plan.seeds) == (7, 8, 9)
    fleet = phold.build_fleet(N, 2, seeds=(11, 4), capacity=64,
                              msgs_per_host=2)
    assert tuple(int(s) for s in fleet.plan.seeds) == (11, 4)
