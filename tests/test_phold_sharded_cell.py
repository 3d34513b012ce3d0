"""The four-chip PHOLD cell (`phold_16k_x4.uniform`, family
`benchmark/models/phold_sharded.py`) at 256 hosts on four virtual CPU
devices, 64 per device, through the benchmark harness: correct on a
sound run, its exchange counters in a traced run, not correct when one
chip's rows stay behind, and the exchange counters against the plain
reference's own count of sends between chips."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.models import phold_sharded
from benchmark.reference import phold as reference

CELL = "phold_16k_x4.uniform"
N, CHIPS = 256, 4
PER = N // CHIPS
SEED = 2**31 + 11  # past 32 signed bits


def _execute(trace=False, fault=None):
    return harness.execute(harness.load_spec(), CELL, SEED, 1.0, trace,
                           platform="cpu", overrides={"n_hosts": N},
                           fault=fault, t_start=time.perf_counter())


def _chip_left_behind(advance):
    """The window advances every host's rows but chip 1's."""
    def run(st, stop):
        new = advance(jax.tree.map(jnp.copy, st), stop)

        def pick(a, b):
            if a.ndim and a.shape[0] == N:
                rows = jnp.arange(N) // PER == 1
                keep = rows.reshape((N,) + (1,) * (a.ndim - 1))
                return jax.device_put(jnp.where(keep, b, a), a.sharding)
            return a
        return jax.tree.map(pick, new, st)
    return run


RUNS = {
    "sound": (False, None, True,
              {"events_per_s", "setup_s"}),
    "traced": (True, None, True,
               {"build_s", "first_call_s", "xchg_rounds_per_window.phold_x4",
                "xchg_fill_pct.phold_x4"}),
    "chip_left_behind": (False, _chip_left_behind, False, None),
}


@pytest.mark.parametrize("case", sorted(RUNS))
def test_cell_run(case):
    """Untraced: correct, with its end-to-end metrics (no peak memory
    on the CPU). Traced: the exchange metrics and no device idle share
    (no device planes on the CPU). Broken: not correct."""
    trace, fault, correct, metrics = RUNS[case]
    out = _execute(trace, fault)
    assert out["correct"] is correct, out["compared"]
    assert out["device"] == dict(out["device"], platform="cpu", count=CHIPS)
    if correct:
        assert out["attempted"] == N and out["failed"] == 0
        assert set(out["metrics"]) == metrics
        assert all(v["value"] > 0 for v in out["metrics"].values())
    else:
        assert out["compared"]["hosts_differing"]["value"] > 0


def _reference_crossings(executed) -> int:
    """Sends between chips over every (host, count < executed) pair,
    from the reference's own draws."""
    host = np.repeat(np.arange(N, dtype=np.int64), executed)
    count = np.concatenate([np.arange(e, dtype=np.int64) for e in executed])
    peer, _ = reference.draws(reference.root_key(SEED), host, count, N, 0)
    return int(np.sum(peer // PER != host // PER))


def _counters_match_reference():
    config = dict(harness.load_json("configs", "phold_16k_x4"), n_hosts=N)
    traffic = harness.load_json("traffic", "uniform")
    built = phold_sharded.build(config, traffic, SEED,
                                harness.accelerators(CHIPS, "cpu"))
    st = built.advance(built.state, 300_000_000)
    c = jax.device_get(built.counters(st))  # shadowlint: no-deadline=test on CPU devices
    executed = built.fetch(st)["executed"]
    assert executed.sum() == c["n_executed"] > 0
    assert c["n_cross_shard"] == _reference_crossings(executed) > 0

    from shadow_tpu.models import phold

    eng, _ = phold.build(PER, capacity=config["capacity"],
                         axis_name="hosts", n_shards=CHIPS)
    assert eng.xchg_width > 0
    assert phold.build(PER)[0].xchg_width == 0  # unsharded
    assert c["n_xchg_rounds"] >= c["n_windows"] > 0
    assert c["xchg_slots"] == c["n_xchg_rounds"] * CHIPS * (CHIPS - 1) * eng.xchg_width
    fill = harness.load_metric("xchg_fill_pct.phold_x4")({"counters": c})
    assert 0 < fill <= 100


def _refuses_one_chip():
    config = dict(harness.load_json("configs", "phold_16k_x4"), n_hosts=N)
    traffic = harness.load_json("traffic", "uniform")
    with pytest.raises(ValueError, match="across chips"):
        phold_sharded.build(config, traffic, SEED, harness.accelerators(1, "cpu"))


@pytest.mark.parametrize("case", [_counters_match_reference, _refuses_one_chip],
                         ids=["counters_match_reference", "refuses_one_chip"])
def test_family(case):
    case()


def test_cell_is_declared_on_four_chips():
    spec = harness.load_spec()
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell["chips"] == CHIPS
    assert harness.load_json("configs", cell["config"])["family"] == "phold_sharded"
