"""Host-side halves of the overlapped drain pipeline (PR 6).

The device halves (donating jits, single-sync harvest) are exercised
end-to-end by the CLI suites and `tests/test_sharded_sim.py`;
this file pins the pure-host policy pieces:

- `runtime/adaptive.WindowController` — the `--window auto` policy must
  be a deterministic function of sim-derived inputs (same counters in →
  same width sequence out), must widen only when windows run empty-ish,
  narrow on new drops or high fill, and stay inside
  [lookahead, max_mult × lookahead].
- `runtime/harvest.HeartbeatHarvest.summary_from` — rebuilding the
  summary dict from a fetched bundle must match `state_summary`'s keys.
"""

import jax
import jax.numpy as jnp

from shadow_tpu.runtime.adaptive import WindowController

BASE = 50_000_000  # 50 ms in ns


def _feed(ctl, rows):
    """rows: (executed_cum, drops_cum, fill) per boundary; returns the
    width the controller held AFTER each update."""
    out = []
    for ex, dr, fill in rows:
        ctl.update(ex, dr, fill)
        out.append(ctl.window_ns)
    return out


def test_widens_on_sparse_windows_and_caps():
    ctl = WindowController(BASE, n_hosts=64, max_mult=8)
    # every window executes far fewer events than hosts, fill near zero
    widths = _feed(ctl, [(i * 4, 0, 0.01) for i in range(1, 8)])
    assert widths[0] == 2 * BASE and widths[1] == 4 * BASE
    assert widths[-1] == 8 * BASE  # capped at max_mult
    assert max(widths) <= 8 * BASE


def test_narrows_on_new_drops_and_high_fill():
    ctl = WindowController(BASE, n_hosts=4, max_mult=64)
    _feed(ctl, [(2, 0, 0.01), (4, 0, 0.01)])  # widen to 4x
    assert ctl.window_ns == 4 * BASE
    _feed(ctl, [(6, 5, 0.01)])  # 5 NEW drops -> halve
    assert ctl.window_ns == 2 * BASE
    _feed(ctl, [(8, 5, 0.9)])  # drops stale, but fill past shrink
    assert ctl.window_ns == BASE
    _feed(ctl, [(10, 5, 0.9)])  # never below the lookahead base
    assert ctl.window_ns == BASE


def test_busy_windows_hold_width():
    ctl = WindowController(BASE, n_hosts=4)
    # plenty of events per window, moderate fill: no reason to move
    widths = _feed(ctl, [(100 * i, 0, 0.3) for i in range(1, 5)])
    assert widths == [BASE] * 4


def test_policy_is_deterministic():
    rows = [(30 * i, i // 3, 0.1 * (i % 5)) for i in range(1, 20)]
    a = _feed(WindowController(BASE, n_hosts=16), list(rows))
    b = _feed(WindowController(BASE, n_hosts=16), list(rows))
    assert a == b


def test_harvest_summary_matches_state_summary():
    from shadow_tpu.core.engine import state_summary
    from shadow_tpu.models import phold
    from shadow_tpu.runtime.harvest import HeartbeatHarvest
    from shadow_tpu.sim import Simulation

    eng, init = phold.build(4, seed=2, capacity=16, msgs_per_host=2)
    sim = Simulation(
        engine=eng, state0=init(), stop_ns=1_000_000_000,
        dns=None, topo=None, names=[f"h{i}" for i in range(4)],
        app=None, stack=None,
    )
    harvest = HeartbeatHarvest(sim)
    st = sim.run(500_000_000)
    st, bundle = harvest.extract(st, full=False)
    got = harvest.summary_from(harvest.fetch(bundle))
    want = state_summary(st)
    for k, v in want.items():
        assert got[k] == int(v), f"summary key {k}: {got[k]} != {int(v)}"
