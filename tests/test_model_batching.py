"""Frontier drain vs chained drain: bit-identity pins (ISSUE 13).

The engine's third drain contract (`EngineConfig.frontier > 0`,
docs/11-Performance.md "Model-tier batching") executes every handler
kind once per round over the sorted below-barrier frontier instead of
one event per host per sweep. The contract is BIT-identity with the
chained drain — same final state, same `(time, src, seq)` emit order,
same trace records — because run membership preserves the per-host
sequential fold wherever the model declares ordering sensitivity
(`frontier_kinds`). This file pins that contract:

- a tier-1 tgen pair (pure TCP: the transport fold is the hard case)
  with trace records compared through TraceDrain — the ring's PHYSICAL
  layout legitimately differs between builds (`Engine._trace_slack`
  reserves `u * (1 + K)` rows under the frontier drain), so identity
  is asserted on drained records, not raw ring leaves;
- a randomized property sweep (slow lane) across tor / tgen / bitcoin
  seeds, frontier widths, and workload shapes;
- a zero-cost check: `frontier=0` spelled out lowers byte-identically
  to the knob-absent default, so the third path leaves no residue in
  the two existing drains.

`stats.n_inner_steps` is exempt from the state comparison by design:
the chained drain counts per-event inner steps, the frontier drain
counts per-position rounds (including one terminating probe per run).
So are the queue merge's overflow counters (`n_merge_over_*`), which
count how pushes split into merge rounds. Sweeps, windows, and every
other counter must match exactly.
"""

import jax
import numpy as np
import pytest

from shadow_tpu import examples
from shadow_tpu.analysis.hlo_audit import lower_text
from shadow_tpu.config import parse_config
from shadow_tpu.obs.trace import TraceDrain
from shadow_tpu.sim import build_simulation

# state-leaf paths the contract deliberately leaves free: bookkeeping
# whose granularity differs between drains (inner steps, merge rounds),
# and the trace ring's physical layout (records must still match, see
# _run_pair)
_EXEMPT = ("n_inner_steps", "n_merge_over_", ".trace.")


def _run_pair(cfg_xml, frontier, *, seed, trace=0, **kw):
    """Run one config under the chained and the frontier drain; return
    [(state, records)] for both."""
    cfg = parse_config(cfg_xml)
    out = []
    for f in (0, frontier):
        sim = build_simulation(cfg, seed=seed, frontier=f, trace=trace,
                               **kw)
        sim.strict_overflow = False
        st = sim.run()
        recs = None
        if trace:
            d = TraceDrain(trace, kind_names=sim.kind_names)
            d.drain(st.trace)
            recs = d.records()
        out.append((jax.device_get(st), recs))
    return out


def _assert_identical(pair):
    (a, ra), (b, rb) = pair
    la = jax.tree_util.tree_flatten_with_path(a)[0]
    lb = jax.tree_util.tree_flatten_with_path(b)[0]
    assert len(la) == len(lb)
    for (pa, va), (pb, vb) in zip(la, lb):
        name = jax.tree_util.keystr(pa)
        assert name == jax.tree_util.keystr(pb)
        if any(tag in name for tag in _EXEMPT):
            continue
        np.testing.assert_array_equal(
            np.asarray(va), np.asarray(vb),
            err_msg=f"state leaf {name} differs between drains")
    if ra is not None:
        assert ra.keys() == rb.keys()
        for k in ra:
            np.testing.assert_array_equal(
                ra[k], rb[k],
                err_msg=f"trace record field {k} differs between drains")
    # the identity is not vacuous
    assert int(np.sum(a.stats.n_executed)) > 0


def test_tgen_frontier_bit_identity():
    """Tier-1 pin: pure TCP under the frontier drain, trace records
    included (emit order is part of the contract)."""
    pair = _run_pair(
        examples.tgen_example(n_pairs=3, sendsize="8KiB",
                              recvsize="24KiB", count=3, stoptime=15),
        frontier=8, seed=1, trace=2048, n_sockets=4,
    )
    _assert_identical(pair)


def test_frontier_knob_default_is_zero_cost():
    """`frontier=0` spelled out lowers byte-identically to the
    knob-absent default — the drain selection happens at trace time,
    so the third path leaves no residue when off."""
    cfg = parse_config(examples.tgen_example(n_pairs=2, stoptime=10))
    texts = []
    for kw in ({}, {"frontier": 0}):
        sim = build_simulation(cfg, seed=1, n_sockets=4, **kw)
        texts.append(
            lower_text(sim.engine.run, sim._fresh_state(None),
                       jax.numpy.int64(10_000_000_000)))
    assert texts[0] == texts[1]


@pytest.mark.slow
def test_tor_frontier_bit_identity_with_cpu_model():
    """Tor with the relay-crypto CPU model on: the burst CPU charge and
    the crash-quarantine masks must fold identically."""
    pair = _run_pair(
        examples.tor_example(n_relays_per_class=2, n_clients=6,
                             n_servers=2, filesize="40KiB", count=3,
                             stoptime=20, relay_cpu_ghz=1.0),
        frontier=8, seed=3, trace=4096,
    )
    _assert_identical(pair)


@pytest.mark.slow
@pytest.mark.parametrize("seed,frontier", [(2, 4), (11, 16)])
def test_tgen_frontier_property_sweep(seed, frontier):
    """Randomized workload shapes: sizes/counts drawn per seed so the
    sweep covers different retransmit/pause interleavings."""
    rng = np.random.default_rng(seed)
    pair = _run_pair(
        examples.tgen_example(
            n_pairs=int(rng.integers(2, 5)),
            sendsize=f"{int(rng.integers(4, 32))}KiB",
            recvsize=f"{int(rng.integers(16, 96))}KiB",
            count=int(rng.integers(2, 5)),
            stoptime=int(rng.integers(12, 20)),
        ),
        frontier=frontier, seed=seed, trace=2048, n_sockets=4,
    )
    _assert_identical(pair)


@pytest.mark.slow
def test_bitcoin_frontier_bit_identity():
    """Gossip fan-out: the densest emit pattern of the three models."""
    pair = _run_pair(
        examples.bitcoin_example(n_nodes=16, blocks=2,
                                 blocksize="64KiB", interval=20,
                                 stoptime=70),
        frontier=8, seed=7, n_sockets=16,
    )
    _assert_identical(pair)
