"""Pallas-vs-XLA queue-merge equivalence (ISSUE 6 pin).

`queue_push` has two implementations of its densify + rotate + merge
stage: plain XLA ops (`kernel="xla"`, the default) and one fused Pallas
kernel invocation (`kernel="pallas"`, interpret-mode off-TPU). The two
share the arithmetic verbatim (`core/merge_pallas.merge_body`), so they
must be BIT-identical on every input — queues, drop counters, and
spill-ring contents including eviction order. This file pins that:

- a randomized property sweep across capacity/pressure regimes (sparse,
  overflowing, spill-ring, multi-round rank overflow, out-of-shard and
  masked rejects, cleared-empty prefixes from engine pops);
- an engine-level PHOLD run compared state-leaf by state-leaf;
- a zero-cost HLO identity: building with an explicit `kernel="xla"`
  lowers byte-identically to the knob-absent default, so the knob's
  plumbing costs nothing when off.

Everything runs on CPU (interpret mode executes the same jnp ops inside
the jitted program). Off the CPU the engine refuses `kernel="pallas"`
when it is built (pinned below); tests/test_tpu_compile.py pins the TPU
compiler's own refusal of the kernel.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.analysis.hlo_audit import lower_text
from shadow_tpu.core.events import EventQueue, Events, queue_pop, queue_push
from shadow_tpu.core.timebase import TIME_INVALID
from shadow_tpu.models import phold

N_ARGS = 6


def _rand_events(rng, m, n_hosts, t_max):
    """Random batch with ties, rejects, and invalid rows mixed in."""
    t = rng.integers(0, t_max, size=m).astype(np.int64)
    # a few invalid/negative times must be filtered identically
    bad = rng.random(m) < 0.05
    t[bad] = rng.choice([-5, int(TIME_INVALID)], size=int(bad.sum()))
    # dst straddles the shard: in-range plus out-of-shard strays
    d = rng.integers(-1, n_hosts + 2, size=m).astype(np.int32)
    return Events(
        time=jnp.asarray(t),
        dst=jnp.asarray(d),
        src=jnp.asarray(rng.integers(0, 8, size=m), jnp.int32),
        seq=jnp.asarray(rng.integers(0, 4, size=m), jnp.int32),
        kind=jnp.asarray(rng.integers(0, 100, size=m), jnp.int32),
        args=jnp.asarray(
            rng.integers(-(2**31), 2**31 - 1, size=(m, N_ARGS)), jnp.int32
        ),
    )


def _leaves_equal(a, b):
    la, pa = jax.tree.flatten(a)
    lb, pb = jax.tree.flatten(b)
    assert pa == pb, f"pytree structures differ: {pa} vs {pb}"
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _push_both(q, ev, mask, host0):
    qx = queue_push(q, ev, mask, host0, kernel="xla")
    qp = queue_push(q, ev, mask, host0, kernel="pallas")
    _leaves_equal(qx, qp)
    return qx


# regimes: (n_hosts, capacity, batch, spill, t_max)
REGIMES = [
    (4, 16, 12, 0, 1000),     # sparse: no overflow anywhere
    (4, 8, 64, 0, 50),        # heavy overflow + key ties -> drops
    (3, 6, 48, 24, 30),       # overflow into a spill ring
    (2, 4, 40, 8, 10),        # ring itself overflows -> n_lost
    (5, 8, 80, 0, 5),         # multi-round: per-dest counts >> MERGE_W
]


@pytest.mark.parametrize("regime", REGIMES, ids=[
    "sparse", "overflow", "spill", "ring-overflow", "multi-round"])
def test_randomized_push_equivalence(regime):
    n_hosts, cap, m, spill, t_max = regime
    for seed in (0, 1, 2):
        rng = np.random.default_rng(seed * 7 + 1)
        q = EventQueue.create(n_hosts, cap, spill=spill)
        for round_ in range(3):
            ev = _rand_events(rng, m, n_hosts, t_max)
            mask = jnp.asarray(rng.random(m) < 0.9)
            q = _push_both(q, ev, mask, host0=0)
            # pop a frontier so later rounds see the engine's
            # cleared-empty prefix (the rotation path under merge)
            gids = jnp.arange(n_hosts, dtype=jnp.int32)
            q, _, _ = queue_pop(q, jnp.int64(t_max // 2), gids)


def test_sharded_host0_equivalence():
    # a non-zero shard base: locals remap, strays reject — identically
    rng = np.random.default_rng(11)
    q = EventQueue.create(4, 8)
    ev = _rand_events(rng, 32, 8, 100)  # dst over TWO shards' range
    _push_both(q, ev, jnp.ones(32, bool), host0=4)


def test_engine_level_phold_identity():
    """Full PHOLD drains bit-identically under either kernel."""
    stop = jnp.int64(2_000_000_000)
    outs = []
    for kernel in ("xla", "pallas"):
        eng, init = phold.build(
            8, seed=5, capacity=32, msgs_per_host=2, kernel=kernel
        )
        outs.append(jax.device_get(eng.run(init(), stop)))
    _leaves_equal(outs[0], outs[1])
    # the run did real work (the identity is not vacuous)
    assert int(np.sum(outs[0].stats.n_executed)) > 0


def test_kernel_knob_default_is_zero_cost():
    """`kernel="xla"` spelled out lowers byte-identically to the
    knob-absent default — the selection happens at trace time, so the
    knob leaves no residue in the program."""
    stop = jnp.int64(1_000_000_000)
    eng_d, init_d = phold.build(4, seed=1, capacity=16)
    eng_x, init_x = phold.build(4, seed=1, capacity=16, kernel="xla")
    text_d = lower_text(eng_d.run, init_d(), stop)
    text_x = lower_text(eng_x.run, init_x(), stop)
    assert text_d == text_x


def test_pallas_kernel_refused_off_cpu(monkeypatch):
    """No silent interpret mode on a chip: an Engine built with
    kernel="pallas" when the first device is not a CPU raises, naming
    the compiler's reasons."""

    class _Tpu:
        platform = "tpu"

    real = jax.devices
    monkeypatch.setattr(jax, "devices", lambda *a: [_Tpu()] if not a
                        else real(*a))
    with pytest.raises(ValueError, match="64-bit types") as ei:
        phold.build(8, kernel="pallas")
    assert "2-D" in str(ei.value) and "kernel='xla'" in str(ei.value)
    phold.build(8)  # the default kernel builds anywhere
