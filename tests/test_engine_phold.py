"""End-to-end engine tests on the PHOLD workload (SURVEY.md §7 step 2).

Covers: conservation of event population, window-barrier causality,
bit-exact determinism across runs (the reference's determinism tests,
src/test/determinism/), and stats accounting.
"""

import jax
import jax.numpy as jnp

from shadow_tpu.core.timebase import MILLISECOND, SECOND
from shadow_tpu.models import phold


def run_phold(n_hosts=16, stop_s=2, seed=0, msgs=1):
    eng, init = phold.build(n_hosts, seed=seed, msgs_per_host=msgs, capacity=32)
    st = init()
    st = jax.jit(eng.run, static_argnums=())(st, stop_s * SECOND)
    return eng, st


def test_phold_conserves_population():
    eng, st = run_phold(n_hosts=16, stop_s=2)
    # every executed event emits exactly one new one; none dropped
    assert int(st.stats.n_net_dropped.sum()) == 0
    assert int(st.queues.drops.sum()) == 0
    assert int(st.queues.size().sum()) == 16  # steady-state population
    assert int(st.stats.n_executed.sum()) == int(st.stats.n_emitted.sum())
    assert int(st.stats.n_executed.sum()) > 100


def test_phold_progress_and_windows():
    eng, st = run_phold(n_hosts=8, stop_s=1)
    assert int(st.now) == 1 * SECOND
    assert int(st.stats.n_windows) > 5
    # all remaining events are at/after stop
    assert int(st.queues.min_time().min()) >= 1 * SECOND


def test_phold_deterministic_across_runs():
    _, st1 = run_phold(n_hosts=16, stop_s=1, seed=42)
    _, st2 = run_phold(n_hosts=16, stop_s=1, seed=42)
    for a, b in zip(jax.tree.leaves(st1), jax.tree.leaves(st2)):
        assert (a == b).all()


def test_phold_seed_changes_trajectory():
    _, st1 = run_phold(n_hosts=16, stop_s=1, seed=1)
    _, st2 = run_phold(n_hosts=16, stop_s=1, seed=2)
    assert int(st1.stats.n_executed.sum()) != int(st2.stats.n_executed.sum()) or (
        st1.hosts.n_received.tolist() != st2.hosts.n_received.tolist()
    )


def test_step_window_matches_run():
    eng, init = phold.build(8, seed=7, capacity=32)
    st_a = init()
    stop = jnp.int64(300 * MILLISECOND)
    step = jax.jit(eng.step_window)
    for _ in range(64):
        st_a = step(st_a, stop)
    st_b = jax.jit(eng.run)(init(), stop)
    assert int(st_a.stats.n_executed.sum()) == int(st_b.stats.n_executed.sum())
    assert (st_a.queues.time.sort(axis=1) == st_b.queues.time.sort(axis=1)).all()


def test_causality_no_event_executes_before_send():
    # with latency 50ms and exponential delays, received counts grow roughly
    # uniformly; sanity-check no host starves
    eng, st = run_phold(n_hosts=16, stop_s=5)
    assert int(st.hosts.n_received.min()) > 0


def test_batched_drain_bit_identical_to_sequential():
    """The engine's commutative fast path (whole-frontier batch_handler)
    must produce bit-identical results to the sequential drain: same
    per-position RNG keys, same seq numbering, same routing rolls."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from shadow_tpu.core.timebase import SECOND, seconds
    from shadow_tpu.models import phold

    kw = dict(capacity=64, latency_ns=seconds(0.05),
              mean_delay_ns=seconds(0.01), msgs_per_host=4, seed=7)
    eng_b, init_b = phold.build(256, batched=True, **kw)
    eng_s, init_s = phold.build(256, batched=False, **kw)
    a = jax.jit(eng_b.run)(init_b(), jnp.int64(3 * SECOND))
    b = jax.jit(eng_s.run)(init_s(), jnp.int64(3 * SECOND))
    assert int(a.stats.n_executed.sum()) > 1000
    # scheduler self-profiling counters legitimately differ between the
    # two drain strategies (that is what they measure); simulation state
    # must not
    import dataclasses

    strip = lambda st: dataclasses.replace(
        st,
        stats=dataclasses.replace(
            st.stats,
            n_sweeps=jnp.zeros((), jnp.int64),
            n_inner_steps=jnp.zeros((), jnp.int64),
            n_xchg_rounds=jnp.zeros((), jnp.int64),
            n_merge_over_lanes=jnp.zeros_like(st.stats.n_merge_over_lanes),
            n_merge_over_events=jnp.zeros_like(st.stats.n_merge_over_events),
        ),
    )
    for x, y in zip(jax.tree.leaves(strip(a)), jax.tree.leaves(strip(b))):
        assert np.array_equal(np.asarray(x), np.asarray(y))
