"""Unit tests for the bounded per-host event queues (events.py).

Mirrors the correctness properties of the reference's PriorityQueue +
event total order (src/main/utility/priority_queue.c,
src/main/core/work/event.c:110-153): pop yields (time, src, seq)-minimal
events, pushes land in the right queues, overflow is accounted.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.core.events import EventQueue, Events, queue_pop, queue_push
from shadow_tpu.core.timebase import TIME_INVALID


def mk_events(rows):
    """rows: list of (time, dst, src, seq, kind)."""
    n = len(rows)
    t, d, s, q, k = (np.array([r[i] for r in rows]) for i in range(5))
    return Events(
        time=jnp.asarray(t, jnp.int64),
        dst=jnp.asarray(d, jnp.int32),
        src=jnp.asarray(s, jnp.int32),
        seq=jnp.asarray(q, jnp.int32),
        kind=jnp.asarray(k, jnp.int32),
        args=jnp.zeros((n, 6), jnp.int32),
    )


def test_push_pop_roundtrip():
    q = EventQueue.create(n_hosts=4, capacity=8)
    ev = mk_events([(100, 2, 0, 0, 7), (50, 2, 1, 0, 8), (70, 0, 3, 0, 9)])
    q = queue_push(q, ev, jnp.ones(3, bool), host0=0)
    assert q.size().tolist() == [1, 0, 2, 0]

    gids = jnp.arange(4, dtype=jnp.int32)
    q, out, active = queue_pop(q, jnp.int64(10_000), gids)
    assert active.tolist() == [True, False, True, False]
    # host 2 must pop its (time,src,seq)-minimal event: time 50 from src 1
    assert int(out.time[2]) == 50 and int(out.src[2]) == 1 and int(out.kind[2]) == 8
    assert int(out.time[0]) == 70 and int(out.kind[0]) == 9
    assert q.size().tolist() == [0, 0, 1, 0]


def test_pop_respects_window_barrier():
    q = EventQueue.create(2, 4)
    q = queue_push(q, mk_events([(500, 0, 0, 0, 1), (10, 1, 0, 0, 2)]), jnp.ones(2, bool), 0)
    gids = jnp.arange(2, dtype=jnp.int32)
    q, out, active = queue_pop(q, jnp.int64(100), gids)
    assert active.tolist() == [False, True]
    assert q.size().tolist() == [1, 0]


def test_tie_break_src_then_seq():
    # same time: lower src wins; same src: lower seq wins
    q = EventQueue.create(1, 8)
    ev = mk_events([(5, 0, 9, 0, 0), (5, 0, 3, 7, 1), (5, 0, 3, 2, 2)])
    q = queue_push(q, ev, jnp.ones(3, bool), 0)
    gids = jnp.zeros((1,), jnp.int32)
    order = []
    for _ in range(3):
        q, out, active = queue_pop(q, jnp.int64(10), gids)
        assert bool(active[0])
        order.append((int(out.src[0]), int(out.seq[0])))
    assert order == [(3, 2), (3, 7), (9, 0)]


def test_multi_push_same_dst_and_overflow():
    q = EventQueue.create(2, capacity=3)
    rows = [(i + 1, 0, 0, i, 0) for i in range(5)] + [(9, 1, 0, 0, 0)]
    q = queue_push(q, mk_events(rows), jnp.ones(6, bool), 0)
    assert q.size().tolist() == [3, 1]
    assert q.drops.tolist() == [2, 0]
    # surviving events for host 0 are a subset; pop yields increasing times
    gids = jnp.arange(2, dtype=jnp.int32)
    times = []
    for _ in range(3):
        q, out, active = queue_pop(q, jnp.int64(100), gids)
        times.append(int(out.time[0]))
    assert times == sorted(times)


def test_out_of_shard_events_ignored():
    q = EventQueue.create(2, 4)
    ev = mk_events([(1, 5, 0, 0, 0), (2, 3, 0, 0, 0), (3, 2, 0, 0, 0)])
    q = queue_push(q, ev, jnp.ones(3, bool), host0=2)  # shard owns gids [2, 4)
    assert q.size().tolist() == [1, 1]  # gid 2 -> row 0, gid 3 -> row 1; gid 5 dropped
    assert q.drops.tolist() == [0, 0]  # out-of-shard is not an overflow drop


def test_masked_push_ignored():
    q = EventQueue.create(1, 4)
    ev = mk_events([(1, 0, 0, 0, 0), (2, 0, 0, 0, 0)])
    q = queue_push(q, ev, jnp.asarray([True, False]), 0)
    assert int(q.size()[0]) == 1


def test_empty_queue_pop_inactive():
    q = EventQueue.create(3, 4)
    gids = jnp.arange(3, dtype=jnp.int32)
    q, out, active = queue_pop(q, jnp.int64(10**15), gids)
    assert not bool(active.any())
    assert (out.time == TIME_INVALID).all()


def test_burst_beyond_merge_w_exercises_fallback_round():
    """A single destination receiving far more than MERGE_W events in one
    push must land them all (the lax.cond fallback round), in key order,
    with only true capacity overflow counted as drops."""
    from shadow_tpu.core.events import MERGE_W

    n = 3 * MERGE_W  # 72 events to one host, capacity 80: no drops
    q = EventQueue.create(n_hosts=4, capacity=80)
    rows = [(1000 - i, 1, 0, i, 0) for i in range(n)]
    q = queue_push(q, mk_events(rows), jnp.ones(n, bool), host0=0)
    assert q.size().tolist() == [0, n, 0, 0]
    assert q.drops.tolist() == [0, 0, 0, 0]
    # row must hold the full burst sorted by (time, src, seq)
    times = q.time[1, :n].tolist()
    assert times == sorted(times) == list(range(1000 - n + 1, 1001))


def test_burst_beyond_merge_w_with_capacity_overflow():
    """Burst > MERGE_W into a small queue: the smallest keys survive and
    every lost event is accounted as a drop — whichever round it rode."""
    from shadow_tpu.core.events import MERGE_W

    n = 2 * MERGE_W + 10  # 58 events, capacity 16
    cap = 16
    q = EventQueue.create(n_hosts=2, capacity=cap)
    rows = [(i + 1, 0, 0, i, 0) for i in range(n)]
    q = queue_push(q, mk_events(rows), jnp.ones(n, bool), host0=0)
    assert int(q.size()[0]) == cap
    assert q.time[0, :cap].tolist() == list(range(1, cap + 1))
    assert int(q.drops[0]) == n - cap


def test_negative_time_events_excluded():
    """Negative times are invalid input (sim times are ns >= 0); they are
    ignored like out-of-shard destinations and cannot disturb the
    marker-based placement of valid events."""
    q = EventQueue.create(n_hosts=2, capacity=4)
    ev = mk_events([(-1, 0, 0, 0, 0), (-5, 1, 0, 1, 0), (7, 1, 0, 2, 3)])
    q = queue_push(q, ev, jnp.ones(3, bool), host0=0)
    assert q.size().tolist() == [0, 1]
    assert int(q.time[1, 0]) == 7 and int(q.kind[1, 0]) == 3


def _ladder_case(max_count, cap=128, n_hosts=6, seed=0):
    """A resident queue (ties on time, a cleared prefix on every other
    row, as the engine's pop leaves it) and a push whose largest
    per-destination count is `max_count`, padded with out-of-shard and
    masked events so the push spans more than `cap` lanes."""
    rng = np.random.default_rng(seed + max_count)
    counts = [max_count, min(max_count, 30), 5, 0, max_count - 1, 1]
    seq = iter(range(10**6))

    def batch(per_host, t_max):
        rows = [(int(rng.integers(0, t_max)), h, int(rng.integers(0, 8)),
                 next(seq), int(rng.integers(0, 100)))
                for h, n in enumerate(per_host) for _ in range(n)]
        return [rows[i] for i in rng.permutation(len(rows))]

    q = EventQueue.create(n_hosts=n_hosts, capacity=cap)
    res = batch([10] * n_hosts, 40)
    q = queue_push(q, mk_events(res), jnp.ones(len(res), bool), host0=0)
    cleared = ((np.arange(cap)[None, :] < 3)
               & (np.arange(n_hosts)[:, None] % 2 == 0))
    q = dataclasses.replace(
        q, time=jnp.where(jnp.asarray(cleared), TIME_INVALID, q.time))
    rows = batch(counts, 40)
    rows += [(7, n_hosts + 3, 0, next(seq), 0)] * 130  # out of shard
    mask = np.ones(len(rows), bool)
    rows += [(5, 0, 0, next(seq), 0)] * 20  # masked off
    mask = np.concatenate([mask, np.zeros(20, bool)])
    return q, mk_events(rows), jnp.asarray(mask), np.asarray(counts)


def _oracle(q, ev, mask, cap):
    """Per host: stable sort of resident U incoming on (time, src, seq),
    keep the `cap` smallest, the rest are drops."""
    n_hosts = q.time.shape[0]
    want = {k: np.zeros_like(np.asarray(getattr(q, k)))
            for k in ("time", "src", "seq", "kind", "args")}
    want["time"][:] = int(TIME_INVALID)
    drops = np.asarray(q.drops).copy()
    et, ed, es, eq, ek = (np.asarray(getattr(ev, k)) for k in
                          ("time", "dst", "src", "seq", "kind"))
    ea, m = np.asarray(ev.args), np.asarray(mask)
    for h in range(n_hosts):
        qt = np.asarray(q.time[h])
        keep = qt != int(TIME_INVALID)
        recs = [(int(qt[j]), int(q.src[h, j]), int(q.seq[h, j]),
                 int(q.kind[h, j]), tuple(np.asarray(q.args[h, j])))
                for j in np.nonzero(keep)[0]]
        recs += [(int(et[i]), int(es[i]), int(eq[i]), int(ek[i]),
                  tuple(ea[i])) for i in np.nonzero(m & (ed == h))[0]]
        recs.sort(key=lambda r: r[:3])
        for j, (t, s, sq, k, a) in enumerate(recs[:cap]):
            want["time"][h, j], want["src"][h, j], want["seq"][h, j] = t, s, sq
            want["kind"][h, j], want["args"][h, j] = k, a
        drops[h] += max(len(recs) - cap, 0)
    return want, drops


# largest per-destination count -> the overflow rounds it takes
# (C = 128, MERGE_W = 24: a round per 24 ranks past the first 24, until
# 128 ranks are admitted); 170 runs past capacity and drops its tail
LADDER_CASES = [(25, 1), (48, 1), (49, 2), (73, 3), (121, 5), (170, 5)]


@pytest.mark.parametrize("max_count,rounds", LADDER_CASES,
                         ids=[f"count{c}" for c, _ in LADDER_CASES])
def test_overflow_round_width_ladder(max_count, rounds):
    """The overflow rounds read MERGE_W lanes per row each, as many as
    the push's largest count past MERGE_W needs (at most until C ranks
    are in), and the queue is exactly the C smallest keys of resident
    U incoming whatever the rounds."""
    from shadow_tpu.core.events import MERGE_W, queue_push_counted

    cap = 128
    q, ev, mask, counts = _ladder_case(max_count, cap)
    got, lanes, events = jax.jit(queue_push_counted)(q, ev, mask, 0)
    want, drops = _oracle(q, ev, mask, cap)
    for k, v in want.items():
        np.testing.assert_array_equal(np.asarray(getattr(got, k)), v, k)
    np.testing.assert_array_equal(np.asarray(got.drops), drops)
    assert int(lanes.sum()) == q.time.shape[0] * MERGE_W * rounds
    np.testing.assert_array_equal(
        np.asarray(events), np.clip(counts - MERGE_W, 0, MERGE_W * rounds))
    if max_count > MERGE_W * (1 + rounds):
        assert int(got.drops[0]) > 0


def test_overflow_round_skipped_below_merge_w():
    """A push whose largest count fits MERGE_W runs no overflow round:
    no overflow lanes, no overflow events."""
    from shadow_tpu.core.events import MERGE_W, queue_push_counted

    q, ev, mask, _ = _ladder_case(MERGE_W)
    _, lanes, events = queue_push_counted(q, ev, mask, 0)
    assert int(lanes.sum()) == 0 and int(events.sum()) == 0


def test_overflow_round_lowering_reads_merge_w_lanes():
    """Lowering only, no compile, at H = 256 and C = 768: the overflow
    rounds are one `while` whose body densifies [256, MERGE_W] blocks,
    and no [256, 768] gather runs anywhere in the push."""
    from shadow_tpu.analysis.hlo_graph import parse_module
    from shadow_tpu.core.events import MERGE_W

    h, cap, m = 256, 768, 4096
    q = EventQueue.create(n_hosts=h, capacity=cap)
    ev = Events.empty((m,))
    text = jax.jit(queue_push).lower(q, ev, jnp.ones(m, bool), 0).as_text()
    mod = parse_module(text)

    def gathers(ops):
        return [t for op in ops if op.short == "gather"
                for t in op.result_types]

    rounds = [r for op in mod.ops() if op.short == "while"
              for r in op.regions if r.label == "do"
              and any(o.short == "sort" for o in r.walk())]
    assert len(rounds) == 1
    body = gathers(rounds[0].walk())
    assert body and all(t.startswith(f"tensor<{h}x{MERGE_W}") for t in body)
    assert not [t for t in gathers(mod.ops())
                if t.startswith(f"tensor<{h}x{cap}")]
