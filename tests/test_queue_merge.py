"""The queue merge (`core.events.queue_push`, `merge_body`) against
NumPy oracles.

- `queue_push` against `_oracle_push`: per host, out of queue and pushed
  events (those addressed to this shard, unmasked, with a valid time),
  the row keeps the C smallest by key (time, src, seq); without a spill
  ring the rest count as drops, with one they land in the ring in
  eviction order, merge round by merge round. Randomized regimes
  (sparse, overflowing, spill ring, ring overflow, several rounds), a
  non-zero shard base, and pushes sized to run 1, 2 and ceil(C /
  MERGE_W) merge rounds;
- `merge_body` against a stable lexsort of each [resident | block] row,
  across cleared prefixes, key ties, wide blocks and all-empty rows;
- PHOLD through `parallel.mesh.build_sharded` on the 8-device CPU mesh
  against one device.

The push tests draw unique keys, as the engine makes them (per-source
sequence numbers): the order of two events with one key is unspecified.
`merge_body`'s own oracle covers ties, which it resolves resident-first.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.core.events import (
    MERGE_W, EventQueue, Events, merge_body, queue_pop, queue_push_counted,
)
from shadow_tpu.core.timebase import SECOND, TIME_INVALID
from shadow_tpu.models import phold
from shadow_tpu.parallel import mesh as pmesh

N_ARGS = 6
INV = int(TIME_INVALID)


def _rand_events(rng, m, n_hosts, t_max, seq0=0):
    """Random batch with time ties, rejects, and invalid rows mixed in;
    (src, seq) is unique, seq counting up from `seq0`."""
    t = rng.integers(0, t_max, size=m).astype(np.int64)
    # a few invalid/negative times must be filtered out
    bad = rng.random(m) < 0.05
    t[bad] = rng.choice([-5, INV], size=int(bad.sum()))
    # dst straddles the shard: in-range plus out-of-shard strays
    d = rng.integers(-1, n_hosts + 2, size=m).astype(np.int32)
    return _events(t, d, rng.integers(0, 8, size=m),
                   seq0 + rng.permutation(m), rng)


def _events(t, d, src, seq, rng):
    m = len(t)
    return Events(
        time=jnp.asarray(t, jnp.int64),
        dst=jnp.asarray(d, jnp.int32),
        src=jnp.asarray(src, jnp.int32),
        seq=jnp.asarray(seq, jnp.int32),
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


def _pack_words(words):
    """[n, k] i32 payload words -> [n, ceil(k / 2)] i64, two per word
    (high word first), as the merge and the spill ring carry them."""
    w = np.asarray(words, np.int64)
    if w.shape[1] % 2:
        w = np.concatenate([w, np.zeros((w.shape[0], 1), np.int64)], 1)
    return (w[:, 0::2] << 32) | (w[:, 1::2] & 0xFFFFFFFF)


def _oracle_push(q, ev, mask, host0):
    """(queue, over_lanes, over_events) as `queue_push_counted` defines
    them, computed record by record."""
    q, ev = jax.device_get((q, ev))
    mask = np.asarray(mask)
    h, c = q.time.shape
    m = ev.time.shape[0]
    local = ev.dst.astype(np.int64) - host0
    ok = (mask & (local >= 0) & (local < h) & (ev.time >= 0)
          & (ev.time != INV))

    def key(r):
        return r[0], r[1], r[2] & 0xFFFFFFFF

    def record(t, src, seq, kind, args):
        return (int(t), int(src), int(seq), int(kind)) + tuple(
            int(x) for x in args)

    rows, inc = [], []
    for r in range(h):
        live = q.time[r] != INV
        rows.append([record(*f) for f in zip(
            q.time[r][live], q.src[r][live], q.seq[r][live],
            q.kind[r][live], q.args[r][live])])
        sel = np.nonzero(ok & (local == r))[0]
        inc.append(sorted((record(ev.time[i], ev.src[i], ev.seq[i],
                                  ev.kind[i], ev.args[i]) for i in sel),
                          key=key))
    count = np.array([len(x) for x in inc])

    # the rounds every row runs: the first at w1, then while a row has
    # ranks left, at w1 up to w_full without a ring and at w_full with one
    w_full = min(c, m)
    w1 = min(w_full, MERGE_W)
    widths, lo = [w1], w1
    while (count > lo).any() and (q.spill is not None or lo < w_full):
        w = w1 if q.spill is None else w_full
        widths.append(w)
        lo += w

    time = np.full((h, c), INV, np.int64)
    src, seq, kind = (np.zeros((h, c), np.int32) for _ in range(3))
    args = np.zeros((h, c, q.args.shape[-1]), np.int32)
    drops = q.drops.copy()
    spill = q.spill
    evicted = [[] for _ in range(h)]
    for r in range(h):
        row, start = sorted(rows[r], key=key), 0
        for w in widths:
            merged = sorted(row + inc[r][start:start + w], key=key)
            row, start = merged[:c], start + w
            evicted[r] += merged[c:]
        if spill is None:
            # evictions, and ranks never merged (each has C smaller keys
            # in its own row), are drops
            drops[r] += len(evicted[r]) + max(count[r] - start, 0)
        for j, e in enumerate(row):
            time[r, j], src[r, j], seq[r, j], kind[r, j] = e[:4]
            args[r, j] = e[4:]
    out = EventQueue(time=time, src=src, seq=seq, kind=kind, args=args,
                     drops=drops, spill=spill)
    if spill is not None:
        # evictions append at the ring's write cursor, in eviction
        # order; past the ring's capacity they are lost (and dropped)
        cap = spill.time.shape[1] - c
        st, ss, pay = spill.time.copy(), spill.srcseq.copy(), spill.pay.copy()
        wr, n_lost = spill.wr.astype(np.int64), spill.n_lost.copy()
        n_out = np.array([len(e) for e in evicted])
        for r in range(h):
            for j, e in enumerate(evicted[r]):
                at = wr[r] + j
                if at < cap:
                    st[r, at] = e[0]
                    ss[r, at] = (e[1] << 32) | (e[2] & 0xFFFFFFFF)
                    pay[r, at] = _pack_words([e[3:]])[0]
        lost = np.maximum(wr + n_out - cap, 0) - np.maximum(wr - cap, 0)
        fill = (time != INV).sum(1)
        out = dataclasses.replace(out, drops=drops + lost, spill=dataclasses.replace(
            spill, time=st, srcseq=ss, pay=pay,
            wr=(wr + n_out).astype(np.int32),
            n_spilled=spill.n_spilled + n_out, n_lost=n_lost + lost,
            fill_hwm=np.maximum(spill.fill_hwm, fill).astype(np.int32)))
    over = lo - w1
    return out, np.full(h, over), np.clip(count - w1, 0, over)


def _assert_push_matches(got, want):
    """Queue, counters and spill ring equal; the ring is compared where
    it holds records, [0, min(wr, capacity)) per host (the columns past
    the cursor are scratch that no reader sees)."""
    (qg, lanes_g, ev_g), (qw, lanes_w, ev_w) = jax.device_get(got), want
    np.testing.assert_array_equal(lanes_g, lanes_w)
    np.testing.assert_array_equal(ev_g, ev_w)
    strip = dict(time=None, srcseq=None, pay=None)
    _leaves_equal(
        dataclasses.replace(qg, spill=qg.spill and dataclasses.replace(
            qg.spill, **strip)),
        dataclasses.replace(qw, spill=qw.spill and dataclasses.replace(
            qw.spill, **strip)))
    if qw.spill is None:
        return
    cap = qw.spill.time.shape[1] - qw.time.shape[1]
    for r, wr in enumerate(qw.spill.wr):
        n = min(int(wr), cap)
        for f in ("time", "srcseq", "pay"):
            np.testing.assert_array_equal(
                getattr(qg.spill, f)[r, :n], getattr(qw.spill, f)[r, :n])


def _push_checked(q, ev, mask, host0):
    got = queue_push_counted(q, ev, mask, host0)
    _assert_push_matches(got, _oracle_push(q, ev, mask, host0))
    return got[0]


# regimes: (n_hosts, capacity, batch, spill, t_max)
REGIMES = [
    (4, 16, 12, 0, 1000),     # sparse: no overflow anywhere
    (4, 8, 64, 0, 50),        # heavy overflow + time ties -> drops
    (3, 6, 48, 24, 30),       # overflow into a spill ring
    (2, 4, 40, 8, 10),        # ring itself overflows -> n_lost
    (5, 8, 80, 0, 5),         # multi-round: per-dest counts >> MERGE_W
    (2, 128, 360, 0, 40),     # 2-4 overflow rounds: 48-96 lanes per row
]


@pytest.mark.parametrize("regime", REGIMES, ids=[
    "sparse", "overflow", "spill", "ring-overflow", "multi-round",
    "mid-ladder"])
def test_randomized_push_equivalence(regime):
    n_hosts, cap, m, spill, t_max = regime
    for seed in (0, 1, 2):
        rng = np.random.default_rng(seed * 7 + 1)
        q = EventQueue.create(n_hosts, cap, spill=spill)
        for round_ in range(3):
            ev = _rand_events(rng, m, n_hosts, t_max, seq0=round_ * m)
            mask = jnp.asarray(rng.random(m) < 0.9)
            q = _push_checked(q, ev, mask, host0=0)
            # pop between pushes, so later pushes merge into rows that
            # have lost their heads
            gids = jnp.arange(n_hosts, dtype=jnp.int32)
            q, _, _ = queue_pop(q, jnp.int64(t_max // 2), gids)


def test_sharded_host0_equivalence():
    # a non-zero shard base: locals remap, strays reject
    rng = np.random.default_rng(11)
    q = EventQueue.create(4, 8)
    ev = _rand_events(rng, 32, 8, 100)  # dst over TWO shards' range
    _push_checked(q, ev, jnp.ones(32, bool), host0=4)


I64MAX = np.iinfo(np.int64).max


def _merge_case(rng, h, hc, w, nw, t_max, prefixes, fill, cnt):
    """merge_body inputs: row r holds `prefixes[r]` cleared slots (time
    INVALID, stale srcseq and payload, as the engine's prefix clear
    leaves them), then up to `fill` sorted valid events, then canonical
    empties; block row r admits `cnt[r]` sorted events."""
    qt = np.full((h, hc), int(TIME_INVALID), np.int64)
    qss = np.zeros((h, hc), np.int64)
    qpay = np.zeros((h, hc, nw), np.int64)
    for r in range(h):
        k = prefixes[r]
        nv = min(fill, hc - k)
        keys = np.sort(rng.integers(0, t_max, nv) * 4
                       + rng.integers(0, 4, nv))
        qt[r, k:k + nv] = keys // 4
        qss[r, k:k + nv] = keys % 4
        qpay[r, k:k + nv] = rng.integers(-I64MAX, I64MAX, (nv, nw))
        qss[r, :k] = rng.integers(0, 4, k)
        qpay[r, :k] = rng.integers(-I64MAX, I64MAX, (k, nw))
    m = h * w
    st = np.full(m, I64MAX, np.int64)
    sss = np.full(m, I64MAX, np.int64)
    starts = (np.arange(h) * w).astype(np.int32)
    bpay = np.zeros((h, w, nw), np.int64)
    for r in range(h):
        n = cnt[r]
        keys = np.sort(rng.integers(0, t_max, n) * 4 + rng.integers(0, 4, n))
        st[starts[r]:starts[r] + n] = keys // 4
        sss[starts[r]:starts[r] + n] = keys % 4
        bpay[r, :n] = rng.integers(-I64MAX, I64MAX, (n, nw))
    return qt, qss, qpay, st, sss, bpay, starts, np.asarray(cnt, np.int32)


def _merge_oracle(qt, qss, qpay, st, sss, bpay, starts, cnt):
    """Row by row: canonical resident empties (INVALID, 0, 0), then the
    block with fillers (i64max, i64max, 0) past its count, then one
    stable lexsort on (time, srcseq) over [resident | block]."""
    h, hc = qt.shape
    w = bpay.shape[1]
    emp = qt == TIME_INVALID
    rss = np.where(emp, 0, qss)
    rpay = np.where(emp[:, :, None], 0, qpay)
    outs = []
    for r in range(h):
        lane = np.arange(w) < cnt[r]
        idx = np.minimum(starts[r] + np.arange(w), st.shape[0] - 1)
        t = np.concatenate([qt[r], np.where(lane, st[idx], I64MAX)])
        ss = np.concatenate([rss[r], np.where(lane, sss[idx], I64MAX)])
        pay = np.concatenate([rpay[r], np.where(lane[:, None], bpay[r], 0)])
        order = np.lexsort((ss, t))
        outs.append((t[order], ss[order], pay[order]))
    return tuple(np.stack(x) for x in zip(*outs))


# (name, h, hc, w, nw, t_max, prefixes(h, hc), fill, cnt(h, w))
MERGE_CASES = [
    # every cleared-prefix length 0..hc, one per row
    ("prefixes", 13, 12, 5, 4, 1000,
     lambda h, hc: list(range(hc + 1)), 6, lambda h, w: [3] * h),
    # 2 times x 4 srcseqs: resident and block keys tie, payloads differ
    ("ties", 8, 10, 9, 3, 2,
     lambda h, hc: [0, 1, 2, 3, 0, 1, 2, 3], 10, lambda h, w: [9] * h),
    # a block wider than the rows, hc + w = 19
    ("wide-block", 6, 5, 14, 2, 50,
     lambda h, hc: [0, 2, 5, 1, 0, 3], 5,
     lambda h, w: [14, 0, 7, 14, 3, 11]),
    # all-empty resident rows meet partly and wholly filled blocks
    ("empty-rows", 4, 7, 6, 4, 20,
     lambda h, hc: [0, 7, 3, 0], 0, lambda h, w: [0, 6, 2, 5]),
    # full rows meet all-filler blocks
    ("filler-blocks", 4, 9, 7, 1, 20,
     lambda h, hc: [0, 4, 0, 9], 9, lambda h, w: [0] * h),
]


@pytest.mark.parametrize("case", MERGE_CASES, ids=[c[0] for c in MERGE_CASES])
def test_merge_body_matches_lexsort_oracle(case):
    _, h, hc, w, nw, t_max, prefixes, fill, cnt = case
    body = jax.jit(merge_body)
    for seed in range(3):
        rng = np.random.default_rng(seed)
        args = _merge_case(rng, h, hc, w, nw, t_max, prefixes(h, hc),
                           fill, cnt(h, w))
        got = body(*(jnp.asarray(x) for x in args))
        want = _merge_oracle(*args)
        for g, x in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), x)


# (rounds, spill): host 0's count sets the rounds at C = 64. Without a
# ring the rounds are MERGE_W wide until C ranks are admitted, with one
# the rounds past the first are C wide until every rank is.
ROUNDS = [(1, 0, 20), (2, 0, 40), (3, 0, 80),
          (1, 256, 20), (2, 256, 40), (3, 256, 100)]


@pytest.mark.parametrize("rounds,spill,n0", ROUNDS, ids=[
    f"{r}-round-{'spill' if s else 'drop'}" for r, s, _ in ROUNDS])
def test_merge_rounds_match_oracle(rounds, spill, n0):
    h, c, m = 4, 64, 200
    assert -(-c // MERGE_W) == 3  # the third case runs ceil(C / MERGE_W)
    rng = np.random.default_rng(rounds * 10 + bool(spill))
    q = EventQueue.create(h, c, spill=spill)
    # residents: 45 per host, so every case evicts
    fill = np.repeat(np.arange(h), 45)
    q = _push_checked(q, _events(rng.integers(0, 1000, fill.size), fill,
                                 rng.integers(0, 8, fill.size),
                                 np.arange(fill.size), rng),
                      jnp.ones(fill.size, bool), 0)
    # n0 events to host 0, up to 20 to each other host, the rest
    # rejected: out of shard, masked, or with an invalid time
    dst = np.concatenate([np.zeros(n0, int)] + [
        np.full(rng.integers(0, 21), r) for r in range(1, h)])
    n_rej = m - dst.size
    dst = np.concatenate([dst, rng.choice([-1, h, h + 3], n_rej)])
    t = rng.integers(0, 1000, m)
    mask = np.ones(m, bool)
    mask[-n_rej:] = rng.random(n_rej) < 0.5
    t[-3:] = [-5, INV, 7]
    dst[-3:] = 0
    mask[-3:] = [True, True, False]
    order = rng.permutation(m)
    ev = _events(t[order], dst[order], rng.integers(0, 8, m),
                 fill.size + np.arange(m), rng)
    mask = jnp.asarray(mask[order])
    want = _oracle_push(q, ev, mask, 0)
    assert int(want[1][0]) == (rounds - 1) * (c if spill else MERGE_W)
    evicted = want[0].spill.n_spilled if spill else want[0].drops
    assert int(evicted[0]) > 0
    _assert_push_matches(queue_push_counted(q, ev, mask, 0), want)


def test_engine_level_phold_identity():
    """PHOLD over the 8-device mesh through `build_sharded` reaches the
    one-device state bit for bit: every per-host leaf, and each queue
    row's valid events record for record."""
    stop = jnp.int64(2 * SECOND)
    per, shards = 4, 8
    kw = dict(seed=5, capacity=32, msgs_per_host=2)
    eng, init = phold.build(per * shards, **kw)
    one = jax.device_get(jax.jit(eng.run)(init(), stop))
    eng, init_fn = phold.build(per, axis_name=pmesh.HOSTS_AXIS,
                               n_shards=shards, **kw)
    init, run, _ = pmesh.build_sharded(eng, init_fn,
                                       pmesh.make_mesh(shards), per)
    st = jax.device_get(run(init(), stop))
    _leaves_equal((one.now, one.hosts, one.src_seq, one.exec_cnt,
                   one.cpu_free, one.queues.drops, one.stats.n_executed,
                   one.stats.n_emitted),
                  (st.now, st.hosts, st.src_seq, st.exec_cnt, st.cpu_free,
                   st.queues.drops, st.stats.n_executed, st.stats.n_emitted))
    for a, b in zip(_rows(one.queues), _rows(st.queues), strict=True):
        np.testing.assert_array_equal(a, b)
    # the run did real work (the identity is not vacuous)
    assert int(np.sum(one.stats.n_executed)) > 0


def _rows(q):
    """Each host's valid queued events as key-sorted records."""
    rec = np.concatenate([np.stack([q.time, q.src, q.seq, q.kind], -1),
                          q.args], -1)
    out = []
    for r, live in zip(rec, q.time != INV):
        v = r[live]
        out.append(v[np.lexsort(v.T[::-1])])
    return out
