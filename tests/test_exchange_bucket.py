"""The cross-shard exchange's route buckets (`Engine._exchange_push`)
against a NumPy oracle, round by round, over the 8-device virtual mesh.

The oracle: per source shard and destination shard, the run of masked-in
events bound there (in batch order) is cut at [kR, (k+1)R); round k's
bucket row d holds that slice, its empty slots TIME_INVALID with every
other field 0. A shard's own events push locally and never ride a
bucket; an event whose dst lies outside the mesh goes nowhere. The
rounds run until the longest run anywhere is sent.

Checked per case: every round's received bucket (the build plus the
all_to_all), the empty round after the last, the ExchangeBuf the push
returns (the last round's bucket and `sent_min`), the round and crossing
counts, and the queues, which hold the local events and every round but
the last (whose bucket is delivered later).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.core.engine import (
    ConstantNetwork, Emit, Engine, EngineConfig, ExchangeBuf, Stats,
)
from shadow_tpu.core.events import EventQueue, Events
from shadow_tpu.core.timebase import MILLISECOND, TIME_INVALID
from shadow_tpu.parallel import mesh as pmesh

S, H, R, C, A = 8, 4, 4, 64, 3  # shards, hosts per shard, slots, queue, args
FIELDS = ("time", "dst", "src", "seq", "kind", "args")


def _engine() -> Engine:
    def handler(hs, ev, key):
        return hs, Emit.single(dst=ev.dst, dt=0, kind=0, n_args=A)

    cfg = EngineConfig(n_hosts=H, capacity=C, lookahead=MILLISECOND,
                       max_emit=1, n_args=A, axis_name=pmesh.HOSTS_AXIS,
                       n_shards=S, route_bucket=R)
    return Engine(cfg, [handler], ConstantNetwork(MILLISECOND))


def _batch(case: str, seed: int = 7):
    """Per-shard flat batches [S, m] with the case's destinations; every
    case has local and masked-out lanes, and unique (src, seq) keys."""
    rng = np.random.default_rng(seed)
    m = 3 if case == "narrow" else 24
    dst = rng.integers(0, S * H, (S, m))
    if case == "long_run":  # shard 0 sends 4R + 1 events to shard 3
        dst[0, : 4 * R + 1] = 3 * H + rng.integers(0, H, 4 * R + 1)
    elif case == "empty_dest":  # no event anywhere is bound for shard 5
        dst = np.where(dst // H == 5, dst - H, dst)
    elif case == "uneven":  # shard i sends 3i events to shard i + 1, the rest local
        shard = np.where(np.arange(m)[None, :] < 3 * np.arange(S)[:, None],
                         (np.arange(S)[:, None] + 1) % S, np.arange(S)[:, None])
        dst = shard * H + rng.integers(0, H, (S, m))
    elif case == "out_of_range":  # past the last host and negative
        dst[:, ::3] = S * H + rng.integers(0, 5, dst[:, ::3].shape)
        dst[:, 1::5] = -1 - rng.integers(0, 5, dst[:, 1::5].shape)
    src = np.arange(S)[:, None] * H + rng.integers(0, H, (S, m))
    ev = dict(
        time=rng.integers(0, 10**9, (S, m)).astype(np.int64),
        dst=dst.astype(np.int32),
        src=src.astype(np.int32),
        seq=(np.arange(S * m).reshape(S, m) * 3 + 1).astype(np.int32),
        kind=rng.integers(0, 4, (S, m)).astype(np.int32),
        args=rng.integers(-2**31, 2**31, (S, m, A)).astype(np.int32),
    )
    mask = rng.random((S, m)) < 0.85
    if case == "long_run":
        mask[0, : 4 * R + 1] = True
    return ev, mask


def _oracle(ev: dict, mask: np.ndarray):
    """(recv, sent_min, local): recv[k] per field [S dst, S src, R ...];
    sent_min [K, S]; local[s] the events shard s pushes itself."""
    dshard = ev["dst"] // H
    runs = [[np.flatnonzero(mask[s] & (dshard[s] == d)) if d != s
             else np.zeros(0, int) for d in range(S)] for s in range(S)]
    k_all = max(-(-len(run) // R) for row in runs for run in row)
    recv = []
    for k in range(k_all):
        b = {f: np.zeros((S, S, R) + ev[f].shape[2:], ev[f].dtype)
             for f in FIELDS}
        b["time"][:] = TIME_INVALID
        for s in range(S):
            for d in range(S):
                lanes = runs[s][d][k * R:(k + 1) * R]
                for f in FIELDS:
                    b[f][d, s, : len(lanes)] = ev[f][s, lanes]
        recv.append(b)
    sent_min = np.array([[r["time"][:, s].min() for s in range(S)]
                         for r in recv]).reshape(k_all, S)
    local = [np.flatnonzero(mask[s] & (dshard[s] == s)) for s in range(S)]
    return recv, sent_min, local


def _records(ev: dict, idx) -> list:
    return sorted(
        (int(ev["time"][i]), int(ev["dst"][i]), int(ev["src"][i]),
         int(ev["seq"][i]), int(ev["kind"][i]), tuple(ev["args"][i].tolist()))
        for i in idx)


def _run(ev: dict, mask: np.ndarray, n_rounds: int):
    eng = _engine()
    mesh = pmesh.make_mesh(S)
    P = jax.sharding.PartitionSpec
    ax = pmesh.HOSTS_AXIS

    def body(ev, mask, host0):
        my = jax.lax.axis_index(ax).astype(jnp.int32)
        q, xchg, stats = eng._exchange_push(
            EventQueue.create(H, C, A), ExchangeBuf.create(S, R, A),
            Stats.create(H), ev, mask, host0)
        # the bucket build alone, each round and one past the last
        cols, start, count = eng._route_sort(ev, mask & (ev.dst // H != my))
        rounds = [eng._route_bucket(cols, start, count, jnp.int32(k))
                  for k in range(n_rounds + 1)]
        recv = [jax.tree.map(lambda x: jax.lax.all_to_all(
            x, ax, split_axis=0, concat_axis=0), b) for b in rounds]
        recv = jax.tree.map(lambda *x: jnp.stack(x)[None], *recv)
        counts = jnp.stack([stats.n_xchg_rounds, stats.n_cross_shard])
        return q, xchg, counts[None], recv

    fn = pmesh.shard_jit(body, mesh, (P(ax), P(ax)),
                         (P(ax), P(ax), P(ax), P(ax)), H)
    flat = Events(**{f: jnp.asarray(v.reshape((-1,) + v.shape[2:]))
                     for f, v in ev.items()})
    return jax.device_get(fn(flat, jnp.asarray(mask.reshape(-1))))


@pytest.mark.parametrize("case", ["long_run", "empty_dest", "uneven",
                                  "out_of_range", "narrow"])
def test_route_buckets_match_oracle(case):
    ev, mask = _batch(case)
    recv, sent_min, local = _oracle(ev, mask)
    k_all = len(recv)
    dshard = ev["dst"] // H
    if case == "long_run":
        assert k_all >= 5
    if case == "empty_dest":
        assert not (mask & (dshard == 5)).any() and k_all >= 1
    if case == "uneven":  # shard 0 sends empty buckets in every round
        assert not (mask[0] & (dshard[0] != 0)).any() and k_all >= 3
    if case == "out_of_range":
        assert (mask & ((ev["dst"] < 0) | (dshard >= S))).sum() > 10
    if case == "narrow":
        assert ev["time"].shape[1] < R

    q, xchg, counts, got = _run(ev, mask, k_all)
    for k in range(k_all + 1):
        for f in FIELDS:
            want = (recv[k][f] if k < k_all
                    else getattr(Events.empty((S, S, R), n_args=A), f))
            np.testing.assert_array_equal(
                getattr(got, f)[:, k], want, err_msg=f"round {k} {f}")
    assert (counts[:, 0] == k_all).all()
    n_cross = sum(int((mask[s] & (dshard[s] != s) & (dshard[s] >= 0)
                       & (dshard[s] < S)).sum()) for s in range(S))
    assert (counts[:, 1] == n_cross).all()

    # the last round's bucket rides out undelivered
    empty = Events.empty((S, S, R), n_args=A)
    for f in FIELDS:
        last = recv[-1][f] if k_all else getattr(empty, f)
        np.testing.assert_array_equal(
            getattr(xchg.bucket, f).reshape(last.shape), last)
    np.testing.assert_array_equal(
        xchg.sent_min, sent_min[-1] if k_all else np.full(S, TIME_INVALID))

    # the queues: local events plus every delivered round
    for s in range(S):
        want = _records({f: v[s] for f, v in ev.items()}, local[s])
        for k in range(k_all - 1):
            flat = {f: v[s].reshape((-1,) + v.shape[3:])
                    for f, v in recv[k].items()}
            want += _records(flat, np.flatnonzero(
                flat["time"] != TIME_INVALID))
        rows = slice(s * H, (s + 1) * H)
        held = q.time[rows] != TIME_INVALID
        hosts = np.broadcast_to(np.arange(s * H, (s + 1) * H)[:, None],
                                held.shape)
        have = dict(time=q.time[rows][held], dst=hosts[held],
                    src=q.src[rows][held], seq=q.seq[rows][held],
                    kind=q.kind[rows][held], args=q.args[rows][held])
        assert _records(have, range(int(held.sum()))) == sorted(want)
        assert not q.drops[rows].any()
