"""The sharded call (`parallel.mesh.shard_jit`) under every mesh path.

`Simulation` on a 1-D and a 2-D ("dcn" x "hosts") mesh against one
device on the built-in TGen config, its sharded initial state, its
traced-window step against fixed windows, the donation of the managed
paths (`Simulation._wrap`, the harvest program), and `build_sharded`'s
run against repeated `step_window`.

Runs on the conftest's forced 8-device CPU mesh.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.config import parse_config
from shadow_tpu.core.timebase import SECOND, TIME_INVALID
from shadow_tpu.examples import example_config
from shadow_tpu.models import phold
from shadow_tpu.parallel import mesh as pmesh
from shadow_tpu.sim import build_simulation

STOP = 10 * SECOND


def _build(mesh=None):
    return build_simulation(parse_config(example_config()), seed=1,
                            mesh=mesh)


@pytest.fixture(scope="module")
def one():
    sim = _build()
    return sim, sim.run(STOP)


@pytest.fixture(scope="module")
def mesh_1d():
    return _build(pmesh.make_mesh(4))


def _assert_leaves_equal(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _queue_rows(q):
    """Each host's valid queued events as key-sorted records
    (time, src, seq, kind, args...): the slot a valid event sits in
    depends on how the window loop was cut, its record does not."""
    q = jax.device_get(q)
    rec = np.concatenate([np.stack([q.time, q.src, q.seq, q.kind], -1),
                          q.args], -1)
    rows = []
    for r, ok in zip(rec, q.time != TIME_INVALID):
        v = r[ok]
        rows.append(v[np.lexsort(v.T[::-1])])
    return rows


def _assert_same_state(a, b, *, exclude=()):
    """Every leaf equal but the queue slot arrays (compared as
    `_queue_rows`) and the fields named in `exclude`."""
    strip = dict.fromkeys(exclude)
    qa, qb = (dataclasses.replace(s.queues, time=None, src=None, seq=None,
                                  kind=None, args=None) for s in (a, b))
    _assert_leaves_equal(dataclasses.replace(a, queues=qa, **strip),
                         dataclasses.replace(b, queues=qb, **strip))
    for x, y in zip(_queue_rows(a.queues), _queue_rows(b.queues),
                    strict=True):
        np.testing.assert_array_equal(x, y)


def _assert_matches_one_device(st1, stN):
    """Bit for bit but the exchange buffer, which exists only on a mesh,
    and the stats, whose window-loop counters depend on the shard count
    (per-host executed counts are compared)."""
    _assert_same_state(st1, stN, exclude=("xchg", "stats"))
    for f in ("n_executed", "n_by_kind", "n_emitted", "n_net_dropped"):
        np.testing.assert_array_equal(getattr(st1.stats, f),
                                      getattr(stN.stats, f))
    assert int(np.asarray(st1.stats.n_executed).sum()) > 0


def test_simulation_1d_mesh_matches_one_device(one, mesh_1d):
    _, st1 = one
    stN = mesh_1d.run(STOP)
    assert len(stN.queues.time.sharding.device_set) == 4
    _assert_matches_one_device(st1, stN)


def test_simulation_2d_mesh_matches_one_device(one):
    m2 = pmesh.make_mesh(8, dcn_slices=2)
    assert pmesh.hosts_axes(m2) == (pmesh.DCN_AXIS, pmesh.HOSTS_AXIS)
    sim = _build(m2)
    stN = sim.run(STOP)
    assert len(stN.queues.time.sharding.device_set) == 8
    _assert_matches_one_device(one[1], stN)


def test_sharded_initial_state_matches_one_device(one, mesh_1d):
    """The sharded init (each shard pushes its own hosts' start events)
    builds the one-device initial state, leaf by leaf; the exchange
    buffer exists only on the mesh."""
    st1, stN = one[0].state0, mesh_1d.state0
    assert st1.xchg is None and stN.xchg is not None
    _assert_leaves_equal(st1, dataclasses.replace(stN, xchg=None))


def test_windowed_step_on_mesh_matches_fixed_windows(mesh_1d):
    """The traced-window step at the static bound steps to the state the
    fixed-window run reaches."""
    sim = mesh_1d
    w = sim.engine.cfg.lookahead
    st, now = None, 0
    while now < STOP:
        st = sim.step_window(st, STOP, window_ns=w)
        now = int(st.now)
    _assert_leaves_equal(sim.run(STOP), st)


def test_wrap_donates_on_mesh(mesh_1d):
    sim = mesh_1d
    st = sim.run(STOP // 2)
    leaves = jax.tree.leaves(st)
    out = sim.run(STOP, state=st)
    assert all(x.is_deleted() for x in leaves)
    assert int(out.now) == STOP


def test_harvest_donates_on_mesh(mesh_1d):
    from shadow_tpu.runtime.harvest import HeartbeatHarvest

    sim = mesh_1d
    st = sim.run(STOP // 2)
    leaves = jax.tree.leaves(st)
    out, bundle = HeartbeatHarvest(sim).extract(st, full=False)
    assert all(x.is_deleted() for x in leaves)
    assert int(out.now) == STOP // 2
    assert bundle


def test_build_sharded_run_matches_repeated_step_window():
    per, n_shards = 4, 8
    eng, init_fn = phold.build(per, axis_name=pmesh.HOSTS_AXIS,
                               n_shards=n_shards, seed=2, capacity=32,
                               msgs_per_host=4)
    init, run, step = pmesh.build_sharded(eng, init_fn,
                                          pmesh.make_mesh(n_shards), per)
    stop = jnp.int64(SECOND)
    want = run(init(), stop)
    st, windows = init(), 0
    while int(st.now) < SECOND:
        st = step(st, stop)
        windows += 1
    assert windows > 1
    # a window that ends at stop leaves its last exchange round in
    # flight; a step past the end delivers it, as run does
    _assert_same_state(want, step(st, stop))
    assert int(np.asarray(want.stats.n_executed).sum()) > 0
