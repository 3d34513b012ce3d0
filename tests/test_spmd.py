"""Sharded window loop contract tests (docs/12-Sharding.md).

The restructured sharded window loop defuses the jax 0.4.x
experimental-shard_map miscompile structurally: every drain/exchange
flag is computed in a loop BODY and threaded through the carry, so no
collective ever lowers into a while/cond predicate. These tests pin
that contract:

- every `cond { ... }` region of the lowered sharded program is
  collective-free (the HLO-level twin of shadowlint SL108);
- the sharded run executes under shard_map — `jax.pmap` never runs;
- a 2-D (dcn x hosts) mesh is bit-identical to the 1-D mesh at the
  same total host count;
- the sharded lowering meets the hlo_audit phold_sharded budgets.

Runs on the conftest's forced 8-device CPU mesh.
"""

import re

import jax
import jax.numpy as jnp

from shadow_tpu.core.timebase import SECOND
from shadow_tpu.models import phold
from shadow_tpu.parallel import mesh as pmesh

# StableHLO spellings of cross-replica/cross-partition communication.
COLLECTIVE_OPS = (
    "all_reduce", "all_to_all", "collective_permute", "all_gather",
    "reduce_scatter", "collective_broadcast",
)


def _cond_regions(text: str) -> list[str]:
    """The body of every `stablehlo.while(...) cond { ... } do` region."""
    out = []
    i = 0
    while True:
        m = re.search(r"\bcond\s*\{", text[i:])
        if not m:
            return out
        start = i + m.end()
        depth, j = 1, start
        while depth and j < len(text):
            if text[j] == "{":
                depth += 1
            elif text[j] == "}":
                depth -= 1
            j += 1
        out.append(text[start:j - 1])
        i = j


def _sharded_phold(per, n_shards, *, axis=pmesh.HOSTS_AXIS, mesh=None,
                   **kw):
    kw.setdefault("seed", 3)
    kw.setdefault("capacity", 32)
    kw.setdefault("msgs_per_host", 4)
    eng, init = phold.build(per, axis_name=axis, n_shards=n_shards, **kw)
    m = mesh if mesh is not None else pmesh.make_mesh(n_shards)
    return pmesh.build_sharded(eng, init, m, per, axis=axis)


def test_window_predicates_have_no_collective():
    """The tentpole's structural guarantee, checked at the HLO level:
    none of the lowered while predicates contains a collective (they
    only read the carried flag)."""
    init, run, _ = _sharded_phold(8, 8)
    text = run.lower(
        jax.eval_shape(init), jax.ShapeDtypeStruct((), jnp.int64)
    ).as_text()
    regions = _cond_regions(text)
    assert regions, "no while regions found — lowering format changed?"
    for body in regions:
        for op in COLLECTIVE_OPS:
            assert f"stablehlo.{op}" not in body, (
                f"collective {op} inside a while predicate — the 0.4.x "
                f"shard_map miscompile surface is back (see SL108 / "
                f"docs/12-Sharding.md)")
    # non-vacuity: the collectives exist, just not in predicates
    assert any(f"stablehlo.{op}" in text for op in COLLECTIVE_OPS)


def test_no_pmap_in_executed_path(monkeypatch):
    """Sharded runs never route through jax.pmap."""
    def _poisoned(*a, **k):
        raise AssertionError("jax.pmap reached from the default path")

    monkeypatch.setattr(jax, "pmap", _poisoned)
    init, run, _ = _sharded_phold(8, 4)
    st = run(init(), jnp.int64(SECOND))
    assert int(st.now) == SECOND
    assert int(st.stats.n_executed.sum()) > 0


def test_2d_mesh_bit_identical_to_1d():
    """dcn x hosts vs flat hosts at the same total host count: the
    combined-axis collectives must not change results."""
    per, total = 4, 32
    init1, run1, _ = _sharded_phold(per, 8)
    st1 = run1(init1(), jnp.int64(SECOND))

    m2 = pmesh.make_mesh(8, dcn_slices=2)
    axes = pmesh.hosts_axes(m2)
    assert axes == (pmesh.DCN_AXIS, pmesh.HOSTS_AXIS)
    init2, run2, _ = _sharded_phold(per, 8, axis=axes, mesh=m2)
    st2 = run2(init2(), jnp.int64(SECOND))

    assert st1.hosts.n_received.shape[0] == total
    assert st1.hosts.n_received.tolist() == st2.hosts.n_received.tolist()
    assert st1.src_seq.tolist() == st2.src_seq.tolist()
    assert (st1.queues.time.sort(axis=1)
            == st2.queues.time.sort(axis=1)).all()


def test_sharded_hlo_audit_budgets():
    """The phold_sharded contract (collective-op budget, GSPMD-marker
    allowlist, host-callback ban) holds on the forced 8-device mesh."""
    from shadow_tpu.analysis import hlo_audit as H

    out = H.audit_all(["phold_sharded"])["phold_sharded"]
    assert "skipped" not in out, out
    assert out["ok"], out["violations"]
