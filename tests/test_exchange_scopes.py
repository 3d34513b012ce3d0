"""Where the sharded window program's collectives and cross-shard merges
sit among the phases (shadow_tpu/core/phases.py), compiled over four
devices: the placements a device profile by phase relies on to split
the exchange, the delivery and the barrier apart.

- every `all-to-all` counts under `exchange`;
- the exchange's route sort and the slices that cut each round's
  bucket count under `exchange` itself, and no scatter does;
- every `all-gather` (the barrier's `_gmin`) counts under `barrier`;
- the delivery of a received bucket (`Engine._xchg_deliver`) runs its
  queue push as `merge` ops inside `deliver`, both at the top of a
  sweep and between two exchange rounds.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from shadow_tpu.core.phases import PHASES

_OP = re.compile(r"=.*?\s([a-z][\w-]*)\(.*op_name=\"([^\"]*)\"")


@pytest.fixture(scope="module")
def ops():
    """(opcode, enclosing phases outermost first) of every instruction of
    the compiled four-device PHOLD window program that has an op_name."""
    from shadow_tpu.models import phold
    from shadow_tpu.parallel import mesh as pmesh

    eng, init = phold.build(8, seed=3, capacity=32, msgs_per_host=2,
                            axis_name=pmesh.HOSTS_AXIS, n_shards=4)
    init_s, run, _ = pmesh.build_sharded(eng, init, pmesh.make_mesh(4), 8)
    text = jax.jit(run).lower(  # shadowlint: no-donate=compiled for inspection only
        jax.eval_shape(init_s), jnp.int64(5_000_000_000)).compile().as_text()
    out = []
    for m in _OP.finditer(text):
        names = m.group(2).split("/")[:-1]
        out.append((m.group(1), [p for p in names if p in PHASES]))
    return out


@pytest.mark.parametrize("opcode,phase", [("all-to-all", "exchange"),
                                          ("all-gather", "barrier")])
def test_collective_counts_under_its_phase(ops, opcode, phase):
    found = [scopes[-1:] for op, scopes in ops if op == opcode]
    assert found and all(s == [phase] for s in found), found


@pytest.mark.parametrize("inside_exchange", [False, True],
                         ids=["sweep_top", "between_rounds"])
def test_delivery_push_is_merge_inside_deliver(ops, inside_exchange):
    merges = [scopes for _, scopes in ops
              if scopes[-1:] == ["merge"] and "deliver" in scopes]
    assert any(("exchange" in s) == inside_exchange for s in merges)


@pytest.mark.parametrize("opcode", ["sort", "dynamic-slice"])
def test_route_build_counts_under_exchange(ops, opcode):
    """`Engine._route_sort`'s one sort per push and `_route_bucket`'s
    per-round slices sit directly under `exchange` (not under the
    `merge` or `deliver` nested in it)."""
    assert any(op == opcode and scopes[-1:] == ["exchange"]
               for op, scopes in ops)


def test_no_scatter_under_exchange(ops):
    found = [scopes for op, scopes in ops
             if op == "scatter" and "exchange" in scopes]
    assert not found, found
