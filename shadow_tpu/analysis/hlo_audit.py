"""HLO contract auditor: the lowering invariants, declared and checked.

The paper's performance story rests on what the window loop lowers to
(ROADMAP.md invariants): a single fused XLA program, sort-based queue
maintenance with no scatter in the unsharded hot path, no host
callbacks inside the loop, and byte-identical HLO when optional
subsystems (trace ring, spill ring, faults) are off. Until now those
were checked by ad-hoc string asserts copy-pasted across test files;
this module makes them declared contracts:

- `CONTRACTS` maps each model config to an `HloContract` (per-op
  budgets, custom-call allowlist, host-callback ban). The raw phold
  engine must be scatter-free; config-driven models get a small scatter
  budget for the TCP accept/bind row-slot updates in `host/sockets.py`
  (bounded, outside the per-event fast path). Budgets are checked
  against the structural op graph (`hlo_graph.parse_module`), so ops
  in dead private helper funcs never count and quoted custom_call
  targets (`@"..."`) resolve — the flat-regex predecessor had both
  blind spots.
- `audit_model(name)` builds a tiny instance of the config, lowers
  `Engine.run`, and returns violations against the contract.
- `phold_sharded` is the SPMD contract: the sharded PHOLD window loop
  lowered over an 8-device mesh (forced CPU devices in CI), with an
  explicit collective-op budget so exchange-op creep is regression-
  guarded the same way scatter creep is, and an allowlist holding
  exactly the GSPMD partitioning markers (`@Sharding`,
  `@SPMDFullToShardShape`, `@SPMDShardToFullShape`) — host callbacks
  stay banned in the sharded lowering too.
- `assert_no_recompile(fn, calls)` guards the one-program claim via
  jit cache inspection.
- `assert_zero_cost(base, off, on, stop)` is the single zero-cost
  checker (leaf count + pytree structure + checkpoint leaf paths +
  byte-identical lowered text) shared by the trace/pressure/faults
  test suites.

CLI: ``python -m shadow_tpu.tools.lint --hlo-audit all``.
"""

from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Any, Callable, Iterable

from shadow_tpu.analysis import hlo_graph

# Ops that move control to the host (or to an opaque callback) — never
# acceptable inside the window loop under any budget.
HOST_CALLBACK_OPS = frozenset({
    "infeed", "outfeed", "send", "recv",
})
HOST_CALLBACK_TARGETS = (
    "xla_python_cpu_callback",
    "xla_python_gpu_callback",
    "xla_ffi_python_cpu_callback",
    "CallbackCustomCall",
)


@dataclasses.dataclass(frozen=True)
class HloContract:
    """Declared lowering budget for one model config.

    `budgets` caps specific op counts (0 forbids outright); any op not
    listed is unconstrained. `custom_call_allow` lists permitted
    custom_call targets; every other target is a violation. Host
    callbacks (infeed/outfeed/send/recv + python-callback custom
    calls) are always forbidden.
    """

    name: str
    budgets: dict  # op name -> max count
    custom_call_allow: tuple = ()

    def check(self, text: str) -> list[str]:
        return audit_text(text, self)


def ops_histogram(text: str) -> Counter:
    """Per-instance counts of dialect ops reachable from the entry
    func (dead private helpers excluded — structural, not textual)."""
    return hlo_graph.parse_module(text).histogram()


def custom_call_targets(text: str) -> list[str]:
    """Reachable custom_call targets. `call_target_name = "x"` is
    authoritative when present (the `@x` on such a line is just the
    op's pretty-printed symbol); otherwise the `@x` / quoted `@"x"`
    symbol of the stablehlo pretty form counts."""
    return hlo_graph.parse_module(text).custom_call_targets()


def audit_graph(module: hlo_graph.Module,
                contract: HloContract) -> list[str]:
    """Check a parsed op graph against a contract; [] means clean."""
    hist = module.histogram()
    violations: list[str] = []
    for op, cap in sorted(contract.budgets.items()):
        n = hist.get(op, 0)
        if n > cap:
            violations.append(
                f"{contract.name}: {n}x stablehlo.{op} exceeds budget "
                f"{cap}")
    for op in sorted(HOST_CALLBACK_OPS):
        if hist.get(op, 0):
            violations.append(
                f"{contract.name}: host-transfer op stablehlo.{op} in "
                f"lowered program")
    targets = module.custom_call_targets()
    for t in targets:
        if t in HOST_CALLBACK_TARGETS:
            violations.append(
                f"{contract.name}: host-callback custom_call `{t}`")
        elif t not in contract.custom_call_allow:
            violations.append(
                f"{contract.name}: custom_call target `{t}` not in "
                f"allowlist {sorted(contract.custom_call_allow)}")
    return violations


def audit_text(text: str, contract: HloContract) -> list[str]:
    """Check lowered IR text against a contract; [] means clean."""
    return audit_graph(hlo_graph.parse_module(text), contract)


# The raw engine (no socket stack) must stay scatter-free — the queue
# is maintained by sorts alone (ROADMAP invariant). Config-driven
# models lower one scatter per (host_row, slot) socket-table update
# site in host/sockets.py and the app models (accept/bind/stream
# bookkeeping): the count is structural — per traced update site, not
# per host or per event — so it is pinned exactly at today's value per
# config. A failing budget means a new scatter entered the window loop;
# either hoist it to sort/where form or consciously raise the budget
# here with a comment. (Budgets were halved when the audit moved from
# regex counting to the op graph: the regex counted every scatter
# twice — once for the op, once for its `#stablehlo.scatter<...>`
# dimension_numbers attribute.)
def _budget(scatter: int) -> dict:
    return {"scatter": scatter, "select_and_scatter": 0, "custom_call": 0}


# The number of forced-CPU devices the sharded contract lowers over
# (the tests' conftest forces this count).
SHARDED_DEVICES = 8

CONTRACTS: dict[str, HloContract] = {
    "phold": HloContract("phold", _budget(0)),
    "phold_net": HloContract("phold_net", _budget(4)),
    "tgen": HloContract("tgen", _budget(11)),
    "tor": HloContract("tor", _budget(7)),
    "bitcoin": HloContract("bitcoin", _budget(21)),
    # The same configs under the frontier drain (ISSUE 13 model-tier
    # batching). Budgets pinned equal to the chained contracts: the
    # frontier executor is built on sort / one-hot select / dynamic
    # slice only, so switching drains must add NO scatter — a frontier
    # budget above its chained twin means per-position bookkeeping
    # regressed into scattered writes.
    "tgen_frontier": HloContract("tgen_frontier", _budget(11)),
    "tor_frontier": HloContract("tor_frontier", _budget(7)),
    "bitcoin_frontier": HloContract("bitcoin_frontier", _budget(21)),
    # The vmapped fleet lowering (ISSUE 15 scenario fleets): the same
    # window loops batched over a 4-lane seed sweep. Budgets are pinned
    # EQUAL to the solo contracts — batching a program over scenario
    # lanes must add no scatter (vmap maps sort->sort, gather->gather,
    # scatter->scatter with a leading batch dim; the lane binds are
    # plain traced operands), and the op counts are lane-count-
    # independent (tests/test_fleet.py compares L=1 vs L=4 histograms).
    # A fleet budget above its solo twin means lane batching regressed
    # into per-lane bookkeeping writes.
    "phold_fleet": HloContract("phold_fleet", _budget(0)),
    "tgen_fleet": HloContract("tgen_fleet", _budget(11)),
    # The serve warm path (ISSUE 17 resident serving): the fleet's
    # fixed-window lane step under per-lane stops — the program
    # `Fleet.step_window` jits once and the service re-invokes per
    # request batch via `make_inputs`. Budget pinned equal to the
    # phold fleet contract: giving each lane its own traced stop adds
    # one vmap axis on a scalar, which must add NO scatter.
    "phold_serve": HloContract("phold_serve", _budget(0)),
    # The SPMD lowering of the raw PHOLD window loop over an 8-device
    # mesh. Every count is structural (per traced site x per Events
    # leaf), none scale with hosts or events:
    # - scatter 0: like the raw engine on one device, the sharded loop
    #   is scatter-free. The exchange groups each push by destination
    #   shard in one stable sort and cuts every round's [S, R] route
    #   bucket as contiguous slices (`Engine._route_bucket`).
    # - all_to_all 12: one per Events leaf per traced exchange site
    #   (the bucketed cross-shard delivery).
    # - all_reduce 8: the carried drain flags — computed in loop
    #   BODIES; the companion test (test_spmd.py) asserts none sits in
    #   a while predicate — plus, per exchange site, the pmax of the
    #   longest run that fixes the round count before the round loop
    #   and the psum of the crossings.
    # - all_gather 2: the i64 window barrier (`Engine._gmin`), one per
    #   traced site — the TPU lowers a 64-bit all-reduce only as a sum.
    # A count above budget means a new collective or scatter entered
    # the sharded hot path; below budget, re-pin with a comment.
    "phold_sharded": HloContract(
        "phold_sharded",
        {"scatter": 0, "select_and_scatter": 0,
         "all_to_all": 12, "all_reduce": 8,
         "collective_permute": 0, "all_gather": 2},
        custom_call_allow=(
            "Sharding", "SPMDFullToShardShape", "SPMDShardToFullShape",
        ),
    ),
}


# ----------------------------------------------------------- lowering


def lower_text(run: Callable, state: Any, stop) -> str:
    """StableHLO text of jit(run) lowered at (state, stop)."""
    import jax

    return jax.jit(run).lower(state, stop).as_text()  # shadowlint: no-donate=lowering for inspection only; donation would add input_output_alias lines to every audited contract


def _build(name: str):
    """(run, state, stop) for a tiny instance of a model config.

    Sizes are the smallest that exercise the full drain/exchange path;
    the audit checks op structure, which is size-independent.
    """
    import jax.numpy as jnp

    if name == "phold":
        from shadow_tpu.models import phold

        eng, init = phold.build(8, seed=3, capacity=32, msgs_per_host=2)
        return eng.run, init(), jnp.int64(5_000_000_000)

    if name == "phold_fleet":
        from shadow_tpu.models import phold
        from shadow_tpu.runtime.fleet import build_fleet_from_engine

        eng, init = phold.build(8, seed=3, capacity=32, msgs_per_host=2)
        fleet = build_fleet_from_engine(
            eng, init(), 4, seeds=(0, 1, 2, 3)
        )
        return fleet.run_fn(), fleet.state0, jnp.int64(5_000_000_000)

    if name == "phold_serve":
        from shadow_tpu.models import phold
        from shadow_tpu.runtime.fleet import Fleet, FleetPlan

        eng, init = phold.build(8, seed=3, capacity=32, msgs_per_host=2)
        fleet = Fleet(eng, init(), FleetPlan(lanes=4, seeds=(0, 1, 2, 3)),
                      per_lane_stop=True)
        # the warm-path program: the fixed-window lane step the serving
        # plane re-invokes per packed batch (Fleet.step_window's
        # `_jit_step_fixed`), with per-lane [L] stops traced in
        import jax

        _, lane_step = fleet._make_lane_fns()
        stepped = jax.vmap(lambda s, bi, t: lane_step(s, bi, t, None),
                           in_axes=(0, 0, 0))
        binds = fleet.binds
        run = lambda st, stop: stepped(st, binds, stop)  # noqa: E731
        return run, fleet.state0, jnp.full((4,), jnp.int64(5_000_000_000))

    if name == "tgen_fleet":
        from shadow_tpu import examples
        from shadow_tpu.config import parse_config
        from shadow_tpu.sim import build_fleet, build_simulation

        sim = build_simulation(parse_config(examples.example_config()),
                               seed=3)
        fleet = build_fleet(sim, 4, seeds=(0, 1, 2, 3))
        return fleet.run_fn(), fleet.state0, jnp.int64(sim.stop_ns)

    if name == "phold_sharded":
        import jax

        from shadow_tpu.models import phold
        from shadow_tpu.parallel import mesh as pmesh

        n = SHARDED_DEVICES
        eng, init = phold.build(
            8, seed=3, capacity=32, msgs_per_host=2,
            axis_name=pmesh.HOSTS_AXIS, n_shards=n,
        )
        m = pmesh.make_mesh(n)  # raises RuntimeError when devices < n
        init_s, run, _ = pmesh.build_sharded(eng, init, m, 8)
        # abstract state: the audit inspects the lowering, never runs it
        return run, jax.eval_shape(init_s), jnp.int64(5_000_000_000)

    from shadow_tpu import examples
    from shadow_tpu.config import parse_config
    from shadow_tpu.sim import build_simulation

    # `<model>_frontier` lowers the identical config under the frontier
    # drain (docs/11-Performance.md "Model-tier batching") — a separate
    # contract because the window loop's body is a different program
    base, frontier = name, 0
    if name.endswith("_frontier"):
        base, frontier = name[: -len("_frontier")], 8

    if base == "phold_net":
        text = examples.phold_example(8, msgs_per_host=2, stoptime=5)
    elif base == "tgen":
        text = examples.example_config()
    elif base == "tor":
        text = examples.tor_example(n_relays_per_class=2, n_clients=4,
                                    n_servers=2, stoptime=5)
    elif base == "bitcoin":
        text = examples.bitcoin_example(n_nodes=8, blocks=1, stoptime=5)
    else:
        raise KeyError(f"unknown model config `{name}` "
                       f"(have {sorted(CONTRACTS)})")
    sim = build_simulation(parse_config(text), seed=3, frontier=frontier)
    return sim.engine.run, sim.state0, jnp.int64(sim.stop_ns)


def audit_model(name: str) -> tuple[str, list[str]]:
    """Lower one model config and audit it. Returns (text, violations)."""
    contract = CONTRACTS[name]
    run, state, stop = _build(name)
    text = lower_text(run, state, stop)
    return text, audit_text(text, contract)


def audit_all(names: Iterable[str] | None = None) -> dict[str, dict]:
    """Audit several configs; per-config dict has `violations` and the
    op histogram (for the JSON report)."""
    out: dict[str, dict] = {}
    for name in (names or sorted(CONTRACTS)):
        try:
            run, state, stop = _build(name)
            text = lower_text(run, state, stop)
        except RuntimeError as e:
            # the sharded contract needs SHARDED_DEVICES devices; on a
            # smaller host (no --xla_force_host_platform_device_count)
            # it is skipped, not failed
            out[name] = {"ok": True, "skipped": str(e),
                         "violations": [], "ops": {}}
            continue
        module = hlo_graph.parse_module(text)
        violations = audit_graph(module, CONTRACTS[name])
        hist = module.histogram()
        out[name] = {
            "ok": not violations,
            "violations": violations,
            "ops": {k: hist[k] for k in sorted(hist) if k in
                    ("scatter", "sort", "while", "gather", "custom_call",
                     "all_to_all", "all_reduce", "collective_permute",
                     "infeed", "outfeed", "send", "recv")},
        }
    return out


# ----------------------------------------------------- recompile guard


def assert_no_recompile(fn: Callable, calls: Iterable[tuple]) -> int:
    """Call jit(fn) across `calls` (same shapes/dtypes expected) and
    assert the jit cache holds exactly one entry — the one-program
    claim, checked rather than assumed."""
    import jax

    j = jax.jit(fn)
    for args in calls:
        jax.block_until_ready(j(*args))  # shadowlint: no-deadline=offline audit tool; no live mesh to lose
    size = j._cache_size()
    if size != 1:
        raise AssertionError(
            f"expected one compiled program, jit cache holds {size} — "
            f"an argument is changing shape/dtype/structure across calls")
    return size


# ----------------------------------------------------- zero-cost check


def _run_of(obj: Callable | Any) -> Callable:
    return obj.run if hasattr(obj, "run") else obj


def assert_zero_cost(base, off, on, stop, *, get_subtree=None) -> dict:
    """The centralized trace/spill/faults zero-cost check.

    `base`/`off`/`on` are (engine_or_run, state) pairs: `base` built
    with defaults, `off` with the subsystem explicitly disabled, `on`
    with it enabled. Asserts the off build is indistinguishable from
    the base build — same leaf count, same pytree structure, same
    checkpoint leaf paths, byte-identical lowered HLO — and that the
    on build actually lowers differently (so the check cannot pass
    vacuously). `get_subtree(state)` optionally points at the
    subsystem's state slot, asserted None when off / present when on.

    Returns {"base": text, "off": text, "on": text} for extra checks.
    """
    import jax

    from shadow_tpu.utils.checkpoint import _leaf_paths

    (eng_b, st_b), (eng_off, st_off), (eng_on, st_on) = base, off, on

    n_b = len(jax.tree.leaves(st_b))
    n_off = len(jax.tree.leaves(st_off))
    assert n_off == n_b, \
        f"off state has {n_off} leaves vs base {n_b} — the disabled " \
        f"subsystem still contributes pytree leaves"
    assert jax.tree.structure(st_off) == jax.tree.structure(st_b), \
        "off/base pytree structures differ"
    assert _leaf_paths(st_off) == _leaf_paths(st_b), \
        "off/base checkpoint leaf layouts differ"

    if get_subtree is not None:
        # state-carrying subsystems (trace ring, spill ring): the on
        # build must hold the subtree and grow the leaf set. Engine-
        # constant subsystems (faults) change only the program — pass
        # get_subtree=None for those.
        assert get_subtree(st_b) is None, \
            "base state carries the optional subsystem's subtree"
        assert get_subtree(st_off) is None, \
            "off state carries the optional subsystem's subtree"
        assert get_subtree(st_on) is not None, \
            "on state is missing the subsystem's subtree (check knobs)"
        assert len(jax.tree.leaves(st_on)) > n_b, \
            "on state added no leaves — the subsystem is not actually on"

    text_b = lower_text(_run_of(eng_b), st_b, stop)
    text_off = lower_text(_run_of(eng_off), st_off, stop)
    text_on = lower_text(_run_of(eng_on), st_on, stop)
    assert text_off == text_b, \
        "disabled subsystem changed the lowered program (zero-cost " \
        "violation — diff the returned texts)"
    assert text_on != text_b, \
        "enabled subsystem lowered identically to base — the zero-cost " \
        "check is vacuous"
    return {"base": text_b, "off": text_off, "on": text_on}
