"""Elastic shard recovery (docs/13-Elastic-Recovery.md).

Fast lane, in-process (conftest forces 8 virtual CPU devices, so every
mesh size up to 8 is available in tier-1):

- checkpoint format v6 migration: v5 files (no mesh identity) still
  load; `read_header_info` reports the stored mesh;
- reshard-on-resume bit-identity: a checkpoint written at 8 shards
  resumes through 4 shards down to 1 — and 1 back up to 8 — with every
  mesh-portable leaf bit-identical to the uninterrupted single-device
  run (the `.xchg` exchange buffer and the cross-shard telemetry
  counters are the only mesh-shaped state, and are excluded);
- the refusal paths: in-flight exchange events, sharded spill;
- atomic checkpoint IO: transient ENOSPC retries with backoff, and a
  hard failure that must leave the previous generation intact;
- `find_resume_checkpoint` candidates: the `.emergency` crash file and
  all-or-none sharded sets;
- the collective-stall Watchdog: peerlost bundle kind, compile-grace
  re-arming, exit-code taxonomy, `next_retry_argv` / `run_with_retry`
  with injected process control;
- zero-cost: the elastic plumbing (explicit `host_order`) leaves the
  lowered HLO byte-identical when it is a no-op.

Slow lane (subprocess, `-m slow`): the two chaos acceptance scenarios —
a wedged collective must exit 77 with a per-shard diagnostic bundle,
and the same failure under `--retry` must recover on a shrunken mesh to
a bit-identical summary.
"""

import glob
import json
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.config import parse_config
from shadow_tpu.core.timebase import SECOND
from shadow_tpu.parallel import mesh as pmesh
from shadow_tpu.sim import build_simulation
from shadow_tpu.utils import (
    find_resume_checkpoint,
    load_checkpoint,
    save_checkpoint,
    verify_checkpoint,
)
from shadow_tpu.utils import checkpoint as ckpt_mod
from shadow_tpu.utils.checkpoint import (
    _leaf_paths,
    read_header_info,
    shard_member_path,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# 16 hosts: divisible by every mesh size in the 8 -> 4 -> 1 -> 8 chain
CONFIG = """<shadow stoptime="10">
  <topology>
    <![CDATA[<graphml xmlns="http://graphml.graphdrawing.org/xmlns">
      <key attr.name="latency" attr.type="double" for="edge" id="d3" />
      <key attr.name="bandwidthup" attr.type="int" for="node" id="d2" />
      <key attr.name="bandwidthdown" attr.type="int" for="node" id="d1" />
      <graph edgedefault="undirected">
        <node id="poi-1">
          <data key="d1">2048</data>
          <data key="d2">2048</data>
        </node>
        <edge source="poi-1" target="poi-1">
          <data key="d3">50.0</data>
        </edge>
      </graph>
    </graphml>]]>
  </topology>
  <plugin id="phold" path="shadow-plugin-test-phold.so" />
  <host id="peer" quantity="16">
    <process plugin="phold" starttime="1" arguments="basename=peer quantity=16 load=4" />
  </host>
</shadow>"""


def _build(n_shards=1):
    mesh = pmesh.make_mesh(n_shards) if n_shards > 1 else None
    return build_simulation(parse_config(CONFIG), seed=7, mesh=mesh)


def _mesh_info(sim):
    return {
        "n_shards": (int(sim.mesh.devices.size)
                     if sim.mesh is not None else 1),
        "dcn_slices": 1,
        "host_order": (list(sim.host_order)
                       if sim.host_order is not None else None),
    }


# The exchange buffer and the scheduling telemetry counters are the
# only mesh-shaped state; everything else must survive a reshard
# bit-for-bit (ISSUE acceptance — mirrors bench.py CHAOS_CMP_KEYS).
# n_inner_steps counts per-shard drain substeps: each shard drains its
# own slice, so the global total grows with the shard count even when
# every event executes identically.
_MESH_TELEMETRY = ("n_cross_shard", "n_xchg_rounds", "n_inner_steps")


def _portable_leaves(state):
    out = {}
    for pth, leaf in zip(_leaf_paths(state), jax.tree_util.tree_leaves(state)):
        if pth.startswith(".xchg"):
            continue
        if any(t in pth for t in _MESH_TELEMETRY):
            continue
        out[pth] = np.asarray(jax.device_get(leaf))
    return out


def _assert_portable_equal(got, want, label):
    assert got.keys() == want.keys(), (
        f"{label}: portable leaf sets differ: "
        f"{sorted(got.keys() ^ want.keys())}")
    for pth in want:
        assert np.array_equal(got[pth], want[pth]), (
            f"{label}: leaf {pth} diverged from the uninterrupted run")


@pytest.fixture(scope="module")
def straight():
    """Uninterrupted single-device reference run to 10s."""
    sim = _build(1)
    final = sim.run(10 * SECOND)
    return _portable_leaves(final)


# ----------------------------------------------------------- v6 format


def _tree():
    return {
        "a": jnp.arange(64, dtype=jnp.int64),
        "b": jnp.linspace(0.0, 1.0, 32, dtype=jnp.float32),
    }


def _rewrite_header(path, mutate):
    with np.load(path, allow_pickle=False) as z:
        arrays = {k: z[k].copy() for k in z.files}
    header = json.loads(bytes(arrays["__header__"]).decode())
    mutate(header)
    arrays["__header__"] = np.frombuffer(
        json.dumps(header).encode(), dtype=np.uint8)
    np.savez(path, **arrays)


def test_checkpoint_format_v5_still_loads(tmp_path):
    """A v5 file (pre-mesh-identity) loads, reports mesh=None, and the
    reshard flag degrades gracefully on it."""
    path = str(tmp_path / "ck.npz")
    save_checkpoint(path, _tree(), meta={"sim_seconds": 2.0})

    def downgrade(header):
        header["format_version"] = 5
        header.pop("mesh", None)
        header.pop("xchg_empty", None)
        header.pop("shard", None)

    _rewrite_header(path, downgrade)

    info = read_header_info(path)
    assert info["format_version"] == 5
    assert info["mesh"] is None
    assert info["shard"] is None
    assert info["xchg_empty"] is True  # pre-v6 writers never had one

    tree, meta = load_checkpoint(path, _tree(), reshard=True)
    assert meta == {"sim_seconds": 2.0}
    assert jnp.array_equal(tree["a"], _tree()["a"])


def test_header_records_mesh_identity(tmp_path):
    path = str(tmp_path / "ck.npz")
    save_checkpoint(
        path, _tree(),
        mesh_info={"n_shards": 8, "dcn_slices": 2, "host_order": [1, 0]},
    )
    info = read_header_info(path)
    assert info["format_version"] == ckpt_mod.FORMAT_VERSION
    assert info["mesh"] == {
        "n_shards": 8, "dcn_slices": 2, "host_order": [1, 0]}


# ------------------------------------------------- reshard bit-identity


@pytest.mark.slow  # ~17s double-reshard chain; tier-1 keeps the 1->8 reshard
# bit-identity pin which exercises the same v6 mesh-identity path
def test_reshard_8_to_4_to_1_bit_identical(tmp_path, straight):
    """A run checkpointed at 8 shards resumes at 4, checkpoints again,
    resumes unsharded, and finishes bit-identical to the uninterrupted
    single-device run — the full shrink chain a --retry wrapper walks
    when peers keep dying."""
    ck = str(tmp_path / "ck.npz")

    sim8 = _build(8)
    mid = sim8.run(4 * SECOND)
    save_checkpoint(ck, mid, meta={"sim_seconds": 4.0},
                    mesh_info=_mesh_info(sim8))
    assert read_header_info(ck)["mesh"]["n_shards"] == 8
    assert read_header_info(ck)["xchg_empty"] is True

    sim4 = _build(4)
    st4, meta = load_checkpoint(ck, sim4.state0, reshard=True)
    assert meta["sim_seconds"] == 4.0
    later = sim4.run(7 * SECOND, state=st4)
    save_checkpoint(ck, later, meta={"sim_seconds": 7.0},
                    mesh_info=_mesh_info(sim4))

    sim1 = _build(1)
    st1, _ = load_checkpoint(ck, sim1.state0, reshard=True)
    final = sim1.run(10 * SECOND, state=st1)

    _assert_portable_equal(_portable_leaves(final), straight, "8->4->1")


def test_reshard_1_to_8_bit_identical(tmp_path, straight):
    """The grow direction: an unsharded checkpoint restores onto an
    8-shard mesh (capacity came back) and still finishes bit-identical."""
    ck = str(tmp_path / "ck.npz")

    sim1 = _build(1)
    mid = sim1.run(4 * SECOND)
    save_checkpoint(ck, mid, meta={"sim_seconds": 4.0},
                    mesh_info=_mesh_info(sim1))
    assert read_header_info(ck)["mesh"]["n_shards"] == 1

    sim8 = _build(8)
    st8, _ = load_checkpoint(ck, sim8.state0, reshard=True)
    final = sim8.run(10 * SECOND, state=st8)

    _assert_portable_equal(_portable_leaves(final), straight, "1->8")


def test_reshard_refuses_inflight_exchange(tmp_path):
    """A checkpoint whose exchange buffer holds an in-flight event must
    refuse to restore onto a *different* mesh — dropping it silently
    would break the lossless contract."""
    ck = str(tmp_path / "ck.npz")
    sim8 = _build(8)
    save_checkpoint(ck, sim8.state0, mesh_info=_mesh_info(sim8))

    with np.load(ck, allow_pickle=False) as z:
        arrays = {k: z[k].copy() for k in z.files}
    header = json.loads(bytes(arrays["__header__"]).decode())
    idx = next(i for i, p in enumerate(header["paths"])
               if p.startswith(".xchg") and p.endswith(".time"))
    leaf = arrays[f"leaf_{idx}"]
    leaf.flat[0] = 0  # one occupied slot: an event in flight
    np.savez(ck, **arrays)

    sim4 = _build(4)
    with pytest.raises(ValueError, match="in-flight"):
        load_checkpoint(ck, sim4.state0, reshard=True)


def test_reshard_sharded_ckpt_onto_spill_template(tmp_path):
    """The CLI's unsharded default is `--overflow spill`, which sharded
    builds refuse — so every mesh->1 resume crosses spill *presence*.
    The empty ring starts fresh from the template, exactly like the
    exchange buffer (caught live: a `--test --mesh 2` run's checkpoint
    could not resume unsharded)."""
    ck = str(tmp_path / "ck.npz")
    sim2 = _build(2)
    save_checkpoint(ck, sim2.state0, mesh_info=_mesh_info(sim2))

    sim1 = build_simulation(parse_config(CONFIG), seed=7, overflow="spill")
    st, _ = load_checkpoint(ck, sim1.state0, reshard=True)

    def spill_leaves(state):
        return {p: np.asarray(jax.device_get(leaf)) for p, leaf in
                zip(_leaf_paths(state), jax.tree_util.tree_leaves(state))
                if p.startswith(".queues.spill")}

    got, tpl = spill_leaves(st), spill_leaves(sim1.state0)
    assert got and got.keys() == tpl.keys()
    for p in tpl:
        assert np.array_equal(got[p], tpl[p]), p
    _assert_portable_equal(
        {p: a for p, a in _portable_leaves(st).items()
         if not p.startswith(".queues.spill")},
        _portable_leaves(sim2.state0), "2->1+spill")


def test_reshard_spill_ckpt_onto_sharded_mesh(tmp_path):
    """1 -> S crosses spill presence the other way: an empty ring is
    dropped (it cannot exist on a mesh); a ring holding parked events
    refuses loudly — resharding must never lose a spilled event. Same
    shard count keeps loading the ring bit-exact (mid-pressure resume
    is 1->1 only, docs/13)."""
    ck = str(tmp_path / "ck.npz")
    sim1 = build_simulation(parse_config(CONFIG), seed=7, overflow="spill")
    save_checkpoint(ck, sim1.state0, mesh_info=_mesh_info(sim1))
    sim4 = _build(4)
    st, _ = load_checkpoint(ck, sim4.state0, reshard=True)
    assert not any(p.startswith(".queues.spill") for p in _leaf_paths(st))

    with np.load(ck, allow_pickle=False) as z:
        arrays = {k: z[k].copy() for k in z.files}
    header = json.loads(bytes(arrays["__header__"]).decode())
    idx = next(i for i, p in enumerate(header["paths"])
               if p.startswith(".queues.spill") and p.endswith(".wr"))
    arrays[f"leaf_{idx}"].flat[0] = 1  # one parked event
    header["crc32"][idx] = ckpt_mod._crc(arrays[f"leaf_{idx}"])
    arrays["__header__"] = np.frombuffer(
        json.dumps(header).encode(), dtype=np.uint8)
    np.savez(ck, **arrays)

    with pytest.raises(ValueError, match="spilled"):
        load_checkpoint(ck, sim4.state0, reshard=True)
    st11, _ = load_checkpoint(ck, sim1.state0, reshard=True)
    assert np.asarray(jax.device_get(st11.queues.spill.wr)).flat[0] == 1


def test_sharded_mesh_refuses_spill_modes():
    """The pressure reservoir's boundary protocol is single-device only;
    a sharded build must fail loudly at build time, not lose events."""
    with pytest.raises(ValueError, match="sharded"):
        build_simulation(parse_config(CONFIG), seed=7,
                         mesh=pmesh.make_mesh(2), overflow="spill")


# ---------------------------------------------------------- atomic IO


def test_atomic_write_retries_transient_enospc(tmp_path, monkeypatch):
    """A transient ENOSPC mid-write retries with exponential backoff and
    still lands a verifiable checkpoint (the partial tmp reclaimed)."""
    path = str(tmp_path / "ck.npz")
    fails = {"n": 2}
    real = ckpt_mod._savez
    sleeps = []

    def flaky(f, **arrs):
        if fails["n"] > 0:
            fails["n"] -= 1
            raise OSError(28, "No space left on device")  # ENOSPC
        real(f, **arrs)

    monkeypatch.setattr(ckpt_mod, "_savez", flaky)
    monkeypatch.setattr(ckpt_mod, "_io_sleep", sleeps.append)

    save_checkpoint(path, _tree(), meta={"ok": 1})
    assert verify_checkpoint(path)["ok"] == 1
    assert sleeps == [ckpt_mod._IO_BACKOFF_S, 2 * ckpt_mod._IO_BACKOFF_S]
    assert not os.path.exists(path + ".tmp")


def test_atomic_write_hard_failure_keeps_previous(tmp_path, monkeypatch):
    """When every attempt fails, the error propagates AND the previous
    good generation survives untouched — the crash the rename protocol
    exists for."""
    path = str(tmp_path / "ck.npz")
    save_checkpoint(path, _tree(), meta={"gen": 0})

    def always(f, **arrs):
        raise OSError(28, "No space left on device")

    monkeypatch.setattr(ckpt_mod, "_savez", always)
    monkeypatch.setattr(ckpt_mod, "_io_sleep", lambda s: None)
    with pytest.raises(OSError):
        save_checkpoint(path, _tree(), meta={"gen": 1})
    assert verify_checkpoint(path)["gen"] == 0
    assert not os.path.exists(path + ".tmp")

    # a non-transient errno fails fast, no retry loop
    calls = {"n": 0}

    def eacces(f, **arrs):
        calls["n"] += 1
        raise OSError(13, "Permission denied")

    monkeypatch.setattr(ckpt_mod, "_savez", eacces)
    with pytest.raises(OSError):
        save_checkpoint(str(tmp_path / "other.npz"), _tree())
    assert calls["n"] == 1


# ------------------------------------------------- resume candidates


def test_emergency_checkpoint_preferred(tmp_path):
    """The crash-path `.emergency` file outranks the bare generation on
    an mtime tie (it was written at death, so it is furthest along)."""
    path = str(tmp_path / "ck.npz")
    save_checkpoint(path, _tree(), meta={"which": "interval"})
    save_checkpoint(path + ".emergency", _tree(), meta={"which": "crash"})
    now = time.time()
    os.utime(path, (now, now))
    os.utime(path + ".emergency", (now, now))

    chosen, meta, skipped = find_resume_checkpoint(path)
    assert chosen == path + ".emergency"
    assert meta["which"] == "crash"
    assert skipped == []

    # a corrupt emergency file is skipped, falling back to the interval
    open(path + ".emergency", "wb").write(b"junk")
    chosen, meta, skipped = find_resume_checkpoint(path)
    assert chosen == path
    assert meta["which"] == "interval"
    assert [p for p, _ in skipped] == [path + ".emergency"]


def test_shard_set_is_all_or_none(tmp_path):
    """A complete sharded set resumes as a member list; a torn set is
    never chosen, only reported."""
    path = str(tmp_path / "ck.npz")
    tree = {"per_host": jnp.arange(8, dtype=jnp.int64).reshape(4, 2)}
    for i in range(2):
        save_checkpoint(path, {"per_host": tree["per_host"][2 * i:2 * i + 2]},
                        meta={"member": i}, shard=(i, 2))
    members = [shard_member_path(path, i, 2) for i in range(2)]
    assert all(os.path.exists(m) for m in members)

    chosen, meta, skipped = find_resume_checkpoint(path)
    assert chosen == members
    assert meta["member"] == 1  # meta of the last-verified member
    assert skipped == []

    from shadow_tpu.utils import load_shard_set

    state, meta0 = load_shard_set(members, tree)
    assert meta0["member"] == 0
    assert jnp.array_equal(state["per_host"], tree["per_host"])

    # tear the set: the survivor alone must NOT be offered for resume
    os.remove(members[1])
    with pytest.raises(ValueError, match="incomplete shard set"):
        find_resume_checkpoint(path)


# ----------------------------------------------------------- watchdog


def test_watchdog_peerlost_fires_with_bundle(tmp_path):
    from shadow_tpu.runtime import EXIT_PEER_LOST, Watchdog

    codes: list[int] = []
    wd = Watchdog(
        0.3, diag_dir=str(tmp_path), label="t", kind="peerlost",
        exit_code=EXIT_PEER_LOST,
        _exit=codes.append, _stream=open(os.devnull, "w"),
    )
    wd.pet(site="harvest.fetch", sim_seconds=3.0)
    wd.start()
    deadline = time.monotonic() + 10.0
    while not codes and time.monotonic() < deadline:
        time.sleep(0.05)
    wd.stop()
    assert codes == [EXIT_PEER_LOST]

    bundle_path = tmp_path / f"t.peerlost.{os.getpid()}.json"
    bundle = json.loads(bundle_path.read_text())
    assert bundle["exit_code"] == EXIT_PEER_LOST
    assert "peerlost deadline expired" in bundle["reason"]
    assert bundle["progress"]["site"] == "harvest.fetch"
    assert bundle["compile_graces"] == 0
    # the stack dump rides along, distinct from any .stall. bundle
    assert (tmp_path / f"t.peerlost.{os.getpid()}.stacks.txt").exists()


def test_watchdog_compile_grace_rearms_then_fires(tmp_path):
    """With compile_grace, a deadline expiry while the main thread shows
    jax compiler frames re-arms instead of firing; once the compile
    fiction ends, the next expiry fires for real and the bundle records
    how many graces were granted."""
    from shadow_tpu.runtime import EXIT_PEER_LOST, Watchdog

    codes: list[int] = []
    wd = Watchdog(
        0.2, diag_dir=str(tmp_path), label="g", kind="peerlost",
        exit_code=EXIT_PEER_LOST, compile_grace=True,
        _exit=codes.append, _stream=open(os.devnull, "w"),
    )
    answers = iter([True, True])
    wd._main_thread_compiling = lambda: next(answers, False)
    wd.start()
    deadline = time.monotonic() + 15.0
    while not codes and time.monotonic() < deadline:
        time.sleep(0.05)
    wd.stop()
    assert codes == [EXIT_PEER_LOST]
    assert wd.compile_graces == 2
    bundle = json.loads(
        (tmp_path / f"g.peerlost.{os.getpid()}.json").read_text())
    assert bundle["compile_graces"] == 2


def test_watchdog_without_compile_grace_ignores_compiler_frames(tmp_path):
    """compile_grace off (the classic per-window stall deadline): a
    compiling main thread does NOT extend the deadline."""
    from shadow_tpu.runtime import EXIT_STALL, Watchdog

    codes: list[int] = []
    wd = Watchdog(
        0.2, diag_dir=str(tmp_path), label="n",
        _exit=codes.append, _stream=open(os.devnull, "w"),
    )
    wd._main_thread_compiling = lambda: True
    wd.start()
    deadline = time.monotonic() + 10.0
    while not codes and time.monotonic() < deadline:
        time.sleep(0.05)
    wd.stop()
    assert codes == [EXIT_STALL]
    assert wd.compile_graces == 0


def test_main_thread_compiling_false_in_plain_code():
    from shadow_tpu.runtime import Watchdog

    wd = Watchdog(5.0, _exit=lambda c: None)
    assert wd._main_thread_compiling() is False  # we are not in jax lowering


# -------------------------------------------------------- retry loop


def test_exit_code_taxonomy():
    from shadow_tpu.runtime import (
        EXIT_INVARIANT,
        EXIT_PEER_LOST,
        EXIT_PRESSURE,
        EXIT_STALL,
        exit_retryable,
    )

    assert (EXIT_STALL, EXIT_INVARIANT, EXIT_PRESSURE, EXIT_PEER_LOST) \
        == (75, 70, 76, 77)
    assert exit_retryable(EXIT_STALL)
    assert exit_retryable(EXIT_PEER_LOST)
    assert exit_retryable(-int(signal.SIGKILL))  # Popen's signal death
    assert exit_retryable(128 + int(signal.SIGKILL))
    assert exit_retryable(128 + int(signal.SIGTERM))
    assert not exit_retryable(0)
    assert not exit_retryable(EXIT_INVARIANT)  # a bug, not a transient
    assert not exit_retryable(EXIT_PRESSURE)
    assert not exit_retryable(2)


def test_next_retry_argv_resume_and_shrink():
    from shadow_tpu.runtime import EXIT_PEER_LOST, EXIT_STALL, next_retry_argv

    # a stall relaunch resumes (from zero if no checkpoint yet) but
    # keeps its mesh: the peers are all still there
    assert next_retry_argv(["prog", "--mesh", "8"], EXIT_STALL) == \
        ["prog", "--mesh", "8", "--resume", "auto-if-any"]
    # an existing --resume is respected, not duplicated
    assert next_retry_argv(["prog", "--resume", "auto"], EXIT_STALL) == \
        ["prog", "--resume", "auto"]
    assert next_retry_argv(["prog", "--resume=auto"], EXIT_STALL) == \
        ["prog", "--resume=auto"]
    # peer lost: halve the mesh, both flag spellings, floor at 1
    assert next_retry_argv(["p", "--mesh", "8"], EXIT_PEER_LOST,
                           shrink=True)[:3] == ["p", "--mesh", "4"]
    assert next_retry_argv(["p", "--mesh=8"], EXIT_PEER_LOST,
                           shrink=True)[1] == "--mesh=4"
    assert next_retry_argv(["p", "--mesh", "1"], EXIT_PEER_LOST,
                           shrink=True)[:3] == ["p", "--mesh", "1"]


class _FakeProc:
    """Enough of Popen for run_with_retry: a scripted exit code and a
    pid that cannot exist, so the post-mortem killpg is a harmless
    ProcessLookupError."""

    def __init__(self, rc):
        self.rc = rc
        self.stderr = None
        self.pid = 2 ** 31 - 1

    def wait(self):
        return self.rc


def test_run_with_retry_recovers_and_shrinks():
    from shadow_tpu.runtime import run_with_retry

    rcs = iter([75, 77, 0])
    seen: list[list[str]] = []
    sleeps: list[float] = []

    def popen(argv, **kw):
        seen.append(list(argv))
        return _FakeProc(next(rcs))

    report = run_with_retry(["prog", "--mesh", "8"], retries=3,
                            backoff_s=0.5, _sleep=sleeps.append,
                            _popen=popen)
    assert report["attempts"] == 3
    assert report["recoveries"] == 2
    assert report["exit_code"] == 0
    assert report["exit_history"] == [75, 77, 0]
    assert len(report["mttr_s"]) == 2
    assert sleeps == [0.5, 1.0]  # exponential backoff
    assert seen[0] == ["prog", "--mesh", "8"]
    # stall: resume, same mesh
    assert seen[1] == ["prog", "--mesh", "8", "--resume", "auto-if-any"]
    # peer lost: resume AND halve
    assert seen[2] == ["prog", "--mesh", "4", "--resume", "auto-if-any"]


def test_run_with_retry_stops_on_nonretryable():
    from shadow_tpu.runtime import run_with_retry

    report = run_with_retry(["prog"], retries=5, _sleep=lambda s: None,
                            _popen=lambda argv, **kw: _FakeProc(2))
    assert report == {"attempts": 1, "recoveries": 0, "exit_code": 2,
                      "exit_history": [2], "mttr_s": []}


def test_run_with_retry_exhausts_budget():
    from shadow_tpu.runtime import run_with_retry

    report = run_with_retry(["prog"], retries=1, _sleep=lambda s: None,
                            _popen=lambda argv, **kw: _FakeProc(75))
    assert report["attempts"] == 2
    assert report["exit_code"] == 75
    assert report["exit_history"] == [75, 75]
    assert report["recoveries"] == 1


# ----------------------------------------------------------- zero cost


def test_elastic_host_order_plumbing_is_zero_cost():
    """`host_order` is the reshard-resume plumbing threaded through
    build_simulation; passing the identity permutation must leave the
    build indistinguishable — same leaves, same paths, byte-identical
    HLO. (The watchdog and retry loop live entirely outside the jitted
    program, so this pins the only build-path touch point.)"""
    from shadow_tpu.analysis.hlo_audit import assert_zero_cost

    cfg = parse_config(CONFIG)
    base = build_simulation(cfg, seed=7)
    off = build_simulation(cfg, seed=7,
                           host_order=list(range(len(base.names))))
    on = build_simulation(cfg, seed=7, trace=8)  # known-different build
    assert off.host_order is not None
    assert_zero_cost((base.engine, base.state0), (off.engine, off.state0),
                     (on.engine, on.state0), jnp.int64(base.stop_ns))


# ------------------------------------------------ chaos (subprocess)


def _cli_env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    env.update(extra)
    return env


def _last_json(text):
    for line in reversed(text.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise AssertionError(f"no JSON summary line in output:\n{text}")


_CMP_KEYS = ("events", "windows", "net_dropped", "queue_drops",
             "fault_dropped", "quarantined_events", "sweeps",
             "rx_bytes", "tx_bytes", "events_by_kind")


def _sig(summary):
    return {k: summary[k] for k in _CMP_KEYS if k in summary}


@pytest.mark.slow
def test_collective_stall_exits_77_with_bundle(tmp_path):
    """Chaos acceptance, detection half: a wedged collective (injected
    via SHADOW_TPU_CHAOS_HANG_S) must trip the --collective-timeout
    deadline — exit 77 with a peerlost diagnostic bundle, not a hang."""
    cfg_path = tmp_path / "phold.config.xml"
    cfg_path.write_text(CONFIG)
    ck = str(tmp_path / "ck.npz")
    r = subprocess.run(
        [sys.executable, "-m", "shadow_tpu", str(cfg_path),
         "--seed", "1", "--mesh", "8", "--overflow", "drop",
         "--checkpoint-interval", "4", "--checkpoint-path", ck,
         "--collective-timeout", "3", "--diag-dir", str(tmp_path)],
        cwd=REPO, env=_cli_env(SHADOW_TPU_CHAOS_HANG_S="60"),
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 77, f"rc={r.returncode}\n{r.stderr}"
    bundles = glob.glob(str(tmp_path / "*.peerlost.*.json"))
    assert len(bundles) == 1, r.stderr
    bundle = json.loads(open(bundles[0]).read())
    assert bundle["exit_code"] == 77
    # the injection armed only after the first window, so the watchdog
    # had been petted with real progress before the wedge
    assert bundle["windows_reported"] > 0
    assert os.path.exists(ck + ".chaos")  # the one-shot marker


@pytest.mark.slow
def test_retry_recovers_from_wedged_collective_bit_identical(tmp_path):
    """Chaos acceptance, recovery half: the same wedged collective under
    --retry must come back on a halved mesh from the newest checkpoint
    and finish exit 0 with a summary bit-identical to a clean run."""
    cfg_path = tmp_path / "phold.config.xml"
    cfg_path.write_text(CONFIG)

    def run(tag, extra, **env):
        ck = str(tmp_path / f"{tag}.npz")
        r = subprocess.run(
            [sys.executable, "-m", "shadow_tpu", str(cfg_path),
             "--seed", "1", "--mesh", "8", "--overflow", "drop",
             "--checkpoint-interval", "4", "--checkpoint-path", ck,
             "--diag-dir", str(tmp_path)] + extra,
            cwd=REPO, env=_cli_env(**env),
            capture_output=True, text=True, timeout=600,
        )
        return r

    clean = run("clean", [])
    assert clean.returncode == 0, clean.stderr
    want = _sig(_last_json(clean.stdout))

    chaos = run(
        "chaos",
        ["--retry", "2", "--retry-backoff", "0.2",
         "--collective-timeout", "5"],
        SHADOW_TPU_CHAOS_HANG_S="60",
    )
    assert chaos.returncode == 0, chaos.stderr
    assert "retry report" in chaos.stderr
    report = json.loads(
        chaos.stderr.split("retry report ", 1)[1].splitlines()[0])
    assert 77 in report["exit_history"]
    assert report["exit_history"][-1] == 0
    assert report["recoveries"] >= 1
    assert report["mttr_s"], "MTTR must be measured per recovery"
    assert _sig(_last_json(chaos.stdout)) == want, (
        "recovered run diverged from the clean run")
