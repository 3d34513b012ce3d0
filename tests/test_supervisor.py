"""Supervised runs: watchdog, graceful shutdown, invariant guard.

Fast lane: unit tests drive the runtime layer in-process — the watchdog
with an injected exit so a firing is observable instead of fatal, the
supervisor's signal handlers via os.kill on our own pid, the invariant
checker on a real mid-run EngineState and on deliberately corrupted
copies of it.

Slow lane (subprocess, `-m slow`): the two acceptance scenarios from
the issue — SIGTERM mid-run must leave a CRC-verified checkpoint whose
resumed continuation is bit-identical to an uninterrupted run, and a
native plugin spinning inside shim_main must be detected by the
watchdog, which exits 75 with a diagnostic bundle instead of hanging
until the outer CI timeout.
"""

import json
import os
import shutil
import signal
import subprocess
import sys
import textwrap
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------- watchdog


def test_watchdog_rejects_nonpositive_timeout():
    from shadow_tpu.runtime import Watchdog

    with pytest.raises(ValueError):
        Watchdog(0.0)


def test_watchdog_fires_and_writes_bundle(tmp_path):
    from shadow_tpu.runtime import EXIT_STALL, Watchdog

    codes: list[int] = []
    wd = Watchdog(
        0.3, diag_dir=str(tmp_path), label="t",
        info=lambda: {"live_pids": [11, 12]},
        _exit=codes.append, _stream=open(os.devnull, "w"),
    )
    wd.pet(now_ns=123, windows=7)
    wd.start()
    deadline = time.monotonic() + 10.0
    while not codes and time.monotonic() < deadline:
        time.sleep(0.05)
    wd.stop()
    assert codes == [EXIT_STALL]
    assert wd.fired

    base = tmp_path / f"t.stall.{os.getpid()}"
    stacks = (base.parent / (base.name + ".stacks.txt")).read_text(
        errors="replace"
    )
    assert "Thread" in stacks  # faulthandler dumped every thread
    bundle = json.loads((base.parent / (base.name + ".json")).read_text())
    assert bundle["exit_code"] == EXIT_STALL
    assert bundle["stalled_for_s"] >= 0.3
    assert bundle["progress"]["now_ns"] == 123
    assert bundle["progress"]["windows"] == 7
    assert bundle["live_pids"] == [11, 12]


def test_watchdog_pet_keeps_alive(tmp_path):
    from shadow_tpu.runtime import Watchdog

    codes: list[int] = []
    wd = Watchdog(0.5, diag_dir=str(tmp_path), _exit=codes.append)
    wd.start()
    for _ in range(15):  # 1.5s of petting, 3x the deadline
        time.sleep(0.1)
        wd.pet()
    assert wd.margin_s() > 0
    wd.stop()
    assert codes == [] and not wd.fired


def test_watchdog_bundle_survives_broken_info(tmp_path):
    from shadow_tpu.runtime import Watchdog

    codes: list[int] = []

    def bad_info():
        raise RuntimeError("info source is the broken part")

    wd = Watchdog(0.2, diag_dir=str(tmp_path), label="b", info=bad_info,
                  _exit=codes.append, _stream=open(os.devnull, "w"))
    wd.start()
    deadline = time.monotonic() + 10.0
    while not codes and time.monotonic() < deadline:
        time.sleep(0.05)
    wd.stop()
    bundle = json.loads(
        (tmp_path / f"b.stall.{os.getpid()}.json").read_text()
    )
    assert "info_error" in bundle


# ------------------------------------------------------------- supervisor


def test_signal_exit_codes():
    from shadow_tpu.runtime import signal_exit_code

    assert signal_exit_code(signal.SIGTERM) == 143
    assert signal_exit_code(signal.SIGINT) == 130


def test_supervisor_sigusr1_one_shot(capsys):
    from shadow_tpu.runtime import Supervisor

    with Supervisor() as sup:
        assert not sup.take_checkpoint_request()
        os.kill(os.getpid(), signal.SIGUSR1)
        deadline = time.monotonic() + 5.0
        while not sup._ckpt_requested and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sup.take_checkpoint_request()
        assert not sup.take_checkpoint_request()  # drained
        assert not sup.stop_requested


def test_supervisor_sigterm_requests_stop(capsys):
    from shadow_tpu.runtime import Supervisor

    before = signal.getsignal(signal.SIGTERM)
    with Supervisor() as sup:
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.monotonic() + 5.0
        while not sup.stop_requested and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sup.stop_requested
        assert sup.exit_code() == 143
        # one-shot escalation: the next SIGTERM would get the default
        # (fatal) disposition, so a wedged shutdown is still killable
        assert signal.getsignal(signal.SIGTERM) == signal.SIG_DFL
    # leaving the context restores whatever pytest had installed
    assert signal.getsignal(signal.SIGTERM) == before


# ------------------------------------------------------------- invariants

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
  <host id="peer" quantity="6">
    <process plugin="phold" starttime="1" arguments="basename=peer quantity=6 load=4" />
  </host>
</shadow>"""


@pytest.fixture(scope="module")
def mid_state():
    from shadow_tpu.config import parse_config
    from shadow_tpu.core.timebase import SECOND
    from shadow_tpu.sim import build_simulation

    sim = build_simulation(parse_config(CONFIG), seed=7)
    return sim.run(2 * SECOND)


def test_invariants_pass_on_real_state(mid_state):
    from shadow_tpu.runtime.invariants import check_state, validate

    assert check_state(mid_state) == []
    now = validate(mid_state)
    assert now >= 2_000_000_000
    # and the clock threads through as the next prev_now
    assert validate(mid_state, prev_now=now) == now


def test_invariants_catch_clock_regression(mid_state):
    import dataclasses

    import jax.numpy as jnp

    from shadow_tpu.runtime.invariants import InvariantViolation, validate

    bad = dataclasses.replace(
        mid_state, now=jnp.asarray(-5, mid_state.now.dtype)
    )
    with pytest.raises(InvariantViolation, match="negative clock"):
        validate(bad)
    with pytest.raises(InvariantViolation, match="backwards"):
        validate(mid_state, prev_now=int(1e18))


def test_invariants_catch_unsorted_queue(mid_state):
    import dataclasses

    import jax
    import jax.numpy as jnp

    from shadow_tpu.runtime.invariants import InvariantViolation, validate

    t = jax.device_get(mid_state.queues.time).copy()
    # find a host with >= 2 live events and swap-break its time order
    from shadow_tpu.core.timebase import TIME_INVALID

    live = (t != TIME_INVALID).sum(axis=1)
    h = int(live.argmax())
    assert live[h] >= 2, "phold run should leave queued events"
    t[h, 0], t[h, 1] = t[h, 1] + 1, t[h, 0]
    bad = dataclasses.replace(
        mid_state,
        queues=dataclasses.replace(
            mid_state.queues, time=jnp.asarray(t)
        ),
    )
    with pytest.raises(InvariantViolation, match="order"):
        validate(bad)


def test_invariants_catch_empty_slot_ahead(mid_state):
    import dataclasses

    import jax
    import jax.numpy as jnp

    from shadow_tpu.core.timebase import TIME_INVALID
    from shadow_tpu.runtime.invariants import InvariantViolation, validate

    t = jax.device_get(mid_state.queues.time).copy()
    live = (t != TIME_INVALID).sum(axis=1)
    h = int(live.argmax())
    t[h, 0] = TIME_INVALID  # hole ahead of live rows
    bad = dataclasses.replace(
        mid_state,
        queues=dataclasses.replace(
            mid_state.queues, time=jnp.asarray(t)
        ),
    )
    with pytest.raises(InvariantViolation, match="empties-last"):
        validate(bad)


def test_invariants_catch_negative_counter(mid_state):
    import dataclasses

    import jax.numpy as jnp

    from shadow_tpu.runtime.invariants import InvariantViolation, validate

    bad = dataclasses.replace(
        mid_state,
        src_seq=jnp.full_like(mid_state.src_seq, -3),
    )
    with pytest.raises(InvariantViolation, match="negative counter"):
        validate(bad)


def test_invariants_catch_nan(mid_state):
    import jax
    import jax.numpy as jnp

    from shadow_tpu.runtime.invariants import InvariantViolation, validate

    leaves, treedef = jax.tree_util.tree_flatten(mid_state)
    idx = next(
        (i for i, l in enumerate(leaves)
         if jnp.issubdtype(l.dtype, jnp.floating)),
        None,
    )
    if idx is None:
        pytest.skip("EngineState has no float leaves")
    leaves = list(leaves)
    leaves[idx] = jnp.full_like(leaves[idx], jnp.nan)
    bad = jax.tree_util.tree_unflatten(treedef, leaves)
    with pytest.raises(InvariantViolation, match="non-finite"):
        validate(bad)


@pytest.mark.slow  # ~10s CLI subprocess; the invariant-guard unit pins above
# cover the checks themselves in-process
def test_cli_validate_flag_passes_clean_run(tmp_path):
    # end-to-end: --validate on a healthy run must not trip (exercises
    # the every-K-windows cadence inside the real driver loop)
    from shadow_tpu.cli import main

    rc = main(["--test", "--stoptime", "2", "--validate", "3",
               "--heartbeat-frequency", "1",
               "--checkpoint-path", str(tmp_path / "ck.npz")])
    assert rc == 0


# ------------------------------------------- subprocess acceptance (slow)


def _cli_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the suite's compile cache rides in via JAX_COMPILATION_CACHE_DIR,
    # which tests/conftest.py sets unless the caller already has
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    return env


def _wait_for(predicate, timeout_s, what):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.25)
    raise AssertionError(f"timed out after {timeout_s}s waiting for {what}")


@pytest.mark.slow
def test_sigterm_midrun_checkpoints_and_resumes_bit_exact(tmp_path):
    """Issue acceptance: SIGTERM mid-run -> CRC-verified checkpoint;
    resuming it and running to T is bit-identical to an uninterrupted
    run to T."""
    import jax
    import jax.numpy as jnp

    from shadow_tpu.utils import load_checkpoint, verify_checkpoint

    cfg_path = tmp_path / "phold.config.xml"
    cfg_path.write_text(CONFIG)
    ck = str(tmp_path / "ck.npz")
    base = [sys.executable, "-m", "shadow_tpu", str(cfg_path),
            "--seed", "7", "--checkpoint-path", ck]

    # long stoptime + short batches: the run will never finish on its
    # own; we interrupt as soon as the first interval checkpoint lands
    p = subprocess.Popen(
        base + ["--stoptime", "3600", "--heartbeat-frequency", "0.5",
                "--checkpoint-interval", "1", "--checkpoint-keep", "3"],
        cwd=REPO, env=_cli_env(),
        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True,
    )
    try:
        _wait_for(lambda: os.path.exists(ck), 240,
                  "first interval checkpoint")
        time.sleep(1.0)
        p.send_signal(signal.SIGTERM)
        rc = p.wait(timeout=120)
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
    stderr = p.stderr.read()
    assert rc == 143, f"expected 128+SIGTERM, got {rc}\n{stderr}"
    assert "will checkpoint and exit" in stderr

    meta = verify_checkpoint(ck)  # every leaf CRC must hold
    assert meta["interrupted"] == int(signal.SIGTERM)
    t0 = float(meta["sim_seconds"])
    assert t0 > 0
    stop = int(t0) + 2

    # resume to `stop`; the interval cadence is absolute, so the final
    # checkpoint lands exactly at sim time `stop`
    r = subprocess.run(
        base + ["--stoptime", str(stop), "--resume", "auto",
                "--checkpoint-interval", "1", "--checkpoint-keep", "3"],
        cwd=REPO, env=_cli_env(), capture_output=True, text=True,
        timeout=600,
    )
    assert r.returncode == 0, r.stderr
    assert f"resumed from {ck}" in r.stderr
    meta2 = verify_checkpoint(ck)
    assert float(meta2["sim_seconds"]) == float(stop)

    # uninterrupted reference run, in-process (shares the compile cache)
    from shadow_tpu.config import parse_config
    from shadow_tpu.core.timebase import SECOND
    from shadow_tpu.sim import build_simulation

    sim = build_simulation(parse_config(str(cfg_path)), seed=7)
    straight = sim.run(stop * SECOND)
    resumed, _ = load_checkpoint(ck, sim.state0)

    flat_a = jax.tree_util.tree_leaves(straight)
    flat_b = jax.tree_util.tree_leaves(resumed)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        assert jnp.array_equal(a, b), (
            "interrupt+resume diverged from the uninterrupted run"
        )


SPIN_PLUGIN = textwrap.dedent("""\
    /* pathological plugin: never yields, never returns — the hang class
       the watchdog exists for (a cooperative green thread that spins
       blocks shim_pump, and with it the whole driver, forever). */
    #include "shim_api.h"

    int shim_main(const ShimAPI* api, int argc, char** argv) {
        (void)api; (void)argc; (void)argv;
        for (;;) { }
        return 0;
    }
""")


@pytest.mark.slow
@pytest.mark.skipif(shutil.which("gcc") is None, reason="no C toolchain")
def test_watchdog_detects_hung_plugin(tmp_path):
    """Issue acceptance: a plugin spinning in shim_main stalls the proc
    tier; the watchdog must abort with the stall exit code and leave a
    diagnostic bundle within the deadline."""
    from shadow_tpu.proc.native import compile_plugin

    src = tmp_path / "shim_spin.c"
    src.write_text(SPIN_PLUGIN)
    so = compile_plugin(str(src), name="_t_spin")

    topo = """<graphml xmlns="http://graphml.graphdrawing.org/xmlns">
      <key attr.name="latency" attr.type="double" for="edge" id="d3" />
      <key attr.name="bandwidthup" attr.type="int" for="node" id="d2" />
      <key attr.name="bandwidthdown" attr.type="int" for="node" id="d1" />
      <graph edgedefault="undirected">
        <node id="poi-1">
          <data key="d1">2048</data><data key="d2">2048</data>
        </node>
        <edge source="poi-1" target="poi-1">
          <data key="d3">25.0</data>
        </edge>
      </graph>
    </graphml>"""
    cfg_path = tmp_path / "spin.config.xml"
    cfg_path.write_text(textwrap.dedent(f"""\
        <shadow stoptime="30">
          <topology><![CDATA[{topo}]]></topology>
          <plugin id="spin" path="{so}"/>
          <host id="h0">
            <process plugin="spin" starttime="1" arguments=""/>
          </host>
        </shadow>"""))

    diag = tmp_path / "diag"
    # deadline must absorb one cold XLA compile of the proc-tier engine;
    # with the shared persistent cache this is normally seconds
    p = subprocess.run(
        [sys.executable, "-m", "shadow_tpu", str(cfg_path),
         "--watchdog", "60", "--diag-dir", str(diag)],
        cwd=REPO, env=_cli_env(), capture_output=True, text=True,
        timeout=540,
    )
    assert p.returncode == 75, (
        f"expected stall exit code 75, got {p.returncode}\n"
        f"stdout: {p.stdout}\nstderr: {p.stderr}"
    )
    bundles = list(diag.glob("*.stall.*.json"))
    stacks = list(diag.glob("*.stall.*.stacks.txt"))
    assert bundles and stacks, f"missing diagnostics in {diag}"
    bundle = json.loads(bundles[0].read_text())
    assert bundle["exit_code"] == 75
    assert "STALL" in p.stderr
