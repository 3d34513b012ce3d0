"""Static-analysis layer (shadow_tpu/analysis/): lint rules, baseline
workflow, and the HLO contract auditor.

Each lint rule gets a fixture snippet that must trip it and a nearby
idiom that must NOT (the exemptions are as load-bearing as the rules:
bool-compare counts, counter-based stream RNG, ctypes protocol
attributes). The auditor is exercised against the real phold engine —
clean by contract — and against an injected forbidden-op variant it
must reject. The five-config audit runs in the slow lane;
docs/10-Static-Analysis.md is the catalog.
"""

import dataclasses
import json
import textwrap

import jax
import jax.numpy as jnp
import pytest

from shadow_tpu.analysis import hlo_audit as H
from shadow_tpu.analysis import lint as L
from shadow_tpu.core.timebase import MILLISECOND
from shadow_tpu.models import phold


def _lint(src: str, path: str = "<fixture>"):
    return L.lint_source(textwrap.dedent(src), path)


def _rules(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------------------- lint rules


def test_sl101_host_materialization_in_jit():
    fs = _lint("""
        import jax, numpy as np
        @jax.jit
        def f(x):
            y = float(x)
            z = np.sin(x)
            w = x.item()
            return y + z + w
    """)
    assert _rules(fs) == ["SL101"] and len(fs) == 3


def test_sl101_silent_outside_jit():
    fs = _lint("""
        import numpy as np
        def host_side(arr):
            return float(np.sin(arr).sum())
    """)
    assert fs == []


def test_sl102_tracer_branch_in_jit():
    fs = _lint("""
        import jax
        @jax.jit
        def f(x):
            if x > 0:
                return x
            while x < 3:
                x = x + 1
            return x
    """)
    assert _rules(fs) == ["SL102"] and len(fs) == 2


def test_sl102_static_tests_whitelisted():
    # isinstance / `is None` / self.cfg-rooted flags are static dispatch
    fs = _lint("""
        import jax
        @jax.jit
        def f(self, x, flag=None):
            if x is None:
                return 0
            if isinstance(x, tuple):
                return 1
            if self.cfg.trace:
                return 2
            return x
    """)
    assert fs == []


def test_sl102_marks_while_loop_bodies():
    # jit scope via lax.while_loop reference, not a decorator
    fs = _lint("""
        import jax
        from jax import lax
        def outer(st0):
            def body(st):
                if st > 0:
                    st = st - 1
                return st
            def cond(st):
                return st > 0
            return lax.while_loop(cond, body, st0)
    """)
    assert "SL102" in _rules(fs)


def test_sl103_i32_time_cast():
    fs = _lint("""
        import jax.numpy as jnp
        def g(due_time):
            a = due_time.astype(jnp.int32)
            b = jnp.int32(due_time)
            delay_ns = jnp.zeros(4, dtype=jnp.int32)
            return a, b, delay_ns
    """)
    assert _rules(fs) == ["SL103"] and len(fs) == 3


def test_sl103_bool_compare_counts_exempt():
    # `sum(t != INVALID, dtype=int32)` counts booleans derived from
    # time — count arithmetic, not time truncation (engine idiom)
    fs = _lint("""
        import jax.numpy as jnp
        def g(stage_time, TIME_INVALID):
            n = jnp.sum(stage_time != TIME_INVALID, axis=1,
                        dtype=jnp.int32)
            return n
    """)
    assert fs == []


def test_sl104_prng_key_reuse():
    fs = _lint("""
        from shadow_tpu.core import rng as srng
        def h(key):
            a = srng.uniform(key)
            b = srng.randint(key, 0, 4)
            return a, b
    """)
    assert _rules(fs) == ["SL104"]


def test_sl104_split_and_streams_exempt():
    fs = _lint("""
        from shadow_tpu.core import rng as srng
        def h(key, seed):
            k1, k2 = srng.split(key, 2)
            a = srng.uniform(k1)
            b = srng.randint(k2, 0, 4)
            u = srng.fault_stream_uniform(seed, 1, 8)
            v = srng.fault_stream_uniform(seed, 2, 8)
            return a, b, u, v
    """)
    assert fs == []


def test_sl104_reassignment_resets():
    fs = _lint("""
        from shadow_tpu.core import rng as srng
        def h(key):
            a = srng.uniform(key)
            key = srng.fold_in(key, 1)
            b = srng.uniform(key)
            return a, b
    """)
    assert fs == []


def test_sl105_mutable_defaults():
    fs = _lint("""
        def f(x, acc=[]):
            acc.append(x)
            return acc
        class C:
            registry = {}
    """)
    assert _rules(fs) == ["SL105"] and len(fs) == 2


def test_sl105_ctypes_fields_exempt():
    fs = _lint("""
        import ctypes
        class Req(ctypes.Structure):
            _fields_ = [("pid", ctypes.c_int32)]
    """)
    assert fs == []


def test_sl106_set_iteration():
    fs = _lint("""
        import jax
        @jax.jit
        def f(x):
            out = [x[i] for i in {2, 1, 0}]
            for k in set((1, 2)):
                out.append(k)
            return out
    """)
    assert _rules(fs) == ["SL106"] and len(fs) == 2


def test_sl107_undonated_entry_points():
    # all three resolution paths: named entry point, in-file def with a
    # state parameter, and a state-carrying lambda
    fs = _lint("""
        import jax
        def step_window(state, stop, base, window):
            return state
        def drive(st, stop):
            return st
        j1 = jax.jit(step_window)
        j2 = jax.jit(drive)
        j3 = jax.jit(lambda st, stop: st)
    """)
    assert _rules(fs) == ["SL107"] and len(fs) == 3


def test_sl107_donated_clean():
    fs = _lint("""
        import jax
        def run(state, stop):
            return state
        j1 = jax.jit(run, donate_argnums=0)
        j2 = jax.jit(lambda st, stop: st, donate_argnames="st")
        j3 = jax.jit(lambda x, y: x + y)  # no state carry at all
    """)
    assert fs == []


def test_sl107_no_donate_exemption_needs_reason():
    # the reasoned marker suppresses; a bare `no-donate=` does not
    fs = _lint("""
        import jax
        def run(state, stop):
            return state
        j = jax.jit(run)  # shadowlint: no-donate=pmap fallback stacks outputs
    """)
    assert fs == []
    fs = _lint("""
        import jax
        def run(state, stop):
            return state
        j = jax.jit(run)  # shadowlint: no-donate=
    """)
    assert _rules(fs) == ["SL107"]


def test_sl108_collective_in_named_cond_fun():
    # named cond function resolved by the pass-1 predicate marking —
    # the exact shape of the PR-1 miscompile
    fs = _lint("""
        import jax
        from jax import lax
        def drain(q, stop, ax):
            def cond(carry):
                return lax.psum(carry[1], ax) > 0
            def body(carry):
                return carry
            return lax.while_loop(cond, body, (q, stop))
    """)
    assert _rules(fs) == ["SL108"]


def test_sl108_collective_in_lambda_cond_and_cond_pred():
    fs = _lint("""
        import jax
        from jax import lax
        def f(x, ax):
            y = lax.while_loop(
                lambda c: lax.pmin(c, ax) < 9, lambda c: c + 1, x)
            return jax.lax.cond(
                lax.psum(y, ax) > 0, lambda v: v, lambda v: -v, y)
    """)
    # one finding per collective: the pmin in the while's lambda cond
    # AND the psum in lax.cond's predicate expression
    assert [f.rule for f in fs] == ["SL108", "SL108"]


def test_sl108_wrapper_and_method_cond():
    # the engine's reduction wrappers count, and attribute conds
    # (self._more) resolve through pass-1 marking too
    fs = _lint("""
        import jax
        class Eng:
            def _more(self, carry):
                return self._gany(carry[0])
            def loop(self, st):
                return jax.lax.while_loop(
                    self._more, lambda c: c, (st, 0))
    """)
    assert _rules(fs) == ["SL108"]


def test_sl108_carried_flag_clean():
    # the restructured engine shape: flag computed in the BODY,
    # predicate only reads the carry — no finding
    fs = _lint("""
        import jax
        from jax import lax
        def drain(q, stop, ax):
            def cond(carry):
                return carry[0]
            def body(carry):
                flag, q = carry
                return lax.psum(flag, ax) > 0, q
            return lax.while_loop(
                cond, body, (lax.psum(q, ax) > 0, q))
    """)
    assert fs == []


def test_sl109_blocking_sync_outside_jit():
    fs = _lint("""
        import jax
        def poll(st):
            now = int(jax.device_get(st.now))
            st.queues.drops.block_until_ready()
            return now
    """)
    assert _rules(fs) == ["SL109"] and len(fs) == 2


def test_sl109_in_jit_is_sl101_not_sl109():
    # mutually exclusive by construction: inside jit scope the same
    # calls are SL101's host-materialization finding
    fs = _lint("""
        import jax
        @jax.jit
        def f(x):
            return jax.device_get(x)
    """)
    assert _rules(fs) == ["SL101"]


def test_sl109_watchdog_scoped_sites_allowed():
    src = """
        import jax
        class HeartbeatHarvest:
            def fetch(self, bundle):
                return jax.device_get(bundle)
    """
    assert _lint(src) == []
    # the watchdog layer itself is allowed by path
    plain = """
        import jax
        def reap(st):
            return jax.device_get(st.now)
    """
    assert _lint(plain, "shadow_tpu/runtime/supervisor.py") == []
    assert _rules(_lint(plain, "shadow_tpu/runtime/other.py")) == ["SL109"]


def test_sl109_no_deadline_exemption_needs_reason():
    # the reasoned marker suppresses; a bare `no-deadline=` does not
    ok = _lint("""
        import jax
        def probe(st):
            return jax.device_get(st.now)  # shadowlint: no-deadline=build-time fetch
    """)
    assert ok == []
    bare = _lint("""
        import jax
        def probe(st):
            return jax.device_get(st.now)  # shadowlint: no-deadline=
    """)
    assert _rules(bare) == ["SL109"]


def test_sl110_wallclock_in_jit():
    fs = _lint("""
        import time
        import jax
        @jax.jit
        def f(x):
            t0 = time.time()
            t1 = time.perf_counter()
            t2 = time.monotonic_ns()
            return x + t0 + t1 + t2
    """)
    assert _rules(fs) == ["SL110"] and len(fs) == 3


def test_sl110_silent_outside_jit():
    # host-side wall clock is the supervisor/pressure idiom — never a
    # finding outside jit scope (SL110 is about values freezing into
    # compile-time constants, which only tracing can do)
    fs = _lint("""
        import time
        def heartbeat():
            return time.time(), time.monotonic()
    """)
    assert fs == []


def test_sl110_from_import_and_bare_time():
    # `from time import perf_counter` still trips inside jit; a bare
    # `time(...)` call does NOT (too ambiguous — datetime.time, a local
    # helper named time), only the module-attribute form is matched
    fs = _lint("""
        from time import perf_counter
        import jax
        @jax.jit
        def f(x):
            return x + perf_counter()
    """)
    assert _rules(fs) == ["SL110"]
    fs = _lint("""
        import jax
        def time():
            return 0
        @jax.jit
        def f(x):
            return x + time()
    """)
    assert fs == []


def test_sl110_inline_suppression():
    fs = _lint("""
        import time
        import jax
        @jax.jit
        def f(x):
            t = time.time()  # shadowlint: disable=SL110
            return x + t
    """)
    assert fs == []


def test_sl111_double_donate_same_array():
    fs = _lint("""
        import jax
        def f(step, a):
            step2 = jax.jit(step, donate_argnums=(0, 1))
            return step2(a, a)
    """)
    assert _rules(fs) == ["SL111"] and len(fs) == 1
    assert "donated parameters 0 and 1" in fs[0].message


def test_sl111_reuse_after_donation():
    # reading a reference after it was passed to a donated position —
    # the buffer is deleted by the call; both the named-jit and the
    # direct jax.jit(...)(...) forms are tracked
    fs = _lint("""
        import jax
        def g(step, st, stop):
            jstep = jax.jit(step, donate_argnums=0)
            out = jstep(st, stop)
            return out, st.now
    """)
    assert _rules(fs) == ["SL111"] and len(fs) == 1
    assert "`st` was donated" in fs[0].message
    fs = _lint("""
        import jax
        def k(step, st, stop):
            out = jax.jit(step, donate_argnums=0)(st, stop)
            return st + out
    """)
    assert _rules(fs) == ["SL111"]


def test_sl111_rebind_is_clean():
    # the engine convention — st = step(st, stop) — rebinds the name
    # to the jit's output, so later reads are fresh buffers; the
    # run_with_spill window loop is exactly this shape
    fs = _lint("""
        import jax
        def h(step, st, stop):
            jstep = jax.jit(step, donate_argnums=0)
            while int(st.now) < int(stop):
                st = jstep(st, stop)
            return st.now
    """)
    assert fs == []


def test_sl111_undonated_calls_untracked():
    # a jit without donate_argnums consumes nothing (SL107 owns the
    # should-it-donate question for entry points)
    fs = _lint("""
        import jax
        def f(fn, x):
            j = jax.jit(fn)
            y = j(x)
            return x + y
    """)
    assert fs == []


def test_sl112_computed_gather_in_handler_scope():
    # indexing a global table by another host's id gathers the whole
    # [NC] column per host under vmap — both the `_on_*` method
    # convention and make_handlers closures are handler scope
    fs = _lint("""
        class Model:
            def _on_recv(self, hs, slot, pkt, now, key):
                g = self._g
                reply_sz = g["recvsize"][pkt.src_host]
                return reply_sz

            def _make_handlers(self, stack, kind_base):
                g = self._g
                def h_dial(hs, ev, key):
                    return g["dials"][ev.src]
                return (h_dial,)
    """)
    assert _rules(fs) == ["SL112"] and len(fs) == 2


def test_sl112_own_gid_rows_clean():
    # the own-row convention — first index element is the handler's
    # gid (or a static construction) — is an aligned select, not a
    # gather; trailing in-row indices may be computed
    fs = _lint("""
        import jax.numpy as jnp
        class Model:
            def _on_recv(self, hs, slot, pkt, now, key):
                g, me = self._g, hs.gid
                a = g["count"][me]
                b = g["peers"][me, slot % 4]
                c = g["n_blocks"]
                d = g["pause_ns"][0]
                e = g["sendsize"][jnp.arange(4)]
                return a + b + c + d + e
    """)
    assert fs == []


def test_sl112_silent_outside_handler_scope():
    # build-time host code reshuffles global tables freely
    fs = _lint("""
        def build(self, b):
            g = self._g
            order = g["recvsize"][g["peer_gid"]]
            return order
    """)
    assert fs == []


def test_sl112_inline_suppression():
    fs = _lint("""
        class Model:
            def _on_recv(self, hs, slot, pkt, now, key):
                g = self._g
                return g["recvsize"][pkt.src_host]  # shadowlint: disable=SL112
    """)
    assert fs == []


def test_sl113_blocking_socket_in_dispatch_scopes():
    # each window-loop drive scope name trips: the socket call parks
    # the thread in the kernel while the device loop waits behind it
    fs = _lint("""
        def dispatch(stop_ns, state, sock):
            return sock.recv(1024)
        def run(st, stop, httpd):
            httpd.serve_forever()
            return st
        def step_window(st, stop, conn):
            return conn.getresponse()
    """)
    assert _rules(fs) == ["SL113"] and len(fs) == 3


def test_sl113_blocking_socket_in_jit_scope():
    fs = _lint("""
        import jax
        @jax.jit
        def f(x, sock):
            data, addr = sock.accept()
            return x
    """)
    assert _rules(fs) == ["SL113"]


def test_sl113_silent_on_handler_threads():
    # the sanctioned discipline: blocking socket work on HTTP handler
    # threads / plain helper scopes never flags — and a serve_forever
    # passed as a Thread TARGET (attribute reference, no call) is not a
    # blocking call site at all
    fs = _lint("""
        import threading
        def do_GET(self):
            body = self.rfile.recv(4096)
            return body
        def start(httpd):
            t = threading.Thread(target=httpd.serve_forever, daemon=True)
            t.start()
    """)
    assert fs == []


def test_sl113_inline_suppression():
    fs = _lint("""
        def dispatch(stop, state, sock):
            return sock.recv(64)  # shadowlint: disable=SL113
    """)
    assert fs == []


def test_sl114_worker_write_without_lock():
    # a Thread-target method of a lock-owning class writing bare self
    # state races the submitting thread (the supervisor.py
    # compile_graces bug this rule was built from)
    fs = _lint("""
        import threading
        class Svc:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0
                self.jobs = []
                threading.Thread(target=self._worker_loop).start()
            def _worker_loop(self):
                self.count += 1
                self.jobs.append("x")
    """)
    assert _rules(fs) == ["SL114"]
    assert len(fs) == 2  # the augassign and the container mutation


def test_sl114_lock_scope_and_locked_suffix_exempt():
    # the serving discipline: writes under `with self._lock:` / inside
    # a `with self._cond:` wait loop are clean, and `*_locked` methods
    # document that their caller already holds it
    fs = _lint("""
        import threading
        class Svc:
            def __init__(self):
                self._cond = threading.Condition()
                self.count = 0
                threading.Thread(target=self._worker_loop).start()
            def _worker_loop(self):
                with self._cond:
                    self.count += 1
                    self._drain_locked()
            def _drain_locked(self):
                self.count = 0
    """)
    assert fs == []


def test_sl114_handler_shared_chain():
    # a per-request do_* handler mutating the object every request
    # thread shares (the service/server behind the handler) must hold
    # its lock; bare handler attributes are per-request state and the
    # local dict mutation never flags
    fs = _lint("""
        class Handler:
            def do_POST(self):
                self.close_connection = True
                doc = {}
                doc.update(status="ok")
                self.service.total += 1
                self.service.log.append("x")
            def do_GET(self):
                with self.service._lock:
                    self.service.total += 1
    """)
    assert _rules(fs) == ["SL114"]
    assert len(fs) == 2
    assert all(f.func == "Handler.do_POST" for f in fs)


def test_sl114_silent_outside_thread_entry_and_suppression():
    # plain methods (not do_*, never a Thread target) are unchecked
    # even in lock-owning classes — single-threaded mutation is the
    # default — and the inline marker works where a handler write is
    # deliberate (e.g. the object does its own internal locking)
    fs = _lint("""
        import threading
        class Svc:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0
            def bump(self):
                self.count += 1
        class Handler:
            def do_GET(self):
                self.tracer.spans.append("x")  # shadowlint: disable=SL114
    """)
    assert fs == []


def test_inline_suppression():
    fs = _lint("""
        from shadow_tpu.core import rng as srng
        def h(key):
            a = srng.uniform(key)
            b = srng.randint(key, 0, 4)  # shadowlint: disable=SL104
            return a, b
    """)
    assert fs == []


def test_suppression_is_rule_scoped():
    # disabling SL101 does not silence an SL104 on the same line
    fs = _lint("""
        from shadow_tpu.core import rng as srng
        def h(key):
            a = srng.uniform(key)
            b = srng.randint(key, 0, 4)  # shadowlint: disable=SL101
            return a, b
    """)
    assert _rules(fs) == ["SL104"]


# ------------------------------------------------------ baseline workflow


def test_baseline_roundtrip(tmp_path):
    src = textwrap.dedent("""
        from shadow_tpu.core import rng as srng
        def h(key):
            a = srng.uniform(key)
            b = srng.randint(key, 0, 4)
            return a, b
    """)
    findings = L.lint_source(src, "fixture.py")
    assert findings

    path = str(tmp_path / "baseline.json")
    L.save_baseline(findings, path)
    baseline = L.load_baseline(path)

    # accepted findings don't block...
    new, old, stale = L.split_new(findings, baseline)
    assert new == [] and len(old) == len(findings) and stale == []

    # ...a new finding does...
    worse = src + "    c = srng.uniform(key)\n"
    new2, _, _ = L.split_new(L.lint_source(worse, "fixture.py"), baseline)
    assert len(new2) >= 1

    # ...and keys survive pure line drift (comment above the finding)
    drifted = src.replace("def h(key):", "# a comment\ndef h(key):")
    new3, old3, _ = L.split_new(L.lint_source(drifted, "fixture.py"),
                                baseline)
    assert new3 == [] and len(old3) == len(findings)

    # fixed findings surface as stale keys, not errors
    _, _, stale4 = L.split_new([], baseline)
    assert len(stale4) == len(baseline)


def test_repo_is_lint_clean():
    """The acceptance gate: zero findings outside the checked-in
    baseline across the whole package."""
    new, _, _ = L.split_new(L.lint_package(), L.load_baseline())
    assert new == [], "\n".join(str(f) for f in new)


# ------------------------------------------------------------- hlo audit


def test_audit_text_budgets_and_callbacks():
    contract = H.HloContract("t", {"scatter": 1, "custom_call": 0})
    clean = 'stablehlo.sort ...\nstablehlo.scatter ...\n'
    assert H.audit_text(clean, contract) == []
    over = clean + 'stablehlo.scatter ...\n'
    assert any("scatter" in v for v in H.audit_text(over, contract))
    cb = clean + 'stablehlo.outfeed ...\n'
    assert any("outfeed" in v for v in H.audit_text(cb, contract))


def test_audit_text_custom_call_allowlist():
    contract = H.HloContract("t", {"scatter": 0, "custom_call": 2},
                             custom_call_allow=("Sharding",))
    ok = 'stablehlo.custom_call @x {call_target_name = "Sharding"}\n'
    assert H.audit_text(ok, contract) == []
    bad = 'stablehlo.custom_call @x {call_target_name = "MyOp"}\n'
    assert any("MyOp" in v for v in H.audit_text(bad, contract))
    pycb = ('stablehlo.custom_call @x '
            '{call_target_name = "xla_python_cpu_callback"}\n')
    assert any("host-callback" in v for v in H.audit_text(pycb, contract))


@pytest.fixture(scope="module")
def phold_build():
    eng, init = phold.build(8, seed=3, capacity=32, msgs_per_host=2)
    return eng, init()


def test_phold_engine_meets_contract(phold_build):
    eng, st = phold_build
    text = H.lower_text(eng.run, st, jnp.int64(400 * MILLISECOND))
    assert H.audit_text(text, H.CONTRACTS["phold"]) == []
    assert H.ops_histogram(text)["scatter"] == 0


def test_injected_scatter_is_rejected(phold_build):
    """An engine variant smuggling a scatter into the run must fail the
    phold contract — the auditor sees through the real lowering, not a
    string fixture."""
    eng, st = phold_build

    def bad_run(st, stop):
        out = eng.run(st, stop)
        idx = jnp.array([1, 3])
        return dataclasses.replace(
            out, cpu_free=out.cpu_free.at[idx].add(1))

    text = H.lower_text(bad_run, st, jnp.int64(400 * MILLISECOND))
    violations = H.audit_text(text, H.CONTRACTS["phold"])
    assert violations and all("scatter" in v for v in violations)


def test_assert_zero_cost_catches_residue():
    """The shared helper must fail when the 'off' build is not actually
    identical — checked on toy pytrees so the failure mode is cheap."""
    def mk(extra):
        st = {"a": jnp.zeros(4, jnp.int64)}
        if extra:
            st["b"] = jnp.zeros(2, jnp.int64)
        return (lambda s, stop: jax.tree.map(lambda x: x + stop, s)), st

    base_f, base_st = mk(False)
    on_f, on_st = mk(True)
    # healthy triple passes and returns the three texts
    texts = H.assert_zero_cost((base_f, base_st), (base_f, dict(base_st)),
                               (on_f, on_st), jnp.int64(1),
                               get_subtree=lambda s: s.get("b"))
    assert texts["base"] == texts["off"] != texts["on"]
    # off build with residue fails
    with pytest.raises(AssertionError):
        H.assert_zero_cost((base_f, base_st), (on_f, on_st),
                           (on_f, on_st), jnp.int64(1))


def test_recompile_guard(phold_build):
    eng, st = phold_build
    stop = 100 * MILLISECOND
    H.assert_no_recompile(eng.run,
                          [(st, jnp.int64(stop)), (st, jnp.int64(2 * stop))])
    with pytest.raises(AssertionError):
        # dtype flip across calls = a second program
        H.assert_no_recompile(lambda x: x * 2,
                              [(jnp.int64(3),), (jnp.float32(3.0),)])


@pytest.mark.slow
def test_all_model_configs_meet_contracts():
    """The full five-config audit: every declared contract holds on
    today's lowerings."""
    results = H.audit_all()
    assert sorted(results) == sorted(H.CONTRACTS)
    bad = {k: v["violations"] for k, v in results.items() if not v["ok"]}
    assert not bad, json.dumps(bad, indent=1)


# ------------------------------------------------------------------- CLI


def test_cli_exits_nonzero_on_findings(tmp_path):
    from shadow_tpu.tools.lint import main

    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        from shadow_tpu.core import rng as srng
        def h(key):
            a = srng.uniform(key)
            b = srng.randint(key, 0, 4)
            return a, b
    """))
    out = tmp_path / "report.json"
    rc = main([str(bad), "--no-baseline", "--output", str(out)])
    assert rc == 1
    report = json.loads(out.read_text())
    assert report["new"] == 1
    assert report["findings"][0]["rule"] == "SL104"


def test_cli_exits_zero_on_repo(tmp_path):
    from shadow_tpu.tools.lint import main

    out = tmp_path / "report.json"
    rc = main(["--output", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["new"] == 0
