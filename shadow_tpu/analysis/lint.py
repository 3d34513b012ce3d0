"""shadowlint — AST linter for JAX footguns in the shadow_tpu package.

The simulator's correctness story leans on a small set of disciplines
(ROADMAP.md invariants; docs/10-Static-Analysis.md rule catalog):
everything in the window loop traces once and lowers to one XLA
program, simulated time is always the `core.timebase` dtype (i64 ns),
and pytrees have a deterministic leaf order. Each lint rule guards one
way those disciplines have been (or nearly were) broken:

- SL101 host materialization in jit scope — ``float()``/``int()``/
  ``bool()`` on traced values, ``.item()``, ``np.*`` compute,
  ``jax.device_get``: silently forces a device sync per call, or a
  tracer error at the worst possible time.
- SL102 Python branch on a traced value in jit scope — ``if``/``while``
  on a tracer raises ConcretizationTypeError only for the config that
  first reaches the branch.
- SL103 i32 arithmetic/casts on simulated-time expressions — i32
  nanoseconds wrap after ~2.1 s of simulated time; the PR 4 ``drops``
  widening was exactly this bug one field over.
- SL104 PRNG key reuse without ``split`` — two draws from one key are
  perfectly correlated; invisible in smoke tests, fatal to statistics.
- SL105 mutable default (function defaults and class-body defaults) —
  shared-instance aliasing, and a stale-pytree hazard for dataclass
  state.
- SL106 iteration over a ``set`` when building pytrees/collections —
  set order is hash order; pytree leaf order must be deterministic
  across processes (checkpoint layout, multi-host bit-identity).
- SL107 window-loop entry point jitted without buffer donation — a
  ``jax.jit`` over a state-threading callable (``run``/``step_window``,
  or any function whose first parameters include a ``state``/``st``
  carrier) with no ``donate_argnums``: every window then COPIES the
  [H, C] queue arrays and rings instead of aliasing them through. The
  drain hot path's donation (Simulation._wrap) exists precisely to
  kill those copies; new entry points must donate or declare why they
  can't with ``# shadowlint: no-donate=<reason>`` (the bare
  ``disable=SL107`` works too, but the reasoned marker is the
  documented mechanism — it forces the "why" into the source).
- SL109 bare blocking device sync outside watchdog-scoped sites —
  ``jax.device_get``/``.block_until_ready()`` OUTSIDE jit scope (SL101
  owns the inside-jit case) blocks the driver until the device answers,
  with no deadline: a lost mesh peer turns the call into an infinite
  hang the stall watchdog can only attribute to "no progress". The
  sanctioned blocking sites are ``runtime.harvest.HeartbeatHarvest``
  (petted by the CLI's collective watchdog) and ``runtime/supervisor.py``
  (the watchdog layer itself); every other site must carry
  ``# shadowlint: no-deadline=<reason>`` — the reason is mandatory, so
  each undeadlined sync documents why a hang there is acceptable
  (docs/13-Elastic-Recovery.md).
- SL110 wall-clock read inside jit scope — ``time.time()``/
  ``time.perf_counter()``/``time.monotonic()`` (and their ``_ns``
  variants) return Python floats/ints, so inside a traced function the
  "timestamp" freezes into a compile-time constant: every later call
  of the compiled program sees the clock of its first trace. Wall
  timing belongs on host around the jit (``obs.WindowProfiler``); a
  timestamp a kernel needs must be threaded in as an argument.
- SL111 donation misuse at the call site — the two ways
  ``donate_argnums`` silently goes wrong in *caller* code: passing the
  same array object to two donated parameters of one jit call (XLA
  aliases two outputs onto one buffer — results corrupt), and reading
  a Python reference again after it was passed to a donated position
  (the donated buffer is deleted by the call; jax either errors or
  silently re-copies, losing the donation). The fix is the engine's
  own convention: immediately rebind the carry
  (``state = step(state, stop)``) — rebinding clears the tracking.
- SL108 collective call inside a ``while_loop``/``cond`` predicate —
  an older jax's experimental shard_map under ``check_rep=False``
  miscompiled collectives lowered into loop/branch predicates: device
  0's carried state leaked to every shard (the PR-1 pmap-fallback bug;
  docs/12-Sharding.md post-mortem). The engine computes every such
  flag in the loop BODY and threads it through the carry
  (``core.engine._drain_flag``); this rule pins that structurally.
- SL112 computed-index gather of a global ``[NC]``-sized table inside
  vmapped handler scope — model handlers receive the global config
  dict ``g`` and by convention index its per-host tables with their
  own gid (``g["count"][me]``): under vmap that lowers to a cheap
  aligned row select. Indexing with any *other* traced value
  (``g["recvsize"][pkt.src_host]``) lowers to a full gather across the
  whole table per host per sweep — O(H·NC) traffic that scales
  quadratically with host count and silently dominates city-scale
  builds. Cross-host lookups are sometimes the point; sanctioned sites
  carry ``# shadowlint: disable=SL112`` with a reason.
- SL113 blocking socket/HTTP call on the jit or window-dispatch path —
  ``sock.recv()``/``sock.accept()``/``httpd.serve_forever()``/
  ``conn.getresponse()`` park the calling thread in the kernel with no
  deadline. Inside jit scope, or inside a window-loop drive scope
  (``run``/``step_window``/``dispatch``), that stalls the entire
  device loop behind one slow peer. The serving plane's discipline
  (obs/server.py, serve/http.py): blocking socket work lives ONLY on
  ThreadingHTTPServer handler threads; the drive path never touches a
  socket.
- SL114 shared-attribute mutation in a thread-entry scope without the
  instance lock — `do_<VERB>` HTTP handler methods run one per request
  thread, and any method passed as ``threading.Thread(target=...)``
  runs concurrently with the submitting thread. Writing state other
  threads read (`self.attr` in a lock-owning worker class; anything
  reached through ``self.<obj>.<attr>`` from a per-request handler)
  outside a ``with self._lock:`` block is a data race the serving
  plane's discipline (serve/service.py, obs/servetrace.py,
  obs/server.py) already forbids. Code lexically under a ``with`` on a
  lock-ish attribute (``*lock*``/``*cond*``/``*mutex*``), methods
  named ``*_locked`` (caller holds it), and the lock attributes
  themselves are exempt.

Findings carry a stable key (rule | relpath | enclosing function |
stripped source line) so the baseline survives unrelated line drift.
Inline suppression: ``# shadowlint: disable=SL101,SL104`` (or a bare
``# shadowlint: disable``) on the flagged line.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re
from typing import Iterable

RULES = {
    "SL101": "host materialization inside jit scope",
    "SL102": "Python branch on a traced value inside jit scope",
    "SL103": "i32 cast/construction of a simulated-time expression",
    "SL104": "PRNG key reuse without split",
    "SL105": "mutable default argument or class-body default",
    "SL106": "iteration over a set (nondeterministic order)",
    "SL107": "window-loop entry point jitted without donate_argnums",
    "SL108": "collective call inside a while_loop/cond predicate",
    "SL109": "blocking device sync outside watchdog-scoped sites",
    "SL110": "wall-clock read inside jit scope",
    "SL111": "donated buffer double-donated or reused after donation",
    "SL112": "computed-index gather of a global host table in handler scope",
    "SL113": "blocking socket/HTTP call on the jit or window-dispatch path",
    "SL114": "shared-attribute mutation in thread-entry scope without "
             "the instance lock",
}

# SL112: names under which model handlers receive the global config
# dict (models/*.py convention: `def build(...)` packs per-host tables
# into `g`, handlers close over it or take it as a parameter).
_GLOBAL_TABLE_NAMES = {"g", "_g", "gtab", "gtables"}
# Index heads that select the handler's OWN row (aligned under vmap):
# the gid convention plus static full-range constructions.
_OWN_GID_NAMES = {"me", "gid", "gids"}
_STATIC_INDEX_CALLS = {"arange", "iota", "broadcasted_iota"}

# SL110: time-module entry points that read the wall clock. Bare-name
# calls (``from time import perf_counter``) match everything except
# plain ``time`` — a bare ``time()`` is far more often a shadowed
# variable than the stdlib call, and the module-qualified form covers
# the real uses.
_WALLCLOCK_ATTRS = {
    "time", "perf_counter", "monotonic",
    "time_ns", "perf_counter_ns", "monotonic_ns",
}

# SL113: blocking socket / http.server entry points — each parks the
# calling thread in the kernel with NO deadline. Reachable from jit
# scope or from a window-loop drive scope (`run`/`step_window`/
# `dispatch`) they stall the whole device loop behind one slow client.
# The serving discipline (obs/server.py, serve/http.py) keeps them on
# ThreadingHTTPServer handler threads, never on the drive path.
_BLOCKING_SOCKET_ATTRS = {
    "recv", "recvfrom", "recv_into", "recvmsg", "accept",
    "serve_forever", "handle_request", "getresponse",
}
# window-loop drive scopes: the engine/fleet state-threading entry
# points plus the segment-dispatch site of the run loop
_DISPATCH_SCOPES = {"run", "step_window", "dispatch"}

# SL114: thread-entry scopes and the lock discipline they must follow.
# `do_<VERB>` methods run one per ThreadingHTTPServer request thread;
# methods named as a `threading.Thread(target=...)` (pass 1) run
# concurrently with the thread that spawned them.
_HTTP_VERB_RE = re.compile(r"^do_[A-Z]+$")
# attributes that ARE the synchronization (with self._lock: /
# self._cond: / self._scrape_lock:) — both the exemption context and
# excluded as mutation targets
_LOCKISH_RE = re.compile(r"lock|cond|mutex", re.IGNORECASE)
# constructors whose result makes a class "lock-owning" when assigned
# to a self attribute anywhere in the class body
_LOCK_CTORS = {"Lock", "RLock", "Condition", "Semaphore",
               "BoundedSemaphore"}
# container mutators that write through an attribute chain. "set" is
# deliberately absent — `self.metrics.set(...)`-style gauge APIs are
# value setters on objects that do their own locking, and the single
# word collides with far too many benign APIs.
_SL114_MUTATORS = {
    "append", "extend", "insert", "remove", "clear", "update",
    "setdefault", "add", "discard", "popleft", "appendleft",
}

# SL107: callables by these names are window-loop entry points (the
# engine's state-threading convention), and parameters by these names
# carry the donated EngineState.
_ENTRY_NAMES = {"run", "step_window"}
_STATE_PARAMS = {"state", "st"}

# Functions whose callee-arguments are traced (their bodies are jit
# scope): jax.jit itself plus the structured control-flow / mapping
# combinators the engine uses.
_JIT_WRAPPERS = {
    "jit",
    "while_loop",
    "fori_loop",
    "cond",
    "scan",
    "switch",
    "vmap",
    "pmap",
    "shard_map",
    "checkpoint",
    "remat",
    "custom_jvp",
    "custom_vjp",
}

# np.<attr> uses that are dtype/constant plumbing, not host compute.
_NP_ALLOWED = {
    "int8", "int16", "int32", "int64",
    "uint8", "uint16", "uint32", "uint64",
    "float16", "float32", "float64",
    "bool_", "dtype", "ndarray", "generic",
    "pi", "inf", "nan", "newaxis",
    "iinfo", "finfo", "issubdtype", "integer", "floating",
}

# Time-like identifier fragments (core/timebase.py semantics: these
# carry simulated nanoseconds and must stay TIME_DTYPE = i64)...
_TIMEY = re.compile(
    r"(?:^|_|\b)(time|now|deadline|delay|due|latency|clock|window_end|"
    r"stoptime|cpu_free|t0|t1|ns|when|expiry|timeout)(?:_|\b|$)",
    re.IGNORECASE,
)
# ...unless the name is really a count/index that happens to mention
# time (event counts, sequence numbers, shard ranks, ...).
_NOT_TIMEY = re.compile(
    r"(count|idx|index|seq|rank|slot|drops|num_|n_|_id\b|mask|kind|bins)",
    re.IGNORECASE,
)

_PRNG_CONSUMERS_SKIP = {
    "split", "fold_in", "PRNGKey", "key", "key_data", "wrap_key_data",
    "clone",
}
_PRNG_NAMESPACES = {"srng", "random", "jr", "rng"}

# SL108: collective primitives whose lowering into a while_loop cond or
# a lax.cond predicate triggered an older jax's experimental-shard_map
# check_rep=False miscompile (predicate re-evaluated per shard off
# device 0's carry), plus the engine's in-package reduction wrappers
# built directly on them — a `self._gany(...)` in a predicate is the
# same bug one call away.
_COLLECTIVES = {
    "psum", "pmin", "pmax", "pmean", "psum_scatter",
    "all_to_all", "ppermute", "all_gather", "pshuffle", "pbroadcast",
}
_COLLECTIVE_WRAPPERS = {"_gany", "_gmin", "_gsum"}

_SUPPRESS_RE = re.compile(r"#\s*shadowlint:\s*disable(?:=([A-Z0-9,\s]+))?")
# SL107's reasoned exemption: the reason is mandatory (an empty one
# does not suppress), so every undonated entry point documents itself.
_NO_DONATE_RE = re.compile(r"#\s*shadowlint:\s*no-donate=(\S.*)")
# SL109's reasoned exemption, same contract: a bare `no-deadline=` does
# not suppress — the reason documents why an unbounded block is safe.
_NO_DEADLINE_RE = re.compile(r"#\s*shadowlint:\s*no-deadline=(\S.*)")

# SL109 sanctioned blocking scopes: the harvest class whose fetch the
# CLI pets its collective watchdog around, and the watchdog layer
# itself (its whole job is bounding everyone else's blocking).
_SL109_CLASS_ALLOWED = {"HeartbeatHarvest"}
_SL109_FILE_ALLOWED = ("runtime/supervisor.py",)


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str  # repo-relative where possible
    line: int
    col: int
    func: str  # dotted enclosing-scope name ("<module>" at top level)
    message: str
    snippet: str  # stripped source line (stable-key component)

    @property
    def key(self) -> str:
        return f"{self.rule}|{self.path}|{self.func}|{self.snippet}"

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["key"] = self.key
        return d

    def __str__(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: {self.rule} "
                f"[{self.func}] {self.message}")


def _unparse(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:
        return ast.dump(node)


def _call_basename(func: ast.AST) -> str:
    """Rightmost name of a call target: jax.lax.while_loop -> while_loop."""
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return ""


def _attr_root(node: ast.AST) -> str:
    """Leftmost name of an attribute chain: self.cfg.trace -> self."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else ""


def _is_timey(text: str) -> bool:
    return bool(_TIMEY.search(text)) and not _NOT_TIMEY.search(text)


def _is_int32_expr(node: ast.AST) -> bool:
    """jnp.int32 / np.int32 / 'int32' / "i4"-style dtype expressions."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value in ("int32", "i32", "<i4", "i4")
    if isinstance(node, ast.Attribute) and node.attr == "int32":
        return _attr_root(node) in ("jnp", "np", "numpy", "jax")
    return False


class _Scope:
    """Per-function lint context threaded through the visitor."""

    def __init__(self, name: str, jitted: bool, params: set[str],
                 predicate: bool = False):
        self.name = name
        self.jitted = jitted
        self.params = params  # traced-candidate parameter names
        self.predicate = predicate  # body lowers as a while_loop cond


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, src: str):
        self.path = path
        self.lines = src.splitlines()
        self.findings: list[Finding] = []
        self.scopes: list[_Scope] = [_Scope("<module>", False, set())]
        # names referenced as callee arguments of jit wrappers anywhere
        # in the file (pass 1) — their defs are jit scope
        self.jit_marked: set[str] = set()
        # names passed as while_loop's cond_fun (pass 1) — their defs
        # lower as loop predicates (SL108 scope)
        self.pred_marked: set[str] = set()
        # SL108 nodes already reported (a lax.cond inside a predicate
        # function would otherwise double-fire)
        self._sl108_seen: set[int] = set()
        # def name -> parameter names, for SL107's in-file resolution
        self.func_params: dict[str, tuple[str, ...]] = {}
        # per-function PRNG use tracking: {keyname: [linenos]}
        self._prng_uses: list[dict[str, list[ast.Call]]] = [{}]
        # SL111 per-function tracking: names bound to a donating
        # jax.jit (name -> donated positions), and names whose buffer
        # was consumed by a donated call (name -> consuming call)
        self._donating: list[dict[str, set[int]]] = [{}]
        self._donate_consumed: list[dict[str, ast.Call]] = [{}]
        # SL114: method names passed as Thread(target=...) (pass 1),
        # the lock-attr sets of enclosing classes, and the lexical
        # `with <lock>:` nesting depth
        self.thread_marked: set[str] = set()
        self._class_locks: list[set[str]] = []
        self._lock_depth = 0

    # ------------------------------------------------------------ utils

    def _suppressed(self, line: int, rule: str) -> bool:
        if 1 <= line <= len(self.lines):
            m = _SUPPRESS_RE.search(self.lines[line - 1])
            if m:
                if not m.group(1):
                    return True
                rules = {r.strip() for r in m.group(1).split(",")}
                return rule in rules
        return False

    def _emit(self, rule: str, node: ast.AST, message: str) -> None:
        line = getattr(node, "lineno", 1)
        if self._suppressed(line, rule):
            return
        snippet = ""
        if 1 <= line <= len(self.lines):
            snippet = self.lines[line - 1].strip()
        func = ".".join(s.name for s in self.scopes[1:]) or "<module>"
        self.findings.append(
            Finding(rule, self.path, line, getattr(node, "col_offset", 0),
                    func, message, snippet))

    @property
    def _scope(self) -> _Scope:
        return self.scopes[-1]

    def _in_jit(self) -> bool:
        return any(s.jitted for s in self.scopes)

    def _traced_names(self) -> set[str]:
        names: set[str] = set()
        for s in self.scopes:
            if s.jitted:
                names |= s.params
        return names

    # --------------------------------------------------------- functions

    def _func_is_jitted(self, node: ast.FunctionDef | ast.AsyncFunctionDef) -> bool:
        for dec in node.decorator_list:
            base = dec
            if isinstance(base, ast.Call):  # @partial(jax.jit, ...)
                if any(_call_basename(a) in _JIT_WRAPPERS
                       for a in base.args
                       if isinstance(a, (ast.Name, ast.Attribute))):
                    return True
                base = base.func
            if _call_basename(base) in _JIT_WRAPPERS:
                return True
        if node.name in self.jit_marked:
            return True
        return self._in_jit()  # nested defs inherit jit scope

    def _visit_funcdef(self, node) -> None:
        jitted = self._func_is_jitted(node)
        params = set()
        if jitted:
            a = node.args
            names = [p.arg for p in
                     (a.posonlyargs + a.args + a.kwonlyargs)]
            # drop self/cls and obviously-static plumbing names; params
            # with defaults are usually static feature flags
            n_def = len(a.defaults)
            defaulted = {p.arg for p in a.args[len(a.args) - n_def:]} if n_def else set()
            defaulted |= {p.arg for p, d in zip(a.kwonlyargs, a.kw_defaults) if d}
            for n in names:
                if n in ("self", "cls", "cfg", "config", "axis_name",
                         "dtype", "shape", "name"):
                    continue
                if n in defaulted:
                    continue
                params.add(n)
        # SL105: mutable defaults
        for d in list(node.args.defaults) + [d for d in node.args.kw_defaults if d]:
            if self._mutable_literal(d):
                self._emit("SL105", d,
                           f"mutable default `{_unparse(d)}` in "
                           f"{node.name}() is shared across calls; use "
                           f"None + in-body construction (or a tuple)")
        scope = _Scope(node.name, jitted, params,
                       predicate=node.name in self.pred_marked)
        # SL114: a do_<VERB> method or a Thread-target method is a
        # thread-entry scope; nested defs inherit it (closures run on
        # the same thread). `*_locked` methods document that the
        # caller already holds the lock.
        scope.sl114 = next(
            (getattr(s, "sl114", None) for s in reversed(self.scopes)
             if getattr(s, "sl114", None)), None)
        if scope.sl114 is None \
                and getattr(self._scope, "is_class", False):
            locks = self._class_locks[-1] if self._class_locks else set()
            if _HTTP_VERB_RE.match(node.name):
                scope.sl114 = ("handler", locks)
            elif node.name in self.thread_marked:
                scope.sl114 = ("worker", locks)
        if node.name.endswith("_locked"):
            scope.sl114 = None
        self.scopes.append(scope)
        self._prng_uses.append({})
        self._donating.append({})
        self._donate_consumed.append({})
        self.generic_visit(node)
        self._flush_prng()
        self._prng_uses.pop()
        self._donating.pop()
        self._donate_consumed.pop()
        self.scopes.pop()

    visit_FunctionDef = _visit_funcdef
    visit_AsyncFunctionDef = _visit_funcdef

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        # SL105 for class-body defaults (dataclass fields included):
        # a mutable class attribute is shared by every instance/pytree.
        for stmt in node.body:
            val = None
            if isinstance(stmt, ast.AnnAssign):
                val = stmt.value
                tgts = [stmt.target]
            elif isinstance(stmt, ast.Assign):
                val = stmt.value
                tgts = stmt.targets
            if val is not None and any(
                    isinstance(t, ast.Name) and t.id in
                    ("_fields_", "_anonymous_", "__slots__",
                     "__match_args__")
                    for t in tgts):
                # ctypes/structure protocol attributes: consumed by the
                # metaclass at class creation, never mutated
                val = None
            if val is not None and self._mutable_literal(val):
                self._emit("SL105", val,
                           f"mutable class-body default `{_unparse(val)}` "
                           f"in {node.name} is shared by every instance; "
                           f"use dataclasses.field(default_factory=...)")
        scope = _Scope(node.name, False, set())
        scope.is_class = True
        # SL114: lock attributes the class owns (self.X = Lock() /
        # Condition() / ... anywhere in its body)
        locks: set[str] = set()
        for sub in ast.walk(node):
            if isinstance(sub, ast.Assign) \
                    and isinstance(sub.value, ast.Call) \
                    and _call_basename(sub.value.func) in _LOCK_CTORS:
                for t in sub.targets:
                    if isinstance(t, ast.Attribute) \
                            and _attr_root(t) == "self":
                        locks.add(t.attr)
        self.scopes.append(scope)
        self._class_locks.append(locks)
        self._prng_uses.append({})
        self._donating.append({})
        self._donate_consumed.append({})
        self.generic_visit(node)
        self._prng_uses.pop()
        self._donating.pop()
        self._donate_consumed.pop()
        self._class_locks.pop()
        self.scopes.pop()

    @staticmethod
    def _mutable_literal(node: ast.AST) -> bool:
        if isinstance(node, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                             ast.DictComp, ast.SetComp)):
            return True
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
            return node.func.id in ("list", "dict", "set") and not node.args \
                and not node.keywords
        return False

    # ------------------------------------------------------------- calls

    def visit_Call(self, node: ast.Call) -> None:
        base = _call_basename(node.func)

        # pass-1 marking is done before visiting; nothing to do here for
        # wrapper detection.

        in_jit = self._in_jit()
        traced = self._traced_names() if in_jit else set()

        # SL101: float()/int()/bool() on traced-looking args in jit scope
        if in_jit and isinstance(node.func, ast.Name) \
                and node.func.id in ("float", "int", "bool") and node.args:
            if self._mentions(node.args[0], traced):
                self._emit("SL101", node,
                           f"`{node.func.id}()` on a traced value forces "
                           f"host materialization inside jit scope")

        # SL101: .item() / jax.device_get / np.* compute in jit scope
        if in_jit and isinstance(node.func, ast.Attribute):
            if node.func.attr in ("item", "tolist", "block_until_ready"):
                self._emit("SL101", node,
                           f"`.{node.func.attr}()` materializes on host "
                           f"inside jit scope")
            elif node.func.attr == "device_get" \
                    and _attr_root(node.func) == "jax":
                self._emit("SL101", node,
                           "`jax.device_get` inside jit scope")
            elif _attr_root(node.func) in ("np", "numpy") \
                    and node.func.attr not in _NP_ALLOWED:
                self._emit("SL101", node,
                           f"`np.{node.func.attr}(...)` runs on host "
                           f"inside jit scope; use jnp")

        # SL110: wall-clock reads in jit scope — the call traces to a
        # host float, so the "timestamp" is a compile-time constant
        if in_jit and self._is_wallclock_call(node):
            self._emit(
                "SL110", node,
                f"`{_unparse(node.func)}()` inside jit scope freezes "
                f"the wall clock into a compile-time constant; time on "
                f"host around the jit (obs.WindowProfiler) or thread "
                f"the timestamp in as an argument")

        # SL109: bare blocking sync OUTSIDE jit scope (SL101 owns the
        # inside — the two are mutually exclusive by construction)
        if not in_jit and isinstance(node.func, ast.Attribute):
            blocking = (
                node.func.attr == "block_until_ready"
                or (node.func.attr == "device_get"
                    and _attr_root(node.func) == "jax"))
            if blocking and not self._sl109_allowed(node):
                self._emit(
                    "SL109", node,
                    f"`{_unparse(node.func)}` blocks with no deadline — "
                    f"a lost peer hangs here forever; fetch through "
                    f"HeartbeatHarvest / a watchdog-petted site, or mark "
                    f"the line `# shadowlint: no-deadline=<reason>`")

        # SL113: blocking socket/HTTP-server call reachable from jit
        # scope or a window-loop drive scope — the thread parks in the
        # kernel with no deadline while the device loop waits behind it
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in _BLOCKING_SOCKET_ATTRS:
            drive = [s.name for s in self.scopes
                     if s.name in _DISPATCH_SCOPES]
            if in_jit or drive:
                where = ("jit scope" if in_jit
                         else f"window-dispatch scope `{drive[-1]}`")
                self._emit(
                    "SL113", node,
                    f"`{_unparse(node.func)}()` blocks in the kernel "
                    f"with no deadline inside {where}; socket/HTTP work "
                    f"belongs on a handler thread "
                    f"(obs.server/serve.http discipline)")

        # SL108: collectives lowered into a loop/branch predicate
        self._check_pred_collective(node, base)

        # SL107: jit over a window-loop entry point without donation
        self._check_jit_donation(node)

        # SL103: i32 construction of a time-like expression
        self._check_i32_time(node)

        # SL104: collect PRNG consumer uses
        self._track_prng(node)

        # SL114: container mutation through a shared chain in a
        # thread-entry scope
        self._check_sl114_call(node)

        # SL111: donation hazards at the call site. Consumption is
        # registered only AFTER the call's own arguments are visited,
        # so the consuming call never flags itself.
        consumed = self._check_donate_call(node)

        self.generic_visit(node)
        for name in consumed:
            self._donate_consumed[-1].setdefault(name, node)

    @staticmethod
    def _is_wallclock_call(node: ast.Call) -> bool:
        if isinstance(node.func, ast.Attribute):
            return (node.func.attr in _WALLCLOCK_ATTRS
                    and _attr_root(node.func) in ("time", "_time"))
        if isinstance(node.func, ast.Name):
            return node.func.id in _WALLCLOCK_ATTRS - {"time"}
        return False

    def _sl109_allowed(self, node: ast.Call) -> bool:
        if self.path.replace(os.sep, "/").endswith(_SL109_FILE_ALLOWED):
            return True
        if any(s.name in _SL109_CLASS_ALLOWED for s in self.scopes):
            return True
        line = getattr(node, "lineno", 1)
        return bool(1 <= line <= len(self.lines)
                    and _NO_DEADLINE_RE.search(self.lines[line - 1]))

    def _mentions(self, node: ast.AST, names: set[str]) -> bool:
        if not names:
            return False
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and sub.id in names:
                return True
        return False

    # ---------------------------------------------------- SL107 donation

    def _check_jit_donation(self, node: ast.Call) -> None:
        """jax.jit over a state-threading entry point must donate its
        carry (or carry a reasoned `# shadowlint: no-donate=` marker)."""
        if _call_basename(node.func) != "jit" or not node.args:
            return
        if isinstance(node.func, ast.Attribute) \
                and _attr_root(node.func) != "jax":
            return
        if any(kw.arg in ("donate_argnums", "donate_argnames")
               for kw in node.keywords):
            return
        target = node.args[0]
        why = None
        if isinstance(target, ast.Lambda):
            params = tuple(p.arg for p in target.args.args)
            if params and any(p in _STATE_PARAMS for p in params):
                why = (f"lambda with state carry "
                       f"`{', '.join(params)}`")
        elif isinstance(target, (ast.Name, ast.Attribute)):
            name = _call_basename(target)
            if name in _ENTRY_NAMES:
                why = f"window-loop entry point `{_unparse(target)}`"
            elif isinstance(target, ast.Name):
                params = self.func_params.get(name, ())
                if any(p in _STATE_PARAMS for p in params):
                    why = (f"`{name}({', '.join(params)})` threads a "
                           f"state carry")
        if why is None:
            return
        line = getattr(node, "lineno", 1)
        if 1 <= line <= len(self.lines) \
                and _NO_DONATE_RE.search(self.lines[line - 1]):
            return  # reasoned exemption
        self._emit(
            "SL107", node,
            f"jax.jit over {why} without donate_argnums — the window "
            f"carry is copied every call; donate it (see "
            f"Simulation._wrap) or mark the line "
            f"`# shadowlint: no-donate=<reason>`")

    # ------------------------------------------------ SL111 donation use

    @staticmethod
    def _jit_donate_positions(call: ast.Call) -> set[int] | None:
        """Donated positions of a `jax.jit(...)` call expression, or
        None when it isn't one (or they aren't literal ints)."""
        if _call_basename(call.func) != "jit":
            return None
        if isinstance(call.func, ast.Attribute) \
                and _attr_root(call.func) != "jax":
            return None
        for kw in call.keywords:
            if kw.arg != "donate_argnums":
                continue
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return {v.value}
            if isinstance(v, (ast.Tuple, ast.List)):
                out: set[int] = set()
                for el in v.elts:
                    if not (isinstance(el, ast.Constant)
                            and isinstance(el.value, int)):
                        return None
                    out.add(el.value)
                return out or None
            return None
        return None

    def _check_donate_call(self, node: ast.Call) -> list[str]:
        """SL111 at a call site. Returns Name args consumed by
        donation (the caller registers them after generic_visit)."""
        pos: set[int] | None = None
        if isinstance(node.func, ast.Name):
            for frame in reversed(self._donating):
                if node.func.id in frame:
                    pos = frame[node.func.id]
                    break
        elif isinstance(node.func, ast.Call):
            # direct form: jax.jit(f, donate_argnums=0)(state, ...)
            pos = self._jit_donate_positions(node.func)
        if not pos:
            return []
        callee = _unparse(node.func)
        by_name: dict[str, list[int]] = {}
        for p in sorted(pos):
            if p < len(node.args) and isinstance(node.args[p], ast.Name):
                by_name.setdefault(node.args[p].id, []).append(p)
        for name, ps in by_name.items():
            if len(ps) >= 2:
                self._emit(
                    "SL111", node,
                    f"`{name}` fills donated parameters "
                    f"{' and '.join(map(str, ps))} of `{callee}` in one "
                    f"call — XLA aliases two outputs onto one buffer "
                    f"and the results silently corrupt; pass distinct "
                    f"arrays")
        return list(by_name)

    def visit_Name(self, node: ast.Name) -> None:
        if isinstance(node.ctx, ast.Load):
            first = self._donate_consumed[-1].get(node.id)
            if first is not None:
                self._emit(
                    "SL111", node,
                    f"`{node.id}` was donated to `{_unparse(first.func)}` "
                    f"at line {first.lineno} and is read again — the "
                    f"donated buffer is deleted by that call (jax errors "
                    f"or silently re-copies); rebind the result "
                    f"(`{node.id} = ...`) or pass a copy")
        else:
            # Store/Del rebinds the reference to a fresh buffer (for
            # targets, with-as, del) — clear the tracking
            self._donate_consumed[-1].pop(node.id, None)
            self._donating[-1].pop(node.id, None)
        self.generic_visit(node)

    # --------------------------------------------- SL108 pred collective

    @staticmethod
    def _is_collective_call(node: ast.Call) -> bool:
        base = _call_basename(node.func)
        if base in _COLLECTIVE_WRAPPERS:
            return True  # self._gany / eng._gmin — psum/pmin one call away
        if base not in _COLLECTIVES:
            return False
        if isinstance(node.func, ast.Attribute):
            return _attr_root(node.func) in ("lax", "jax")
        return True  # `from jax.lax import psum` style

    def _sl108_emit(self, node: ast.Call) -> None:
        if id(node) in self._sl108_seen:
            return
        self._sl108_seen.add(id(node))
        self._emit(
            "SL108", node,
            f"collective `{_unparse(node.func)}` lowers into a "
            f"while/cond predicate — a predicate evaluated per shard "
            f"off device 0's carry is the miscompile this rule guards "
            f"against; compute the flag in the loop body and carry it "
            f"(core.engine._drain_flag)")

    def _check_pred_collective(self, node: ast.Call, base: str) -> None:
        # (a) any collective lexically inside a cond-function body
        if self._is_collective_call(node) \
                and any(s.predicate for s in self.scopes):
            self._sl108_emit(node)
        # (b) inline-lambda cond: while_loop(lambda c: ..., body, init)
        # (named/attribute conds are resolved by pass-1 pred_marked)
        pred = None
        if base == "while_loop":
            tgt = node.args[0] if node.args else None
            for kw in node.keywords:
                if kw.arg == "cond_fun":
                    tgt = kw.value
            if isinstance(tgt, ast.Lambda):
                pred = tgt.body
        # (c) lax.cond's predicate EXPRESSION (first positional arg)
        elif base == "cond" and isinstance(node.func, ast.Attribute) \
                and _attr_root(node.func) in ("lax", "jax"):
            pred = node.args[0] if node.args else None
        if pred is not None:
            for sub in ast.walk(pred):
                if isinstance(sub, ast.Call) \
                        and self._is_collective_call(sub):
                    self._sl108_emit(sub)

    # ------------------------------------------------------ SL102 branch

    def _check_branch(self, node, kind: str) -> None:
        if not self._in_jit():
            self.generic_visit(node)
            return
        test = node.test
        if self._test_whitelisted(test):
            self.generic_visit(node)
            return
        traced = self._traced_names()
        if self._mentions(test, traced):
            self._emit("SL102", node,
                       f"Python `{kind}` on `{_unparse(test)}` — traced "
                       f"values cannot drive Python control flow; use "
                       f"lax.cond/jnp.where")
        self.generic_visit(node)

    def visit_If(self, node: ast.If) -> None:
        self._check_branch(node, "if")

    def visit_While(self, node: ast.While) -> None:
        self._check_branch(node, "while")

    def visit_IfExp(self, node: ast.IfExp) -> None:
        self._check_branch(node, "ternary")

    @staticmethod
    def _test_whitelisted(test: ast.AST) -> bool:
        """Static-dispatch shapes: isinstance/hasattr/len checks, `is
        (not) None`, and attribute chains rooted at self/cfg (static
        engine configuration, not traced state)."""
        def ok(node: ast.AST) -> bool:
            if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Not):
                return ok(node.operand)
            if isinstance(node, ast.BoolOp):
                return all(ok(v) for v in node.values)
            if isinstance(node, ast.Call):
                return _call_basename(node.func) in (
                    "isinstance", "hasattr", "len", "callable", "getattr")
            if isinstance(node, ast.Compare):
                if all(isinstance(op, (ast.Is, ast.IsNot))
                       for op in node.ops):
                    return True
                return ok(node.left) and all(ok(c) for c in node.comparators)
            if isinstance(node, ast.Attribute):
                return _attr_root(node) in ("self", "cfg", "config")
            if isinstance(node, ast.Constant):
                return True
            return False
        return ok(test)

    # -------------------------------------------------------- SL103 time

    def _check_i32_time(self, node: ast.Call) -> None:
        base = _call_basename(node.func)
        # <timey>.astype(int32-ish)
        if base == "astype" and node.args and _is_int32_expr(node.args[0]) \
                and isinstance(node.func, ast.Attribute):
            target = _unparse(node.func.value)
            if _is_timey(target):
                self._emit("SL103", node,
                           f"`{target}.astype(int32)` truncates simulated "
                           f"time (wraps after ~2.1 s); keep "
                           f"timebase.TIME_DTYPE")
            return
        # jnp.int32(<timey>) / np.int32(<timey>)
        if base == "int32" and node.args \
                and _attr_root(node.func) in ("jnp", "np", "numpy"):
            arg = _unparse(node.args[0])
            if _is_timey(arg):
                self._emit("SL103", node,
                           f"`int32({arg})` truncates simulated time; "
                           f"keep timebase.TIME_DTYPE")
            return
        # dtype=int32 kwarg where a positional arg is time-like.
        # Comparisons are exempt: `sum(t != TIME_INVALID, dtype=int32)`
        # counts booleans derived FROM time — count arithmetic, not
        # time arithmetic.
        for kw in node.keywords:
            if kw.arg == "dtype" and _is_int32_expr(kw.value):
                args = [a for a in node.args
                        if not isinstance(a, ast.Compare)]
                texts = [_unparse(a) for a in args]
                if any(_is_timey(t) for t in texts):
                    self._emit("SL103", node,
                               f"`dtype=int32` on time-like value "
                               f"`{', '.join(texts)}`; keep "
                               f"timebase.TIME_DTYPE")

    def visit_Assign(self, node: ast.Assign) -> None:
        # SL114: shared-attribute store in a thread-entry scope
        for tgt in node.targets:
            self._check_sl114_store(tgt, node)
        # SL103: timey_name = jnp.zeros(..., dtype=int32)-style constructions
        if isinstance(node.value, ast.Call):
            for kw in node.value.keywords:
                if kw.arg == "dtype" and _is_int32_expr(kw.value):
                    for tgt in node.targets:
                        t = _unparse(tgt)
                        if _is_timey(t) and not self._suppressed(
                                node.lineno, "SL103"):
                            self._emit("SL103", node,
                                       f"time-like `{t}` constructed with "
                                       f"dtype=int32; keep "
                                       f"timebase.TIME_DTYPE")
                        break
        # SL104: reassignment of a key name resets its use count
        for tgt in node.targets:
            for sub in ast.walk(tgt):
                if isinstance(sub, ast.Name):
                    self._prng_uses[-1].pop(sub.id, None)
        self.generic_visit(node)
        # SL111: a rebound name is a fresh buffer — clear AFTER the
        # value was visited, so `st = step(st, stop)` first registers
        # st as consumed (by the call) and then immediately clears it;
        # a binding to a donating jax.jit becomes a tracked callee
        tgt_names = [sub.id for tgt in node.targets
                     for sub in ast.walk(tgt)
                     if isinstance(sub, ast.Name)]
        for n in tgt_names:
            self._donate_consumed[-1].pop(n, None)
            self._donating[-1].pop(n, None)
        if isinstance(node.value, ast.Call) and len(tgt_names) == 1:
            pos = self._jit_donate_positions(node.value)
            if pos:
                self._donating[-1][tgt_names[0]] = pos

    # -------------------------------------------------------- SL104 PRNG

    def _track_prng(self, node: ast.Call) -> None:
        if not isinstance(node.func, ast.Attribute):
            return
        root = _attr_root(node.func)
        chain_is_jax_random = (
            isinstance(node.func.value, ast.Attribute)
            and node.func.value.attr == "random"
            and _attr_root(node.func.value) == "jax")
        if root not in _PRNG_NAMESPACES and not chain_is_jax_random:
            return
        if "stream" in node.func.attr:
            # counter-based stream APIs (core/rng.py fault_stream_*,
            # uniform_lanes-style) take (seed, stream_id): the first
            # arg is deliberately reused across distinct stream ids
            return
        if node.func.attr in _PRNG_CONSUMERS_SKIP:
            # split/fold_in consume-and-derive; also reset the budget
            # for their source key (splitting IS the fix for reuse)
            if node.args and isinstance(node.args[0], ast.Name):
                self._prng_uses[-1].pop(node.args[0].id, None)
            return
        if node.args and isinstance(node.args[0], ast.Name):
            self._prng_uses[-1].setdefault(node.args[0].id, []).append(node)

    def _flush_prng(self) -> None:
        for name, calls in self._prng_uses[-1].items():
            if len(calls) >= 2:
                for call in calls[1:]:
                    self._emit(
                        "SL104", call,
                        f"PRNG key `{name}` already consumed at line "
                        f"{calls[0].lineno}; reuse correlates draws — "
                        f"split first")

    # --------------------------------------------------------- SL106 set

    def _check_set_iter(self, iter_node: ast.AST, where: ast.AST) -> None:
        is_set = isinstance(iter_node, (ast.Set, ast.SetComp)) or (
            isinstance(iter_node, ast.Call)
            and isinstance(iter_node.func, ast.Name)
            and iter_node.func.id in ("set", "frozenset"))
        if is_set:
            self._emit("SL106", where,
                       f"iterating `{_unparse(iter_node)}` — set order is "
                       f"hash order; sort first (pytree leaf order must "
                       f"be deterministic)")

    def visit_For(self, node: ast.For) -> None:
        self._check_set_iter(node.iter, node)
        self.generic_visit(node)

    def _visit_comp(self, node) -> None:
        for gen in node.generators:
            self._check_set_iter(gen.iter, node)
        self.generic_visit(node)

    visit_ListComp = _visit_comp
    visit_SetComp = _visit_comp
    visit_DictComp = _visit_comp
    visit_GeneratorExp = _visit_comp

    # ----------------------------------------------------- SL112 gather

    def _in_handler_scope(self) -> bool:
        # Model handlers lower under the engine's vmap even though no
        # jit wrapper appears in the model file itself: they are either
        # closures inside a *make_handlers factory or `_on_*` methods
        # registered by one (models/*.py convention). Jit scope proper
        # also counts.
        return self._in_jit() or any(
            "handlers" in s.name or s.name.startswith("_on_")
            for s in self.scopes[1:])

    @staticmethod
    def _is_own_row_index(idx: ast.AST) -> bool:
        # Only the FIRST index element picks the host row; trailing
        # elements (`g["peers"][me, j]`) index within the own row.
        if isinstance(idx, ast.Tuple) and idx.elts:
            idx = idx.elts[0]
        if isinstance(idx, (ast.Constant, ast.Slice)):
            return True
        if isinstance(idx, ast.Name):
            return idx.id in _OWN_GID_NAMES
        if isinstance(idx, ast.Attribute):
            return idx.attr in _OWN_GID_NAMES
        if isinstance(idx, ast.Call):
            return _call_basename(idx.func) in _STATIC_INDEX_CALLS
        return False

    def visit_Subscript(self, node: ast.Subscript) -> None:
        inner = node.value
        if (isinstance(inner, ast.Subscript)
                and isinstance(inner.slice, ast.Constant)
                and isinstance(inner.slice.value, str)
                and _attr_root(inner.value) in _GLOBAL_TABLE_NAMES
                and self._in_handler_scope()
                and not self._is_own_row_index(node.slice)):
            table = _unparse(inner)
            head = node.slice
            if isinstance(head, ast.Tuple) and head.elts:
                head = head.elts[0]
            self._emit(
                "SL112", node,
                f"`{table}[{_unparse(head)}]` gathers a global table by "
                f"a computed index inside vmapped handler scope — under "
                f"vmap this reads the whole [NC] table per host per "
                f"sweep; index by own gid (`me`) or, if the cross-host "
                f"lookup is intended, suppress with a reason")
        self.generic_visit(node)

    # ---------------------------------------------------- SL114 threads

    def _sl114_ctx(self):
        """(kind, class_locks) when the current scope is a thread-entry
        scope and the write is not under a lock; None otherwise."""
        if self._lock_depth:
            return None
        for s in reversed(self.scopes):
            ctx = getattr(s, "sl114", None)
            if ctx:
                return ctx
        return None

    @staticmethod
    def _is_lockish(expr: ast.AST) -> bool:
        """`with self._lock:` / `with self._cond:` / `with lock:` —
        also through chains (`self.service._lock`)."""
        if isinstance(expr, ast.Call):  # acquire_timeout()-style helpers
            expr = expr.func
        if isinstance(expr, ast.Attribute):
            return bool(_LOCKISH_RE.search(expr.attr))
        if isinstance(expr, ast.Name):
            return bool(_LOCKISH_RE.search(expr.id))
        return False

    @staticmethod
    def _self_chain(node: ast.AST) -> list[str] | None:
        """Attribute names of a chain rooted at `self`, outermost last;
        None for non-self targets. Subscripts are transparent: storing
        to `self.a.b[k]` mutates the shared `self.a.b`."""
        attrs: list[str] = []
        while True:
            if isinstance(node, ast.Subscript):
                node = node.value
            elif isinstance(node, ast.Attribute):
                attrs.append(node.attr)
                node = node.value
            else:
                break
        if isinstance(node, ast.Name) and node.id == "self" and attrs:
            return list(reversed(attrs))
        return None

    def _check_sl114_store(self, target: ast.AST, node: ast.AST) -> None:
        ctx = self._sl114_ctx()
        if ctx is None:
            return
        kind, locks = ctx
        chain = self._self_chain(target)
        if not chain or any(_LOCKISH_RE.search(a) for a in chain):
            return
        dotted = "self." + ".".join(chain)
        if len(chain) >= 2:
            # a handler/worker writing through self.<obj>.<attr>
            # mutates an object every other request thread shares
            self._emit(
                "SL114", node,
                f"`{dotted}` written in thread-entry scope "
                f"`{self._scope.name}` mutates a shared object without "
                f"the instance lock; wrap in `with ...lock:` (or move "
                f"the write behind a `*_locked` method)")
        elif kind == "worker" and locks:
            # a Thread-target method of a lock-owning class: every
            # bare self write races the submitting thread
            self._emit(
                "SL114", node,
                f"`{dotted}` written in worker-thread scope "
                f"`{self._scope.name}` outside "
                f"`with self.{sorted(locks)[0]}:` — the class owns a "
                f"lock precisely so worker-visible state is only "
                f"touched under it")

    def _check_sl114_call(self, node: ast.Call) -> None:
        if not isinstance(node.func, ast.Attribute) \
                or node.func.attr not in _SL114_MUTATORS:
            return
        ctx = self._sl114_ctx()
        if ctx is None:
            return
        kind, locks = ctx
        chain = self._self_chain(node.func.value)
        if not chain or any(_LOCKISH_RE.search(a) for a in chain):
            return
        if len(chain) >= 2 or (kind == "worker" and locks):
            dotted = "self." + ".".join(chain)
            self._emit(
                "SL114", node,
                f"`{dotted}.{node.func.attr}(...)` mutates shared "
                f"state in thread-entry scope `{self._scope.name}` "
                f"without the instance lock; wrap in `with ...lock:`")

    def visit_With(self, node: ast.With) -> None:
        lockish = any(self._is_lockish(item.context_expr)
                      for item in node.items)
        if lockish:
            self._lock_depth += 1
        self.generic_visit(node)
        if lockish:
            self._lock_depth -= 1

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._check_sl114_store(node.target, node)
        self.generic_visit(node)


class _JitMarker(ast.NodeVisitor):
    """Pass 1: collect names referenced as callee arguments of jit
    wrappers (lax.while_loop(cond, body, ...) marks cond/body)."""

    def __init__(self) -> None:
        self.marked: set[str] = set()
        # def name -> parameter names (SL107 resolves in-file callables)
        self.func_params: dict[str, tuple[str, ...]] = {}
        # names passed as while_loop's cond_fun — predicate scope (SL108)
        self.pred_marked: set[str] = set()
        # names passed as Thread(target=...) — thread-entry scope (SL114)
        self.thread_targets: set[str] = set()

    def _visit_funcdef(self, node) -> None:
        a = node.args
        self.func_params[node.name] = tuple(
            p.arg for p in (a.posonlyargs + a.args))
        self.generic_visit(node)

    visit_FunctionDef = _visit_funcdef
    visit_AsyncFunctionDef = _visit_funcdef

    def visit_Call(self, node: ast.Call) -> None:
        if _call_basename(node.func) == "Thread":
            for kw in node.keywords:
                if kw.arg == "target":
                    if isinstance(kw.value, ast.Attribute):
                        self.thread_targets.add(kw.value.attr)
                    elif isinstance(kw.value, ast.Name):
                        self.thread_targets.add(kw.value.id)
        if _call_basename(node.func) == "while_loop":
            tgt = node.args[0] if node.args else None
            for kw in node.keywords:
                if kw.arg == "cond_fun":
                    tgt = kw.value
            if isinstance(tgt, ast.Name):
                self.pred_marked.add(tgt.id)
            elif isinstance(tgt, ast.Attribute):
                self.pred_marked.add(tgt.attr)
        if _call_basename(node.func) in _JIT_WRAPPERS:
            for a in list(node.args) + [k.value for k in node.keywords]:
                if isinstance(a, ast.Name):
                    self.marked.add(a.id)
                elif isinstance(a, (ast.List, ast.Tuple)):
                    for el in a.elts:
                        if isinstance(el, ast.Name):
                            self.marked.add(el.id)
                elif isinstance(a, ast.Attribute):
                    # lax.while_loop(cond, self._body, ...) marks _body
                    self.marked.add(a.attr)
        self.generic_visit(node)


# ------------------------------------------------------------- frontend


def _rel(path: str) -> str:
    root = _repo_root()
    try:
        return os.path.relpath(os.path.abspath(path), root)
    except ValueError:
        return path


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def lint_source(src: str, path: str = "<string>") -> list[Finding]:
    """Lint one source text. `path` labels findings (and baseline keys)."""
    tree = ast.parse(src, filename=path)
    marker = _JitMarker()
    marker.visit(tree)
    linter = _Linter(path, src)
    linter.jit_marked = marker.marked
    linter.func_params = marker.func_params
    linter.pred_marked = marker.pred_marked
    linter.thread_marked = marker.thread_targets
    linter.visit(tree)
    return sorted(linter.findings, key=lambda f: (f.path, f.line, f.rule))


def lint_paths(paths: Iterable[str]) -> list[Finding]:
    findings: list[Finding] = []
    for p in paths:
        with open(p, "r", encoding="utf-8") as fh:
            src = fh.read()
        findings.extend(lint_source(src, _rel(p)))
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


def package_files(root: str | None = None) -> list[str]:
    """All .py files of the shadow_tpu package (analysis included —
    the linter lints itself)."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                out.append(os.path.join(dirpath, fn))
    return out


def lint_package(root: str | None = None) -> list[Finding]:
    return lint_paths(package_files(root))


# ------------------------------------------------------------- baseline

BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "lint_baseline.json")


def load_baseline(path: str = BASELINE_PATH) -> dict[str, int]:
    if not os.path.exists(path):
        return {}
    with open(path, "r", encoding="utf-8") as fh:
        data = json.load(fh)
    return {str(k): int(v) for k, v in data.get("entries", {}).items()}


def save_baseline(findings: Iterable[Finding],
                  path: str = BASELINE_PATH) -> dict[str, int]:
    entries: dict[str, int] = {}
    for f in findings:
        entries[f.key] = entries.get(f.key, 0) + 1
    data = {
        "version": 1,
        "comment": "shadowlint accepted findings; regenerate with "
                   "`python -m shadow_tpu.tools.lint --update-baseline`",
        "entries": dict(sorted(entries.items())),
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(data, fh, indent=1, sort_keys=False)
        fh.write("\n")
    return entries


def split_new(findings: Iterable[Finding],
              baseline: dict[str, int]) -> tuple[list[Finding], list[Finding], list[str]]:
    """Partition findings into (new, baselined) and report stale
    baseline keys that matched nothing (candidates for pruning)."""
    budget = dict(baseline)
    new: list[Finding] = []
    old: list[Finding] = []
    for f in findings:
        if budget.get(f.key, 0) > 0:
            budget[f.key] -= 1
            old.append(f)
        else:
            new.append(f)
    stale = sorted(k for k, v in budget.items() if v > 0)
    return new, old, stale
