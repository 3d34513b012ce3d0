"""StableHLO pretty-text -> structural op graph (defs/uses/regions/bytes).

The contract audits (hlo_audit), the donation verifier (donation) and
the peak-memory estimator (memory) all interrogate the *lowered
program*, not the Python source. Until PR 12 that interrogation was a
flat regex over the text — which cannot tell an op inside the window
loop's while body from one in a dead private helper, counts the
`applies stablehlo.minimum` clause of a reduce as an op, and misses the
quoted `custom_call @"..."` target form. This module parses the MLIR
pretty form jax emits (`jit(f).lower(...).as_text()`) into a real
graph:

- `Module` / `Func` / `Region` / `Op`: ops with result names, operand
  names (SSA base names, `%123#15` -> `%123`), result types, and
  nested regions (while cond/do, sort comparators, reduce reducers,
  case branches) attached where they occur.
- Reachability from the public funcs over `func.call` edges, so dead
  private helpers never count against a budget.
- `bytes_of_type("tensor<8x32xi64>")` for the liveness estimator.

The grammar is the subset the installed jax actually prints (verified against
full engine lowerings of every model config); unrecognized lines are
skipped, never fatal — an auditor must degrade to "saw less", not
crash the lint gate. Loose op fragments outside any `func.func` (used
by contract tests) land in an implicit public `<toplevel>` func.
"""

from __future__ import annotations

import dataclasses
import re
from collections import Counter
from typing import Iterator

# --------------------------------------------------------------- bytes

_DTYPE_BYTES = {
    "i1": 1, "i2": 1, "i4": 1, "i8": 1, "i16": 2, "i32": 4, "i64": 8,
    "ui2": 1, "ui4": 1, "ui8": 1, "ui16": 2, "ui32": 4, "ui64": 8,
    "f8E4M3FN": 1, "f8E4M3": 1, "f8E5M2": 1, "f8E4M3B11FNUZ": 1,
    "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
    "pred": 1, "index": 8,
}

def dtype_bytes(dtype: str) -> int:
    """Bytes per element of an MLIR element type; 0 when unknown."""
    if dtype in _DTYPE_BYTES:
        return _DTYPE_BYTES[dtype]
    m = re.fullmatch(r"[a-z]+?(\d+)(?:E\w+)?", dtype)
    return (int(m.group(1)) + 7) // 8 if m else 0


def bytes_of_type(t: str) -> int:
    """Total bytes of one MLIR type string; 0 for non-tensor types
    (tokens, tuples sum their tensor elements). Encoding attributes
    after the dims (``tensor<8xi64, #stablehlo...<...>>``) nest angle
    brackets, so the payload is cut with a balanced scan, not a regex.
    """
    total = 0
    i = 0
    while True:
        j = t.find("tensor<", i)
        if j < 0:
            break
        end = _balanced(t, j + len("tensor"), "<", ">")
        payload = t[j + len("tensor<"):end - 1]
        i = end
        payload = _split_commas(payload)[0].strip()  # drop encoding attr
        parts = payload.split("x")
        n = 1
        for dim in parts[:-1]:
            n *= int(dim) if dim.isdigit() else 0
        total += n * dtype_bytes(parts[-1])
    return total


def _split_commas(s: str) -> list[str]:
    """Split on top-level commas, respecting <> () {} [] and quotes."""
    out, depth, start, i, q = [], 0, 0, 0, False
    while i < len(s):
        c = s[i]
        if q:
            if c == '"' and s[i - 1] != "\\":
                q = False
        elif c == '"':
            q = True
        elif c in "<({[":
            depth += 1
        elif c in ">)}]":
            depth -= 1
        elif c == "," and depth == 0:
            out.append(s[start:i].strip())
            start = i + 1
        i += 1
    tail = s[start:].strip()
    if tail:
        out.append(tail)
    return out


# ---------------------------------------------------------------- model


SHARDY_MARKERS = ("sdy.manual_computation", "sdy.sharding_constraint")


@dataclasses.dataclass
class Op:
    """One op instance. `result` is the SSA base name (`%2` for a
    `%2:29 = ...` group of 29 results); `operands` are base names of
    every value the op (or any op in its regions) reads."""

    name: str
    result: str | None = None
    n_results: int = 0
    result_types: list[str] = dataclasses.field(default_factory=list)
    operands: list[str] = dataclasses.field(default_factory=list)
    regions: list["Region"] = dataclasses.field(default_factory=list)
    line: int = 0
    callee: str | None = None
    custom_target: str | None = None

    @property
    def short(self) -> str:
        return self.name.rsplit(".", 1)[-1]

    @property
    def dialect(self) -> str:
        return self.name.rsplit(".", 1)[0] if "." in self.name else ""

    def result_bytes(self) -> int:
        return sum(bytes_of_type(t) for t in self.result_types)

    def walk(self) -> Iterator["Op"]:
        yield self
        for r in self.regions:
            yield from r.walk()


@dataclasses.dataclass
class Region:
    label: str = ""  # "cond" / "do" / "reducer" / "" (generic branch)
    block_args: list[tuple[str, str]] = dataclasses.field(
        default_factory=list)  # (name, type)
    ops: list[Op] = dataclasses.field(default_factory=list)

    def walk(self) -> Iterator[Op]:
        for op in self.ops:
            yield from op.walk()


@dataclasses.dataclass
class Func:
    name: str
    visibility: str  # "public" | "private"
    args: list[tuple[str, str, str]]  # (name, type, attr text)
    result_types: list[str]
    result_infos: list[str]  # jax.result_info per result ("" if absent)
    body: Region

    def arg_bytes(self) -> int:
        return sum(bytes_of_type(t) for _, t, _a in self.args)

    def walk(self) -> Iterator[Op]:
        yield from self.body.walk()


class Module:
    def __init__(self) -> None:
        self.funcs: dict[str, Func] = {}
        self.order: list[str] = []

    def add(self, f: Func) -> None:
        self.funcs[f.name] = f
        self.order.append(f.name)

    @property
    def entry(self) -> Func | None:
        for name in self.order:
            if self.funcs[name].visibility == "public":
                return self.funcs[name]
        return self.funcs[self.order[0]] if self.order else None

    def reachable_funcs(self) -> list[Func]:
        """Funcs reachable from the public funcs over call edges —
        structural dead-code elimination for the audits."""
        roots = [n for n in self.order
                 if self.funcs[n].visibility == "public"]
        if not roots and self.order:
            roots = [self.order[0]]
        seen: list[str] = []
        stack = list(roots)
        while stack:
            name = stack.pop()
            if name in seen or name not in self.funcs:
                continue
            seen.append(name)
            for op in self.funcs[name].walk():
                if op.callee and op.callee not in seen:
                    stack.append(op.callee)
        return [self.funcs[n] for n in self.order if n in seen]

    def ops(self, *, reachable_only: bool = True) -> Iterator[Op]:
        funcs = (self.reachable_funcs() if reachable_only
                 else [self.funcs[n] for n in self.order])
        for f in funcs:
            yield from f.walk()

    def histogram(self, *, reachable_only: bool = True) -> Counter:
        """Per-op-instance counts of dialect ops (short names), over
        reachable funcs only by default — the regex predecessor counted
        dead private helpers and `applies` clauses identically."""
        hist: Counter = Counter()
        for op in self.ops(reachable_only=reachable_only):
            if op.dialect in ("stablehlo", "mhlo", "chlo"):
                hist[op.short] += 1
        return hist

    def find_ops(self, short: str, *,
                 reachable_only: bool = True) -> list[Op]:
        return [op for op in self.ops(reachable_only=reachable_only)
                if op.short == short]

    def custom_call_targets(self, *,
                            reachable_only: bool = True) -> list[str]:
        """Unique custom_call targets, sorted (126 GSPMD `Sharding`
        markers are one fact about the module, not 126)."""
        return sorted({op.custom_target
                       for op in self.find_ops(
                           "custom_call", reachable_only=reachable_only)
                       if op.custom_target})

    def sharding_markers(self, *,
                         reachable_only: bool = True) -> list[str]:
        """Unique Shardy sharding ops, sorted: what the installed jax
        prints for a shard_map (`sdy.manual_computation`) or a sharding
        constraint (`sdy.sharding_constraint`) where older releases
        printed GSPMD `custom_call @Sharding` markers."""
        return sorted({op.name for op in self.ops(
            reachable_only=reachable_only) if op.name in SHARDY_MARKERS})

    def ops_with_path(self) -> Iterator[tuple[Op, str]]:
        """(op, region path) over reachable funcs. The path names every
        enclosing op region, e.g. ``main/while@12.do/while@40.do`` —
        a path containing ``while@N.do`` places the op inside the
        window loop's hot path, and the tail says exactly where (the
        tile/placement auditor's provenance string)."""
        def _walk(region: Region, prefix: str) -> Iterator[tuple[Op, str]]:
            for op in region.ops:
                yield op, prefix
                for i, r in enumerate(op.regions):
                    label = r.label or str(i)
                    yield from _walk(
                        r, f"{prefix}/{op.short}@{op.line}.{label}")

        for f in self.reachable_funcs():
            yield from _walk(f.body, f.name)

    def while_body_ops(self) -> Iterator[Op]:
        """Ops inside any while body ("do" region) — the structural
        form of "in the window loop's hot path"."""
        for op in self.ops():
            if op.short == "while":
                for r in op.regions:
                    if r.label == "do":
                        yield from r.walk()


# --------------------------------------------------------------- parser

_RESULT_RE = re.compile(r"^(%[A-Za-z0-9_]+)(?::(\d+))?\s*=\s*")
_OPNAME_QUOTED_RE = re.compile(r'^"([A-Za-z_][\w.$-]*)"')
_OPNAME_BARE_RE = re.compile(r"^([A-Za-z_][\w$]*\.[A-Za-z_][\w$]*)\b")
_ITER_RE = re.compile(r"(%iterArg\w*)\s*=\s*(%\w+)")
_VALUE_RE = re.compile(r"%([A-Za-z0-9_]+)")
_BLOCK_ARG_RE = re.compile(r"(%[A-Za-z0-9_]+):\s*([^,()]+)")
# quoted names may carry escaped characters (`@"a\"b"`): a string
# atom is any run of non-quote/non-backslash chars or escape pairs
_QSTR = r'(?:[^"\\]|\\.)'
_CALLEE_RE = re.compile(r'@(?:"(' + _QSTR + r'+)"|([\w.$-]+))')
_TARGET_NAME_RE = re.compile(
    r'call_target_name\s*=\s*"(' + _QSTR + r'+)"')
_RESULT_INFO_RE = re.compile(
    r'jax\.result_info\s*=\s*"(' + _QSTR + r'*)"')
_FUNC_RE = re.compile(r"^func\.func\s+(?:(public|private)\s+)?@"
                      r'(?:"(' + _QSTR + r'+)"|([\w.$-]+))\s*\(')


def _balanced(s: str, start: int, open_c: str, close_c: str) -> int:
    """Index just past the matching close for the open at `start`."""
    depth, i, q = 0, start, False
    while i < len(s):
        c = s[i]
        if q:
            if c == '"' and s[i - 1] != "\\":
                q = False
        elif c == '"':
            q = True
        elif c == open_c:
            depth += 1
        elif c == close_c:
            depth -= 1
            if depth == 0:
                return i + 1
        i += 1
    return len(s)


class _Parser:
    def __init__(self) -> None:
        self.module = Module()
        # frames: {"kind": "module"|"func"|"region", "region": Region|None,
        #          "owner": Op|None, "pending_types": bool}
        self.stack: list[dict] = []

    # ------------------------------------------------------------ frames

    def _current_region(self) -> Region | None:
        for fr in reversed(self.stack):
            if fr["region"] is not None:
                return fr["region"]
        return None

    def _ensure_region(self) -> Region:
        r = self._current_region()
        if r is None:
            f = Func("<toplevel>", "public", [], [], [], Region())
            self.module.add(f)
            self.stack.append({"kind": "func", "region": f.body,
                               "owner": None, "pending_types": False})
            r = f.body
        return r

    def _last_op(self) -> Op | None:
        r = self._current_region()
        return r.ops[-1] if r is not None and r.ops else None

    def _push_region(self, owner: Op, region: Region,
                     pending: bool = False) -> None:
        owner.regions.append(region)
        self.stack.append({"kind": "region", "region": region,
                           "owner": owner, "pending_types": pending})

    def _pop_region(self) -> dict | None:
        if self.stack and self.stack[-1]["kind"] == "region":
            return self.stack.pop()
        return None

    # -------------------------------------------------------------- feed

    def feed(self, line: str, lineno: int) -> None:
        s = line.strip()
        if not s or s.startswith("//"):
            return
        if s.startswith("module"):
            self.stack.append({"kind": "module", "region": None,
                               "owner": None, "pending_types": False})
            return
        if s.startswith("func.func"):
            f = self._parse_func(s)
            if f is not None:
                self.module.add(f)
                self.stack.append({"kind": "func", "region": f.body,
                                   "owner": None, "pending_types": False})
            return
        if s.startswith("^"):  # ^bb0(%a: t, ...):
            r = self._current_region()
            if r is not None and not r.block_args:
                r.block_args = _BLOCK_ARG_RE.findall(s)
            return
        if s.startswith("cond {"):
            self._open_while_region("cond")
            return
        if s.startswith("} do {"):
            fr = self._pop_region()
            if fr is not None:
                self._open_while_region("do", owner=fr["owner"])
            return
        if s.startswith("}, {"):  # sibling generic region (case branch)
            fr = self._pop_region()
            if fr is not None:
                self._push_region(fr["owner"], Region(),
                                  pending=fr["pending_types"])
                # siblings were appended by _push_region; undo the extra
                # stack entry duplication is fine — same owner, new region
            return
        if s.startswith("reducer(") and s.endswith("{"):
            op = self._last_op()
            if op is not None:
                self._push_region(op, Region(
                    "reducer", block_args=_BLOCK_ARG_RE.findall(s)))
            return
        if s.startswith("})"):
            fr = self._pop_region()
            if fr is not None and fr["pending_types"] and " : " in s:
                self._apply_types(fr["owner"], s.rsplit(" : ", 1)[1])
            return
        if s == "}":
            if self.stack:
                self.stack.pop()
            return
        self._parse_op(s, lineno)

    def _open_while_region(self, label: str, owner: Op | None = None) -> None:
        op = owner if owner is not None else self._last_op()
        if op is None:
            return
        region = Region(label)
        # the while declares its carry on the op line; both regions see
        # the same %iterArg block args
        region.block_args = list(getattr(op, "_carry", []))
        self._push_region(op, region)

    # ----------------------------------------------------------- pieces

    def _parse_func(self, s: str) -> Func | None:
        m = _FUNC_RE.match(s)
        if not m:
            return None
        vis = m.group(1) or "private"
        name = m.group(2) or m.group(3)
        paren_open = s.index("(", m.end() - 1)
        paren_close = _balanced(s, paren_open, "(", ")")
        args = []
        for item in _split_commas(s[paren_open + 1:paren_close - 1]):
            am = re.match(r"(%[A-Za-z0-9_]+):\s*(.*)", item)
            if not am:
                continue
            rest = am.group(2).strip()
            attr = ""
            brace = rest.find("{")
            if brace >= 0:
                attr = rest[brace:]
                rest = rest[:brace].strip()
            args.append((am.group(1), rest, attr))
        result_types: list[str] = []
        result_infos: list[str] = []
        tail = s[paren_close:]
        arrow = tail.find("->")
        if arrow >= 0:
            res = tail[arrow + 2:].strip()
            if res.endswith("{"):
                res = res[:-1].strip()
            if res.startswith("("):
                res = res[1:_balanced(res, 0, "(", ")") - 1]
            for item in _split_commas(res):
                im = _RESULT_INFO_RE.search(item)
                result_infos.append(im.group(1) if im else "")
                brace = item.find("{")
                result_types.append(
                    (item[:brace] if brace >= 0 else item).strip())
        return Func(name, vis, args, result_types, result_infos, Region())

    def _parse_op(self, s: str, lineno: int) -> None:
        m = _RESULT_RE.match(s)
        result, n_results, rest = None, 0, s
        if m:
            result = m.group(1)
            n_results = int(m.group(2) or 1)
            rest = s[m.end():]
        mq = _OPNAME_QUOTED_RE.match(rest)
        if mq:
            name, tail = mq.group(1), rest[mq.end():]
        else:
            mb = _OPNAME_BARE_RE.match(rest)
            if mb:
                name, tail = mb.group(1), rest[mb.end():]
            elif rest.startswith("return"):
                name, tail = "func.return", rest[len("return"):]
            elif rest.startswith("call ") or rest.startswith("call@"):
                # bare `call @callee(...)` — GSPMD-partitioned modules
                # wrap the real computation this way; losing it would
                # silently empty the reachable graph
                name, tail = "func.call", rest[len("call"):]
            else:
                return  # unrecognized line — lenient by design
        op = Op(name=name, result=result, n_results=n_results, line=lineno)

        if name == "stablehlo.while":
            pairs = _ITER_RE.findall(rest)
            op.operands = [rhs for _lhs, rhs in pairs]
            if " : " in rest:
                types = _split_commas(rest.rsplit(" : ", 1)[1])
                op.result_types = types
                op._carry = list(zip([lhs for lhs, _ in pairs], types))
            self._ensure_region().ops.append(op)
            return

        opens_region = tail.rstrip().endswith("({")
        scan = tail
        if " : " in tail and not opens_region:
            scan, types = tail.rsplit(" : ", 1)
            if op.result is not None:
                self._apply_types(op, types)
        seen: set[str] = set()
        for v in _VALUE_RE.findall(scan):
            if v not in seen:
                seen.add(v)
                op.operands.append("%" + v)

        if name in ("func.call", "call"):
            cm = _CALLEE_RE.search(tail)
            if cm:
                op.callee = cm.group(1) or cm.group(2)
        if op.short == "custom_call":
            tm = _TARGET_NAME_RE.search(s)
            if tm:
                op.custom_target = tm.group(1)
            else:
                am = _CALLEE_RE.search(tail)
                if am:
                    op.custom_target = am.group(1) or am.group(2)

        self._ensure_region().ops.append(op)
        if opens_region:
            self._push_region(op, Region(), pending=True)

    def _apply_types(self, op: Op | None, types: str) -> None:
        if op is None:
            return
        types = types.strip()
        if "->" in types:
            types = types.rsplit("->", 1)[1].strip()
        if types.startswith("("):
            types = types[1:_balanced(types, 0, "(", ")") - 1]
            op.result_types = _split_commas(types)
        else:
            parts = _split_commas(types)
            # pretty form lists operand types with the result last
            # (select/or/add print one shared type)
            op.result_types = parts[-1:] if parts else []


def parse_module(text: str) -> Module:
    """Parse lowered StableHLO pretty text into a Module graph."""
    p = _Parser()
    for lineno, line in enumerate(text.splitlines(), 1):
        p.feed(line, lineno)
    return p.module
