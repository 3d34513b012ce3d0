"""From a profiler trace (`.xplane.pb`) to device time by engine phase.

The program runs each phase of its window loop under
`jax.named_scope(<phase>)` (`shadow_tpu/core/phases.py`). XLA keeps the
name stack as each op's `op_name`, and the profiler records it as the
`tf_op` stat of the op's metadata on the device plane, e.g.
`jit(<lambda>)/while/body/flush/cond/branch_0_fun/merge/gather:`.
`jax.profiler.ProfileData` does not expose event metadata, so the
metadata is read from the XSpace protobuf's wire format here; the event
times come from `ProfileData`, as in `trace_reduce`.

An op counts under the innermost component of its `tf_op` that is a
phase name, matched exactly (the last component, the primitive, is never
one); a `while` or `conditional` instruction's own time, told by the
HLO opcode in its name, under `control`; any other op under
`unscoped`. XLA gives a fusion its root instruction's metadata, so a
fusion that mixes phases counts under its root's. Self time follows
`trace_reduce.self_times` over the ops clipped to `trace_reduce`'s window
and is averaged over devices like `busy_s`, so the phases, `control` and
`unscoped` sum to `busy_s`.

    python3 -m benchmark.scope_reduce TRACE_DIR_OR_FILE   # one JSON line

(a capture made without the harness, e.g. by `--xprof`, is read whole:
its window is its first device op to its last).
"""

from __future__ import annotations

import json
import os
import re
import sys

from benchmark import trace_reduce as tr

# The program's phase names (shadow_tpu.core.phases.PHASES), copied so
# that the benchmark imports nothing of the program;
# benchmark/tests/test_scope_reduce.py checks the two agree.
PHASES = ("deliver", "pop", "select", "handler", "route", "append", "flush",
          "merge", "exchange", "barrier", "fault", "observe")
CONTROL = "control"
UNSCOPED = "unscoped"
KEYS = PHASES + (CONTROL, UNSCOPED)
_CONTROL_OP = re.compile(r"\s(?:while|conditional)\(")

# Shares of device busy time that name a layer: phases summed.
SHARES = {
    "merge_busy_pct": ("flush", "merge"),
    "staging_busy_pct": ("pop", "select", "append"),
    "handler_busy_pct": ("handler", "route"),
}

# XSpace wire-format field numbers (tsl/profiler/protobuf/xplane.proto)
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 3, 4, 5
_LINE_NAME, _LINE_EVENTS = 2, 4
_EVENT_MD_ID = 1
_MD_NAME, _MD_STATS = 2, 5
_STAT_MD_ID, _STAT_STR, _STAT_REF = 1, 5, 7


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of each field of one message: an int for a
    varint, a memoryview for bytes and submessages, None for fixed."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif kind in (1, 5):
            v, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, v


def _map_entry(buf):
    kv = dict(_fields(buf))
    return kv.get(1, 0), kv.get(2, b"")


def _label(name: str, tf_op: str) -> str:
    if _CONTROL_OP.search(name):
        return CONTROL
    for part in reversed(tf_op.split("/")[:-1]):
        if part in PHASES:
            return part
    return UNSCOPED


def _plane_labels(plane) -> tuple[str, list[list[str]]]:
    """A device plane's name and, per "XLA Ops" line, each event's label."""
    name, lines, ev_md, stat_names = "", [], {}, {}
    for f, v in _fields(plane):
        if f == _PLANE_NAME:
            name = bytes(v).decode()
        elif f == _PLANE_LINES:
            lines.append(v)
        elif f == _PLANE_EVENT_MD:
            k, md = _map_entry(v)
            ev_md[k] = md
        elif f == _PLANE_STAT_MD:
            k, md = _map_entry(v)
            stat_names[k] = bytes(dict(_fields(md)).get(_MD_NAME, b"")).decode()
    tf_op_id = next((k for k, n in stat_names.items() if n == "tf_op"), None)

    def label_of(md) -> str:
        op, tf_op = "", ""
        for f, v in _fields(md):
            if f == _MD_NAME:
                op = bytes(v).decode()
            elif f == _MD_STATS:
                st = dict(_fields(v))
                if st.get(_STAT_MD_ID) == tf_op_id:
                    tf_op = (bytes(st[_STAT_STR]).decode() if _STAT_STR in st
                             else stat_names.get(st.get(_STAT_REF), ""))
        return _label(op, tf_op)

    labels = {k: label_of(md) for k, md in ev_md.items()}
    out = []
    for line in lines:
        fl = list(_fields(line))
        if any(f == _LINE_NAME and bytes(v).decode() == tr.OPS_LINE
               for f, v in fl):
            out.append([labels.get(dict(_fields(v)).get(_EVENT_MD_ID, 0),
                                   UNSCOPED)
                        for f, v in fl if f == _LINE_EVENTS])
    return name, out


def read_ops(path: str) -> dict[str, list[tuple[str, int, int]]]:
    """Device op events labelled by phase, with times in ns as
    `trace_reduce.read_planes` reads them: {device: [(label, start, end)]}."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        space = memoryview(f.read())
    labels = {}
    for fnum, plane in _fields(space):
        if fnum == _SPACE_PLANES:
            name, per_line = _plane_labels(plane)
            if name.startswith("/device:") and per_line:
                labels[name] = per_line
    ops = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name not in labels:
            continue
        evs = []
        lines = [ln for ln in plane.lines if ln.name == tr.OPS_LINE]
        for ln, lab in zip(lines, labels[plane.name], strict=True):
            es = list(ln.events)
            if len(es) != len(lab):
                raise RuntimeError(f"{plane.name}: {len(es)} events, "
                                   f"{len(lab)} read from the wire")
            evs += [(lb, int(e.start_ns), int(e.end_ns))
                    for lb, e in zip(lab, es)]
        if evs:
            ops[plane.name] = evs
    return ops


def reduce(ops: dict, spans: list) -> dict | None:
    """Self seconds per phase, `control` and `unscoped`, averaged over
    devices; None where the trace holds no device op or no chunk."""
    chunks = [(s, e) for n, s, e in spans if n in ("chunk", "fetch")]
    if not ops or not chunks:
        return None
    lo = min(s for n, s, e in spans if n == "chunk")
    hi = max(e for s, e in chunks)
    total = dict.fromkeys(KEYS, 0)
    for evs in ops.values():
        clipped = [(lb, max(s, lo), min(e, hi)) for lb, s, e in evs
                   if min(e, hi) > max(s, lo)]
        for lb, ns in tr.self_times(clipped).items():
            total[lb] += ns
    return {k: v / len(ops) / 1e9 for k, v in total.items()}


def reduce_file(path: str) -> dict | None:
    """`trace_reduce.reduce`'s record with `scopes` added. A capture
    without the harness's `chunk` spans (`--xprof`) is read whole."""
    t_ops, spans = tr.read_planes(path)
    if t_ops and not any(n == "chunk" for n, _, _ in spans):
        evs = [ev for evs in t_ops.values() for ev in evs]
        spans = [("chunk", min(s for _, s, _ in evs),
                  max(e for _, _, e in evs))]
    out = tr.reduce(t_ops, spans)
    if out is not None:
        out["scopes"] = reduce(read_ops(path), spans)
    return out


def share(rec: dict, phases) -> float | None:
    """The phases' share of device busy time in the traced window, in
    percent; None where the record holds no scopes."""
    t = rec.get("trace") or {}
    scopes = t.get("scopes")
    if not scopes or not t.get("busy_s"):
        return None
    return 100.0 * sum(scopes[p] for p in phases) / t["busy_s"]


def main(argv=None) -> int:
    (arg,) = argv if argv is not None else sys.argv[1:]
    path = arg if os.path.isfile(arg) else tr.find(arg)
    out = reduce_file(path)
    if out is not None:
        rec = {"trace": out}
        out["shares_pct"] = {k: share(rec, v) for k, v in SHARES.items()}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
