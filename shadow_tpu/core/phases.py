"""Stable names for the window loop's phases on the device.

Each phase of the compiled window program runs under
`jax.named_scope(<phase>)`, so every XLA op carries its phase in the
`op_name` metadata of the compiled HLO and in the `tf_op` stat of a
profiler trace (`jit(...)/while/body/flush/cond/branch_0_fun/merge/
gather`). A scope is metadata only: the compiled instructions are the
same with or without it. Scopes nest as the code does; an op belongs to
the innermost phase on its name stack.

| Phase | Work |
|---|---|
| deliver | merging the in-flight cross-shard exchange buffer |
| pop | the frontier dump into staging, burst fold, queue-head guard |
| select | min-key selection, run/active masks, the frontier's key sort |
| handler | the vmapped handler call and the per-event accounting |
| route | per-source sequence numbers and emit routing |
| append | staging append and the virtual-CPU charge |
| flush | the staging row sort and the narrow/full push choice |
| merge | `queue_push`, its merge rounds |
| exchange | the all_to_all rounds of a sharded push |
| barrier | next-event time, drain flag, global reductions |
| fault | fault-schedule epoch transitions |
| observe | trace ring, StatPlane and pcap appends |

docs/8-Tracing-Profiling.md says how to read them in a capture.
"""

from __future__ import annotations

import jax

PHASES = ("deliver", "pop", "select", "handler", "route", "append", "flush",
          "merge", "exchange", "barrier", "fault", "observe")


def phase(name: str):
    """`jax.named_scope(name)` for a name in PHASES; a context manager
    that also works as a function decorator."""
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r}; phases are {PHASES}")
    return jax.named_scope(name)
