"""Window-phase wall-clock profiler for the un-jitted run-loop skeleton.

The compiled window step is opaque to Python, but everything around it
is not: building the simulation, the jitted step call (whose first
invocation is dominated by compile), the host-side drains (tracker
snapshot, pcap ring, trace ring), the process-tier shim pump, and
checkpoint writes all happen in plain Python. `WindowProfiler` times
those phases with `time.perf_counter()` context managers, keeps both
aggregates (count / total / max per phase) and a bounded span list (for
the Chrome wall-time tracks), and samples per-window occupancy —
events per sweep, queue fill, stall margin — from engine summary
deltas.

Every phase also opens a `jax.profiler.TraceAnnotation` of its name,
with or without a `WindowProfiler` (`host_phase`), so an XLA profiler
capture (`--xprof`) shows each phase on the host's timeline, on the same
clock as the device ops: a gap on the device lines up with the host
phase that held it up. The annotation costs next to nothing while no
capture runs.

Wall-clock numbers are nondeterministic by nature; everything this
module emits is either confined to the `"profile"` summary key or the
`[supervisor]`-style heartbeat fields, both of which
`tools/strip_log.py` strips so determinism diffs stay byte-stable with
`--profile` on.
"""

from __future__ import annotations

import time
from contextlib import contextmanager

# Canonical phase names (any string works; these are what the CLI and
# tiers use, and what the exporter turns into wall-time tracks):
#   build      — simulation construction + initial state
#   step       — the jitted window step/run call (first call = compile)
#   drain      — host-side drains: tracker snapshot, pcap ring, trace ring
#   pump       — process-tier shim syscall pump
#   checkpoint — checkpoint serialization + write
PHASES = ("build", "step", "drain", "pump", "checkpoint")


def host_phase(name: str):
    """The phase's span on the profiler's host timeline, untimed."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class WindowProfiler:
    """Accumulates per-phase wall time + per-window occupancy samples."""

    def __init__(self, max_spans: int = 50_000, max_occ: int = 50_000):
        self._t0 = time.perf_counter()
        self._agg: dict[str, dict] = {}
        self._max_spans = max_spans
        self._max_occ = max_occ
        self._spans_dropped = 0
        self.spans: list[tuple[str, float, float]] = []  # (phase, start, dur)
        self.occupancy: list[dict] = []
        self._last: dict | None = None

    @contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            with host_phase(name):
                yield
        finally:
            dt = time.perf_counter() - t0
            a = self._agg.setdefault(
                name, {"count": 0, "total_s": 0.0, "max_s": 0.0}
            )
            a["count"] += 1
            a["total_s"] += dt
            a["max_s"] = max(a["max_s"], dt)
            if len(self.spans) < self._max_spans:
                self.spans.append((name, t0 - self._t0, dt))
            else:
                self._spans_dropped += 1

    def observe(self, summary: dict, *, queue_fill: float | None = None,
                stall_margin_s: float | None = None) -> dict:
        """Record one occupancy sample from an engine `state_summary`
        dict (deltas against the previous observation)."""
        last = self._last or {}
        dw = summary["windows"] - last.get("windows", 0)
        de = summary["executed"] - last.get("executed", 0)
        ds = summary["sweeps"] - last.get("sweeps", 0)
        sample = {
            "now_ns": summary["now_ns"],
            "windows_d": dw,
            "events_d": de,
            "sweeps_d": ds,
            "events_per_sweep": (de / ds) if ds else 0.0,
            "queue_fill": queue_fill,
            "stall_margin_s": stall_margin_s,
        }
        if len(self.occupancy) < self._max_occ:
            self.occupancy.append(sample)
        self._last = dict(summary)
        return sample

    def summary(self) -> dict:
        """Aggregate view, merged under Simulation.summary's "profile"
        key (wall-clock: stripped by tools/strip_log.py)."""
        occ = self.occupancy
        n = len(occ)
        fills = [s["queue_fill"] for s in occ if s["queue_fill"] is not None]
        out = {
            "wall_s": time.perf_counter() - self._t0,
            "phases": {k: dict(v) for k, v in sorted(self._agg.items())},
            "occupancy": {
                "samples": n,
                "events_per_sweep": (
                    sum(s["events_per_sweep"] for s in occ) / n if n else 0.0
                ),
                "queue_fill_mean": (
                    sum(fills) / len(fills) if fills else None
                ),
            },
        }
        if self._spans_dropped:
            out["spans_dropped"] = self._spans_dropped
        return out

    def export(self) -> dict:
        """JSON-able payload for the trace .npz meta: aggregates plus the
        raw span list the exporter turns into per-phase wall tracks."""
        return {
            "phases": {k: dict(v) for k, v in sorted(self._agg.items())},
            "spans": [[n, round(s, 9), round(d, 9)]
                      for n, s, d in self.spans],
            "occupancy": self.occupancy,
        }


def queue_fill(state) -> float:
    """Fraction of event-queue slots holding a live event (one device
    reduction + one scalar transfer; safe at heartbeat cadence)."""
    import jax
    import jax.numpy as jnp

    from shadow_tpu.core.timebase import TIME_INVALID

    occ = jnp.mean(
        (state.queues.time != TIME_INVALID).astype(jnp.float32)
    )
    return float(jax.device_get(occ))  # shadowlint: no-deadline=profiler occupancy probe; off the hot loop
