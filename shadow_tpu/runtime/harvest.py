"""Single-sync heartbeat harvest for the overlapped CLI run loop.

The pre-overlap run loop paid one device round-trip per consumer at
every segment boundary: the strict-overflow drop probe, the summary
scalars, the profiler's queue-fill reduction, and — at heartbeats — the
tracker counters, the trace ring, and the pcap ring each did their own
`jax.device_get`. Every one of those is a full host<->device sync that
idles the device.

This module folds all of it into ONE donating extraction jit per
segment:

    state' , bundle = extract(state)      # queued behind the segment
    ...                                   # host work overlaps the device
    fetched = fetch(bundle)               # the segment's ONLY sync
    consume(fetched, sim_ns)              # pure host-side formatting

`extract` runs on device right after the dispatched window segment: it
applies every reduction (sums, means) device-side, resets the trace
ring inside the same program, and returns the untouched simulation
state alongside a dict of small device arrays. The state input is
DONATED (single-device builds), so the pass-through costs no copies;
jit outputs never alias each other, so the
bundle stays fetchable after `state'` is donated into the *next*
segment — which is exactly the depth-1 dispatch-ahead the CLI loop
runs: dispatch segment k+1, then consume heartbeat k's fetched bundle
while the device works.

Consumers keep their legacy synchronous entry points
(`Tracker.heartbeat`, `TraceDrain.drain`, `CaptureDrain.drain`,
`state_summary`); this class is only the batching layer over their
gather/ingest halves.
"""

from __future__ import annotations

import dataclasses
from typing import Any


class HeartbeatHarvest:
    """Batches every segment-boundary device read into one transfer.

    `tracker` / `tdrain` / `pcap` are the CLI's observability consumers
    (any may be None); `sim` provides the pressure controller, the mesh
    (donation gate), and the state-ownership registry that makes
    donation safe (`Simulation._fresh_state`).
    """

    def __init__(self, sim, *, tracker=None, tdrain=None, pcap=None,
                 metrics=None):
        self.sim = sim
        self.tracker = tracker
        self.tdrain = tdrain
        self.pcap = pcap
        # truthy => embed the live-telemetry reductions
        # (obs.metrics.metrics_device_refs) in the extraction bundle.
        # Off, the extraction lowers byte-identically to pre-metrics —
        # the --metrics zero-cost pin.
        self.metrics = metrics
        self._jits: dict[bool, Any] = {}

    def rebind(self, sim) -> None:
        """Point at a rebuilt Simulation (the --overflow grow
        re-template); cached extraction jits close over the old engine
        and must be dropped."""
        self.sim = sim
        self._jits.clear()

    # -- device half -----------------------------------------------------

    def _build(self, full: bool):
        import jax
        import jax.numpy as jnp

        from shadow_tpu.core.timebase import TIME_INVALID

        sim = self.sim
        tracker, tdrain, pcap = self.tracker, self.tdrain, self.pcap
        lanes = int(getattr(sim, "lanes", 0) or 0)
        if lanes:
            # fleet path: the bundle carries [L]-valued per-lane summary
            # reductions (computed on device) through the SAME single
            # fetch. The per-scenario observability consumers are not
            # lane-aware; the fleet CLI runs without them.
            if (tracker is not None or tdrain is not None
                    or pcap is not None or self.metrics):
                raise ValueError(
                    "fleet harvest carries per-lane summaries only; "
                    "tracker/trace/pcap/metrics consumers are "
                    "per-scenario — attach them to solo runs"
                )
            from shadow_tpu.core.timebase import TIME_INVALID
            from shadow_tpu.runtime.fleet import lane_summary_refs

            def extract_fleet(state):
                q = state.queues
                bundle = {
                    "summary": lane_summary_refs(state),
                    "fill": jnp.mean(
                        (q.time != TIME_INVALID).astype(jnp.float32),
                        axis=tuple(range(1, q.time.ndim)),
                    ),
                }
                return state, bundle

            return jax.jit(extract_fleet, donate_argnums=0)
        has_trace = tdrain is not None and sim.state0.trace is not None
        has_pcap = pcap is not None and sim.state0.hosts.net.cap is not None
        has_ring = sim.state0.queues.spill is not None
        has_metrics = self.metrics is not None
        has_stats = sim.state0.splane is not None

        def extract(state):
            q = state.queues
            bundle: dict[str, Any] = {
                # mirrors core.engine.state_summary's keys/reductions
                "summary": {
                    "now_ns": state.now,
                    "windows": state.stats.n_windows,
                    "executed": state.stats.n_executed.sum(),
                    "sweeps": state.stats.n_sweeps,
                    "queue_drops": q.drops.sum(),
                },
                # obs.profiler.queue_fill's reduction
                "fill": jnp.mean(
                    (q.time != TIME_INVALID).astype(jnp.float32)
                ),
            }
            if has_ring:
                ring = q.spill
                bundle["summary"]["spilled"] = ring.n_spilled.sum()
                bundle["summary"]["spill_lost"] = ring.n_lost.sum()
                bundle["summary"]["fill_hwm"] = ring.fill_hwm.max()
            if sim.pressure is not None:
                bundle["pressure"] = sim.pressure.gather(state)
            if has_metrics:
                from shadow_tpu.obs.metrics import metrics_device_refs

                # a handful of extra global reductions riding the same
                # single fetch — the exporter's live counters
                bundle["metrics"] = metrics_device_refs(state)
            if has_stats:
                from shadow_tpu.obs.stats import stats_device_refs

                # global (host-summed) histogram reductions, computed on
                # device so sharded runs fetch exact totals through the
                # same single transfer as the rest of the bundle
                bundle["stats"] = stats_device_refs(state.splane)
            if full:
                if tracker is not None:
                    bundle["tracker"] = tracker.gather(state)
                if has_trace:
                    from shadow_tpu.obs.trace import TraceDrain, reset_ring

                    bundle["trace"] = TraceDrain.gather(state.trace)
                    # the ring reset rides the same program — the bundle
                    # keeps the pre-reset record columns
                    state = dataclasses.replace(
                        state, trace=reset_ring(state.trace)
                    )
                if has_pcap:
                    from shadow_tpu.utils.pcap import CaptureDrain

                    bundle["pcap"] = CaptureDrain.gather(
                        state.hosts.net.cap
                    )
            return state, bundle

        # donates as Simulation._wrap does: a sharded state is an
        # ordinary sharded jit array, safe to donate through the
        # pass-through
        return jax.jit(extract, donate_argnums=0)

    def extract(self, state, *, full: bool):
        """Queue the extraction behind whatever is in flight; returns
        (chained state, bundle of device refs). No sync happens here —
        `fetch` is the transfer."""
        jit = self._jits.get(full)
        if jit is None:
            jit = self._jits[full] = self._build(full)
        st = self.sim._fresh_state(state)
        out, bundle = jit(st)
        return self.sim._note_owned(out), bundle

    # -- host half -------------------------------------------------------

    @staticmethod
    def fetch(bundle) -> dict:
        """The segment's one batched device transfer."""
        import jax

        return jax.device_get(bundle)

    def lane_summaries_from(self, fetched: dict) -> list:
        """Fleet bundles only: per-lane summary dicts, each
        bit-identical to the solo run's `state_summary`."""
        from shadow_tpu.runtime.fleet import lane_summaries_from

        return lane_summaries_from(fetched["summary"])

    def summary_from(self, fetched: dict) -> dict:
        """Rebuild `Simulation.summary`'s dict from a fetched bundle
        (no state access, no extra sync)."""
        if getattr(self.sim, "lanes", 0):
            from shadow_tpu.runtime.fleet import aggregate_summary

            return aggregate_summary(fetched["summary"])
        out = {k: int(v) for k, v in fetched["summary"].items()}
        sim = self.sim
        if sim.profiler is not None:
            out["profile"] = sim.profiler.summary()
        if sim.pressure is not None and "pressure" in fetched:
            snap = sim.pressure.snapshot_from(fetched["pressure"])
            out["refilled"] = snap.get("refilled", 0)
            out["reservoir"] = snap.get("resident", 0)
            out["overdue"] = snap.get("overdue", 0)
        return out

    def consume(self, fetched: dict, sim_ns: int) -> None:
        """Feed a fetched FULL bundle to every observability consumer —
        pure host-side work, run while the device computes the next
        segment. Trace first: the tracker's [trace] section reads the
        drain's interval counts."""
        if self.tdrain is not None and "trace" in fetched:
            self.tdrain.ingest(fetched["trace"])
        if self.tracker is not None and "tracker" in fetched:
            self.tracker.heartbeat_from(fetched["tracker"], sim_ns)
        if self.tracker is not None and "stats" in fetched:
            self.tracker.stats_from(fetched["stats"], sim_ns)
        if self.pcap is not None and "pcap" in fetched:
            self.pcap.ingest(fetched["pcap"])
