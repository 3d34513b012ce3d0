"""StatPlane: device-side streaming histograms of sim-time behavior.

The telemetry plane (obs.metrics) exposes counters and gauges; the
trace ring (obs.trace) records raw events. Neither answers the
*distribution* questions ROADMAP items 1-2 hinge on: how long do
events wait between enqueue and execution, what does the send->exec
network latency look like, how many events does each host execute per
window (the lockstep occupancy that bounds vmap efficiency), how full
are the queues when the drain pops, and how long are the frontier
drain's same-time same-kind runs — the direct measurement of the
PR 13 TPU bet.

The StatPlane holds one fixed-bucket log2 histogram per family as
plain device arrays, updated inside the jitted window loop under the
engine's static `stats` flag across all three drain contracts. The
design rules are the engine's own:

- No computed-index scatter: bucket indexing is a power-of-two compare
  ladder and accumulation is a one-hot masked sum — pure VPU work.
- [H]-leading leaves: per-host counts shard exactly like
  `Stats.n_executed`, and the harvest bundle embeds the device-side
  `.sum(axis=0)` reduction so the global histogram is exact whether
  the run is sharded or not.
- Zero cost when off: `EngineState.splane` is None (a leaf-free
  pytree subtree) unless `EngineConfig.stats > 0`, so the compiled
  program, pytree structure, and checkpoint leaf list are
  byte-identical to a stats-free build (the trace/spill/xchg
  discipline, pinned by the shared `assert_zero_cost`).

Bucket scheme (NB = 64 buckets per family): values are non-negative
i64 sim quantities (ns deltas, counts). Bucket 0 holds v <= 0;
bucket i (1 <= i <= 62) holds 2^(i-1) <= v <= 2^i - 1 (upper bound
`le` = 2^i - 1); bucket 63 is the +Inf overflow (v >= 2^62). The
index is simply the bit length of v, computed as
`sum(v >= 2^i for i in 0..62)` — 63 elementwise compares, no gather.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from shadow_tpu.core.phases import phase

NB = 64  # buckets per family
_N_POWERS = NB - 1  # compare ladder 2^0 .. 2^62
_POWERS = tuple(1 << i for i in range(_N_POWERS))

# family key -> (OpenMetrics family name, help text). Order is the
# exposition/report order everywhere (registry render, [stats] rows).
FAMILIES = (
    ("wait", "event_wait_ns",
     "sim-time between event enqueue and execution (ns)"),
    ("net", "net_latency_ns",
     "send->exec network latency of delivered packets (ns)"),
    ("occ", "window_events_per_host",
     "events executed per host per window (hosts with work)"),
    ("qfill", "queue_fill_at_pop",
     "per-host event-queue fill at frontier dump"),
    ("runlen", "frontier_run_len",
     "frontier-drain run length (positions per round)"),
)
FAMILY_KEYS = tuple(k for k, _, _ in FAMILIES)

# `le` upper bound of each bucket: 0, 1, 3, 7, ..., 2^62 - 1, +Inf
BUCKET_LE = tuple((1 << i) - 1 for i in range(NB - 1)) + (float("inf"),)
BUCKET_LE_LABELS = tuple(
    "+Inf" if le == float("inf") else str(le) for le in BUCKET_LE
)

# heartbeat [stats] section: one cumulative row per beat. `hist` is the
# family's sparse bucket spec — "idx:count" pairs joined by "|" (empty
# when the family has no samples) — so parse/plot can rebuild the full
# distribution from the log alone.
STATS_HEADER = "t_s," + ",".join(
    f"{k}_count,{k}_sum,{k}_p50,{k}_p95,{k}_hist" for k in FAMILY_KEYS
)


def bucket_of(v: jax.Array) -> jax.Array:
    """Histogram bucket index of non-negative i64 values (elementwise;
    any shape). The index is bit_length(v) clipped into [0, NB-1]:
    63 broadcast compares against the power ladder, no gather."""
    powers = jnp.asarray(_POWERS, jnp.int64)
    return jnp.sum(
        v[..., None] >= powers, axis=-1, dtype=jnp.int32
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class StatPlane:
    """Per-shard histogram state: counts i64[H, NB] + value sum i64[H]
    per family. The total sample count of a family is the sum of its
    buckets (no separate counter leaf)."""

    wait_n: jax.Array  # i64[H, NB]
    wait_s: jax.Array  # i64[H]
    net_n: jax.Array
    net_s: jax.Array
    occ_n: jax.Array
    occ_s: jax.Array
    qfill_n: jax.Array
    qfill_s: jax.Array
    runlen_n: jax.Array
    runlen_s: jax.Array

    @staticmethod
    def create(n_hosts: int) -> "StatPlane":
        n = jnp.zeros((n_hosts, NB), jnp.int64)
        s = jnp.zeros((n_hosts,), jnp.int64)
        return StatPlane(n, s, n, s, n, s, n, s, n, s)

    @phase("observe")
    def observe(self, family: str, values: jax.Array,
                mask: jax.Array) -> "StatPlane":
        """Fold a batch of samples into one family's histogram.

        `values` is [H] or [H, ...] i64 (leading host axis), `mask` the
        same shape; masked-out lanes contribute nothing. One-hot
        accumulate — no scatter — so this lowers to the same op family
        as the engine's stats counters.
        """
        h = values.shape[0]
        v = values.reshape(h, -1).astype(jnp.int64)
        m = mask.reshape(h, -1)
        idx = bucket_of(v)  # [H, M]
        onehot = (
            idx[:, :, None] == jnp.arange(NB, dtype=jnp.int32)
        ) & m[:, :, None]
        cnts = getattr(self, family + "_n") + jnp.sum(
            onehot, axis=1, dtype=jnp.int64
        )
        sums = getattr(self, family + "_s") + jnp.sum(
            jnp.where(m, v, 0), axis=1, dtype=jnp.int64
        )
        return dataclasses.replace(
            self, **{family + "_n": cnts, family + "_s": sums}
        )


def stats_device_refs(splane: StatPlane) -> dict:
    """Device-array refs of the global (host-summed) histograms, for
    the harvest bundle: per family a [NB] bucket vector and a scalar
    value sum. The reduction runs ON DEVICE, so sharded runs fetch
    exact global totals through the same single `device_get` as the
    rest of the heartbeat bundle — zero extra round-trips."""
    return {
        **{f"{k}_bucket": getattr(splane, k + "_n").sum(axis=0)
           for k in FAMILY_KEYS},
        **{f"{k}_sum": getattr(splane, k + "_s").sum()
           for k in FAMILY_KEYS},
    }


def percentile(buckets: np.ndarray, q: float) -> float:
    """Approximate q-quantile (q in [0, 1]) from a per-bucket count
    vector [NB]: the `le` upper bound of the bucket where the
    cumulative count first reaches q * total. 0.0 when empty; the
    +Inf bucket reports 2^63 (a finite sentinel for arithmetic)."""
    b = np.asarray(buckets, np.int64)
    total = int(b.sum())
    if total <= 0:
        return 0.0
    cum = np.cumsum(b)
    i = int(np.searchsorted(cum, q * total))
    i = min(i, NB - 1)
    le = BUCKET_LE[i]
    return float(1 << 63) if le == float("inf") else float(le)


def summarize(fetched: dict) -> dict:
    """Host-side per-family summary of a fetched stats bundle
    (`stats_device_refs` after device_get): count, sum, mean, p50,
    p95, and the sparse bucket list [(idx, count), ...]."""
    out = {}
    for k in FAMILY_KEYS:
        b = np.asarray(fetched[f"{k}_bucket"], np.int64)
        s = int(np.asarray(fetched[f"{k}_sum"]))
        n = int(b.sum())
        nz = np.nonzero(b)[0]
        out[k] = {
            "count": n,
            "sum": s,
            "mean": (s / n) if n else 0.0,
            "p50": percentile(b, 0.50),
            "p95": percentile(b, 0.95),
            "buckets": [(int(i), int(b[i])) for i in nz],
        }
    return out


def stats_row(t_s: float, summary: dict) -> str:
    """One `[stats]` heartbeat CSV row (see STATS_HEADER) from a
    `summarize` result — cumulative totals, like the [metrics] row."""
    cells = [f"{t_s:.3f}"]
    for k in FAMILY_KEYS:
        f = summary[k]
        hist = "|".join(f"{i}:{c}" for i, c in f["buckets"])
        cells += [str(f["count"]), str(f["sum"]),
                  f"{f['p50']:.0f}", f"{f['p95']:.0f}", hist]
    return ",".join(cells)


def parse_hist(cell: str) -> np.ndarray:
    """Rebuild a [NB] bucket vector from a `{fam}_hist` CSV cell."""
    b = np.zeros((NB,), np.int64)
    if cell:
        for pair in cell.split("|"):
            i, c = pair.split(":")
            b[int(i)] = int(c)
    return b
