"""Device-side event trace: a fixed-capacity per-host ring buffer.

The jitted window loop is a black box to every existing observability
layer (ShadowLogger, Tracker, parse_shadow are all host-side and
interval-aggregated): nobody can answer "which packet took that path"
without re-deriving it from pcaps. This module gives the engine a
struct-of-arrays trace ring it appends into *inside* the compiled drain
— one record per executed event and one per routed emit — that the CLI
drains to host at heartbeat boundaries alongside the pcap ring.

Design constraints the layout answers:

- **[H]-leading.** `parallel.mesh.state_specs` shards any state leaf
  whose leading dim equals the local host count; per-host rows make the
  ring shard (and checkpoint) like every other state array, and row
  index == gid on the host side, same as `utils.pcap.CaptureDrain`.
- **Stop-at-full, never wrap.** Records land at `min(wr, cap)`; the
  arrays carry `slack` scratch columns past `cap` (sized to the widest
  single append) so overflow writes fall into a zone the drain never
  reads. The first `cap` records per drain interval are exact and
  uncorrupted; `wr > cap` flags truncation and `wr - cap` counts the
  loss — corruption-free degradation instead of a wrapped ring whose
  oldest records silently vanish mid-interval.
- **No scatter.** Appends compact the masked records to a per-row
  prefix with the rank-matching one-hot idiom (`Engine._stage_append`)
  and land them with one vmapped `lax.dynamic_update_slice` per field.
- **Zero-cost when off.** `EngineState.trace` is `None` when
  `EngineConfig.trace == 0`: a leaf-free pytree subtree, so the
  compiled program, the checkpoint leaf list, and the state tree
  structure are bit-identical to a build that never heard of tracing
  (asserted by tests/test_trace_export.py).

Record schema (all [H, cap+slack], int32 unless noted):
  time  i64  sim time — execution time for EXEC, emission time otherwise
  src        originating host gid ((src, seq) is the global event id)
  dst        destination gid (executing host for EXEC rows)
  kind       handler/event kind index
  plen       payload-length arg (raw word; burst folds pack count<<24)
  seq        per-source sequence number
  op         record class: OP_EXEC / OP_SEND / OP_DROP / OP_FDROP, or
             the host-injected OP_SPILL / OP_REFILL pressure pair

Flow reconstruction: an OP_SEND row on the source host and the OP_EXEC
row of the same (src, seq) on the destination host are the two ends of
one network delivery — the exporter draws the Chrome flow arrow between
them.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from shadow_tpu.core.phases import phase

# record classes
OP_EXEC = 0   # event executed (row = executing host)
OP_SEND = 1   # non-local emit routed onto the wire (row = source host)
OP_DROP = 2   # non-local emit lost to a reliability roll
OP_FDROP = 3  # non-local emit lost to the fault overlay
# pressure path (host-side synthetic records, TraceDrain.inject): an
# event evicted from the bounded device queue into the spill ring, and
# its later re-insertion from the host reservoir — together they bound
# the event's off-device residency in the exported timeline
OP_SPILL = 4   # evicted to the spill ring (row = owning host)
OP_REFILL = 5  # re-seated from the reservoir (row = owning host)

OP_NAMES = {OP_EXEC: "exec", OP_SEND: "send", OP_DROP: "drop",
            OP_FDROP: "fault_drop", OP_SPILL: "spill",
            OP_REFILL: "refill"}

_FIELDS = ("time", "src", "dst", "kind", "plen", "seq", "op")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TraceRing:
    """Per-host event-trace ring ([H]-leading struct-of-arrays)."""

    time: jax.Array  # i64[H, cap + slack]
    src: jax.Array   # i32[H, cap + slack]
    dst: jax.Array   # i32[H, cap + slack]
    kind: jax.Array  # i32[H, cap + slack]
    plen: jax.Array  # i32[H, cap + slack]
    seq: jax.Array   # i32[H, cap + slack]
    op: jax.Array    # i32[H, cap + slack]
    wr: jax.Array    # i32[H] monotone count of records OFFERED (incl. lost)

    @staticmethod
    def create(n_hosts: int, cap: int, slack: int) -> "TraceRing":
        w = cap + slack
        z32 = jnp.zeros((n_hosts, w), jnp.int32)
        return TraceRing(
            time=jnp.zeros((n_hosts, w), jnp.int64),
            src=z32, dst=z32, kind=z32, plen=z32, seq=z32, op=z32,
            wr=jnp.zeros((n_hosts,), jnp.int32),
        )


@phase("observe")
def trace_append(ring: TraceRing, cap: int, *, time, src, dst, kind, plen,
                 seq, op, mask) -> TraceRing:
    """Append a masked [H, M] record batch into each host's ring.

    Valid records compact to a per-row prefix (lane order preserved —
    the rank one-hot of `Engine._stage_append`) and land at column
    `min(wr, cap)` via one vmapped `dynamic_update_slice` per field.
    Rows already at capacity write into the `[cap, cap+slack)` scratch
    zone, which the drain never reads; `wr` keeps counting so the host
    side knows exactly how many records were lost. All elementwise /
    reduction work — no scatter, no sort.
    """
    h, m = mask.shape
    slack = ring.time.shape[1] - cap
    assert m <= slack, (
        f"trace append width {m} exceeds ring slack {slack}; "
        "Engine._trace_slack must cover the widest append"
    )
    inc = mask.astype(jnp.int32)
    rank = jnp.cumsum(inc, axis=1) - inc  # dense index among valid lanes
    outpos = jnp.arange(m, dtype=jnp.int32)
    match = (
        (outpos[None, :, None] == rank[:, None, :]) & mask[:, None, :]
    )  # [H, M_out, M_in]; at most one True per out lane

    def compact(a):
        return jnp.sum(
            jnp.where(match, a[:, None, :], jnp.zeros((), a.dtype)),
            axis=2, dtype=a.dtype,
        )

    starts = jnp.minimum(ring.wr, jnp.int32(cap))
    put = jax.vmap(
        lambda row, rec, s: jax.lax.dynamic_update_slice(row, rec, (s,))
    )
    n_new = jnp.sum(inc, axis=1, dtype=jnp.int32)
    fields = {
        "time": jnp.asarray(time, jnp.int64),
        "src": jnp.asarray(src, jnp.int32),
        "dst": jnp.asarray(dst, jnp.int32),
        "kind": jnp.asarray(kind, jnp.int32),
        "plen": jnp.asarray(plen, jnp.int32),
        "seq": jnp.asarray(seq, jnp.int32),
        "op": jnp.asarray(op, jnp.int32),
    }
    new = {
        name: put(getattr(ring, name), compact(val), starts)
        for name, val in fields.items()
    }
    return TraceRing(wr=ring.wr + n_new, **new)


def reset_ring(ring: TraceRing) -> TraceRing:
    """Rewind the write counters; record slots are overwritten lazily."""
    return dataclasses.replace(ring, wr=jnp.zeros_like(ring.wr))


class TraceDrain:
    """Incrementally drains a TraceRing to host-side numpy records.

    Mirrors `utils.pcap.CaptureDrain`: one batched device_get per drain,
    per-host valid prefixes (`min(wr, cap)` slots), overflow counted in
    `lost` and flagged in `truncated` — never emitted as garbage rows.
    Accumulates record segments across the run for the final export and
    per-host per-op interval counts for the Tracker's exact drop
    attribution.
    """

    def __init__(self, cap: int, *, names=(), kind_names=()):
        self.cap = int(cap)
        self.names = list(names)
        self.kind_names = list(kind_names)
        self.lost = 0
        self.truncated = False
        self.n_records = 0
        self._segs: list[dict[str, np.ndarray]] = []
        self._interval: dict[str, np.ndarray] | None = None

    @staticmethod
    def gather(ring: TraceRing) -> dict:
        """Device-array refs for one drain (record columns + write
        cursor; nothing transferred). The heartbeat-harvest bundle
        embeds this dict so the trace drain shares the heartbeat's one
        batched `jax.device_get`; hand the fetched copy to `ingest`."""
        refs = {f: getattr(ring, f) for f in _FIELDS}
        refs["wr"] = ring.wr
        return refs

    def drain(self, ring: TraceRing) -> int:
        """Harvest every record written since the last reset; returns the
        number of records drained. Call `reset_ring` (or `drain_state`)
        after, or the next drain re-reads the same rows."""
        return self.ingest(jax.device_get(self.gather(ring)))  # shadowlint: no-deadline=trace drain; the caller overlaps it behind dispatch

    def ingest(self, fetched: dict) -> int:
        """Host-side half of `drain`: fold a fetched (numpy) `gather`
        dict into the record segments — safe to run while the device
        computes the next window segment (the overlapped CLI loop)."""
        cols = {f: np.asarray(fetched[f]) for f in _FIELDS}
        wr = np.asarray(fetched["wr"]).astype(np.int64)
        h, w = cols["time"].shape
        n = np.minimum(wr, self.cap)
        lost = np.maximum(wr - self.cap, 0)
        if lost.any():
            self.truncated = True
            self.lost += int(lost.sum())
        sel = np.arange(w)[None, :] < n[:, None]  # [H, W] valid prefixes
        owner = np.broadcast_to(np.arange(h, dtype=np.int32)[:, None],
                                (h, w))
        seg = {f: cols[f][sel] for f in _FIELDS}
        seg["owner"] = owner[sel].astype(np.int32)
        drained = int(seg["time"].shape[0])
        if drained:
            self._segs.append(seg)
            self.n_records += drained
        self._acc_interval(seg, lost, h)
        return drained

    def inject(self, *, time, src, dst, kind, plen, seq, op, owner,
               n_hosts: int) -> int:
        """Append host-side synthetic records (the pressure layer's
        OP_SPILL / OP_REFILL rows — those moments happen on the host, so
        the device ring never sees them). Records enter the same segment
        list and interval accounting as drained device records, and the
        deterministic sort in `records()` interleaves them byte-stably.
        `op` may be a scalar; array fields must share one length."""
        time = np.asarray(time, np.int64).reshape(-1)
        n = int(time.shape[0])
        if n == 0:
            return 0
        as32 = lambda a: np.broadcast_to(
            np.asarray(a, np.int32).reshape(-1), (n,)
        ).copy()
        seg = {
            "time": time, "src": as32(src), "dst": as32(dst),
            "kind": as32(kind), "plen": as32(plen), "seq": as32(seq),
            "op": as32(op), "owner": as32(owner),
        }
        self._segs.append(seg)
        self.n_records += n
        self._acc_interval(seg, np.zeros((n_hosts,), np.int64), n_hosts)
        return n

    def drain_state(self, state: Any) -> Any:
        """Drain `state.trace` and return the state with the ring reset
        (the host-side replacement keeps the jitted program oblivious)."""
        if state.trace is None:
            return state
        self.drain(state.trace)
        return dataclasses.replace(state, trace=reset_ring(state.trace))

    def _acc_interval(self, seg, lost, h):
        ops = seg["op"]
        own = seg["owner"]
        cur = {
            name: np.bincount(own[ops == code], minlength=h).astype(np.int64)
            for code, name in OP_NAMES.items()
        }
        cur["lost"] = lost.astype(np.int64)
        if self._interval is None:
            self._interval = cur
        else:
            for k_, v in cur.items():
                self._interval[k_] = self._interval[k_] + v

    def take_interval(self) -> dict[str, np.ndarray] | None:
        """Per-host per-op record counts since the previous take (exact,
        straight from the drained records — not interval-sampled counter
        deltas). None before the first drain."""
        out = self._interval
        self._interval = None
        return out

    def records(self) -> dict[str, np.ndarray]:
        """All drained records, globally sorted by the deterministic key
        (time, src, seq, op, dst) — (src, seq) names an event uniquely
        and an event contributes at most one row per op, so the order
        (and any export derived from it) is byte-stable across runs and
        shard counts."""
        keys = _FIELDS + ("owner",)
        if not self._segs:
            return {
                k: np.zeros(0, np.int64 if k == "time" else np.int32)
                for k in keys
            }
        cat = {k: np.concatenate([s[k] for s in self._segs])
               for k in keys}
        order = np.lexsort(
            (cat["dst"], cat["op"], cat["seq"], cat["src"], cat["time"])
        )
        return {k: v[order] for k, v in cat.items()}

    def save(self, path: str, *, profile: dict | None = None,
             extra_meta: dict | None = None) -> dict:
        """Write the accumulated trace as an .npz (record arrays + one
        JSON meta string) for `tools/export_trace.py`. Returns the meta
        dict."""
        recs = self.records()
        meta = {
            "names": self.names,
            "kind_names": self.kind_names,
            "op_names": [OP_NAMES[i] for i in sorted(OP_NAMES)],
            "cap": self.cap,
            "n_records": int(recs["time"].shape[0]),
            "lost": self.lost,
            "truncated": self.truncated,
            "profile": profile or {},
        }
        if extra_meta:
            meta.update(extra_meta)
        np.savez_compressed(
            path, meta=np.asarray(json.dumps(meta, sort_keys=True)), **recs
        )
        return meta


def load_trace(path: str) -> tuple[dict[str, np.ndarray], dict]:
    """Read a TraceDrain.save() file back as (records, meta)."""
    with np.load(path, allow_pickle=False) as data:
        meta = json.loads(str(data["meta"]))
        recs = {k: data[k] for k in data.files if k != "meta"}
    return recs, meta
