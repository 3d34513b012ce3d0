"""Event records and bounded per-host event queues, struct-of-arrays.

The reference keeps one locked binary-heap priority queue per virtual host
(reference: src/main/core/scheduler/scheduler_policy_host_single.c:20-25,
src/main/utility/priority_queue.c) and defines a deterministic total order
over events as the tuple (time, dstHostID, srcHostID, per-src sequence)
(reference: src/main/core/work/event.c:110-153).

Here every host's queue is a fixed-capacity slot array; all hosts' queues
form [H, C] device arrays. Rows maintain a **sorted invariant**: slots are
ordered by the event key (time, src, seq) with empty slots
(time == TIME_INVALID) at the end. That choice is TPU-motivated: XLA
scatters with computed indices serialize on TPU (~ms for tens of
thousands of updates), while flat and row-wise `lax.sort`s are fast —
so push is implemented as "group incoming events by destination via one
flat sort, gather each host's contiguous run into a dense block, merge
the block into the row with one stable row sort" with no scatter
anywhere (see `queue_push_counted` and `merge_body`), and pop-min /
frontier extraction are free prefix reads of the sorted rows. Bounded
capacity drops the *largest*-key events on overflow and accounts them
in `drops` — or, when the queue carries a `SpillRing`
(shadow_tpu.runtime.pressure), lands them in the per-host overflow ring
instead so a host-side reservoir can harvest and re-insert them at
window boundaries (lossless pressure handling; see
docs/9-Queue-Pressure.md).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from shadow_tpu.core.phases import phase
from shadow_tpu.core.timebase import TIME_INVALID

# Number of i32 payload words carried by every event. The reference carries a
# Task closure pointer + argument pointers (src/main/core/work/task.c:13-41);
# we carry a fixed tuple of words whose meaning depends on `kind`.
N_ARGS = 6

# Common-round block width for queue_push (step 3 of its docstring): the
# [H, MERGE_W] incoming block bounds the width of the merge's row sort,
# so it is sized to cover every per-destination per-sweep count a
# steady-state workload produces (Poisson tails at typical loads put
# P(count > 24) below 1e-8 per host); rarer bursts take further exact
# rounds of the same width, as many as the push's largest count needs.
MERGE_W = 24

# Hot-region width for the row-wise merge (step 4): when every row's
# resident population plus the incoming block fits inside the first
# HOT_C columns, the merge touches only [H, HOT_C + W] and leaves the
# (all-empty) tail untouched — exact, because the sorted-rows invariant
# makes "population <= HOT_C" mean "all valid slots live in the first
# HOT_C columns". Large-capacity TCP simulations size C for worst-case
# bursts (a full receive window in flight) but hold far fewer resident
# events in steady state, so this bounds the dominant per-sweep merge
# cost by HOT_C, not C, per row. Rows past the bound fall back to the
# full-width merge (a lax.cond; no collectives inside).
HOT_C = 128


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Events:
    """A batch of event records (any leading shape).

    time: i64[...]  absolute sim time (TIME_INVALID = empty)
    dst:  i32[...]  destination (global) host id
    src:  i32[...]  source (global) host id
    seq:  i32[...]  per-source sequence number (tie-break)
    kind: i32[...]  handler index
    args: i32[..., N_ARGS] payload words
    """

    time: jax.Array
    dst: jax.Array
    src: jax.Array
    seq: jax.Array
    kind: jax.Array
    args: jax.Array

    @staticmethod
    def empty(shape, n_args: int = N_ARGS) -> "Events":
        shape = tuple(shape) if not isinstance(shape, int) else (shape,)
        i32 = jnp.int32
        return Events(
            time=jnp.full(shape, TIME_INVALID, jnp.int64),
            dst=jnp.zeros(shape, i32),
            src=jnp.zeros(shape, i32),
            seq=jnp.zeros(shape, i32),
            kind=jnp.zeros(shape, i32),
            args=jnp.zeros(shape + (n_args,), i32),
        )

    @property
    def shape(self):
        return self.time.shape

    def flatten(self) -> "Events":
        """Collapse the batch dims shared by all fields into one.

        args keeps its trailing N_ARGS dim; every other field is fully flat.
        """
        nb = self.time.ndim
        return jax.tree.map(
            lambda a: a.reshape((-1,) + a.shape[nb:]), self
        )

    def at(self, idx) -> "Events":
        return jax.tree.map(lambda a: a[idx], self)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SpillRing:
    """Per-host overflow ring: events evicted by `queue_push` land here
    instead of vanishing, in eviction order, for a host-side reservoir
    (shadow_tpu.runtime.pressure) to harvest at window boundaries.

    Same stop-at-full SoA discipline as obs.trace.TraceRing: `wr` counts
    events *offered* since the last reset; records land at min(wr, cap)
    so a full ring's writes fall into `slack` scratch columns (sized to
    the widest single eviction, the queue capacity) that the harvester
    never reads. Ring-overflow events are the only ones truly lost under
    spill, accounted in both `n_lost` and the queue's `drops`.

    Payload rides bit-packed exactly as inside `queue_push` (kind + args
    as i64 word pairs), so spilling adds no pack/unpack work to the merge.
    """

    time: jax.Array  # i64[H, cap + slack]
    srcseq: jax.Array  # i64[H, cap + slack] pack_srcseq(src, seq)
    pay: jax.Array  # i64[H, cap + slack, NW] packed kind+args words
    wr: jax.Array  # i32[H] events offered since last reset
    n_spilled: jax.Array  # i64[H] cumulative events evicted into the ring
    n_lost: jax.Array  # i64[H] cumulative events lost to ring overflow
    fill_hwm: jax.Array  # i32[H] high-water mark of queue fill

    @staticmethod
    def create(n_hosts: int, cap: int, slack: int, n_args: int = N_ARGS
               ) -> "SpillRing":
        nw = (1 + n_args + 1) // 2  # payload words, packed two per i64
        width = cap + slack
        return SpillRing(
            time=jnp.full((n_hosts, width), TIME_INVALID, jnp.int64),
            srcseq=jnp.zeros((n_hosts, width), jnp.int64),
            pay=jnp.zeros((n_hosts, width, nw), jnp.int64),
            wr=jnp.zeros((n_hosts,), jnp.int32),
            n_spilled=jnp.zeros((n_hosts,), jnp.int64),
            n_lost=jnp.zeros((n_hosts,), jnp.int64),
            fill_hwm=jnp.zeros((n_hosts,), jnp.int32),
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EventQueue:
    """All hosts' bounded event queues on one shard: [H, C] slot arrays.

    A slot is empty iff time == TIME_INVALID. `drops` counts events lost to
    queue overflow per host (the reference's queues are unbounded; we bound
    and account, in the spirit of its ObjectCounter leak accounting —
    reference: src/main/core/support/object_counter.c). i64: multi-hour
    campaigns overflow an i32 long before they finish.

    `spill` is None (zero pytree leaves — compiled program and checkpoint
    leaf layout identical to a spill-free build) unless the engine was
    configured with an overflow ring, in which case evictions land there
    instead of being counted as drops.
    """

    time: jax.Array  # i64[H, C]
    src: jax.Array  # i32[H, C]
    seq: jax.Array  # i32[H, C]
    kind: jax.Array  # i32[H, C]
    args: jax.Array  # i32[H, C, N_ARGS]
    drops: jax.Array  # i64[H]
    spill: Any = None  # SpillRing, or None when spill is off

    @staticmethod
    def create(n_hosts: int, capacity: int, n_args: int = N_ARGS,
               spill: int = 0) -> "EventQueue":
        i32 = jnp.int32
        return EventQueue(
            time=jnp.full((n_hosts, capacity), TIME_INVALID, jnp.int64),
            src=jnp.zeros((n_hosts, capacity), i32),
            seq=jnp.zeros((n_hosts, capacity), i32),
            kind=jnp.zeros((n_hosts, capacity), i32),
            args=jnp.zeros((n_hosts, capacity, n_args), i32),
            drops=jnp.zeros((n_hosts,), jnp.int64),
            # slack = capacity: every merge round evicts at most
            # w <= min(C, M) <= C events per host in one append
            spill=(
                SpillRing.create(n_hosts, spill, capacity, n_args)
                if spill > 0 else None
            ),
        )

    @property
    def n_hosts(self) -> int:
        return self.time.shape[0]

    @property
    def capacity(self) -> int:
        return self.time.shape[1]

    def valid(self) -> jax.Array:
        return self.time != TIME_INVALID

    def size(self) -> jax.Array:
        return jnp.sum(self.valid(), axis=1, dtype=jnp.int32)

    def min_time(self) -> jax.Array:
        """Earliest pending event time per host (TIME_INVALID if empty)."""
        return jnp.min(self.time, axis=1)


def pack_srcseq(src: jax.Array, seq: jax.Array) -> jax.Array:
    """Pack (src, seq) into one i64 preserving lexicographic order.

    Within one host's queue, dst is constant, so the reference's total order
    (time, dst, src, seq) (event.c:110-153) reduces to (time, src, seq);
    this packing lets a single compare/sort operand resolve the tie. seq is
    masked through u32 so a (never expected) negative value cannot
    sign-extend into the src bits.
    """
    return (src.astype(jnp.int64) << 32) | seq.astype(jnp.uint32).astype(jnp.int64)


def unpack_srcseq(p: jax.Array) -> tuple[jax.Array, jax.Array]:
    return (
        (p >> 32).astype(jnp.int32),
        (p & 0xFFFFFFFF).astype(jnp.uint32).astype(jnp.int32),
    )




def queue_pop(
    q: EventQueue, before: jax.Array, host_ids: jax.Array
) -> tuple[EventQueue, Events, jax.Array]:
    """Pop, per host, the minimum-(time,src,seq) event with time < `before`.

    Rows carry the sorted-by-key invariant (module docstring), so the
    minimum is column 0 and popping is a left shift of the popped rows —
    which *preserves* the invariant, keeping this safe to mix with the
    engine's prefix reads. (The engine itself drains frontiers in batch
    via `_drain_window`; this single-pop form serves tests and simple
    drivers.)

    Returns (queue', events[H], active[H]) where active[h] says host h
    popped a real event. Inactive rows contain garbage fields
    (time=TIME_INVALID).
    """
    active = (q.time[:, 0] < before) & (q.time[:, 0] != TIME_INVALID)

    ev = Events(
        time=jnp.where(active, q.time[:, 0], TIME_INVALID),
        dst=host_ids.astype(jnp.int32),
        src=q.src[:, 0],
        seq=q.seq[:, 0],
        kind=q.kind[:, 0],
        args=q.args[:, 0],
    )

    def shift(a, fill):
        pad = jnp.full_like(a[:, :1], fill)
        shifted = jnp.concatenate([a[:, 1:], pad], axis=1)
        m = active.reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.where(m, shifted, a)

    q2 = dataclasses.replace(
        q,
        time=shift(q.time, TIME_INVALID),
        src=shift(q.src, 0),
        seq=shift(q.seq, 0),
        kind=shift(q.kind, 0),
        args=shift(q.args, 0),
    )
    return q2, ev, active


def queue_push(
    q: EventQueue, ev: Events, mask: jax.Array, host0,
) -> EventQueue:
    """`queue_push_counted` without its overflow-round counters."""
    return queue_push_counted(q, ev, mask, host0)[0]


_I64MAX = jnp.iinfo(jnp.int64).max


def merge_body(qt, qss, qpay, st, sss, bpay, starts, cnt):
    """One merge round's densify + row sort (step 4 of
    `queue_push_counted`). Shapes: qt/qss [H, hc], qpay [H, hc, nw],
    st/sss [m] flat grouped keys, bpay [H, w, nw], starts/cnt [H].
    Returns (mt, mss, mpay) of width hc + w: each row of
    [resident | block] sorted stably by (time, srcseq), so ties keep
    resident-first order.

    One row-wise `lax.sort` does the merge: the TPU sorts rows cheaply,
    while a computed-index gather along the minor axis costs
    milliseconds per push.
    """
    h, hc = qt.shape
    w = bpay.shape[1]
    m = st.shape[0]

    # densify: group g's admitted events sit at flat positions
    # starts[g] .. starts[g]+cnt[g]-1 in key order; masked lanes become
    # fillers (i64max, i64max, 0) that sort after every resident empty
    lane = jnp.arange(w, dtype=jnp.int32)
    gidx = starts[:, None] + lane[None, :]
    okl = lane[None, :] < cnt[:, None]
    gsafe = jnp.minimum(gidx, m - 1)
    bt = jnp.where(okl, st[gsafe], _I64MAX)
    bss = jnp.where(okl, sss[gsafe], _I64MAX)

    # resident empties (anywhere in the row: the engine clears executed
    # prefixes by time alone) become canonical (INV, 0, 0), so they sort
    # after every valid event and before the fillers, in a fixed order
    inv = qt == TIME_INVALID
    qss = jnp.where(inv, 0, qss)
    qpay = jnp.where(inv[:, :, None], 0, qpay)

    cat = lambda a, b: jnp.concatenate([a, b], axis=1)
    out = jax.lax.sort(
        (cat(qt, bt), cat(qss, bss))
        + tuple(cat(qpay[..., i], bpay[..., i])
                for i in range(qpay.shape[-1])),
        dimension=1, num_keys=2, is_stable=True,
    )
    return out[0], out[1], jnp.stack(out[2:], axis=-1)


@phase("merge")
def queue_push_counted(
    q: EventQueue, ev: Events, mask: jax.Array, host0,
) -> tuple[EventQueue, jax.Array, jax.Array]:
    """Insert a flat batch of events [M] into their destination queues.

    Returns (queue, over_lanes, over_events): per host, i64[H], the
    lanes its row read in overflow rounds (the rounds past the first,
    see "Round structure" below) and the events it admitted in them.

    `host0` is the global id of this shard's first host; events whose dst
    falls outside [host0, host0 + H) are silently ignored (the caller routes
    cross-shard events via collectives before pushing). When a destination
    queue overflows its capacity, the *largest*-key events are dropped and
    counted in `drops` (the reference's heaps are unbounded; we bound and
    account — src/main/core/support/object_counter.c spirit) — unless the
    queue carries a SpillRing, in which case every evicted event lands in
    the ring (the merge leaves the evicted tail contiguous, so the
    capture is one vmapped dynamic_update_slice per field) and only
    ring-overflow events count as drops. With a ring attached the final
    round's admission width is not capped either: extra full-width rounds
    run under a while_loop until every rank is admitted, so no event can
    bypass the ring as an unmaterialized rank-overflow.

    Scatter-free algorithm (XLA scatters with computed indices serialize
    on TPU; everything here is one flat sort plus gathers, searchsorted,
    and a row sort — all budgeted by analysis/hlo_audit.py):

    1. One flat multi-key sort groups incoming events by destination in
       (time, src, seq) order. Grouping in key order means the per-row
       admission cap W admits each destination's *smallest*-key events —
       which events survive overflow then depends only on keys, never on
       batch composition (single-vs-sharded runs stay identical under
       overflow: "keep the C smallest" commutes with batch splits).
       Only the keys and an i32 position index ride the sort; payload
       words are gathered afterward through the sorted index, so wide
       payloads (network-stack models) never inflate the sort operand
       set. (Earlier revisions packed kind+args into extra i64 sort
       operands and derived counts from injected boundary markers plus
       a second recovery sort — profiled against this lowering, the
       marker machinery and payload operands together roughly double
       the flat-sort cost, and the jnp.searchsorted below lowers as a
       scatter-free fori/gather binary search that costs a rounding
       error next to the sort.)
    2. Per-destination run starts and counts come from ONE
       `jnp.searchsorted(sdst, arange(H + 1))` over the grouped
       destination keys: start[g] = bounds[g], count[g] =
       bounds[g + 1] - bounds[g]. Rejected events carry key H and fall
       past bounds[H], so no separate compaction pass is needed.
    3. Each merge round DENSIFIES its [H, W] incoming block by value
       gather — lane j of row g reads flat position start[g] + lo + j,
       masked to a canonical filler (time = i64max, srcseq = i64max,
       payload = 0) past the row's count. (Earlier revisions densified
       with a second flat sort over [incoming | H*W fillers]; the
       gather replaces the dominant sort of the whole push at ~1/300
       of its cost on the current bench target.)
    4. The block merges into the resident rows by ONE stable row-wise
       `lax.sort` over [resident | block], keyed on (time, srcseq), the
       packed payload words riding along as operands (`merge_body`).
       Resident empties, including the cleared prefix the engine leaves
       after executing a frontier, are made canonical first, so they
       sort after every valid event and before the block's fillers; ties
       resolve resident-first by stability. No computed-index gather
       runs here: on the TPU a row sort is cheap and a gather along the
       row is not.
    5. Truncating the merged row to capacity keeps the smallest keys;
       the cut tail plus the final round's rank overflow are counted as
       drops (or spill to the ring). The kept region's empty slots are
       all resident empties, made canonical (src = seq = kind = args =
       0) in step 4: the block's fillers sort after every one of the hc
       resident slots, so none reaches the kept region.

    Round structure: every round runs `merge_body` once. Round k
    admits ranks [lo, lo + w) of each destination's grouped run, where
    W1 = min(w_full, MERGE_W) and w_full = min(C, M).
    - Without a spill ring, one `lax.while_loop` runs rounds of width
      W1. The first always runs (it also restores the sorted invariant
      after the engine's prefix clear); further rounds run while some
      destination's count exceeds the ranks admitted, until w_full
      ranks are. A push whose largest count is n reads
      ceil(n / W1) x H x W1 lanes, sized by the push and not by C. A
      rank >= w_full >= C has C smaller incoming keys in its own row,
      so it is counted as a drop without being merged.
    - With a spill ring every rank must reach the ring: after a first
      round of width W1, the loop runs rounds of width w_full until
      every rank is admitted (the ring's slack equals C >= w_full, so
      each round's eviction tail fits one append).
    The split into rounds is exact, not approximate: the merge keeps
    the C smallest keys whatever round events arrive in, so one round
    vs several produces identical queues (an element dropped at an
    intermediate truncation has C smaller elements that persist to the
    end, so it would have been dropped regardless), and each dropped
    event is counted once, where it leaves the row. One loop body holds
    one round's temporaries; a `lax.switch` over rounds of several
    widths would hold each branch's apart and raise the window
    program's peak memory. over_lanes and over_events count the rounds
    after the first.

    Payload words (kind + args) ride bit-packed into i64 word pairs.
    """
    h, c = q.n_hosts, q.capacity
    m = ev.time.shape[0]
    a = q.args.shape[-1]
    i64max = jnp.iinfo(jnp.int64).max

    local = ev.dst - jnp.asarray(host0, jnp.int32)
    # sim times are non-negative ns by construction (the engine clamps
    # dt and latency); a negative-time event is invalid input and is
    # excluded here like an out-of-shard destination.
    ok = (
        mask & (local >= 0) & (local < h)
        & (ev.time >= 0) & (ev.time != TIME_INVALID)
    )

    pk, unpk = pack_srcseq, unpack_srcseq
    nw = 1 + a  # payload words per event

    def pack_words(words):  # list of i32[...] -> list of i64[...]
        out = []
        for i in range(0, len(words), 2):
            hi = words[i].astype(jnp.int64) << 32
            lo = (
                words[i + 1].astype(jnp.int64) & 0xFFFFFFFF
                if i + 1 < len(words)
                else 0
            )
            out.append(hi | lo)
        return out

    def unpack_words(packed, n):  # list of i64[...] -> n i32[...] words
        words = []
        for i, p in enumerate(packed):
            words.append((p >> 32).astype(jnp.int32))
            if 2 * i + 1 < n:
                words.append((p & 0xFFFFFFFF).astype(jnp.uint32).astype(jnp.int32))
        return words[:n]

    # -- 1. group incoming by destination in (time, src, seq) order;
    # rejected events key to H and group past every real destination
    dkey = jnp.where(ok, local, h)
    flat_idx = jnp.arange(m, dtype=jnp.int32)
    sdst, st, sss, sidx = jax.lax.sort(
        (dkey, ev.time, pk(ev.src, ev.seq), flat_idx), num_keys=3
    )

    # -- 2. per-destination run bounds in one searchsorted
    bounds = jnp.searchsorted(
        sdst, jnp.arange(h + 1, dtype=sdst.dtype), side="left"
    ).astype(jnp.int32)
    mpos = bounds[:h]
    count = bounds[1:] - mpos

    def merge_round(q, lo, w):
        """Admit rank in [lo, lo + w) into a [H, w] block, merge into the
        queue rows, truncate to capacity."""
        cnt_r = jnp.clip(count - lo, 0, w)
        starts = mpos + lo
        # -- 3. densify the block payload by gather through the sorted
        # position index (keys densify inside the merge body, which
        # recomputes the same lane mask)
        lane = jnp.arange(w, dtype=jnp.int32)
        gidx = starts[:, None] + lane[None, :]
        okl = lane[None, :] < cnt_r[:, None]
        oidx = sidx[jnp.minimum(gidx, m - 1)]
        bw = [jnp.where(okl, ev.kind[oidx], 0)] + [
            jnp.where(okl, ev.args[:, i][oidx], 0) for i in range(a)
        ]
        bpay = jnp.stack(pack_words(bw), axis=-1)  # [H, w, NW]

        def row_merge(q, hc):
            """Merge the incoming [H, w] block into the first `hc` queue
            columns and truncate back to hc; columns >= hc ride along
            untouched. Exact when every valid slot lives below hc (the
            hot-branch predicate guarantees it; hc == c is the general
            case, where the tail is empty by construction)."""
            qt = q.time[:, :hc]
            qss = pk(q.src[:, :hc], q.seq[:, :hc])
            qpay = jnp.stack(
                pack_words(
                    [q.kind[:, :hc]] + [q.args[:, :hc, i] for i in range(a)]
                ),
                axis=-1,
            )  # [H, hc, NW]
            # -- 4. densify + row sort (see step 4 above)
            mt, mss, mpay = merge_body(
                qt, qss, qpay, st, sss, bpay, starts, cnt_r
            )

            over = jnp.sum(
                mt[:, hc:] != TIME_INVALID, axis=1, dtype=jnp.int32
            )
            spill = q.spill
            if spill is None:
                drops_add = over.astype(jnp.int64)
            else:
                # the merged row keeps empties last, so the evicted
                # events sit contiguously at the FRONT of the [H, w]
                # tail: append the whole tail at min(wr, cap) and
                # advance the cursor by the valid count only — garbage
                # beyond it is overwritten by the next append or never
                # read (slack columns absorb full-ring writes)
                scap = spill.time.shape[1] - c  # slack == queue capacity
                sstarts = jnp.minimum(spill.wr, scap)
                put = jax.vmap(
                    lambda row, rec, s: jax.lax.dynamic_update_slice(
                        row, rec, (s,)
                    )
                )
                put2 = jax.vmap(
                    lambda row, rec, s: jax.lax.dynamic_update_slice(
                        row, rec, (s, jnp.int32(0))
                    )
                )
                wr2 = spill.wr + over
                lost = (
                    jnp.maximum(wr2 - scap, 0)
                    - jnp.maximum(spill.wr - scap, 0)
                ).astype(jnp.int64)
                spill = SpillRing(
                    time=put(spill.time, mt[:, hc:], sstarts),
                    srcseq=put(spill.srcseq, mss[:, hc:], sstarts),
                    pay=put2(spill.pay, mpay[:, hc:, :], sstarts),
                    wr=wr2,
                    n_spilled=spill.n_spilled + over.astype(jnp.int64),
                    n_lost=spill.n_lost + lost,
                    fill_hwm=spill.fill_hwm,
                )
                drops_add = lost
            # -- 5. truncate to capacity
            keep_t = mt[:, :hc]
            new_src, new_seq = unpk(mss[:, :hc])
            pay_k = mpay[:, :hc, :]
            words = unpack_words(
                [pay_k[:, :, i] for i in range(pay_k.shape[-1])], nw
            )
            glue = lambda head, tail: jnp.concatenate([head, tail], axis=1)
            new_time = glue(keep_t, q.time[:, hc:])
            if spill is not None:
                fill = jnp.sum(
                    new_time != TIME_INVALID, axis=1, dtype=jnp.int32
                )
                spill = dataclasses.replace(
                    spill, fill_hwm=jnp.maximum(spill.fill_hwm, fill)
                )
            return EventQueue(
                time=new_time,
                src=glue(new_src, q.src[:, hc:]),
                seq=glue(new_seq, q.seq[:, hc:]),
                kind=glue(words[0], q.kind[:, hc:]),
                args=jnp.concatenate(
                    [jnp.stack(words[1:], axis=-1), q.args[:, hc:]], axis=1
                ),
                drops=q.drops + drops_add,
                spill=spill,
            )

        if c < 2 * HOT_C:
            return row_merge(q, c)
        # hot-region fast path: all resident events in the first HOT_C
        # columns AND guaranteed to still fit after admitting w more.
        # (The engine's frontier prefix-clear leaves an INVALID prefix, so
        # residency is checked structurally — any valid slot at or past
        # HOT_C forces the full-width merge.)
        n_res = jnp.max(
            jnp.sum(q.time != TIME_INVALID, axis=1, dtype=jnp.int32)
        )
        tail_clear = ~jnp.any(q.time[:, HOT_C:] != TIME_INVALID)
        # the cleared prefix can push residents past HOT_C even when the
        # count fits; bound it by the worst-case prefix offset (<= the
        # count of leading INVALIDs is unknown here, so rely on the
        # structural tail check plus the post-merge fit guarantee)
        hot_ok = tail_clear & (n_res + w <= HOT_C)
        return jax.lax.cond(
            hot_ok,
            lambda q: row_merge(q, HOT_C),
            lambda q: row_merge(q, c),
            q,
        )

    w_full = min(c, m)
    w1 = min(w_full, MERGE_W)

    def rounds(q, lo, w, more):
        """Merge rounds of width w from rank lo while `more(lo)`."""
        return jax.lax.while_loop(
            lambda carry: more(carry[1]),
            lambda carry: (merge_round(carry[0], carry[1], w), carry[1] + w),
            (q, jnp.asarray(lo, jnp.int32)),
        )

    if q.spill is None:
        # the first round always runs: it also restores the sorted
        # invariant after the engine's prefix clear
        q, lo = rounds(q, 0, w1, lambda lo: (lo < w1) | (
            jnp.any(count > lo) & (lo < w_full)))
        # ranks >= lo were never merged: drops (see above)
        q = dataclasses.replace(
            q, drops=q.drops + jnp.maximum(count - lo, 0).astype(jnp.int64)
        )
    else:
        q, lo = rounds(merge_round(q, 0, w1), w1, w_full,
                       lambda lo: jnp.any(count > lo))
    # every row read lo - w1 lanes past the first round
    over = (lo - w1).astype(jnp.int64)
    admitted = jnp.clip(count - w1, 0, over).astype(jnp.int64)
    return q, jnp.full((h,), over), admitted
