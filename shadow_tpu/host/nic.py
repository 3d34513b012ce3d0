"""NIC rate limiting + CoDel AQM as vectorized per-host state.

The reference models each NIC with token buckets refilled by scheduled
tasks every 1ms in both directions (reference:
src/main/host/network_interface.c:32-40,93-226,121-183), a qdisc that picks
the next sending socket (FIFO-by-priority or round-robin, :466-517), and an
upstream-ISP router running CoDel in front of the receive path
(src/main/routing/router_queue_codel.c:36-267).

TPU-native redesign — **virtual-clock rate limiting**: instead of refill
events and materialized packet queues, each NIC direction keeps a single
`free_at` timestamp: the sim time its serialization of previous packets
ends. A packet of B bytes offered at time t starts transmitting at
max(t, free_at) and finishes at start + B/rate; `free_at` advances to the
finish time. This is exactly the fluid limit of a 1ms-refill token bucket,
costs zero events (pure arithmetic in the packet's own handler), and
vectorizes over all hosts. The "queue" at the receive side is implicit —
it is the set of in-flight delivery events — and its sojourn time
(rx_start - arrival) is what CoDel's control law consumes.

Burst allowance: a real token bucket lets an idle NIC burst a bucket's
worth of bytes at line rate. We model this by letting `free_at` lag `now`
by up to `burst_ns` (bucket depth / rate): an idle NIC accumulates credit
capped at burst_ns, mirroring networkinterface_receivePackets' capped
bucket (network_interface.c:93-100).

State dataclasses hold [H]-leading arrays at rest; inside engine handlers
(which run under vmap) every leaf is the per-host scalar slice, so all
methods are written elementwise.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from shadow_tpu.core.timebase import MILLISECOND, SECOND

# CoDel control-law constants (router_queue_codel.c:36-49; RFC 8289).
CODEL_TARGET = 10 * MILLISECOND
CODEL_INTERVAL = 100 * MILLISECOND

# Wire overhead (definitions.h:176-188).
MTU = 1500
HEADER_UDP = 42
HEADER_TCP = 66


def kib_per_sec_to_bytes_per_ns(kib) -> np.ndarray:
    """Bandwidth conversion; GraphML bandwidths are KiB/s
    (docs/3.2-Network-Config.md). Host-side float64: a build-time
    constant must not depend on the device that builds it."""
    return np.asarray(kib, np.float64) * 1024.0 / SECOND


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class NIC:
    """One direction's virtual-clock rate limiter (elementwise methods)."""

    free_at: jax.Array  # i64 time the link is next free
    rate: jax.Array  # f32 bytes per ns
    burst_ns: jax.Array  # i64 max idle credit (bucket depth in time)
    pkts: jax.Array  # i64 packets admitted (tracker wire accounting)
    wire: jax.Array  # i64 wire bytes admitted (payload + headers)
    buf_bytes: jax.Array  # i64 drop-tail buffer bound (0 = unlimited)
    drops: jax.Array  # i64 packets tail-dropped at this NIC

    @staticmethod
    def create(bandwidth_kib, burst_bytes: int = 16 * 1024,
               buf_bytes=0) -> "NIC":
        # computed on the host: the TPU's emulated f64 division gave
        # burst_ns a different last place than the CPU's (chip_smoke
        # tor76, PR 21)
        rate = np.maximum(kib_per_sec_to_bytes_per_ns(bandwidth_kib),
                          1e-12).astype(np.float32)
        burst = (burst_bytes / rate.astype(np.float64)).astype(np.int64)
        rate, burst = jnp.asarray(rate), jnp.asarray(burst)
        z = jnp.zeros_like(burst)
        return NIC(
            free_at=z, rate=rate, burst_ns=burst, pkts=z, wire=z,
            buf_bytes=jnp.broadcast_to(
                jnp.asarray(buf_bytes, jnp.int64), burst.shape
            ),
            drops=z,
        )

    def backlog_bytes(self, t):
        """Bytes currently queued behind the virtual clock at time t (the
        implicit receive queue the reference bounds with interfacebuffer,
        options.c:132 'interface receive buffer')."""
        lag = jnp.maximum(self.free_at - jnp.asarray(t, jnp.int64), 0)
        return (lag.astype(jnp.float32) * self.rate).astype(jnp.int64)

    def admit(self, t, nbytes, unlimited=False):
        """Serialize `nbytes` starting no earlier than t.

        Returns (nic', start_time, finish_time). With `unlimited` (the
        reference's bootstrap mode, network_interface.c:432-434 /
        worker.c:445-453) the packet passes through instantly. Wire-level
        packet/byte counters ride along (the tracker's in/out byte-class
        splits, tracker.c:433-479 — header bytes = wire - payload).
        """
        t = jnp.asarray(t, jnp.int64)
        free = jnp.maximum(self.free_at, t - self.burst_ns)
        start = jnp.maximum(t, free)
        dur = (jnp.asarray(nbytes, jnp.float32) / self.rate).astype(jnp.int64)
        finish = start + jnp.maximum(dur, 1)
        start = jnp.where(unlimited, t, start)
        finish = jnp.where(unlimited, t, finish)
        new_free = jnp.where(unlimited, self.free_at, finish)
        return (
            dataclasses.replace(
                self,
                free_at=new_free,
                pkts=self.pkts + 1,
                wire=self.wire + jnp.asarray(nbytes, jnp.int64),
            ),
            start,
            finish,
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CoDel:
    """RFC-8289 CoDel controller state (elementwise methods).

    The drop law and mode machine mirror router_queue_codel.c:198-267:
    sojourn < target for any packet resets the first-above clock and exits
    drop mode; sojourn >= target continuously for `interval` enters drop
    mode; while dropping, packets are dropped at times
    drop_next += interval/sqrt(count).
    """

    dropping: jax.Array  # bool
    count: jax.Array  # i32 drops in the current dropping episode
    first_above: jax.Array  # i64 when sojourn first exceeded target (0 = not)
    drop_next: jax.Array  # i64 next scheduled drop time

    @staticmethod
    def create(n_hosts: int) -> "CoDel":
        return CoDel(
            dropping=jnp.zeros((n_hosts,), bool),
            count=jnp.zeros((n_hosts,), jnp.int32),
            first_above=jnp.zeros((n_hosts,), jnp.int64),
            drop_next=jnp.zeros((n_hosts,), jnp.int64),
        )

    def on_dequeue(self, now, sojourn):
        """Process one dequeue; returns (codel', drop: bool)."""
        now = jnp.asarray(now, jnp.int64)
        below = sojourn < CODEL_TARGET
        # first time above target: arm the interval clock
        first_above = jnp.where(
            below,
            jnp.int64(0),
            jnp.where(self.first_above == 0, now + CODEL_INTERVAL, self.first_above),
        )
        ok_to_drop = (~below) & (first_above != 0) & (now >= first_above)

        # a below-target packet ends any dropping episode
        dropping = self.dropping & ~below

        # entering drop state (router_queue_codel.c:230-253): if we were
        # dropping within the last interval, resume with a higher count so
        # the drop rate re-ramps quickly, else restart at 1
        enter = ok_to_drop & ~dropping
        resume = enter & (now - self.drop_next < CODEL_INTERVAL) & (self.count > 2)
        count_on_enter = jnp.where(resume, self.count - 2, jnp.int32(1))
        drop_next_on_enter = _control_law(now, count_on_enter)

        # while in drop state: drop when now >= drop_next, then reschedule
        in_drop = dropping & (now >= self.drop_next) & ok_to_drop
        count_in_drop = self.count + 1
        drop_next_in_drop = _control_law(self.drop_next, count_in_drop)

        drop = enter | in_drop
        new = CoDel(
            dropping=dropping | enter,
            count=jnp.where(
                enter, count_on_enter, jnp.where(in_drop, count_in_drop, self.count)
            ),
            first_above=first_above,
            drop_next=jnp.where(
                enter,
                drop_next_on_enter,
                jnp.where(in_drop, drop_next_in_drop, self.drop_next),
            ),
        )
        return new, drop


def _control_law(t, count):
    """drop_next = t + interval / sqrt(count) (router_queue_codel.c:198-206)."""
    return t + (
        CODEL_INTERVAL / jnp.sqrt(jnp.maximum(count, 1).astype(jnp.float32))
    ).astype(jnp.int64)
