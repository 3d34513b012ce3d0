"""PCAP capture: device-side packet ring -> libpcap files.

The reference captures per-interface packets into .pcap files when a host
sets logpcap/pcapdir (reference: src/main/host/network_interface.c:337-373
_networkinterface_capturePacket; src/main/utility/pcap_writer.c writes the
global header + per-packet records with synthesized Ethernet/IP/TCP
headers and no payload bytes).

TPU-native redesign: packets never exist host-side, so capture is a
fixed-size **ring buffer in device state** ([H, R] struct-of-arrays).
Every KIND_PKT_ARRIVE handler appends one record — timestamp, src/dst
host, ports, proto/flags, length, seq/ack, and the queue verdict
(delivered / CoDel drop / tail drop; richer than the reference, which
cannot see drops in its capture). The CLI drains rings at heartbeat
boundaries and the writer synthesizes wire-format headers exactly like
pcap_writer.c — payload bytes are zero-filled metadata-only frames
(`incl_len` truncated at the headers, the standard snaplen convention).

Sequence numbers are in MSS-sized segments on device (transport/tcp.py);
the writer rescales them to byte offsets (seq * MSS) so wireshark-style
flow analysis lines up.
"""

from __future__ import annotations

import dataclasses
import struct
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from shadow_tpu.core.phases import phase

# record meta word layout ([H, R, 8] i32)
M_SRC = 0
M_DST = 1
M_SPORT = 2
M_DPORT = 3
M_META = 4  # proto | tcp flag bits | verdict << 16
M_LEN = 5
M_SEQ = 6
M_ACK = 7
N_META = 8

# Packet-lifecycle STAGE bitmask (the reference appends 21 PDS_* stage
# flags to every packet as it moves, packet.h:20-40,
# packet_addDeliveryStatus; this is the observable-stage subset of that
# lifecycle for the two-hop device pipeline). Rides the capture record's
# verdict byte, so a packet's path is reconstructible from its capture
# row — and from any standard pcap tool via the IP TOS field.
STG_ARRIVED = 1 << 0     # reached the destination host edge (PDS_RCV_INTERFACE_*)
STG_QUEUED = 1 << 1      # waited in the rx queue (standing sojourn > 0)
STG_DELIVERED = 1 << 2   # handed to the socket demux (PDS_RCV_SOCKET_*)
STG_AQM_DROP = 1 << 3    # CoDel control-law drop (PDS_RCV_INTERFACE_DROPPED)
STG_TAIL_DROP = 1 << 4   # rx-buffer tail drop
STG_RETX = 1 << 5        # sender stamped this a retransmission
STG_SENT = 1 << 6        # tx-side record (source host's own ring)

STAGE_NAMES = {
    STG_ARRIVED: "arrived", STG_QUEUED: "queued",
    STG_DELIVERED: "delivered", STG_AQM_DROP: "dropped_aqm",
    STG_TAIL_DROP: "dropped_tail", STG_RETX: "retransmitted",
    STG_SENT: "sent",
}

# legacy single-verdict aliases (round-2 records; still what the drop
# analysis keys on)
V_DELIVERED = STG_DELIVERED
V_AQM_DROP = STG_AQM_DROP
V_TAIL_DROP = STG_TAIL_DROP


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CaptureRing:
    """Per-host packet capture ring ([H]-leading; elementwise append)."""

    t: jax.Array  # i64[H, R] arrival sim time
    meta: jax.Array  # i32[H, R, N_META]
    wr: jax.Array  # i32[H] monotone write counter
    enabled: jax.Array  # bool[H]

    @staticmethod
    def create(enabled, ring: int = 1024) -> "CaptureRing":
        enabled = jnp.asarray(enabled, bool)
        h = enabled.shape[0]
        return CaptureRing(
            t=jnp.zeros((h, ring), jnp.int64),
            meta=jnp.zeros((h, ring, N_META), jnp.int32),
            wr=jnp.zeros((h,), jnp.int32),
            enabled=enabled,
        )

    @phase("observe")
    def append(self, now, src, dst, sport, dport, meta_word, length, seq,
               ack, verdict):
        """Append one record (scalar row context under vmap)."""
        r = self.t.shape[0]
        slot = self.wr % r
        on = self.enabled
        rec = jnp.stack([
            jnp.asarray(src, jnp.int32),
            jnp.asarray(dst, jnp.int32),
            jnp.asarray(sport, jnp.int32),
            jnp.asarray(dport, jnp.int32),
            jnp.asarray(meta_word, jnp.int32)
            | (jnp.asarray(verdict, jnp.int32) << 16),
            jnp.asarray(length, jnp.int32),
            jnp.asarray(seq, jnp.int32),
            jnp.asarray(ack, jnp.int32),
        ])
        return CaptureRing(
            t=self.t.at[slot].set(
                jnp.where(on, jnp.asarray(now, jnp.int64), self.t[slot])
            ),
            meta=self.meta.at[slot].set(
                jnp.where(on, rec, self.meta[slot])
            ),
            wr=self.wr + on.astype(jnp.int32),
            enabled=self.enabled,
        )


def _ip_of(host_id: int) -> bytes:
    """Deterministic fallback 10.x.y.z from the host id."""
    return bytes([10, (host_id >> 16) & 0xFF, (host_id >> 8) & 0xFF,
                  host_id & 0xFF])


class PcapWriter:
    """One host's capture file (pcap_writer.c format, LINKTYPE_ETHERNET)."""

    # our flag bits (transport/stack.py) -> wire TCP flag bits
    _FLAGMAP = ((1 << 2, 0x02), (1 << 3, 0x10), (1 << 4, 0x01),
                (1 << 5, 0x04))  # SYN, ACK, FIN, RST

    def __init__(self, path: str, ip_lookup=None, mss: int = 1434):
        self.f = open(path, "wb")
        self.ip_lookup = ip_lookup or _ip_of
        self.mss = mss
        # magic, version 2.4, tz 0, sigfigs 0, snaplen, LINKTYPE_ETHERNET
        self.f.write(struct.pack("<IHHiIII", 0xA1B2C3D4, 2, 4, 0, 0,
                                 65535, 1))

    def record(self, t_ns: int, src: int, dst: int, sport: int, dport: int,
               meta: int, length: int, seq: int, ack: int,
               verdict: int = 0) -> None:
        proto = meta & 0x3
        is_tcp = proto == 2  # sockets.PROTO_TCP
        wire_flags = 0
        for ours, theirs in self._FLAGMAP:
            if meta & ours:
                wire_flags |= theirs
        l4 = (
            struct.pack(
                ">HHIIBBHHH", sport & 0xFFFF, dport & 0xFFFF,
                (seq * self.mss) & 0xFFFFFFFF, (ack * self.mss) & 0xFFFFFFFF,
                5 << 4, wire_flags, 65535, 0, 0,
            )
            if is_tcp
            else struct.pack(">HHHH", sport & 0xFFFF, dport & 0xFFFF,
                             8 + length, 0)
        )
        ip_len = 20 + len(l4) + length
        # the lifecycle STAGE BITMASK (STG_* bits above) rides the IP
        # TOS/DSCP byte, so stage analysis works in any standard pcap
        # tool via ip.dsfield bit filters (e.g. delivered = bit 2)
        ip = struct.pack(
            ">BBHHHBBH4s4s", 0x45, verdict & 0xFF, ip_len & 0xFFFF, 0, 0,
            64, 6 if is_tcp else 17, 0, self.ip_lookup(src),
            self.ip_lookup(dst),
        )
        eth = (
            dst.to_bytes(6, "big", signed=False)
            + src.to_bytes(6, "big", signed=False)
        ) + b"\x08\x00"
        frame = eth + ip + l4  # headers only; payload is metadata
        orig = len(eth) + ip_len
        self.f.write(struct.pack("<IIII", t_ns // 10**9,
                                 (t_ns % 10**9) // 1000, len(frame), orig))
        self.f.write(frame)

    def close(self) -> None:
        self.f.close()


class CaptureDrain:
    """Incrementally drains a CaptureRing into per-host pcap files.

    Tracks each host's last-seen write counter; overrun records (ring
    wrapped between drains) are counted in `lost`."""

    def __init__(self, names, host_ids, pcap_dir: str, dns=None):
        import os

        os.makedirs(pcap_dir, exist_ok=True)
        self.lost = 0

        def lookup(gid: int) -> bytes:
            if dns is not None:
                addr = dns.address_of(gid)
                if addr is not None:
                    return addr.ip.to_bytes(4, "big")
            return _ip_of(gid)

        self.writers = {
            gid: PcapWriter(
                os.path.join(pcap_dir, f"{name}.pcap"), ip_lookup=lookup
            )
            for gid, name in zip(host_ids, names)
        }
        self.last_wr = {gid: 0 for gid in host_ids}
        # per-lifecycle-stage record counts across all drained rings
        # (surfaced by the CLI summary; the parse/plot tools read the
        # same classes from the capture files' TOS byte)
        self.stage_counts = {name: 0 for name in STAGE_NAMES.values()}

    @staticmethod
    def gather(cap: CaptureRing) -> dict:
        """Device-array refs for one drain (the heartbeat-harvest bundle
        embeds this so the pcap drain shares the heartbeat's one batched
        `jax.device_get`; hand the fetched copy to `ingest`)."""
        return {"t": cap.t, "meta": cap.meta, "wr": cap.wr}

    def drain(self, cap: CaptureRing) -> None:
        self.ingest(jax.device_get(self.gather(cap)))  # shadowlint: no-deadline=pcap drain; off the supervised loop

    def ingest(self, fetched: dict) -> None:
        """Host-side half of `drain`: decode a fetched (numpy) `gather`
        dict into the per-host pcap files. The ring is cursor-tracked
        (never reset on device), so ingesting the same snapshot twice is
        a no-op."""
        t = np.asarray(fetched["t"])
        meta = np.asarray(fetched["meta"])
        wr = np.asarray(fetched["wr"])
        r = t.shape[1]  # derive from the ring itself
        for gid, w in self.writers.items():
            new = int(wr[gid])
            start = self.last_wr[gid]
            if new - start > r:
                self.lost += new - start - r
                start = new - r
            idx = [(i % r) for i in range(start, new)]
            order = sorted(idx, key=lambda i: int(t[gid, i]))
            for i in order:
                m = meta[gid, i]
                stages = (int(m[M_META]) >> 16) & 0xFF
                for bit, name in STAGE_NAMES.items():
                    if stages & bit:
                        self.stage_counts[name] += 1
                src = int(m[M_SRC])
                if src < 0:
                    src = gid  # tx-side record: the ring's own host
                w.record(
                    int(t[gid, i]), src, int(m[M_DST]),
                    int(m[M_SPORT]), int(m[M_DPORT]),
                    int(m[M_META]) & 0xFFFF, int(m[M_LEN]),
                    int(m[M_SEQ]), int(m[M_ACK]),
                    verdict=stages,
                )
            self.last_wr[gid] = new

    def close(self) -> None:
        for w in self.writers.values():
            w.close()
