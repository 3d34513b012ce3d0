"""ProcessTier: window-batched syscall exchange between the native
green-thread runtime and the device simulation.

The reference interleaves plugin execution with simulation events at
nanosecond granularity (+1ns epoll notify tasks, epoll.c:500-583 →
process_continue). A TPU cannot afford a host↔device round trip per
syscall, so this driver batches the exchange at conservative-window
granularity (SURVEY.md §7 step 6b): once per window it

  1. feeds completions (established connects, accepted children, timer
     wakes) into `shim_pump`, which runs every runnable green thread
     until all block again and returns their syscall requests;
  2. translates requests into command events injected into the device
     queues (executed by ProcTierModel's handler at the window open);
  3. steps the simulation one window;
  4. diffs the device socket/TCB tables: newly-established connections
     become completions for the next pump, per-socket delivered-byte
     growth moves real bytes between the native runtime's endpoint
     streams (shim_wire_deliver), consumed FINs become stream EOFs.

Deviation from the reference, documented for the parity check: process
reactions land at window boundaries (one lookahead of added latency per
blocking syscall round trip). Byte-stream order is exact on lossy paths
too: config-built simulations run the device TCP in strict in-order
delivery mode (transport/tcp.py in_order), so the per-socket delivered
counters this driver diffs only ever advance in stream order.
"""

from __future__ import annotations

import heapq
import os
import shlex
from typing import Any

import dataclasses
import jax
import jax.numpy as jnp
import numpy as np

from shadow_tpu.config import ShadowConfig, expand_hosts, resolve_path
from shadow_tpu.core.events import Events, queue_push
from shadow_tpu.core.timebase import SECOND
from shadow_tpu.host.sockets import EPHEMERAL_BASE
from shadow_tpu.proc.model import (
    CMD_CLOSE,
    CMD_CONNECT,
    CMD_LISTEN,
    CMD_SEND,
    CMD_SENDTO,
    CMD_UDP_BIND,
    CMD_UDP_CLOSE,
    UDP_RING,
    ProcTierModel,
)
from shadow_tpu.proc.native import (
    COMP_ACCEPT,
    COMP_CONNECT_FAIL,
    COMP_CONNECT_OK,
    COMP_WAKE,
    REQ_CLOSE,
    REQ_CONNECT,
    REQ_EXIT,
    REQ_LISTEN,
    COMP_TIMER,
    REQ_LOG,
    REQ_SEND,
    REQ_SENDTO,
    REQ_SLEEP,
    REQ_TIMER,
    REQ_UDP_BIND,
    ShimRuntime,
)
from shadow_tpu.sim import build_simulation
from shadow_tpu.transport.stack import N_PKT_ARGS
from shadow_tpu.transport.tcp import CLOSED, ESTABLISHED

# how long a closed UDP endpoint's source-attribution zombie may outlive
# its last unaccounted datagram: covers datagrams the network dropped
# outright (they never reach a ring, so the seq-set drain can't retire
# them) — far beyond any path latency the topology can express
_UDP_ZOMBIE_TTL_NS = 30 * SECOND


class ProcessTier:
    """Drives native plugin processes against a config-built simulation.

    Every <process> in the config whose plugin path is a .so exporting
    `shim_main` runs as a green thread in the native runtime; argv is
    [basename, *arguments.split()].
    """

    def __init__(self, cfg: ShadowConfig, *, seed: int = 0,
                 n_sockets: int = 8, capacity: int | None = None,
                 strict_overflow: bool = True, tcp_cc: str = "reno",
                 rx_queue: str = "codel", qdisc: str = "fifo",
                 interface_buffer: int = 1_024_000, mesh=None,
                 driver_slots: int | None = None, locality: bool = False,
                 trace: int = 0, profiler=None, overflow: str = "drop"):
        self.strict_overflow = strict_overflow
        self.overflow = overflow
        self.model = ProcTierModel()
        # hard slot-space split: device-created children live in
        # [0, child_limit), driver-owned sockets in [child_limit, S).
        # Without it, a recycled driver slot could be claimed by an
        # inbound SYN while the driver still holds it in its free list.
        if driver_slots is None:
            driver_slots = min(max(1, n_sockets // 2), n_sockets - 1)
        if not 0 < driver_slots < n_sockets:
            raise ValueError(
                f"driver_slots must be in (0, {n_sockets}), got {driver_slots}"
            )
        self._child_limit = n_sockets - driver_slots
        self.sim = build_simulation(
            cfg, seed=seed, n_sockets=n_sockets, capacity=capacity,
            app_model=self.model, tcp_cc=tcp_cc, rx_queue=rx_queue,
            qdisc=qdisc, interface_buffer=interface_buffer, mesh=mesh,
            tcp_child_slot_limit=self._child_limit, locality=locality,
            trace=trace, profiler=profiler, overflow=overflow,
        )
        self.rt = ShimRuntime()
        self.rt.set_seed(seed)  # roots plugin rand()/urandom determinism
        self.lost_stream_bytes = 0  # bytes unflushable at endpoint drop
        self.n_sockets = n_sockets
        # the interposer's getaddrinfo resolves against the runtime's DNS
        # table; push the whole (static) registry up front (dns.c role)
        for addr in self.sim.dns.entries():
            self.rt.dns_add(addr.name, addr.ip)
        self.logs: list[tuple[int, int, str]] = []  # (sim_ns, pid, msg)
        self.exit_codes: dict[int, int] = {}

        # (pid, fd) <-> (gid, slot) endpoint maps
        self.slot_of: dict[tuple[int, int], tuple[int, int]] = {}
        self.ep_of: dict[tuple[int, int], tuple[int, int]] = {}
        self.listen_ep: dict[tuple[int, int], tuple[int, int]] = {}
        self._listen_of_ep: dict[tuple[int, int], tuple[int, int]] = {}
        self.pending_conn: dict[tuple[int, int], tuple[int, int]] = {}
        self.wire: dict[tuple[int, int], tuple[int, int]] = {}  # slot<->slot
        # full-4-tuple wire index: (gid, lport, peer_gid, pport) -> (gid,
        # slot). The reference demuxes by the same 4-tuple key
        # (network_interface.c:375-455); matching on it makes parallel
        # same-port connects between one host pair unambiguous.
        self._four: dict[tuple[int, int, int, int], tuple[int, int]] = {}
        self._four_key: dict[tuple[int, int], tuple] = {}  # ep -> its key
        self._driver_owned: set[tuple[int, int]] = set()
        self._free_slots: dict[int, list[int]] = {}
        self.pid_host: dict[int, int] = {}
        self._next_slot: dict[int, int] = {}
        self._next_sport: dict[int, int] = {}
        self._next_fd: dict[int, int] = {}
        self._starts: list[tuple[int, int]] = []  # (t_ns, pid) heap
        self._wakes: list[tuple[int, int, int]] = []  # (t_ns, pid, gen)
        # timerfd arms: (deadline_ns, pid, fd, interval_ns, gen) heap;
        # _timer_gen holds each fd's current arm generation so re-armed
        # or closed timers' stale entries retire on pop
        self._timers: list[tuple[int, int, int, int, int]] = []
        self._timer_gen: dict[tuple[int, int], int] = {}
        self._pending_comps: list[tuple] = []
        self._push_jit = jax.jit(queue_push, static_argnames=())

        # per-process stoptime heap ((stop_ns, pid); the reference stops
        # each plugin individually, configuration.h:38-102 + process_stop)
        self._stops: list[tuple[int, int]] = []
        # per-host process specs, kept for fault restarts: a host coming
        # back up respawns these with fresh state (new pids, empty fds)
        self._proc_spec: dict[int, list[tuple]] = {}
        # locality may have renumbered gids; map hosts by NAME
        gid_of = {name: g for g, name in enumerate(self.sim.names)}
        for h in expand_hosts(cfg):
            gid = gid_of.get(h.name, h.gid)
            for p in h.spec.processes:
                spec = cfg.plugin_by_id(p.plugin)
                path = resolve_path(spec.path, cfg.base_dir) if spec else p.plugin
                if not (path.endswith(".so") and os.path.exists(path)):
                    raise ValueError(
                        "the process tier runs native plugins only: "
                        f"plugin {p.plugin!r} resolves to {path!r}, which "
                        "is not an existing .so — configs cannot mix "
                        "native plugins with modeled ones yet"
                    )
                argv = [os.path.basename(path)] + shlex.split(p.arguments)
                pid = self.rt.spawn(gid, path, argv)
                self.rt.set_host_name(pid, h.name)
                self.pid_host[pid] = gid
                heapq.heappush(self._starts, (int(p.starttime * SECOND), pid))
                if p.stoptime:
                    heapq.heappush(
                        self._stops, (int(p.stoptime * SECOND), pid)
                    )
                self._proc_spec.setdefault(gid, []).append((
                    path, argv, h.name, int(p.starttime * SECOND),
                    int(p.stoptime * SECOND) if p.stoptime else None,
                ))

        # UDP endpoint bookkeeping (udp.c:26-60 association realized as
        # driver maps): (pid, fd) -> (gid, slot, port) for runtime
        # endpoints, the (gid, port) demux index for routing delivery
        # records back to senders and receivers, and each host's
        # virtual IP for recvfrom addresses
        self.udp_eps: dict[tuple[int, int], tuple[int, int, int]] = {}
        self.udp_port: dict[tuple[int, int], tuple[int, int]] = {}
        self._udp_used = False
        self._gid_ip: dict[int, int] = {
            a.host_id: a.ip for a in self.sim.dns.entries()
        }

        # device arrays may be shape-bucketed wider than the real host
        # count; the observe mirrors must match the DEVICE row dimension
        # (padded rows stay inert/zero)
        h_n = (self.sim.engine.cfg.n_hosts
               * self.sim.engine.cfg.n_shards)
        self._prev_udp_cnt = np.zeros((h_n,), np.int32)
        # (gid, port) -> (pid, fd) for EXITED senders whose in-flight
        # datagrams still need payload attribution at the ring drain.
        # _udp_outstanding holds each source endpoint's sent-but-not-yet-
        # drained datagram seqs: a zombie is pruned the moment its set
        # empties (the drain cursor passed its last in-flight datagram),
        # with _udp_zombie_deadline as the TTL backstop for datagrams the
        # network dropped (those never reach any ring)
        self._udp_src_zombies: dict[tuple[int, int], tuple[int, int]] = {}
        self._udp_outstanding: dict[tuple[int, int], set[int]] = {}
        self._udp_zombie_deadline: dict[tuple[int, int], int] = {}
        self._prev_rx = np.zeros((h_n, n_sockets), np.int64)
        self._prev_fin = np.zeros((h_n, n_sockets), bool)
        # vectorized-observe state: endpoint membership, per-slot owed
        # bytes, and the device TCB's slot-incarnation counter (conn_gen)
        # for robust reuse detection
        self._known = np.zeros((h_n, n_sockets), bool)
        self._undeliv = np.zeros((h_n, n_sockets), np.int64)
        self._prev_gen = np.zeros((h_n, n_sockets), np.int32)

    # ------------------------------------------------------------- helpers
    def _alloc_slot(self, gid: int) -> int:
        # driver-owned slots grow downward from the top (TCP child
        # sockets allocate first-free from 0 upward, so the ends never
        # collide); slots freed by completed close handshakes recycle
        # first, so connection churn no longer exhausts the table
        free = self._free_slots.get(gid)
        if free:
            return free.pop()
        s = self._next_slot.get(gid, self.n_sockets - 1)
        self._next_slot[gid] = s - 1
        if s < self._child_limit:
            raise RuntimeError(
                f"host {gid}: out of driver socket slots (reserved "
                f"[{self._child_limit}, {self.n_sockets}); raise "
                "n_sockets or driver_slots)"
            )
        return s

    def _close_udp_ep(self, key, rows, now: int) -> None:
        """Tear down one UDP endpoint (exit/close/stoptime-kill/crash
        share this): free the driver slot, clear the DESTINATION demux
        row — arrivals addressed to it now drop, kernel semantics — but
        keep SOURCE attribution for datagrams already sent: the ring
        drain needs (pid, fd) to locate the payload stash (the runtime
        keeps fds entries until shim_free), and dropping it lost a
        server's final reply when it echoed then returned from main().
        A zombie is only created while datagrams are actually
        outstanding, and the ring drain prunes it the moment its last
        one is accounted for — churny UDP workloads no longer grow this
        map without bound."""
        gid, slot, port = self.udp_eps.pop(key)
        self.udp_port.pop((gid, port), None)
        src_key = (gid, port)
        if self._udp_outstanding.get(src_key):
            self._udp_src_zombies[src_key] = key
            self._udp_zombie_deadline[src_key] = now + _UDP_ZOMBIE_TTL_NS
        else:
            self._udp_outstanding.pop(src_key, None)
        self._free_slots.setdefault(gid, []).append(slot)
        rows.append((gid, [CMD_UDP_CLOSE, slot]))

    def _register_ep(self, gid: int, slot: int, pid: int, fd: int,
                     driver_owned: bool) -> None:
        self.ep_of[(gid, slot)] = (pid, fd)
        self.slot_of[(pid, fd)] = (gid, slot)
        self._known[gid, slot] = True
        self._undeliv[gid, slot] = 0
        self._prev_fin[gid, slot] = False  # fresh incarnation baseline
        if driver_owned:
            self._driver_owned.add((gid, slot))

    def _drop_ep(self, gid: int, slot: int, *, recycle: bool,
                 surface_eof: bool = False) -> None:
        """Forget one endpoint's mappings, flushing owed bytes in BOTH
        wire directions first (the endpoints' byte streams outlive the
        slot mapping in the native runtime, so a final flush here keeps
        a peer from being stranded mid-stream). Optionally recycles a
        driver-owned slot and surfaces EOF to the dropped side."""
        key = (gid, slot)
        ep = self.ep_of.pop(key, None)
        peer = self.wire.pop(key, None)
        if peer is not None:
            self.wire.pop(peer, None)
        if ep is not None:
            pid, fd = ep
            if peer is not None and peer in self.ep_of:
                ppid, pfd = self.ep_of[peer]
                # 1. bytes this reader is still owed from its peer
                owed = int(self._undeliv[key])
                if owed:
                    moved = self.rt.wire_deliver(ppid, pfd, pid, fd, owed)
                    self._undeliv[key] -= max(moved, 0)
                # 2. bytes the peer is still owed from this endpoint —
                # after this drop nothing would route them
                powed = int(self._undeliv[peer])
                if powed:
                    moved = self.rt.wire_deliver(pid, fd, ppid, pfd, powed)
                    self._undeliv[peer] -= max(moved, 0)
            if self._undeliv[key]:
                self.lost_stream_bytes += int(self._undeliv[key])
            if surface_eof:
                self.rt.wire_fin(pid, fd)
            self.slot_of.pop(ep, None)
        fk = self._four_key.pop(key, None)
        if fk is not None:
            self._four.pop(fk, None)
        self.pending_conn.pop(key, None)
        self._known[gid, slot] = False
        self._undeliv[gid, slot] = 0
        if key in self._driver_owned:
            self._driver_owned.discard(key)
            if recycle:
                self._free_slots.setdefault(gid, []).append(slot)

    # ------------------------------------------------------------- faults
    def _fault_down(self, gid: int, rows, now: int) -> None:
        """A scheduled crash took the host down: kill its native
        processes and drop the driver's endpoint bookkeeping outright.
        The device side wipes the host's queue and re-templates its rows
        at the fault epoch, so there is no close handshake to observe —
        surviving peers tear down through the real retransmit/RST paths
        instead, exactly as against a real dead box."""
        for pid, g in list(self.pid_host.items()):
            if g != gid or pid in self.exit_codes:
                continue
            self.rt.kill(pid, 0)
            self.exit_codes[pid] = 0
            for key in [k for k in self._timer_gen if k[0] == pid]:
                self._timer_gen[key] += 1
            self._wakes = [w for w in self._wakes if w[1] != pid]
            heapq.heapify(self._wakes)
            # a process the crash beat to its starttime never boots this
            # incarnation (it comes back with the host, if it restarts)
            self._starts = [s for s in self._starts if s[1] != pid]
            heapq.heapify(self._starts)
        for key in [k for k in list(self.ep_of) if k[0] == gid]:
            self._drop_ep(*key, recycle=True)
        for gp in [k for k in self.listen_ep if k[0] == gid]:
            ep = self.listen_ep.pop(gp)
            self._listen_of_ep.pop(ep, None)
        for key in [k for k, v in self.udp_eps.items() if v[0] == gid]:
            self._close_udp_ep(key, rows, now)

    def _fault_up(self, gid: int, now: int) -> None:
        """The host rebooted: respawn its configured processes with
        fresh state — new pids, empty fd tables, starttime re-applied
        relative to boot (the operator-restarts-the-daemon analog)."""
        for path, argv, name, start_ns, stop_ns in self._proc_spec.get(
                gid, ()):
            if stop_ns is not None and stop_ns <= now:
                continue  # its configured lifetime already ended
            pid = self.rt.spawn(gid, path, argv)
            self.rt.set_host_name(pid, name)
            self.pid_host[pid] = gid
            heapq.heappush(self._starts, (max(start_ns, now), pid))
            if stop_ns is not None:
                heapq.heappush(self._stops, (stop_ns, pid))

    def _wire_try_pair(self, gid: int, slot: int, lport: int,
                       peer_gid: int, pport: int) -> None:
        """Index an endpoint by its connection 4-tuple and pair it with
        the reverse tuple's endpoint when that side exists."""
        key = (gid, slot)
        fk = (gid, lport, peer_gid, pport)
        self._four[fk] = key
        self._four_key[key] = fk
        other = self._four.get((peer_gid, pport, gid, lport))
        if other is not None and other != key:
            self.wire[key] = other
            self.wire[other] = key

    def _alloc_sport(self, gid: int) -> int:
        p = self._next_sport.get(gid, EPHEMERAL_BASE + 4096)
        self._next_sport[gid] = p + 1
        return p

    def _alloc_fd(self, pid: int) -> int:
        # driver-assigned child fds live in the 2'000'000+ band, disjoint
        # from the runtime's own 1'000'000+ allocations (shim_runtime.cpp
        # kFirstFd) — fd numbers stay globally unique
        f = self._next_fd.get(pid, 2_000_000)
        self._next_fd[pid] = f + 1
        return f

    # ---------------------------------------------------------- translate
    def _translate(self, reqs, now: int) -> list[tuple[int, list[int]]]:
        rows: list[tuple[int, list[int]]] = []
        for r in reqs:
            pid, fd = int(r.pid), int(r.fd)
            gid = self.pid_host[pid]
            if r.op == REQ_LISTEN:
                slot = self._alloc_slot(gid)
                self._register_ep(gid, slot, pid, fd, driver_owned=True)
                self.listen_ep[(gid, int(r.port))] = (pid, fd)
                self._listen_of_ep[(pid, fd)] = (gid, int(r.port))
                rows.append((gid, [CMD_LISTEN, slot, int(r.port)]))
            elif r.op == REQ_CONNECT:
                name = r.name.decode()
                if name:
                    addr = self.sim.dns.resolve_name(name)
                elif int(r.a1) in (0, 0x7F000001):
                    # wildcard/loopback: this host (the reference's
                    # single-process tests connect to INADDR_LOOPBACK;
                    # the device routes it over the topology self-loop)
                    addr = self.sim.dns.resolve_name(
                        self.sim.names[gid]
                    )
                else:
                    # interposer form: a1 carries the virtual IPv4 from
                    # connect(sockaddr_in) (host order)
                    addr = self.sim.dns.resolve_ip(int(r.a1))
                if addr is None:
                    self._pending_comps.append(
                        (pid, COMP_CONNECT_FAIL, fd, 0)
                    )
                    continue
                slot = self._alloc_slot(gid)
                sport = self._alloc_sport(gid)
                self._register_ep(gid, slot, pid, fd, driver_owned=True)
                self.pending_conn[(gid, slot)] = (pid, fd)
                self._wire_try_pair(gid, slot, sport, addr.host_id,
                                    int(r.port))
                rows.append(
                    (gid, [CMD_CONNECT, slot, sport, addr.host_id,
                           int(r.port)])
                )
            elif r.op == REQ_SEND:
                key = (pid, fd)
                if key in self.slot_of:
                    gid, slot = self.slot_of[key]
                    rows.append((gid, [CMD_SEND, slot, int(r.a0)]))
            elif r.op == REQ_UDP_BIND:
                self._udp_used = True
                slot = self._alloc_slot(gid)
                self.udp_eps[(pid, fd)] = (gid, slot, int(r.port))
                self.udp_port[(gid, int(r.port))] = (pid, fd)
                # a re-bound port supersedes any exited sender's zombie:
                # without this, the drain could attribute the NEW
                # process's in-flight datagrams to the old one's stash
                self._udp_src_zombies.pop((gid, int(r.port)), None)
                self._udp_outstanding.pop((gid, int(r.port)), None)
                self._udp_zombie_deadline.pop((gid, int(r.port)), None)
                rows.append((gid, [CMD_UDP_BIND, slot, int(r.port)]))
            elif r.op == REQ_SENDTO:
                ep = self.udp_eps.get((pid, fd))
                if ep is None:
                    continue  # closed underneath the sender
                seq = int(r.a0) >> 32
                nbytes = int(r.a0) & 0xFFFFFFFF
                ip = int(r.a1)
                # wildcard/loopback route to the sending host itself
                if ip in (0, 0x7F000001):
                    dst_gid = gid
                else:
                    addr = self.sim.dns.resolve_ip(ip)
                    if addr is None:
                        continue  # unroutable: the datagram just drops
                    dst_gid = addr.host_id
                rows.append((gid, [CMD_SENDTO, ep[1], dst_gid,
                                   int(r.port), nbytes, seq]))
                self._udp_outstanding.setdefault(
                    (gid, ep[2]), set()).add(seq)
            elif r.op == REQ_CLOSE:
                key = (pid, fd)
                if key in self._listen_of_ep:
                    # a closed listener has no handshake to run down:
                    # recycle its slot NOW so a close-then-listen pair
                    # arriving in one pump (the reference's sequential
                    # test programs do this) never exhausts the band;
                    # the device resets the row at the window open
                    gp = self._listen_of_ep.pop(key)
                    self.listen_ep.pop(gp, None)
                    if key in self.slot_of:
                        gid, slot = self.slot_of[key]
                        rows.append((gid, [CMD_CLOSE, slot]))
                        self._drop_ep(gid, slot, recycle=True)
                        # pre-acknowledge the conn_gen bump the device's
                        # listener reset will apply at the window open:
                        # without this, a re-listen that reuses the slot
                        # in this same pump would read the bump as ITS
                        # OWN turnover and be torn down by observe
                        self._prev_gen[gid, slot] += 1
                elif key in self.udp_eps:
                    self._close_udp_ep(key, rows, now)
                elif key in self.slot_of:
                    gid, slot = self.slot_of[key]
                    rows.append((gid, [CMD_CLOSE, slot]))
            elif r.op == REQ_SLEEP:
                heapq.heappush(self._wakes, (int(r.a0), pid, int(r.port)))
            elif r.op == REQ_TIMER:
                gen = int(r.port)
                self._timer_gen[(pid, fd)] = gen
                if int(r.a0) >= 0:  # a0 = -1 is a disarm
                    heapq.heappush(
                        self._timers, (int(r.a0), pid, fd, int(r.a1), gen)
                    )
            elif r.op == REQ_LOG:
                self.logs.append((now, pid, r.name.decode()))
            elif r.op == REQ_EXIT:
                self.exit_codes[pid] = int(r.a0)
                # a process that returns from main() with sockets still
                # open gets the kernel-close semantics: FIN every driver
                # endpoint it holds (the same sweep the stoptime-kill
                # path runs) and free its datagram slots — without this
                # its peers never see EOF and slot_of pins the
                # all-exited early break open forever
                for (p_pid, p_fd), (gid, slot) in list(self.slot_of.items()):
                    if p_pid == pid:
                        rows.append((gid, [CMD_CLOSE, slot]))
                for key in [k for k in self.udp_eps if k[0] == pid]:
                    self._close_udp_ep(key, rows, now)
        return rows

    # ------------------------------------------------------------- inject
    def _inject(self, st, rows, now: int):
        if not rows:
            return st
        m = len(rows)
        cap = 1 << max(m - 1, 0).bit_length()  # pad: bounded recompiles
        times = np.full((cap,), np.iinfo(np.int64).max, np.int64)
        dst = np.zeros((cap,), np.int32)
        seq = np.zeros((cap,), np.int32)
        kind = np.zeros((cap,), np.int32)
        argw = np.zeros((cap, N_PKT_ARGS), np.int32)
        src_seq = np.array(jax.device_get(st.src_seq))  # shadowlint: no-deadline=proc-tier pump; covered by the stall watchdog's pets
        for i, (gid, args) in enumerate(rows):
            times[i] = now
            dst[i] = gid
            seq[i] = src_seq[gid]
            src_seq[gid] += 1
            kind[i] = self.model.kind_cmd
            argw[i, : len(args)] = args
        ev = Events(
            time=jnp.asarray(times), dst=jnp.asarray(dst),
            src=jnp.asarray(dst), seq=jnp.asarray(seq),
            kind=jnp.asarray(kind), args=jnp.asarray(argw),
        )
        mask = jnp.asarray(np.arange(cap) < m)
        q2 = self._push_jit(st.queues, ev, mask, jnp.int32(0))
        return dataclasses.replace(
            st, queues=q2, src_seq=jnp.asarray(src_seq)
        )

    # ------------------------------------------------------------ observe
    def _observe(self, st) -> None:
        """Diff device tables into completions + byte/FIN wire ops.

        One batched device_get per window; every scan below walks only
        numpy-selected CHANGED entries, never the full [H, S] table in
        Python (the round-2 version's per-slot loops were O(hosts x
        slots) per window — hopeless at 1k processes)."""
        net = st.hosts.net
        tstate, rx, fin_raw, fgen, lport, phost, pport, cgen = (
            np.asarray(x)
            for x in jax.device_get((  # shadowlint: no-deadline=proc-tier pump; covered by the stall watchdog's pets
                net.tcb.state, net.sockets.rx_bytes, st.hosts.app.fin_seen,
                st.hosts.app.fin_gen, net.sockets.local_port,
                net.sockets.peer_host, net.sockets.peer_port,
                net.tcb.conn_gen,
            ))
        )
        # a fin_seen flag only counts for the slot incarnation it was
        # recorded against; a sticky flag from a previous connection on a
        # reused slot must not read as this stream's EOF
        fin = fin_raw & (fgen == cgen)

        # UDP delivery ring: move each newly-recorded datagram's payload
        # from its sender's in-flight pool to the receiver's queue
        # (fetched only once a datagram socket exists — pure-TCP runs
        # pay nothing)
        if self._udp_used:
            app = st.hosts.app
            ucnt, usrc, usport, udport, _ulen, useq = (
                np.asarray(x) for x in jax.device_get((  # shadowlint: no-deadline=proc-tier pump; covered by the stall watchdog's pets
                    app.udp_cnt, app.udp_src, app.udp_sport,
                    app.udp_dport, app.udp_len, app.udp_seq,
                ))
            )
            for g in np.nonzero(ucnt != self._prev_udp_cnt)[0]:
                g = int(g)
                lo, hi = int(self._prev_udp_cnt[g]), int(ucnt[g])
                if hi - lo > UDP_RING:
                    raise RuntimeError(
                        f"host {g}: {hi - lo} UDP datagrams delivered in "
                        f"one window overran the {UDP_RING}-slot ring; "
                        "deliveries were lost"
                    )
                for i in range(lo, hi):
                    k = i % UDP_RING
                    dst_ep = self.udp_port.get((g, int(udport[g, k])))
                    src_key = (int(usrc[g, k]), int(usport[g, k]))
                    src_ep = (self.udp_port.get(src_key)
                              or self._udp_src_zombies.get(src_key))
                    out = self._udp_outstanding.get(src_key)
                    if out is not None:
                        out.discard(int(useq[g, k]))
                        if not out and src_key in self._udp_src_zombies:
                            # the drain cursor just passed the zombie's
                            # last in-flight datagram: nothing can
                            # attribute to it anymore
                            del self._udp_src_zombies[src_key]
                            del self._udp_outstanding[src_key]
                            self._udp_zombie_deadline.pop(src_key, None)
                    if dst_ep is None or src_ep is None:
                        continue  # endpoint closed while in flight
                    self.rt.udp_deliver(
                        src_ep[0], src_ep[1], int(useq[g, k]),
                        dst_ep[0], dst_ep[1],
                        self._gid_ip.get(int(usrc[g, k]), 0),
                        int(usport[g, k]),
                    )
            self._prev_udp_cnt = ucnt.copy()

        # accumulate this window's delivered-byte deltas FIRST (against
        # the pre-drop _known mask): bytes that land in the same window
        # an endpoint's slot turns over must reach the drop-time flush,
        # not vanish with the _known clear
        self._undeliv += np.where(self._known,
                                  np.maximum(rx - self._prev_rx, 0), 0)
        prev_rx = self._prev_rx  # pre-update snapshot for step 2 below
        self._prev_rx = rx

        # 0. slot incarnation changed under a live endpoint: the device
        # TCP closed and reset the slot (every path back to CLOSED goes
        # through _fresh_row_like's conn_gen bump — tcp.py RST/final-ACK
        # frees and TIME_WAIT expiry). The old incarnation's stream is
        # over: flush owed bytes, surface EOF, recycle driver slots.
        for gid, slot in zip(*np.nonzero((cgen != self._prev_gen)
                                         & self._known)):
            key = (int(gid), int(slot))
            if key in self.pending_conn:
                continue  # refused connect: handled below as CLOSED
            self._drop_ep(*key, recycle=True, surface_eof=True)

        # 1. pending active opens resolve
        for key, (pid, fd) in list(self.pending_conn.items()):
            s = tstate[key]
            if s >= ESTABLISHED:
                self._pending_comps.append((pid, COMP_CONNECT_OK, fd, 0))
                del self.pending_conn[key]
            elif s == CLOSED:
                self._pending_comps.append((pid, COMP_CONNECT_FAIL, fd, 0))
                self._drop_ep(*key, recycle=True)

        # 2. new established connections we don't know -> accepted
        # children (their local port is a listen port; driver-owned
        # connect slots are marked known at translate time)
        for gid, slot in zip(*np.nonzero((tstate >= ESTABLISHED)
                                         & ~self._known)):
            gid, slot = int(gid), int(slot)
            lp = self.listen_ep.get((gid, int(lport[gid, slot])))
            if lp is None:
                continue
            lpid, lfd = lp
            nfd = self._alloc_fd(lpid)
            self._register_ep(gid, slot, lpid, nfd, driver_owned=False)
            # under loss the handshake's final ACK can arrive in the same
            # window as the first data burst: the child is ESTABLISHED
            # with rx_bytes already advanced, but the delta pass above ran
            # before this endpoint was _known. Everything delivered since
            # the last window is owed. rx_bytes is a cumulative lifetime
            # counter (never reset on slot reuse), so the baseline is the
            # pre-update snapshot — the previous incarnation's final
            # count — not zero.
            self._undeliv[gid, slot] = max(
                int(rx[gid, slot]) - int(prev_rx[gid, slot]), 0
            )
            self._wire_try_pair(gid, slot, int(lport[gid, slot]),
                                int(phost[gid, slot]),
                                int(pport[gid, slot]))
            self._pending_comps.append((lpid, COMP_ACCEPT, lfd, nfd))

        # 3. delivered bytes + FIN propagation, changed endpoints only
        fresh_fin = fin & ~self._prev_fin
        for gid, slot in zip(*np.nonzero(
            self._known & ((self._undeliv > 0) | fresh_fin)
        )):
            key = (int(gid), int(slot))
            pid, fd = self.ep_of[key]
            owed = int(self._undeliv[key])
            if owed and key in self.wire:
                src = self.wire[key]
                if src in self.ep_of:
                    spid, sfd = self.ep_of[src]
                    moved = self.rt.wire_deliver(spid, sfd, pid, fd, owed)
                    if moved > 0:
                        self._undeliv[key] -= moved
            if fresh_fin[key]:
                if not self._undeliv[key]:
                    self.rt.wire_fin(pid, fd)
                else:
                    # bytes still owed; FIN re-checked next window
                    fin[key] = False

        self._prev_fin = fin
        self._prev_gen = cgen.copy()

    def live_pids(self) -> list[int]:
        """Virtual pids still running, by the native runtime's green-
        thread ground truth (driver bookkeeping can lag a window behind
        on kills; the runtime cannot) — recorded in the watchdog's
        stall diagnostic bundle."""
        alive = set(self.rt.live_pids())
        return sorted(p for p in self.pid_host if p in alive)

    # ---------------------------------------------------------------- run
    def run(self, stop_s: float | None = None, supervisor=None):
        """Drive the window loop to the stop time.

        `supervisor` (runtime.Supervisor, optional) is petted once per
        window with the frontier time — covering BOTH blocking sites,
        the jitted step and the native `shim_pump` (a plugin spinning
        without yielding hangs the pump forever; the watchdog converts
        that into a stall abort with the live pids in the bundle) — and
        its stop requests (SIGINT/SIGTERM) end the run at the next
        window boundary.
        """
        sim = self.sim
        stop_ns = int(stop_s * SECOND) if stop_s is not None else sim.stop_ns
        st = sim.state0
        now = 0
        # host-side mirror of the fault schedule's liveness flips: a
        # down-flip kills the host's native processes, an up-flip
        # reboots them (the device applies the matching queue wipe and
        # state re-template at the same epoch inside the jitted loop)
        flips = (sim.faults.transitions_in(-1, stop_ns)
                 if sim.faults is not None else [])
        fcur = 0
        while True:
            comps = self._pending_comps
            self._pending_comps = []
            stop_rows = []
            while fcur < len(flips) and flips[fcur][0] <= now:
                _, fgid, up = flips[fcur]
                fcur += 1
                if up:
                    self._fault_up(fgid, now)
                else:
                    self._fault_down(fgid, stop_rows, now)
            while self._starts and self._starts[0][0] <= now:
                _, pid = heapq.heappop(self._starts)
                self.rt.start(pid)
            while self._stops and self._stops[0][0] <= now:
                _, pid = heapq.heappop(self._stops)
                if pid in self.exit_codes:
                    continue  # already exited on its own
                self.rt.kill(pid, 0)
                self.exit_codes[pid] = 0
                # retire the dead process's timer arms and sleeps so
                # they stop bounding window sizes and pumping
                # completions at nobody (the stale-gen path drops the
                # heap entries lazily)
                for key in [k for k in self._timer_gen if k[0] == pid]:
                    self._timer_gen[key] += 1
                self._wakes = [w for w in self._wakes if w[1] != pid]
                heapq.heapify(self._wakes)
                # kernel-side teardown continues for the dead process's
                # sockets (the reference's process_stop leaves the TCP
                # close handshakes to the host model): FIN every driver
                # endpoint the process still holds
                for (pfd_pid, fd), (gid, slot) in list(self.slot_of.items()):
                    if pfd_pid == pid:
                        stop_rows.append((gid, [CMD_CLOSE, slot]))
                # and its datagram sockets (no handshake to run down:
                # free the slot and clear the demux row immediately)
                for key in [k for k in self.udp_eps if k[0] == pid]:
                    self._close_udp_ep(key, stop_rows, now)
            if stop_rows:
                st = self._inject(st, stop_rows, now)
            while self._wakes and self._wakes[0][0] <= now:
                _, pid, gen = heapq.heappop(self._wakes)
                comps.append((pid, COMP_WAKE, -1, gen))
            while self._timers and self._timers[0][0] <= now:
                t, pid, fd, interval, gen = heapq.heappop(self._timers)
                if self._timer_gen.get((pid, fd)) != gen:
                    continue  # re-armed or closed since: stale
                if interval > 0:
                    # credit every expiration the window skipped over and
                    # re-arm on the absolute grid (timer.c interval
                    # expirations with no drift)
                    n_exp = (now - t) // interval + 1
                    heapq.heappush(
                        self._timers, (t + n_exp * interval, pid, fd,
                                       interval, gen)
                    )
                comps.append((pid, COMP_TIMER, fd, int(n_exp if interval > 0 else 1), gen))

            with self.sim.phase("pump"):
                reqs = self.rt.pump(now, comps)
            st = self._inject(st, self._translate(reqs, now), now)
            if supervisor is not None:
                supervisor.pet(
                    now_ns=now, n_live_processes=len(self.live_pids()),
                    n_exited=len(self.exit_codes),
                )
                if supervisor.stop_requested:
                    # graceful shutdown: the proc tier has no checkpoint
                    # (native endpoint streams live host-side), so "at
                    # the next window boundary" just means stop cleanly
                    # — logs and exit codes collected so far survive
                    break

            if now >= stop_ns:
                break
            # every process has exited and no driver endpoint still owes
            # a teardown handshake: the remaining horizon is dead time
            # (the reference likewise ends when its process count hits
            # zero before stoptime, master.c end-of-simulation path)
            if (
                self.exit_codes
                and len(self.exit_codes) >= len(self.pid_host)
                and not self._starts
                and not self.slot_of
                and not self.udp_eps
                and fcur >= len(flips)  # a restart could revive hosts
            ):
                break
            # never step past the next host-side interest point
            bound = stop_ns
            if self._starts:
                bound = min(bound, max(self._starts[0][0], now + 1))
            if self._stops:
                bound = min(bound, max(self._stops[0][0], now + 1))
            if self._wakes:
                bound = min(bound, max(self._wakes[0][0], now + 1))
            # retire re-armed/disarmed timer entries so a dead arm stops
            # bounding window sizes
            while self._timers and self._timer_gen.get(
                (self._timers[0][1], self._timers[0][2])
            ) != self._timers[0][4]:
                heapq.heappop(self._timers)
            if self._timers:
                bound = min(bound, max(self._timers[0][0], now + 1))
            # land the window edge on the next liveness flip so the
            # device's epoch switch and the driver's kill/respawn agree
            # on when the crash happened
            if fcur < len(flips):
                bound = min(bound, max(flips[fcur][0], now + 1))
            st = sim.step_window(st, bound)
            if sim.pressure is not None:
                # the tier already steps window-by-window (bounded by
                # host-side interest points), so the spill reservoir's
                # harvest/refill hook slots in at every boundary for
                # free — sharing the frontier probe's device_get so the
                # idle refill check costs no extra round-trip
                now_a, wr = jax.device_get((st.now, st.queues.spill.wr))  # shadowlint: no-deadline=proc-tier pump; covered by the stall watchdog's pets
                st = sim._note_owned(
                    sim.pressure.boundary(st, wr=np.asarray(wr))
                )
                now = int(now_a)
            else:
                now = int(jax.device_get(st.now))  # shadowlint: no-deadline=proc-tier pump; covered by the stall watchdog's pets
            self._observe(st)
            if self._udp_zombie_deadline:
                for zk in [k for k, d in self._udp_zombie_deadline.items()
                           if d <= now]:
                    del self._udp_zombie_deadline[zk]
                    self._udp_src_zombies.pop(zk, None)
                    self._udp_outstanding.pop(zk, None)
        drops = int(jax.device_get(st.queues.drops.sum()))  # shadowlint: no-deadline=proc-tier pump; covered by the stall watchdog's pets
        if drops and self.overflow == "strict":
            from shadow_tpu.runtime.pressure import QueuePressureError

            raise QueuePressureError(
                drops, self.sim.engine.cfg.capacity, self.sim.summary(st)
            )
        if drops and self.strict_overflow and self.overflow == "drop":
            raise RuntimeError(
                f"event queue overflow: {drops} events dropped (capacity "
                f"{self.sim.engine.cfg.capacity}); native processes may "
                "have observed a corrupted simulation — rerun with a "
                "larger capacity"
            )
        if self.lost_stream_bytes and self.strict_overflow:
            raise RuntimeError(
                f"{self.lost_stream_bytes} delivered bytes could not be "
                "flushed to their endpoint before its slot turned over — "
                "a native process observed a truncated stream"
            )
        return st

    def close(self):
        self.rt.close()
