"""Config knobs that round 1 accepted-and-ignored: jitter, cpufrequency,
process stoptime, socketrecvbuffer — each must act; unimplementable ones
must fail loudly (VERDICT round 1 items 7/8; weak #5).
"""

import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.config import parse_config
from shadow_tpu.core.timebase import SECOND
from shadow_tpu.sim import build_simulation


def topo(latency=25.0, jitter=0.0):
    return f"""<graphml xmlns="http://graphml.graphdrawing.org/xmlns">
  <key attr.name="packetloss" attr.type="double" for="edge" id="d4" />
  <key attr.name="latency" attr.type="double" for="edge" id="d3" />
  <key attr.name="jitter" attr.type="double" for="edge" id="d5" />
  <key attr.name="bandwidthup" attr.type="int" for="node" id="d2" />
  <key attr.name="bandwidthdown" attr.type="int" for="node" id="d1" />
  <graph edgedefault="undirected">
    <node id="poi-1">
      <data key="d1">10240</data>
      <data key="d2">10240</data>
    </node>
    <edge source="poi-1" target="poi-1">
      <data key="d3">{latency}</data>
      <data key="d4">0.0</data>
      <data key="d5">{jitter}</data>
    </edge>
  </graph>
</graphml>"""


def phold_cfg(n=6, jitter=0.0, host_extra="", proc_extra="", stoptime=20):
    return textwrap.dedent(f"""\
    <shadow stoptime="{stoptime}">
      <topology><![CDATA[{topo(jitter=jitter)}]]></topology>
      <plugin id="phold" path="shadow-plugin-test-phold"/>
      <host id="peer" quantity="{n}" {host_extra}>
        <process plugin="phold" starttime="1" arguments="load=3" {proc_extra}/>
      </host>
    </shadow>""")


def test_jitter_spreads_arrival_times():
    """Seeded latency noise must widen the arrival-time distribution:
    with zero jitter all same-window deliveries share exact latencies;
    with jitter they spread (reference edge attr, topology.c:101-105)."""
    base = build_simulation(parse_config(phold_cfg()), seed=2)
    jit = build_simulation(parse_config(phold_cfg(jitter=10.0)), seed=2)
    st0 = base.run()
    st1 = jit.run()
    # same workload shape either way
    assert int(st1.hosts.app.n_recv.sum()) > 0
    # jittered deliveries land at different times than unjittered ones
    assert int(st0.stats.n_executed.sum()) != 0
    t0 = np.array(jax.device_get(st0.queues.time))
    t1 = np.array(jax.device_get(st1.queues.time))
    assert not np.array_equal(t0, t1)
    # jittered latencies are no longer multiples of the base latency:
    # pending event times modulo 1ms spread over many residues
    valid = t1[t1 < np.iinfo(np.int64).max]
    res = np.unique(valid % 1_000_000)
    assert len(res) > len(valid) // 2 or len(valid) == 0


def test_cpufrequency_slows_a_host():
    """A slow-CPU host must lag a fast one (cpu.c:56-107 semantics): same
    workload, the throttled host executes fewer events by stoptime."""
    fast = parse_config(phold_cfg(n=4))
    slow_xml = phold_cfg(n=4).replace(
        '<host id="peer" quantity="4" >',
        '<host id="peer" quantity="4" cpufrequency="1000">',
    )
    slow = parse_config(slow_xml)
    # cpufrequency=1000 KHz -> 10ms per event: a severe throttle
    st_f = build_simulation(fast, seed=3).run()
    st_s = build_simulation(slow, seed=3).run()
    ex_f = int(st_f.stats.n_executed.sum())
    ex_s = int(st_s.stats.n_executed.sum())
    assert ex_s < ex_f // 2, (ex_f, ex_s)
    # the CPU model leaves a busy-until trace
    assert int(st_s.cpu_free.max()) > 0
    assert int(st_f.cpu_free.max()) == 0


def test_process_stoptime_stops_emissions():
    """A process with stoptime stops driving traffic at that instant
    (configuration.h kill time): its message counters freeze."""
    forever = parse_config(phold_cfg(n=4, stoptime=30))
    st_a = build_simulation(forever, seed=5).run()
    stopped_xml = phold_cfg(n=4, stoptime=30).replace(
        'arguments="load=3" />', 'arguments="load=3" stoptime="5"/>'
    )
    st_b = build_simulation(parse_config(stopped_xml), seed=5).run()
    # all processes stopped at t=5: far fewer messages moved
    a = int(st_a.hosts.app.n_recv.sum())
    b = int(st_b.hosts.app.n_recv.sum())
    assert 0 < b < a // 2, (a, b)


def test_socketsendbuffer_bounds_and_still_delivers():
    """socketsendbuffer (tcp.c:407-598 buffer family): app bytes beyond
    the cap wait in the TCB's app_pending and drain as ACKs free space,
    so a transfer far larger than the buffer still completes — the
    jitted analog of the reference's blocking send. Round-3 hard-errored
    this attribute; now it acts."""
    import textwrap as tw

    def cfg(extra=""):
        return tw.dedent(f"""\
        <shadow stoptime="60">
          <topology><![CDATA[{topo()}]]></topology>
          <plugin id="tgen" path="tgen"/>
          <host id="server">
            <process plugin="tgen" starttime="1"
              arguments="server port=8888"/>
          </host>
          <host id="client"{extra}>
            <process plugin="tgen" starttime="2"
              arguments="peers=server:8888 sendsize=300KiB recvsize=1KiB
              count=1"/>
          </host>
        </shadow>""")

    # a 16 KiB cap on a 300 KiB send: the cap is ~1/20th of the payload
    sim = build_simulation(
        parse_config(cfg(' socketsendbuffer="16384"')), seed=3
    )
    # the cap is actually installed in the TCB
    assert int(sim.state0.hosts.net.tcb.snd_cap.max()) == 16384
    # mid-run: TGen issues the whole 300 KiB in one send at t~2s and
    # the cap drains ~16 KiB per RTT, so just after the send most bytes
    # must be waiting BEHIND the cap (a no-op knob would show zero
    # pending here)
    st = sim.run(int(2.2 * SECOND))
    assert int(st.hosts.net.tcb.app_pending.sum()) > 100 * 1024
    st = sim.run(state=st)
    rx = int(st.hosts.net.sockets.rx_bytes.sum())
    assert rx >= 300 * 1024, rx  # every byte still arrived
    # ...and the pending queue fully drained by completion
    assert int(st.hosts.net.tcb.app_pending.sum()) == 0
    # and the capped run matches the uncapped run's delivered bytes
    st_u = build_simulation(parse_config(cfg()), seed=3).run()
    assert int(st_u.hosts.net.sockets.rx_bytes.sum()) == rx


def test_interfacebuffer_bounds_receive_queue():
    """interfacebuffer drop-tails the implicit NIC receive queue
    (options.c:132 'interface receive buffer'): a bulk transfer into a
    slow receiver with a tiny buffer must shed packets; the default
    megabyte buffer must not (CoDel acts first)."""
    def run(attr):
        xml = textwrap.dedent(f"""\
        <shadow stoptime="40">
          <topology><![CDATA[{topo()}]]></topology>
          <plugin id="tgen" path="tgen"/>
          <host id="server" bandwidthdown="128" {attr}>
            <process plugin="tgen" starttime="1" arguments="server port=80"/>
          </host>
          <host id="client">
            <process plugin="tgen" starttime="2"
              arguments="peers=server:80 sendsize=200KiB recvsize=1KiB count=1"/>
          </host>
        </shadow>""")
        sim = build_simulation(parse_config(xml), seed=3)
        sim.strict_overflow = False
        st = sim.run()
        return int(st.hosts.net.nic_rx.drops.sum())

    assert run('interfacebuffer="3000"') > 0
    assert run("") == 0


@pytest.mark.parametrize("qdisc", ["fifo", "rr"])
@pytest.mark.parametrize("rx_queue", ["codel", "static", "single"])
def test_qdisc_router_queue_matrix(qdisc, rx_queue):
    """Every interface-qdisc x router-queue combination must carry a
    2-client TGen exchange to completion (options.c interface-qdisc;
    router.c:50-55 queue managers)."""
    xml = textwrap.dedent(f"""\
    <shadow stoptime="60">
      <topology><![CDATA[{topo()}]]></topology>
      <plugin id="tgen" path="tgen"/>
      <host id="server">
        <process plugin="tgen" starttime="1" arguments="server port=80"/>
      </host>
      <host id="client" quantity="2">
        <process plugin="tgen" starttime="2"
          arguments="peers=server:80 sendsize=20KiB recvsize=4KiB count=1"/>
      </host>
    </shadow>""")
    sim = build_simulation(
        parse_config(xml), seed=2, qdisc=qdisc, rx_queue=rx_queue,
    )
    sim.strict_overflow = False
    st = sim.run()
    assert [int(x) for x in st.hosts.app.streams_done[1:3]] == [1, 1], (
        qdisc, rx_queue,
    )


def test_socketrecvbuffer_caps_advertised_window():
    from shadow_tpu.transport.tcp import MSS, RCV_WND

    xml = textwrap.dedent(f"""\
    <shadow stoptime="30">
      <topology><![CDATA[{topo()}]]></topology>
      <plugin id="tgen" path="tgen"/>
      <host id="server" socketrecvbuffer="{8 * 1434}">
        <process plugin="tgen" starttime="1" arguments="server port=80"/>
      </host>
      <host id="client">
        <process plugin="tgen" starttime="2"
          arguments="peers=server:80 sendsize=200KiB recvsize=1KiB count=1 pause=1"/>
      </host>
    </shadow>""")
    sim = build_simulation(parse_config(xml), seed=1)
    assert int(sim.state0.hosts.net.tcb.rwnd[0, 0]) == 8
    assert int(sim.state0.hosts.net.tcb.rwnd[1, 0]) == RCV_WND
    st = sim.run()
    # the transfer still completes under the tiny window
    assert int(st.hosts.app.streams_done[1]) == 1

def test_cpufrequency_works_sharded():
    """The CPU model under a device mesh: global-gid cost indexing means
    a sharded run matches the single-device run bit for bit."""
    from shadow_tpu.parallel.mesh import make_mesh

    slow_xml = phold_cfg(n=8).replace(
        '<host id="peer" quantity="8" >',
        '<host id="peer" quantity="8" cpufrequency="1000">',
    )
    cfg = parse_config(slow_xml)
    st1 = build_simulation(cfg, seed=3).run()
    stN = build_simulation(cfg, seed=3, mesh=make_mesh(4)).run()
    assert st1.stats.n_executed.tolist() == stN.stats.n_executed.tolist()
    assert st1.cpu_free.tolist() == stN.cpu_free.tolist()
    assert int(st1.cpu_free.max()) > 0


def test_shape_bucketing_shares_program_shapes():
    """Configs of nearby sizes pad to ONE standard host-row bucket, so
    they compile to the same XLA program (the minutes-long per-shape TPU
    compile, docs/5-Known-Issues.md, is paid once per bucket). Padded rows are inert: results must match the
    unbucketed build exactly."""
    import textwrap as tw

    from tests.test_config_sim import TOPO_1POI

    def cfg_n(n_clients):
        return parse_config(tw.dedent(f"""\
        <shadow stoptime="30">
          <topology><![CDATA[{TOPO_1POI}]]></topology>
          <plugin id="tgen" path="tgen"/>
          <host id="server">
            <process plugin="tgen" starttime="1" arguments="server port=80"/>
          </host>
          <host id="client" quantity="{n_clients}">
            <process plugin="tgen" starttime="2"
              arguments="peers=server:80 sendsize=1KiB recvsize=4KiB count=1 pause=1"/>
          </host>
        </shadow>"""))

    cfg_a = cfg_n(3)
    cfg_b = cfg_n(5)
    sim_a = build_simulation(cfg_a, seed=1)
    sim_b = build_simulation(cfg_b, seed=1)
    # 4 and 6 hosts both land in the 16-row bucket -> identical shapes
    assert sim_a.engine.cfg.n_hosts == sim_b.engine.cfg.n_hosts == 16
    assert (
        jax.tree.map(lambda a: a.shape, sim_a.state0)
        == jax.tree.map(lambda a: a.shape, sim_b.state0)
    )
    # inert padding: bucketed vs unbucketed runs agree bit-exactly on
    # the real hosts' results
    sim_u = build_simulation(cfg_a, seed=1, shape_bucket=False)
    st_b = sim_a.run(10 * SECOND)
    st_u = sim_u.run(10 * SECOND)
    n = len(sim_u.names)
    assert (
        jax.device_get(st_b.hosts.net.sockets.rx_bytes[:n]).tolist()
        == jax.device_get(st_u.hosts.net.sockets.rx_bytes[:n]).tolist()
    )
    assert (
        jax.device_get(st_b.stats.n_executed[:n]).tolist()
        == jax.device_get(st_u.stats.n_executed[:n]).tolist()
    )
