"""PHOLD: the classic parallel-DES benchmark, as a jitted host behavior.

The reference ships PHOLD as a plugin — N peers bounce UDP messages to
weighted-random targets (reference: src/test/phold/test_phold.c:36-52, config
src/test/phold/phold.test.shadow.config.xml). It is the natural first
benchmark for the engine (SURVEY.md §4, §6): every executed event emits one
new event to a random peer, so steady-state event population is constant and
events/sec is measured directly.

Here each host's behavior is a handler compiled into the device step: on
receiving a message, pick a uniform random peer and send a new message with
an exponential service delay.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from shadow_tpu.core import rng as srng
from shadow_tpu.core.engine import Emit, Engine, EngineConfig, ConstantNetwork
from shadow_tpu.core.events import Events
from shadow_tpu.core.timebase import MILLISECOND, TIME_INVALID

KIND_MSG = 0

# PHOLD events carry no payload; one arg word keeps the queue sorts narrow.
N_PHOLD_ARGS = 1


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PholdHost:
    n_received: jax.Array  # i64[] per host

    @staticmethod
    def create(n_hosts: int) -> "PholdHost":
        return PholdHost(n_received=jnp.zeros((n_hosts,), jnp.int64))


def make_handler(
    n_hosts_global: int,
    mean_delay_ns: int,
    hot_hosts: int = 0,
    hot_weight: float = 0.0,
):
    """PHOLD message handler; optional skewed target weights.

    The reference's PHOLD supports non-uniform target selection via a
    weights file (reference: src/test/phold/test_phold.c:36-52 weights /
    totalWeight). Here the skew is parametric: with probability
    `hot_weight` the target is drawn from the first `hot_hosts` hosts —
    the classic hot-spot variant that collapses one-event-per-sweep
    schedulers.
    """

    draw = _make_draw(n_hosts_global, mean_delay_ns, hot_hosts, hot_weight)

    def on_msg(hs: PholdHost, ev: Events, key: jax.Array):
        peer, delay = draw(key)
        hs = PholdHost(n_received=hs.n_received + 1)
        return hs, Emit.single(dst=peer, dt=delay, kind=KIND_MSG, n_args=N_PHOLD_ARGS)

    return on_msg


def _make_draw(n_hosts_global, mean_delay_ns, hot_hosts, hot_weight):
    """The per-event (peer, delay) draw — one definition shared by the
    sequential and batched handlers, so the engine's bit-identity
    guarantee cannot be broken by the two drifting apart."""

    def draw(key):
        kp, kd, kh = srng.split(key, 3)
        peer = srng.randint(kp, 0, n_hosts_global)
        if hot_hosts > 0 and hot_weight > 0.0:
            hot = srng.uniform(kh) < hot_weight
            # folded sub-key: reusing kp here would correlate the hot
            # draw with the uniform one (peer_hot == peer % hot_hosts
            # whenever bounds divide); non-hot draws keep their keys so
            # plain-PHOLD trajectories are unchanged
            peer_hot = srng.randint(srng.fold_in(kp, 1), 0, hot_hosts)
            peer = jnp.where(hot, peer_hot, peer)
        return peer, srng.exponential_ns(kd, mean_delay_ns)

    return draw


def make_batch_handler(
    n_hosts_global: int,
    mean_delay_ns: int,
    hot_hosts: int = 0,
    hot_weight: float = 0.0,
):
    """Whole-frontier PHOLD handler for the engine's commutative fast
    path: executes a host's [B] below-barrier events in one call. PHOLD
    qualifies — the state fold is a counter (order-insensitive) and every
    emit is a remote send (never local below the barrier). Per-position
    keys and the same split/draw sequence keep results bit-identical to
    the sequential path."""

    draw = _make_draw(n_hosts_global, mean_delay_ns, hot_hosts, hot_weight)

    def on_msgs(hs: PholdHost, evs: Events, keys: jax.Array):
        valid = evs.time != TIME_INVALID  # [B]
        peers, delays = jax.vmap(draw)(keys)
        hs = PholdHost(
            n_received=hs.n_received + jnp.sum(valid, dtype=jnp.int64)
        )
        b = valid.shape[0]
        em = Emit(
            dst=peers[:, None],
            dt=delays[:, None],
            kind=jnp.full((b, 1), KIND_MSG, jnp.int32),
            args=jnp.zeros((b, 1, N_PHOLD_ARGS), jnp.int32),
            mask=valid[:, None],
            local=jnp.zeros((b, 1), bool),
        )
        return hs, em

    return on_msgs


def build(
    n_hosts: int,
    *,
    hot_hosts: int = 0,
    hot_weight: float = 0.0,
    capacity: int = 64,
    latency_ns: int = 50 * MILLISECOND,
    mean_delay_ns: int = 10 * MILLISECOND,
    msgs_per_host: int = 1,
    seed: int = 0,
    axis_name: str | None = None,
    n_shards: int = 1,
    # 24 covers the steady-state frontier (Poisson tail ~1e-8 per host at
    # the stock load) while keeping the push's flat sorts -- which scale
    # with H*drain_batch -- 25% smaller than the engine's general default
    drain_batch: int = 24,
    batched: bool = False,
    trace: int = 0,
    stats: int = 0,
    spill: int = 0,
    kernel: str = "xla",
):
    """Build (engine, initial_state) for an n_hosts PHOLD network.

    The 50ms single-PoI topology matches the reference's stock config.
    With axis_name set, n_hosts is the *per-shard* host count.
    `batched` uses the engine's commutative fast path (whole frontiers
    per handler call); results are bit-identical either way.
    """
    cfg = EngineConfig(
        n_hosts=n_hosts,
        capacity=capacity,
        lookahead=latency_ns,
        max_emit=1,
        n_args=N_PHOLD_ARGS,
        seed=seed,
        axis_name=axis_name,
        n_shards=n_shards,
        drain_batch=drain_batch,
        trace=trace,
        stats=stats,
        spill=spill,
        kernel=kernel,
    )
    net = ConstantNetwork(latency_ns)
    eng = Engine(
        cfg,
        [make_handler(n_hosts * n_shards, mean_delay_ns, hot_hosts, hot_weight)],
        net,
        batch_handler=(
            make_batch_handler(
                n_hosts * n_shards, mean_delay_ns, hot_hosts, hot_weight
            )
            if batched
            else None
        ),
    )

    def init(host0=0):
        init_ev = Events.empty((n_hosts, msgs_per_host), n_args=N_PHOLD_ARGS)
        gids = host0 + jnp.arange(n_hosts, dtype=jnp.int32)
        init_ev = dataclasses.replace(
            init_ev,
            # stagger start times so the first window isn't one giant burst
            time=jnp.broadcast_to(
                (gids[:, None].astype(jnp.int64) % 16 + 1) * MILLISECOND,
                (n_hosts, msgs_per_host),
            ),
            dst=jnp.broadcast_to(gids[:, None], (n_hosts, msgs_per_host)),
            src=jnp.broadcast_to(gids[:, None], (n_hosts, msgs_per_host)),
            seq=jnp.broadcast_to(
                jnp.arange(msgs_per_host, dtype=jnp.int32)[None, :],
                (n_hosts, msgs_per_host),
            ),
            kind=jnp.full((n_hosts, msgs_per_host), KIND_MSG, jnp.int32),
        )
        return eng.init_state(PholdHost.create(n_hosts), init_ev, host0)

    return eng, init


def build_fleet(n_hosts: int, lanes: int, *, seeds=None, stop_ns: int = 0,
                **build_kw):
    """Seed-sweep fleet over one PHOLD shape: `lanes` copies of the
    `build(n_hosts, **build_kw)` scenario vmapped into one program
    (docs/16-Scenario-Fleets.md). `seeds` defaults to `seed .. seed+L-1`
    off the base build's seed; every other knob is uniform across lanes
    by construction, which is exactly the fleet tier's static-knob rule.
    """
    from shadow_tpu.runtime.fleet import build_fleet_from_engine

    eng, init = build(n_hosts, **build_kw)
    if seeds is None:
        base = build_kw.get("seed", 0)
        seeds = tuple(base + i for i in range(lanes))
    return build_fleet_from_engine(
        eng, init(), lanes, seeds=tuple(seeds), stop_ns=stop_ns
    )
