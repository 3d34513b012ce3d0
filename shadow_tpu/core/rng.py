"""Deterministic per-host random streams, vectorized threefry2x32.

The reference derives determinism from a seed hierarchy master→slave→host of
`rand_r` streams (reference: src/main/utility/random.c:15-50,
src/main/core/master.c:95, src/main/host/host.c:176). Here every executed
event gets a counter-based key derived from (root seed, global host id,
per-host execution counter) — bit-reproducible regardless of how hosts are
sharded across chips.

Why not `jax.random`: its typed-key API lowers vmapped `fold_in`/`split`
chains into per-lane key plumbing that measures ~100× slower than bulk
elementwise work on TPU (5.7 ms vs 0.06 ms for 131k lanes on v5e — the
engine's dominant per-sweep cost when profiled). The generator below is
the same threefry2x32 construction (20 rounds, Salmon et al. SC'11), but
keys are plain `uint32[..., 2]` arrays and every derivation/sample is a
single fused elementwise pass over the batch, so deriving 131k event keys
costs microseconds. Handlers receive such a key per event and consume it
with the helpers here (`split`, `uniform`, `randint`, `exponential`).

Stream-separation discipline: every derivation folds a distinct DOMAIN
tag into the counter word, so handler keys, route keys, split children,
and lane rolls can never collide however many draws a handler makes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_KS_PARITY = 0x1BD11BDA  # threefry key-schedule parity constant
# domain tags (counter-word c1) for the derivation kinds
_DOM_EVENT = 0x45564E54  # "EVNT": (gid, cnt) -> event key
_DOM_HANDLER = 0x484E444C  # "HNDL": event key -> handler key
_DOM_ROUTE = 0x524F5554  # "ROUT": event key -> route key
_DOM_SPLIT = 0x53504C54  # "SPLT": split children
_DOM_LANE = 0x4C414E45  # "LANE": per-lane rolls
_DOM_FOLD = 0x464F4C44  # "FOLD": fold_in derivations
_DOM_UNIF = 0x554E4946  # "UNIF": uniform/bernoulli draws
_DOM_RINT = 0x52494E54  # "RINT": randint draws
_DOM_FAULT = 0x464C5453  # "FLTS": named fault-schedule streams


def _rotl(x: jax.Array, r: int) -> jax.Array:
    return (x << r) | (x >> (32 - r))


def threefry2x32(k0, k1, c0, c1) -> tuple[jax.Array, jax.Array]:
    """The standard 20-round threefry2x32 block cipher, elementwise over
    arbitrary (broadcastable) uint32 array operands."""
    k0 = jnp.asarray(k0, jnp.uint32)
    k1 = jnp.asarray(k1, jnp.uint32)
    c0 = jnp.asarray(c0, jnp.uint32)
    c1 = jnp.asarray(c1, jnp.uint32)
    ks2 = k0 ^ k1 ^ jnp.uint32(_KS_PARITY)
    rot = ((13, 15, 26, 6), (17, 29, 16, 24))
    x0 = c0 + k0
    x1 = c1 + k1
    ks = (k1, ks2, k0)
    for i in range(5):
        for r in rot[i % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x0 ^ x1
        x0 = x0 + ks[i % 3]
        x1 = x1 + ks[(i + 1) % 3] + jnp.uint32(i + 1)
    return x0, x1


def _key(k0: jax.Array, k1: jax.Array) -> jax.Array:
    return jnp.stack([k0, k1], axis=-1)


def root_key(seed: int) -> jax.Array:
    """uint32[2] root key from a Python seed (both halves mixed)."""
    s = jnp.uint32(seed & 0xFFFFFFFF)
    hi = jnp.uint32((int(seed) >> 32) & 0xFFFFFFFF)
    return _key(*threefry2x32(s, hi, jnp.uint32(0), jnp.uint32(0)))


def event_keys(base: jax.Array, host_gids: jax.Array, exec_cnt: jax.Array):
    """Per-event (handler_key, route_key), each uint32[..., 2].

    handler_key is consumed by the application/protocol handler; route_key
    by the engine for reliability/jitter rolls — separated by domain tag so
    the two can never collide however many draws a handler performs.
    """
    g = host_gids.astype(jnp.uint32)
    c = exec_cnt.astype(jnp.uint32)
    a, b = threefry2x32(base[..., 0], base[..., 1], g, c ^ jnp.uint32(_DOM_EVENT))
    hk = _key(*threefry2x32(a, b, jnp.uint32(0), jnp.uint32(_DOM_HANDLER)))
    rk = _key(*threefry2x32(a, b, jnp.uint32(0), jnp.uint32(_DOM_ROUTE)))
    return hk, rk


def fold_in(key: jax.Array, data) -> jax.Array:
    """New key folding integer `data` (array or scalar) into `key`."""
    d = jnp.asarray(data).astype(jnp.uint32)
    return _key(*threefry2x32(key[..., 0], key[..., 1], d,
                              jnp.uint32(_DOM_FOLD)))


def split(key: jax.Array, n: int):
    """n statically-indexed child keys (tuple). Elementwise over any
    leading batch shape — under vmap this is still one fused pass."""
    return tuple(
        _key(*threefry2x32(key[..., 0], key[..., 1], jnp.uint32(i),
                           jnp.uint32(_DOM_SPLIT)))
        for i in range(n)
    )


def _bits(key: jax.Array, c0=0, c1=0) -> jax.Array:
    x0, _ = threefry2x32(key[..., 0], key[..., 1], c0, c1)
    return x0


def _to_unit(bits: jax.Array) -> jax.Array:
    # 24-bit mantissa path: exact on f32, uniform in [0, 1)
    return (bits >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def uniform(key: jax.Array) -> jax.Array:
    """f32 uniform in [0, 1), shaped like the key's batch shape."""
    return _to_unit(_bits(key, c1=jnp.uint32(_DOM_UNIF)))


def uniform_lanes(key: jax.Array, n_lanes: int, offset: int = 0) -> jax.Array:
    """[..., n_lanes] uniforms from one key: lane i uses counter offset+i.
    The bulk replacement for per-lane fold_in+uniform chains."""
    lanes = jnp.arange(n_lanes, dtype=jnp.uint32) + jnp.uint32(offset)
    x0, _ = threefry2x32(
        key[..., 0:1], key[..., 1:2], lanes, jnp.uint32(_DOM_LANE)
    )
    return _to_unit(x0)


def fault_stream_uniform(seed: int, stream: int, n: int) -> jax.Array:
    """f32[n] uniforms from the named fault-schedule stream.

    Derived from (root seed, stream index, element index) only — never
    from host sharding or execution counters — so a fault timeline built
    from these draws is identical across shard counts and across
    checkpoint/restore (the schedule is recompiled from the same config;
    faults/schedule.py consumes this at build time, host-side).
    """
    base = root_key(seed)
    k = _key(*threefry2x32(base[..., 0], base[..., 1],
                           jnp.uint32(stream & 0xFFFFFFFF),
                           jnp.uint32(_DOM_FAULT)))
    idx = jnp.arange(n, dtype=jnp.uint32)
    x0, _ = threefry2x32(k[..., 0:1], k[..., 1:2], idx,
                         jnp.uint32(_DOM_FAULT))
    return _to_unit(x0)


def randint(key: jax.Array, minval: int, maxval: int,
            dtype=jnp.int32) -> jax.Array:
    """Integer in [minval, maxval); modulo draw (bias < 2^-20 for any
    simulation-scale range, irrelevant for DES workloads). An empty
    range returns minval (u32 x % 0 is backend-undefined in XLA, which
    would break bit-reproducibility)."""
    span = jnp.maximum(jnp.uint32(maxval - minval), jnp.uint32(1))
    return (jnp.asarray(minval, dtype)
            + (_bits(key, c1=jnp.uint32(_DOM_RINT)) % span).astype(dtype))


# ln(1 + i/2^10) for i in 0..2^10 in 2^-24 fixed point: the table
# `exponential_ns` interpolates, computed once on the host in float64.
_LN_TABLE = np.round(
    np.log1p(np.arange((1 << 10) + 1) / (1 << 10)) * (1 << 24)
).astype(np.int64)
_LN2_FIX = round(math.log(2) * (1 << 24))


def exponential_ns(key: jax.Array, mean_ns: int) -> jax.Array:
    """i64 exponential draw with mean `mean_ns` nanoseconds.

    Integer arithmetic only, so every backend gives the same bits: a
    float `log1p` differs in its last place between the TPU and the CPU,
    and scaling by `mean_ns` turns that place into different
    nanoseconds (the PHOLD trajectories diverged, chip_smoke, PR 21).
    With u = k/2^24 the same draw `uniform` makes, -ln(1-u) =
    ln 2^24 - ln x for x = 2^24 - k in [1, 2^24]; ln x is e*ln 2 plus a
    table-interpolated ln of the mantissa in [1, 2), all in 2^-24 fixed
    point (error below 2e-7, about one f32 place)."""
    k = _bits(key, c1=jnp.uint32(_DOM_UNIF)) >> 8
    x = jnp.uint32(1 << 24) - k
    e = (31 - jax.lax.clz(x)).astype(jnp.int64)  # floor(log2 x), 0..24
    frac = (x.astype(jnp.int64) << (24 - e)) - (1 << 24)  # [0, 2^24)
    i, r = frac >> 14, frac & ((1 << 14) - 1)
    table = jnp.asarray(_LN_TABLE)
    lo = table[i]
    ln_x = e * _LN2_FIX + lo + (((table[i + 1] - lo) * r) >> 14)
    return (jnp.int64(mean_ns) * (24 * _LN2_FIX - ln_x)) >> 24


def bernoulli(key: jax.Array, p) -> jax.Array:
    """Shares uniform's draw: bernoulli(key, p) and uniform(key) are the
    same sample viewed two ways — derive child keys to get both."""
    return uniform(key) < p
