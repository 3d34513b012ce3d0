"""PHOLD over several chips: the `phold` family's sharded build, with the
cross-shard exchange's counters added to its counter read.

The hosts are cut into one contiguous block per chip (host h lives on
chip h // (n_hosts / chips)), and each chip holds its hosts' queues.
An event whose destination lies on another chip crosses by the engine's
bucketed all_to_all (`Engine._exchange_push`): each round moves up to
R = `Engine.xchg_width` events from every chip to every other, and the
rounds repeat until nothing is left. Each round's received bucket is
merged into the queues at the top of the next round or sweep
(`Engine._xchg_deliver`). `build`, `fetch` and `check` are the `phold`
family's; `build` refuses fewer than two chips.

Counters, beside `engine_counters` (window deltas in the harness):
- `n_xchg_rounds`: all_to_all rounds (every chip runs the same number);
- `n_cross_shard`: events sent to a host on another chip, all chips;
- `xchg_slots`: the slots those rounds moved between distinct chips,
  rounds x S x (S - 1) x R for S chips (a chip's row to itself is
  always empty).

The metrics that read them, each listing the cells it reads in:
- `xchg_rounds_per_window.phold_x4`: rounds per window;
- `xchg_fill_pct.phold_x4`: 100 x `n_cross_shard` / `xchg_slots`, the
  share of the moved slots that carried an event (at most 100);
- `device_idle_pct.phold_x4`: the devices' idle share of the traced
  window (`trace_reduce.idle_pct`).
"""

from __future__ import annotations

import dataclasses

import jax

from benchmark.models import Built, engine_counters
from benchmark.models import phold as family
from shadow_tpu.models import phold
from shadow_tpu.parallel import mesh as pmesh

fetch = family.fetch
check = family.check


def build(config: dict, traffic: dict, seed: int, devices: list) -> Built:
    chips = len(devices)
    if chips < 2:
        raise ValueError(f"the phold_sharded family runs across chips and was "
                         f"given {chips}; one chip is the phold family's")
    with jax.default_device(jax.devices("cpu")[0]):
        eng, _ = phold.build(config["n_hosts"] // chips,
                             axis_name=pmesh.HOSTS_AXIS, n_shards=chips,
                             **family._kwargs(config, traffic))
    slots = chips * (chips - 1) * eng.xchg_width

    def counters(st) -> dict:
        rounds = st.stats.n_xchg_rounds.max()
        return dict(engine_counters(st), n_xchg_rounds=rounds,
                    n_cross_shard=st.stats.n_cross_shard.max(),
                    xchg_slots=rounds * slots)

    built = family.build(config, traffic, seed, devices)
    read = jax.jit(counters)  # shadowlint: no-donate=a read; the state lives on
    return dataclasses.replace(built, counters=read)
