"""Chip parameter table for the TPU-readiness audits (docs/10, "TPU
readiness").

One frozen row per accelerator generation the silicon campaign
(ROADMAP item 1) targets, plus a CPU row describing the single-core
container every BENCH_r* number was measured on. The rows feed two
consumers:

- the tile auditor (`tpu_readiness`): native tile geometry per dtype
  width — the (sublane, 128) minimum tile of the Pallas guide's table
  (f32 (8,128), bf16 (16,128), int8/fp8 (32,128); i64 is emulated as
  two i32 words so it pads like a 4-byte type);
- the roofline cost model (`costmodel`): HBM bandwidth and VPU/MXU
  peaks that price one window round.

Provenance: tile geometry and the 8x128 VPU / 128x128 MXU shapes
come from the Pallas TPU guide; HBM
capacity/bandwidth and peak bf16 FLOPs are the public v5e/v5p/v6e
spec-sheet numbers. `sort_gcps` (sustainable sort compares/s) is the
one deliberately soft number: on the TPU rows it assumes the
per-round `lax.sort` lowers to a vectorized bitonic network filling
the 8x128 VPU (the frontier drain's whole bet, BENCH_r07); the CPU
row is calibrated against this repo's measured single-core container
(BENCH_r07: scalar, branchy compare-exchange ~0.1 G compares/s).
Error bars are a factor of ~2 either way — the model ranks drains, it
does not predict wall seconds to a percent (docs/10-Static-Analysis.md spells this out).
"""

from __future__ import annotations

import dataclasses

GIB = 1 << 30


@dataclasses.dataclass(frozen=True)
class Chip:
    """One accelerator generation's audit-relevant parameters."""

    name: str
    lane: int                 # last-dim tile width (128 on TPU)
    sublanes: dict            # element bytes -> second-to-last tile dim
    hbm_bytes: int            # device memory capacity
    hbm_gbps: float           # memory bandwidth, GB/s
    vpu_gflops: float         # elementwise/vector peak, GFLOP/s
    mxu_tflops: float         # matmul peak (bf16), TFLOP/s; 0 = no MXU
    sort_gcps: float          # sustainable sort compare-exchanges/s, G/s
    round_overhead_us: float  # fixed per-round dispatch/latency charge

    def tile(self, elem_bytes: int) -> tuple[int, int]:
        """Minimum (sublane, lane) tile for an element width. 8-byte
        types (the engine's i64 timestamps) are emulated as two 4-byte
        words, so they tile like f32/i32."""
        b = 4 if elem_bytes >= 8 else max(int(elem_bytes), 1)
        sub = self.sublanes.get(b, self.sublanes.get(4, 1))
        return (sub, self.lane)

    def padded_dims(self, dims: list, elem_bytes: int) -> list:
        """Tile-padded physical dims for a logical shape: the last two
        dims round up to the native tile; leading dims are unpadded.
        Rank-0/rank-1 arrays occupy one tile's worth of lanes."""
        sub, lane = self.tile(elem_bytes)
        if not dims:
            return [sub, lane] if self.lane > 1 else []
        out = list(dims)
        out[-1] = _round_up(out[-1], lane)
        if len(out) >= 2:
            out[-2] = _round_up(out[-2], sub)
        elif self.lane > 1:
            out = [sub, out[-1]]
        return out

    def padded_bytes(self, dims: list, elem_bytes: int) -> int:
        n = 1
        for d in self.padded_dims(dims, elem_bytes):
            n *= int(d)
        return n * int(elem_bytes)


def _round_up(n: int, to: int) -> int:
    return -(-int(n) // int(to)) * int(to) if to > 1 else int(n)


# TPU native sublane counts by element width (Pallas guide tiling
# table): 4-byte (8,128), 2-byte (16,128), 1-byte (32,128). 8-byte
# i64 is handled in Chip.tile (two 4-byte words).
_TPU_SUBLANES = {1: 32, 2: 16, 4: 8}

CHIPS: dict[str, Chip] = {
    # v5e: 16 GiB HBM @ 819 GB/s, 197 bf16 TFLOP/s MXU per chip.
    "v5e": Chip(
        name="v5e", lane=128, sublanes=_TPU_SUBLANES,
        hbm_bytes=16 * GIB, hbm_gbps=819.0,
        vpu_gflops=3900.0, mxu_tflops=197.0, sort_gcps=450.0,
        round_overhead_us=2.0,
    ),
    # v5p: 95 GiB HBM @ 2765 GB/s, 459 bf16 TFLOP/s per chip (2 cores).
    "v5p": Chip(
        name="v5p", lane=128, sublanes=_TPU_SUBLANES,
        hbm_bytes=95 * GIB, hbm_gbps=2765.0,
        vpu_gflops=7800.0, mxu_tflops=459.0, sort_gcps=900.0,
        round_overhead_us=2.0,
    ),
    # v6e (Trillium): 32 GiB HBM @ 1640 GB/s, 918 bf16 TFLOP/s.
    "v6e": Chip(
        name="v6e", lane=128, sublanes=_TPU_SUBLANES,
        hbm_bytes=32 * GIB, hbm_gbps=1640.0,
        vpu_gflops=7800.0, mxu_tflops=918.0, sort_gcps=900.0,
        round_overhead_us=2.0,
    ),
    # The measured baseline: one CPU core of the CI container (every
    # BENCH_r* CPU number). No tiling (lane 1), no MXU; sort_gcps is
    # the scalar compare-exchange rate calibrated against BENCH_r07's
    # chained-vs-frontier gap on this box.
    "cpu": Chip(
        name="cpu", lane=1, sublanes={1: 1, 2: 1, 4: 1},
        hbm_bytes=16 * GIB, hbm_gbps=12.0,
        vpu_gflops=12.0, mxu_tflops=0.0, sort_gcps=0.1,
        round_overhead_us=0.5,
    ),
}

# Order reports/baselines list the rows in.
CHIP_NAMES = ("v5e", "v5p", "v6e", "cpu")


def chip(name: str) -> Chip:
    try:
        return CHIPS[name]
    except KeyError:
        raise KeyError(f"unknown chip `{name}` (have {CHIP_NAMES})")
