"""Fused queue-merge Pallas kernel: densify + merge in one pass.

`queue_push` (core.events) splits a push into a flat grouping sort, a
gather densify, and a stable merge of each row's sorted incoming block
into its sorted resident prefix. With ``kernel="xla"`` those last two
stages lower as separate XLA ops — gathers and broadcast compares that
each round-trip the hot columns through memory. This module fuses them
into ONE Pallas kernel invocation per merge round: the kernel reads the
flat grouped key arrays and the queue's hot columns once, densifies the
per-destination runs by value-level gather, rotates each row's
cleared-empty prefix out, computes stable merge-path positions, and
writes the merged rows — a single pass over the hot columns.

The arithmetic is element-for-element the same as the XLA path, so the
two kernels are bit-identical on every input (pinned by
tests/test_kernel_equivalence.py, including spill-ring eviction order).

On the CPU the kernel runs under ``interpret=True``, which executes the
same jnp ops inside the jitted program, so the CPU suite exercises the
identical arithmetic. On any other backend it does not run at all: the
TPU's Mosaic compiler refuses this kernel (`MOSAIC_REFUSAL`), and
`Engine` refuses ``kernel="pallas"`` off the CPU when it is built
rather than falling back to the interpreter without a word
(tests/test_tpu_compile.py pins the refusal against a described v5e).
Whether to rewrite it on i32 key pairs with a grid over hosts, or to
delete it, is ROADMAP C2.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from shadow_tpu.core.timebase import TIME_INVALID

_I64MAX = jnp.iinfo(jnp.int64).max

# Why the TPU compiler refuses the kernel, as Mosaic reports it when
# compiling for v5e: i64 queue keys ("64-bit types are not supported")
# and the value-level row gathers ("Only 2D gather is supported").
MOSAIC_REFUSAL = (
    "Mosaic refuses the fused merge kernel on the TPU: its queue keys "
    "are 64-bit types, which Mosaic does not support, and its row "
    "gathers are not 2-D gathers, the only gather Mosaic supports"
)


def merge_body(qt, qss, qpay, st, sss, bpay, starts, cnt):
    """The densify + rotate + merge arithmetic, shared verbatim by the
    Pallas kernel body and the plain-XLA path (`queue_push` calls this
    directly when kernel="xla"). Shapes: qt/qss [H, hc], qpay
    [H, hc, nw], st/sss [m] flat grouped keys, bpay [H, w, nw],
    starts/cnt [H]."""
    h, hc = qt.shape
    w = bpay.shape[1]
    m = st.shape[0]

    # densify: group g's admitted events sit at flat positions
    # starts[g] .. starts[g]+cnt[g]-1 in key order; masked lanes become
    # fillers with the same key an empty-padded sort would produce
    lane = jnp.arange(w, dtype=jnp.int32)
    gidx = starts[:, None] + lane[None, :]
    okl = lane[None, :] < cnt[:, None]
    gsafe = jnp.minimum(gidx, m - 1)
    bt = jnp.where(okl, st[gsafe], _I64MAX)
    bss = jnp.where(okl, sss[gsafe], _I64MAX)

    # rotate the cleared-empty prefix to the tail: rows arrive as
    # [empties x k | valid ascending | empties] (the engine's frontier
    # prefix-clear), and every empty is canonical (t=INV, ss=0, pay=0)
    inv = qt == TIME_INVALID
    k = jnp.sum(jnp.cumprod(inv.astype(jnp.int32), axis=1), axis=1)
    ridx = jnp.arange(hc, dtype=jnp.int32)[None, :] + k[:, None]
    rin = ridx < hc
    rsafe = jnp.minimum(ridx, hc - 1)
    gat = lambda x, fill: jnp.where(
        rin, jnp.take_along_axis(x, rsafe, axis=1), fill
    )
    at = gat(qt, _I64MAX)
    ass = gat(qss, 0)
    apay = jnp.where(
        rin[:, :, None],
        jnp.take_along_axis(qpay, rsafe[:, :, None], axis=1),
        0,
    )

    # stable merge-path: A ([H, hc] sorted) + B ([H, w] sorted); ties
    # place A first, matching lax.sort's stability over [A | B]
    le = (at[:, :, None] < bt[:, None, :]) | (
        (at[:, :, None] == bt[:, None, :])
        & (ass[:, :, None] <= bss[:, None, :])
    )
    pos_b = lane[None, :] + jnp.sum(le, axis=1, dtype=jnp.int32)  # [H, w]
    ncol = hc + w
    p = jnp.arange(ncol, dtype=jnp.int32)[None, :]
    jb = jnp.sum(
        pos_b[:, None, :] <= p[:, :, None], axis=2, dtype=jnp.int32
    )  # [H, ncol]: incoming events placed at or before each output slot
    ib = jnp.clip(jb - 1, 0, w - 1)
    isb = (jb > 0) & (jnp.take_along_axis(pos_b, ib, axis=1) == p)
    ia = jnp.clip(p - jb, 0, hc - 1)
    mrg = lambda xa, xb: jnp.where(
        isb,
        jnp.take_along_axis(xb, ib, axis=1),
        jnp.take_along_axis(xa, ia, axis=1),
    )
    mt = mrg(at, bt)
    mss = mrg(ass, bss)
    mpay = jnp.where(
        isb[:, :, None],
        jnp.take_along_axis(bpay, ib[:, :, None], axis=1),
        jnp.take_along_axis(apay, ia[:, :, None], axis=1),
    )
    return mt, mss, mpay


def _kernel(qt_ref, qss_ref, qpay_ref, st_ref, sss_ref, bpay_ref,
            starts_ref, cnt_ref, ot_ref, oss_ref, opay_ref):
    mt, mss, mpay = merge_body(
        qt_ref[...], qss_ref[...], qpay_ref[...], st_ref[...], sss_ref[...],
        bpay_ref[...], starts_ref[...], cnt_ref[...],
    )
    ot_ref[...] = mt
    oss_ref[...] = mss
    opay_ref[...] = mpay


@functools.lru_cache(maxsize=None)
def _build_call(h, hc, w, m, nw, interpret):
    from jax.experimental import pallas as pl

    ncol = hc + w
    i64 = jnp.int64
    return pl.pallas_call(
        _kernel,
        out_shape=(
            jax.ShapeDtypeStruct((h, ncol), i64),
            jax.ShapeDtypeStruct((h, ncol), i64),
            jax.ShapeDtypeStruct((h, ncol, nw), i64),
        ),
        interpret=interpret,
    )


def fused_merge(qt, qss, qpay, st, sss, bpay, starts, cnt, *,
                interpret: bool | None = None):
    """One fused densify + rotate + merge pass over the hot columns.

    Returns (mt, mss, mpay) merged rows of width hc + w, exactly what
    `lax.sort` over [resident | block] with key (time, srcseq) yields.
    `interpret` defaults to True only when the first device is a CPU.
    """
    h, hc = qt.shape
    w = bpay.shape[1]
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    call = _build_call(h, hc, w, st.shape[0], qpay.shape[-1], interpret)
    return call(qt, qss, qpay, st, sss, bpay, starts, cnt)
