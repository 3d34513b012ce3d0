"""Fused queue-merge Pallas kernel: densify + row sort in one pass.

`queue_push` (core.events) splits a push into a flat grouping sort, a
gather densify, and a merge of each row's sorted incoming block into its
resident row. The merge is one stable row-wise `lax.sort` over
[resident | block] keyed on (time, srcseq), with the payload words
riding along as sort operands: the TPU sorts rows cheaply, while a
computed-index gather along a row costs milliseconds per push. With
``kernel="xla"`` the densify and the sort lower as separate XLA ops;
this module also runs them as ONE Pallas kernel invocation per merge
round.

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
    """The densify + row-sort arithmetic, shared verbatim by the Pallas
    kernel body and the plain-XLA path (`queue_push` calls this directly
    when kernel="xla"). Shapes: qt/qss [H, hc], qpay [H, hc, nw], st/sss
    [m] flat grouped keys, bpay [H, w, nw], starts/cnt [H]. Returns
    (mt, mss, mpay) of width hc + w: each row of [resident | block]
    sorted stably by (time, srcseq), so ties keep resident-first order.

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
    """One fused densify + row-sort pass over the hot columns.

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
