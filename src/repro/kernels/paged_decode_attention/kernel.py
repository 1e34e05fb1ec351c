"""Paged GQA flash-decode — Pallas TPU kernels over non-contiguous pages.

The decode-attention kernel streams a *contiguous* per-sequence KV block;
these kernels attend directly over the engine's device-resident page
pool, so the dense gather that used to materialize each sequence (the
host ``_rebuild_view`` round-trip) never happens.  Three variants share
the flash-decode recurrence from ``kernels/common``:

* ``single``  — one physical page (every KV head of it) per grid step,
  fetched by BlockSpec indexing through the scalar-prefetched page
  table (``pltpu.PrefetchScalarGridSpec``).  Kept as the A/B baseline.
* ``blocked`` — the innermost grid dim covers ``pages_per_block >= 2``
  physical pages per step.  The pool stays in ANY/HBM and each block is
  hand-DMA'd into a 2-slot VMEM scratch ring, double-buffered: block
  ``i+1``'s DMA is issued before block ``i``'s compute, so page fetches
  overlap the matmuls.  Per-row early-out: a page whose positions start
  past ``lengths[b]`` is neither copied nor multiplied, so short rows
  stop paying for the longest row's page count.
* ``fused``   — ``blocked`` plus the scatter-append folded in: the
  newest token's KV rows (one ``(Hkv, Dh)`` row per sequence) are
  DMA'd into their ``(page, offset)`` pool slots INSIDE the same
  ``pallas_call``, before any page of that row is read.  This removes
  the separate scatter dispatch in ``TransformerLM.paged_decode_step``
  and one full pool round-trip per layer per step.  The pool operands
  are aliased to outputs (``input_output_aliases``) so the append is
  in-place.

Grid: ``(B, Hkv, n)`` (layout ``bh``) or ``(Hkv, B, n)`` (layout
``hb``), block/page dim innermost — TPU grids run sequentially with the
last dim minor, which is what makes the fused write-before-read ordering
sound.  Positions are implicit: page ``i`` of a row's table holds tokens
``[i*page_size, (i+1)*page_size)``, valid while ``<= lengths[b]``
(``lengths[b]`` is the query position).  Rows with ``lengths[b] < 0``
are padding: fully masked, output zeros, and — fused — nothing written.

Fused-append contract (DESIGN.md §3): the write target is derived
in-kernel from the prefetched scalars — ``page_table[b, len // page]``
at offset ``len % page`` — and that page must be PRIVATE to row ``b``
(refcount 1).  ``PagedKVCache.prepare_append`` guarantees this: a row
at a page boundary gets a fresh page, a row appending into a shared
page gets a copy-on-write clone first.  Aliased *read* pages (shared
prefixes) remain fine — only the append page must be exclusive.

The optional (m, l) outputs expose the log-sum-exp state for combining
with other passes (e.g. a shared-prefix split), mirroring
``decode_attention``; fully-masked rows are pinned to
``(NEG_INF, 0)`` by ``finalize_online_softmax``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (NEG_INF, finalize_online_softmax,
                                  online_softmax_update, qk_logits)

GRID_LAYOUTS = ("bh", "hb")


def _paged_decode_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref,
                         o_ref, m_out_ref, l_out_ref,
                         acc_ref, m_ref, l_ref, *,
                         scale: float, page_size: int, n_pages: int,
                         n_kv_heads: int):
    b = pl.program_id(0)
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]                                  # query position
    # token t of page slot j is position it*page_size + j in the
    # sequence; stale / unwritten slots sit past `length` and padding
    # rows carry length < 0 (everything masked)
    kv_pos = it * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    mask = kv_pos <= length                              # (1, page)

    for h in range(n_kv_heads):          # the page block holds every head
        q = q_ref[0, h].astype(jnp.float32)              # (G, Dh)
        k = k_ref[0, :, h, :].astype(jnp.float32)        # (page, Dh)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        logits = qk_logits(q, k, scale)                  # (G, page)
        acc_ref[h], m_ref[h, :, 0], l_ref[h, :, 0] = online_softmax_update(
            logits, mask, v, acc_ref[h], m_ref[h, :, 0], l_ref[h, :, 0])

    @pl.when(it == n_pages - 1)
    def _done():
        for h in range(n_kv_heads):
            out, m, l = finalize_online_softmax(
                acc_ref[h], m_ref[h, :, 0], l_ref[h, :, 0])
            o_ref[0, h] = out.astype(o_ref.dtype)
            m_out_ref[0, h, :, 0] = m
            l_out_ref[0, h, :, 0] = l


# vmem-budget: 0.6 MiB @ page_size=64 Dh=128 H=32 Hkv=8
def paged_decode_attention_kernel(q, k_pages, v_pages, page_table, lengths,
                                  *, interpret: bool = False):
    """q: (B,H,Dh); k_pages/v_pages: (P, page, Hkv, Dh) — the pool;
    page_table: (B, n_pages) int32; lengths: (B,) int32 (-1 = padding).

    One grid step per (row, page): the BlockSpec fetches the whole
    physical page — every KV head, so the block's last two dims are the
    pool's own (Hkv, Dh) — and the kernel attends each head in turn.

    Returns (out (B,H,Dh), m (B,H), l (B,H)).
    """
    B, H, Dh = q.shape
    P, page_size, Hkv, _ = k_pages.shape
    n_pages = page_table.shape[1]
    G = H // Hkv
    grid = (B, n_pages)
    qg = q.reshape(B, Hkv, G, Dh)

    kernel = functools.partial(
        _paged_decode_kernel, scale=1.0 / math.sqrt(Dh),
        page_size=page_size, n_pages=n_pages, n_kv_heads=Hkv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,               # page_table, lengths
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Hkv, G, Dh), lambda b, i, pt, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, page_size, Hkv, Dh),
                         lambda b, i, pt, ln: (pt[b, i], 0, 0, 0)),
            pl.BlockSpec((1, page_size, Hkv, Dh),
                         lambda b, i, pt, ln: (pt[b, i], 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Hkv, G, Dh), lambda b, i, pt, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, Hkv, G, 1), lambda b, i, pt, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, Hkv, G, 1), lambda b, i, pt, ln: (b, 0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, Dh), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
        ],
    )
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, G, Dh), q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(page_table, lengths, qg, k_pages, v_pages)
    return (out.reshape(B, H, Dh), m.reshape(B, H), l.reshape(B, H))


def _paged_blocked_kernel(pt_ref, len_ref, *refs, scale: float,
                          page_size: int, ppb: int, nb: int,
                          layout: str, fused: bool):
    """Shared body of the ``blocked`` and ``fused`` variants.

    Positional refs after the two scalar-prefetch refs:
      blocked: q, k_hbm, v_hbm | o, m_out, l_out
               | acc, m, l, k_buf, v_buf, sems
      fused:   q, k_hbm, v_hbm, k_new, v_new | o, m_out, l_out, k_out,
               v_out | acc, m, l, k_buf, v_buf, sems, wsem
    With fused the pool inputs are aliased to (k_out, v_out); all pool
    traffic goes through the OUTPUT refs so the in-kernel append and the
    block reads see one coherent buffer.
    """
    if fused:
        (q_ref, _k_in, _v_in, knew_ref, vnew_ref,
         o_ref, m_out_ref, l_out_ref, k_hbm, v_hbm,
         acc_ref, m_ref, l_ref, k_buf, v_buf, sems, wsem) = refs
    else:
        (q_ref, k_hbm, v_hbm,
         o_ref, m_out_ref, l_out_ref,
         acc_ref, m_ref, l_ref, k_buf, v_buf, sems) = refs

    if layout == "bh":
        b, h, it = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    else:                                    # "hb": Hkv outermost
        h, b, it = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    length = len_ref[b]                                  # query position
    # pages holding positions <= length; 0 for padding rows -> the row
    # issues no DMA and no compute (the per-row early-out)
    np_b = jnp.where(length < 0, 0, length // page_size + 1)

    @pl.when(it == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    if fused:
        # Append the newest token's KV before ANY page of row b is read.
        # First visit of row b is (hkv 0, block 0) under both layouts;
        # the wait() before the warm-up reads below gives write->read
        # ordering on the sequential TPU grid.  Padding rows write
        # nothing (DMA has no out-of-bounds drop mode, so gate, never
        # clamp).  Target page is private to row b by the
        # prepare_append COW contract (module docstring).
        @pl.when((h == 0) & (it == 0) & (length >= 0))
        def _append_new():
            wp = pt_ref[b, length // page_size]
            wo = length % page_size
            ck = pltpu.make_async_copy(
                knew_ref.at[pl.ds(b, 1)], k_hbm.at[wp, pl.ds(wo, 1)],
                wsem.at[0])
            cv = pltpu.make_async_copy(
                vnew_ref.at[pl.ds(b, 1)], v_hbm.at[wp, pl.ds(wo, 1)],
                wsem.at[1])
            ck.start()
            cv.start()
            ck.wait()
            cv.wait()

    def block_dma(j, slot, start: bool):
        # start/wait the per-page copies of block j into ring slot
        # `slot`; both gate on the SAME per-page live predicate (np_b
        # depends only on b, constant along the block dim), so every
        # started DMA is waited exactly once.
        for jj in range(ppb):
            idx = j * ppb + jj
            page = pt_ref[b, idx]

            @pl.when(idx < np_b)
            def _():
                ck = pltpu.make_async_copy(
                    k_hbm.at[page, :, h], k_buf.at[slot, jj],
                    sems.at[slot, jj, 0])
                cv = pltpu.make_async_copy(
                    v_hbm.at[page, :, h], v_buf.at[slot, jj],
                    sems.at[slot, jj, 1])
                if start:
                    ck.start()
                    cv.start()
                else:
                    ck.wait()
                    cv.wait()

    # double buffering: warm-up block 0, then issue block it+1 before
    # waiting on block it, so the next fetch overlaps this compute
    @pl.when(it == 0)
    def _warmup():
        block_dma(it, it % 2, start=True)

    @pl.when(it + 1 < nb)
    def _prefetch_next():
        block_dma(it + 1, (it + 1) % 2, start=True)

    block_dma(it, it % 2, start=False)

    q = q_ref[0, 0, :, :].astype(jnp.float32)            # (G, Dh)
    slot = it % 2
    for jj in range(ppb):
        # per-page compute, gated: dead pages hold stale VMEM garbage
        # (never DMA'd), so they must not reach the matmul
        @pl.when(it * ppb + jj < np_b)
        def _page_update():
            k = k_buf[slot, jj].astype(jnp.float32)      # (page, Dh)
            v = v_buf[slot, jj].astype(jnp.float32)
            logits = qk_logits(q, k, scale)              # (G, page)
            kv_pos = (it * ppb + jj) * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (1, page_size), 1)[0]
            mask = kv_pos <= length
            acc_ref[...], m_ref[:, 0], l_ref[:, 0] = online_softmax_update(
                logits, mask[None, :], v,
                acc_ref[...], m_ref[:, 0], l_ref[:, 0])

    @pl.when(it == nb - 1)
    def _done():
        out, m, l = finalize_online_softmax(
            acc_ref[...], m_ref[:, 0], l_ref[:, 0])
        o_ref[0, 0, :, :] = out.astype(o_ref.dtype)
        m_out_ref[0, 0, :, 0] = m
        l_out_ref[0, 0, :, 0] = l


def _blocked_specs(B, Hkv, G, Dh, nb, layout):
    """Grid + q/out BlockSpecs for both grid layouts (block dim minor)."""
    if layout == "bh":
        grid = (B, Hkv, nb)

        def qmap(b, h, i, pt, ln):
            return (b, h, 0, 0)

        def smap(b, h, i, pt, ln):
            return (b, h, 0, 0)
    else:
        grid = (Hkv, B, nb)

        def qmap(h, b, i, pt, ln):
            return (b, h, 0, 0)

        def smap(h, b, i, pt, ln):
            return (b, h, 0, 0)
    q_spec = pl.BlockSpec((1, 1, G, Dh), qmap)
    o_spec = pl.BlockSpec((1, 1, G, Dh), smap)
    ml_spec = pl.BlockSpec((1, 1, G, 1), smap)
    return grid, q_spec, o_spec, ml_spec


def _pad_page_table(page_table, ppb):
    """Pad the page dim to a multiple of ppb; padded entries are never
    DMA'd (they sit past every row's np_b) so the pad value is inert."""
    n_pages = page_table.shape[1]
    pad = (-n_pages) % ppb
    if pad:
        page_table = jnp.pad(page_table, ((0, 0), (0, pad)))
    return page_table, (n_pages + pad) // ppb


# vmem-budget: 0.6 MiB @ pages_per_block=4 page_size=64 Dh=128 H=32 Hkv=8
def paged_decode_attention_blocked_kernel(q, k_pages, v_pages, page_table,
                                          lengths, *, pages_per_block: int,
                                          grid_layout: str = "bh",
                                          interpret: bool = False):
    """Multi-page double-buffered variant.  Same contract as
    :func:`paged_decode_attention_kernel`; ``pages_per_block`` pages are
    hand-DMA'd per grid step (the table is padded up to a multiple — a
    row whose page count the block size does not divide simply has dead
    tail pages in its last block).
    """
    B, H, Dh = q.shape
    P, page_size, Hkv, _ = k_pages.shape
    G = H // Hkv
    ppb = pages_per_block
    assert ppb >= 1
    assert grid_layout in GRID_LAYOUTS
    page_table, nb = _pad_page_table(page_table, ppb)
    qg = q.reshape(B, Hkv, G, Dh)

    kernel = functools.partial(
        _paged_blocked_kernel, scale=1.0 / math.sqrt(Dh),
        page_size=page_size, ppb=ppb, nb=nb, layout=grid_layout,
        fused=False)

    grid, q_spec, o_spec, ml_spec = _blocked_specs(B, Hkv, G, Dh, nb,
                                                   grid_layout)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,               # page_table, lengths
        grid=grid,
        in_specs=[
            q_spec,
            pl.BlockSpec(memory_space=pl.ANY),    # k pool stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),    # v pool stays in HBM
        ],
        out_specs=[o_spec, ml_spec, ml_spec],
        scratch_shapes=[
            pltpu.VMEM((G, Dh), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((2, ppb, page_size, Dh), k_pages.dtype),
            pltpu.VMEM((2, ppb, page_size, Dh), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, ppb, 2)),
        ],
    )
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, G, Dh), q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(page_table, lengths, qg, k_pages, v_pages)
    return (out.reshape(B, H, Dh), m.reshape(B, H), l.reshape(B, H))


# vmem-budget: 0.6 MiB @ pages_per_block=4 page_size=64 Dh=128 H=32 Hkv=8
def fused_paged_decode_attention_kernel(q, k_pages, v_pages, page_table,
                                        lengths, k_new, v_new, *,
                                        pages_per_block: int,
                                        grid_layout: str = "bh",
                                        interpret: bool = False):
    """Blocked variant with the scatter-append fused in.

    k_new/v_new: (B, Hkv, Dh) — the newest token's KV rows, written to
    ``page_table[b, lengths[b] // page] . (lengths[b] % page)`` inside
    the kernel (nothing written for padding rows).  The pool arrays are
    aliased in-place; callers must treat the INPUT pool buffers as
    consumed (the jit wrapper in ops.py donates them).

    Returns (out (B,H,Dh), m (B,H), l (B,H), k_pages, v_pages).
    """
    B, H, Dh = q.shape
    P, page_size, Hkv, _ = k_pages.shape
    G = H // Hkv
    ppb = pages_per_block
    assert ppb >= 1
    assert grid_layout in GRID_LAYOUTS
    assert k_new.shape == (B, Hkv, Dh)
    assert k_new.dtype == k_pages.dtype and v_new.dtype == v_pages.dtype, \
        "fused append DMAs raw bytes: new-KV dtype must match the pool"
    page_table, nb = _pad_page_table(page_table, ppb)
    qg = q.reshape(B, Hkv, G, Dh)

    kernel = functools.partial(
        _paged_blocked_kernel, scale=1.0 / math.sqrt(Dh),
        page_size=page_size, ppb=ppb, nb=nb, layout=grid_layout,
        fused=True)

    grid, q_spec, o_spec, ml_spec = _blocked_specs(B, Hkv, G, Dh, nb,
                                                   grid_layout)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,               # page_table, lengths
        grid=grid,
        in_specs=[q_spec, any_spec, any_spec, any_spec, any_spec],
        out_specs=[o_spec, ml_spec, ml_spec, any_spec, any_spec],
        scratch_shapes=[
            pltpu.VMEM((G, Dh), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((2, ppb, page_size, Dh), k_pages.dtype),
            pltpu.VMEM((2, ppb, page_size, Dh), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, ppb, 2)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out, m, l, k_out, v_out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, G, Dh), q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # operand indices COUNT the scalar-prefetch args: (pt, lens, q,
        # k_pages, v_pages, k_new, v_new) -> pools are 3 and 4
        input_output_aliases={3: 3, 4: 4},
        interpret=interpret,
    )(page_table, lengths, qg, k_pages, v_pages, k_new, v_new)
    return (out.reshape(B, H, Dh), m.reshape(B, H), l.reshape(B, H),
            k_out, v_out)
