"""Paged KV cache — the TPU adaptation of PagedAttention (DESIGN.md §2).

GPU PagedAttention chases per-page pointers inside the kernel; TPUs want
dense DMA.  Layout here: one DEVICE array per layer-stack of shape
``(num_layers, num_pages, page_size, kv_heads, head_dim)`` plus an
integer page table per sequence.  The page storage is device-resident
(jnp): prefill scatters KV rows into freshly allocated pages, decode
scatters one token per sequence per step at ``(page, offset)`` computed
from the page table, and the paged decode-attention kernel (or the XLA
device gather it falls back to) reads the pages in place — the KV bytes
never round-trip through the host.  Only METADATA lives on the host:
refcounts, the free list, per-sequence page tables and lengths.

Every write into the pool outside the decode step (prefilled pages,
a partial-page fill, a copy-on-write page copy, a migrated import, the
dense-view reference path's appends) is ONE jitted program that takes
the k and v pools, donated on device backends, and returns them: XLA
scatters in place, so no pool-sized copy or second pool ever exists.
Page ids and offsets are traced int32 operands and the rows written are
padded to a power-of-two number of pages (padding targets page
``num_pages`` and is dropped), so a pool layout compiles at most
``ceil(log2(num_pages)) + 1`` writer shapes per row dtype.

This is the authoritative KV store behind the continuous-batching
``InferenceEngine``: every full-attention transformer sequence lives
here from admission to retirement.  Host staging happens exactly at the
migration boundary (``export_sequence``/``import_sequence`` — the
cross-worker wire format), never on the decode path.

Prefix sharing: pages are REFCOUNTED.  When a new sequence's prompt hits
a cached prefix (the engine's radix tree), its page table aliases the
donor's pages — the shared prefix is stored (and was computed) exactly
once.  Full pages are immutable, so aliasing them needs no copy; a
*partial* trailing page may be aliased too (the prefix need not be
page-aligned), in which case the first append by EITHER sequence into a
page with refcount > 1 triggers copy-on-write (a device-side page copy),
so neither sequence can corrupt the other's tokens.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def pool_copy(k, v, cow):
    """Copy whole pages ``cow[0] -> cow[1]`` (int32 ``(2, C)``) in both
    pools; a destination of ``num_pages`` is padding and is dropped."""
    src, dst = cow[0], cow[1]
    return (k.at[:, dst].set(k[:, src], mode="drop"),
            v.at[:, dst].set(v[:, src], mode="drop"))


def pool_write(k, v, cow, k_rows, v_rows, at):
    """The page copies of :func:`pool_copy`, then token row ``i`` of
    ``k_rows``/``v_rows`` ``(L, R, Hkv, Dh)``, cast to the pool's dtype,
    into slot ``at[1, i]`` of page ``at[0, i]`` (int32 ``(2, R)``; page
    ``num_pages`` is padding and is dropped)."""
    pages, offs = at[0], at[1]

    def layer(i, pools):
        # a layer at a time, so only one layer's rows are ever cast
        return tuple(p.at[i, pages, offs].set(r[i].astype(p.dtype),
                                              mode="drop")
                     for p, r in zip(pools, (k_rows, v_rows)))

    return jax.lax.fori_loop(0, k.shape[0], layer, pool_copy(k, v, cow))


def _pow2_at_least(n: int, start: int = 1) -> int:
    """The least ``start * 2**i`` that is at least ``n``."""
    b = start
    while b < n:
        b *= 2
    return b


def _pad_rows(a, n: int):
    """Zero-pad ``a`` (L, S, Hkv, Dh) to ``n`` token rows: on the host
    for a host block (the migration staging path), else on device."""
    pad = ((0, 0), (0, n - a.shape[1]), (0, 0), (0, 0))
    return np.pad(a, pad) if isinstance(a, np.ndarray) else jnp.pad(a, pad)


@dataclass
class SequenceEntry:
    seq_id: int
    page_ids: List[int]
    length: int                      # tokens written


class PagedKVCache:  # requires: InferenceEngine._cv | engine-loop
    """Device-resident paged KV store for ONE layer-stacked model.

    Thread contract: the cache has no lock of its own — every method
    runs either on the owning engine's loop thread or under
    ``InferenceEngine._cv`` (the engine's step-gap protocol serializes
    the two; DESIGN.md §11)."""

    def __init__(self, num_layers: int, num_pages: int, page_size: int,
                 kv_heads: int, head_dim: int, dtype=jnp.float32):
        self.num_layers = num_layers
        self.num_pages = num_pages
        self.page_size = page_size
        self.kv_heads = kv_heads
        self.head_dim = head_dim
        self.dtype = jnp.dtype(dtype)
        # (L, P, page, Hkv, Dh) — jnp on DEVICE; host never holds the KV
        shape = (num_layers, num_pages, page_size, kv_heads, head_dim)
        self.k = jnp.zeros(shape, self.dtype)   # memspace: device
        self.v = jnp.zeros(shape, self.dtype)   # memspace: device
        self.refcount = np.zeros((num_pages,), np.int64)  # memspace: host
        self.free_pages: List[int] = list(range(num_pages - 1, -1, -1))
        self.sequences: Dict[int, SequenceEntry] = {}
        self._next_seq = 0
        # copy-on-write page copies (src, dst) queued by _cow_last_page;
        # the public method that queued them dispatches them before it
        # returns, so none outlives the call
        self._copies: List[Tuple[int, int]] = []
        # the pools flow through the writers; donating them lets XLA
        # write in place on device backends (CPU ignores it)
        donate = (0, 1) if jax.default_backend() != "cpu" else ()
        self._copy_jit = jax.jit(pool_copy, donate_argnums=donate)
        self._write_jit = jax.jit(pool_write, donate_argnums=donate)
        # stats
        self.pages_shared = 0
        self.tokens_reused = 0
        self.pool_writes = 0             # donated writer dispatches
        self.pool_write_rows = 0         # token rows written, COW rows too

    # ------------------------------------------------------------ alloc/free
    def _alloc_page(self) -> int:
        if not self.free_pages:
            raise MemoryError("KV cache out of pages")
        p = self.free_pages.pop()
        self.refcount[p] = 1
        return p

    def _ref_page(self, p: int) -> None:
        self.refcount[p] += 1

    def _unref_page(self, p: int) -> None:
        self.refcount[p] -= 1
        if self.refcount[p] == 0:
            self.free_pages.append(p)

    @property
    def pages_in_use(self) -> int:
        return int((self.refcount > 0).sum())

    # ----------------------------------------------------- device plumbing
    def _write(self, k=None, v=None,
               targets: List[Tuple[int, int]] = ()) -> None:
        """Dispatch the queued copy-on-write page copies and the token
        rows of ``k``/``v`` (L, S, Hkv, Dh), row ``i`` at ``targets[i] =
        (page, offset)``, as one donated program.  ``targets`` may run
        past ``S`` to the end of the last page: those slots get zero
        rows, as a whole-page write always left them."""
        copies, self._copies = self._copies, []
        if not copies and not targets:
            return
        n_pages = self.num_pages
        cow = np.zeros((2, _pow2_at_least(len(copies))), np.int32)
        cow[1] = n_pages
        if copies:
            cow[:, :len(copies)] = list(zip(*copies))
        if targets:
            # a power of two pages, at most the pool's tokens
            r = min(_pow2_at_least(len(targets), self.page_size),
                    n_pages * self.page_size)
            at = np.zeros((2, r), np.int32)
            at[0] = n_pages
            at[:, :len(targets)] = list(zip(*targets))
            self.k, self.v = self._write_jit(
                self.k, self.v, cow, _pad_rows(k, r), _pad_rows(v, r), at)
            self.pool_write_rows += k.shape[1]
        else:
            self.k, self.v = self._copy_jit(self.k, self.v, cow)
        self.pool_writes += 1
        self.pool_write_rows += len(copies) * self.page_size

    def _cow_last_page(self, e: SequenceEntry) -> int:
        """Make the trailing page of ``e`` private (queue a device page
        copy when it is aliased); returns the (possibly new) page id."""
        p = e.page_ids[-1]
        if self.refcount[p] > 1:                 # copy-on-write partial page
            newp = self._alloc_page()
            self._copies.append((p, newp))
            self._unref_page(p)
            e.page_ids[-1] = newp
            p = newp
        return p

    def adopt_pages(self, k, v) -> None:
        """Install updated pool arrays returned by a jitted step that
        scattered this step's KV in place (the paged decode path: the
        pool is an input/output of the decode jit, donated on device)."""
        self.k = k
        self.v = v

    # --------------------------------------------------------------- write
    def add_sequence(self, k=None, v=None,
                     shared_from: Optional[int] = None,
                     shared_len: int = 0) -> int:
        """Store a prefilled sequence's KV. k/v: (L, S, Hkv, Dh) arrays
        (jnp device rows from prefill, or numpy at the import staging
        boundary) or None.

        If ``shared_from`` names an existing sequence, its first
        ``shared_len`` tokens are aliased.  A non-page-aligned
        ``shared_len`` additionally aliases the donor's *partial* page;
        that page stays copy-on-write protected, so the caller must then
        pass no bulk suffix (k is None / empty) and extend the sequence
        via :meth:`extend_sequence` / :meth:`append_token`, which perform
        the COW copy before the first private write.  Page-aligned
        sharing may carry a bulk suffix in k/v as before.
        """
        ps = self.page_size
        seq_id = self._next_seq
        self._next_seq += 1
        page_ids: List[int] = []
        length = 0

        if shared_from is not None and shared_len:
            donor = self.sequences[shared_from]
            assert donor.length >= shared_len
            n_full, tail = divmod(shared_len, ps)
            n_alias = n_full + (1 if tail else 0)
            for p in donor.page_ids[:n_alias]:
                self._ref_page(p)
                page_ids.append(p)
            length = shared_len
            self.pages_shared += n_alias
            self.tokens_reused += shared_len

        self.sequences[seq_id] = SequenceEntry(seq_id, page_ids, length)
        if k is not None and k.shape[1]:
            assert length % ps == 0, \
                "bulk suffix requires a page-aligned shared prefix; " \
                "extend_sequence() handles the copy-on-write case"
            try:
                self.extend_sequence(seq_id, k, v)
            except MemoryError:
                self.free_sequence(seq_id)
                raise
        return seq_id

    def extend_sequence(self, seq_id: int, k, v) -> None:
        """Append a bulk KV block (L, S, Hkv, Dh) at the sequence tail.

        Fills the trailing partial page first (copy-on-write if it is
        aliased), then whole fresh pages, zero-padded past the block —
        ONE donated device dispatch however long the block, which is how
        chunked prefill writes its suffix through the pool without a
        per-token loop."""
        e = self.sequences[seq_id]
        S = k.shape[1]
        if not S:
            return
        ps = self.page_size
        off = e.length % ps
        targets: List[Tuple[int, int]] = []
        if off:
            p = self._cow_last_page(e)
            targets = [(p, o) for o in range(off, min(ps, off + S))]
        pages = [self._alloc_page()
                 for _ in range(-(-(S - len(targets)) // ps))]
        targets += [(p, o) for p in pages for o in range(ps)]
        self._write(k, v, targets)
        e.page_ids.extend(pages)
        e.length += S

    def append_token(self, seq_id: int, k_t, v_t) -> None:
        """k_t/v_t: (L, Hkv, Dh) — one decode step's KV (device scatter)."""
        self.append_tokens([seq_id], k_t[:, None], v_t[:, None])

    def append_tokens(self, seq_ids: List[int], k_t, v_t) -> None:
        """One decode step's KV for a whole batch: k_t/v_t (L, B, Hkv, Dh).

        Allocates / copy-on-writes each sequence's trailing page, then
        lands the page copies and every row in ONE device dispatch (the
        dense-view reference path's append; the paged path scatters
        inside the decode jit)."""
        targets = [self._prepare_append(sid) for sid in seq_ids]
        self._write(k_t, v_t, targets)
        for sid in seq_ids:
            self.sequences[sid].length += 1

    def _prepare_append(self, seq_id: int) -> Tuple[int, int]:
        """:meth:`prepare_append` with any copy-on-write copy left
        queued for the caller's dispatch."""
        e = self.sequences[seq_id]
        slot = e.length % self.page_size
        if slot == 0:
            e.page_ids.append(self._alloc_page())
            return e.page_ids[-1], 0
        return self._cow_last_page(e), slot

    def prepare_append(self, seq_id: int) -> Tuple[int, int]:
        """Host-metadata half of a one-token append: allocate the next
        page at a boundary, copy-on-write an aliased trailing page, and
        return the ``(page, offset)`` the token's KV must land at.  The
        caller writes the KV (device scatter — possibly inside a jitted
        decode step) and then bumps the length via
        :meth:`commit_append`."""
        target = self._prepare_append(seq_id)
        self._write()
        return target

    def commit_append(self, seq_id: int, n: int = 1) -> None:
        self.sequences[seq_id].length += n

    def prepare_appends(self, seq_ids: List[int]
                        ) -> Tuple[List[int], List[int]]:
        """Batch :meth:`prepare_append` — the host-metadata half of one
        decode step for a whole batch.  After this, every returned page
        is PRIVATE to its sequence (refcount 1: boundary rows got a
        fresh page, aliased trailing pages were copy-on-written), which
        is the safety contract the fused append+attend kernel relies on
        to write ``(page, offset)`` slots inside the attention dispatch.
        The copy-on-write copies of the whole batch are one dispatch.
        Returns ``(pages, offsets)`` parallel to ``seq_ids``."""
        pages, offsets = [], []
        for sid in seq_ids:
            p, o = self._prepare_append(sid)
            pages.append(p)
            offsets.append(o)
        self._write()
        return pages, offsets

    def commit_appends(self, seq_ids: List[int], n: int = 1) -> None:
        """Batch :meth:`commit_append`: bump lengths once the step that
        wrote the prepared slots (scatter or fused kernel) has run."""
        for sid in seq_ids:
            self.commit_append(sid, n)

    # --------------------------------------------------------------- read
    def gather(self, seq_id: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Contiguous (L, T, Hkv, Dh) DEVICE views for a sequence (an
        on-device page gather; nothing crosses the host boundary)."""
        e = self.sequences[seq_id]
        idx = jnp.asarray(e.page_ids, jnp.int32)
        L, H, D = self.num_layers, self.kv_heads, self.head_dim
        k = self.k[:, idx].reshape(L, -1, H, D)
        v = self.v[:, idx].reshape(L, -1, H, D)
        return k[:, :e.length], v[:, :e.length]

    def page_table(self, seq_id: int) -> List[int]:
        return list(self.sequences[seq_id].page_ids)

    # --------------------------------------------------------- migration
    # memspace: staging (the allowlisted D2H boundary for migration)
    def export_sequence(self, seq_id: int,
                        length: Optional[int] = None
                        ) -> Tuple[np.ndarray, np.ndarray]:
        """Contiguous (L, T, Hkv, Dh) HOST COPIES of a sequence's first
        ``length`` tokens (default: all of them) — the wire format for
        cross-worker KV migration, and the ONLY device->host staging
        point in the pool.  Copies (not views) so the exported block
        stays valid after the source evicts or COWs the pages."""
        e = self.sequences[seq_id]
        n = e.length if length is None else min(length, e.length)
        L, H, D = self.num_layers, self.kv_heads, self.head_dim
        if n == 0:
            z = np.zeros((L, 0, H, D), np.float32)
            return z, z.copy()
        idx = jnp.asarray(e.page_ids[:-(-n // self.page_size)], jnp.int32)
        out_k = np.asarray(self.k[:, idx].reshape(L, -1, H, D)[:, :n],
                           np.float32)
        out_v = np.asarray(self.v[:, idx].reshape(L, -1, H, D)[:, :n],
                           np.float32)
        return out_k, out_v

    # memspace: staging (the allowlisted H2D boundary for migration)
    def import_sequence(self, k: np.ndarray, v: np.ndarray) -> int:
        """Adopt a migrated contiguous KV block: allocate pages, scatter
        the tokens in (the host->device staging point), refcount them,
        and register a new sequence.  The inverse of
        :meth:`export_sequence`; raises MemoryError if the pool cannot
        hold it (callers pre-check free pages)."""
        if k.shape != v.shape or k.shape[0] != self.num_layers \
                or k.shape[2:] != (self.kv_heads, self.head_dim):
            raise ValueError(
                f"imported KV shape {k.shape} does not match cache layout "
                f"(L={self.num_layers}, Hkv={self.kv_heads}, "
                f"Dh={self.head_dim})")
        return self.add_sequence(k=k, v=v)

    def free_sequence(self, seq_id: int) -> None:
        e = self.sequences.pop(seq_id)
        for p in e.page_ids:
            self._unref_page(p)

    # --------------------------------------------------------------- sizing
    @staticmethod
    def pool_bytes(num_layers: int, num_pages: int, page_size: int,
                   kv_heads: int, head_dim: int, dtype_bytes: int = 4) -> int:
        """Device bytes of a K+V pool of this layout (float32, the pool's
        default, unless told otherwise), before allocating one."""
        return 2 * num_layers * num_pages * page_size * kv_heads \
            * head_dim * dtype_bytes

    def hbm_bytes(self, dtype_bytes: Optional[int] = None) -> int:
        """Pool footprint in bytes.  Defaults to the POOL's element
        width — the old ``=2`` default silently assumed bf16 while the
        pool allocates f32, undercounting by 2x."""
        return self.pool_bytes(self.num_layers, self.num_pages,
                               self.page_size, self.kv_heads, self.head_dim,
                               dtype_bytes or self.dtype.itemsize)
