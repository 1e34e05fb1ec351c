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

import jax.numpy as jnp
import numpy as np


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
        # stats
        self.pages_shared = 0
        self.tokens_reused = 0

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
    def _page_blocks(self, a) -> jnp.ndarray:
        """(L, S, Hkv, Dh) -> (L, n_pages, page, Hkv, Dh), zero-padded to
        whole pages, in pool dtype on device."""
        a = jnp.asarray(a, self.dtype)
        S = a.shape[1]
        ps = self.page_size
        n = -(-S // ps)
        pad = n * ps - S
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return a.reshape(self.num_layers, n, ps, self.kv_heads, self.head_dim)

    def _write_pages(self, pages: List[int], k, v) -> None:
        """Scatter whole-page blocks into freshly allocated pages."""
        idx = jnp.asarray(pages, jnp.int32)
        self.k = self.k.at[:, idx].set(self._page_blocks(k))
        self.v = self.v.at[:, idx].set(self._page_blocks(v))

    def _cow_last_page(self, e: SequenceEntry) -> int:
        """Make the trailing page of ``e`` private (device page copy when
        it is aliased); returns the (possibly new) page id."""
        p = e.page_ids[-1]
        if self.refcount[p] > 1:                 # copy-on-write partial page
            newp = self._alloc_page()
            self.k = self.k.at[:, newp].set(self.k[:, p])
            self.v = self.v.at[:, newp].set(self.v[:, p])
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

        S = 0 if k is None else k.shape[1]
        if S:
            assert length % ps == 0, \
                "bulk suffix requires a page-aligned shared prefix; " \
                "extend_sequence() handles the copy-on-write case"
            pages = [self._alloc_page() for _ in range(-(-S // ps))]
            self._write_pages(pages, k, v)
            page_ids.extend(pages)
            length += S
        self.sequences[seq_id] = SequenceEntry(seq_id, page_ids, length)
        return seq_id

    def extend_sequence(self, seq_id: int, k, v) -> None:
        """Append a bulk KV block (L, S, Hkv, Dh) at the sequence tail.

        Fills the trailing partial page first (copy-on-write if it is
        aliased), then scatters whole pages — O(1) device calls however
        long the block, which is how chunked prefill writes its suffix
        through the pool without a per-token loop."""
        e = self.sequences[seq_id]
        k = jnp.asarray(k, self.dtype)
        v = jnp.asarray(v, self.dtype)
        S = k.shape[1]
        ps = self.page_size
        off = e.length % ps
        if off and S:
            p = self._cow_last_page(e)
            n = min(ps - off, S)
            self.k = self.k.at[:, p, off:off + n].set(k[:, :n])
            self.v = self.v.at[:, p, off:off + n].set(v[:, :n])
            e.length += n
            k, v = k[:, n:], v[:, n:]
            S -= n
        if S:
            pages = [self._alloc_page() for _ in range(-(-S // ps))]
            self._write_pages(pages, k, v)
            e.page_ids.extend(pages)
            e.length += S

    def append_token(self, seq_id: int, k_t, v_t) -> None:
        """k_t/v_t: (L, Hkv, Dh) — one decode step's KV (device scatter)."""
        e = self.sequences[seq_id]
        p, slot = self.prepare_append(seq_id)
        self.k = self.k.at[:, p, slot].set(jnp.asarray(k_t, self.dtype))
        self.v = self.v.at[:, p, slot].set(jnp.asarray(v_t, self.dtype))
        e.length += 1

    def append_tokens(self, seq_ids: List[int], k_t, v_t) -> None:
        """One decode step's KV for a whole batch: k_t/v_t (L, B, Hkv, Dh).

        Allocates / copy-on-writes each sequence's trailing page, then
        lands every row in ONE device scatter (the dense-view reference
        path's append; the paged path scatters inside the decode jit)."""
        pages, slots = [], []
        for sid in seq_ids:
            p, s = self.prepare_append(sid)
            pages.append(p)
            slots.append(s)
        pi = jnp.asarray(pages, jnp.int32)
        si = jnp.asarray(slots, jnp.int32)
        self.k = self.k.at[:, pi, si].set(jnp.asarray(k_t, self.dtype))
        self.v = self.v.at[:, pi, si].set(jnp.asarray(v_t, self.dtype))
        for sid in seq_ids:
            self.sequences[sid].length += 1

    def prepare_append(self, seq_id: int) -> Tuple[int, int]:
        """Host-metadata half of a one-token append: allocate the next
        page at a boundary, copy-on-write an aliased trailing page, and
        return the ``(page, offset)`` the token's KV must land at.  The
        caller writes the KV (device scatter — possibly inside a jitted
        decode step) and then bumps the length via
        :meth:`commit_append`."""
        e = self.sequences[seq_id]
        slot = e.length % self.page_size
        if slot == 0:
            e.page_ids.append(self._alloc_page())
            return e.page_ids[-1], 0
        return self._cow_last_page(e), slot

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
        Returns ``(pages, offsets)`` parallel to ``seq_ids``."""
        pages, offsets = [], []
        for sid in seq_ids:
            p, o = self.prepare_append(sid)
            pages.append(p)
            offsets.append(o)
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
