"""InferenceEngine — continuous batching over a paged KV cache.

This is the pure-JAX stand-in for a vLLM instance (DESIGN.md §2), rebuilt
around slot-based continuous batching (the Processor's "adaptive
batching, KV-cache sharing and migration"):

* a persistent engine loop owns a fixed-capacity decode batch; requests
  are *submitted* into it (``submit()`` returns a handle, ``generate()``
  is submit-then-wait) and are admitted mid-decode — prefill for new
  arrivals is interleaved between decode steps, so a request never waits
  for the running batch to drain;
* variable-length prompts coexist in one batch via per-row lengths and
  attention masking — there is no group-by-prompt-length step and no
  dense cache tiling;
* for full-attention transformers the ONLY KV store is the refcounted
  DEVICE-RESIDENT ``PagedKVCache``: prefill scatters KV rows into pages
  on device, and each decode step runs ``paged_decode_step`` straight
  over the pool — the new token's KV is scattered at (page, offset)
  computed from the per-slot page table inside the jitted step, and
  attention reads the non-contiguous pages in place (paged Pallas
  kernel, or an on-device gather under the XLA impl).  Per-step
  host<->device traffic is O(batch) ints (tokens, page tables, sampled
  ids), not O(batch x seq_len) KV bytes; batch-composition changes are
  free.  The dense-view reference path (gather-to-view + decode_step +
  KV tap sync) remains behind ``paged_decode=False`` for A/B and for
  models without the paged hook.  Prompt prefixes found in the
  ``RadixPrefixTree`` are served by aliasing the donor's pages
  (copy-on-write guards partial pages) and chunk-prefilling only the
  unseen suffix;
* recurrent / ring-buffer families (ssm, hybrid, audio, SWA) have no
  token-paged KV; the same scheduler batches their per-sequence state as
  dense rows (split/stacked via ``cache_batch_axes``);
* exact-duplicate (prompt, decode-params) requests are coalesced against
  the in-flight batch (per-request sampling streams are deterministic,
  so duplicates are provably identical at any temperature);
* outputs are bitwise-identical at temperature 0 regardless of admission
  timing: rows are computed independently and masked padding contributes
  exact zeros.

All numerics run on CPU with tiny smoke configs in tests; the same code
lowers under pjit for the dry-run meshes.
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time
import zlib
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.debugsync import named_condition, named_lock
from repro.engine.kvcache import PagedKVCache
from repro.engine.models import build_model
from repro.engine.prefix_tree import RadixPrefixTree
from repro.engine.sampling import sample


@functools.partial(jax.jit, static_argnames=("vocab_size",))
def _batched_sample(logits, keys, temps, *, vocab_size: int):
    """Sample every active row in ONE device call.

    logits: (B, Vpad); keys: (B, 2) per-slot PRNG keys (ignored for
    greedy rows); temps: (B,) float32.  Row-for-row bitwise identical to
    the per-slot ``sample()`` loop it replaces: argmax is per-row, and a
    vmapped split/categorical over a row's key draws the same bits as
    the single-row call (threefry bits depend on flat size only) — so
    per-slot RNG streams are preserved exactly.  Returns (tokens (B,)
    int32, advanced keys (B, 2))."""
    lg = logits.astype(jnp.float32)
    if vocab_size and vocab_size < lg.shape[-1]:
        mask = jnp.arange(lg.shape[-1], dtype=jnp.int32) < vocab_size
        lg = jnp.where(mask, lg, -1e30)
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    stoch = temps > 0.0
    pairs = jax.vmap(jax.random.split)(keys)             # (B, 2, 2)
    new_keys, subs = pairs[:, 0], pairs[:, 1]
    safe_t = jnp.where(stoch, temps, 1.0)[:, None]
    drawn = jax.vmap(jax.random.categorical)(subs, lg / safe_t)
    tokens = jnp.where(stoch, drawn.astype(jnp.int32), greedy)
    return tokens, jnp.where(stoch[:, None], new_keys, keys)


@functools.partial(jax.jit, static_argnames=("vocab_size",))
def _sample_counted(logits, keys, temps, read, *, vocab_size: int):
    """``_batched_sample`` for a MoE step: the step's experts read (int32
    scalar) rides after the (B,) ids, so that it reaches the host in the
    same transfer."""
    tokens, keys = _batched_sample(logits, keys, temps,
                                   vocab_size=vocab_size)
    return jnp.append(tokens, read), keys


@functools.partial(jax.jit, static_argnums=0)
def _init_params(cfg: ModelConfig, key):
    """Seeded random weights in one compiled program (cached per config):
    eagerly, every initializer op is its own dispatch and each weight
    passes through a float32 copy before its cast."""
    return build_model(cfg).init(key)


@dataclass
class EngineStats:
    prefill_tokens: int = 0
    prefill_tokens_saved: int = 0        # tokens served from shared pages
    decode_tokens: int = 0
    decode_steps: int = 0
    decode_kv_tokens: int = 0            # live KV tokens read, summed over steps
    pool_pages_used: int = 0             # pages not free, summed over steps
    pool_pages_total: int = 0            # num_pages, summed over steps
    pool_writes: int = 0                 # donated pool-writer dispatches
    pool_write_rows: int = 0             # token rows they wrote (COW too)
    admit_deferrals: int = 0             # admissions deferred for pages
    moe_token_slots: int = 0             # token-expert slots computed
    moe_experts_read: int = 0            # experts with a slot, summed over
                                         # MoE layer calls
    coalesced_requests: int = 0
    prefix_hits: int = 0
    admission_waves: int = 0             # scheduler passes that admitted >=1
    priority_jumps: int = 0              # admissions that bypassed FIFO order
    peak_batch: int = 0                  # max concurrent decode slots
    pages_shared: int = 0                # mirrored from PagedKVCache
    tokens_reused: int = 0               # mirrored from PagedKVCache
    pages_migrated_in: int = 0           # pages imported from a peer engine
    pages_migrated_out: int = 0          # pages exported to a peer engine
    migrate_seconds: float = 0.0         # modeled link-transfer time (import side)
    h2d_bytes: int = 0                   # host->device traffic (KV + step inputs)
    d2h_bytes: int = 0                   # device->host traffic (KV + sampled ids)
    view_rebuilds: int = 0               # dense decode-view materializations

    def as_dict(self) -> Dict[str, float]:
        return dict(self.__dict__)


class EngineError(RuntimeError):
    pass


class RequestHandle:
    """Completion handle for one submitted request.

    ``add_done_callback`` is the per-request pipelining hook: the real
    Processor publishes each query's result (and wakes its downstream
    tool tasks) the moment that request retires, instead of waiting for
    the slowest request of the macro-batch.
    """

    def __init__(self, rid: int):
        self.rid = rid
        self._event = threading.Event()
        self._result: Optional[List[int]] = None
        self._error: Optional[BaseException] = None
        self._cb_lock = named_lock("RequestHandle._cb_lock")
        self._callbacks: List[Any] = []       # guarded-by: self._cb_lock

    def add_done_callback(self, fn) -> None:
        """Call ``fn(handle)`` when the request completes (or failed).

        Runs on the engine loop thread (or inline if already done) —
        callbacks must be quick and must not block on engine work.
        """
        with self._cb_lock:
            if not self._event.is_set():
                self._callbacks.append(fn)
                return
        fn(self)

    def _fire_callbacks(self) -> None:
        with self._cb_lock:
            cbs, self._callbacks = self._callbacks, []
        for fn in cbs:
            # callbacks run inside the engine loop's fatal-error scope;
            # one misbehaving observer must not fail every in-flight
            # request (or kill the loop thread during _fail_all)
            try:
                fn(self)
            except Exception:
                pass

    def _fulfill(self, tokens: List[int]) -> None:
        self._result = tokens
        self._event.set()
        self._fire_callbacks()

    def _fail(self, err: BaseException) -> None:
        self._error = err
        self._event.set()
        self._fire_callbacks()

    def done(self) -> bool:
        return self._event.is_set()

    def exception(self) -> Optional[BaseException]:
        """The failure, if the request failed (None while pending/ok)."""
        return self._error

    def result(self, timeout: float = 600.0) -> List[int]:
        if not self._event.wait(timeout):
            raise TimeoutError(f"request {self.rid} not finished "
                               f"after {timeout}s")
        if self._error is not None:
            raise self._error
        assert self._result is not None
        return self._result


@dataclass
class _Request:
    rid: int
    prompt: tuple
    extra: Dict[str, Any]
    max_new: int
    temperature: float
    handle: RequestHandle
    priority: int = 0                    # SLO lane (DESIGN.md §10.3)
    t_submit: int = 0                    # perf_counter_ns at submit
    parent: Any = None                   # the submitter's span (obs)


@dataclass
class _Slot:
    req: _Request
    seq_id: Optional[int] = None         # paged path
    row: Any = None                      # dense path: B=1 cache pytree
    length: int = 0                      # tokens whose KV is stored
    last_token: int = -1
    remaining: int = 0                   # samples still to produce
    generated: List[int] = field(default_factory=list)
    followers: List[RequestHandle] = field(default_factory=list)
    rng: Optional[jax.Array] = None
    view_ix: int = -1                    # row index in the current view


class _Defer(Exception):
    """Admission must wait for pages freed by in-flight retirements."""


class InferenceEngine:
    """One engine instance == one Halo GPU-worker's resident model."""

    MIN_SHARED_PREFIX = 4        # tokens; below this, page aliasing not worth it
    _T_QUANTUM = 32              # decode-view time bucket (bounds recompiles)
    _PF_QUANTUM = 16             # chunk-prefill suffix bucket (share points
                                 # are timing-dependent under streaming)

    def __init__(self, cfg: ModelConfig, seed: int = 0, max_batch: int = 8,
                 enable_prefix_sharing: bool = True, page_size: int = 8,
                 num_pages: Optional[int] = None, max_seq_len: int = 512,
                 max_warm_sequences: int = 32, paged_decode: bool = True,
                 admission_window: float = 0.0,
                 kernel_variant: Optional[str] = None):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.seed = seed
        self.max_batch = max_batch
        self.enable_prefix_sharing = enable_prefix_sharing
        self.page_size = page_size
        self.max_seq_len = max_seq_len
        self.max_warm_sequences = max_warm_sequences
        # decode straight from the device-resident pages (paged_decode_step)
        # vs. the dense-view reference path (gather + decode_step); the
        # dense path stays for A/B and for models without the paged hook
        self.paged_decode = paged_decode
        # paged-kernel variant override (None = the autotune table in
        # kernels/paged_decode_attention; the A/B harness pins it)
        self.kernel_variant = kernel_variant
        # grace window (seconds): a fresh batch waits this long after the
        # LAST submission before admitting, so near-simultaneous
        # (pipelined, staggered) arrivals form ONE decode batch shape
        # instead of fragmenting into per-arrival recompiles.  Applied
        # only while the engine is idle — a running batch is never stalled.
        self.admission_window = admission_window
        self.params = None   # guarded-by: self._cv | engine-loop ;; memspace: device
        self.stats = EngineStats()
        self.warm_prefixes = RadixPrefixTree()  # guarded-by: self._cv | engine-loop
        self._paged_layout = self.model.paged_kv_layout()
        self._use_paged = bool(self._paged_layout) and paged_decode \
            and hasattr(self.model, "paged_decode_step")
        self.num_pages = num_pages or max(
            64, 2 * max_batch * -(-max_seq_len // page_size))
        self.kv: Optional[PagedKVCache] = None   # guarded-by: self._cv | engine-loop
        # a MoE model's steps also return the experts they read, which
        # come back with the sampled ids (EngineStats.moe_*)
        m = cfg.moe
        self._moe_slots = (m.top_k * (cfg.num_layers - m.first_dense_layers)
                           if m else 0)      # slots a token takes
        count = {"count_experts": True} if m else {}
        # jitted steps (cached per input/cache shape signature), named so
        # the device trace's program line reads jit_<name>
        model = self.model

        def decode_step(p, tok, cache):
            return model.decode_step(p, tok, cache, **count)

        def prefill(p, toks):
            return model.prefill(p, toks, **count)

        def chunk_prefill(p, toks, cache, n):
            return model.prefill_with_cache(p, toks, cache, valid_len=n,
                                            **count)

        def paged_decode_step(p, tok, kp, vp, pt, ln):
            return model.paged_decode_step(p, tok, kp, vp, pt, ln,
                                           variant=self.kernel_variant,
                                           **count)

        # jit-ok: cache/toks shapes ARE the bucketing keys (view pad, _round_t)
        self._decode_jit = jax.jit(decode_step)
        # jit-ok: cold prefill; toks already padded to _PF_QUANTUM buckets
        self._prefill_jit = jax.jit(prefill)
        if self._paged_layout:
            # jit-ok: suffix chunks arrive _round_t-bucketed; n is traced
            self._chunk_prefill_jit = jax.jit(chunk_prefill)
        if self._use_paged:
            # the pool arrays flow through the step; donating them lets
            # XLA scatter in place on device backends (CPU ignores it)
            donate = (2, 3) if jax.default_backend() != "cpu" else ()
            self._paged_step_jit = jax.jit(paged_decode_step,
                                           donate_argnums=donate)
        # scheduler state — owned by the loop thread ("engine-loop"),
        # shared with submitters/importers under _cv (DESIGN.md §11)
        self._cv = named_condition("InferenceEngine._cv")
        self._pending: "deque[_Request]" = deque()   # guarded-by: self._cv | engine-loop
        self._active: List[_Slot] = []               # guarded-by: self._cv | engine-loop
        self._warm: "OrderedDict[int, tuple]" = OrderedDict()  # guarded-by: self._cv | engine-loop
        self._view = None    # guarded-by: self._cv | engine-loop ;; memspace: device
        self._view_pad = 0               # guarded-by: self._cv | engine-loop
        self._dirty = True               # guarded-by: self._cv | engine-loop
        self._loop_thread: Optional[threading.Thread] = None
        self._stepping = False           # guarded-by: self._cv
        self._shutdown = False           # guarded-by: self._cv
        self._rid = 0                    # guarded-by: self._cv
        self._zero_key = jax.random.PRNGKey(0)
        self._last_submit = 0            # guarded-by: self._cv

    # ---------------------------------------------------------------- weights
    def load(self) -> float:
        """Materialize params (the T_model event). Returns seconds."""
        if self.params is not None:
            return 0.0
        t0 = time.perf_counter()
        # params depend on neither the served name nor the attention
        # path: engines differing only there share one compiled init
        self.params = _init_params(
            self.cfg.replace(name="", attention_impl="xla"),
            jax.random.PRNGKey(self.seed))
        jax.block_until_ready(jax.tree.leaves(self.params)[0])
        return time.perf_counter() - t0

    def unload(self) -> None:
        """Drain in-flight work, then drop params, pages and warm prefixes."""
        with self._cv:
            self._wait_idle_locked(time.monotonic() + 600.0)
            self.params = None
            self.kv = None
            self._warm.clear()
            self.warm_prefixes = RadixPrefixTree()
            self._view = None
            self._dirty = True

    @property
    def loaded(self) -> bool:
        return self.params is not None

    def param_bytes(self) -> int:
        return self.cfg.param_count() * 2          # bf16

    def hbm_bytes(self) -> int:
        """Device bytes this engine holds at its peak once loaded: the
        params and one KV pool.  Every write into the pool — the paged
        decode step's, and ``PagedKVCache``'s writer for prefilled,
        copy-on-write and imported pages — takes the pool donated and
        updates it in place, so no second pool is ever alive."""
        if not self._paged_layout:
            return self.param_bytes()
        layers, kv_heads, head_dim = self._paged_layout
        pool = PagedKVCache.pool_bytes(layers, self.num_pages,
                                       self.page_size, kv_heads, head_dim)
        return self.param_bytes() + pool

    # ----------------------------------------------------------- submission
    def submit(self, prompt: Sequence[int], *, max_new_tokens: int = 16,
               temperature: float = 0.0,
               extra: Optional[Dict[str, Any]] = None,
               priority: int = 0, parent=None) -> RequestHandle:
        """Enqueue one request into the persistent engine loop.

        Returns immediately; the request joins the running decode batch at
        the next admission pass (mid-decode if a batch is in flight).
        ``priority`` is the SLO lane (DESIGN.md §10.3): each admission
        pass picks the highest-priority waiting request (FIFO within a
        lane), so an interactive request preempts batch-lane admission —
        including under KV-pool pressure, where a deferred interactive
        request holds the pass rather than letting batch work slip past
        it.  All-equal priorities reduce exactly to FIFO.  ``parent`` is
        the submitter's span (``repro.obs``): the request's
        ``engine.queue`` span, submit to slot, is its child.
        """
        if not self._paged_layout \
                and len(prompt) + max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds engine max_seq_len ({self.max_seq_len}); dense-row "
                f"caches would wrap and corrupt state")
        with self._cv:
            if self._shutdown:
                raise EngineError("engine is shut down")
            self._rid += 1
            self._last_submit = time.perf_counter_ns()
            req = _Request(self._rid, tuple(int(t) for t in prompt),
                           dict(extra or {}), max_new_tokens, temperature,
                           RequestHandle(self._rid), priority=int(priority),
                           t_submit=self._last_submit, parent=parent)
            self._pending.append(req)
            self._ensure_loop()
            self._cv.notify_all()
        return req.handle

    def generate(self, prompts: Sequence[Sequence[int]], *,
                 max_new_tokens: int = 16, temperature: float = 0.0,
                 extras: Optional[List[Dict[str, Any]]] = None,
                 ) -> List[List[int]]:
        """Generate continuations for a batch of token prompts.

        Submit-then-wait over the continuous-batching loop: the prompts
        join whatever is already in flight.  Deterministic for
        temperature=0.  Identical prompts are coalesced.  Returns one
        generated-token list per prompt (same order).
        """
        extras = extras or [{} for _ in prompts]
        handles = [self.submit(p, max_new_tokens=max_new_tokens,
                               temperature=temperature, extra=e)
                   for p, e in zip(prompts, extras)]
        return [h.result() for h in handles]

    # requires: self._cv
    def _wait_idle_locked(self, deadline: float) -> None:
        """Wait (holding _cv) until the loop is quiescent: nothing queued,
        nothing in flight, and the loop thread is not inside _step().
        While the caller keeps holding _cv afterwards, the loop cannot
        start a new step, so engine state is safe to mutate."""
        while self._pending or self._active or self._stepping:
            if not self._cv.wait(timeout=min(1.0,
                                             deadline - time.monotonic())):
                if time.monotonic() >= deadline:
                    raise TimeoutError("engine drain timed out")

    def drain(self, timeout: float = 600.0) -> None:
        """Block until no request is pending or in flight."""
        with self._cv:
            self._wait_idle_locked(time.monotonic() + timeout)

    def reset_peak_batch(self) -> None:
        """Reset the peak-concurrency watermark to the current batch size.

        ``peak_batch`` is a high-watermark gauge; per-run reporting over
        persistent hosts resets it at run start so a later run does not
        re-report an earlier run's peak.
        """
        with self._cv:
            self.stats.peak_batch = len(self._active)

    # ------------------------------------------------------- kv migration
    # requires: self._cv
    def _wait_step_gap_locked(self, deadline: float) -> None:
        """Wait (holding _cv) until the loop thread is between steps.
        While the caller keeps holding _cv, the loop cannot enter the
        next step, so pages / warm set / radix tree are safe to touch
        even with work in flight."""
        while self._stepping:
            if not self._cv.wait(timeout=min(1.0,
                                             deadline - time.monotonic())):
                if time.monotonic() >= deadline:
                    raise TimeoutError("engine never paused between steps")

    # requires: self._cv | engine-loop
    def _find_warm_donor(self, tokens: Sequence[int],
                         cap: Optional[int] = None):
        """Deepest valid warm donor covering a prefix of ``tokens``:
        ``(seq_id, depth)``, or ``(None, 0)``.  ``cap`` bounds the usable
        depth (admission caps at S-1 so one fresh token remains to
        decode from).  Caller must either BE the loop thread or hold
        ``_cv`` in a step gap — donors can be evicted mid-step."""
        kv = self.kv
        if kv is None or not self.enable_prefix_sharing:
            return None, 0
        _, cands = self.warm_prefixes.match_all(tokens)
        for depth, payload in cands:                     # deepest first
            d = depth if cap is None else min(depth, cap)
            if (d >= self.MIN_SHARED_PREFIX and isinstance(payload, int)
                    and payload in kv.sequences
                    and kv.sequences[payload].length >= d):
                return payload, d
        return None, 0

    def probe_prefix(self, prompt: Sequence[int], timeout: float = 60.0
                     ) -> int:
        """Longest warm-donor prefix of ``prompt`` resident here (tokens);
        0 when nothing useful is cached.  Thread-safe (runs in a step
        gap, like export) — lets a migrator decide migrate-vs-recompute
        before paying the export."""
        prompt = tuple(int(t) for t in prompt)
        deadline = time.monotonic() + timeout
        with self._cv:
            self._wait_step_gap_locked(deadline)
            return self._find_warm_donor(prompt)[1]

    def export_prefix(self, prompt: Sequence[int], timeout: float = 60.0):
        """Export the warm KV prefix matching ``prompt``.

        Returns ``(tokens, k, v)`` — the matched prompt prefix plus
        contiguous per-layer KV copies — or None when no warm donor
        covers at least MIN_SHARED_PREFIX tokens.  Thread-safe: runs in
        a gap between engine steps so an eviction or copy-on-write
        cannot mutate the donor's pages mid-copy.
        """
        prompt = tuple(int(t) for t in prompt)
        deadline = time.monotonic() + timeout
        with self._cv:
            self._wait_step_gap_locked(deadline)
            donor, depth = self._find_warm_donor(prompt)
            if donor is None:
                return None
            kv = self.kv
            # pages_migrated_out is NOT counted here: the caller credits
            # it only once the destination confirms the import, so the
            # out/in counters track real transfers, not attempts
            k, v = kv.export_sequence(donor, depth)
            # the migration boundary is the ONE place the device pool
            # stages through the host (priced by the caller as before)
            self.stats.d2h_bytes += k.nbytes + v.nbytes
            return prompt[:depth], k, v

    def import_prefix(self, tokens: Sequence[int], k, v,
                      migrate_seconds: float = 0.0,
                      timeout: float = 60.0) -> int:
        """Adopt a migrated KV prefix as a warm donor: write the pages,
        register the sequence in the warm set and stamp the radix tree so
        the next admission of a matching prompt aliases it.

        Best-effort: returns the number of pages imported, or 0 when the
        prefix is already resident or the pool has no headroom beyond the
        active batch's decode reservation (migration must never destabil-
        ize in-flight work).  ``migrate_seconds`` is the modeled link-
        transfer time the caller priced the copy at.
        """
        tokens = tuple(int(t) for t in tokens)
        if not self._paged_layout or not self.enable_prefix_sharing \
                or len(tokens) < self.MIN_SHARED_PREFIX:
            return 0                                 # donor would be unusable
        deadline = time.monotonic() + timeout
        with self._cv:
            self._wait_step_gap_locked(deadline)
            kv = self._ensure_kv()
            if self._find_warm_donor(tokens)[1] >= len(tokens):
                return 0                             # already resident
            need = -(-len(tokens) // self.page_size)
            # feasibility BEFORE evicting anything: a page is reclaimable
            # only if every reference to it comes from warm sequences —
            # an infeasible import must not wipe the destination's warm
            # locality just to fail anyway
            warm_refs: Dict[int, int] = {}
            for seq_id in self._warm:
                for p in kv.sequences[seq_id].page_ids:
                    warm_refs[p] = warm_refs.get(p, 0) + 1
            reclaimable = sum(1 for p, n in warm_refs.items()
                              if n == kv.refcount[p])
            headroom = len(kv.free_pages) - self._reserved_pages()
            if headroom + reclaimable < need:
                return 0
            while headroom < need and self._warm:    # evict LRU warm only
                # prefer victims whose eviction actually frees pages;
                # a warm sequence fully aliased by in-flight work frees
                # nothing and would be destroyed for zero gain (fall
                # back to any victim to unlock warm-warm aliased pages)
                victim = next(
                    (s for s in self._warm
                     if any(kv.refcount[p] == 1
                            for p in kv.sequences[s].page_ids)),
                    None) or next(iter(self._warm))
                self._warm.pop(victim)
                kv.free_sequence(victim)
                headroom = len(kv.free_pages) - self._reserved_pages()
            if headroom < need:
                return 0
            with self._counting_pool_writes():
                seq = kv.import_sequence(k, v)
            self.stats.h2d_bytes += k.nbytes + v.nbytes   # staging upload
            self._warm[seq] = tokens
            self._warm.move_to_end(seq)
            while len(self._warm) > self.max_warm_sequences:
                victim, _ = self._warm.popitem(last=False)
                kv.free_sequence(victim)
            self.warm_prefixes.insert(tokens, payload=seq, stamp_path=True)
            self._maybe_prune_tree()
            pages = len(kv.sequences[seq].page_ids)
            self.stats.pages_migrated_in += pages
            self.stats.migrate_seconds += migrate_seconds
            return pages

    def release_warm(self, timeout: float = 600.0) -> None:
        """Free every warm (retained-for-prefix-reuse) sequence's pages.

        Waits for the engine to go idle first — the warm set and page
        refcounts belong to the loop thread while work is in flight.
        """
        with self._cv:
            self._wait_idle_locked(time.monotonic() + timeout)
            for seq_id in list(self._warm):
                self.kv.free_sequence(seq_id)
            self._warm.clear()

    def shutdown(self) -> None:
        with self._cv:
            self._shutdown = True
            self._cv.notify_all()
        if self._loop_thread is not None:
            self._loop_thread.join(timeout=10)

    # ------------------------------------------------------------- the loop
    def _ensure_loop(self) -> None:
        if self._loop_thread is None or not self._loop_thread.is_alive():
            self._loop_thread = threading.Thread(
                target=self._run_loop, daemon=True,
                name=f"engine-{self.cfg.name}")
            self._loop_thread.start()

    # runs-on: engine-loop
    def _run_loop(self) -> None:
        while True:
            with self._cv:
                while not self._shutdown and not self._pending \
                        and not self._active:
                    self._cv.wait()
                if self._shutdown:
                    return
                self._stepping = True
            try:
                self._step()
            except BaseException as e:                  # engine-fatal
                self._fail_all(e)
            finally:
                with self._cv:
                    self._stepping = False
                    self._cv.notify_all()

    def _fail_all(self, err: BaseException) -> None:
        with self._cv:
            victims = list(self._pending)
            self._pending.clear()
            slots, self._active = self._active, []
        for req in victims:
            req.handle._fail(err)
        for s in slots:
            s.req.handle._fail(err)
            for f in s.followers:
                f._fail(err)
            # return the slot's pages: a failed batch must not leak them
            if self.kv is not None and s.seq_id in self.kv.sequences:
                try:
                    self.kv.free_sequence(s.seq_id)
                except Exception:
                    pass                        # pool corrupt > pool leaked
        self._dirty = True
        self._view = None

    def _step(self) -> None:
        """One scheduler iteration: admit, then one decode step."""
        with obs.span("engine.step"):
            self._grace_window()
            self._admit()
            if self._active:
                self._decode_once()

    def _grace_window(self) -> None:
        """Hold a FRESH batch's admission until ``admission_window``
        seconds have passed since the last submission (capped at 10
        windows), so a burst of staggered arrivals lands as one
        admission wave / one batch shape.  Running batches are never
        delayed — mid-decode arrivals batch naturally between steps."""
        w = self.admission_window
        if w <= 0 or self._active:
            return
        cap = time.perf_counter() + 10 * w
        with self._cv:
            while not self._shutdown and self._pending:
                now = time.perf_counter()
                wait = self._last_submit / 1e9 + w - now
                if wait <= 0 or now >= cap:
                    break
                self._cv.wait(timeout=min(wait, cap - now))

    # ------------------------------------------------------------- admission
    def _admit(self) -> None:
        with obs.span("engine.admit") as sp:
            admitted, deferred = self._admit_pass()
            sp.set(admitted=admitted, deferred=deferred)

    def _admit_pass(self):
        """Admit waiting requests while slots are free; (admitted,
        deferred) for the pass."""
        admitted = deferred = 0
        while len(self._active) < self.max_batch:
            with self._cv:
                if not self._pending:
                    break
                # peek the highest-priority waiting request (max() keeps
                # the FIRST maximum, so equal priorities — the common
                # all-zero case — are exact FIFO); the request stays
                # visible to drain() until it has a slot (only the loop
                # thread ever removes it)
                req = max(self._pending, key=lambda r: r.priority)
                jumped = req is not self._pending[0]
            if self._coalesce(req):
                self._remove_pending(req)
                continue
            try:
                slot = self._admit_one(req)
            except _Defer:
                # left in the queue: under KV-pool pressure the deferred
                # request blocks the whole pass, so lower-priority work
                # can never be admitted around a waiting interactive
                # request (it preempts batch, never vice versa)
                deferred = 1
                self.stats.admit_deferrals += 1
                break
            except BaseException as e:                  # per-request failure
                self._remove_pending(req)
                req.handle._fail(e)
                continue
            # attach still-queued exact duplicates NOW: a leader that
            # retires within this admission pass (small max_new) would
            # otherwise leave _active before its duplicates reach
            # _coalesce, and both would prefill
            slot.followers.extend(self._claim_pending_duplicates(req))
            if slot.remaining > 0:
                self._active.append(slot)
                admitted += 1
            else:
                self._retire(slot)
            self._remove_pending(req)
            if jumped:
                self.stats.priority_jumps += 1
        if admitted:
            self.stats.admission_waves += 1
            self.stats.peak_batch = max(self.stats.peak_batch,
                                        len(self._active))
            self._dirty = True
        return admitted, deferred

    @staticmethod
    def _queue_done(req: _Request, t1: Optional[int] = None) -> None:
        """The request's wait for a slot (or a leader) ended at ``t1``."""
        obs.add("engine.queue", req.t_submit, t1, parent=req.parent,
                rid=req.rid)

    @staticmethod
    def _duplicates(a: _Request, b: _Request) -> bool:
        return (not a.extra and not b.extra and a.prompt == b.prompt
                and a.max_new == b.max_new
                and a.temperature == b.temperature)

    def _coalesce(self, req: _Request) -> bool:
        """Attach an exact duplicate of an in-flight request as follower.

        Per-request sampling streams are a pure function of (engine seed,
        prompt, max_new) — see _request_rng — so two requests with equal
        (prompt, max_new, temperature) provably decode the same tokens at
        ANY temperature; the leader's full output is the follower's.
        """
        if req.extra:
            return False
        for s in self._active:
            if self._duplicates(s.req, req):
                s.followers.append(req.handle)
                self.stats.coalesced_requests += 1
                self._queue_done(req)
                return True
        return False

    def _claim_pending_duplicates(self, req: _Request) -> List[RequestHandle]:
        """Pop every exact duplicate of ``req`` still waiting in _pending
        and return their handles — the admission-time counterpart of
        _coalesce, covering duplicates submitted in the same wave."""
        if req.extra:
            return []
        out: List[RequestHandle] = []
        with self._cv:
            kept: "deque[_Request]" = deque()
            for r in self._pending:
                if r is not req and self._duplicates(r, req):
                    out.append(r.handle)
                    self._queue_done(r)
                else:
                    kept.append(r)
            self._pending = kept
        self.stats.coalesced_requests += len(out)
        return out

    def _request_rng(self, req: _Request) -> jax.Array:
        """Per-request stream, stable under plan/arrival reordering."""
        h = zlib.crc32(np.asarray(req.prompt, np.int64).tobytes())
        h = zlib.crc32(np.asarray([req.max_new], np.int64).tobytes(), h)
        return jax.random.fold_in(jax.random.PRNGKey(self.seed), h)

    # requires: self._cv | engine-loop
    def _ensure_kv(self) -> PagedKVCache:
        if self.kv is None:
            layers, kv_heads, head_dim = self._paged_layout
            self.kv = PagedKVCache(layers, self.num_pages, self.page_size,
                                   kv_heads, head_dim)
        return self.kv

    @contextlib.contextmanager
    # requires: self._cv | engine-loop
    def _counting_pool_writes(self, sp=None):
        """Count the pool-writer dispatches and rows made inside the
        block onto the stats and, as ``pool_writes``, onto ``sp``."""
        kv = self.kv
        if kv is None:
            yield
            return
        writes, rows = kv.pool_writes, kv.pool_write_rows
        yield
        self.stats.pool_writes += kv.pool_writes - writes
        self.stats.pool_write_rows += kv.pool_write_rows - rows
        if sp is not None:
            sp.set(pool_writes=kv.pool_writes - writes)

    def _remove_pending(self, req: _Request) -> None:
        with self._cv:
            try:
                self._pending.remove(req)
            except ValueError:       # already claimed as a duplicate
                pass

    # requires: self._cv | engine-loop
    def _reserved_pages(self) -> int:
        """Pages the in-flight batch may still allocate: each active slot
        appends one token's KV per remaining step (+1 for page-boundary
        slack).  Admission must leave this headroom free or a decode-time
        ``append_token`` could exhaust the pool and fail the whole batch."""
        ps = self.page_size
        return sum(-(-s.remaining // ps) + 1 for s in self._active)

    # requires: self._cv | engine-loop
    def _ensure_pages(self, needed: int, protect: Optional[int] = None) -> None:
        """Evict warm sequences (LRU, never ``protect``) until ``needed``
        pages are free beyond the active batch's decode reservation;
        defer admission if in-flight work will free more."""
        kv = self.kv
        if needed > kv.num_pages:
            # can NEVER fit — not even with every warm sequence evicted
            # and the active batch fully drained.  Deferring would
            # livelock behind in-flight work until the caller's 600s
            # result() timeout; fail the request now with a diagnosis.
            raise MemoryError(
                f"request needs {needed} KV pages but the pool holds only "
                f"{kv.num_pages} ({kv.page_size} tokens/page) — it cannot "
                f"be admitted even after evicting all warm sequences; "
                f"raise num_pages/max_seq_len or shrink the prompt / "
                f"max_new_tokens")
        needed += self._reserved_pages()
        while len(kv.free_pages) < needed:
            victim = next((s for s in self._warm if s != protect), None)
            if victim is None:
                if self._active:
                    raise _Defer()
                raise MemoryError(
                    f"KV cache out of pages ({needed} needed, "
                    f"{len(kv.free_pages)} free, no warm sequences left)")
            self._warm.pop(victim)
            kv.free_sequence(victim)

    def _prefill(self, tokens: jax.Array, extra: Dict[str, Any]):
        if self.cfg.family == "audio":
            return self.model.prefill(self.params, tokens, extra["frames"])
        if self.cfg.family == "vlm" and extra.get("patch_embeds") is not None:
            return self.model.prefill(self.params, tokens,
                                      prefix_embeds=extra["patch_embeds"])
        return self._prefill_jit(self.params, tokens)

    def _kv_rows(self, cache, row: int, length: int):
        """One prefill row's KV in the page-store write format — device
        arrays when the model exposes the device hook (no staging), host
        float32 otherwise (the pool uploads them on write)."""
        if hasattr(self.model, "cache_kv_rows_dev"):
            return self.model.cache_kv_rows_dev(cache, row, length)
        k, v = self.model.cache_kv_rows(cache, row)
        self.stats.d2h_bytes += k.nbytes + v.nbytes
        self.stats.h2d_bytes += k.nbytes + v.nbytes
        return k, v

    def _admit_one(self, req: _Request) -> _Slot:
        if self.params is None:
            self.load()
        S = len(req.prompt)
        slot = _Slot(req=req, remaining=req.max_new,
                     rng=self._request_rng(req))
        shareable = (self.enable_prefix_sharing and not req.extra and S > 1)
        donor = None
        shared = 0
        if self._paged_layout:
            kv = self._ensure_kv()
            if shareable:
                # deepest-first fallback; cap at S-1 so one fresh token
                # remains to decode from
                donor, shared = self._find_warm_donor(req.prompt, cap=S - 1)
            fresh_tokens = S - shared + req.max_new
            if req.extra.get("patch_embeds") is not None:
                fresh_tokens += req.extra["patch_embeds"].shape[-2]
            self._ensure_pages(-(-fresh_tokens // self.page_size) + 1,
                               protect=donor)
        # past _ensure_pages the request holds its slot: its queue ends
        with obs.span("engine.prefill", rid=req.rid, prompt_tokens=S,
                      shared_tokens=shared) as sp:
            self._queue_done(req, sp.t0)
            with self._counting_pool_writes(sp):
                logits, read = self._prefill_into(slot, donor, shared,
                                                  shareable)
            if self._moe_slots:
                slots = (S - shared) * self._moe_slots
                self.stats.moe_token_slots += slots
                sp.set(slots=slots)
            # the experts read come back with the first sampled id (a
            # prompt that samples nothing leaves them uncounted)
            if req.max_new > 0:
                self._count_read(sp, self._emit_token(slot, logits[0:1],
                                                      read))
        return slot

    def _split_count(self, out: tuple):
        """(a jitted step's outputs, the experts it read or None)."""
        return (out[:-1], out[-1]) if self._moe_slots else (out, None)

    def _count_read(self, sp, read: Optional[int]) -> None:
        """Count a step's experts read, already on the host (None for a
        dense model), onto ``sp`` and the stats."""
        if read is not None:
            self.stats.moe_experts_read += read
            sp.set(experts_read=read)

    def _prefill_into(self, slot: _Slot, donor: Optional[int], shared: int,
                      shareable: bool):
        """Prefill ``slot``'s prompt into its cache (the donor's first
        ``shared`` tokens aliased); the last position's logits and, for a
        MoE model, the experts read (a device scalar, else None)."""
        req = slot.req
        S = len(req.prompt)
        read = None
        if self._paged_layout:
            kv = self.kv
            if donor is not None:
                logits, read = self._prefill_shared(slot, donor, shared)
                self.stats.prefix_hits += 1
                self.stats.prefill_tokens += S - shared
                self.stats.prefill_tokens_saved += shared
            elif not req.extra and hasattr(self.model,
                                           "prefill_with_cache"):
                # cold prompts run the SAME bucketed chunk-prefill step
                # as shared ones: whether a prompt finds a warm donor is
                # timing-dependent under streaming arrivals, so giving
                # the cold path its own per-length compiled shape would
                # re-trace run to run
                logits, read = self._prefill_cold(slot)
                self.stats.prefill_tokens += S
            else:
                tokens = jnp.asarray([req.prompt], jnp.int32)
                (logits, cache), read = self._split_count(
                    self._prefill(tokens, req.extra))
                S_kv = S
                if req.extra.get("patch_embeds") is not None:
                    S_kv += req.extra["patch_embeds"].shape[-2]
                k_row, v_row = self._kv_rows(cache, 0, S_kv)
                slot.seq_id = kv.add_sequence(k_row, v_row)
                self.stats.prefill_tokens += S_kv
            slot.length = kv.sequences[slot.seq_id].length
            if shareable:
                self.warm_prefixes.insert(req.prompt, payload=slot.seq_id,
                                          stamp_path=True)
            self.stats.pages_shared = kv.pages_shared
            self.stats.tokens_reused = kv.tokens_reused
        else:
            tokens = jnp.asarray([req.prompt], jnp.int32)
            (logits, cache), read = self._split_count(
                self._prefill(tokens, req.extra))
            t_cur = S
            slot.row = self.model.extend_cache(cache,
                                               self.max_seq_len - t_cur)
            slot.length = S
            self.stats.prefill_tokens += S
        return logits, read

    def _prefill_cold(self, slot: _Slot):
        """Prefill a donor-less prompt via the bucketed chunk step over
        an empty cache view — one compiled shape per (suffix bucket,
        time bucket) instead of one per prompt length."""
        kv = self.kv
        req = slot.req
        S = len(req.prompt)
        pad = -(-S // self._PF_QUANTUM) * self._PF_QUANTUM
        T1 = self._round_t(pad + req.max_new)
        layers, heads, dh = self._paged_layout
        k_rows = jnp.zeros((1, layers, T1, heads, dh), jnp.float32)
        v_rows = jnp.zeros((1, layers, T1, heads, dh), jnp.float32)
        cache = self.model.paged_cache_view(k_rows, v_rows, [0])
        toks = jnp.asarray([list(req.prompt) + [0] * (pad - S)], jnp.int32)
        (logits, cache), read = self._split_count(self._chunk_prefill_jit(
            self.params, toks, cache, jnp.asarray([S], jnp.int32)))
        k_row, v_row = self._kv_rows(cache, 0, S)           # (L, S, H, D)
        slot.seq_id = kv.add_sequence(k_row, v_row)
        return logits, read

    def _prefill_shared(self, slot: _Slot, donor: int, shared: int):
        """Admit via page aliasing: reuse the donor's first ``shared``
        tokens, chunk-prefill only the unseen suffix, append its KV.
        The reused prefix is gathered from the device pool and the
        suffix KV written back through it entirely on device."""
        kv = self.kv
        req = slot.req
        seq = kv.add_sequence(shared_from=donor, shared_len=shared)
        slot.seq_id = seq
        kp, vp = kv.gather(seq)              # device (L, shared, H, D)
        S = len(req.prompt)
        # pad the suffix to a quantum: the share point depends on which
        # prefixes happen to be warm at admission time, so under
        # streaming arrivals raw suffix shapes are timing-dependent and
        # each one would JIT-compile its own chunk-prefill step
        n_suf = S - shared
        pad = -(-n_suf // self._PF_QUANTUM) * self._PF_QUANTUM
        T1 = self._round_t(shared + pad + req.max_new)
        L, _, H, D = kp.shape
        k_rows = jnp.zeros((1, L, T1, H, D), jnp.float32).at[
            0, :, :shared].set(kp)
        v_rows = jnp.zeros((1, L, T1, H, D), jnp.float32).at[
            0, :, :shared].set(vp)
        cache = self.model.paged_cache_view(k_rows, v_rows, [shared])
        suffix = jnp.asarray(
            [list(req.prompt[shared:]) + [0] * (pad - n_suf)], jnp.int32)
        (logits, cache), read = self._split_count(self._chunk_prefill_jit(
            self.params, suffix, cache, jnp.asarray([n_suf], jnp.int32)))
        k_row, v_row = self._kv_rows(cache, 0, S)           # (L, S, H, D)
        kv.extend_sequence(seq, k_row[:, shared:], v_row[:, shared:])
        return logits, read

    # ---------------------------------------------------------------- decode
    def _round_t(self, n: int) -> int:
        q = self._T_QUANTUM
        return -(-n // q) * q

    @staticmethod
    def _round_b(n: int) -> int:
        b = 1
        while b < n:
            b *= 2
        return b

    def _rebuild_view(self) -> None:
        """Re-materialize the dense decode batch after composition change.

        Paged models gather every active row from its pages (the pages
        stay authoritative); dense-row models restack per-sequence rows.
        Batch is padded to a power of two and time to _T_QUANTUM multiples
        so recompiles stay bounded; padded rows compute garbage that is
        never sampled and never written back anywhere.
        """
        slots = self._active
        b_pad = self._round_b(len(slots))
        self.stats.view_rebuilds += 1
        if self._paged_layout:
            kv = self.kv
            t_view = self._round_t(max(s.length + s.remaining for s in slots))
            layers, heads, dh = self._paged_layout
            k_rows = np.zeros((b_pad, layers, t_view, heads, dh), np.float32)
            v_rows = np.zeros_like(k_rows)
            lengths = [0] * b_pad
            for i, s in enumerate(slots):
                kr, vr = kv.gather(s.seq_id)
                # device pool -> host rows -> padded device view: the
                # O(batch x seq_len) round-trip the paged path deletes
                self.stats.d2h_bytes += kr.nbytes + vr.nbytes
                k_rows[i, :, :s.length] = kr
                v_rows[i, :, :s.length] = vr
                lengths[i] = s.length
            self.stats.h2d_bytes += k_rows.nbytes + v_rows.nbytes
            self._view = self.model.paged_cache_view(k_rows, v_rows, lengths)
        else:
            rows = self._dense_rows() + [None] * (b_pad - len(slots))
            axes = self.model.cache_batch_axes(rows[0])
            dummy = jax.tree.map(jnp.zeros_like, rows[0])
            rows = [dummy if r is None else r for r in rows]
            self._view = {
                key: jnp.concatenate([r[key] for r in rows], axis=ax)
                for key, ax in axes.items()}
        self._view_pad = b_pad
        self._dirty = False

    def _dense_rows(self) -> List[Any]:
        """Per-slot cache rows; slots already in the current view are
        sliced back out of it (they carry the decoded state)."""
        out = []
        for s in self._active:
            if s.row is None:
                s.row = self._slice_row(self._view, s.view_ix)
            out.append(s.row)
            s.row = None                    # ownership moves into the view
        return out

    def _slice_row(self, view, ix: int):
        axes = self.model.cache_batch_axes(view)
        return {k: jax.lax.slice_in_dim(v, ix, ix + 1, axis=axes[k])
                for k, v in view.items()}

    def _decode_once(self) -> None:
        """One decode step over every active slot: host preparation and
        dispatch (span ``engine.decode``), then sampling and the sync."""
        b_real = len(self._active)
        with obs.span("engine.decode", rows=b_real) as sp, \
                self._counting_pool_writes():
            logits, read = (self._decode_paged(sp) if self._use_paged
                            else self._decode_dense())
            if self._moe_slots:
                self.stats.moe_token_slots += b_real * self._moe_slots
                sp.set(slots=b_real * self._moe_slots)
        self.stats.decode_steps += 1
        self.stats.decode_tokens += b_real
        self._advance(logits, read)

    def _decode_dense(self):
        if self._dirty:
            self._rebuild_view()
            for i, s in enumerate(self._active):
                s.view_ix = i
        slots = self._active
        b_real = len(slots)
        tokens = np.zeros((self._view_pad,), np.int32)
        tokens[:b_real] = [s.last_token for s in slots]
        prev_lengths = [s.length for s in slots]
        self.stats.h2d_bytes += tokens.nbytes
        (logits, self._view), read = self._split_count(self._decode_jit(
            self.params, jnp.asarray(tokens), self._view))
        if self._paged_layout:
            taps_ix = np.zeros((self._view_pad,), np.int32)
            taps_ix[:b_real] = prev_lengths      # identity slots (no wrap)
            k_taps, v_taps = self.model.decode_kv_taps(self._view, taps_ix)
            # fresh KV taps sync to host, then re-upload into the pool —
            # the per-step D2H round-trip the paged path deletes
            self.stats.d2h_bytes += k_taps.nbytes + v_taps.nbytes
            k_taps, v_taps = k_taps[:, :b_real], v_taps[:, :b_real]
            self.stats.h2d_bytes += k_taps.nbytes + v_taps.nbytes
            self.kv.append_tokens([s.seq_id for s in slots], k_taps, v_taps)
        for s in slots:
            s.length += 1
        return logits, read

    def _decode_paged(self, sp):
        """One decode step straight over the device-resident page pool:
        upload O(batch) metadata (tokens, page tables, lengths), run the
        paged step (in-pool KV scatter + paged attention), and return its
        logits (and the experts read, for a MoE model); O(batch) sampled
        ids come back in ``_advance``.  No dense
        view exists, so composition changes are free — no
        ``_rebuild_view``, no KV tap sync.  Counts the live KV tokens the
        step reads and the pool's pages in use onto ``sp`` and the
        stats."""
        kv = self.kv
        slots = self._active
        b_real = len(slots)
        # page alloc + COW (host metadata): after this every write-target
        # page is private to its row — the fused append+attend kernel's
        # safety contract (the step derives (page, offset) from the
        # uploaded table, so the returned targets aren't re-shipped)
        kv.prepare_appends([s.seq_id for s in slots])
        live = sum(s.length + 1 for s in slots)     # the new token's too
        used = kv.num_pages - len(kv.free_pages)
        self.stats.decode_kv_tokens += live
        self.stats.pool_pages_used += used
        self.stats.pool_pages_total += kv.num_pages
        sp.set(kv_tokens=live, pages_used=used, pages_total=kv.num_pages)
        b_pad = self._round_b(b_real)
        # pad like the dense view's quanta so recompiles stay bounded
        t_cap = self._round_t(max(s.length + s.remaining for s in slots))
        n_pages = -(-t_cap // self.page_size)
        pt = np.zeros((b_pad, n_pages), np.int32)
        lens = np.full((b_pad,), -1, np.int32)
        tokens = np.zeros((b_pad,), np.int32)
        for i, s in enumerate(slots):
            ids = kv.sequences[s.seq_id].page_ids
            pt[i, :len(ids)] = ids
            lens[i] = s.length
            tokens[i] = s.last_token
        self.stats.h2d_bytes += pt.nbytes + lens.nbytes + tokens.nbytes
        (logits, new_k, new_v), read = self._split_count(self._paged_step_jit(
            self.params, jnp.asarray(tokens), kv.k, kv.v,
            jnp.asarray(pt), jnp.asarray(lens)))
        kv.adopt_pages(new_k, new_v)
        kv.commit_appends([s.seq_id for s in slots])
        for s in slots:
            s.length += 1
        return logits, read

    def _emit_token(self, slot: _Slot, logits, read=None) -> Optional[int]:
        """Sample one token for ``slot`` from (1, Vpad) logits; the
        prefill's experts read (``read``, a device scalar or None) comes
        back in the same transfer and is returned as a host int."""
        if slot.req.temperature == 0.0:
            nxt = sample(logits, self._zero_key, temperature=0.0,
                         vocab_size=self.cfg.vocab_size)
        else:
            slot.rng, sub = jax.random.split(slot.rng)
            nxt = sample(logits, sub, temperature=slot.req.temperature,
                         vocab_size=self.cfg.vocab_size)
        if read is not None:
            nxt = jnp.append(nxt, read)
        # not-a-transfer: the admission-time sync of the prefill's one id
        got = np.asarray(nxt)            # (and its experts read)
        tok = int(got[0])
        slot.generated.append(tok)
        slot.last_token = tok
        slot.remaining -= 1
        return None if read is None else int(got[1])

    def _advance(self, logits, read=None) -> None:
        """Advance every active slot from one decode step's logits.

        The whole (B, Vpad) batch is sampled in a single device call and
        synced once (one O(batch)-ints D2H per step, the step's experts
        read with them) — no per-slot logits slicing or per-sequence
        ``int()`` syncs."""
        slots = list(self._active)
        b = len(slots)
        b_pad = logits.shape[0]          # sample the PADDED batch so the
        temps = [s.req.temperature for s in slots]   # jit stays keyed on
        temps_pad = np.zeros((b_pad,), np.float32)   # the step's quanta,
        temps_pad[:b] = temps            # not on every live-slot count
        if any(t != 0.0 for t in temps):
            zero = jnp.zeros_like(slots[0].rng)
            keys = jnp.stack([s.rng for s in slots]
                             + [zero] * (b_pad - b))
        else:                            # greedy rows never read their key
            keys = jnp.zeros((b_pad, 2), jnp.uint32)
        if read is None:
            toks_dev, new_keys = _batched_sample(
                logits, keys, jnp.asarray(temps_pad),
                vocab_size=self.cfg.vocab_size)
        else:
            toks_dev, new_keys = _sample_counted(
                logits, keys, jnp.asarray(temps_pad), read,
                vocab_size=self.cfg.vocab_size)
        with obs.span("engine.sync") as sp:
            toks = np.asarray(toks_dev)  # one O(batch)-ints sync per step
            self._count_read(sp, None if read is None else int(toks[-1]))
        self.stats.d2h_bytes += toks.nbytes
        finished = []
        for i, s in enumerate(slots):
            if temps[i] != 0.0:
                s.rng = new_keys[i]
            tok = int(toks[i])
            s.generated.append(tok)
            s.last_token = tok
            s.remaining -= 1
            if s.remaining == 0:
                finished.append(s)
        for s in finished:
            self._active.remove(s)
            self._retire(s)
        if finished:
            self._dirty = True

    def _retire(self, slot: _Slot) -> None:
        req = slot.req
        if self._paged_layout and slot.seq_id is not None:
            keep = (self.enable_prefix_sharing and not req.extra)
            if keep:
                self._warm[slot.seq_id] = req.prompt
                self._warm.move_to_end(slot.seq_id)
                while len(self._warm) > self.max_warm_sequences:
                    victim, _ = self._warm.popitem(last=False)
                    self.kv.free_sequence(victim)
                self._maybe_prune_tree()
            else:
                self.kv.free_sequence(slot.seq_id)
        out = list(slot.generated)
        req.handle._fulfill(out)
        for f in slot.followers:
            f._fulfill(list(out))

    # requires: self._cv | engine-loop
    def _maybe_prune_tree(self) -> None:
        """Rebuild the radix tree from live donors once stale entries
        dominate — evicted sequences leave nodes and stamped payloads
        behind, and a persistent-host engine would otherwise grow the
        tree with every prompt it ever served."""
        if self.warm_prefixes.num_sequences <= 8 * self.max_warm_sequences:
            return
        tree = RadixPrefixTree()
        for seq_id, prompt in self._warm.items():
            tree.insert(prompt, payload=seq_id, stamp_path=True)
        for s in self._active:
            if s.seq_id is not None and not s.req.extra:
                tree.insert(s.req.prompt, payload=s.seq_id, stamp_path=True)
        self.warm_prefixes = tree
