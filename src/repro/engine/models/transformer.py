"""Decoder-only transformer LM (dense / MoE / SWA / qk-norm / VLM prefix).

Covers: deepseek-67b, llama3.2-3b, qwen3-1.7b/8b (dense, GQA, qk-norm),
deepseek-moe-16b (fine-grained MoE + shared experts, first layer dense),
mixtral-8x22b (MoE top-2 + sliding-window attention), internvl2-2b
(VLM backbone — precomputed patch embeddings prepended; frontend stubbed).

Layers are scanned (stacked params) so 95-layer models lower to O(1) HLO.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.engine.models import layers as L
from repro.engine.models import moe as M

# memspace: device (model arrays are device-resident jnp values)

Params = Dict[str, Any]


class TransformerLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.head_dim = cfg.resolved_head_dim
        self.dtype = jnp.dtype(cfg.dtype)
        m = cfg.moe
        self.n_lead = m.first_dense_layers if m else 0     # unscanned lead layers
        self.n_scan = cfg.num_layers - self.n_lead

    def _scan_index(self) -> jax.Array:
        """0 .. n_scan-1: each scanned layer's index in the stacked blocks."""
        return jnp.arange(self.n_scan, dtype=jnp.int32)

    # ------------------------------------------------------------------ init
    def _block_init(self, rng, lead: bool):
        cfg = self.cfg
        k1, k2, k3 = jax.random.split(rng, 3)
        p = {
            "ln1": jnp.zeros((cfg.d_model,), self.dtype),
            "ln2": jnp.zeros((cfg.d_model,), self.dtype),
            "attn": L.attn_init(k1, cfg.d_model, cfg.num_heads,
                                cfg.num_kv_heads, self.head_dim, self.dtype,
                                qk_norm=cfg.qk_norm),
        }
        if cfg.moe is not None and not lead:
            p["moe"] = M.moe_init(k2, cfg.d_model, cfg.moe, self.dtype)
        else:
            d_ff = (cfg.moe.d_ff_dense if (cfg.moe and lead) else cfg.d_ff)
            p["ffn"] = L.ffn_init(k3, cfg.d_model, d_ff, self.dtype)
        return p

    def init(self, rng) -> Params:
        cfg = self.cfg
        ks = jax.random.split(rng, 4)
        params: Params = {
            "embed": L.embed_init(ks[0], cfg.padded_vocab, cfg.d_model, self.dtype),
            "final_norm": jnp.zeros((cfg.d_model,), self.dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = L.dense_init(ks[1], cfg.d_model,
                                             cfg.padded_vocab, self.dtype)
        if self.n_lead:
            lead_ks = jax.random.split(ks[2], self.n_lead)
            params["lead_blocks"] = [self._block_init(k, lead=True)
                                     for k in lead_ks]
        scan_ks = jax.random.split(ks[3], self.n_scan)
        params["blocks"] = jax.vmap(
            functools.partial(self._block_init, lead=False))(scan_ks)
        return params

    # ----------------------------------------------------------------- block
    def _block(self, p, x, positions, aux, *, impl=None, experts=None,
               layer=None):
        cfg = self.cfg
        impl = impl or cfg.attention_impl
        if cfg.family == "dense":
            # sequence-parallel residual stream (§Perf A5/B3).  Gated to
            # the dense family: MoE dispatch and VLM prefix concat fight
            # the seq-sharded layout (measured regressions, EXPERIMENTS).
            x = L.constrain_hidden(x)
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = L.attn_qkv(p["attn"], h, num_heads=cfg.num_heads,
                             num_kv_heads=cfg.num_kv_heads,
                             head_dim=self.head_dim, positions=positions,
                             rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
                             norm_eps=cfg.norm_eps)
        o = L.attention(q, k, v, q_positions=positions, kv_positions=positions,
                        causal=True, window=cfg.swa_window, impl=impl)
        x = x + L.attn_out(p["attn"], o)
        h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        y, a, _ = self._ffn(p, h, experts=experts, layer=layer)
        return x + y, aux + a

    def _ffn(self, p, h, valid=None, experts=None, layer=None):
        """A block's feed-forward on ``h``: (output, aux loss, experts
        read).  ``valid`` (B, S) marks real tokens; a MoE block routes
        padded ones to no expert.  ``experts``: the routed experts'
        weights of every scanned layer (``_split_experts``), of which the
        block is scanned layer ``layer``."""
        if "moe" in p:
            mp = p["moe"] if experts is None else {**p["moe"], **experts}
            return M.moe_ffn(h, mp, self.cfg.moe, valid, layer)
        return L.ffn_apply(p["ffn"], h), jnp.float32(0.0), jnp.int32(0)

    def _split_experts(self, blocks):
        """(the scanned blocks without their routed experts' weights,
        those weights stacked over the layers, or None).  The layer scans
        carry the experts whole and hand each layer its index instead of
        its slice: a scan's slice of the (E, D, F) weights would copy
        every expert of the layer, read or not, on every call."""
        if "moe" not in blocks:
            return blocks, None
        moe = dict(blocks["moe"])
        experts = {k: moe.pop(k) for k in ("w_gate", "w_up", "w_down")}
        return dict(blocks, moe=moe), experts

    # --------------------------------------------------------------- forward
    def forward(self, params: Params, tokens: jax.Array,
                prefix_embeds: Optional[jax.Array] = None,
                remat: bool = False) -> Tuple[jax.Array, jax.Array]:
        """Teacher-forced forward. Returns (logits (B,S,Vpad), aux_loss)."""
        cfg = self.cfg
        x = params["embed"][tokens]
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        aux = jnp.float32(0.0)

        for p in params.get("lead_blocks", []):
            x, aux = self._block(p, x, positions, aux)
        blocks, experts = self._split_experts(params["blocks"])

        def body(carry, xs):
            x, aux = carry
            p, l = xs
            x, aux = self._block(p, x, positions, aux, experts=experts,
                                 layer=l)
            return (x, aux), None

        if remat:
            body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
        (x, aux), _ = lax.scan(body, (x, aux), (blocks, self._scan_index()))

        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = x @ head
        return logits, aux

    def loss_fn(self, params: Params, batch: Dict[str, jax.Array],
                remat: bool = False) -> jax.Array:
        cfg = self.cfg
        logits, aux = self.forward(params, batch["tokens"],
                                   prefix_embeds=batch.get("patch_embeds"),
                                   remat=remat)
        labels, mask = batch["labels"], batch.get("loss_mask")
        if batch.get("patch_embeds") is not None:
            npat = batch["patch_embeds"].shape[1]
            logits = logits[:, npat:]
        ce = L.cross_entropy(logits[:, :-1], labels[:, 1:], cfg.vocab_size,
                             mask=None if mask is None else mask[:, 1:])
        weight = cfg.moe.router_aux_weight if cfg.moe else 0.0
        return ce + weight * aux

    # ------------------------------------------------------------- KV cache
    def cache_batch_axes(self, cache):
        """Batch axis per cache leaf (for tiling/splitting request batches)."""
        return {k: (0 if k == "length" else 1) for k in cache}

    # ------------------------------------------------- paged-KV engine hooks
    def paged_kv_layout(self) -> Optional[Tuple[int, int, int]]:
        """(layers, kv_heads, head_dim) for a PagedKVCache backing this
        model's KV, or None when pages can't back it (SWA ring buffers
        wrap in place, which fights immutable full pages)."""
        if self.cfg.swa_window:
            return None
        return (self.cfg.num_layers, self.cfg.num_kv_heads, self.head_dim)

    def cache_kv_rows_dev(self, cache, row: int, length: int):
        """One sequence's KV from a dense cache as DEVICE arrays
        ``(L_total, length, Hkv, Dh)`` — lead layers first, then scanned.
        This is the page-store write format: the device-resident pool
        scatters these rows into pages without a host round-trip
        (``length`` is passed by the caller so no device sync is needed
        to read ``cache['length']``)."""
        ks = [cache["k"][:, row, :length]]
        vs = [cache["v"][:, row, :length]]
        if "lead_k" in cache:
            ks.insert(0, cache["lead_k"][:, row, :length])
            vs.insert(0, cache["lead_v"][:, row, :length])
        k = jnp.concatenate(ks, axis=0) if len(ks) > 1 else ks[0]
        v = jnp.concatenate(vs, axis=0) if len(vs) > 1 else vs[0]
        return k, v

    def cache_kv_rows(self, cache, row: int):
        """Host (float32 numpy) variant of :meth:`cache_kv_rows_dev` —
        the migration wire format (exact for bf16)."""
        ln = int(cache["length"][row])
        k, v = self.cache_kv_rows_dev(cache, row, ln)
        return (np.asarray(k, dtype=np.float32),
                np.asarray(v, dtype=np.float32))

    def paged_cache_view(self, k_rows, v_rows, lengths):
        """Materialize the dense decode cache from gathered page rows.

        k_rows/v_rows: float32 numpy ``(B, L_total, T, Hkv, Dh)`` (zero-
        padded past each row's length); lengths: per-row token counts.
        The float32→model-dtype cast is exact for bf16 page contents.
        """
        k = jnp.asarray(k_rows, self.dtype).swapaxes(0, 1)  # (L,B,T,H,D)
        v = jnp.asarray(v_rows, self.dtype).swapaxes(0, 1)
        cache = {"k": k[self.n_lead:], "v": v[self.n_lead:],
                 "length": jnp.asarray(lengths, jnp.int32)}
        if self.n_lead:
            cache["lead_k"] = k[:self.n_lead]
            cache["lead_v"] = v[:self.n_lead]
        return cache

    def decode_kv_taps(self, cache, slots):
        """KV written at per-row ``slots`` (the last decode step's token)
        as float32 numpy ``(L_total, B, Hkv, Dh)`` — the page-append
        payload mirroring one `decode_step`."""
        ix = jnp.asarray(slots, jnp.int32)[None, :, None, None, None]

        def tap(a):                                   # (L,B,T,H,D)->(L,B,H,D)
            idx = jnp.broadcast_to(ix, a.shape[:2] + (1,) + a.shape[3:])
            return jnp.take_along_axis(a, idx, axis=2)[:, :, 0]

        ks = [tap(cache["k"])]
        vs = [tap(cache["v"])]
        if "lead_k" in cache:
            ks.insert(0, tap(cache["lead_k"]))
            vs.insert(0, tap(cache["lead_v"]))
        k = jnp.concatenate(ks, axis=0) if len(ks) > 1 else ks[0]
        v = jnp.concatenate(vs, axis=0) if len(vs) > 1 else vs[0]
        return (np.asarray(k, dtype=np.float32),
                np.asarray(v, dtype=np.float32))

    def cache_capacity(self, max_len: int) -> int:
        cfg = self.cfg
        return min(max_len, cfg.swa_window) if cfg.swa_window else max_len

    def extend_cache(self, cache, extra: int):
        """Grow the KV time axis by ``extra`` zero slots (decode headroom).

        Ring-buffer (SWA) caches already at window capacity are returned
        unchanged — the ring slot logic handles wrap-around.
        """
        T = cache["k"].shape[2]
        target = self.cache_capacity(T + extra)
        if target <= T:
            return cache
        pad = target - T
        out = dict(cache)
        for key in ("k", "v", "lead_k", "lead_v"):
            if key in cache:
                c = cache[key]
                cfgpad = [(0, 0)] * c.ndim
                cfgpad[2] = (0, pad)
                out[key] = jnp.pad(c, cfgpad)
        return out

    def init_cache(self, batch: int, max_len: int) -> Dict[str, jax.Array]:
        cfg = self.cfg
        T = self.cache_capacity(max_len)
        shape = (self.n_scan, batch, T, cfg.num_kv_heads, self.head_dim)
        cache = {
            "k": jnp.zeros(shape, self.dtype),
            "v": jnp.zeros(shape, self.dtype),
            "length": jnp.zeros((batch,), jnp.int32),
        }
        if self.n_lead:
            lshape = (self.n_lead,) + shape[1:]
            cache["lead_k"] = jnp.zeros(lshape, self.dtype)
            cache["lead_v"] = jnp.zeros(lshape, self.dtype)
        return cache

    def _kv_slot_positions(self, pos: jax.Array, T: int) -> jax.Array:
        """Absolute position stored in each cache slot when writing at `pos`.

        pos: (B,). Returns (B, T) with -1 for empty slots.  For ring buffers
        (SWA) slot s holds the newest position q ≡ s (mod T), q <= pos.
        """
        slots = jnp.arange(T, dtype=jnp.int32)[None, :]
        p = pos[:, None]
        if self.cfg.swa_window and self.cfg.swa_window == T:
            q = p - ((p - slots) % T)
        else:
            q = slots
        return jnp.where((q >= 0) & (q <= p), q, -1)

    # --------------------------------------------------------------- prefill
    def prefill(self, params: Params, tokens: jax.Array,
                prefix_embeds: Optional[jax.Array] = None,
                impl: Optional[str] = None, count_experts: bool = False):
        """Run the full prompt; return (last-position logits, filled
        cache), and with ``count_experts`` the experts read, summed over
        the MoE layers (int32)."""
        cfg = self.cfg
        x = params["embed"][tokens]
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        T = self.cache_capacity(S)

        def run_block(p, x, experts=None, layer=None):
            if cfg.family == "dense":      # sequence-parallel residual (SP)
                x = L.constrain_hidden(x)
            h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
            q, k, v = L.attn_qkv(p["attn"], h, num_heads=cfg.num_heads,
                                 num_kv_heads=cfg.num_kv_heads,
                                 head_dim=self.head_dim, positions=positions,
                                 rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
                                 norm_eps=cfg.norm_eps)
            o = L.attention(q, k, v, q_positions=positions,
                            kv_positions=positions, causal=True,
                            window=cfg.swa_window, impl=impl or cfg.attention_impl)
            x = x + L.attn_out(p["attn"], o)
            h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
            y, _, read = self._ffn(p, h, experts=experts, layer=layer)
            # keep only the cache window (T divides S for all assigned shapes)
            return x + y, (k[:, S - T:], v[:, S - T:]), read

        lead_kv = []
        read = jnp.int32(0)
        for p in params.get("lead_blocks", []):
            x, kv, r = run_block(p, x)
            lead_kv.append(kv)
            read = read + r

        blocks, experts = self._split_experts(params["blocks"])

        def body(carry, xs):
            x, read = carry
            p, l = xs
            x, kv, r = run_block(p, x, experts, l)
            return (x, read + r), kv

        (x, read), (ks, vs) = lax.scan(body, (x, read),
                                       (blocks, self._scan_index()))

        cache = {
            "k": ks, "v": vs,
            "length": jnp.full((B,), S, jnp.int32),
        }
        if lead_kv:
            cache["lead_k"] = jnp.stack([kv[0] for kv in lead_kv])
            cache["lead_v"] = jnp.stack([kv[1] for kv in lead_kv])
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        return _counted((x[:, -1] @ head, cache), read, count_experts)

    # ----------------------------------------------------- chunked prefill
    def prefill_with_cache(self, params: Params, tokens: jax.Array,
                           cache: Dict[str, jax.Array],
                           impl: Optional[str] = None,
                           valid_len: Optional[jax.Array] = None,
                           count_experts: bool = False):
        """Prefill ``tokens`` (B, S_suf) as a continuation of ``cache``.

        The chunk's queries attend to the cached KV (a reused prefix, in
        the engine: gathered from shared pages) plus the chunk itself;
        the chunk's KV is written into the cache at its absolute slots.
        Full-attention caches only (slot s holds position s), so the
        result is bitwise what a monolithic ``prefill`` of prefix+chunk
        would produce for these positions.

        ``valid_len`` (B,) marks the REAL chunk length when ``tokens``
        is right-padded to a bucketed shape (the engine pads suffixes so
        timing-dependent prefix-share points reuse one compiled step).
        Causal attention keeps pad rows out of every real row's result;
        logits are read at ``valid_len - 1`` and the cache length
        advances by ``valid_len``, so padding is bitwise-invisible; a MoE
        block routes pad rows to no expert.  ``count_experts`` appends
        the experts read, as in :meth:`prefill`.
        """
        cfg = self.cfg
        assert not cfg.swa_window, "chunked prefill needs full attention"
        B, Ssuf = tokens.shape
        pos0 = cache["length"]                               # (B,)
        x = params["embed"][tokens]
        positions = pos0[:, None] + jnp.arange(Ssuf, dtype=jnp.int32)[None, :]
        T = cache["k"].shape[2]
        arange_t = jnp.arange(T, dtype=jnp.int32)[None, :]
        kv_pos = jnp.where(arange_t < (pos0 + Ssuf)[:, None], arange_t, -1)
        batch_ix = jnp.arange(B, dtype=jnp.int32)[:, None]
        tok_valid = None if valid_len is None else (
            jnp.arange(Ssuf, dtype=jnp.int32)[None, :] < valid_len[:, None])

        def run_block(p, x, k_cache, v_cache, experts=None, layer=None):
            if cfg.family == "dense":      # sequence-parallel residual (SP)
                x = L.constrain_hidden(x)
            h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
            q, k, v = L.attn_qkv(p["attn"], h, num_heads=cfg.num_heads,
                                 num_kv_heads=cfg.num_kv_heads,
                                 head_dim=self.head_dim, positions=positions,
                                 rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
                                 norm_eps=cfg.norm_eps)
            k_cache = k_cache.at[batch_ix, positions].set(k)
            v_cache = v_cache.at[batch_ix, positions].set(v)
            o = L.attention(q, k_cache, v_cache, q_positions=positions,
                            kv_positions=kv_pos, causal=True, window=0,
                            impl=impl or cfg.attention_impl)
            x = x + L.attn_out(p["attn"], o)
            h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
            y, _, read = self._ffn(p, h, tok_valid, experts, layer)
            return x + y, k_cache, v_cache, read

        new_cache = dict(cache)
        read = jnp.int32(0)
        if self.n_lead:
            lk, lv = [], []
            for i, p in enumerate(params["lead_blocks"]):
                x, k_c, v_c, r = run_block(p, x, cache["lead_k"][i],
                                           cache["lead_v"][i])
                lk.append(k_c)
                lv.append(v_c)
                read = read + r
            new_cache["lead_k"] = jnp.stack(lk)
            new_cache["lead_v"] = jnp.stack(lv)

        blocks, experts = self._split_experts(params["blocks"])

        def body(carry, xs):
            x, read = carry
            p, k_c, v_c, l = xs
            x, k_c, v_c, r = run_block(p, x, k_c, v_c, experts, l)
            return (x, read + r), (k_c, v_c)

        (x, read), (ks, vs) = lax.scan(
            body, (x, read),
            (blocks, cache["k"], cache["v"], self._scan_index()))
        new_cache["k"], new_cache["v"] = ks, vs
        new_cache["length"] = pos0 + (Ssuf if valid_len is None
                                      else valid_len)

        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        if valid_len is None:
            last = x[:, -1]
        else:
            last = jnp.take_along_axis(
                x, (valid_len - 1).astype(jnp.int32)[:, None, None],
                axis=1)[:, 0]
        return _counted((last @ head, new_cache), read, count_experts)

    # ------------------------------------------------------------ decode step
    def decode_step(self, params: Params, token: jax.Array,
                    cache: Dict[str, jax.Array],
                    impl: Optional[str] = None, count_experts: bool = False):
        """token: (B,) int32. One autoregressive step; updates cache in
        place.  ``count_experts`` appends the experts read, as in
        :meth:`prefill`."""
        cfg = self.cfg
        B = token.shape[0]
        pos = cache["length"]                                  # (B,)
        x = params["embed"][token][:, None, :]                 # (B,1,D)
        T = cache["k"].shape[2]
        slot = (pos % T).astype(jnp.int32)
        kv_pos = self._kv_slot_positions(pos, T)               # (B,T)
        batch_ix = jnp.arange(B, dtype=jnp.int32)

        def step_block(p, x, k_cache, v_cache, experts=None, layer=None):
            h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
            q, k, v = L.attn_qkv(p["attn"], h, num_heads=cfg.num_heads,
                                 num_kv_heads=cfg.num_kv_heads,
                                 head_dim=self.head_dim,
                                 positions=pos[:, None],
                                 rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
                                 norm_eps=cfg.norm_eps)
            k_cache = k_cache.at[batch_ix, slot].set(k[:, 0])
            v_cache = v_cache.at[batch_ix, slot].set(v[:, 0])
            o = L.attention(q, k_cache, v_cache, q_positions=pos[:, None],
                            kv_positions=kv_pos, causal=True,
                            window=cfg.swa_window,
                            impl=impl or cfg.attention_impl)
            x = x + L.attn_out(p["attn"], o)
            h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
            y, _, read = self._ffn(p, h, experts=experts, layer=layer)
            return x + y, k_cache, v_cache, read

        new_cache = dict(cache)
        read = jnp.int32(0)
        if self.n_lead:
            lk, lv = [], []
            for i, p in enumerate(params["lead_blocks"]):
                x, k_c, v_c, r = step_block(p, x, cache["lead_k"][i],
                                            cache["lead_v"][i])
                lk.append(k_c)
                lv.append(v_c)
                read = read + r
            new_cache["lead_k"] = jnp.stack(lk)
            new_cache["lead_v"] = jnp.stack(lv)

        blocks, experts = self._split_experts(params["blocks"])

        def body(carry, xs):
            x, read = carry
            p, k_c, v_c, l = xs
            x, k_c, v_c, r = step_block(p, x, k_c, v_c, experts, l)
            return (x, read + r), (k_c, v_c)

        (x, read), (ks, vs) = lax.scan(
            body, (x, read),
            (blocks, cache["k"], cache["v"], self._scan_index()))
        new_cache["k"], new_cache["v"] = ks, vs
        new_cache["length"] = pos + 1

        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        return _counted((x[:, -1] @ head, new_cache), read, count_experts)

    # ----------------------------------------------------- paged decode step
    def paged_decode_step(self, params: Params, token: jax.Array,
                          k_pages: jax.Array, v_pages: jax.Array,
                          page_table: jax.Array, lengths: jax.Array,
                          impl: Optional[str] = None,
                          variant: Optional[str] = None,
                          count_experts: bool = False):
        """One autoregressive step straight over the device-resident page
        pool — no dense KV view exists anywhere.

        token: (B,) int32; k_pages/v_pages: the pool, (L_total, P, page,
        Hkv, Dh); page_table: (B, n_pages) int32 (each row's pages in
        sequence order, zero-padded); lengths: (B,) int32 with -1 for
        padded rows.  Each layer lands the new token's KV at
        ``(page_table[b, len//page], len % page)`` and attends over the
        row's pages.  Under the Pallas impls the kernel ``variant``
        (None = the autotune table, see
        ``kernels/paged_decode_attention/ops.py``) picks how: ``fused``
        appends INSIDE the attention ``pallas_call`` (no separate
        scatter dispatch, no extra pool round-trip per layer);
        ``single``/``blocked`` scatter first, then attend.  The XLA
        fallback scatters and gathers densely.  Padded rows write
        nothing and are fully masked.  The whole pool is the layer
        scan's carry, so under donation every layer's write lands in
        the donated buffer: no pool-sized temporary, no copy back.
        Returns ``(logits (B, Vpad), new_k_pages, new_v_pages)``, and
        with ``count_experts`` the experts read (a MoE block routes padded
        rows to no expert), as in :meth:`prefill`; the caller adopts the
        returned pool arrays (donated under jit).
        """
        cfg = self.cfg
        impl = impl or cfg.attention_impl
        B = token.shape[0]
        P, ps = k_pages.shape[1], k_pages.shape[2]
        T = page_table.shape[1] * ps
        pos = lengths                                        # (B,)
        valid = pos >= 0
        posc = jnp.maximum(pos, 0)
        x = params["embed"][token][:, None, :]               # (B,1,D)
        write_page = jnp.take_along_axis(
            page_table, (posc // ps)[:, None], axis=1)[:, 0]
        write_page = jnp.where(valid, write_page, P)         # OOB -> dropped
        write_off = posc % ps
        t_idx = jnp.arange(T, dtype=jnp.int32)
        kv_pos = jnp.where(t_idx[None, :] <= pos[:, None], t_idx[None, :], -1)

        use_pallas = impl in ("pallas", "pallas_interpret")
        if use_pallas:
            from repro.kernels.paged_decode_attention.ops import (
                fused_paged_decode_attention, kernel_config,
                paged_decode_attention)
            kc = kernel_config(ps, cfg.num_kv_heads, self.head_dim,
                               cfg.num_heads // cfg.num_kv_heads)
            eff_variant = variant or kc["variant"]

        def step_block(p, x, kp, vp, l, experts=None):
            # kp/vp: the whole pool; l: this layer's index in it
            h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
            q, k, v = L.attn_qkv(p["attn"], h, num_heads=cfg.num_heads,
                                 num_kv_heads=cfg.num_kv_heads,
                                 head_dim=self.head_dim,
                                 positions=posc[:, None],
                                 rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
                                 norm_eps=cfg.norm_eps)
            if use_pallas and eff_variant == "fused":
                # append+attend in ONE dispatch: the kernel writes the
                # new KV rows into their (page, offset) slots of its
                # layer before any page is read; the layer goes back in
                o, kp_l, vp_l = fused_paged_decode_attention(
                    q, lax.dynamic_index_in_dim(kp, l, keepdims=False),
                    lax.dynamic_index_in_dim(vp, l, keepdims=False),
                    page_table, pos,
                    k[:, 0].astype(kp.dtype), v[:, 0].astype(vp.dtype),
                    interpret=impl == "pallas_interpret")
                kp = lax.dynamic_update_index_in_dim(kp, kp_l, l, 0)
                vp = lax.dynamic_update_index_in_dim(vp, vp_l, l, 0)
            else:
                kp = kp.at[l, write_page, write_off].set(
                    k[:, 0].astype(kp.dtype), mode="drop")
                vp = vp.at[l, write_page, write_off].set(
                    v[:, 0].astype(vp.dtype), mode="drop")
                if use_pallas:
                    # the kernels read the pool as stored and upcast per
                    # page; casting it here would copy the whole layer
                    o = paged_decode_attention(
                        q, lax.dynamic_index_in_dim(kp, l, keepdims=False),
                        lax.dynamic_index_in_dim(vp, l, keepdims=False),
                        page_table, pos,
                        interpret=impl == "pallas_interpret",
                        variant=eff_variant)
                else:
                    kd = kp[l, page_table].reshape(
                        B, T, cfg.num_kv_heads, self.head_dim).astype(self.dtype)
                    vd = vp[l, page_table].reshape(
                        B, T, cfg.num_kv_heads, self.head_dim).astype(self.dtype)
                    o = L.attention(q, kd, vd, q_positions=posc[:, None],
                                    kv_positions=kv_pos, causal=True,
                                    window=cfg.swa_window, impl="xla")
            x = x + L.attn_out(p["attn"], o)
            h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
            y, _, read = self._ffn(p, h, valid[:, None], experts,
                                   l - self.n_lead)
            return x + y, kp, vp, read

        # the pool rides in the scan's carry and each layer updates it in
        # place; as xs/ys it would be sliced per layer into a fresh
        # pool-sized buffer and copied back over the donated one every step
        ks, vs, read = k_pages, v_pages, jnp.int32(0)
        for i, p in enumerate(params.get("lead_blocks", [])):
            x, ks, vs, r = step_block(p, x, ks, vs, i)
            read = read + r

        blocks, experts = self._split_experts(params["blocks"])

        def body(carry, xs):
            x, ks, vs, read = carry
            p, l = xs
            x, ks, vs, r = step_block(p, x, ks, vs, l, experts)
            return (x, ks, vs, read + r), None

        (x, ks, vs, read), _ = lax.scan(
            body, (x, ks, vs, read),
            (blocks, self.n_lead + self._scan_index()))

        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        return _counted((x[:, -1] @ head, ks, vs), read, count_experts)


def _counted(out: tuple, read: jax.Array, count_experts: bool) -> tuple:
    """A step's outputs, with the experts it read appended when asked."""
    return out + (read,) if count_experts else out
