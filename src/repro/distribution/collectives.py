"""shard_map collectives: KV-seq-split flash-decoding + compressed
cross-pod gradient reduction.

``sharded_decode_attention`` is the distribution-level twin of the
decode kernel: the KV cache is sharded along its TIME axis over the
"model" mesh axis; every shard runs flash-decode over its local chunk
and the partial (out, m, l) triples merge with the log-sum-exp combine —
the same merge the kernel uses across VMEM chunks, lifted to ICI.  This
is how a 67B × 32k × 128-request cache (~0.8 TiB) decodes across 256
chips without any single chip holding the context.

``compressed_psum_grads`` wires grad_compress into a cross-pod psum:
int8 quantize (+error feedback) → int32 psum over "pod" → dequantize.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.training.grad_compress import dequantize, quantize_error_feedback

NEG_INF = -1e30


def sharded_decode_attention(q, k, v, q_positions, kv_positions, *,
                             mesh: Mesh, kv_axis: str = "model",
                             window: int = 0):
    """q: (B,H,Dh) replicated over kv_axis; k,v: (B,T,Hkv,Dh) with T
    sharded over kv_axis; kv_positions (B,T) sharded alike."""

    def local(qb, kb, vb, qp, kp):
        out, m, l = decode_attention_ref(
            qb, kb, vb, q_positions=qp, kv_positions=kp, window=window,
            return_lse=True)
        # merge partial softmax stats across KV shards (flash-decoding)
        m_max = lax.pmax(m, kv_axis)                      # (B,H)
        w = jnp.exp(m - m_max) * l
        num = lax.psum(out.astype(jnp.float32) * w[..., None], kv_axis)
        den = lax.psum(w, kv_axis)
        den = jnp.where(den == 0.0, 1.0, den)
        return (num / den[..., None]).astype(qb.dtype)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, None, None), P(None, kv_axis, None, None),
                  P(None, kv_axis, None, None), P(None),
                  P(None, kv_axis)),
        out_specs=P(None, None, None),
        check_vma=False,
    )(q, k, v, q_positions, kv_positions)


def compressed_psum_grads(grads: Any, err_state: Any, *, mesh: Mesh,
                          axis: str = "pod") -> Tuple[Any, Any]:
    """int8(+EF) all-reduce of a gradient pytree over the slow axis.

    Inputs are assumed replicated over ``axis`` up to their local shard
    values (per-pod partial gradients); returns (mean grads, new error
    state).  2× less DCN traffic than bf16, 4× less than f32.
    """
    n = mesh.shape[axis]

    def local(g_tree, e_tree):
        def one(g, e):
            q, scale, new_err = quantize_error_feedback(g, e)
            q32 = lax.psum(q.astype(jnp.int32), axis)
            # conservative shared scale: max over pods
            s = lax.pmax(scale, axis)
            return dequantize(q32, s) / n, new_err
        flat_g, treedef = jax.tree.flatten(g_tree)
        flat_e = jax.tree.leaves(e_tree)
        outs = [one(g, e) for g, e in zip(flat_g, flat_e)]
        return (jax.tree.unflatten(treedef, [o[0] for o in outs]),
                jax.tree.unflatten(treedef, [o[1] for o in outs]))

    spec = jax.tree.map(lambda _: P(), grads)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec, spec), out_specs=(spec, spec),
        check_vma=False,
    )(grads, err_state)
