"""Distribution layer: sharding-policy divisibility (pure logic) and
shard_map collectives (subprocess with 8 host devices)."""
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.distribution.sharding import ShardingPolicy, _spec_for_leaf
from repro.engine.models import build_model


def _fake_mesh(shape_dict):
    return SimpleNamespace(shape=shape_dict)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_policy_specs_always_divisible(arch):
    """Every generated PartitionSpec divides its tensor dim — jax would
    reject NamedShardings otherwise (llama3.2-3b's 24 heads etc.)."""
    cfg = get_config(arch)
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    mesh = _fake_mesh({"pod": 2, "data": 16, "model": 16})
    pol = ShardingPolicy(fsdp_axes=("pod", "data"),
                         batch_axes=("pod", "data"))

    def walk(tree, prefix=""):
        if isinstance(tree, dict):
            for k, v in tree.items():
                walk(v, f"{prefix}/{k}")
            return
        if isinstance(tree, (list, tuple)):
            for i, v in enumerate(tree):
                walk(v, f"{prefix}/{i}")
            return
        spec = _spec_for_leaf(prefix, tree.shape, mesh, pol)
        for dim, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            assert tree.shape[dim] % n == 0, (prefix, tree.shape, spec)

    walk(shapes)


def test_big_weights_are_sharded():
    cfg = get_config("qwen3-8b")
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    mesh = _fake_mesh({"data": 16, "model": 16})
    pol = ShardingPolicy()
    spec = _spec_for_leaf("/embed", shapes["embed"].shape, mesh, pol)
    assert any(e is not None for e in spec)
    blocks_wq = shapes["blocks"]["attn"]["wq"]
    spec = _spec_for_leaf("/blocks/attn/wq", blocks_wq.shape, mesh, pol)
    assert spec[0] is None                      # stacked layer dim untouched
    assert any(e is not None for e in spec[1:])


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.distribution.collectives import (sharded_decode_attention,
                                            compressed_psum_grads)
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.training.grad_compress import init_error_state

auto = jax.sharding.AxisType.Auto
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(auto,) * 2)
rng = np.random.default_rng(0)
B, H, Hkv, Dh, T = 2, 4, 2, 16, 32
q = jnp.asarray(rng.normal(size=(B, H, Dh)), jnp.float32)
k = jnp.asarray(rng.normal(size=(B, T, Hkv, Dh)), jnp.float32)
v = jnp.asarray(rng.normal(size=(B, T, Hkv, Dh)), jnp.float32)
qp = jnp.array([25, 31], jnp.int32)
kp = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
kp = jnp.where(kp <= qp[:, None], kp, -1)
out = sharded_decode_attention(q, k, v, qp, kp, mesh=mesh)
ref = decode_attention_ref(q, k, v, q_positions=qp, kv_positions=kp)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           atol=2e-5, rtol=2e-5)

mesh2 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                      axis_types=(auto,) * 3)
g = {"w": jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)}
mean_g, _ = compressed_psum_grads(g, init_error_state(g), mesh=mesh2)
rel = float(jnp.abs(mean_g["w"] - g["w"]).max() / jnp.abs(g["w"]).max())
assert rel < 0.02, rel
print("SUBPROC_OK")
"""


@pytest.mark.multidevice
def test_shard_map_collectives_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SUBPROC],
                       capture_output=True, text=True, timeout=300,
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       env=env)
    assert "SUBPROC_OK" in r.stdout, r.stderr[-2000:]
