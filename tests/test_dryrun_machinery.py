"""Dry-run machinery on a small (8-device) mesh — subprocess so the
device count doesn't leak into other tests."""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax
from repro.configs import SHAPES, get_smoke, input_specs
from repro.distribution.sharding import (ShardingPolicy, input_shardings,
                                         param_shardings)
from repro.engine.models import build_model
from repro.launch.hlo_cost import analyze_hlo
from repro.training.optimizer import AdamWConfig, adamw_init
from repro.training.trainer import TrainerConfig, make_train_step

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_smoke("qwen3-8b").replace(d_model=64, d_ff=256, vocab_size=512)
model = build_model(cfg)
pol = ShardingPolicy.for_mesh(mesh)
params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
p_sh = param_shardings(params_shape, mesh, pol)
opt_shape = jax.eval_shape(adamw_init, params_shape)
o_sh = param_shardings(opt_shape, mesh, pol)

step = make_train_step(cfg, TrainerConfig(remat=True,
                                          adamw=AdamWConfig(total_steps=10)))
specs = {"tokens": jax.ShapeDtypeStruct((8, 32), jax.numpy.int32),
         "labels": jax.ShapeDtypeStruct((8, 32), jax.numpy.int32)}
from jax.sharding import NamedSharding, PartitionSpec as P
b_sh = {k: NamedSharding(mesh, P("data", None)) for k in specs}
fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
             out_shardings=(p_sh, o_sh, None))
compiled = fn.lower(params_shape, opt_shape, specs).compile()
r = analyze_hlo(compiled.as_text(), score_dims={32})
assert r["flops"] > 0, r
assert compiled.cost_analysis() is not None
print("DRYRUN_OK", r["flops"])
"""


@pytest.mark.multidevice
def test_lower_compile_on_8_device_mesh():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SCRIPT],
                       capture_output=True, text=True, timeout=420,
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       env=env)
    assert "DRYRUN_OK" in r.stdout, r.stderr[-2000:]
