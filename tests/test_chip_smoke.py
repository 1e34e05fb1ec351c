"""The chip bring-up path on the CPU: ``chip_smoke.py --cpu-rehearsal``
runs every phase of the chip check at the smoke preset (so the script
cannot rot between chip runs), and the pieces of real mode it shares
with ``repro.launch.serve`` hold without a chip."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from repro.configs import SERVED_ARCH, get_config, get_smoke, models_for
from repro.core import TPU_V5E, hardware_for_device_kind
from repro.engine.engine import InferenceEngine
from repro.engine.kvcache import PagedKVCache
from repro.launch import serve
from repro.workloads import build_workload

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cpu_rehearsal_runs_every_phase(capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert _chip_smoke().main(["--cpu-rehearsal"]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}
    for tag in ("[a]", "[b]", "[c]", "[d]"):
        assert any(line.startswith(tag) for line in lines), tag
    assert any("warm rerun identical" in line for line in lines)


def test_without_a_tpu_the_chip_run_fails(capsys):
    with pytest.raises(RuntimeError, match="needs 'tpu'"):
        _chip_smoke().main([])
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = subprocess.run([sys.executable, str(tmp_path / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=60,
                       cwd=tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_models_for_maps_every_model_id():
    graph, _, _ = build_workload("w1", 2)
    names = {graph.nodes[n].model for n in graph.llm_nodes()}
    full = models_for(graph)
    assert set(full) == names
    for name, cfg in full.items():
        assert cfg == get_config(SERVED_ARCH).replace(name=name)
    smoke = models_for(graph, smoke=True)
    assert smoke[sorted(names)[0]].d_model == get_smoke(SERVED_ARCH).d_model


def test_hardware_comes_from_the_device_kind():
    assert hardware_for_device_kind("TPU v5 lite") is TPU_V5E
    with pytest.raises(ValueError, match="no hardware profile"):
        hardware_for_device_kind("cpu")
    with pytest.raises(ValueError, match="no hardware profile"):
        serve.device_hardware()              # the CPU names none
    assert serve.device_hardware("h200").name == "h200"


def test_workers_that_fit_without_a_memory_limit():
    graph, _, _ = build_workload("wt", 2)
    n, why = serve.workers_that_fit(models_for(graph, smoke=True), 2)
    assert n == 2 and "no memory limit" in why


@pytest.mark.parametrize("cfg, gb", [(get_smoke("qwen3-1.7b"), None),
                                     (get_smoke("deepseek-moe-16b"), None),
                                     (get_config("qwen3-1.7b"), 5.32)],
                         ids=["qwen3-smoke", "moe-smoke", "qwen3-full"])
def test_engine_hbm_bytes_count_params_and_one_pool(cfg, gb):
    """A paged engine holds its params and one KV pool: the decode step
    and ``PagedKVCache``'s writer both take the pool donated and update
    it in place, so no second pool is ever alive and ``workers_that_fit``
    counts the pool once.  A full-width qwen3-1.7b engine is 5.32 GB, and
    16.91 GB holds three of them."""
    eng = InferenceEngine(cfg)
    layers, kv_heads, head_dim = eng.model.paged_kv_layout()
    pool = PagedKVCache.pool_bytes(layers, eng.num_pages, eng.page_size,
                                   kv_heads, head_dim)
    assert eng.hbm_bytes() == eng.param_bytes() + pool
    if gb is not None:
        assert round(eng.hbm_bytes() / 1e9, 2) == gb
        assert int(16.91e9 // eng.hbm_bytes()) == 3
