"""Autotune table plumbing + kernel roofline accounting.

Covers the pieces that make the speed-of-light decode kernel safe to
ship: ``kernel_config`` resolution (checked-in table -> exact shape key
-> env overrides), the ``persist_table`` refusal to write tables
measured under the Pallas interpreter, and the bytes/FLOPs cost model
the %-of-roofline rows score against.
"""
import json

import pytest

from benchmarks import kernel_bench
from repro.configs.base import HBM_BW, PEAK_FLOPS_BF16
from repro.kernels.paged_decode_attention import ops as paged_ops
from repro.launch.roofline import (kernel_time_bound, paged_decode_cost,
                                   pct_of_roofline)


# ---------------------------------------------------------------------------
# kernel_config resolution
# ---------------------------------------------------------------------------

def test_shape_key_format():
    assert paged_ops.shape_key(64, 8, 128, 4) == "ps64-hkv8-dh128-g4"


def test_kernel_config_default_and_exact_key():
    """The checked-in table's default applies to unknown shapes; an
    exact shape key overrides it."""
    kc = paged_ops.kernel_config(999, 999, 999, 999)   # no such key
    assert kc["variant"] in paged_ops.VARIANTS
    assert kc["pages_per_block"] >= 1
    assert kc["grid_layout"] in ("bh", "hb")
    # ps64-hkv4-dh64-g8 is a seeded entry with ppb=8
    kc = paged_ops.kernel_config(64, 4, 64, 8)
    assert kc["pages_per_block"] == 8


def test_kernel_config_env_table_override(tmp_path, monkeypatch):
    """REPRO_KERNEL_AUTOTUNE points at an alternate table file."""
    table = {"configs": {
        "default": {"variant": "blocked", "pages_per_block": 2,
                    "grid_layout": "hb"},
        "ps32-hkv4-dh64-g2": {"variant": "single", "pages_per_block": 1,
                              "grid_layout": "bh"}}}
    p = tmp_path / "table.json"
    p.write_text(json.dumps(table))
    monkeypatch.setenv("REPRO_KERNEL_AUTOTUNE", str(p))
    paged_ops._load_table.cache_clear()
    try:
        assert paged_ops.kernel_config(7, 7, 7, 7) == {
            "variant": "blocked", "pages_per_block": 2,
            "grid_layout": "hb"}
        assert paged_ops.kernel_config(32, 4, 64, 2)["variant"] == "single"
    finally:
        paged_ops._load_table.cache_clear()


def test_kernel_config_env_variant_force(monkeypatch):
    """REPRO_PAGED_VARIANT force-overrides whatever the table says."""
    monkeypatch.setenv("REPRO_PAGED_VARIANT", "single")
    assert paged_ops.kernel_config(64, 8, 128, 8)["variant"] == "single"
    monkeypatch.setenv("REPRO_PAGED_VARIANT", "fused")
    assert paged_ops.kernel_config(64, 8, 128, 8)["variant"] == "fused"


def test_kernel_config_unreadable_table_falls_back(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_AUTOTUNE",
                       str(tmp_path / "missing.json"))
    paged_ops._load_table.cache_clear()
    try:
        kc = paged_ops.kernel_config(64, 8, 128, 8)
        assert kc["variant"] in paged_ops.VARIANTS   # built-in defaults
    finally:
        paged_ops._load_table.cache_clear()


def test_checked_in_table_is_well_formed():
    with open(paged_ops._DEFAULT_TABLE) as f:
        table = json.load(f)
    assert "default" in table["configs"]
    for key, kc in table["configs"].items():
        assert kc["variant"] in paged_ops.VARIANTS, key
        assert kc["pages_per_block"] >= 1
        assert kc["grid_layout"] in ("bh", "hb")


# ---------------------------------------------------------------------------
# persist refusal (interpret-mode measurements must never seed the table)
# ---------------------------------------------------------------------------

def test_persist_refuses_interpret_rows(tmp_path):
    rows = [{"shape_key": "ps8-hkv2-dh16-g2", "variant": "blocked",
             "pages_per_block": 2, "grid_layout": "bh",
             "tokens_per_s": 100.0, "interpret": True}]
    with pytest.raises(RuntimeError, match="interpret"):
        kernel_bench.persist_table(rows, str(tmp_path / "t.json"))
    assert not (tmp_path / "t.json").exists()


def test_persist_writes_winners_for_hardware_rows(tmp_path):
    rows = [
        {"shape_key": "k", "variant": "single", "pages_per_block": 1,
         "grid_layout": "bh", "tokens_per_s": 10.0, "interpret": False},
        {"shape_key": "k", "variant": "fused", "pages_per_block": 4,
         "grid_layout": "hb", "tokens_per_s": 30.0, "interpret": False},
    ]
    path = kernel_bench.persist_table(rows, str(tmp_path / "t.json"))
    with open(path) as f:
        table = json.load(f)
    assert table["configs"]["k"] == {"variant": "fused",
                                     "pages_per_block": 4,
                                     "grid_layout": "hb"}
    assert "default" in table["configs"]


# ---------------------------------------------------------------------------
# roofline cost model
# ---------------------------------------------------------------------------

def test_kernel_time_bound_picks_slower_term():
    assert kernel_time_bound(HBM_BW, 0.0) == pytest.approx(1.0)
    assert kernel_time_bound(0.0, PEAK_FLOPS_BF16) == pytest.approx(1.0)
    assert pct_of_roofline(2.0, HBM_BW, 0.0) == pytest.approx(50.0)


def test_paged_decode_cost_scales_with_live_pages():
    """Bytes follow the LIVE page count (early-out) and the fused
    append adds exactly the new token's KV."""
    base, _ = paged_decode_cost(2, 4, 2, 16, 8, 4)
    half, _ = paged_decode_cost(2, 4, 2, 16, 8, 4,
                                lengths=[8 * 4 - 1, -1])
    assert half < base
    fused, _ = paged_decode_cost(2, 4, 2, 16, 8, 4, fused=True)
    assert fused - base == 2 * 2 * 2 * 16 * 4      # 2B rows of K and V
    _, flops = paged_decode_cost(2, 4, 2, 16, 8, 4)
    assert flops == 4.0 * 4 * 16 * 2 * (8 * 4)     # 4·H·Dh·tokens


# ---------------------------------------------------------------------------
# sweep rows (interpret mode, tiny shape — structure only, no timing claims)
# ---------------------------------------------------------------------------

def test_bench_rows_smoke_structure(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    rows = kernel_bench.bench_rows(
        smoke=True, reps=1, shapes=[("tiny", 2, 4, 2, 16, 8, 2)])
    assert len(rows) == 5                           # trimmed candidate grid
    for r in rows:
        assert r["interpret"] is True               # asked for above
        assert r["tokens_per_s"] > 0
        # interpreter timings sit far off the roofline; the rounded
        # figure may be 0.00 but can never exceed the bound
        assert 0 <= r["pct_of_roofline"] <= 100
    assert kernel_bench.winners(rows)               # one winner per key


# ---------------------------------------------------------------------------
# no silent interpreter: without a TPU the interpreter must be asked for
# ---------------------------------------------------------------------------

def test_bench_rows_refuses_cpu_without_interpret(monkeypatch):
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    with pytest.raises(RuntimeError, match="no TPU backend"):
        kernel_bench.bench_rows(smoke=True, reps=1,
                                shapes=[("tiny", 2, 4, 2, 16, 8, 2)])


def test_run_kernel_ab_refuses_cpu_without_interpret(monkeypatch):
    from benchmarks.common import run_kernel_ab
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    with pytest.raises(RuntimeError, match="no TPU backend"):
        run_kernel_ab()


def test_interpret_override_is_an_error_on_tpu(monkeypatch):
    """REPRO_PALLAS_INTERPRET=1 may not swap a TPU's kernels for the
    interpreter; off a TPU it is the explicit request."""
    import jax
    from repro.kernels import env_interpret
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert env_interpret(False) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="TPU backend"):
        env_interpret(False)
