"""Static-analysis checkers (tools/analysis) + debugsync runtime verifier.

Covers DESIGN.md §11: the fixture corpus under tests/fixtures/analysis/,
the tree-is-clean gate the CI ``analysis`` job enforces, a seeded
in-memory violation smoke, the CLI exit codes, the REPRO_DEBUG_SYNC
lock-order verifier, and regressions for the concurrency fixes the
checkers surfaced.
"""
import pathlib
import subprocess
import sys
import threading
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.analysis import DEFAULT_SRC, run  # noqa: E402
from repro import debugsync  # noqa: E402

FX = REPO / "tests" / "fixtures" / "analysis"
# intentionally absent -> empty allowlist (run() must NOT fall back to
# the real one when analyzing a fixture tree)
NO_ALLOW = FX / "no-allowlist.toml"


def _fixture(case, allow=None, roots=("Engine._step",)):
    return run(root=FX / case,
               allowlist=allow if allow is not None else NO_ALLOW,
               roots=roots)


# ---------------------------------------------------------------- tree gate


def test_tree_is_clean_strict():
    res = run()
    assert res.ok(strict=True), "\n".join(
        [f.render() for f in res.findings + res.config_errors]
        + res.allow_errors + [f"UNUSED {e.site}" for e in res.unused])


def test_tree_counts_are_sane():
    c = run().counts
    assert c["named_locks"] >= 10
    assert c["guarded_attrs"] >= 50
    assert c["jit_sites"] >= 10
    assert c["hot_path_functions"] >= 20
    assert c["findings"] == 0


# ---------------------------------------------------------------- fixtures


def test_unguarded_write_is_caught():
    res = _fixture("locks_bad")
    assert not res.ok()
    [f] = res.findings
    assert f.checker == "locks"
    assert f.qualname == "Counter.bump_racy"
    assert "guarded-by Counter.lock" in f.message


def test_guarded_write_is_clean():
    res = _fixture("locks_good")
    assert res.ok(strict=True)
    assert res.findings == []


def test_lock_order_cycle_is_caught():
    res = _fixture("locks_cycle")
    assert not res.ok()
    [f] = res.findings
    assert f.checker == "locks" and f.symbol == "cycle"
    assert "Pair.a -> Pair.b" in f.message
    assert "Pair.b -> Pair.a" in f.message


def test_unbucketed_jit_arg_is_caught():
    res = _fixture("jit_bad")
    assert not res.ok()
    [f] = res.findings
    assert f.checker == "jit" and f.symbol == "_step"
    assert "bucketing" in f.message


def test_bucketed_jit_arg_is_clean():
    res = _fixture("jit_good")
    assert res.ok(strict=True)
    assert res.findings == []


def test_hot_path_sync_is_caught():
    res = _fixture("hostsync_bad")
    assert not res.ok()
    [f] = res.findings
    assert f.checker == "hostsync" and f.symbol == "int"
    assert f.qualname == "Engine._step"


def test_allowlisted_sync_passes_and_counts():
    res = _fixture("hostsync_allowed",
                   allow=FX / "hostsync_allowed" / "allow.toml")
    assert res.ok(strict=True)
    assert len(res.suppressed) == 1
    assert res.counts["syncs_allowed"] == 1


def test_devmem_violations_are_caught():
    res = _fixture("devmem_bad")
    assert not res.ok()
    symbols = sorted(f.symbol for f in res.findings
                     if f.checker == "devmem")
    assert symbols == ["d2h", "dtype", "h2d-loop", "use-after-donate"]
    uad = next(f for f in res.findings if f.symbol == "use-after-donate")
    assert "pool.k" in uad.message and "rebound" in uad.message


def test_devmem_disciplined_tree_is_clean():
    res = _fixture("devmem_good")
    assert res.ok(strict=True)
    assert res.findings == []
    assert res.counts["memspace_attrs"] == 4
    assert res.counts["donate_sites"] == 1


def test_kernel_contract_violations_are_caught():
    res = _fixture("kernel_bad")
    assert not res.ok()
    symbols = [f.symbol for f in res.findings if f.checker == "kernel"]
    assert symbols.count("triple") == 2          # ops.py + ref.py missing
    assert "blockspec-divide" in symbols
    assert "grid-arity" in symbols
    assert "vmem-budget" in symbols
    vb = next(f for f in res.findings if f.symbol == "vmem-budget")
    # the static estimate is exact at the annotated bindings
    assert "6.00 MiB" in vb.message and "0.50 MiB" in vb.message


def test_kernel_contract_clean_package_passes():
    res = _fixture("kernel_good")
    assert res.ok(strict=True)
    assert res.findings == []
    assert res.counts["kernels_checked"] == 1
    assert res.counts["vmem_budgets"] == 1


def test_units_mismatches_are_caught():
    res = _fixture("units_bad")
    assert not res.ok()
    msgs = [f.message for f in res.findings if f.checker == "units"]
    assert any("@kv bytes priced over the @host path" in m
               for m in msgs)
    assert any("incompatible terms" in m for m in msgs)


def test_units_sound_tree_is_clean():
    res = _fixture("units_good")
    assert res.ok(strict=True)
    assert res.findings == []
    assert res.counts["unit_fields"] >= 6
    assert res.counts["unit_functions"] >= 2


def test_allowlist_entry_without_reason_is_an_error(tmp_path):
    bad = tmp_path / "allow.toml"
    bad.write_text('[[allow]]\nchecker = "hostsync"\n'
                   'site = "engine.py:Engine._step:int"\n')
    res = _fixture("hostsync_allowed", allow=bad)
    assert not res.ok()
    assert res.allow_errors


# ------------------------------------------------------- seeded violation


def test_seeded_violation_is_caught():
    """Break the tree in-memory: a method touching a guarded attr with
    no lock held must turn the clean run red."""
    source = (DEFAULT_SRC / "engine" / "engine.py").read_text()
    # keep the '# runs-on: engine-loop' comment glued to _run_loop —
    # inserting between them would re-target the annotation
    needle = "\n    # runs-on: engine-loop\n    def _run_loop"
    assert needle in source
    evil = ("\n    def _evil(self):\n"
            "        return len(self._pending)\n" + needle)
    res = run(override={"engine/engine.py":
                        source.replace(needle, evil, 1)})
    assert not res.ok()
    assert any(f.checker == "locks" and f.qualname.endswith("._evil")
               for f in res.findings)


def test_seeded_use_after_donate_is_caught():
    """Read the donated pool between the step and the rebind — the
    exact hazard adopt_pages exists to prevent."""
    source = (DEFAULT_SRC / "engine" / "engine.py").read_text()
    needle = "        kv.adopt_pages(new_k, new_v)"
    assert needle in source
    evil = ("        checksum = kv.k.sum()\n" + needle)
    res = run(override={"engine/engine.py":
                        source.replace(needle, evil, 1)})
    assert any(f.checker == "devmem" and f.symbol == "use-after-donate"
               and f.qualname.endswith("._decode_paged")
               for f in res.findings)


def test_seeded_d2h_in_hot_path_is_caught():
    """An un-annotated np.asarray on the donated pool's device arrays
    must be flagged as an implicit transfer."""
    source = (DEFAULT_SRC / "engine" / "kvcache.py").read_text()
    needle = "    def adopt_pages(self, k, v) -> None:"
    assert needle in source
    evil = needle + "\n        shadow = np.asarray(self.k)"
    res = run(override={"engine/kvcache.py":
                        source.replace(needle, evil, 1)})
    assert any(f.checker == "devmem" and f.symbol == "d2h"
               and f.qualname.endswith(".adopt_pages")
               for f in res.findings)


def test_seeded_vmem_overflow_is_caught():
    """Shrinking a kernel's declared budget below its static footprint
    must turn the run red."""
    rel = "kernels/flash_attention/kernel.py"
    source = (DEFAULT_SRC / rel).read_text()
    needle = "# vmem-budget: 2.0 MiB"
    assert needle in source
    res = run(override={rel: source.replace(
        needle, "# vmem-budget: 0.5 MiB", 1)})
    assert any(f.checker == "kernel" and f.symbol == "vmem-budget"
               and "exceeds the declared budget" in f.message
               for f in res.findings)


def test_seeded_unit_mismatch_is_caught():
    """Pricing KV migration at host_bw instead of link_bw is the §11.6
    channel confusion the units checker exists for."""
    rel = "core/cost_model.py"
    source = (DEFAULT_SRC / rel).read_text()
    needle = "tokens * prof.kv_bytes_per_token / self.hw.link_bw"
    assert needle in source
    res = run(override={rel: source.replace(
        needle,
        "tokens * prof.kv_bytes_per_token / self.hw.host_bw", 1)})
    assert any(f.checker == "units"
               and "priced over the @host path" in f.message
               for f in res.findings)


# ------------------------------------------------------------------- CLI


def test_cli_strict_exits_zero_on_tree():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--strict"],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


def test_cli_exits_nonzero_on_violating_fixture():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--strict",
         "--root", str(FX / "locks_bad"),
         "--allowlist", str(NO_ALLOW)],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode != 0
    assert "bump_racy" in proc.stdout


def test_cli_only_restricts_checkers():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--strict",
         "--only", "units", "--json"],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    import json
    payload = json.loads(proc.stdout)
    assert payload["ok"]
    # locks/hostsync allowlist entries are waived under --only units
    assert payload["unused_allowlist"] == []


def test_cli_sarif_output(tmp_path):
    sarif_path = tmp_path / "analysis.sarif"
    proc = subprocess.run(
        [sys.executable, "-m", "tools.analysis",
         "--root", str(FX / "units_bad"),
         "--allowlist", str(NO_ALLOW),
         "--sarif", str(sarif_path)],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode != 0          # fixture violates on purpose
    import json
    doc = json.loads(sarif_path.read_text())
    assert doc["version"] == "2.1.0"
    run_ = doc["runs"][0]
    assert run_["tool"]["driver"]["name"] == "tools.analysis"
    results = run_["results"]
    assert results, "violating fixture must produce SARIF results"
    for r in results:
        loc = r["locations"][0]["physicalLocation"]
        assert loc["region"]["startLine"] >= 1
        assert loc["artifactLocation"]["uri"].endswith("cost.py")
    assert any(r["ruleId"].startswith("units/") for r in results)


def test_cli_sarif_on_clean_tree_is_empty(tmp_path):
    sarif_path = tmp_path / "clean.sarif"
    proc = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--strict",
         "--sarif", str(sarif_path)],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    import json
    doc = json.loads(sarif_path.read_text())
    assert doc["runs"][0]["results"] == []


# ------------------------------------------------- debugsync runtime layer


def test_named_lock_disabled_is_plain_lock(monkeypatch):
    monkeypatch.delenv("REPRO_DEBUG_SYNC", raising=False)
    lk = debugsync.named_lock("TestPlain.lk")
    assert isinstance(lk, type(threading.Lock()))
    assert isinstance(debugsync.named_condition("TestPlain.cv"),
                      threading.Condition)


def test_lock_order_inversion_raises(monkeypatch):
    monkeypatch.setenv("REPRO_DEBUG_SYNC", "1")
    a = debugsync.named_lock("TestInv.a")
    b = debugsync.named_lock("TestInv.b")
    with a:
        with b:
            pass
    with pytest.raises(debugsync.LockOrderError):
        with b:
            with a:
                pass
    assert debugsync.registry().held() == []


def test_reentrant_same_name_is_not_an_inversion(monkeypatch):
    monkeypatch.setenv("REPRO_DEBUG_SYNC", "1")
    cv = debugsync.named_condition("TestReent.cv")
    with cv:
        with cv:
            pass
    assert debugsync.registry().held() == []


def test_condition_wait_repushes_held_stack(monkeypatch):
    monkeypatch.setenv("REPRO_DEBUG_SYNC", "1")
    cv = debugsync.named_condition("TestWait.cv")
    ready, held_after_wait = [], []

    def waiter():
        with cv:
            while not ready:
                cv.wait(timeout=5)
            held_after_wait.extend(debugsync.registry().held())

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    with cv:
        ready.append(1)
        cv.notify()
    t.join(timeout=5)
    assert not t.is_alive()
    assert "TestWait.cv" in held_after_wait
    assert debugsync.registry().held() == []


# -------------------------------------------- regressions (checker finds)


def test_batch_state_is_macro_done_locked_view():
    from repro.core import consolidate
    from repro.runtime.coordinator import BatchState
    from repro.workloads import build_workload

    g, bindings, _ = build_workload("w+", 4, seed=0)
    consolidate(g, bindings)
    st = BatchState(g, 4)
    assert not st.is_macro_done("draft")
    for q in range(4):
        st.set_result(q, "draft", f"r{q}")
    assert st.is_macro_done("draft")


def test_moe_router_combine_survives_strict_promotion():
    """devmem/CI-dtype-leg find: the router combine multiplied f32
    weights by a raw bool keep-mask — f32*bool has no promotion path
    under jax_numpy_dtype_promotion=strict.  Pin that the combine, with
    a padding mask, stays clean under strict promotion."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import MoEConfig
    from repro.engine.models.moe import moe_ffn, moe_init

    cfg = MoEConfig(num_experts=4, top_k=2, d_ff_expert=8)
    p = moe_init(jax.random.PRNGKey(0), 16, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 16), jnp.float32)
    valid = jnp.arange(4)[None, :] < jnp.asarray([[4], [2]])
    with jax.numpy_dtype_promotion("strict"):
        out, aux, _ = moe_ffn(x, p, cfg)
        masked, _, _ = moe_ffn(x, p, cfg, valid)
    assert out.shape == masked.shape == x.shape
    assert out.dtype == masked.dtype == jnp.float32


def test_kvcache_hbm_bytes_uses_pool_dtype():
    """devmem find: hbm_bytes() defaulted to 2 bytes/elem (bf16) while
    the pool allocates float32 — a silent 2x undercount."""
    import jax.numpy as jnp
    from repro.engine.kvcache import PagedKVCache

    kv = PagedKVCache(num_layers=1, num_pages=2, page_size=4,
                      kv_heads=2, head_dim=8)      # default f32 pool
    elems = 2 * 1 * 2 * 4 * 2 * 8
    assert kv.dtype == jnp.float32
    assert kv.hbm_bytes() == elems * 4             # pool's own width
    assert kv.hbm_bytes(dtype_bytes=2) == elems * 2  # explicit override
    bf16 = PagedKVCache(num_layers=1, num_pages=2, page_size=4,
                        kv_heads=2, head_dim=8, dtype=jnp.bfloat16)
    assert bf16.hbm_bytes() == elems * 2


def test_batched_sample_index_mask_is_int32_pinned():
    """devmem dtype find: the vocab mask built its arange without a
    dtype (platform-int width).  Pin the jnp.int32 fix end to end."""
    import jax
    import jax.numpy as jnp
    from repro.engine.engine import _batched_sample

    logits = jnp.zeros((2, 8), jnp.float32).at[:, 3].set(5.0)
    keys = jnp.zeros((2, 2), jnp.uint32)
    temps = jnp.zeros((2,), jnp.float32)
    with jax.numpy_dtype_promotion("strict"):
        toks, _ = _batched_sample(logits, keys, temps, vocab_size=6)
    assert toks.dtype == jnp.int32
    assert list(toks) == [3, 3]


def test_checkpoint_batch_size_mismatch_raises(tmp_path):
    from repro.runtime.checkpoint import (load_batch_state,
                                          save_batch_state)
    from repro.runtime.coordinator import BatchState
    from repro.workloads import build_workload

    g, _, _ = build_workload("w+", 4, seed=0)
    st = BatchState(g, 4)
    st.set_result(0, "draft", "r0")
    p = str(tmp_path / "ck.json")
    save_batch_state(st, p)
    with pytest.raises(ValueError, match="different batch size"):
        load_batch_state(BatchState(g, 3), p)
