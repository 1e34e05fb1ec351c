"""Roofline-driven paged-decode kernel autotune sweep.

    PYTHONPATH=src python -m benchmarks.kernel_bench [--smoke] [--json]
    PYTHONPATH=src python -m benchmarks.kernel_bench --persist [--out F]

Sweeps ``(variant, pages_per_block, grid_layout)`` — the single-page
baseline, multi-page double-buffered blocks, and the fused
append+attend variant — per pool shape, times each candidate warm, and
scores achieved HBM bandwidth against the ``launch/roofline.py`` peaks
(%-of-roofline).  ``--persist`` writes the per-shape winners into the
``autotune.json`` table that ``kernels/paged_decode_attention/ops.py``
consults at call time.

The sweep runs the kernels on a TPU.  Without one it raises, unless the
Pallas interpreter is asked for (``REPRO_PALLAS_INTERPRET=1``).
Persisting REFUSES to run when the sweep was measured in interpret mode:
interpret timings measure the interpreter, not the TPU, and a table
seeded from them would be meaningless.  Rows from an interpret sweep
are still exported (marked ``interpret: true``) so the CI smoke
exercises the full path; the nightly tokens/s gate in
``benchmarks/smoke.py`` likewise skips interpret rows.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import env_interpret
from repro.kernels.paged_decode_attention import ops as paged_ops
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.roofline import paged_decode_cost, pct_of_roofline

# (name, B, H, Hkv, Dh, page_size, n_pages) — smoke first; the larger
# shapes mirror the serving configs and only run in a full sweep
SHAPES = [
    ("smoke-qwen3", 4, 4, 2, 16, 8, 8),
    ("decode-2k", 8, 32, 8, 128, 64, 32),
    ("decode-8k", 4, 32, 8, 128, 64, 128),
]

# the sweep grid; "single" ignores ppb/layout (one page per grid step)
CANDIDATES = [{"variant": "single", "pages_per_block": 1,
               "grid_layout": "bh"}] + [
    {"variant": variant, "pages_per_block": ppb, "grid_layout": layout}
    for variant in ("blocked", "fused")
    for ppb in (2, 4, 8)
    for layout in ("bh", "hb")
]


def interpret_mode() -> bool:
    """True when a measurement runs (and times) the Pallas interpreter,
    which must be asked for with REPRO_PALLAS_INTERPRET=1; with neither
    that request nor a TPU it raises."""
    if env_interpret(False):
        return True
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"no TPU backend ({jax.default_backend()}): ask for the Pallas "
            f"interpreter (REPRO_PALLAS_INTERPRET=1) to run the kernels "
            f"without one")
    return False


def _make_inputs(B, H, Hkv, Dh, page_size, n_pages, seed=0):
    rng = np.random.default_rng(seed)
    P = 2 * B * n_pages
    q = jnp.asarray(rng.standard_normal((B, H, Dh)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((P, page_size, Hkv, Dh)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((P, page_size, Hkv, Dh)),
                     jnp.float32)
    pt = jnp.asarray(
        rng.permutation(P)[:B * n_pages].reshape(B, n_pages), jnp.int32)
    lens = jnp.full((B,), n_pages * page_size - 1, jnp.int32)
    k_new = jnp.asarray(rng.standard_normal((B, Hkv, Dh)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((B, Hkv, Dh)), jnp.float32)
    return q, kp, vp, pt, lens, k_new, v_new


def _time_candidate(cand: Dict, inputs, interpret: bool, reps: int) -> float:
    q, kp, vp, pt, lens, k_new, v_new = inputs

    if cand["variant"] == "fused":
        def call():
            return paged_ops.fused_paged_decode_attention(
                q, kp, vp, pt, lens, k_new, v_new,
                pages_per_block=cand["pages_per_block"],
                grid_layout=cand["grid_layout"], interpret=interpret)[0]
    else:
        def call():
            return paged_ops.paged_decode_attention(
                q, kp, vp, pt, lens, variant=cand["variant"],
                pages_per_block=cand["pages_per_block"],
                grid_layout=cand["grid_layout"], interpret=interpret)

    call().block_until_ready()                       # compile + warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        call().block_until_ready()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def bench_rows(smoke: bool = False, reps: int = 3,
               shapes=None) -> List[Dict]:
    """Sweep every candidate over the benchmark shapes.  Each row
    carries tokens/s, achieved GB/s, and %-of-roofline — the quantities
    the nightly gate tracks (docs/BENCHMARKS.md)."""
    interp = interpret_mode()
    if shapes is None:
        shapes = SHAPES[:1] if smoke else SHAPES
    cands = CANDIDATES[:5] if smoke else CANDIDATES
    rows = []
    for (name, B, H, Hkv, Dh, page_size, n_pages) in shapes:
        inputs = _make_inputs(B, H, Hkv, Dh, page_size, n_pages)
        key = paged_ops.shape_key(page_size, Hkv, Dh, H // Hkv)
        for cand in cands:
            dt = _time_candidate(cand, inputs, interp, reps)
            bytes_hbm, flops = paged_decode_cost(
                B, H, Hkv, Dh, page_size, n_pages,
                fused=cand["variant"] == "fused")
            rows.append({
                "system": "kernel-bench", "shape": name, "shape_key": key,
                **cand,
                "time_s": round(dt, 6),
                "tokens_per_s": round(B / dt, 2),
                "achieved_gb_s": round(bytes_hbm / dt / 1e9, 3),
                "pct_of_roofline": round(
                    pct_of_roofline(dt, bytes_hbm, flops), 2),
                "interpret": interp,
            })
    return rows


def winners(rows: List[Dict]) -> Dict[str, Dict]:
    """Best candidate (highest tokens/s) per shape key."""
    best: Dict[str, Dict] = {}
    for r in rows:
        k = r["shape_key"]
        if k not in best or r["tokens_per_s"] > best[k]["tokens_per_s"]:
            best[k] = r
    return {k: {"variant": r["variant"],
                "pages_per_block": r["pages_per_block"],
                "grid_layout": r["grid_layout"]}
            for k, r in best.items()}


def persist_table(rows: List[Dict], path: Optional[str] = None) -> str:
    """Write the per-shape winners as the autotune table ops.py loads.

    Refuses interpret-mode measurements: a table tuned on interpreter
    timings would steer real hardware with noise.
    """
    bad = [r for r in rows if r.get("interpret")]
    if bad:
        raise RuntimeError(
            "refusing to persist autotune table: "
            f"{len(bad)}/{len(rows)} rows were measured under Pallas "
            "interpret mode (REPRO_PALLAS_INTERPRET=1 or no TPU "
            "backend).  Interpret timings measure the interpreter, not "
            "the kernel — re-run the sweep on TPU hardware without the "
            "override to regenerate the table.")
    if path is None:
        path = paged_ops._DEFAULT_TABLE
    table = {
        "_provenance": f"swept by benchmarks.kernel_bench on "
                       f"{jax.default_backend()} "
                       f"({len(rows)} measurements)",
        "configs": {"default": {"variant": "fused", "pages_per_block": 4,
                                "grid_layout": "bh"},
                    **winners(rows)},
    }
    with open(path, "w") as f:
        json.dump(table, f, indent=1)
        f.write("\n")
    return path


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="first shape + trimmed candidate grid")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--persist", action="store_true",
                    help="write winners into the checked-in autotune.json "
                         "(refused under interpret mode)")
    ap.add_argument("--out", default=None,
                    help="alternate table path for --persist")
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()

    enable_compile_cache()
    rows = bench_rows(smoke=args.smoke, reps=args.reps)
    if args.json:
        print(json.dumps(rows, indent=1))
    else:
        for r in rows:
            print(f"{r['shape']:12s} {r['variant']:8s} "
                  f"ppb={r['pages_per_block']} {r['grid_layout']} "
                  f"{r['time_s'] * 1e3:8.3f} ms  {r['tokens_per_s']:10.1f} "
                  f"tok/s  {r['achieved_gb_s']:8.2f} GB/s  "
                  f"{r['pct_of_roofline']:6.2f}% SoL"
                  f"{'  [interpret]' if r['interpret'] else ''}")
    if args.persist:
        path = persist_table(rows, args.out)
        print(f"autotune table -> {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
