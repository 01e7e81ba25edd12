"""Measured kernel roofline gate (DESIGN.md §14).

For each of the four kernel packages this times the backend that actually
serves on this host (`dispatch.resolve_backend(None)` — ref on CPU, the
Pallas kernel on TPU) against an **analytical roofline bound**:

    bound_s = max(flops / peak_flops, bytes / peak_bw) + dispatch_overhead

where peak_flops / peak_bw / dispatch_overhead are **self-calibrated** on
the same machine right before the measurements (a big f32 matmul, a big
device copy, and a trivial jitted fn), so the gate is a property of the
kernel, not of the hardware the CI runner happens to be.

The gate fails when measured_s > GATE_X * bound_s for any kernel —
GATE_X is deliberately generous (see DESIGN.md §14): it exists to catch
catastrophic regressions (an accidentally-interpreted kernel, a
materialized gather, an O(n^2) blowup), not to police single-digit
percentages. Interpret-mode timings are reported for reference and never
gated (interpret mode is a debugging path).

CLI: ``python benchmarks/bench_roofline.py [--smoke]`` writes
BENCH_roofline.json and exits nonzero on gate failure (the CI hook).
`run()` keeps the benchmark-driver contract (rows of (name, us, derived))
and appends the legacy dry-run analytic table when results/dryrun exists.
"""
from __future__ import annotations

import glob
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _bootstrap import ensure_env_and_path  # noqa: E402

ensure_env_and_path()

GATE_X = 50.0       # measured <= GATE_X * analytic bound (DESIGN.md §14)


# ---------------------------------------------------------------------------
# machine self-calibration
# ---------------------------------------------------------------------------
def calibrate(smoke: bool = False) -> dict:
    """Achievable peaks on THIS machine: f32 matmul flops/s, device copy
    bytes/s, and the per-dispatch overhead of a trivial jitted fn."""
    import jax
    import jax.numpy as jnp
    from benchmarks.common import time_call

    n = 512 if smoke else 1024
    a = jnp.ones((n, n), jnp.float32)
    mm = jax.jit(lambda x: x @ x)
    t_mm = time_call(mm, a, warmup=2, iters=5)
    peak_flops = 2.0 * n ** 3 / t_mm

    m = (16 if smoke else 64) * 2 ** 20 // 4
    b = jnp.ones((m,), jnp.float32)
    cp = jax.jit(lambda x: x + 1.0)
    t_cp = time_call(cp, b, warmup=2, iters=5)
    peak_bw = 2.0 * m * 4 / t_cp          # read + write

    tiny = jnp.ones((8,), jnp.float32)
    noop = jax.jit(lambda x: x)
    overhead = time_call(noop, tiny, warmup=2, iters=20)
    return {"peak_flops": peak_flops, "peak_bw": peak_bw,
            "dispatch_overhead_s": overhead}


def _bound(flops: float, bytes_: float, cal: dict) -> float:
    return (max(flops / cal["peak_flops"], bytes_ / cal["peak_bw"])
            + cal["dispatch_overhead_s"])


# ---------------------------------------------------------------------------
# per-kernel measured cases
# ---------------------------------------------------------------------------
def _cases(smoke: bool):
    """(name, build() -> (fn, args, flops, bytes)) for all four kernels."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    s = 2 if smoke else 1

    def moe_gemm():
        from repro.kernels.moe_gemm.ops import grouped_matmul
        E, C, D, W = 8, 256 // s, 256 // s, 512 // s
        x = jnp.ones((E, C, D), jnp.float32)
        w = jnp.ones((1, E, W, D), jnp.float32)
        fl = 2.0 * E * C * W * D
        by = 4.0 * (E * C * D + E * W * D + E * C * W)
        n = jnp.full((E,), C, jnp.int32)
        return (lambda bk: jax.jit(
            lambda a, b: grouped_matmul(a, b, 0, n, backend=bk))), (x, w), \
            fl, by

    def kv_pack():
        from repro.kernels.kv_pack.ops import gather_pages_rows
        R, pages, M, n = 16, 256 // s, 4096 // s, 64
        pool = jnp.ones((R, pages, M), jnp.float32)
        idx = jnp.asarray(np.arange(n) % pages, jnp.int32)
        by = 2.0 * 4 * R * n * M          # read + write the moved pages
        return (lambda bk: jax.jit(
            lambda p, i: gather_pages_rows(p, i, backend=bk))), \
            (pool, idx), 0.0, by

    def expert_reshard():
        from repro.kernels.expert_reshard.ops import pack_peer_chunks
        E_loc, I, D, G = 8, 2048 // s, 256 // s, 4
        w13 = jnp.ones((E_loc, 2 * I, D), jnp.float32)
        by = 2.0 * 4 * E_loc * 2 * I * D
        return (lambda bk: jax.jit(
            lambda w: pack_peer_chunks(w, G, backend=bk))), (w13,), 0.0, by

    def paged_attention():
        from repro.kernels.paged_attention.ops import paged_attention
        B, Sq, H, K, dh = 8, 1, 8, 2, 64
        page, maxp, pages = 16, 64 // s, 256 // s
        q = jnp.ones((B, Sq, H, dh), jnp.float32)
        kp = jnp.ones((pages, page, K, dh), jnp.float32)
        bt = jnp.asarray(np.arange(B * maxp).reshape(B, maxp) % pages,
                         jnp.int32)
        kvl = jnp.full((B,), maxp * page, jnp.int32)
        qoff = kvl - Sq
        ctx = maxp * page
        fl = 2.0 * 2 * B * H * Sq * ctx * dh
        by = 4.0 * (B * maxp * page * K * dh * 2 + 2 * B * Sq * H * dh)
        return (lambda bk: jax.jit(
            lambda qq, k, v, b, kl, qo: paged_attention(
                qq, k, v, b, kl, q_offset=qo, backend=bk))), \
            (q, kp, kp, bt, kvl, qoff), fl, by

    return [("moe_gemm.grouped_matmul", moe_gemm),
            ("kv_pack.gather_pages_rows", kv_pack),
            ("expert_reshard.pack_peer_chunks", expert_reshard),
            ("paged_attention.paged_attention", paged_attention)]


def measure(smoke: bool = False) -> dict:
    """Time all four kernels vs their analytic bounds. Returns the full
    payload: calibration, per-kernel measurements, gate verdicts."""
    from benchmarks.common import time_call
    from repro.kernels import dispatch

    cal = calibrate(smoke)
    serving = dispatch.resolve_backend(None)
    iters = 5 if smoke else 10
    kernels, ok = [], True
    for name, build in _cases(smoke):
        mk, args, fl, by = build()
        bound = _bound(fl, by, cal)
        t_serve = time_call(mk(serving), *args, warmup=2, iters=iters)
        ratio = t_serve / bound
        passed = ratio <= GATE_X
        ok = ok and passed
        row = {"kernel": name, "backend": serving, "flops": fl, "bytes": by,
               "bound_s": bound, "measured_s": t_serve, "ratio": ratio,
               "gate_x": GATE_X, "pass": passed}
        # interpret mode: reported, never gated (debugging path)
        try:
            row["interpret_s"] = time_call(mk("interpret"), *args,
                                           warmup=1, iters=2)
        except Exception as e:  # noqa: BLE001 — report-only path
            row["interpret_error"] = f"{type(e).__name__}: {e}"
        kernels.append(row)
    return {"calibration": cal, "gate_x": GATE_X, "smoke": smoke,
            "kernels": kernels, "pass": ok}


# ---------------------------------------------------------------------------
# legacy dry-run analytic table (kept; non-gating)
# ---------------------------------------------------------------------------
def load_cells(pattern: str = "results/dryrun/*.json") -> list[dict]:
    cells = []
    for f in sorted(glob.glob(pattern)):
        try:
            cells.append(json.load(open(f)))
        except Exception:
            pass
    return cells


def dominant(a: dict) -> str:
    terms = {"compute": a["t_compute"], "memory": a["t_memory"],
             "collective": a["t_collective"]}
    return max(terms, key=terms.get)


def dryrun_rows(write_md: bool = True):
    rows = []
    cells = load_cells()
    md = ["| cell | layout | t_comp (us) | t_mem (us) | t_coll (us) | "
          "bottleneck | useful/HLO | fits? |",
          "|---|---|---|---|---|---|---|---|"]
    for c in cells:
        if c.get("status") != "ok" or c.get("mesh") != "pod1":
            continue
        a = c["analytic"]
        name = f"{c['arch']}.{c['shape']}"
        dom = dominant(a)
        hlo_flops = c.get("cost_analysis", {}).get("flops", 0.0)
        useful = a["useful_flops_per_dev"]
        ratio = useful / hlo_flops if hlo_flops else float("nan")
        arg_gib = c.get("memory", {}).get("argument_size_in_bytes", 0) / 2**30
        fits = "yes" if arg_gib < 14.5 else f"NO ({arg_gib:.1f}GiB)"
        rows.append((f"roofline.{name}.{c['layout']}.t_compute_s",
                     a["t_compute"] * 1e6, dom))
        rows.append((f"roofline.{name}.{c['layout']}.t_memory_s",
                     a["t_memory"] * 1e6, ""))
        rows.append((f"roofline.{name}.{c['layout']}.t_collective_s",
                     a["t_collective"] * 1e6, ""))
        md.append(f"| {name} | {c['layout']} | {a['t_compute']*1e6:.1f} | "
                  f"{a['t_memory']*1e6:.1f} | {a['t_collective']*1e6:.1f} | "
                  f"{dom} | {ratio:.3f} | {fits} |")
    if write_md and rows:
        Path("results").mkdir(exist_ok=True)
        Path("results/roofline.md").write_text("\n".join(md) + "\n")
        rows.append(("roofline.table_rows", float(len(md) - 2),
                     "results/roofline.md"))
    return rows


def run(write_md: bool = True, smoke: bool = True):
    """Benchmark-driver entry: measured kernel rooflines (+ the legacy
    dry-run table when results/dryrun exists)."""
    payload = measure(smoke=smoke)
    rows = []
    for k in payload["kernels"]:
        rows.append((f"roofline.{k['kernel']}.{k['backend']}_s",
                     k["measured_s"] * 1e6,
                     f"bound={k['bound_s']*1e6:.1f}us "
                     f"ratio={k['ratio']:.1f} "
                     f"{'PASS' if k['pass'] else 'FAIL'}"))
        if "interpret_s" in k:
            rows.append((f"roofline.{k['kernel']}.interpret_s",
                         k["interpret_s"] * 1e6, "report-only"))
    rows.append(("roofline.gate", 1.0 if payload["pass"] else 0.0,
                 f"X={GATE_X}"))
    rows.extend(dryrun_rows(write_md))
    return rows


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="smaller shapes / fewer iters (CI mode)")
    ap.add_argument("--json", default=None,
                    help="mirror BENCH_roofline.json here as well")
    args = ap.parse_args()
    payload = measure(smoke=args.smoke)
    from benchmarks.common import write_bench_json
    write_bench_json(payload, args.json, "roofline")
    for k in payload["kernels"]:
        mark = "PASS" if k["pass"] else "FAIL"
        extra = (f" interpret={k['interpret_s']*1e6:.0f}us"
                 if "interpret_s" in k else "")
        print(f"{mark} {k['kernel']} [{k['backend']}] "
              f"measured={k['measured_s']*1e6:.1f}us "
              f"bound={k['bound_s']*1e6:.1f}us "
              f"ratio={k['ratio']:.1f} (gate {GATE_X:.0f}x){extra}")
    if not payload["pass"]:
        print("roofline gate FAILED", file=sys.stderr)
        return 1
    print("roofline gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
