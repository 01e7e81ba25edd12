#!/usr/bin/env python3
"""Prove that the serving main path runs on the TPU.

    python chip_smoke.py             # one TPU v5e chip
    python chip_smoke.py --chips 4   # the 2x2 host: the live switch only

One chip serves mixtral-8x7b at its published widths in bf16, cut to 4
whole layers, with dropless expert capacity, in two phases:
  (a) kernel parity: every Pallas kernel of the serve and switch paths
      against its jnp reference at those widths (DESIGN.md §14 tolerance);
  (b) serving: engine construction and warmup, then 8 greedy requests
      (prompts of 64-512 tokens, 32 new tokens each, so prefill chunks and
      decode rows share steps) through AsyncEngine.generate.
With --chips 4 it runs only the switch phase: 8 layers on a 1x4 mesh with
tp and ep resident, the same requests served across a live tp->ep switch
mid-decode and an ep->tp switch back, against a never-switched run built
in the same process after the first engine is freed.

The script exits non-zero, without its summary line, when JAX finds no
TPU, when any traced kernel op resolved to the reference or to interpret
mode, when anything compiles inside the serving window after warmup, or
when any phase raises. The last
line of its output is one JSON object, {"ok": true, "device": {...}}.
Timings are printed for information only. Compiled programs are cached
where JAX_COMPILATION_CACHE_DIR says, else in <repo>/.jax_cache.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "mixtral-8x7b"
# whole layers kept per chip count: 4 layers' weights (11.30 GiB) plus the
# serve step fit one 16 GB chip; 8 layers' experts (22.5 GB) need four
LAYERS = {1: 4, 4: 8}
BF16_TOL = 2e-2            # DESIGN.md §14: bf16 kernel-vs-reference
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def log(*parts) -> None:
    print(*parts, flush=True)


def device_info() -> dict:
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def require_tpu(chips: int) -> dict:
    dev = device_info()
    if dev["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX runs on {dev['platform']!r}")
    if dev["count"] < chips:
        raise SystemExit(f"{chips} chips wanted, JAX sees {dev['count']}")
    return dev


class CompileCounter:
    """Counts XLA backend compiles (persistent-cache loads included) and
    their seconds, through jax.monitoring."""

    def __init__(self):
        import jax
        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.n += 1
            self.secs += duration


def smoke_config(layers: int, cfg=None):
    """mixtral-8x7b at its published widths, cut to `layers` whole layers,
    with dropless expert capacity (no token is ever dropped, so the outputs
    do not depend on how the batch is packed)."""
    from repro.launch.serve import model_config
    cfg = cfg or model_config(ARCH, layers=layers)
    return cfg.replace(capacity_factor=cfg.num_experts / cfg.top_k)


def _close(name, got, want, tol=BF16_TOL) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    scale = max(1.0, float(np.max(np.abs(want))))
    if not np.all(np.isfinite(got)) or err > tol * scale:
        raise AssertionError(f"{name}: max |kernel - ref| = {err} > "
                             f"{tol} x {scale}")
    return err


def _equal(name, got, want) -> float:
    import numpy as np
    if not np.array_equal(np.asarray(got), np.asarray(want)):
        raise AssertionError(f"{name}: kernel differs from ref")
    return 0.0


def kernel_parity(cfg, backend=None, *, G: int = 4, page: int = 16,
                  pages: int = 256, seed: int = 0) -> dict:
    """Each kernel through its dispatcher (auto backend: pallas on the
    chip) against its jnp reference, at the config's widths: attention and
    the grouped GEMM within the bf16 tolerance, the movers bitwise. The
    switch movers use the per-rank shapes of a G-rank group."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.expert_reshard import ops as er, ref as er_ref
    from repro.kernels.kv_pack import ops as kp, ref as kp_ref
    from repro.kernels.moe_gemm.ops import grouped_matmul
    from repro.kernels.moe_gemm.ref import grouped_matmul_ref
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.kernels.paged_attention.ref import paged_attention_ref

    dt = cfg.param_dtype
    H, K, dh = cfg.num_heads, cfg.num_kv_heads, cfg.dh
    D, I, E = cfg.d_model, cfg.d_expert, cfg.num_experts
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def rnd(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dt)

    errs = {}
    kpool, vpool = rnd(pages, page, K, dh), rnd(pages, page, K, dh)
    maxp = 48
    # the second case's window is short enough to skip leading chunks
    for B, Sq, window in ((8, 1, cfg.sliding_window), (4, 64, 200)):
        q = rnd(B, Sq, H, dh)
        bt = jax.random.randint(next(keys), (B, maxp), 1, pages)
        kv = jnp.arange(1, B + 1, dtype=jnp.int32) * (maxp * page // B)
        qo = kv - Sq
        args = (q, kpool, vpool, bt, kv)
        errs[f"paged_attention_B{B}_Sq{Sq}"] = _close(
            "paged_attention",
            paged_attention(*args, q_offset=qo, window=window,
                            backend=backend),
            paged_attention_ref(*args, q_offset=qo, window=window))
    # a two-layer stack read at layer 1 (layer 0 zeros): the kernel must
    # take its tiles from the layer the index names, and only the rows the
    # counts route (0 to all 32, some experts none; the rest zero)
    counts = (jnp.arange(E, dtype=jnp.int32) * 13) % 33
    routed = jnp.arange(32)[None, :, None] < counts[:, None, None]
    for name, (w_out, w_in) in (("w13", (2 * I, D)), ("w2", (D, I))):
        x, wl = rnd(E, 32, w_in), rnd(E, w_out, w_in)
        w = jnp.stack([jnp.zeros_like(wl), wl])
        errs[f"grouped_matmul_{name}"] = _close(
            "grouped_matmul",
            grouped_matmul(x, w, 1, counts, backend=backend),
            jnp.where(routed, grouped_matmul_ref(x, wl), 0))
        del x, wl, w
    # duplicate-free page lists (scatter is unspecified on duplicates)
    idx = jax.random.permutation(next(keys), pages)[:8].astype(jnp.int32)
    errs["gather_pages"] = _equal(
        "gather_pages", kp.gather_pages(kpool, idx, backend=backend),
        kp_ref.gather_pages_ref(kpool, idx))
    vals = rnd(idx.shape[0], page, K, dh)
    errs["scatter_pages"] = _equal(
        "scatter_pages", kp.scatter_pages(kpool, idx, vals, backend=backend),
        kp_ref.scatter_pages_ref(kpool, idx, vals))
    rows = kpool.reshape(2, pages // 2, -1)
    ridx = jax.random.permutation(next(keys), pages // 2)[:8].astype(
        jnp.int32)
    errs["gather_pages_rows"] = _equal(
        "gather_pages_rows", kp.gather_pages_rows(rows, ridx,
                                                  backend=backend),
        kp_ref.gather_pages_rows_ref(rows, ridx))
    rvals = rnd(1, ridx.shape[0], rows.shape[2])
    errs["scatter_pages_rows"] = _equal(
        "scatter_pages_rows",
        kp.scatter_pages_rows(rows, ridx, rvals, row0=1, backend=backend),
        kp_ref.scatter_pages_rows_ref(rows, ridx, rvals, row0=1))
    e_loc = max(1, E // G)
    w13, w2 = rnd(e_loc, 2 * I, D), rnd(e_loc, D, I)
    c13 = er.pack_peer_chunks(w13, G, backend=backend)
    errs["pack_peer_chunks"] = _equal(
        "pack_peer_chunks", c13, er_ref.pack_peer_chunks_ref(w13, G))
    errs["interleave_shards"] = _equal(
        "interleave_shards", er.interleave_shards(c13, backend=backend), w13)
    c2 = er.pack_width_chunks(w2, G, backend=backend)
    errs["pack_width_chunks"] = _equal(
        "pack_width_chunks", c2, er_ref.pack_width_chunks_ref(w2, G))
    errs["interleave_width_shards"] = _equal(
        "interleave_width_shards",
        er.interleave_width_shards(c2, backend=backend), w2)
    return errs


def check_backends(expect: str = "pallas") -> dict:
    """Every kernel op traced so far resolved to `expect`."""
    from repro.kernels import dispatch
    counts = {f"{op}[{b}]": n for (op, b), n in sorted(dispatch.COUNTS.items())}
    bad = {k: n for k, n in counts.items() if not k.endswith(f"[{expect}]")}
    if bad:
        raise AssertionError(f"kernel ops not on {expect!r}: {bad}")
    for op in ("paged_attention.paged_attention", "moe_gemm.grouped_matmul"):
        if dispatch.calls(op, expect) == 0:
            raise AssertionError(f"{op} never traced on {expect!r}")
    return counts


def make_prompts(n: int, vocab: int, lo: int, hi: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, int(rng.integers(lo, hi + 1))).tolist()
            for _ in range(n)]


def serve(cfg, prompts, new_tokens: int, counter: CompileCounter, *,
          backend=None, mesh: str = "1x1", layouts: str = "tp",
          switches=(), ladder=(8,), prefill_chunk: int = 64,
          chunk_layers: int = 0, record_logits: bool = False,
          seed: int = 0) -> dict:
    """Build the engine through the launcher's construction path, warm it,
    then serve `prompts` through AsyncEngine.generate. `switches` is a
    sequence of (tokens of request 0, target layout): each switch runs
    live once request 0 has streamed that many tokens. Returns outputs,
    compile counts inside the serving and switch windows, the switch
    records and timings."""
    from repro.launch.serve import build_engine
    from repro.serving.frontend import AsyncEngine
    from repro.serving.kvcache import CacheConfig

    maxp = -(-(max(map(len, prompts)) + new_tokens) // 16)
    cache = CacheConfig(page_size=16, max_pages_per_req=maxp,
                        pages_ep=len(prompts) * maxp + 16)
    t0 = time.perf_counter()
    c0, s0 = counter.n, counter.secs
    eng = build_engine(
        cfg, mesh=mesh, layouts=layouts, policy="static-tp", cache=cache,
        ladder=ladder, prefill_chunk=prefill_chunk, seed=seed,
        attn_backend=backend, moe_backend=backend, switch_backend=backend,
        chunk_layers=chunk_layers, warm_switches=chunk_layers > 0,
        record_logits=record_logits)
    t_build = time.perf_counter() - t0
    eng.warmup()
    t_warm = time.perf_counter() - t0 - t_build
    warm_compiles, warm_secs = counter.n - c0, counter.secs - s0

    fe = AsyncEngine(eng)
    c_serve = counter.n
    t1 = time.perf_counter()
    streams = [fe.generate(p, max_new_tokens=new_tokens) for p in prompts]
    head, switch_compiles = [], []
    for at, target in switches:
        while len(head) < at:
            head.append(next(streams[0]))
        c = counter.n
        if not eng.execute_switch(target):
            raise AssertionError(f"switch to {target} aborted")
        switch_compiles.append(counter.n - c)
    outputs = {s.rid: s.tokens() for s in streams}
    outputs[streams[0].rid] = head + outputs[streams[0].rid]
    t_serve = time.perf_counter() - t1
    return {
        "outputs": outputs, "engine": eng,
        "serve_compiles": counter.n - c_serve,
        "switch_compiles": switch_compiles,
        "warm_compiles": warm_compiles, "warm_compile_s": warm_secs,
        "build_s": t_build, "warmup_s": t_warm, "serve_s": t_serve,
        "records": list(eng.switch_records),
        "logits": eng.ex.logits,
    }


def check_outputs(outputs: dict, n: int, new_tokens: int, vocab: int):
    if len(outputs) != n:
        raise AssertionError(f"{len(outputs)} of {n} requests finished")
    for rid, toks in outputs.items():
        if len(toks) != new_tokens:
            raise AssertionError(f"request {rid}: {len(toks)} tokens, "
                                 f"{new_tokens} requested")
        if not all(0 <= t < vocab for t in toks):
            raise AssertionError(f"request {rid}: token out of vocab")


def serving_phase(cfg, counter: CompileCounter, *, backend=None,
                  n_requests: int = 8, new_tokens: int = 32,
                  prompt_lens=(64, 512), seed: int = 0, **kw) -> dict:
    """Phase (b): serve and check. Fails on a compile inside the serving
    window and on any request without its requested count of in-vocab
    tokens."""
    prompts = make_prompts(n_requests, cfg.vocab_size, *prompt_lens, seed)
    res = serve(cfg, prompts, new_tokens, counter, backend=backend,
                seed=seed, **kw)
    check_outputs(res["outputs"], n_requests, new_tokens, cfg.vocab_size)
    if res["serve_compiles"]:
        raise AssertionError(f"{res['serve_compiles']} compiles inside the "
                             f"serving window after warmup")
    return res


def first_divergence(a: dict, b: dict, prompts, la: dict, lb: dict):
    """(rid, index, max |logit diff|, scale) at the first generated token
    where two runs part, or None when every request matches."""
    import numpy as np
    for rid in sorted(a):
        for i, (x, y) in enumerate(zip(a[rid], b[rid])):
            if x != y:
                pos = len(prompts[rid]) + i
                ga, gb = la[(rid, pos)], lb[(rid, pos)]
                return (rid, i, float(np.max(np.abs(ga - gb))),
                        max(1.0, float(np.max(np.abs(gb)))))
    return None


def switch_phase(cfg, counter: CompileCounter, *, backend=None,
                 n_requests: int = 8, new_tokens: int = 32,
                 prompt_lens=(64, 512), seed: int = 0, mesh="1x4",
                 chunk_layers: int = 1, switch_at=(8, 20)) -> dict:
    """Four chips: serve across a live tp->ep switch mid-decode and an
    ep->tp switch back, then the same requests on a never-switched engine
    built after the first is freed. Greedy tokens must match; where bf16
    reduction order parts them, the first diverging step's logits must
    agree within the bf16 tolerance."""
    import gc
    prompts = make_prompts(n_requests, cfg.vocab_size, *prompt_lens, seed)
    kw = dict(backend=backend, mesh=mesh, layouts="tp,ep",
              record_logits=True, seed=seed)
    sw = serve(cfg, prompts, new_tokens, counter, chunk_layers=chunk_layers,
               switches=((switch_at[0], "ep"), (switch_at[1], "tp")), **kw)
    check_outputs(sw["outputs"], n_requests, new_tokens, cfg.vocab_size)
    del sw["engine"]
    gc.collect()
    base = serve(cfg, prompts, new_tokens, counter, **kw)   # never switches
    check_outputs(base["outputs"], n_requests, new_tokens, cfg.vocab_size)
    del base["engine"]
    div = first_divergence(sw["outputs"], base["outputs"], prompts,
                           sw["logits"], base["logits"])
    if div is not None and div[2] > BF16_TOL * div[3]:
        raise AssertionError(f"request {div[0]} parts at token {div[1]} "
                             f"with max logit diff {div[2]} > "
                             f"{BF16_TOL} x {div[3]}")
    return {"switched": sw, "base": base, "divergence": div}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=sorted(LAYERS))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax  # noqa: F401 — import only after argument errors
    dev = require_tpu(args.chips)
    from repro.launch.serve import use_compile_cache
    from repro.models.registry import count_params_analytic
    cache_dir = use_compile_cache(ROOT)
    counter = CompileCounter()
    layers = LAYERS[args.chips]
    cfg = smoke_config(layers)
    nbytes = count_params_analytic(cfg) * jax.numpy.dtype(
        cfg.param_dtype).itemsize
    log(f"device: {dev}")
    log(f"config: {cfg.name} d_model={cfg.d_model} heads={cfg.num_heads}/"
        f"{cfg.num_kv_heads} head_dim={cfg.dh} experts={cfg.num_experts} "
        f"top{cfg.top_k} d_expert={cfg.d_expert} vocab={cfg.vocab_size} "
        f"window={cfg.sliding_window} dtype={jax.numpy.dtype(cfg.param_dtype)}"
        f"; cut: {layers} of 32 layers, capacity_factor="
        f"{cfg.capacity_factor} (dropless)")
    log(f"parameter bytes: {nbytes} ({nbytes / 2**30:.2f} GiB)")
    log(f"compile cache: {cache_dir}")

    t0 = time.perf_counter()
    if args.chips == 1:
        errs = kernel_parity(cfg)
        log(f"(a) kernel parity, max |kernel - ref|: {errs}")
        log(f"(a) wall {time.perf_counter() - t0:.1f}s [informational]")
        res = serving_phase(cfg, counter, seed=args.seed)
        log(f"(b) {len(res['outputs'])} requests x 32 tokens, compiles in "
            f"serving window: {res['serve_compiles']}")
        for rid, toks in sorted(res["outputs"].items()):
            log(f"    request {rid}: {toks[:8]}...")
    else:
        out = switch_phase(cfg, counter, seed=args.seed)
        res = out["switched"]
        for r in res["records"]:
            log(f"switch {r.direction}: pause_s={r.pause_s:.4f} "
                f"total_s={r.total_s:.4f} kv_pages={r.kv_pages} "
                f"chunks={r.chunks} [first readings, not metrics]")
        log(f"compiles inside switch windows: {res['switch_compiles']}")
        log(f"compiles in serving windows: switched={res['serve_compiles']} "
            f"never-switched={out['base']['serve_compiles']}")
        div = out["divergence"]
        log("greedy tokens equal to the never-switched run" if div is None
            else f"first divergence: request {div[0]} token {div[1]}, max "
                 f"|logit diff| {div[2]:.4g} within {BF16_TOL} x "
                 f"{div[3]:.4g}")
        if (any(res["switch_compiles"]) or res["serve_compiles"]
                or out["base"]["serve_compiles"]):
            raise AssertionError("compiles inside the switch or serving "
                                 "window after warmup")
    log(f"warmup: {res['warm_compiles']} compiles, {res['warm_compile_s']:.1f}"
        f"s compiling; build {res['build_s']:.1f}s, warmup "
        f"{res['warmup_s']:.1f}s, serve {res['serve_s']:.1f}s "
        f"[informational]")
    log(f"all compiles: {counter.n}, {counter.secs:.1f}s [informational]")
    log(f"kernel backends: {check_backends('pallas')}")
    peak = [d.memory_stats().get("peak_bytes_in_use") for d in jax.devices()]
    limit = [d.memory_stats().get("bytes_limit") for d in jax.devices()]
    log(f"peak_bytes_in_use: {peak} (limit {limit})")
    log(f"total wall {time.perf_counter() - t0:.1f}s [informational]")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
