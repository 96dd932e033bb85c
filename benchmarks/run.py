"""Benchmark driver: one function per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV rows per the harness contract:
  * paper tables — derived = (model value, paper value) pairs;
  * kernel benches — us_per_call measured, derived = byte-reduction factors;
  * roofline summary — derived = dominant term + roofline fraction (full
    table lives in EXPERIMENTS.md §Roofline, built from the same artifacts).
"""
from __future__ import annotations

import time


def _row(name, us, derived):
    print(f"{name},{'' if us is None else f'{us:.2f}'},{derived}")


def main() -> None:
    t_start = time.time()
    print("name,us_per_call,derived")

    # ---- paper tables/figures (analytical CUTIE model) ----
    from benchmarks import paper_tables as pt
    from repro.launch.compile_cache import use_compile_cache  # src/ is on the path now

    use_compile_cache()

    for name, model_v, paper_v in pt.table1():
        d = f"model={model_v:.4g}" + ("" if paper_v is None else f";paper={paper_v:.4g}")
        _row(f"table1/{name}", None, d)
    for net, v, uj, ips in pt.fig5(steps=5):
        _row(f"fig5/{net}@{v}V", None, f"uJ={uj:.3g};inf_per_s={ips:.5g}")
    for v, eff, tput in pt.fig6(steps=5):
        _row(f"fig6/peak@{v}V", None, f"TOp_s_W={eff:.4g};TOp_s={tput:.4g}")
    for name, val, note in pt.dvs_tcn_soa_comparison():
        _row(f"soa/{name}", None, f"value={val:.4g};note={note}")

    # ---- kernel microbenches ----
    from benchmarks.kernel_bench import bench_conv, bench_matmul

    r = bench_matmul()
    _row(f"kernel/{r['name']}", r["pallas_interp_us"],
         f"dense_us={r['dense_us']:.1f};bytes_reduction={r['bytes_reduction']:.1f}x;"
         f"err={r['max_err_vs_ref']:.2g}")
    r = bench_conv()
    _row(f"kernel/{r['name']}", r["pallas_interp_us"],
         f"ref_us={r['ref_packed_us']:.1f};err={r['max_err_vs_ref']:.2g}")

    # ---- end-to-end smoke benches (CPU, reduced configs) ----
    import jax
    from repro.configs import get_config
    from repro.data.pipeline import LMTokenPipeline
    from repro.launch.steps import make_train_state, make_train_step
    from repro.optim.adamw import AdamWConfig

    for arch in ("gemma-2b", "mamba2-370m"):
        cfg = get_config(arch, smoke=True)
        pipe = LMTokenPipeline(cfg.vocab_size, 32, 4, seed=0)
        step = jax.jit(make_train_step(cfg, AdamWConfig(warmup_steps=1)), donate_argnums=(0,))
        state = make_train_state(cfg, jax.random.PRNGKey(0))
        b = pipe.next_batch()
        state, _ = step(state, b)  # compile
        t0 = time.perf_counter()
        for _ in range(3):
            state, m = step(state, pipe.next_batch())
        jax.block_until_ready(m["loss"])
        _row(f"train_smoke/{arch}", (time.perf_counter() - t0) / 3 * 1e6,
             f"loss={float(m['loss']):.3f}")

    # ---- roofline summary from dry-run artifacts (if present) ----
    try:
        from benchmarks.roofline import full_table

        rows = [r for r in full_table() if r.get("status") == "ok"]
        if rows:
            worst = min(rows, key=lambda r: r["roofline_fraction"])
            best = max(rows, key=lambda r: r["roofline_fraction"])
            _row("roofline/cells_ok", None, f"n={len(rows)}")
            _row("roofline/best", None,
                 f"{best['arch']}/{best['shape']}={best['roofline_fraction']*100:.1f}%;"
                 f"bound={best['dominant']}")
            _row("roofline/worst", None,
                 f"{worst['arch']}/{worst['shape']}={worst['roofline_fraction']*100:.1f}%;"
                 f"bound={worst['dominant']}")
    except Exception as e:  # noqa: BLE001
        _row("roofline/unavailable", None, str(e)[:60])

    _row("total_bench_seconds", None, f"{time.time()-t_start:.1f}")


if __name__ == "__main__":
    main()
