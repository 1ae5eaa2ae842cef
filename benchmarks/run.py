"""Benchmark harness — one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

Default (quick) mode runs reduced sizes suitable for the CPU container; each
row prints `name,seconds,derived` CSV.  --full reproduces the paper-scale
settings (slower).  Individual figures: `python -m benchmarks.fig4_p_sweep`.
"""
from __future__ import annotations

import argparse
import time


def _row(name: str, seconds: float, derived: str) -> None:
    print(f"{name},{seconds:.2f},{derived}")


# Every figure/table this harness knows how to run.  "ablation" and "driver"
# are opt-in (not part of the default sweep).
KNOWN = (
    "fig4", "fig5", "fig6", "fig7", "table2", "roofline", "compression",
    "dynamic", "optimizers", "timecost", "sparse", "async", "robust",
    "serve", "ablation", "driver",
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale settings")
    ap.add_argument(
        "--only", nargs="*", default=None,
        help=f"subset of: {' '.join(KNOWN)}",
    )
    args = ap.parse_args()
    quick = not args.full
    only = set(args.only) if args.only else None
    if only is not None:
        unknown = only - set(KNOWN)
        if unknown:
            ap.error(
                f"unknown figure name(s): {' '.join(sorted(unknown))}; "
                f"choose from: {' '.join(KNOWN)}"
            )

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,seconds,derived")

    if only is None or "fig4" in only:
        from benchmarks import fig4_p_sweep

        t0 = time.perf_counter()
        payload = fig4_p_sweep.run(quick=quick)
        res = payload["results"]
        p0 = next((v for k, v in res.items() if k.startswith("p=0.0000")), None)
        pm = min(
            (v for k, v in res.items() if not k.startswith("p=0.0000") and v["train"]),
            key=lambda v: v["train"]["rounds"],
            default=None,
        )
        p1 = res.get("p=1.0000")
        derived = "n/a"
        if p0 and p0["train"] and pm:
            saving = 1.0 - pm["train"]["a2a"] / max(1.0, p0["train"]["a2a"])
            derived = f"a2a_savings_vs_p0={saving:.0%}"
        elif pm and p1 and p1["train"]:
            # p=0 never reached the target (the strongest form of the claim)
            derived = (
                f"p0_never_reached;best_semi_a2s={pm['train']['a2s']:.0f}"
                f";p1_a2s={p1['train']['a2s']:.0f}"
            )
        _row("fig4_p_sweep", time.perf_counter() - t0, derived)

    if only is None or "fig5" in only:
        from benchmarks import fig5_local_updates

        t0 = time.perf_counter()
        payload = fig5_local_updates.run(quick=quick)
        res = payload["results"]
        r1 = res.get("T_o=1,p=0.1000", {}).get("train_rounds")
        r10 = res.get("T_o=10,p=0.1000", {}).get("train_rounds")
        derived = (
            f"rounds_T1={r1:.0f};rounds_T10={r10:.0f}" if r1 and r10 else "n/a"
        )
        _row("fig5_local_updates", time.perf_counter() - t0, derived)

    if only is None or "fig6" in only:
        from benchmarks import fig6_topology

        t0 = time.perf_counter()
        payload = fig6_topology.run(quick=quick)
        res = payload["results"]
        dis0 = res.get("er_disconnected,p=0.0000", {}).get("final_train_loss")
        dis1 = res.get("er_disconnected,p=0.1000", {}).get("final_train_loss")
        derived = (
            f"disc_loss_p0={dis0:.3f};p0.1={dis1:.3f}" if dis0 and dis1 else "n/a"
        )
        _row("fig6_topology", time.perf_counter() - t0, derived)

    if only is None or "fig7" in only:
        from benchmarks import fig7_cnn

        t0 = time.perf_counter()
        payload = fig7_cnn.run(quick=quick)
        res = payload["results"]
        accs = {k: v["final_test_acc"] for k, v in res.items()}
        derived = ";".join(f"{k}={v:.2f}" for k, v in accs.items())
        _row("fig7_cnn", time.perf_counter() - t0, derived)

    if only is None or "compression" in only:
        from benchmarks import fig_compression

        t0 = time.perf_counter()
        payload = fig_compression.run(quick=quick)
        res = payload["results"]
        saving = fig_compression.best_same_p_savings(res)
        derived = (
            f"gossip_byte_savings_vs_fp32={saving:.1f}x" if saving else "n/a"
        )
        _row("fig_compression", time.perf_counter() - t0, derived)

    if only is None or "dynamic" in only:
        from benchmarks import fig_dynamic

        t0 = time.perf_counter()
        payload = fig_dynamic.run(quick=quick)
        saving = fig_dynamic.participation_byte_savings(payload["results"])
        _row(
            "fig_dynamic",
            time.perf_counter() - t0,
            f"server_byte_savings_half_part={saving:.2f}x" if saving else "n/a",
        )

    if only is None or "optimizers" in only:
        from benchmarks import fig_optimizers

        t0 = time.perf_counter()
        payload = fig_optimizers.run(quick=quick)
        s = fig_optimizers.best_adaptive_speedup(payload["results"])
        _row(
            "fig_optimizers",
            time.perf_counter() - t0,
            f"best_adaptive_speedup={s:.2f}x" if s else "n/a",
        )

    if only is None or "timecost" in only:
        from benchmarks import fig_timecost

        t0 = time.perf_counter()
        payload = fig_timecost.run(quick=quick)
        flip = fig_timecost.tuner_flip(payload["profiles"])
        derived = (
            f"best_p_lan={flip[0]:g};best_p_wan={flip[1]:g}" if flip else "n/a"
        )
        _row("fig_timecost", time.perf_counter() - t0, derived)

    if only is None or "async" in only:
        from benchmarks import fig_async

        t0 = time.perf_counter()
        payload = fig_async.run(quick=quick)
        speed = fig_async.async_flip(payload["profiles"])
        trivial_ok = payload["profiles"]["free"]["bit_identical_loss"]
        derived = (
            f"free_bit_identical={trivial_ok}"
            + "".join(f";{k}_speedup={v:.2f}x" for k, v in speed.items()
                      if k != "free")
        )
        _row("fig_async", time.perf_counter() - t0, derived)

    if only is None or "robust" in only:
        from benchmarks import fig_robust

        t0 = time.perf_counter()
        payload = fig_robust.run(quick=quick)
        rows = payload["rows"]
        clean = payload["clean_final_loss"]
        trim_ratio = rows["signflip+trimmed"]["final_loss"] / max(clean, 1e-12)
        mean_ratio = rows["signflip+mean"]["final_loss"] / max(clean, 1e-12)
        derived = (
            f"flip={payload['robustness_flip']}"
            f";trimmed_vs_clean={trim_ratio:.2f}x"
            f";mean_vs_clean={mean_ratio:.2f}x"
        )
        _row("fig_robust", time.perf_counter() - t0, derived)

    if only is None or "table2" in only:
        from benchmarks import table2_complexity

        t0 = time.perf_counter()
        payload = table2_complexity.run(quick=quick)
        nd = payload["network_dependency"]
        r = next(x for x in nd if x["lambda_w"] == 1e-4 and 0 < x["p"] < 1 and x["p"] > x["lambda_w"])
        derived = f"lam1e-4_sqrtp_dependency={r['network_term']:.1e}"
        _row("table2_complexity", time.perf_counter() - t0, derived)

    if only is not None and "ablation" in only:
        from benchmarks import ablation_eta_c

        t0 = time.perf_counter()
        payload = ablation_eta_c.run(quick=quick)
        best = min(
            (v["final_grad_sq"] for v in payload["results"].values()),
        )
        _row("ablation_eta_c", time.perf_counter() - t0, f"best_grad_sq={best:.2e}")

    if only is not None and "driver" in only:
        from benchmarks import bench_driver

        t0 = time.perf_counter()
        payload = bench_driver.run(quick=quick)
        _row(
            "bench_driver",
            time.perf_counter() - t0,
            f"scan_speedup={payload['speedup']:.2f}x",
        )

    if only is None or "sparse" in only:
        from benchmarks import fig_sparse

        t0 = time.perf_counter()
        payload = fig_sparse.run(quick=quick)
        ratio = fig_sparse.memory_ratio(payload["results"])
        biggest = max(payload["results"].values(), key=lambda r: r["n_agents"])
        derived = (
            f"mem_savings_n{biggest['n_agents']}={ratio:.0f}x"
            f";per_round_ms={biggest['per_round_s'] * 1e3:.1f}"
        )
        _row("fig_sparse", time.perf_counter() - t0, derived)

    if only is None or "serve" in only:
        from benchmarks import fig_serve

        t0 = time.perf_counter()
        payload = fig_serve.run(quick=quick)
        mem = payload["memory"]["64"]["ratio"]
        bit = all(payload["bit_identity"][k] for k in
                  ("admit_vs_dense", "step_vs_dense"))
        best = max(v["tokens_per_s"] for v in payload["rates"].values())
        derived = (
            f"mem_savings_n64={mem:.0f}x;bit_identical={bit}"
            f";best_tok_s={best:.0f}"
        )
        _row("fig_serve", time.perf_counter() - t0, derived)

    if only is None or "roofline" in only:
        from benchmarks import roofline
        from benchmarks.common import save_result

        t0 = time.perf_counter()
        recs = roofline.load_records()
        s = roofline.summarize(recs)
        # persist the aggregation so the regression gate can pin n_fail == 0
        save_result(
            "BENCH_roofline",
            {"bench": "roofline", "quick": quick, "summary": s},
        )
        derived = f"ok={s['n_ok']};fail={s['n_fail']};dominant={s['dominant_counts']}"
        _row("roofline", time.perf_counter() - t0, derived.replace(",", ";"))

    # re-index whatever BENCH_* artifacts now exist (this run's plus any
    # earlier ones in the same artifacts dir) for benchmarks/check_regress.py
    from benchmarks.common import write_manifest

    write_manifest()


if __name__ == "__main__":
    main()
