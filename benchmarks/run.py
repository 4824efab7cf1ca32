"""Benchmark harness entry point — one section per paper table/figure plus
the roofline report. Prints ``name,us_per_call,derived`` CSV lines and
writes JSON artifacts to benchmarks/results/.

  PYTHONPATH=src python -m benchmarks.run            # quick (CI) sizes
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale sizes
  PYTHONPATH=src python -m benchmarks.run --only paper_tables,roofline
  PYTHONPATH=src python -m benchmarks.run --only dmf_train,serving --devices 8
                                # ^ learner-sharded sections need host devices
"""
from __future__ import annotations

import argparse
import sys
import time

# every section this harness dispatches — `--only` takes a comma-separated
# subset (whitespace tolerated) and rejects unknown names instead of
# silently running nothing
SECTIONS = (
    "paper_tables", "convergence", "reg_sweep", "walk_sweep", "dmf_train",
    "serving", "scheduler", "privacy", "robustness", "byzantine",
    "complexity",
    "gossip_ablation", "perf_report", "kernels", "roofline",
)


def _section(name):
    # marker event in the span trace (no-op while tracing is disabled);
    # importing repro.obs.trace binds no XLA flag, so this is safe
    # pre-device-flag
    from repro.obs import trace as trace_lib
    trace_lib.get_tracer().instant("bench.section", section=name)
    print(f"# --- {name} " + "-" * max(0, 60 - len(name)), flush=True)


def parse_only(spec: str) -> set | None:
    """``--only a, b`` -> {'a', 'b'}; empty/None -> run everything."""
    if not spec:
        return None
    only = {s.strip() for s in spec.split(",") if s.strip()}
    unknown = only - set(SECTIONS)
    if unknown:
        raise SystemExit(
            f"--only: unknown section(s) {sorted(unknown)}; "
            f"choose from {', '.join(SECTIONS)}")
    return only


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma-separated section list "
                         f"({', '.join(SECTIONS)}); default: all")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host-platform devices (the dmf_train/"
                         "serving `sharded` sections need 8; 0 = leave the "
                         "jax default — sharded entries are then recorded "
                         "as skipped)")
    ap.add_argument("--trace-out", default=None,
                    help="enable span tracing (repro.obs.trace) for the "
                         "whole run and write the Chrome-trace/Perfetto "
                         "JSON here at the end")
    ap.add_argument("--metrics-out", default=None,
                    help="append a final metrics-registry snapshot "
                         "(JSONL) here at the end")
    args = ap.parse_args()
    if args.devices > 0:
        # must happen before ANY jax backend init — the bench modules are
        # imported lazily below for exactly this reason (importing
        # repro.launch.mesh itself is safe: imports don't bind XLA_FLAGS)
        from repro.launch.mesh import ensure_host_platform_devices

        ensure_host_platform_devices(args.devices)
    only = parse_only(args.only)
    from repro.launch import compile_cache
    compile_cache.enable()

    if args.trace_out:
        from repro.obs import trace as trace_lib
        trace_lib.configure_tracing(True)

    from benchmarks import common

    def want(name):
        return only is None or name in only

    if want("paper_tables"):
        from benchmarks import paper_tables
        _section("paper_tables (Tables 2 & 3)")
        t0 = time.perf_counter()
        res = paper_tables.main(full=args.full, seeds=(0, 1) if not args.full else (0, 1, 2))
        us = (time.perf_counter() - t0) * 1e6
        common.save_json("paper_tables", res)
        for ds, r in res.items():
            claims = " ".join(f"{k}={v}" for k, v in r["claims"].items())
            print(f"paper_tables_{ds},{us:.0f},{claims}")
            for K, models in r["table"].items():
                for m, ev in models.items():
                    print(
                        f"paper_tables_{ds}_K{K}_{m},0,"
                        f"P@5={ev['P@5']:.4f};R@5={ev['R@5']:.4f};"
                        f"P@10={ev['P@10']:.4f};R@10={ev['R@10']:.4f}"
                    )

    if want("convergence"):
        from benchmarks import convergence
        _section("convergence (Fig. 4)")
        t0 = time.perf_counter()
        res = convergence.main(full=args.full)   # saves BENCH_convergence itself
        us = (time.perf_counter() - t0) * 1e6
        for ds, r in res.items():
            print(
                f"convergence_{ds},{us:.0f},converged={r['converged']};"
                f"first={r['train_loss'][0]};last={r['train_loss'][-1]}"
            )

    if want("reg_sweep"):
        from benchmarks import reg_sweep
        _section("reg_sweep (Fig. 5)")
        t0 = time.perf_counter()
        res = reg_sweep.main(full=args.full)
        us = (time.perf_counter() - t0) * 1e6
        common.save_json("reg_sweep", res)
        print(
            f"reg_sweep,{us:.0f},best={res['best']};"
            f"sensitive={res['spread_validates_sensitivity']}"
        )

    if want("walk_sweep"):
        from benchmarks import walk_sweep
        _section("walk_sweep (Fig. 6)")
        t0 = time.perf_counter()
        res = walk_sweep.main(full=args.full)    # saves BENCH_walk_sweep itself
        us = (time.perf_counter() - t0) * 1e6
        for ds, r in res.items():
            print(
                f"walk_sweep_{ds},{us:.0f},"
                + ";".join(f"D{d}={v}" for d, v in r["R@10_by_D"].items())
                + f";stable_after_3={r['stable_after_3']}"
            )

    if want("dmf_train"):
        from benchmarks import dmf_train_bench
        _section("dmf_train (sparse-scan vs seed dense hot path)")
        t0 = time.perf_counter()
        res = dmf_train_bench.main(full=args.full)
        us = (time.perf_counter() - t0) * 1e6
        e = res["epochs_per_sec"]
        print(
            f"dmf_train,{us:.0f},"
            f"dense={e['dense_per_batch']:.3f}eps;sparse={e['sparse_scan']:.3f}eps;"
            f"pallas={e['sparse_scan_pallas']:.3f}eps;"
            f"speedup={res['speedup_sparse_vs_dense']:.1f}x;"
            f"loss_dev={res['train_loss_max_diff_sparse']:.2e}"
        )
        sh = res["sharded"]
        eps_sh = ";".join(
            f"{k}={v:.3f}eps" for k, v in sh["epochs_per_sec"].items()
            if v is not None)
        print(
            f"dmf_train_sharded,0,I={sh['config']['n_users']};"
            f"devices={sh['config']['n_devices']};{eps_sh or 'all_skipped'}"
        )

    if want("serving"):
        from benchmarks import serving_bench
        _section("serving (engine: loop vs batched vs geo-pruned)")
        t0 = time.perf_counter()
        res = serving_bench.main(full=args.full)
        us = (time.perf_counter() - t0) * 1e6
        r = res["requests_per_sec"]
        print(
            f"serving,{us:.0f},"
            f"loop={r['loop_per_request']:.1f}rps;"
            f"dense={r['batched_dense']:.1f}rps;"
            f"pruned={r['batched_pruned']:.1f}rps;"
            f"speedup_vs_loop={res['speedup_pruned_vs_loop']:.1f}x;"
            f"agree_in_bucket="
            f"{res['pruned_dense_topk_agreement_where_in_bucket']:.3f};"
            f"agree_raw={res['pruned_dense_topk_agreement']:.3f}"
        )
        sh = res["sharded"]
        rps_sh = ";".join(
            f"{k}={v:.1f}rps" for k, v in sh["requests_per_sec"].items()
            if v is not None)
        print(
            f"serving_sharded,0,devices={sh['config']['n_devices']};"
            f"{rps_sh or 'all_skipped'}"
        )
        mil = res["million"]
        mr = mil["requests_per_sec"]
        print(
            f"serving_million,0,"
            f"I={mil['config']['n_users']};J={mil['config']['n_items']};"
            f"cells={mil['index']['n_cells']};cap={mil['index']['cap']};"
            f"slab_gb={mil['resident_gb']['slab_fp32']:.2f};"
            f"fp32={mr['fp32']:.0f}rps;int8={mr['int8']:.0f}rps;"
            f"bf16={mr['bf16']:.0f}rps;"
            f"fp32_bitwise={mil['exact']['fp32_bitwise_vs_dense_engine']};"
            f"int8_delta={mil['exact']['int8']['max_abs_score_delta']:.2e}"
        )

    if want("scheduler"):
        from benchmarks import scheduler_bench
        _section("scheduler (continuous batching + SLO admission)")
        t0 = time.perf_counter()
        res = scheduler_bench.main(full=args.full)   # saves BENCH_scheduler
        us = (time.perf_counter() - t0) * 1e6
        for key, entry in res["grid"].items():
            if "skipped" in entry:
                print(f"scheduler_{key},0,skipped={entry['skipped']}")
                continue
            pts = ";".join(
                f"x{row['offered_frac_of_capacity']}:"
                f"goodput={row['scheduler']['goodput_rps']:.0f}rps:"
                f"slo={row['scheduler']['slo_attainment']:.3f}:"
                f"p50={row['scheduler']['latency_ms']['p50_ms']:.1f}ms"
                for row in entry["loads"])
            print(f"scheduler_{key},0,{pts};"
                  f"bit_identical={entry['bit_identical_vs_direct']}")
        p50 = res["p50_ms_at_max_shards"]
        ing = res["ingest_interleave"]
        print(
            f"scheduler,{us:.0f},"
            f"capacity={res['single_shard_capacity_rps']:.0f}rps;"
            f"max_shards={res['max_shards_measured']};"
            f"p50_sched={p50['scheduler']:.1f}ms;"
            f"p50_lockstep={p50['lockstep']:.1f}ms;"
            f"beats_lockstep={res['scheduler_beats_lockstep_p50_at_max_shards']};"
            f"ingest_idle={ing['ingest_ran_in_idle_gap']};"
            f"ingest_snapshots_exact="
            f"{ing['pre_ingest_bit_identical_to_no_ingest'] and ing['post_ingest_bit_identical_to_ingested_snapshot']}"
        )

    if want("privacy"):
        from benchmarks import privacy_bench
        _section("privacy (DP exchange: eps-utility frontier + audit)")
        t0 = time.perf_counter()
        res = privacy_bench.main(full=args.full)   # saves BENCH_privacy itself
        us = (time.perf_counter() - t0) * 1e6
        fr = res["frontier"]
        pts = ";".join(
            f"eps={'inf' if r['eps'] is None else round(r['eps'], 2)}:"
            f"P@10={r['P@10']:.4f}:adv={r['rating_inversion_advantage']:.3f}"
            for r in fr)
        print(
            f"privacy,{us:.0f},{pts};"
            f"monotone={res['attack_advantage_monotone_nonincreasing']};"
            f"dp_overhead_fused="
            f"{res['dp_overhead_fused_vs_pallas_base']:.3f}"
        )

    if want("robustness"):
        from benchmarks import churn_bench
        _section("robustness (churn/staleness degradation + crash-resume)")
        t0 = time.perf_counter()
        res = churn_bench.main(full=args.full)   # saves BENCH_churn itself
        us = (time.perf_counter() - t0) * 1e6
        worst = max(res["grid"][1:],
                    key=lambda r: abs(r["loss_gap_vs_faultfree"]))
        print(
            f"robustness,{us:.0f},"
            f"anchor_gap={res['grid'][0]['loss_gap_vs_faultfree']:.2e};"
            f"worst_gap=p{worst['dropout']}k{worst['k_max']}:"
            f"{worst['loss_gap_vs_faultfree']:.4f};"
            f"resume_bit_identical={res['resume']['bit_identical_with_dp']};"
            f"churn_overhead={res['churn_overhead_vs_base']:.3f};"
            f"ckpt_overhead={res['checkpoint_overhead_vs_base']:.3f}"
        )

    if want("byzantine"):
        from benchmarks import byzantine_bench
        _section("byzantine (attack injection vs screening/robust agg)")
        t0 = time.perf_counter()
        res = byzantine_bench.main(full=args.full)  # saves BENCH_byzantine
        us = (time.perf_counter() - t0) * 1e6
        h = res["headline"]
        ratio = h["undefended_collapse_ratio"]
        print(
            f"byzantine,{us:.0f},"
            f"anchor_gap={res['anchor']['byz_off_gap']:.2e};"
            f"undefended="
            f"{'nonfinite' if h['undefended_nonfinite'] else f'{ratio:.1f}x'};"
            f"collapsed={h['undefended_collapsed']};"
            f"defended={h['defended_ratio']:.3f}x;"
            f"within_1p5x={h['defended_within_1p5x']};"
            f"screen_overhead={res['screening_overhead_vs_base']:.3f};"
            f"trim_overhead={res['robust_agg_overhead_vs_base']:.3f};"
            f"dp_pass_rate={res['dp_interaction']['honest_pass_rate']:.4f}"
        )

    if want("complexity"):
        from benchmarks import complexity
        _section("complexity (paper §Complexity)")
        t0 = time.perf_counter()
        res = complexity.main(full=args.full)
        us = (time.perf_counter() - t0) * 1e6
        common.save_json("complexity", res)
        print(
            f"complexity,{us:.0f},comm_linear={res['comm_linear']};"
            f"compute_linear={res['compute_linear']}"
        )

    if want("gossip_ablation"):
        from benchmarks import gossip_ablation
        _section("gossip_ablation (beyond-paper: DMF sync at LM scale)")
        t0 = time.perf_counter()
        res = gossip_ablation.main()     # saves BENCH_gossip_ablation itself
        us = (time.perf_counter() - t0) * 1e6
        print(
            f"gossip_ablation,{us:.0f},"
            f"allreduce={res['allreduce']['last']};"
            f"gossip_d1={res['gossip_d1']['last']};"
            f"gossip_d2={res['gossip_d2']['last']};"
            f"gap={res['gossip_minus_allreduce_final_loss']};"
            f"consensus_err={res['gossip_d1']['consensus_err']}"
        )

    if want("perf_report"):
        from benchmarks import perf_report
        _section("perf_report (§Perf before/after)")
        for line in perf_report.render(perf_report.main()).splitlines():
            print(line)

    if want("kernels"):
        from benchmarks import kernels_bench
        _section("kernels (Pallas vs ref)")
        for name, us, extra in kernels_bench.main():
            print(f"{name},{us:.0f},{extra}")

    if want("roofline"):
        from benchmarks import roofline
        _section("roofline (dry-run artifacts, analytic fallback)")
        rows = roofline.main()
        common.save_json("roofline", rows)
        for r in rows:
            print(
                f"roofline_{r['arch']}_{r['shape']},0,"
                f"compute={r['t_compute_s']:.3e};memory={r['t_memory_s']:.3e};"
                f"collective={r['t_collective_s']:.3e};dominant={r['dominant']};"
                f"useful={r['useful_ratio']:.2f};src={r['collective_source']}"
            )

    if args.trace_out:
        from repro.obs import trace as trace_lib
        trace_lib.get_tracer().export_chrome_trace(args.trace_out)
        print(f"# trace written to {args.trace_out} "
              f"({len(trace_lib.get_tracer().events())} events)", flush=True)
    if args.metrics_out:
        from repro.obs import metrics as obs_metrics
        obs_metrics.get_registry().write_jsonl(args.metrics_out,
                                               event="bench_run_final")
        print(f"# metrics snapshot appended to {args.metrics_out}",
              flush=True)


if __name__ == "__main__":
    main()
