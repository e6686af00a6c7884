# One function per paper table. Print ``name,us_per_call,derived`` CSV.
# ``--json`` additionally writes BENCH_decode.json (machine-readable decode
# perf: tokens/s, us/step, projected kernel bytes for contiguous vs paged at two
# length mixes) so the perf trajectory is tracked across PRs.
import argparse
import json
import sys

from benchmarks import (attention_error, bitwidth_ablation, e2e_decode,
                        error_bench, kernel_bench, kernel_variants,
                        memory_table, overload, paged_vs_contiguous,
                        perplexity_delta, prefix_cache, sensitivity,
                        tiering)

SUITES = [
    ("table1_memory", memory_table),
    ("table3_fig123_kernel_perf", kernel_bench),
    ("fig4_left_reconstruction", error_bench),
    ("fig4_right_attention_error", attention_error),
    ("sec7.4_kernel_variants", kernel_variants),
    ("beyond_paper_e2e_decode", e2e_decode),
    ("beyond_paper_bitwidth_ablation", bitwidth_ablation),
    ("beyond_paper_perplexity_delta", perplexity_delta),
    ("beyond_paper_paged_vs_contiguous", paged_vs_contiguous),
    ("beyond_paper_prefix_cache", prefix_cache),
    ("beyond_paper_overload", overload),
    ("beyond_paper_tiering", tiering),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-size workloads (up to 1B elements; slow on CPU)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", action="store_true",
                    help="write the decode benchmark to BENCH_decode.json")
    ap.add_argument("--json-path", default="BENCH_decode.json")
    ap.add_argument("--accuracy-json", action="store_true",
                    help="run the bitwidth ablation + perplexity delta + "
                         "per-layer sensitivity profiler and write "
                         "BENCH_accuracy.json (multi-precision accuracy "
                         "gate inputs — DESIGN.md §9/§10); the profiler's "
                         "plan is also written to --plan-json-path")
    ap.add_argument("--accuracy-json-path", default="BENCH_accuracy.json")
    ap.add_argument("--plan-json-path", default="PLAN_kv_mixed.json",
                    help="where --accuracy-json writes the profiler's "
                         "PrecisionPlan (DESIGN.md §10)")
    args = ap.parse_args()
    failures = 0
    for name, mod in SUITES:
        if args.only and args.only not in name:
            continue
        print(f"# === {name} ===")
        try:
            if name == "table3_fig123_kernel_perf":
                for r in mod.run(full=args.full):
                    print(f"{r['bench']}_{r['config']},{r['xla_us']:.1f},"
                          f"cpu_us={r['cpu_us']:.1f} "
                          f"speedup={r['speedup']:.1f} "
                          f"tpu_proj_us={r['tpu_proj_us']:.1f} "
                          f"proj_speedup={r['proj_speedup']:.0f}")
            else:
                mod.main()
        except Exception as e:                        # pragma: no cover
            failures += 1
            print(f"{name},FAILED,{type(e).__name__}: {e}")
    if args.json:
        try:
            data = e2e_decode.bench_json()
            with open(args.json_path, "w") as f:
                json.dump(data, f, indent=2)
            print(f"# wrote {args.json_path}")
        except Exception as e:                        # pragma: no cover
            failures += 1
            print(f"{args.json_path},FAILED,{type(e).__name__}: {e}")
    if args.accuracy_json:
        try:
            profile = sensitivity.run()
            data = {
                "bitwidth": bitwidth_ablation.run(),
                "perplexity": [{k: v for k, v in r.items()
                                if not k.startswith("_")}
                               for r in perplexity_delta.run()],
                "mixed_plan": profile["summary"],
            }
            with open(args.accuracy_json_path, "w") as f:
                json.dump(data, f, indent=2)
            print(f"# wrote {args.accuracy_json_path}")
            with open(args.plan_json_path, "w") as f:
                json.dump(profile["plan"], f, indent=2)
            print(f"# wrote {args.plan_json_path}")
        except Exception as e:                        # pragma: no cover
            failures += 1
            print(f"{args.accuracy_json_path},FAILED,"
                  f"{type(e).__name__}: {e}")
    sys.exit(1 if failures else 0)


if __name__ == '__main__':
    main()
