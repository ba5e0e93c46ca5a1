"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Sections:
  table1/*  LoRA parameter % across the Falcon3 family   (Table I)
  table2/*  adapter-placement ablation                   (Table II)
  table3/*  hardware comparison column                   (Table III)
  fig1a/*   CiROM full-model area estimates              (Fig. 1a)
  fig5b/*   DR eDRAM access-reduction sweep              (Fig. 5b)
  fig6a/*   LoRA quantization-bit ablation (measured)    (Fig. 6a)
  kernel/*  ternary matmul + packing microbenchmarks: impl axis
            (xla vs pallas), decode-shaped rows, shape-aware blocking vs
            pad-to-256, fused epilogue, fused QKV projections, and the
            flash-decode attention capacity × length sweep
  serving/* packed decode + DR traffic (measured), the
            continuous-batching vs lock-step throughput comparison,
            chunked vs grouped admission, prefix sharing, the overload
            degradation sweep, the speculative-decoding K x
            draft-quality sweep (tokens per verify round + ledger), and
            the router-failover replicas x kill-rate sweep (goodput +
            migration ledger, bit-exactness asserted under kills)

Run:  PYTHONPATH=src python -m benchmarks.run [--fast] [--only PREFIX]
                                              [--json [PATH]]

``--only kernel`` runs just the kernel sections; ``--json`` additionally
records the rows as structured JSON, split by section family: kernel and
paper-table rows land in PATH (default BENCH_kernels.json), ``serving/``
rows in BENCH_serving.json next to it.
"""

from __future__ import annotations

import argparse
import json
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="skip the trained ablation")
    ap.add_argument("--only", default=None,
                    help="run only sections whose name starts with this prefix")
    ap.add_argument("--json", nargs="?", const="BENCH_kernels.json", default=None,
                    help="also write rows as JSON (default: BENCH_kernels.json)")
    args = ap.parse_args()

    from benchmarks import kernel_bench, paper_tables, serving_bench
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    rows: list = []
    sections = [
        ("table1", paper_tables.table1),
        ("table2", paper_tables.table2),
        ("table3", paper_tables.table3),
        ("fig1a", paper_tables.fig1a),
        ("fig5b", paper_tables.fig5b),
        ("kernel/density", kernel_bench.packing_density),
        ("kernel/matmul", kernel_bench.ternary_matmul_shapes),
        ("kernel/decode_blocking", kernel_bench.decode_blocking),
        ("kernel/fused_epilogue", kernel_bench.fused_epilogue),
        ("kernel/fused_prologue", kernel_bench.fused_prologue),
        ("kernel/expert_eloop", kernel_bench.expert_eloop),
        ("kernel/fused_qkv", kernel_bench.fused_projection),
        ("kernel/flash_decode", kernel_bench.flash_decode),
        ("kernel/flash_prefill", kernel_bench.flash_prefill),
        ("serving", kernel_bench.serving_token_rate),
        ("serving/continuous", serving_bench.serving_throughput),
        ("serving/admission", serving_bench.chunked_admission),
        ("serving/prefix", serving_bench.shared_prefix),
        ("serving/overload", serving_bench.overload),
        ("serving/speculative", serving_bench.speculative_sweep),
        ("serving/router", serving_bench.router_failover),
        ("serving/sdc", serving_bench.sdc_resilience),
    ]
    if not args.fast:
        sections.append(("fig6a", paper_tables.fig6a))
    if args.only:
        sections = [(n, f) for n, f in sections if n.startswith(args.only)]

    failures = 0
    for name, fn in sections:
        try:
            rows.extend(fn())
        except AssertionError as e:
            failures += 1
            rows.append(f"{name}/REPRODUCTION-MISMATCH,0.0,{e}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            rows.append(f"{name}/ERROR,0.0,{type(e).__name__}:{e}")

    print("name,us_per_call,derived")
    for r in rows:
        print(r)
    if args.json:
        import os

        import jax

        backend = jax.default_backend()
        # serving rows go to their own artifact: the CI conformance job
        # diffs BENCH_serving.json (scheduling + speculation ledgers)
        # independently of the kernel-latency file
        serving_path = os.path.join(
            os.path.dirname(args.json) or ".", "BENCH_serving.json")
        buckets = {args.json: [], serving_path: []}
        for r in rows:
            name, us, derived = r.split(",", 2)
            path = serving_path if name.startswith("serving") else args.json
            buckets[path].append({"name": name, "us_per_call": float(us),
                                  "derived": derived})
        for path, structured in buckets.items():
            if not structured:
                continue
            with open(path, "w") as f:
                json.dump({"backend": backend, "rows": structured},
                          f, indent=1)
            print(f"\nwrote {len(structured)} rows to {path}",
                  file=sys.stderr)
    if failures:
        print(f"\n{failures} section(s) failed", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
