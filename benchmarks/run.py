"""Benchmark orchestrator — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines per benchmark, then the
roofline table from the dry-run reports (if present).

  PYTHONPATH=src python -m benchmarks.run [--quick]
"""

import argparse
import json
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer FL rounds")
    ap.add_argument("--out", default="reports/bench_results.json")
    args, _ = ap.parse_known_args()

    rounds = 25 if args.quick else None

    from . import fig3_dynamic_b, fig4_clients_privacy, table1_byzantine
    from . import fig_async_staleness, fig_privacy_amplification
    from . import fig_campaign_throughput, fig_streaming_clients
    from . import fig_bits_frontier, fig_tree_throughput
    from . import theorem_rates, kernels_micro, roofline
    import jax

    results = {}
    print("name,us_per_call,derived")
    print("# --- Theorem validation (Thm 1.3 / Thm 2) ---")
    results["theorems"] = theorem_rates.main()
    print("# --- Kernel microbenchmarks ---")
    results["kernels"] = kernels_micro.main()
    print("# --- Fig. 3: dynamic vs fixed vs oracle b ---")
    results["fig3"] = fig3_dynamic_b.main(rounds)
    print("# --- Fig. 4: clients / privacy sweeps ---")
    results["fig4"] = fig4_clients_privacy.main(rounds)
    print("# --- Table I: Byzantine attack grid (10% malicious) ---")
    results["table1"] = table1_byzantine.main(rounds)
    print("# --- Async staleness: buffer x decay x byz_frac stragglers ---")
    results["fig_async"] = fig_async_staleness.main(rounds)
    print("# --- Privacy amplification: participation x eps x aggregator ---")
    results["fig_privacy"] = fig_privacy_amplification.main(rounds)
    # The CPU virtual-device sweeps spawn children that would each need
    # the chip this process holds; on an accelerator they are not started.
    cpu = jax.default_backend() == "cpu"
    if cpu:
        print("# --- Campaign throughput: cells/sec vs virtual device count ---")
        results["fig_throughput"] = fig_campaign_throughput.main(rounds)
        print("# --- Streaming clients: dense vs chunked vs sharded M-sweep ---")
        results["fig_streaming"] = fig_streaming_clients.main(
            m_grid=(1_000, 10_000, 100_000) if args.quick else None
        )
    else:
        print("# skipped off the CPU backend: fig_campaign_throughput, "
              "fig_streaming_clients, fig_tree_throughput")
    print("# --- Bits frontier: wire_bits x byz_frac x eps grid ---")
    results["fig_bits"] = fig_bits_frontier.main(rounds)
    if cpu:
        print("# --- Tree throughput: clients/sec vs edge count ---")
        # --quick runs the reduced (smoke) grid: smaller M, fewer edge counts
        results["fig_tree"] = fig_tree_throughput.main(rounds, smoke=args.quick)

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1, default=str)
    print(f"# results written to {args.out}")

    print("# --- Roofline (from dry-run reports) ---")
    roofline.main()


if __name__ == "__main__":
    main()
