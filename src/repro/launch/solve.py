"""Distributed D-iteration solve driver — the CLI over ``repro.solve``.

Every run goes through the :mod:`repro.api` front door: a
:class:`Problem` + :class:`SolverOptions` + a registry ``--method``
key (or ``auto``).  Flag combinations are validated — ``--k`` is
honored (or rejected) on every path and ``--policy`` implies
``--dynamic`` everywhere, instead of the historical behavior where the
engine path silently ignored both.

  PYTHONPATH=src python -m repro.launch.solve --n 20000 --dynamic --k 8
  PYTHONPATH=src python -m repro.launch.solve --method simulator --k 16
  PYTHONPATH=src python -m repro.launch.solve --method engine:bsr
  PYTHONPATH=src python -m repro.launch.solve --policy hysteresis --k 8
  PYTHONPATH=src python -m repro.launch.solve --graph-file web.txt

``--graph-file`` loads a SNAP-style edge-list text file (``src dst``
per line, ``#`` comments) through :meth:`repro.GraphStore.
from_edge_file` — real-graph workloads beyond the synthetic
generators.
"""
import argparse

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="Solve a synthetic PageRank instance through the "
        "repro.api backend registry."
    )
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--graph", choices=["powerlaw", "web"], default="web")
    ap.add_argument("--graph-file", default=None,
                    help="SNAP-style edge-list file ('src dst' lines, "
                    "'#' comments) loaded via GraphStore.from_edge_file; "
                    "overrides --n/--graph")
    ap.add_argument("--weighted", action="store_true",
                    help="--graph-file has a third weight column")
    ap.add_argument("--target-error", type=float, default=None,
                    help="stopping target (default 1/N, paper §3.1)")
    ap.add_argument("--method", default="auto",
                    help="registry key (see repro.list_backends()) or "
                    "'auto'")
    ap.add_argument("--simulate", action="store_true",
                    help="alias for --method simulator")
    ap.add_argument("--k", type=int, default=None,
                    help="PID/device count; validated against the chosen "
                    "backend (raises instead of being silently ignored)")
    ap.add_argument("--dynamic", action="store_true",
                    help="enable the §2.5.2 dynamic partition controller")
    ap.add_argument("--policy", default=None,
                    choices=["slope_ema", "cost_refresh", "hysteresis"],
                    help="rebalancing policy (implies --dynamic)")
    ap.add_argument("--signal", default="residual",
                    choices=["residual", "edge-ops"])
    ap.add_argument("--partition", default="uniform",
                    choices=["uniform", "cb"])
    ap.add_argument("--buckets-per-dev", type=int, default=8)
    ap.add_argument("--verbose", action="store_true")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.simulate:
        if args.method not in ("auto", "simulator"):
            raise SystemExit(
                f"--simulate conflicts with --method {args.method!r}"
            )
        args.method = "simulator"

    import repro
    from repro.compile_cache import enable_compile_cache
    from repro.core import power_law_graph, webgraph_like

    enable_compile_cache()
    if args.graph_file is not None:
        store = repro.GraphStore.from_edge_file(args.graph_file,
                                                weighted=args.weighted)
        g = store.csr()
        print(f"loaded {args.graph_file}: N={g.n} L={g.n_edges}")
    else:
        g = (power_law_graph(args.n, seed=0) if args.graph == "powerlaw"
             else webgraph_like(args.n, seed=1))
    problem = repro.Problem.pagerank(g, target_error=args.target_error)
    print(f"N={g.n} L={g.n_edges} target_error={problem.target_error:.2e}")

    k = args.k
    if k is None and args.method.startswith("engine:"):
        # the engine's historical CLI default: one PID per visible device
        import jax

        k = len(jax.devices())
    options = repro.SolverOptions(
        k=k,
        dynamic=args.dynamic,
        policy=args.policy,
        signal=args.signal,
        partition=args.partition,
        buckets_per_dev=args.buckets_per_dev,
        mode="batch",
        record_every=100,
        verbose=args.verbose,
    )
    # validate the flag set up front so a rejected combination exits
    # cleanly, while genuine solver failures keep their tracebacks
    try:
        if args.method == "auto":
            options.validated()
        else:
            options.validated(repro.get_backend(args.method).caps,
                              args.method)
    except (KeyError, ValueError) as e:
        raise SystemExit(f"inconsistent flags: {e}")
    report = repro.solve(problem, method=args.method, options=options)
    print(report.summary())
    if report.move_log:
        print(f"moves: {report.move_log[:8]}"
              f"{' ...' if len(report.move_log) > 8 else ''}")
    print("top-5:", np.argsort(-report.x)[:5].tolist())
    return report


if __name__ == "__main__":
    main()
