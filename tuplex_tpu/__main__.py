"""``python -m tuplex_tpu`` — CLI entry point.

Bare invocation keeps the interactive shell with a ready Context and jedi
tab-completion (reference: python/tuplex/utils/interactive_shell.py
TuplexShell, launched by the `tuplex` console entry point). Subcommands:

    python -m tuplex_tpu                  # interactive shell (default)
    python -m tuplex_tpu shell            # same, explicit
    python -m tuplex_tpu lint script.py   # plan-time UDF static analysis
    python -m tuplex_tpu compilestats script.py   # compile forecast
    python -m tuplex_tpu trace out.json   # history -> Chrome trace JSON
    python -m tuplex_tpu excstats         # exception-plane readout
    python -m tuplex_tpu whyslow [job]    # latency-budget readout
    python -m tuplex_tpu serve <root>     # multi-tenant job service
    python -m tuplex_tpu version          # print the package version

`lint` runs the compiler's static analyzer (compiler/analyzer.py) over every
UDF the script hands to DataSet methods — purely syntactic — and prints
per-UDF fallback, exception-site, purity, and static-type findings with
file:line locations, plus dead-resolver warnings (a resolve()/ignore()
targeting an error the guarded UDF provably cannot raise). It then imports
the script with actions stubbed (compilestats harness: no stage executes,
nothing compiles) and prints a jaxpr findings section — every
compiler/graphlint verdict from plan-time stage vetting (compile-wedge
rules, dtype creep, broadcast blowup, static peak-memory). `--strict`
exits non-zero when any fallback finding, dead resolver, or
wedge-severity jaxpr finding exists.

`compilestats` imports the script with actions stubbed out (no stage
executes, nothing compiles), plans each action, and prints per-stage op
counts, predicted compile seconds where the platform has a cost curve,
and which stages the content-addressed compile cache would dedup into one
executable (utils/compilestats.py).
"""

from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tuplex_tpu",
        description="tuplex_tpu — TPU-native data-processing framework")
    sub = parser.add_subparsers(dest="cmd")
    sub.add_parser("shell", help="interactive shell (the default)")
    lint = sub.add_parser(
        "lint", help="static-analyze the UDFs of a pipeline script")
    lint.add_argument("script", help="path to a python pipeline script")
    lint.add_argument("--strict", action="store_true",
                      help="exit non-zero on any fallback finding or "
                           "dead resolver")
    cs = sub.add_parser(
        "compilestats",
        help="per-stage op counts, predicted compile seconds, dedup groups")
    cs.add_argument("script", help="path to a python pipeline script")
    cs.add_argument("--platform", default=None,
                    help="platform of the compile-cost curve (default: jax "
                         "backend)")
    ex = sub.add_parser(
        "excstats",
        help="exception-plane readout from the job history: per-stage x "
             "code fallback counts vs the plan-time inventory, resolve-"
             "tier mix, drift score + respecialize signal, sampled "
             "deviant rows (runtime/excprof)")
    ex.add_argument("--log-dir", default=".",
                    help="directory holding tuplex_history.jsonl "
                         "(tuplex.logDir; default .)")
    ex.add_argument("--job", default=None,
                    help="only jobs whose id starts with this prefix")
    ws = sub.add_parser(
        "whyslow",
        help="latency-budget readout from the job history: per-job "
             "critical-path bucket vector vs the tenant's EWMA baseline, "
             "slow-job blame, SLO verdicts (runtime/critpath)")
    ws.add_argument("job", nargs="?", default=None,
                    help="only jobs whose id starts with this prefix")
    ws.add_argument("--log-dir", default=".",
                    help="directory holding tuplex_history.jsonl "
                         "(tuplex.logDir; default .)")
    ws.add_argument("--glossary", action="store_true",
                    help="print the bucket glossary and exit")
    tr = sub.add_parser(
        "trace",
        help="replay the job history as Chrome trace-event JSON "
             "(open in Perfetto / chrome://tracing)")
    tr.add_argument("out", help="output .json path")
    tr.add_argument("--log-dir", default=".",
                    help="directory holding tuplex_history.jsonl "
                         "(tuplex.logDir; default .)")
    sv = sub.add_parser(
        "serve",
        help="run the multi-tenant job service on this process's warm "
             "device (scratch-dir submit/poll/fetch protocol; stop by "
             "touching <root>/STOP)")
    sv.add_argument("root", help="service root directory (clients drop "
                                 "requests under <root>/inbox/)")
    sv.add_argument("--conf", default=None,
                    help="options file (YAML/JSON) merged over defaults")
    sv.add_argument("--slots", type=int, default=None,
                    help="scheduler slots (tuplex.serve.slots)")
    sv.add_argument("--queue-depth", type=int, default=None,
                    help="admission queue depth (tuplex.serve.queueDepth)")
    sv.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus /metrics + /healthz on this "
                         "loopback port (0 = pick a free one, announced "
                         "in <root>/metrics.port; default off — the "
                         "periodic <root>/metrics.prom drop happens "
                         "regardless; tuplex.serve.metricsPort)")
    sv.add_argument("--retry-count", type=int, default=None,
                    help="job-level retries for transient failures, and "
                         "the crash-requeue budget for jobs recovered "
                         "from a previous process's journal "
                         "(tuplex.serve.retryCount)")
    sv.add_argument("--retry-backoff", type=float, default=None,
                    help="base seconds of the exponential retry backoff "
                         "(tuplex.serve.retryBackoffS)")
    sub.add_parser("version", help="print the package version")
    args = parser.parse_args(argv)

    if args.cmd == "version":
        from . import __version__

        print(__version__)
        return 0
    if args.cmd == "lint":
        from .compiler.analyzer import lint_file

        try:
            rc = lint_file(args.script, strict=args.strict)
        except OSError as e:
            print(f"lint: {e}", file=sys.stderr)
            return 2
        # jaxpr findings section (compiler/graphlint): unlike the UDF
        # lint above this must IMPORT the script (actions stubbed, same
        # harness as compilestats — nothing executes or compiles); an
        # unimportable script degrades to the syntactic report alone
        try:
            from .utils.compilestats import lint_jaxprs

            _, n_wedge = lint_jaxprs(args.script)
            if args.strict and n_wedge:
                rc = rc or 1
        except Exception as e:
            print(f"lint: jaxpr section skipped "
                  f"({type(e).__name__}: {e})", file=sys.stderr)
        return rc
    if args.cmd == "compilestats":
        from .utils.compilestats import main as cs_main

        try:
            return cs_main(args.script, platform=args.platform)
        except OSError as e:
            print(f"compilestats: {e}", file=sys.stderr)
            return 2
    if args.cmd == "serve":
        from .core.options import ContextOptions
        from .serve.client import service_loop

        opts = ContextOptions()
        if args.conf:
            opts.update(args.conf)
        if args.slots is not None:
            opts.set("tuplex.serve.slots", args.slots)
        if args.queue_depth is not None:
            opts.set("tuplex.serve.queueDepth", args.queue_depth)
        if args.metrics_port is not None:
            opts.set("tuplex.serve.metricsPort", args.metrics_port)
        if args.retry_count is not None:
            opts.set("tuplex.serve.retryCount", args.retry_count)
        if args.retry_backoff is not None:
            opts.set("tuplex.serve.retryBackoffS", args.retry_backoff)
        try:
            n = service_loop(args.root, opts)
        except KeyboardInterrupt:
            print("serve: interrupted", file=sys.stderr)
            return 130
        print(f"serve: {n} job(s) served")
        return 0
    if args.cmd == "excstats":
        from .utils.excstats import main as ex_main

        try:
            return ex_main(args.log_dir, job=args.job)
        except OSError as e:
            print(f"excstats: {e}", file=sys.stderr)
            return 2
    if args.cmd == "whyslow":
        from .utils.whyslow import glossary, main as ws_main

        if args.glossary:
            glossary()
            return 0
        try:
            return ws_main(args.log_dir, job=args.job)
        except OSError as e:
            print(f"whyslow: {e}", file=sys.stderr)
            return 2
    if args.cmd == "trace":
        from .history.recorder import history_to_chrome

        try:
            out = history_to_chrome(args.log_dir, args.out)
        except OSError as e:
            print(f"trace: {e}", file=sys.stderr)
            return 2
        print(f"wrote {out} — open at ui.perfetto.dev or chrome://tracing")
        return 0
    # bare invocation or explicit `shell`
    from .utils.repl import interactive_shell

    interactive_shell()
    return 0


if __name__ == "__main__":
    sys.exit(main())
