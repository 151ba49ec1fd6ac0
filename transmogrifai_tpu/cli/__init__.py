"""Developer CLI (reference ``cli/`` module): project generation + shell.

``python -m transmogrifai_tpu.cli gen --input data.csv --id id
--response label ProjectName`` emits a runnable AutoML project.
``python -m transmogrifai_tpu.cli shell`` opens the preloaded REPL
(reference ``repl/`` module analog).
"""

from transmogrifai_tpu.cli.gen import (
    ProblemKind, detect_problem_kind, generate_project,
)

__all__ = ["ProblemKind", "detect_problem_kind", "generate_project", "main"]


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser("transmogrifai_tpu")
    sub = ap.add_subparsers(dest="command", required=True)
    gen = sub.add_parser("gen", help="generate a project from a dataset")
    gen.add_argument("name", help="project name (output directory name)")
    gen.add_argument("--input", required=True,
                     help="CSV or parquet dataset path")
    gen.add_argument("--id", required=True, dest="id_col",
                     help="id column name")
    gen.add_argument("--response", required=True, help="response column")
    gen.add_argument("--schema", default=None,
                     help="optional Avro .avsc schema path")
    gen.add_argument("--output", default=".", help="output directory")
    gen.add_argument("--overwrite", action="store_true")
    sub.add_parser("shell", help="interactive shell with the framework "
                                 "preloaded (reference repl analog)")
    from transmogrifai_tpu.cli.continuous import (
        add_continuous_args, run_continuous,
    )
    from transmogrifai_tpu.cli.profile import add_profile_args, run_profile
    from transmogrifai_tpu.cli.scaleout import (
        add_scaleout_args, run_scaleout,
    )
    from transmogrifai_tpu.cli.explain import (
        add_explain_args, run_explain,
    )
    from transmogrifai_tpu.cli.serve import add_serve_args, run_serve
    from transmogrifai_tpu.cli.slo import add_slo_args, run_slo
    add_serve_args(sub.add_parser(
        "serve", help="online micro-batched scoring over a saved model "
                      "(jsonl/csv in, jsonl scores out); "
                      "--explain-top-k adds per-request LOCO "
                      "attributions"))
    add_explain_args(sub.add_parser(
        "explain", help="batch explainability: ModelInsights report + "
                        "per-row LOCO insight maps over a saved model"))
    add_scaleout_args(sub.add_parser(
        "scaleout", help="multi-process serving scale-out: consistent-"
                         "hash router + N replica fleet workers + "
                         "heartbeat supervision + autoscaling"))
    add_continuous_args(sub.add_parser(
        "continuous", help="closed-loop daemon: stream ingest + drift "
                           "detection + checkpoint-resumed retrain + "
                           "zero-downtime hot-swap"))
    add_profile_args(sub.add_parser(
        "profile", help="score a dataset under full tracing; emit a "
                        "Perfetto/chrome://tracing JSON + slowest-stages "
                        "table"))
    add_slo_args(sub.add_parser(
        "slo", help="SLO burn-rate status of a running serve/continuous "
                    "daemon (scrapes its /healthz + /metrics)"))
    from transmogrifai_tpu.cli.autopsy import add_autopsy_args, run_autopsy
    add_autopsy_args(sub.add_parser(
        "autopsy", help="pretty-print an incident dump / device-stall "
                        "autopsy (stall site, thread stacks, HBM "
                        "holders, pending dispatches, event tail)"))
    args = ap.parse_args(argv)

    if args.command in ("shell", "serve", "explain", "continuous",
                        "profile"):
        # the commands that compile in THIS process (scaleout's workers
        # enable the cache themselves; gen/slo/autopsy never touch jax)
        from transmogrifai_tpu.utils.compile_cache import (
            enable_compile_cache,
        )
        enable_compile_cache()
    if args.command == "shell":
        from transmogrifai_tpu.cli.shell import run_shell
        return run_shell()
    if args.command == "serve":
        return run_serve(args)
    if args.command == "explain":
        return run_explain(args)
    if args.command == "scaleout":
        return run_scaleout(args)
    if args.command == "continuous":
        return run_continuous(args)
    if args.command == "profile":
        return run_profile(args)
    if args.command == "slo":
        return run_slo(args)
    if args.command == "autopsy":
        return run_autopsy(args)
    if args.command == "gen":
        path = generate_project(
            name=args.name, input_path=args.input, id_col=args.id_col,
            response_col=args.response, output_dir=args.output,
            avro_schema_path=args.schema, overwrite=args.overwrite)
        print(f"Generated project at {path}")
        return 0
    return 1
