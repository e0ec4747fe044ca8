"""Benchmark harness — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run [--only fig7,table1] [--fast]
                                          [--trace [--trace-dir DIR]]

``--trace`` installs the repro.obs flight recorder around every module: each
table/figure writes ``DIR/<name>.jsonl`` (structured span/event records —
the input of ``python -m repro.obs.audit``) plus ``DIR/<name>.timeline.txt``
(the text Gantt of the file's last run).  Tracing rides the module-global
``obs.trace.install`` hook, so the modules themselves stay trace-agnostic.

``--profile`` installs a fresh :class:`repro.obs.profile.SimProfiler` around
each module the same way and prints per-module ``<name>.profile.*`` rows
(per-event-kind handler cost, heap/metrics section cost) after the module's
own rows — where each table's wall-clock actually goes.
"""
import argparse
import os
import sys
import traceback

MODULES = [
    ("fig4", "benchmarks.fig4_scaling"),
    ("fig5", "benchmarks.fig5_rescale_overhead"),
    ("fig6", "benchmarks.fig6_timeline"),
    ("fig7", "benchmarks.fig7_submission_gap"),
    ("fig8", "benchmarks.fig8_rescale_gap"),
    ("table1", "benchmarks.table1_policies"),
    ("table2", "benchmarks.table2_cloud_cost"),
    ("table3", "benchmarks.table3_placement"),
    ("table4", "benchmarks.table4_traces"),
    ("table5", "benchmarks.table5_zones"),
    ("table6", "benchmarks.table6_bidding"),
    ("roofline", "benchmarks.roofline"),
]


def _run_traced(name, fn, trace_dir: str) -> None:
    from repro.obs.timeline import render_last_run
    from repro.obs.trace import Tracer, install
    os.makedirs(trace_dir, exist_ok=True)
    path = os.path.join(trace_dir, f"{name}.jsonl")
    with Tracer(path) as tracer, install(tracer):
        fn()
    records = Tracer.load(path)
    if records:
        art = os.path.join(trace_dir, f"{name}.timeline.txt")
        with open(art, "w") as fh:
            fh.write(render_last_run(records) + "\n")


def _emit_profile(name, prof) -> None:
    from benchmarks.common import emit, kv
    report = prof.report()
    for kind, row in report["events"].items():
        emit(f"{name}.profile.event.{kind}", row["mean_us"],
             kv(count=row["count"], total_s=row["total_s"]))
    for sec, row in report["sections"].items():
        emit(f"{name}.profile.section.{sec}", row["mean_us"],
             kv(count=row["count"], total_s=row["total_s"]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--fast", action="store_true",
                    help="fewer seeds for the simulation sweeps; fig5 skips "
                         "its live-subprocess section (sim+model only)")
    ap.add_argument("--trace", action="store_true",
                    help="record per-module trace JSONL + timeline artifacts")
    ap.add_argument("--trace-dir", default="trace-artifacts")
    ap.add_argument("--profile", action="store_true",
                    help="self-profile each module's simulator event loop "
                         "and print <name>.profile.* rows")
    args = ap.parse_args()
    only = {s.strip() for s in args.only.split(",") if s.strip()}

    print("name,us_per_call,derived")
    failed = []
    for name, module in MODULES:
        if only and name not in only:
            continue
        try:
            import importlib
            mod = importlib.import_module(module)
            if args.fast and name in ("fig7", "fig8"):
                fn = lambda: mod.run(seeds=range(3))  # noqa: E731
            elif args.fast and name == "fig5":
                fn = lambda: mod.run(sim_only=True)  # noqa: E731
            else:
                fn = mod.run
            if args.profile:
                from repro.obs.profile import SimProfiler, install_profiler
                prof = SimProfiler()
                inner = fn

                def fn(inner=inner, prof=prof):
                    with install_profiler(prof):
                        inner()
            if args.trace:
                _run_traced(name, fn, args.trace_dir)
            else:
                fn()
            if args.profile:
                _emit_profile(name, prof)
        except Exception as e:
            # keep going so one broken table does not hide the others' rows,
            # but the run as a whole fails
            failed.append(name)
            print(f"{name}.ERROR,0.0,{e!r}"[:400].replace("\n", " "))
            traceback.print_exc(file=sys.stderr)
    if failed:
        sys.exit(f"benchmark modules failed: {','.join(failed)}")


if __name__ == "__main__":
    main()
