"""Benchmark driver — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only fig6,fig9]

Prints ``name,us_per_call,derived`` CSV rows.  Default is the quick profile
(CPU-scaled dataset sizes, same generative models and worker ratios as the
paper's experiments; see repro/configs/paper.py).

Modules listed in ``PERSIST_JSON`` additionally write their rows (plus
backend / jax-version / git-sha / config-hash metadata) to a
``BENCH_*.json`` file at the repo root — the persistent perf trajectory CI
archives per push, so kernel regressions have a baseline to diff against
(see kernels/README.md).  Before overwriting a prior BENCH file the driver
prints a report-only noise-aware diff against it (``repro.obs.diff``), and
``--store`` appends the fresh payload to a cross-run JSONL warehouse
(``repro.obs.store``) for history-aware gating.

Trace/report artifacts default into the git-ignored ``artifacts/``
directory: a bare ``--trace-out run.perfetto.json`` lands at
``artifacts/run.perfetto.json`` (explicit directories are honored).
"""
from __future__ import annotations

import argparse
import inspect
import json
import pathlib
import sys
import time

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
ARTIFACTS = REPO_ROOT / "artifacts"

# module -> repo-root JSON file persisting its rows as a perf baseline.
# Two modules may share one file (fleet_bench + scheduler_bench both feed
# BENCH_fleet.json): within one invocation their rows are merged by name
# (later module wins on collision) so the second write doesn't clobber
# the first; ``--store`` still appends each module's own payload
# separately, keyed by its module name.
PERSIST_JSON = {
    "fleet_bench": "BENCH_fleet.json",
    "kernels_bench": "BENCH_kernels.json",
    "scheduler_bench": "BENCH_fleet.json",
    "tenancy_bench": "BENCH_fleet.json",
}

MODULES = [
    "fig1_stragglers",
    "fig6_logistic_synthetic",
    "fig7_epsilon",
    "fig8_small_datasets",
    "fig9_softmax",
    "fig10_coded_vs_spec",
    "fig11_first_order",
    "fig12_serverful",
    "fleet_bench",
    "kernels_bench",
    "roofline",
    "scheduler_bench",
    "tenancy_bench",
]


def _artifact_path(name: str) -> pathlib.Path:
    """Bare filenames land in the git-ignored ``artifacts/`` directory;
    paths with an explicit directory component are honored as-is."""
    p = pathlib.Path(name)
    if p.parent == pathlib.Path("."):
        p = ARTIFACTS / p
    p.parent.mkdir(parents=True, exist_ok=True)
    return p


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="larger problem sizes (slower)")
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated module prefixes")
    ap.add_argument("--trace-out", type=str, default=None,
                    help="write a Perfetto trace of an instrumented run "
                         "here (modules whose run() accepts trace_out; "
                         "a .jsonl sibling feeds make_report --trace; "
                         "bare filenames go under artifacts/)")
    ap.add_argument("--store", type=str, default=None,
                    help="append each persisted BENCH payload to this "
                         "cross-run JSONL store (repro.obs.store)")
    ap.add_argument("--console-out", type=str, default=None,
                    help="render the --trace-out run's telemetry (its "
                         ".jsonl sibling) plus the written BENCH rows "
                         "into a self-contained HTML fleet console "
                         "(repro.obs.console); bare filenames go under "
                         "artifacts/")
    args = ap.parse_args(argv)

    if args.console_out and not args.trace_out:
        ap.error("--console-out needs --trace-out (the console renders "
                 "the trace's .jsonl sibling)")

    mods = MODULES
    if args.only:
        keys = args.only.split(",")
        mods = [m for m in MODULES if any(m.startswith(k) for k in keys)]

    print("name,us_per_call,derived")
    failures = 0
    written: dict = {}   # BENCH file -> payload written this invocation
    for mod_name in mods:
        mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
        t0 = time.time()
        kwargs = {}
        if args.trace_out and \
                "trace_out" in inspect.signature(mod.run).parameters:
            kwargs["trace_out"] = str(_artifact_path(args.trace_out))
        try:
            rows = mod.run(quick=not args.full, **kwargs)
        except Exception as e:   # noqa: BLE001 — surface and continue
            print(f"{mod_name},NaN,ERROR:{type(e).__name__}:{e}",
                  file=sys.stderr)
            failures += 1
            continue
        for r in rows:
            print(f"{r['name']},{r['us']:.1f},{r['derived']}")
        if mod_name in PERSIST_JSON:
            import jax

            from repro.obs import diff as obs_diff
            from repro.obs import store as obs_store

            # Every persisted row carries a ``path`` field naming what
            # actually executed (such as ref | pallas) so the perf
            # trajectory is attributable; backfill
            # rows from modules that predate the field.
            for r in rows:
                r.setdefault("path", "unknown")
            payload = {
                "meta": {
                    "module": mod_name,
                    "profile": "full" if args.full else "quick",
                    "backend": jax.default_backend(),
                    "jax_version": jax.__version__,
                    "git_sha": obs_store.git_sha(REPO_ROOT),
                    "config_hash": obs_store.config_hash(
                        {"module": mod_name,
                         "profile": "full" if args.full else "quick"}),
                    "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime()),
                },
                "rows": rows,
            }
            file_rel = PERSIST_JSON[mod_name]
            file_payload = payload
            prior_merge = written.get(file_rel)
            path = REPO_ROOT / file_rel
            if (prior_merge is None and path.exists()
                    and sum(f == file_rel
                            for f in PERSIST_JSON.values()) > 1):
                # Shared BENCH file, first writer this invocation: seed
                # the merge from the rows already on disk so a partial
                # run (e.g. --only tenancy) keeps the other modules'
                # rows instead of clobbering them.  Renamed/removed rows
                # of THIS module are replaced wholesale by name below;
                # stale rows only linger if a module itself is dropped.
                try:
                    prior_merge = json.loads(path.read_text())
                except Exception:   # noqa: BLE001 — corrupt prior file
                    prior_merge = None
            if prior_merge is not None:
                # Another module already wrote this file (this invocation
                # or a prior one): merge by row name instead of
                # clobbering; meta.module tracks every contributor.
                names = {r["name"] for r in rows}
                prior_mods = prior_merge["meta"].get(
                    "module", "unknown").split("+")
                merged_mods = "+".join(
                    [m for m in prior_mods if m != mod_name] + [mod_name])
                file_payload = {
                    "meta": {**payload["meta"], "module": merged_mods},
                    "rows": [r for r in prior_merge["rows"]
                             if r["name"] not in names] + rows,
                }
            if path.exists():
                # Report-only noise-aware diff vs the file being replaced
                # (CI gates via `repro.obs.diff --gate`; here we only warn).
                try:
                    prior = json.loads(path.read_text())
                    rep = obs_diff.diff_bench(prior, file_payload)
                    print(f"# diff vs previous {path.name}: {rep.summary()}",
                          file=sys.stderr)
                    for row in rep.regressions:
                        print(f"#   regression: {row.name}: {row.detail}",
                              file=sys.stderr)
                except Exception as e:  # noqa: BLE001 — diff is best-effort
                    print(f"# diff vs previous {path.name} failed: {e}",
                          file=sys.stderr)
            path.write_text(json.dumps(file_payload, indent=1) + "\n")
            written[file_rel] = file_payload
            print(f"# wrote {path}", file=sys.stderr)
            if args.store:
                store = obs_store.Store(_artifact_path(args.store))
                store.append(obs_store.bench_record(payload))
                print(f"# appended {mod_name} to {store.path}",
                      file=sys.stderr)
        print(f"# {mod_name} done in {time.time()-t0:.1f}s", file=sys.stderr)

    if args.console_out:
        from repro.obs import console as obs_console
        from repro.obs import export as obs_export

        trace_path = str(_artifact_path(args.trace_out))
        jsonl = (trace_path[:-5] if trace_path.endswith(".json")
                 else trace_path) + ".jsonl"
        try:
            rows = obs_export.load_jsonl(jsonl)
        except OSError as e:
            print(f"# console: no trace JSONL at {jsonl} ({e})",
                  file=sys.stderr)
            rows = []
        bench_rows = [r for payload in written.values()
                      for r in payload["rows"]]
        out = _artifact_path(args.console_out)
        obs_console.write_console(out, rows, bench=bench_rows or None,
                                  title="fleet console")
        print(f"# wrote console {out}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
