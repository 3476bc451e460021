"""Benchmark driver: a thin CLI over the campaign runner.

Every benchmark is a stage of a declared campaign (``benchmarks/campaigns.py``);
this module only selects a campaign and hands it to ``repro.campaign.runner``:

    PYTHONPATH=src python -m benchmarks.run --campaign engine-smoke
    PYTHONPATH=src python -m benchmarks.run --campaign serve-smoke --resume
    PYTHONPATH=src python -m benchmarks.run --campaign all --only kernels
    PYTHONPATH=src python -m benchmarks.run --list

``--resume`` skips runs whose record already exists under
``campaigns/<name>/<run_key>/`` and re-merges their persisted records, so a
killed campaign picks up where it stopped and the merged document is
byte-identical to an uninterrupted one. Legacy flags (``--engine-smoke``,
``--serve-smoke``, ``--skip-lm``) map onto campaigns.
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--campaign", default=None,
                    help="campaign name (see --list); default: all")
    ap.add_argument("--resume", action="store_true",
                    help="skip runs already completed on disk")
    ap.add_argument("--only", default=None, metavar="STAGE",
                    help="run one stage (plus its dependency closure; "
                         "completed dep runs are skipped)")
    ap.add_argument("--list", action="store_true", dest="list_campaigns",
                    help="list registered campaigns and their stages")
    ap.add_argument("--out", default="BENCH_engine.json",
                    help="results store path (default: BENCH_engine.json)")
    ap.add_argument("--state-root", default="campaigns",
                    help="per-run state directory root (default: campaigns)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the "
                         "campaign (one span per run on "
                         "'<stage>/<display>' tracks; same as "
                         "REPRO_TRACE=PATH)")
    # legacy aliases, kept so existing invocations keep working
    ap.add_argument("--engine-smoke", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--serve-smoke", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--skip-lm", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    compile_cache.enable()
    from benchmarks import campaigns
    from repro.campaign.runner import Runner
    from repro.campaign.spec import CAMPAIGNS
    from repro.campaign.store import ResultStore

    if args.list_campaigns:
        for name in sorted(CAMPAIGNS):
            camp = CAMPAIGNS[name]
            n_runs = sum(len(s.runs) for s in camp.stages)
            print(f"{name}: {n_runs} runs")
            for line in Runner(camp).describe():
                print(f"  {line}")
        return 0

    name = args.campaign
    if name is None:
        if args.engine_smoke:
            name = "engine-smoke"
        elif args.serve_smoke:
            name = "serve-smoke"
        else:
            name = "all"

    campaign = campaigns.get(name)
    if name == "all" and args.skip_lm:
        drop = {"lm-baseline", "lm-grid"}
        campaign = campaign.subset(
            [s.name for s in campaign.stages if s.name not in drop])

    if args.trace:
        from repro.obs import trace as obs_trace
        obs_trace.enable(args.trace)
    t0 = time.time()
    summary = Runner(campaign, store=ResultStore(args.out),
                     state_root=args.state_root, resume=args.resume,
                     only=args.only).run()
    print(f"# benchmarks done in {time.time() - t0:.0f}s")
    if args.trace:
        obs_trace.save()
    return summary.exit_code


if __name__ == "__main__":
    sys.exit(main())
