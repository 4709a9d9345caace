"""graft-search CLI: enumerate + statically price program candidates and
commit the Pareto frontier.

Runs the declared candidate spaces (deepspeed_tpu/analysis/search.py) —
remat policy at block boundaries, LM-head loss/grad chunk sizes, QKV /
attention-output projection fusion, optimizer-fusion variants — through
the REAL engine knobs (the "program" config block +
``optimizer.legacy_fusion``), prices every candidate from its traced
jaxpr alone (peak transient bytes, analytic wire bytes, a trip-count-
weighted dot-FLOP proxy; no lowering, no compilation), and prints the
frontier with full dominated-candidate provenance. The judged 350M space
(26 candidates) prices in a few minutes on the 1-core CPU rig.

Default mode verifies against the committed
``analysis_results/search_pareto.json`` (the R014 contract: exit 1 on
candidate-set drift, winner price drift >5%, or a dominated committed
winner); ``--update`` banks the current results instead (merge semantics
— a single-space update never drops another space's entry).

Usage:
  python tools/graft_search.py                          # price + verify all spaces
  python tools/graft_search.py --spaces gpt2_test_gate  # subset
  python tools/graft_search.py --update                 # bank the frontier
"""

import argparse
import json
import os
import sys
import time

# CPU trace-only by design, same bootstrap as graft_lint (prices must
# never depend on an accelerator being attached, or on its device count —
# spaces pin a 1-device topology regardless)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DEFAULT_ARTIFACT = os.path.join(REPO, "analysis_results", "search_pareto.json")


def _fmt_bytes(n):
    return f"{n / 2**20:8.1f}M"


def _print_space(name, result, quiet=False):
    cands = result["candidates"]
    frontier = set(result["frontier"])
    calibrated = "predicted_seconds" in result["objectives"]
    print(f"space {name}: {len(cands)} candidates, "
          f"{len(frontier)} on the frontier "
          f"(objectives: {', '.join(result['objectives'])})")
    if quiet:
        return
    sec_hdr = f" {'pred-sec':>9s}" if calibrated else ""
    header = (f"  {'':1s} {'candidate':58s} {'transient':>9s} {'comms':>9s} "
              f"{'dot-TFLOP':>9s}{sec_hdr}")
    print(header)
    for cid, entry in cands.items():
        m = entry["metrics"]
        mark = "*" if cid in frontier else " "
        dom = ("" if cid in frontier
               else f"  << {entry.get('dominated_by', ['?'])[0]}")
        sec = (f" {m['predicted_seconds']:9.4f}" if calibrated else "")
        print(f"  {mark} {cid:58s} {_fmt_bytes(m['peak_transient_bytes'])} "
              f"{_fmt_bytes(m['bytes_moved'])} {m['flops_proxy'] / 1e12:9.3f}"
              f"{sec}{dom}")
    if calibrated and result.get("seconds_rank"):
        key = (result.get("calibration") or {}).get("key")
        print(f"  frontier in calibrated seconds ({key}):")
        for i, cid in enumerate(result["seconds_rank"]):
            sec = cands[cid]["metrics"]["predicted_seconds"]
            print(f"    #{i + 1} {cid} ({sec:.4f}s)")


def run(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="graft_search", description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--spaces", default=None,
                    help="comma list of space names (default: all declared)")
    ap.add_argument("--update", action="store_true",
                    help="bank the current results into the committed artifact "
                         "(merge semantics) instead of verifying against it")
    ap.add_argument("--artifact", default=DEFAULT_ARTIFACT)
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)

    import jax
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        jax.config.update("jax_platforms", "cpu")

    from deepspeed_tpu import analysis

    names = (args.spaces.split(",") if args.spaces else list(analysis.SPACES))
    unknown = [n for n in names if n not in analysis.SPACES]
    if unknown:
        print(f"graft-search: unknown space(s) {unknown}; "
              f"valid: {sorted(analysis.SPACES)}", file=sys.stderr)
        return 2

    # the committed calibration (if banked) adds the predicted_seconds
    # objective + seconds_rank to every priced space
    calibration = analysis.load_calibration()

    results = {}
    for name in names:
        t0 = time.time()
        log = None if args.quiet else (lambda s: print(f"  {s}", flush=True))
        if not args.quiet:
            n = len(analysis.enumerate_candidates(analysis.SPACES[name]))
            print(f"# pricing {name} ({n} candidates)...", flush=True)
        results[name] = analysis.run_space(name, log=log, calibration=calibration)
        if not args.quiet:
            print(f"# {name} priced in {time.time() - t0:.1f}s", flush=True)
        _print_space(name, results[name], quiet=args.quiet)

    if args.update:
        prior = analysis.load_search_artifact(args.artifact)
        artifact = analysis.search_artifact_from(results, prior=prior)
        os.makedirs(os.path.dirname(args.artifact), exist_ok=True)
        with open(args.artifact, "w") as fh:
            json.dump(artifact, fh, indent=2, sort_keys=False)
            fh.write("\n")
        print(f"search artifact updated: {os.path.relpath(args.artifact, REPO)} "
              f"({len(results)} space(s) refreshed, "
              f"{len(artifact['spaces'])} total)")
        return 0

    # verify mode: the R014 contract against the committed artifact
    artifact = analysis.load_search_artifact(args.artifact)
    findings = analysis.r014_search_frontier(artifact, results)
    errors = [f for f in findings if f.severity == analysis.ERROR]
    for f in findings:
        loc = f" @ {f.location}" if f.location else ""
        print(f"  {f.severity:5s} {f.rule} [{f.scenario}]{loc}: {f.message}",
              file=sys.stderr if f.severity == analysis.ERROR else sys.stdout)
    if errors:
        print(f"graft-search: {len(errors)} ERROR finding(s) vs "
              f"{os.path.relpath(args.artifact, REPO)} — fix the drift or bank "
              f"with --update", file=sys.stderr)
        return 1
    print("graft-search: committed frontier verified clean")
    return 0


if __name__ == "__main__":
    sys.exit(run())
