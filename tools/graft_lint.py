"""graft-lint CLI: run the static-analysis scenario matrix and gate.

Traces the representative program matrix (deepspeed_tpu/analysis/
scenarios.py) on CPU — no compilation, <2 min — runs every registered
rule (R001..R008, deepspeed_tpu/analysis/rules.py + source_rules.py),
writes ``analysis_results/lint_<sig>.json``, and exits non-zero when a
NEW unwaived ERROR appears relative to the committed baseline
(``analysis_results/baseline.json``). A seeded regression — e.g. the MoE
scenarios built with the dense dispatch (a test patches
``analysis.scenarios.SCENARIO_CONFIG``) — must fail this gate; that is
the acceptance check.

``--cost`` adds the graft-audit pass (deepspeed_tpu/analysis/cost.py):
per program, a jaxpr-liveness static memory estimate + the three-layer
collective inventory (jaxpr / stablehlo / compiled post-SPMD, with the
backend's own cost/memory analysis as cross-check), rules R009-R012,
and the R013 ratchet against ``analysis_results/cost_baseline.json``
(peak bytes + wire bytes + collective counts per scenario; growth past
tolerance gates). ``--cost --update-baseline`` banks the current costs
(merge semantics — subset runs refresh only their own entries).

Full-matrix ``--cost`` runs additionally re-price every ``gate=True``
graft-search space (deepspeed_tpu/analysis/search.py) and ratchet it
against the committed ``analysis_results/search_pareto.json`` (rule
R014): a drifted candidate set, a committed Pareto winner whose static
price moves >5%, or a winner that is now dominated fails the gate.
``--search`` forces the pass on scenario subsets; ``--no-search`` skips
it. Bank frontier changes with ``tools/graft_search.py --update``,
never here. The same full-matrix runs judge the committed measured-mode
calibration with rule R016 (deepspeed_tpu/analysis/calibrate.py):
perturbed coefficients, a stale jax signature, or a stale
``predicted_seconds`` frontier re-rank vs
``analysis_results/cost_calibration.json`` fail the gate; bank with
``tools/graft_calibrate.py fit --update``.
Seeded cost regressions: the dense MoE route patched into the scenarios
(R009 route-signature drift + the dense-einsum memory delta) and
``DS_PIPE_ACT_BUDGET_MB=2`` on ``pipe_chunked_step`` (R010: the chunked
schedule cannot fit the 1F1B activation budget the ``pipe_1f1b_step``
scenario passes).

Usage:
  python tools/graft_lint.py                         # full matrix + AST, gate vs baseline
  python tools/graft_lint.py --cost                  # + memory/comms cost pass & ratchet
  python tools/graft_lint.py --scenarios moe_top1_route,moe_top2_route
  python tools/graft_lint.py --update-baseline       # acknowledge current ERRORs
  python tools/graft_lint.py --no-ast | --ast-only
  python tools/graft_lint.py --list                  # rule + scenario inventory

Waivers: ``analysis_results/waivers.json`` — a list of
``{"rule": "R003", "scenario": "train_batch*", "match": "...", "reason": "..."}``
entries — plus inline ``# graft-lint: waive R008 <reason>`` comments for
the AST rule. Waived findings report but never gate; waivers that match
NO current finding are reported as stale (WARN) so dead entries get
pruned.

``GRAFT_LINT_DEVICES=16`` raises the forced host-device count so the
16-virtual-device composition scenario can attempt its trace.
"""

import argparse
import ast
import json
import os
import sys

# CPU + a multi-device host mesh BEFORE jax initializes: the matrix
# includes multi-device programs (same bootstrap as tests/conftest.py).
# GRAFT_LINT_DEVICES overrides the count for the 16-device composition.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
_n_dev = os.environ.get("GRAFT_LINT_DEVICES", "8")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count={_n_dev}").strip()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: source roots the AST rule sweeps
AST_ROOTS = ("deepspeed_tpu", "tools", "envutil.py")


def collect_source_files(repo=REPO, roots=AST_ROOTS):
    files = []
    for root in roots:
        path = os.path.join(repo, root)
        if os.path.isfile(path):
            paths = [path]
        else:
            paths = [os.path.join(dp, f) for dp, _, fs in os.walk(path)
                     for f in fs if f.endswith(".py")]
        for p in sorted(paths):
            rel = os.path.relpath(p, repo)
            try:
                with open(p) as fh:
                    src = fh.read()
                files.append((rel, src, ast.parse(src, filename=rel)))
            except SyntaxError as e:  # a broken file is its own finding
                print(f"graft-lint: cannot parse {rel}: {e}", file=sys.stderr)
    return files


def run(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="graft_lint", description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--scenarios", default=None,
                    help="comma list of scenario names (default: all)")
    ap.add_argument("--baseline", default=os.path.join(REPO, "analysis_results", "baseline.json"))
    ap.add_argument("--waivers", default=os.path.join(REPO, "analysis_results", "waivers.json"))
    ap.add_argument("--out", default=os.path.join(REPO, "analysis_results"))
    ap.add_argument("--update-baseline", action="store_true",
                    help="acknowledge every current ERROR into the baseline and exit 0 "
                         "(with --cost: also bank current costs into the cost baseline)")
    ap.add_argument("--cost", action="store_true",
                    help="run the graft-audit cost pass: static memory + collective "
                         "inventory, rules R009-R013, ratchet vs the cost baseline")
    ap.add_argument("--cost-baseline",
                    default=os.path.join(REPO, "analysis_results", "cost_baseline.json"))
    ap.add_argument("--no-compile", action="store_true",
                    help="with --cost: skip compiling programs (no post-SPMD "
                         "collective layer / backend cross-check; trace-only)")
    ap.add_argument("--no-ast", action="store_true", help="skip the source AST pass")
    ap.add_argument("--ast-only", action="store_true", help="run ONLY the source AST pass")
    ap.add_argument("--search", action="store_true",
                    help="with --cost: run the R014 search-frontier gate even on a "
                         "--scenarios subset (default: full-matrix runs only)")
    ap.add_argument("--no-search", action="store_true",
                    help="with --cost: skip the R014 search-frontier gate")
    ap.add_argument("--search-pareto",
                    default=os.path.join(REPO, "analysis_results", "search_pareto.json"))
    ap.add_argument("--cost-calibration",
                    default=os.path.join(REPO, "analysis_results",
                                         "cost_calibration.json"))
    ap.add_argument("--list", action="store_true", help="print rules + scenarios and exit")
    ap.add_argument("--rules-md", action="store_true",
                    help="print the README rule table generated from the rule "
                         "registry and exit (keeps docs from drifting behind new rules)")
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from deepspeed_tpu import analysis
    from deepspeed_tpu.analysis import scenarios as scen

    if args.rules_md:
        print(analysis.rules_markdown())
        return 0

    if args.list:
        # generated from the registry — a newly registered rule (e.g. R014)
        # appears here with zero doc edits; same source as --rules-md
        print("rules:")
        for r in sorted(analysis.RULES.values(), key=lambda r: r.id):
            print(f"  {r.id}  [{r.severity:5s} {r.layer:5s}] {r.title}")
        print("scenarios:")
        for name in scen.SCENARIOS:
            print(f"  {name}")
        print("search spaces (analysis/search.py; R014 gates gate=True spaces):")
        for name, space in analysis.SPACES.items():
            n = len(analysis.enumerate_candidates(space))
            print(f"  {name}  [{n} candidates{' gate' if space.gate else ''}]")
        print("cost metrics (per program, --cost):")
        print("  peak_bytes / peak_transient_bytes  static liveness estimate (analysis/memory.py)")
        print("  bytes_moved{jaxpr,stablehlo,compiled}  analytic wire bytes (analysis/hlo_cost.py)")
        print("  collective counts per layer+kind   ratcheted by R013 vs cost_baseline.json")
        print("  frontier winners + price drift     ratcheted by R014 vs search_pareto.json")
        print("  calibrated seconds + residual fit  ratcheted by R016 vs cost_calibration.json")
        return 0

    # ---- program layer -------------------------------------------------
    per_program, skipped, cost_by_program = {}, {}, {}
    if not args.ast_only:
        names = args.scenarios.split(",") if args.scenarios else None
        programs, skipped = scen.build(names)
        for info in programs:
            analyzer = analysis.ProgramAnalyzer(info)
            findings, metrics = analysis.run_program_rules(info, analyzer=analyzer)
            if args.cost:
                cost = analysis.build_cost(info, analyzer=analyzer,
                                           compile=not args.no_compile)
                findings.extend(analysis.run_cost_rules(info, cost, analyzer))
                cost_by_program[info.name] = cost
            per_program[info.name] = (findings, metrics)
            if not args.quiet:
                s = analysis.summarize(findings)
                line = (f"  {info.name:24s} rules_hit={s['rule_hits'] or '{}'} "
                        f"errors={s['errors']}")
                if args.cost:
                    cost = cost_by_program[info.name]
                    line += (f" peak={cost.memory.peak_bytes / 2**20:.1f}MiB "
                             f"transient={cost.memory.peak_transient_bytes / 2**20:.1f}MiB "
                             f"comms={cost.bytes_moved()}")
                print(line)
        for name, gap in skipped.items():
            print(f"  {name:24s} SKIPPED [{gap['kind']}]: {gap['detail']}")

    # ---- source layer --------------------------------------------------
    ast_findings = []
    if not args.no_ast:
        files = collect_source_files()
        for rule in analysis.ast_rules():
            ast_findings.extend(rule.check(files))
        if not args.quiet:
            s = analysis.summarize(ast_findings)
            print(f"  {'<source AST>':24s} rules_hit={s['rule_hits'] or '{}'} "
                  f"errors={s['errors']} waived={s['waived']}")

    # ---- cost ratchet (R013) -------------------------------------------
    cost_baseline = None
    if args.cost and not args.ast_only:
        cost_baseline = analysis.load_cost_baseline(args.cost_baseline)
        if not args.update_baseline:
            ratchet = analysis.r013_cost_ratchet(cost_by_program, cost_baseline)
            for f in ratchet:
                fs, metrics = per_program.setdefault(f.scenario, ([], {}))
                fs.append(f)

    # ---- search-frontier ratchet (R014) --------------------------------
    # full-matrix --cost runs re-price the gate spaces against the
    # committed Pareto artifact; subset runs skip (their scenario list was
    # scoped on purpose) unless --search forces it. Banking happens in
    # tools/graft_search.py --update, never via --update-baseline.
    if (args.cost and not args.ast_only and not args.no_search
            and not args.update_baseline
            and (args.scenarios is None or args.search)):
        for f in analysis.verify_spaces(
                args.search_pareto,
                log=(None if args.quiet else lambda s: print(f"  [search]{s}"))):
            fs, metrics = per_program.setdefault(f.scenario, ([], {}))
            fs.append(f)
        # R016: the calibration artifact's own ratchet — hermetic
        # self-consistency + the frontier's predicted_seconds re-rank
        # provenance against the committed cost_calibration.json. Banking
        # happens in tools/graft_calibrate.py fit --update, never here.
        for f in analysis.verify_calibration(
                calibration_path=args.cost_calibration,
                search_pareto_path=args.search_pareto):
            fs, metrics = per_program.setdefault(f.scenario, ([], {}))
            fs.append(f)

    # ---- waivers -------------------------------------------------------
    waiver_entries = []
    if os.path.exists(args.waivers):
        with open(args.waivers) as fh:
            waiver_entries = json.load(fh)
    waivers = analysis.load_waivers(waiver_entries)
    all_findings = [f for fs, _ in per_program.values() for f in fs] + ast_findings
    analysis.apply_waivers(all_findings, waivers)

    # ---- stale waivers (WARN, never gating) ----------------------------
    # config waivers are judged only on full-matrix program runs (a subset
    # run legitimately produces no findings for the scenarios it skipped);
    # inline waivers are judged whenever the AST pass swept all files
    stale = []
    if not args.ast_only and args.scenarios is None:
        from deepspeed_tpu.analysis.core import stale_config_waivers
        for w in stale_config_waivers(all_findings, waivers):
            stale.append({"kind": "config", "rule": w.rule, "scenario": w.scenario,
                          "match": w.match, "reason": w.reason})
    if not args.no_ast:
        from deepspeed_tpu.analysis.source_rules import stale_inline_waivers
        stale.extend(stale_inline_waivers(files, ast_findings))
    for s in stale:
        where = (f"{s['file']}:{s['line']}" if s["kind"] == "inline"
                 else f"{s['rule']}/{s['scenario']}")
        print(f"graft-lint: WARN stale waiver [{s['kind']}] {where} matches no "
              f"current finding — prune it", file=sys.stderr)

    # ---- report --------------------------------------------------------
    sig = analysis.matrix_signature(list(per_program) + (["ast"] if not args.no_ast else []))
    report = analysis.build_report(per_program, ast_findings, skipped=skipped,
                                   waivers_in_effect=waiver_entries,
                                   cost_by_program=cost_by_program if args.cost else None,
                                   stale_waivers=stale)
    path = analysis.write_report(report, args.out, sig)
    if not args.quiet:
        print(f"report: {os.path.relpath(path, REPO)}")

    # ---- gate ----------------------------------------------------------
    if args.update_baseline:
        baseline = analysis.baseline_from(all_findings)
        os.makedirs(os.path.dirname(args.baseline), exist_ok=True)
        with open(args.baseline, "w") as fh:
            json.dump(baseline, fh, indent=2)
            fh.write("\n")
        print(f"baseline updated: {os.path.relpath(args.baseline, REPO)} "
              f"({len(baseline['fingerprints'])} acknowledged ERRORs)")
        if args.cost and cost_by_program:
            new_cost = analysis.cost_baseline_from(cost_by_program, prior=cost_baseline)
            with open(args.cost_baseline, "w") as fh:
                json.dump(new_cost, fh, indent=2)
                fh.write("\n")
            print(f"cost baseline updated: {os.path.relpath(args.cost_baseline, REPO)} "
                  f"({len(cost_by_program)} program(s) refreshed, "
                  f"{len(new_cost['programs'])} total)")
        return 0

    baseline = analysis.load_baseline(args.baseline)
    fresh = analysis.new_errors(all_findings, baseline)
    if fresh:
        print(f"graft-lint: {len(fresh)} NEW ERROR finding(s) vs baseline "
              f"{os.path.relpath(args.baseline, REPO)}:", file=sys.stderr)
        for f in fresh:
            loc = f" @ {f.location}" if f.location else ""
            print(f"  {f.rule} [{f.scenario}]{loc}: {f.message}", file=sys.stderr)
        return 1
    unwaived_warns = sum(1 for f in all_findings
                         if not f.waived and f.severity == analysis.WARN)
    if not args.quiet:
        print(f"graft-lint: clean vs baseline "
              f"({len(all_findings)} findings: "
              f"{sum(1 for f in all_findings if f.waived)} waived, "
              f"{unwaived_warns} warn)")
    return 0


if __name__ == "__main__":
    sys.exit(run())
