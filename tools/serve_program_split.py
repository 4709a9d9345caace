"""Where one prefill program and one decode program of a saturated serving
cell spend their device time, by part of the model: the benchmark's
breakdown names operations by kind (``fusion``, ``sort``) and cannot say
which ``fusion`` is whose. The profiler's events carry an operation's HLO
text and no ``jax.named_scope``, so the parts are told from the parameters
an operation reads and from the expert layers' row counts
(:func:`rules_for`: written for the reasoning cell's shapes; what matches
nothing is listed by kind).

The server is built as the cell builds it (``runners/serve.py::_server``),
fed the cell's traffic for its pre-roll (the backlog's steady state: a
prefill tick a fifth fed, not the first ticks' every slot), then traced for
``--ticks`` ticks. Device time is summed by program (``jit_prefill`` / ``jit_decode``)
and divided by the runs of that program in the trace.

    python3 tools/serve_program_split.py [--workload <cell>] [--seed <n>]
        [--ticks 34] [--out chiprun_out/split]
    python3 tools/serve_program_split.py --reduce <file.xplane.pb>
"""

import argparse
import glob
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

def rules_for(row_counts):
    """(label, pattern) in order, the first that matches an operation's HLO
    text names its part. The profiler's events carry that text and no scope
    (no ``tf_op``), so parts are told by the parameters an operation reads
    and by the expert layers' row counts, which no other tensor has."""
    rows = r"\[(1,)?(%s)[,\]]" % "|".join(str(r) for r in row_counts)
    return [(label, re.compile(pattern)) for label, pattern in (
        ("expert kernels", r"^%gmm"),
        ("head", r"lm_head"),
        ("mamba projections", r"in_proj|out_proj"),
        ("shared expert", r"shared_expert"),
        ("latent projections", r"latent_(down|up)"),
        ("router and top-k", r"gate____wg|e_score_correction|^%(sort|custom-call)[.\d]* = .*\[(1,)?\d+,512\]"),
        ("expert rows: sorts", r"^%sort.*" + rows),
        ("expert rows: relu^2", r"^%maximum_multiply.*" + rows),
        ("expert rows: scatter-add", r"^%(scatter|[a-z_]*scatter[a-z_]*fusion).*" + rows),
        ("expert rows: gathers", r"= bf16" + rows + r".*kind=kCustom"),
        ("expert rows: index arithmetic", rows),
        ("state and scan", r"ssm_state|conv_state|\[64,8,16,64,128\]|\[64,128,8,16\]|\[64,8,128,128\]"),
        ("attention", r"cached_(key|value)|,2048\]"),
    )]


def reduce_trace(path, row_counts):
    """``{program: {"runs": n, "ms_a_run": .., "by_part_ms": {...}}}`` of the
    first device's ``XLA Ops`` line: an operation belongs to the program
    (``XLA Modules`` line) it started inside, nested time goes to the
    innermost operation (a ``while`` or ``conditional`` is not counted again
    for its body), and :func:`rules_for` names the part."""
    from jax.profiler import ProfileData

    plane = next(p for p in ProfileData.from_file(path).planes if p.name.startswith("/device:"))
    lines = {line.name: list(line.events) for line in plane.lines}
    runs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name.split("(")[0])
                  for ev in lines["XLA Modules"])
    rules, parts, stack, at = rules_for(row_counts), {}, [], 0

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _, program, text, self_ns = stack.pop()
            label = next((name for name, rule in rules if rule.search(text)),
                         "other: " + re.sub(r"[.\d]*$", "", text.split(" = ")[0].lstrip("%")))
            by = parts.setdefault(program, {})
            by[label] = by.get(label, 0) + self_ns

    for ev in sorted(lines["XLA Ops"], key=lambda e: (e.start_ns, -e.duration_ns)):
        while at < len(runs) and runs[at][1] <= ev.start_ns:
            at += 1
        if at == len(runs) or ev.start_ns < runs[at][0]:
            continue
        close(ev.start_ns)
        if stack:
            stack[-1][3] -= ev.duration_ns
        stack.append([ev.start_ns + ev.duration_ns, runs[at][2], ev.name, ev.duration_ns])
    close(float("inf"))
    out = {}
    for program, by in parts.items():
        n = sum(1 for run in runs if run[2] == program)
        out[program] = {"runs": n, "ms_a_run": round(sum(by.values()) / n / 1e6, 2),
                        "by_part_ms": {k: round(v / n / 1e6, 2)
                                       for k, v in sorted(by.items(), key=lambda kv: -kv[1])
                                       if v / n >= 5e4}}
    return out


def row_counts_of(cell):
    """Every size an expert layer's row buffer can take in the cell's
    prefill and decode programs."""
    from deepspeed_tpu.moe.sharded_moe import _row_rungs
    serve, k = cell.config["serve"], int(cell.config.get("num_experts_per_tok", 1))
    return sorted({rows for positions in (serve["slots"] * serve["prefill_chunk"], serve["slots"])
                   for rows in _row_rungs(positions * k)})


def main(argv):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", default="serve-nemotron-3-super-reason-sat")
    parser.add_argument("--seed", type=int, default=3100000701)
    parser.add_argument("--ticks", type=int, default=34)
    parser.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "split"))
    parser.add_argument("--reduce")
    args = parser.parse_args(argv)
    from benchmarks.lib import harness

    cell = harness.Cell(ROOT, harness.load_json(ROOT, "BENCHMARK.json"), args.workload)
    if args.reduce:
        print(json.dumps(reduce_trace(args.reduce, row_counts_of(cell))))
        return
    import jax
    from benchmarks.lib.traffic import serve_schedule
    from deepspeed_tpu.inference.serving import Request

    env = harness.Env(args.seed, 0, 0, harness.Setup(time.time()), jax.devices()[:1],
                      harness.Tracer(False, ""))
    _, sched = cell.runner._server(cell, env, cell.family)
    sched.warmup()
    for r in serve_schedule(cell.traffic, cell.config["vocab_size"], args.seed, 0.0):
        sched.submit(Request(prompt=r["prompt"], max_new_tokens=r["max_new_tokens"]))
    settled = time.perf_counter() + float(cell.traffic["preroll_s"])
    while time.perf_counter() < settled:
        sched.step()
    jax.profiler.start_trace(args.out)
    kinds = [sched.step() for _ in range(args.ticks)]
    jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(args.out, "plugins", "profile", "*", "*.xplane.pb")),
               key=os.path.getmtime)
    print(json.dumps({"device": jax.devices()[0].device_kind, "trace": os.path.relpath(path, ROOT),
                      "ticks": {k: kinds.count(k) for k in set(kinds)}}))
    print(json.dumps(reduce_trace(path, row_counts_of(cell))))


if __name__ == "__main__":
    main(sys.argv[1:])
