"""A host-side model of a saturated serving cell's schedule: which seeds
read how many tokens a second, with no chip.

The continuous scheduler's policy is small (``scheduler.py::step``): free
slots are filled from the queue in order; a prefill tick feeds one chunk to
every slot still in its prompt, and runs when there is such a slot and
either nothing decodes or ``prefill_interleave`` decode ticks have passed
since the last one; every other tick decodes one token a busy slot. With
the two tick times fixed (they are the fixed-shape programs') the tokens a
window counts depend only on the order the traffic's requests come in,
which is what ``--seed`` decides. For ``serve-nemotron-3-super-reason-sat``
at 18.7 and 343.8 ms a tick the model gave the chip's six seeds at ``block``
64 as 3,419 3,456 3,380 3,439 3,364 3,379 tokens/s where they read 3,425.9
3,462.9 3,379.5 3,432.8 3,358.6 3,365.4 (PERF.md section 6, PR 30), and it
is what chose ``block`` 16 for that cell. It knows nothing of a machine
that runs slow: a spread it does not give is not the seeds'.

Since the scheduler dispatches a tick before it reads the one before it
(PR 35), the host's time a tick runs under the device's program: the two
times are the PROGRAMS' (a tick's ``device_wait`` plus the host time it now
hides), and a tick costs the longer of its program and ``--host-ms``, not
their sum. (Those readings of PR 30 were whole ticks of the serial order,
host included: the same model with no ``--host-ms``.)

The plain decode program has two lengths where its ladder has two rungs
(``serving/programs.py`` ``decode_rungs``, PR 42): a decode tick that feeds
no more slots than the quarter rung holds runs the program over that many
rows. ``--decode-rung-ms`` prices those ticks; without it every decode tick
costs ``--decode-ms``, which is right only where the batch never falls to a
quarter of the slots. The prefill program's quarter rung (``prefill_rungs``,
PR 33) is priced the same way by ``--prefill-rung-ms``, and matters where
prompts are long: under ``serve-laguna-xs2-mixedlen-sat`` nine prefill ticks
in ten feed no more than eight slots. There (13.72, 110 and 36.1 ms) the
model gave twelve chip runs at ``block`` 32 to -2.2..+0.5% each, the slowest
seed's 13,651 tokens/s as 13,609, and it is what chose ``block`` 16 for that
cell too (PERF.md section 6, PR 46).

    python3 tools/serve_schedule_model.py --decode-ms 18.7 --prefill-ms 343.8 \\
        [--host-ms 1.8] [--decode-rung-ms 5.0] [--prefill-rung-ms 36.1]
        [--workload <cell>] [--block <n> ...] [--interleave <n>] [--sets 12]

Prints, for each ``block``, the spread (quartiles over the median) of sets
of six consecutive seeds.
"""

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def tokens_per_s(lengths, slots, chunk, interleave, decode_s, prefill_s, preroll_s, window_s,
                 host_s=0.0, decode_rung=None, prefill_rung=None):
    """``serve_total_tok_s`` of one run: ``lengths`` the (prompt, output)
    pairs in queue order, all due at time zero; a tick takes the longer of
    its program and the host's ``host_s``, which runs under it.
    ``decode_rung``: ``(rows, seconds)`` of the decode program's rung below
    the whole, which a decode tick that feeds no more than ``rows`` slots runs;
    ``prefill_rung`` the same of the prefill program."""
    decode_s, prefill_s = max(decode_s, host_s), max(prefill_s, host_s)
    rung_rows, rung_s = decode_rung or (0, decode_s)
    rung_s = max(rung_s, host_s)
    prefill_rows, prefill_rung_s = prefill_rung or (0, prefill_s)
    prefill_rung_s = max(prefill_rung_s, host_s)
    queue = iter(lengths)
    held = [None] * slots           # [prompt tokens left, outputs made, outputs wanted]
    now, since_prefill, progress, opened = 0.0, 0, 0, None
    while True:
        if opened is None and now >= preroll_s:
            opened = (progress, now)
        if opened is not None and now - opened[1] >= window_s:
            return (progress - opened[0]) / (now - opened[1])
        for i in range(slots):
            if held[i] is None:
                pair = next(queue, None)
                held[i] = None if pair is None else [pair[0], 0, pair[1]]
        prefilling = [s for s in held if s and s[0] > 0]
        active = [s for s in held if s and s[0] == 0]
        if prefilling and (not active or since_prefill >= interleave):
            for s in prefilling:
                fed = min(chunk, s[0])
                s[0] -= fed
                progress += fed
                if s[0] == 0:       # the chunk that ends a prompt samples the first token
                    s[1] += 1
                    progress += 1
            now += prefill_rung_s if len(prefilling) <= prefill_rows else prefill_s
            since_prefill = 0
        else:
            for s in active:
                s[1] += 1
                progress += 1
            now += rung_s if len(active) <= rung_rows else decode_s
            since_prefill += 1
        held = [None if s and s[0] == 0 and s[1] >= s[2] else s for s in held]


def spread_pct(values):
    first, _, third = statistics.quantiles(values, n=4)
    return 100.0 * (third - first) / statistics.median(values)


def run_seed(cell, seed, decode_s, prefill_s, block=None, interleave=None, window_s=51.0,
             host_s=0.0, decode_rung_s=None, prefill_rung_s=None):
    from benchmarks.lib.traffic import serve_schedule

    traffic = dict(cell.traffic, block=block or cell.traffic["block"])
    serve = cell.config["serve"]
    lengths = [(len(r["prompt"]), r["max_new_tokens"])
               for r in serve_schedule(traffic, cell.config["vocab_size"], seed, 0.0)]

    def rung(ladder, seconds):
        if seconds is None:
            return None
        from deepspeed_tpu.inference.serving import programs
        rungs = getattr(programs, ladder)(serve["slots"])
        return (rungs[0], seconds) if len(rungs) > 1 else None

    return tokens_per_s(lengths, serve["slots"], serve["prefill_chunk"],
                        interleave or serve["prefill_interleave"], decode_s, prefill_s,
                        float(traffic["preroll_s"]), window_s, host_s,
                        rung("decode_rungs", decode_rung_s), rung("prefill_rungs", prefill_rung_s))


def main(argv):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", default="serve-nemotron-3-super-reason-sat")
    parser.add_argument("--decode-ms", type=float, required=True)
    parser.add_argument("--prefill-ms", type=float, required=True)
    parser.add_argument("--host-ms", type=float, default=0.0)
    parser.add_argument("--decode-rung-ms", type=float,
                        help="the decode program over the quarter rung, where the cell has one")
    parser.add_argument("--prefill-rung-ms", type=float,
                        help="the prefill program over the quarter rung, where the cell has one")
    parser.add_argument("--block", type=int, nargs="+")
    parser.add_argument("--interleave", type=int)
    parser.add_argument("--sets", type=int, default=12)
    parser.add_argument("--first-seed", type=int, default=2000000000)
    args = parser.parse_args(argv)

    from benchmarks.lib import harness

    cell = harness.Cell(ROOT, harness.load_json(ROOT, "BENCHMARK.json"), args.workload)
    if cell.traffic["arrivals"]["process"] != "all_at_zero":
        raise SystemExit("the model is of a standing backlog (arrivals all_at_zero)")
    rung_s = {name: None if ms is None else ms / 1e3 for name, ms in
              (("decode_rung_s", args.decode_rung_ms), ("prefill_rung_s", args.prefill_rung_ms))}
    for block in args.block or [cell.traffic["block"]]:
        spreads, medians = [], []
        for k in range(args.sets):
            values = [run_seed(cell, args.first_seed + 7919 * k + j, args.decode_ms / 1e3,
                               args.prefill_ms / 1e3, block, args.interleave,
                               host_s=args.host_ms / 1e3, **rung_s) for j in range(6)]
            spreads.append(spread_pct(values))
            medians.append(statistics.median(values))
        print(json.dumps({"block": block, "sets_of_six": args.sets,
                          "spread_pct_median": round(statistics.median(spreads), 3),
                          "spread_pct_max": round(max(spreads), 3),
                          "tokens_per_s_medians": [round(min(medians), 1), round(max(medians), 1)]}))


if __name__ == "__main__":
    main(sys.argv[1:])
