"""Readings that the benchmark's fixed numbers are set from. Not part of a
benchmark run; run it on the chip when a cell is defined.

    # knee sweep: isolated TTFT/TBT, then one window per seed and offered
    # rate, all on one set-up
    python3 bench/calibrate.py sweep --workload <cell> --seeds 5,6 \\
        --seconds 51 --rates 0.2,0.3,0.4
    # correctness readings, one run per seed, all in one process; with
    # --control fp8 each run's verdict is the control's and the program's
    # own gap is printed beside it
    python3 bench/calibrate.py correct --workload <cell> --seeds 1,2,3 \\
        --seconds 51 --control fp8

The knee is the highest offered rate at which, on every seed swept (each
seed its own arrival order), at least 90% of the requests due in the window meet both limits and the
backlog does not grow across the window. The limits are the paper's SLO
rule (``SLO_MULTIPLIER`` in the program's ``core/lookup.py``): TTFT within
5x the isolated TTFT of a prompt of the same length (each length of the
sweep is measured alone), and mean time between tokens within 5x the
isolated one. The backlog is the number of requests submitted and not yet
finished; it grows when it is larger at the window's close than at its open
by more than ``max(2, 10%`` of the window's requests``)``. Each line
printed is one JSON object.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLO_MULTIPLIER = 5.0
ISOLATED_NEW_TOKENS = 24
MIN_ATTAINMENT = 0.9


def _emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def _idle(drv) -> None:
    """Stop submitting and evict whatever the engine still holds."""
    drv.next = len(drv.recs)
    drv.engine.drain()
    drv.outstanding = []


def backlog_grows(open_: int, close: int, requests: int) -> bool:
    return close - open_ > max(2, 0.1 * requests)


def sweep(args) -> None:
    import numpy as np

    from bench import harness as H
    from bench import traffic

    seeds = [int(x) for x in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")]
    cell, devices, peaks, engine = H.start(
        args.root, args.workload, seeds[0],
        require_accelerator=not args.cpu)
    vocab = cell.config["vocab_size"]
    mix = cell.mix
    # each seed sweeps its own arrival order, so the knee holds for more
    # than the benchmark's one
    plans = {(seed, rate): traffic.schedule(
        H.dataclasses.replace(mix, rate_rps=rate), args.seconds, seed, vocab,
        order_seed=seed) for seed in seeds for rate in rates}
    every = [a for arr in plans.values() for a in arr]
    _emit({"warm": H.warm(engine, cell, every, seeds[0])})
    clock = time.perf_counter

    # isolated: each prompt length the windows hold, alone, once
    lens = sorted({len(a.prompt) for a in every if a.segment == "window"})
    iso_ttft, iso_gap = {}, []
    rng = np.random.default_rng([seeds[0], 2])
    for i, n in enumerate(lens):
        a = traffic.Arrival(i, "window", 0.0,
                            rng.integers(0, vocab, n).astype(np.int32),
                            ISOLATED_NEW_TOKENS)
        rec = H.Rec(a, due=clock())
        drv = H.OpenLoop(engine, [rec], clock)
        drv.run_until(clock() + 600, stop=lambda: rec.req is not None
                      and rec.req.finish_s is not None)
        iso_ttft[n] = rec.stamps[0] - rec.due
        iso_gap.append((rec.stamps[-1] - rec.stamps[1])
                       / (len(rec.stamps) - 2))
    tbt_iso = float(np.median(iso_gap))
    _emit({"isolated": {"prompt_len": lens,
                        "ttft_s": [iso_ttft[n] for n in lens],
                        "tbt_s": tbt_iso}})

    verdict = {}
    for seed in seeds:
        for rate in rates:
            m = H.dataclasses.replace(mix, rate_rps=rate)
            origin = clock() + m.lead_in_s
            recs = [H.Rec(a, due=origin + a.due_s)
                    for a in plans[(seed, rate)]]
            drv = H.OpenLoop(engine, recs, clock)
            drv.run_until(origin)
            b0 = len(drv.outstanding)
            w0 = clock()
            drv.run_until(origin + args.seconds)
            w1 = clock()
            b1 = len(drv.outstanding)
            wr = [r for r in recs if r.arrival.segment == "window"]
            drv.run_until(origin + args.seconds + m.tail_s,
                          stop=lambda: all(r.req is not None and r.req.tokens
                                           for r in wr))
            end = clock()
            _idle(drv)
            met, ttft, gaps, toks = 0, [], [], 0
            for r in wr:
                t = (r.stamps[0] if r.stamps else end) - r.due
                ttft.append(t)
                lim = SLO_MULTIPLIER * iso_ttft[len(r.arrival.prompt)]
                g = ((r.stamps[-1] - r.stamps[0]) / (len(r.stamps) - 1)
                     if len(r.stamps) > 1 else 0.0)
                met += bool(r.stamps) and t <= lim and \
                    g <= SLO_MULTIPLIER * tbt_iso
            for r in recs:
                for j, t in enumerate(r.stamps):
                    if w0 <= t < w1:
                        toks += 1
                        if j:
                            gaps.append(t - r.stamps[j - 1])
            rows = [s.decode_rows for s in drv.steps
                    if w0 <= s.t0 < w1 and s.decode_rows]
            att = met / max(len(wr), 1)
            grows = backlog_grows(b0, b1, len(wr))
            ok = att >= MIN_ATTAINMENT and not grows
            verdict[rate] = verdict.get(rate, True) and ok
            _emit({"seed": seed, "rate_rps": rate, "requests": len(wr),
                   "attainment": att, "backlog_open_close": [b0, b1],
                   "backlog_grows": grows, "sustained": ok,
                   "ttft_mean_ms": float(np.mean(ttft)) * 1e3,
                   "ttft_p90_ms": float(np.percentile(ttft, 90)) * 1e3,
                   "tbt_mean_ms": float(np.mean(gaps)) * 1e3
                   if gaps else None,
                   "tbt_p99_ms": float(np.percentile(gaps, 99)) * 1e3
                   if gaps else None,
                   "output_tokens_per_s": toks / (w1 - w0),
                   "decode_rows": sum(rows) / len(rows) if rows else None,
                   "no_first_token": sum(not r.stamps for r in wr)})
    held = [r for r in rates if verdict[r]]
    _emit({"sustained_on_every_seed": verdict,
           "knee_rps": max(held) if held else None})


def correct(args) -> None:
    from bench import harness as H

    for seed in [int(s) for s in args.seeds.split(",")]:
        out = H.run_cell(args.root, args.workload, seed, args.seconds, False,
                         control=args.control or None,
                         require_accelerator=not args.cpu)
        log = out["_log"]
        _emit({"seed": seed, "control": args.control or None,
               "correct": out["correct"],
               "max_logit_gap": out["checks"]["max_logit_gap"]["value"],
               "limit": out["checks"]["max_logit_gap"]["limit"],
               "program_max_logit_gap": log.get("program_max_logit_gap"),
               "tokens_checked": log["tokens_checked"],
               "checked_requests": log["checked_requests"],
               "reference_s": log["reference_s"],
               "attempted": out["attempted"], "failed": out["failed"],
               "metrics": {k: v["value"] for k, v in out["metrics"].items()},
               "memory_peak_bytes": out["device"]["memory_peak_bytes"],
               "window_compiles": log["window_compiles"],
               "window_compiled": log["window_compiled"]})


def record_trace(args) -> None:
    """One traced run; writes ``--ms`` milliseconds of its trace extract
    (from the window's start) to ``--out`` as recorded test data."""
    from bench import harness as H
    from bench import trace as T

    out = H.run_cell(args.root, args.workload, args.seed, args.seconds, True,
                     check=False, require_accelerator=not args.cpu)
    tr = out["_data"].trace
    lo, _ = T.window(tr)
    small = T.trim(tr, lo, lo + args.ms * 1e6)
    with open(args.out, "w") as f:
        json.dump(small, f)
    _emit({"metrics": out["metrics"], "device": out["device"],
           "breakdown": out.get("breakdown"),
           "trace_modules": out["_log"].get("trace_modules"),
           "planes": tr["planes"]})


def main(argv=None) -> int:
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    s = sub.add_parser("sweep")
    s.add_argument("--workload", required=True)
    s.add_argument("--seeds", required=True)
    s.add_argument("--seconds", type=float, default=51.0)
    s.add_argument("--rates", required=True)
    c = sub.add_parser("correct")
    c.add_argument("--workload", required=True)
    c.add_argument("--seeds", required=True)
    c.add_argument("--seconds", type=float, default=51.0)
    c.add_argument("--control", default="", choices=("", "fp8"))
    t = sub.add_parser("trace")
    t.add_argument("--workload", required=True)
    t.add_argument("--seed", type=int, default=1)
    t.add_argument("--seconds", type=float, default=10.0)
    t.add_argument("--ms", type=float, default=300.0)
    t.add_argument("--out", required=True)
    for p in (s, c, t):
        p.add_argument("--root", default=ROOT,
                       help="checkout holding BENCHMARK.json")
        p.add_argument("--cpu", action="store_true",
                       help="rehearse on the CPU (no device check)")
    args = ap.parse_args(argv)
    {"sweep": sweep, "correct": correct,
     "trace": record_trace}[args.cmd](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
