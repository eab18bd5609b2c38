"""Find an open-loop cell's knee: one set-up, a pre-roll at the first
rate, then one window per offered rate, rising, with no pause between
them, so that each window opens on the steady state of the rate before.

    python3 benchmarks/chip/sweep.py --workload qwen05_fast_chat \
        --rates 0.6,0.7,0.8,0.9,1.0 --seconds 50 --preroll 60 --seed 11

For each rate it prints one JSON line: the requests waiting for a slot
as the window opens and as it closes, the median queue wait of the
requests due in each half of the window, the live slots per decode
step, requests finished and tokens made per second, the pool's peak,
admission waits and evictions. Below the knee the queue holds steady;
above it, it grows through the window. The cell's traffic file then
fixes its rate at about four fifths of the knee.
"""

from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import math
import sys

import harness
import stats
import traffic as traffic_gen


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--preroll", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=11)
    args = ap.parse_args()
    cell = harness.resolve(harness.ROOT, args.workload)
    harness.use_compile_cache()
    sess = harness.build(cell, args.seed, args.seconds, trace=False)
    eng, tel = sess.eng, sess.tel
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        tr = copy.deepcopy(cell.traffic)
        tr["arrivals"].update(rate_per_s=rate,
                              preroll_s=args.preroll if k == 0 else 0.0)
        reqs = traffic_gen.generate(tr, args.seed + k, args.seconds,
                                    cell.model["vocab_size"])
        reqs = [dataclasses.replace(r, uid=r.uid + 100_000 * (k + 1))
                for r in reqs]      # uids unique across windows
        queued = len(eng.queue) if k else None
        done0 = len(tel.token_times)
        win = harness.serve(sess, reqs, args.seconds, backlog=False,
                            preroll_s=traffic_gen.preroll_s(tr), drain=False)
        a, b = win.stats_at_open, win.stats_at_close
        waits = {u: tel.admitted.get(u, math.inf) - t
                 for u, t in win.due.items()}
        mid = win.t0 + args.seconds / 2
        halves = [[w for u, w in waits.items() if (win.due[u] < mid) == h]
                  for h in (True, False)]
        steps = b["decode_steps"] - a["decode_steps"]
        out = {
            "rate_per_s": rate, "requests": len(win.due),
            "queue_at_open": queued, "queue_at_close": len(eng.queue),
            "queue_wait_p50_s_by_half": [
                stats.percentile(h, 0.5) if h else None for h in halves],
            "live_slots_mean": ((b["decode_slot_tokens"]
                                 - a["decode_slot_tokens"]) / max(steps, 1)),
            "finished_per_s": len(win.finished) / args.seconds,
            "tokens_per_s": stats.tokens_between(
                tel.token_times, win.t0, win.t_end) / args.seconds,
            "step_s": args.seconds / max(steps, 1),
            "peak_blocks": b["peak_blocks_in_use"],
            "admission_waits": b["admission_waits"] - a["admission_waits"],
            "evictions": b["evictions"] - a["evictions"],
            "started": len(tel.token_times) - done0,
        }
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
