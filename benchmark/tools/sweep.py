"""Find the knee of an open-loop serve cell: the highest of a few fixed rates
that the system sustains. Not run by the driver; run it once when a cell is
defined, or again after an optimisation has moved the knee:

    chiprun -- python3 benchmark/tools/sweep.py --workload <cell> --rates 2,3,4,5,6 [--seconds 30]

One process, one set-up; each rate gets the cell's pre-roll and one window on
the same warm engine, and the engine is drained between rates. A rate is
*sustained* when (a) at least 95% of the requests due in the first half of the
window complete inside the window (a request due near its end cannot, however
light the load), (b) the backlog (requests due and not yet done) at the
window's end is no larger than at its middle, give or take three times its
square root (arrivals are random), and (c) the median time to first token is
under five times that of the lowest rate swept: past the knee requests queue
for a slot and that median jumps from tens of milliseconds to seconds. The cell's ``rate_rps`` is then four fifths of the
highest sustained rate; write it into the traffic file by hand, with the
table this prints in ``PERF.md``. Like ``run.py`` it refuses to run off a TPU.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def backlog(records: list, t: float) -> int:
    return sum(1 for r in records if r.due <= t and (math.isnan(r.done) or r.done > t))


def judge(records: list, seconds: float, ttft_floor_ms: float = math.inf) -> dict:
    """One row of the sweep. ``ttft_floor_ms``: the lowest rate's median time
    to first token."""
    from benchmark.lib import loadgen

    due = [r for r in records if 0.0 <= r.due < seconds]
    done = [r for r in due if r.ok and r.done <= seconds]
    early = [r for r in due if r.due < seconds / 2]
    early_done = [r for r in early if r.ok and r.done <= seconds]
    row = {"due": len(due), "completed": len(done),
           "first_half_completed_share": len(early_done) / len(early) if early else 0.0,
           "backlog_middle": backlog(records, seconds / 2), "backlog_end": backlog(records, seconds)}
    if done:
        row["tpot_p50_ms"] = loadgen.percentile([(r.done - r.due) / r.n_new * 1e3 for r in done], 50)
        row["ttft_p50_ms"] = loadgen.percentile([r.ttft_from_due_s * 1e3 for r in done], 50)
        row["ttft_p95_ms"] = loadgen.percentile([r.ttft_from_due_s * 1e3 for r in done], 95)
    row["sustained"] = bool(done) and row["first_half_completed_share"] >= 0.95 \
        and row["backlog_end"] <= row["backlog_middle"] + 3 * math.sqrt(max(row["backlog_middle"], 1)) \
        and row["ttft_p50_ms"] <= 5 * min(ttft_floor_ms, row["ttft_p50_ms"])
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second, ascending")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "sweep.json"))
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU tests only: the traffic and configuration files' tiny sizes")
    args = ap.parse_args(argv)

    if not args.rehearse:
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".tt_cache"))
    from benchmark.lib import harness, manifest

    man = manifest.load_manifest(ROOT)
    cell = manifest.resolve(man, args.workload, root=ROOT, rehearse=args.rehearse)
    seconds = args.seconds or float(man["run_seconds"])
    if cell.traffic["loop"]["kind"] != "open":
        raise SystemExit("only an open-loop cell has a knee to find")

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.rehearse:
        harness.say(f"refusing to sweep: jax found platform {devices[0].platform!r}, not a TPU")
        return 2
    drv = cell.driver
    notes: list = []
    engine, _ = drv.set_up(cell, args.seed, notes)
    watch = harness.CompileWatch()
    rows = []
    try:
        for rate in [float(r) for r in args.rates.split(",")]:
            traffic = manifest.merged(cell.traffic, {"loop": {"rate_rps": rate}})
            w = drv.offer(traffic, engine, seconds, args.seed, cell.config["vocab_size"], watch)
            engine.drain()
            # requests finished while draining count as late, not as done in the window
            floor = rows[0]["ttft_p50_ms"] if rows else math.inf
            row = dict(rate_rps=rate, **judge(w["records"], seconds, floor),
                       decode_steps=w["decode_steps"], builds_in_window=w["compiles"]["builds"])
            rows.append(row)
            harness.say(json.dumps(row))
            time.sleep(0.5)
    finally:
        engine.stop()
    sustained = [r["rate_rps"] for r in rows if r["sustained"]]
    knee = max(sustained) if sustained else None
    harness.say(f"knee: {knee} requests/s; four fifths of it: {0.8 * knee if knee else None}")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"workload": cell.name, "seconds": seconds, "seed": args.seed,
                   "device_kind": devices[0].device_kind, "rows": rows, "knee_rps": knee,
                   "notes": notes}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
