"""Look at one profiler trace by hand before writing code or patterns against it.

    python3 benchmark/tools/trace_digest.py <trace dir or .xplane.pb> [--top 40] [--json out.json]

Prints every plane and line with its number of events, then, for each device
plane, the operations with most self time together with *all* their stats —
which is where one finds how a kernel is named (the patterns of
``benchmark/kernels/classes.json``) and which line holds what. ``--json``
writes the same digest as a file, e.g. under ``chiprun_out/``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark.lib import xplane  # noqa: E402


def digest(planes: list, top: int) -> dict:
    out = {"planes": []}
    for p in planes:
        entry = {"name": p.name, "lines": []}
        for ln in p.lines:
            line = {"name": ln.name, "events": len(ln.events)}
            if ln.events:
                line["first"] = [ln.events[0].name[:120], ln.events[0].start, ln.events[0].dur]
                acc = {}
                for e, s in xplane.self_times(ln.events):
                    n, t, ex = acc.get(e.name, (0, 0.0, e))
                    acc[e.name] = (n + 1, t + s, ex)
                ranked = sorted(acc.items(), key=lambda kv: -kv[1][1])[:top]
                line["top"] = [{"name": name[:160], "count": n, "self_ms": t / 1e6,
                                "stats": {k: str(v)[:400] for k, v in ex.stats.items()}}
                               for name, (n, t, ex) in ranked]
            entry["lines"].append(line)
        out["planes"].append(entry)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--json")
    args = ap.parse_args(argv)
    path = args.trace if args.trace.endswith(".pb") else xplane.find_xplane(args.trace)
    d = digest(xplane.load(path), args.top)
    for p in d["planes"]:
        print(f"PLANE {p['name']}")
        for ln in p["lines"]:
            print(f"  LINE {ln['name']!r}: {ln['events']} events")
            show = xplane.DEVICE_PLANE.match(p["name"]) is not None or ln["name"].startswith("bench")
            for t in ln.get("top", [])[: args.top if show else 3]:
                print(f"    {t['self_ms']:10.3f} ms x{t['count']:<6} {t['name']}")
                if show:
                    for k, v in t["stats"].items():
                        print(f"        {k} = {v[:200]}")
    if args.json:
        os.makedirs(os.path.dirname(os.path.abspath(args.json)), exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(d, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
