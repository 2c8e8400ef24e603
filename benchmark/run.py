"""Run one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Measures on the machine it is started on and refuses to measure without a TPU
holding the chips the cell asks for (exit 2, no result line). The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``, and
last ``compared``: each number that decided ``correct`` beside its limit (also
the last lines on standard error).

``--rehearse`` is for the CPU tests: the tiny sizes of each file's
``rehearsal`` block, any platform, and a result that carries counts, ``correct``
and the *names* of the metrics that could be read, never a time or a rate.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

NO_CHIP = 2


@dataclass
class Env:
    devices: list
    watch: object
    spans: object
    t_start: float
    out_dir: str

    def profiler(self):
        from benchmark.lib.harness import Profiler

        return Profiler(os.path.join(self.out_dir, "trace"), self.spans)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: the manifest's run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    return ap.parse_args(argv)


def place_cache(rehearse: bool) -> None:
    """JAX's persistent compilation cache at a fixed path inside the checkout,
    decided before jax is imported so that the model's init programs are cached
    too. Where JAX_COMPILATION_CACHE_DIR is set from outside it is left alone.
    A rehearsal compiles for the CPU, which is quick and machine-specific: no
    cache."""
    if not rehearse:
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".tt_cache"))


def per_layer_metrics(cell, run, say) -> dict:
    out = {}
    for entry in cell.per_layer:
        reader = cell.reader(entry["name"])  # a missing reader is a broken manifest: raise
        try:
            value = reader.read(run)
        except Exception as e:  # a reader must not take the whole line down
            say(f"reader {entry['name']} failed: {type(e).__name__}: {e}")
            continue
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def main(argv=None) -> int:
    opts = parse(argv)
    place_cache(opts.rehearse)

    from benchmark.lib import harness, manifest

    man = manifest.load_manifest(ROOT)
    if opts.seconds is None:
        opts.seconds = float(man["run_seconds"])
    cell = manifest.resolve(man, opts.workload, root=ROOT, rehearse=opts.rehearse)

    import jax

    devices = jax.devices()
    info = harness.device_info(devices)
    harness.say(f"{cell.name}: seed {opts.seed}, {opts.seconds:g} s, trace {opts.trace}; "
                f"platform {info['platform']}, device_kind {info['kind']}, {info['count']} devices; "
                f"jax {jax.__version__}; compile cache "
                f"{os.environ.get('JAX_COMPILATION_CACHE_DIR', 'off')}")
    if not opts.rehearse and info["platform"] != "tpu":
        harness.say(f"refusing to measure: jax found platform {info['platform']!r}, not a TPU")
        return NO_CHIP
    if len(devices) < cell.chips:
        harness.say(f"refusing to measure: the cell needs {cell.chips} chips, jax found {len(devices)}")
        return NO_CHIP

    env = Env(devices=devices, watch=harness.CompileWatch(), spans=harness.Spans(),
              t_start=T_START, out_dir=os.path.join(ROOT, ".bench_out", cell.name))
    run = cell.driver.run(cell, opts, env)
    setup = env.watch.snapshot()
    harness.say(f"set-up {run.end_to_end['setup_s']:.2f} s; executables built {setup['builds']}, "
                f"of them served by the persistent cache {setup['cache_hits']}, compiled "
                f"{setup['compiled']} ({'cold' if setup['compiled'] else 'warm'} run)")
    for note in run.notes:
        harness.say(f"NOT CORRECT: {note}")

    used = devices[:cell.chips]
    device = dict(info, memory_peak_bytes=harness.memory_peak_bytes(used))
    line = {"correct": bool(run.correct), "attempted": int(run.attempted),
            "failed": int(run.failed), "metrics": {}, "device": device}
    if opts.trace:
        metrics = per_layer_metrics(cell, run, harness.say)
        # the driver's check takes a traced line only with every metric the manifest lists
        # for the cell: "workloads" may name only cells where the reader finds its number
        missing = [m["name"] for m in cell.per_layer if m["name"] not in metrics]
        if missing and not opts.rehearse:
            harness.say(f"NOT REPORTED, though BENCHMARK.json lists them for this cell: {missing}")
        if run.trace is not None and run.trace.devices:
            device["busy_s"] = run.trace.busy_s
            device["window_s"] = run.trace.window_s
            from benchmark.lib.readers import breakdown_label

            line["breakdown"] = {"device_ops": run.trace.top_ops(10, breakdown_label(ROOT)),
                                 "idle_gaps": run.trace.longest_gaps(5)}
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        missing = [n for n in units if n not in run.end_to_end]
        if missing:
            harness.say(f"NOT CORRECT: the driver took no {missing}")
            line["correct"] = False
        metrics = {n: {"value": float(run.end_to_end[n]), "unit": units[n]}
                   for n in units if n in run.end_to_end}
    if opts.rehearse:
        # a CPU run reports what could be read, never a number under a device metric's name
        line["rehearsal"] = {"metrics_read": sorted(metrics),
                             "breakdown_read": sorted(line.pop("breakdown", {})),
                             "compiles_in_window": run.compiles.get("builds")}
        for k in ("busy_s", "window_s"):
            device.pop(k, None)
    else:
        line["metrics"] = metrics
    # each number that decided `correct` beside its limit: last in the line, and the last
    # lines on standard error, where the driver's record of a run that is not correct ends
    # (a NaN goes as a string: it is no JSON)
    line["compared"] = {name: {"value": value if value == value else "nan", "rule": rule, "limit": limit}
                        for name, (value, rule, limit) in run.compared.items()}
    sys.stdout.flush()
    for name, (value, rule, limit) in run.compared.items():
        print(f"bench: compared {name}: {value!r} {rule} {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
