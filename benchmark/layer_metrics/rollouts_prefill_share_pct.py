"""Share of the device's program time spent in the prefill and chunk programs: every executable
of a region of the program's traces (`jit_xla_fusion_<n>`: the executor numbers its regions
across the whole process) that is not one of the decode program's (the driver read those off its
executed trace), over all executables, the samplers' among them. What the prompts cost beside the
decode steps the cell is about: each such program streams the touched experts' panels as a decode
step does."""
import re


def read(run):
    decode = run.stats.get("decode_regions")
    if run.trace is None or not decode or not run.trace.devices:
        return None
    mine = re.compile("^jit_(" + "|".join(map(re.escape, decode)) + ")$")
    region = re.compile(r"^jit_xla_fusion_\d+$")
    modules = run.trace.devices[0].modules
    every = sum(t for _, t in modules.values())
    other = sum(t for name, (_, t) in modules.items() if region.search(name) and not mine.search(name))
    return 100.0 * other / every if every else None
