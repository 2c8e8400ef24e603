"""What XLA's memory analysis says the step executable needs (arguments + temporaries +
outputs - aliased), to set beside the runtime's peak until one is shown right."""
def read(run):
    nbytes = run.stats.get("xla_step_bytes")
    return None if nbytes is None else nbytes / 2**30
