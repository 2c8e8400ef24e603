"""Window pages handed back to the allocator a decode step: `serve.window_pages_freed` over
the window's decode steps."""
def read(run):
    steps = run.stats.get("decode_steps")
    if not steps or "serve.state.window_pages" not in run.counters:
        return None
    return run.counters.get("serve.window_pages_freed", 0) / steps
