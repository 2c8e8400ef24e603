"""Cached state a sequence in a decode step held, in MB: the program's `serve.state.*` counters
(pages of the shared pool, pages of the window pools, bytes of recurrent state, each summed
over the window's decode steps) over `serve.tokens` (the sequences, summed the same way)."""
def read(run):
    c, seqs = run.counters, run.counters.get("serve.tokens")
    if not seqs or "serve.state.shared_kv_pages" not in c:
        return None
    d = run.cell.builder.dims(run.cell.config)
    page = int(run.cell.traffic["engine"]["page_size"]) * 2 * d["kv_heads"] * d["head_dim"] * 2
    total = (c["serve.state.shared_kv_pages"] * page * d["layers"]["full_attn"]
             + c.get("serve.state.window_pages", 0) * page * d["layers"]["window_attn"]
             + c.get("serve.state.recurrent_bytes", 0))
    return total / seqs / 1e6
