"""Per-layer metric ``prefill_tokens_per_s.serve``: real prompt tokens prefilled inside the window (engine counter) over the summed time from each `prefill.launch` to the end of its `prefill.pull` (batcher lane)."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    a, b = ctx["stats0"], ctx["stats1"]
    if "prefill_prompt_tokens" not in a or "prefill_prompt_tokens" not in b:
        return None
    busy = sum(s.t1 - s.t0 for s in ctx["spans"]
               if s.name in ("prefill.launch", "prefill.pull")
               and s.t0 >= ctx["t_open"])
    tokens = b["prefill_prompt_tokens"] - a["prefill_prompt_tokens"]
    return tokens / busy if busy > 0 and tokens > 0 else None
