"""Per-layer metric ``expert_pairs_per_held_expert.serve``: the prefills' real token-expert pairs that fall on experts held on this chip, over the experts held, a launch: mean over the window's `prefill.pull` spans of the attribute the prefill hands out (how far the expert layer's load is from a deployment's)."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    vals = [s.attrs["expert_pairs_per_held_expert"] for s in ctx["spans"]
            if s.name == "prefill.pull" and s.t0 >= ctx["t_open"]
            and "expert_pairs_per_held_expert" in (s.attrs or {})]
    return sum(vals) / len(vals) if vals else None
