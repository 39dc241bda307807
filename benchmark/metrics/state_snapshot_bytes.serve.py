"""Per-layer metric ``state_snapshot_bytes.serve``: bytes of the state snapshots the prefix cache retains (host or device) at the close (engine gauge)."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    prefix = ctx["stats1"].get("prefix_cache", {}).get(ctx["head"]) or {}
    return prefix.get("snapshot_bytes")
