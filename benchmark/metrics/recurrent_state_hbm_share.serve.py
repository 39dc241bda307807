"""Per-layer metric ``recurrent_state_hbm_share.serve``: bytes of the slot table's recurrent leaves plus the retained state snapshots held on the device, over the device's memory limit, at the close (engine gauges)."""


def read(ctx):
    if ctx["kind"] != "serve" or not ctx.get("bytes_limit"):
        return None
    pool = ctx["stats1"].get("kv_pool", {}).get(ctx["head"]) or {}
    if "recurrent_state_bytes" not in pool:
        return None
    prefix = ctx["stats1"].get("prefix_cache", {}).get(ctx["head"]) or {}
    held = pool["recurrent_state_bytes"] + prefix.get("snapshot_device_bytes", 0)
    return 100.0 * held / ctx["bytes_limit"]
