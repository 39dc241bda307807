"""Per-layer metric ``compile_cache_hit_share.serve``: 100 x set-up's `compile.backend` spans with `cache="hit"` over those with `hit` or `miss`: the persistent compile cache's useful answers over its attempts (program counter)."""

from benchmark.harness import setup_readers


def read(ctx):
    return setup_readers.cache_hit_share(ctx, "serve")
