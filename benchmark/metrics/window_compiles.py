"""Programs the window compiled: misses of JAX's persistent compilation cache
(`xla.compile_cache.cache_misses`, counted by telemetry/compile_log.py)."""


def read(ctx):
    return ctx.counters.get("xla.compile_cache.cache_misses", 0)
