"""Benchmark driver: 99th percentile of how late each request was
submitted after its due time (a starved generator reads high here)."""


def read(ctx):
    return ctx.counters["late_p99_ms"]
