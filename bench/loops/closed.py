"""Closed loop: ``clients`` callers, each sending its next request as soon
as its last one completes. The clients start evenly over ``ramp_s``."""
import queue


def drive(ctx, spec: dict) -> None:
    n = spec["clients"]
    gap = spec.get("ramp_s", 0.0) / n
    for c in range(n):
        if c and ctx.stop.wait(gap):
            return
        ctx.submit(ctx.pool.next())
    while not ctx.stop.is_set():
        try:
            ctx.completions.get(timeout=0.05)
        except queue.Empty:
            continue
        if not ctx.stop.is_set():
            ctx.submit(ctx.pool.next())
