"""Open loop: Poisson arrivals at ``rate`` requests per second, sent on
schedule whatever the server does. Each request is timed from when it was
due."""


def drive(ctx, spec: dict) -> None:
    due = ctx.clock()
    while True:
        due += ctx.rng.exponential(1.0 / spec["rate"])
        if ctx.stop.wait(max(0.0, due - ctx.clock())):
            return
        ctx.submit(ctx.pool.next(), due=due)
