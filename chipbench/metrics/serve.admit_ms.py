"""Mean admission stall, in milliseconds: ``t_first - t_admit`` of the
program's requests (``ServeSession``'s ``Request``: its admission's start
and its first token on the host, both ``time.perf_counter`` like the
loop's clock), over the requests whose admission started in the window.
None where no request in the window carries both times."""


def read(ctx):
    t0, t1 = ctx.window
    stalls = []
    for r in ctx.reqs:
        a = getattr(r.handle, "t_admit", None)
        f = getattr(r.handle, "t_first", None)
        if a is not None and f is not None and t0 <= a <= t1:
            stalls.append(f - a)
    return 1e3 * sum(stalls) / len(stalls) if stalls else None
