"""Mean over the window's steps of ``ServeSession.step()``'s live count
over ``max_slots``, in percent."""
from cbench import derive


def read(ctx):
    st = derive.window_steps(ctx)
    if not st:
        return None
    return 100.0 * sum(s.live for s in st) / (len(st) * ctx.mix["max_slots"])
