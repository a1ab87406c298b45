"""Share of the traced window with no operation on the device, in percent."""
from cbench import derive


def read(ctx):
    return derive.idle_share(ctx)
