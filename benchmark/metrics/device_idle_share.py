"""Share of the window in which no operation ran on the device, from the profiler trace."""


def read(ctx):
    return 100.0 * ctx.trace["idle_share"]
