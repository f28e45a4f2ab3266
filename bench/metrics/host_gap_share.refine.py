"""Share of the window spent outside the refinement calls, on the host's
clock: the memo preload before each call, the drain after it and the
loop between them.  The device trace cannot read it: one call is one
program of several seconds, and the device's trace buffers fill inside
it."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("seconds"):
        return None
    return 100.0 * (1.0 - c["in_call_s"] / c["seconds"])
