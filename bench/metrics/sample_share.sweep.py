"""Share of the window the sweep spends drawing its stratified genomes
on the host: the program's span ``sweep.sample`` over the driver's
window seconds.  The device waits for it."""


def read(ctx):
    s = (ctx.get("program") or {}).get("spans", {}).get("sweep.sample")
    seconds = (ctx.get("counters") or {}).get("seconds")
    if not s or not seconds:
        return None
    return 100.0 * s[0] / seconds
