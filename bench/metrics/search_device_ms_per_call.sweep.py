"""Device time per execution of the search kernel.  In this cell's
window the only program that runs under the trace name ``jit_run_all``
is ``batched_mapper._jitted_search_population`` (its jitted function is
``vmap(run_all)``)."""


def read(ctx):
    mod = (ctx.get("trace") or {}).get("modules", {}).get("jit_run_all")
    if not mod or not mod[1]:
        return None
    return 1e3 * mod[0] / mod[1]
