"""Chip-area evaluations per genome the stratified sampler returned:
program counters ``sweep.area_evals`` over ``sweep.sampled`` (each
repair step of a draw scores an area)."""


def read(ctx):
    c = (ctx.get("program") or {}).get("counters", {})
    if not c.get("sweep.sampled"):
        return None
    return c.get("sweep.area_evals", 0) / c["sweep.sampled"]
