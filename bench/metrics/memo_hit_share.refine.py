"""Share of the rows the fused refinement scored that its device memo
answered: program counters ``refine.memo_hits`` over ``refine.rows``
(seed population and every generation's children, summed over the
window's calls)."""


def read(ctx):
    c = (ctx.get("program") or {}).get("counters", {})
    if not c.get("refine.rows"):
        return None
    return 100.0 * c.get("refine.memo_hits", 0) / c["refine.rows"]
