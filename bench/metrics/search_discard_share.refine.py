"""Share of the rows the refinement's search scan ran over whose answer
the memo already held, so the scan's work on them is thrown away:
program counters ``refine.discarded_rows`` over ``refine.searched_rows``.
The scan runs over all P rows whenever any row misses, so while every
generation searches this equals the memo hit share."""


def read(ctx):
    c = (ctx.get("program") or {}).get("counters", {})
    if not c.get("refine.searched_rows"):
        return None
    return (100.0 * c.get("refine.discarded_rows", 0)
            / c["refine.searched_rows"])
