"""Host time of the device memo's sync per refinement call: the program's
spans ``memo.preload`` (store to device, before a call) and
``memo.drain`` (device to store, after it), over the number of
preloads."""


def read(ctx):
    s = (ctx.get("program") or {}).get("spans", {})
    pre = s.get("memo.preload")
    if not pre or not pre[1]:
        return None
    return 1e3 * (pre[0] + s.get("memo.drain", (0.0, 0))[0]) / pre[1]
