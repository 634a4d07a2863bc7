"""Share of the traced serving window in which no operation ran on the
chip: 1 - (union of the device-op intervals) / window, in percent."""


def read(ctx):
    t = ctx.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
