"""1 - device busy time over the traced window, in per cent."""


def read(ctx):
    trace = ctx["trace"]
    if not trace.get("busy_s") or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
