"""The highest governor level of the window."""


def read(ctx):
    before, after = ctx["before"].get("overload"), ctx["after"].get("overload")
    if not before or not after or not after.get("governor"):
        return None
    gov, tick0 = after["governor"], before["governor"]["ticks"]
    return max([gov["level"], before["governor"]["level"]]
               + [t["to"] for t in gov["transitions"] if t["tick"] >= tick0])
