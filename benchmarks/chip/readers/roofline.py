"""A payload's share of the chip's roofline, from the device trace: the
least time the chip could take for the floor that the payload's file states
(bytes over peak bytes/s, or operations over peak FLOP/s, whichever
`args["bound"]` names), over the median device-busy time of the WHOLE
profiled turn. It reads the same work whatever implements it. Nothing
traced, nothing returned: never 0."""

import statistics


def read(turns, args, ctx):
    if ctx["busy"] is None:
        return None
    for name in args["payloads"]:
        busy = ctx["busy"]["turn_busy"].get(name)
        params = next((t["params"] for t in turns if t["payload"] == name), None)
        if not busy or params is None:
            continue
        floor = ctx["evaluate"](ctx["payloads"][name]["floor"][args["bound"]], params)
        least = floor / ctx["peaks"][args["peak"]]
        return 100.0 * least / statistics.median(busy)
    return None
