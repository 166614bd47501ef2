"""Mean over the served, unprofiled turns of the window of the sum of the
stamped phases named in `args["phases"]`, times `args["scale"]` (seconds to
ms). Profiled turns are left out: the profiler's start and stop sit inside
their exec phase."""


def read(turns, args, ctx):
    values = [
        sum(t["phases"][k] for k in args["phases"])
        for t in turns if t["status"] == 200 and not t["profiled"]
    ]
    if not values:
        return None
    return args.get("scale", 1.0) * sum(values) / len(values)
