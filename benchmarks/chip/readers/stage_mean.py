"""Mean over the served, unprofiled turns of the window of the sum of the
stage keys named in `args["phases"]`, times `args["scale"]` (seconds to ms):
`phase_mean` for keys that only a program with the stage spans stamps. A turn
whose `phases` lacks one of the keys is left out, so a program from before
them (the parent of the PR that brought them) gives nothing to read: the
metric is then left out of the line, never 0 and never an error."""


def read(turns, args, ctx):
    values = [
        sum(t["phases"][k] for k in args["phases"])
        for t in turns
        if t["status"] == 200 and not t["profiled"] and all(k in t["phases"] for k in args["phases"])
    ]
    if not values:
        return None
    return args.get("scale", 1.0) * sum(values) / len(values)
