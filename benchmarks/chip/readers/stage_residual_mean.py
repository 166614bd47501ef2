"""Mean over the served, unprofiled turns of the window of one stage key less
the sum of others: `phases[args["of"]] - sum(phases[k] for k in
args["minus"])`, times `args["scale"]` (seconds to ms). What a stage spent
where none of the stages inside it was counting. A turn whose `phases` lacks
one of the keys is left out, as `stage_mean` leaves it out, so a program from
before them gives nothing to read. No clamp: the inner stages tile the outer
one, so a negative value is a second that two of them counted, to mend."""


def read(turns, args, ctx):
    keys = [args["of"], *args["minus"]]
    values = [
        t["phases"][args["of"]] - sum(t["phases"][k] for k in args["minus"])
        for t in turns
        if t["status"] == 200 and not t["profiled"] and all(k in t["phases"] for k in keys)
    ]
    if not values:
        return None
    return args.get("scale", 1.0) * sum(values) / len(values)
