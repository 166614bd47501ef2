"""Mean over the served, unprofiled turns of the client's time minus the
stamped phases in `args["phases"]`: what the turn spent where no span is
yet (pool acquire, /reset turnover, HTTP), read from outside."""


def read(turns, args, ctx):
    values = [
        t["client_s"] - sum(t["phases"][k] for k in args["phases"])
        for t in turns if t["status"] == 200 and not t["profiled"]
    ]
    if not values:
        return None
    return args.get("scale", 1.0) * sum(values) / len(values)
