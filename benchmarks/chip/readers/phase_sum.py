"""Sum over ALL served turns of the window of one counter in `phases`."""


def read(turns, args, ctx):
    served = [t for t in turns if t["status"] == 200]
    if not served:
        return None
    return float(sum(t["phases"].get(args["counter"], 0) for t in served))
