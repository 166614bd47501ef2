"""Median of the client's time of all served turns of the window, in ms."""

import statistics


def read(turns, args, ctx):
    values = [t["client_s"] for t in turns if t["status"] == 200]
    return 1000.0 * statistics.median(values) if values else None
