"""The device's idle share of the window: 1 - busy/window, busy being the
union of device-op intervals of the profiled turns scaled by their share of
the turns (run.py's `device_time`)."""


def read(turns, args, ctx):
    if ctx["busy"] is None:
        return None
    return 100.0 * (1.0 - ctx["busy"]["busy_s"] / ctx["window_s"])
