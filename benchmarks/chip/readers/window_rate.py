"""Turns of the window that were served and equal to the reference, over the
window's seconds: all the work over all the time."""


def read(turns, args, ctx):
    if not any(t["status"] == 200 for t in turns):
        return None
    return sum(1 for t in turns if t.get("equal")) / ctx["window_s"]
