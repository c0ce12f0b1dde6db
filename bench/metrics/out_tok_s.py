"""Output tokens banked to requests by the ticks of the window, over the
window's length (first tick's start to last tick's end)."""


def read(run):
    w = run.window
    if not w.ticks:
        return None
    return sum(t.banked for t in w.ticks) / w.seconds
