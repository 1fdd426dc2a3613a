"""Packets whose verdicts reached the host inside the window, over the
window's seconds (host clock): all the work over all the time."""


def read(ctx):
    w = ctx.served.window
    return w.answered_in_window / w.seconds
