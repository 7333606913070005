"""Certified solutions completed per second. The clients stop sending at
``--seconds``; the window ends when the last request sent before then
completes, so every request in it is whole and the rate is all of the
window's work over all of its time."""


def read(r):
    return r.solutions / r.window_s if r.solutions else None
