"""Device (TPU v5e): share of the traced window in which no operation ran on
the chip while a request was in the program (%): the idle time less the gaps
labelled ``host.no_span``.  The program records one ``sched.request`` span
from each request's submit to its resolution, so a gap with no span open is
one with no request in the program; a program without request spans has no
such reading."""

NO_SPAN = "host.no_span"


def read(ctx):
    dev = ctx["device"]
    if dev is None or dev["window_s"] <= 0:
        return None
    if not any(s.name == "sched.request" for s in ctx["spans"]):
        return None
    no_request = dict(dev["idle_gaps"]).get(NO_SPAN, 0.0)
    return 100.0 * (dev["window_s"] - dev["busy_s"] - no_request) / dev["window_s"]
