"""Device (TPU v5e): share of the traced window in which no operation ran on
the chip, 1 - union of operation intervals / window (%)."""


def read(ctx):
    dev = ctx["device"]
    if dev is None or dev["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
