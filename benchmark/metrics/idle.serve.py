"""Share of the traced slice in which no operation ran on the device, %."""


def read(ctx):
    d = ctx["device_trace"]
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])
