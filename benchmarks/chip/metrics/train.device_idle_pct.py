"""Share of the traced window in which no operation ran on the device."""
import trace_reduce as TR


def read(ctx):
    busy, window = TR.busy_s(ctx["events"])
    return 100.0 * (1.0 - busy / window)
