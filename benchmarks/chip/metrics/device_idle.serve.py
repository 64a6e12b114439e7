"""Share of the traced window in which no operation ran on the device."""
import devtrace


def read(run, cell):
    t = run["trace"]
    return 100.0 * (1.0 - devtrace.busy_s(t) / devtrace.window_s(t))
