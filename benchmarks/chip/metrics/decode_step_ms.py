"""Mean device time of one run of the decode step program in the trace."""
import devtrace


def read(run, cell):
    runs = devtrace.program_runs(run["trace"], "decode_step")
    return 1e3 * sum(d for _, _, d in runs) / len(runs) / 1e9 if runs else None
