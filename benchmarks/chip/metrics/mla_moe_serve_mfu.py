"""Model FLOP/s of the decode steps in the traced window of a latent-
attention and expert model, over the chip's peak: operations the model
requires (``counts_mla_moe.py``) for every token that the window's steps
processed, over the traced window's length."""
import counts_mla_moe as counts
import devtrace
import schedule


def read(run, cell):
    t = run["trace"]
    runs = devtrace.program_runs(t, "decode_step")
    steps = schedule.traced_positions(run["records"], t["first_step"], len(runs))
    if not runs or len(steps) != len(runs):
        return None
    mc, peak = cell.config["model_config"], cell.peak
    flops = sum(counts.decode_flops(mc, b, p) for b, p in steps)
    return 100.0 * flops / devtrace.window_s(t) / (peak["bf16_flops_per_s"] * cell.chips)
