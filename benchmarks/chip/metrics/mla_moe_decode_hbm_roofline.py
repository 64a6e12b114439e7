"""Share of the roofline that the traced decode steps of a latent-attention
and expert model reach: the least time the chip could take for the work the
model requires at each step's position (``counts_mla_moe.py``: the larger
of operations over peak FLOP/s and bytes over peak HBM bandwidth), over the
device time of the steps."""
import counts_mla_moe as counts
import devtrace
import schedule


def read(run, cell):
    runs = devtrace.program_runs(run["trace"], "decode_step")
    steps = schedule.traced_positions(run["records"], run["trace"]["first_step"], len(runs))
    if not runs or len(steps) != len(runs):
        return None
    mc, peak = cell.config["model_config"], cell.peak
    least = sum(max(counts.decode_bytes(mc, b, p) / peak["hbm_bytes_per_s"],
                    counts.decode_flops(mc, b, p) / peak["bf16_flops_per_s"])
                for b, p in steps)
    return 100.0 * least / (sum(d for _, _, d in runs) / 1e9)
