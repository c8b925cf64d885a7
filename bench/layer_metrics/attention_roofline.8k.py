"""Splash attention kernels' (forward, dq, dkv) share of their roofline in
the 8k-token hybrid cell: least time of their calls at the chip's peaks
over their device time; moves train_tokens_per_s.  The context that
bench/drivers/train.py gives its readers does not name the cell, so this
file, listed for this cell alone, names the model and traffic whose calls
it counts."""
from bench import kernel_time
from bench.costs import hybrid_moe_lm as costs

MODEL, JOB = costs.cell("nemotron-3-nano-30b-a3b", "pretrain_8k")


def read(ctx):
    return kernel_time.kernel_roofline(
        ctx, "attention", costs.splash_calls(MODEL, JOB["batch"], JOB["seq"]))
