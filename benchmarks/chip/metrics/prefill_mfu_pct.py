"""Model FLOPs of the prompt tokens the chunk loop computed (past the
prefix-cache-covered blocks; padding rows and chunk tails not counted)
over the device time of the prefill programs times the chip's peak."""
from harness.readings import program_s, share


def read(run):
    t = program_s(run, "prefill")
    return share(run.prefill_flops(), t * run.peaks["bf16_flops_per_s"])
