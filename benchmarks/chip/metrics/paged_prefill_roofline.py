"""Least time of the paged-prefill kernel's calls (per layer and round,
for the real query rows and the K/V they attend) over its time in the
trace's prefill programs."""
from harness.readings import kernel_s, share


def read(run):
    return share(run.paged_prefill_min_s(),
                 kernel_s(run, "prefill", "paged_prefill"))
