"""Least time of the paged-decode kernel's calls (per layer and step, the
larger of FLOPs over peak and bytes over bandwidth, for the K/V each
running row attends) over its time in the trace's decode programs."""
from harness.readings import kernel_s, share


def read(run):
    return share(run.paged_decode_min_s(),
                 kernel_s(run, "decode", "paged_decode"))
