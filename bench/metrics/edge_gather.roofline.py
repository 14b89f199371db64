"""The edge gather's share of its roofline: the least time its necessary
bytes (``roofline.edge_gather_bytes``) take at the chip's HBM bandwidth,
over the device time of the ``csr_spmv`` kernel's runs in the traced
window. Nothing to read where the kernel did not run.

It times the ``pallas_call`` alone: the gathers that put the kernel's
output back in edge order (``gather_channels``) run as separate XLA
fusions that no name ties to the stage, and fall outside it. A change
that moves that work into the kernel then reads lower here while the
stage gets faster, which ``edges_per_s`` shows."""
import re

from bench import roofline, tracedata

KERNEL = re.compile(r"csr_spmv(\.\d+)?$")


def read(run):
    if not run.trace or not tracedata.device_planes(run.trace):
        return None
    events = tracedata.ops_in_window(run.trace, KERNEL)
    return roofline.roofline_pct(
        len(events) * roofline.edge_gather_bytes(
            run.edge_slots, run.vertices, run.value_channels),
        sum(d for _, _, d in events) / 1e9, run.peaks["hbm_bytes_per_s"])
