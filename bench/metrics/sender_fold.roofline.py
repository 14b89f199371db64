"""The sender fold's share of its roofline: the least time its necessary
bytes take at the chip's HBM bandwidth, over the device time of the
``segment_combine`` kernel's runs in the traced window.

Only live rows count, not the stream's capacity (keys
``s32[P, M/128, 128]``, payload ``f32[P, D, M/128, 128]`` in each run's
operand shapes): a run reads at most one message per edge slot, and
writes one folded row per receiving vertex. Both counts are exact where
every edge slot sends in every superstep, as under the full-outer plan;
under a frontier they are upper bounds, so the metric lists only
full-outer cells."""
import re

from bench import roofline, tracedata

KERNEL = re.compile(r"segment_combine(\.\d+)?$")


def read(run):
    if not run.trace or not tracedata.device_planes(run.trace):
        return None
    events = tracedata.ops_in_window(run.trace, KERNEL)
    need = 0
    for name, _, _ in events:
        (_, keys), (_, payload) = tracedata.operand_shapes(name)[:2]
        capacity = 1
        for d in keys:
            capacity *= d
        rows = min(capacity, run.edge_slots)
        need += roofline.sender_fold_bytes(rows, min(rows, run.receivers),
                                           payload[1])
    return roofline.roofline_pct(need, sum(d for _, _, d in events) / 1e9,
                                 run.peaks["hbm_bytes_per_s"])
