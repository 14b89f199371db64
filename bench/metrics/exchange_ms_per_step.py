"""Device milliseconds of the exchange per superstep: the operations of
the ``jit_exchange`` program (``run_sharded``'s all_to_all of the message
buckets, with the reorder around it) per run in the traced window, on
the chip where they took longest. Nothing to read where no such program
ran."""
from bench import chips, tracedata

PROGRAM = "jit_exchange"


def read(run):
    if not run.trace or not tracedata.device_planes(run.trace):
        return None
    per_chip = [sum(ns for _, _, ns in runs) / len(runs) / 1e6
                for runs in chips.program_runs(run.trace, PROGRAM) if runs]
    return max(per_chip, default=None)
