"""Device milliseconds per superstep of the ``pregel.gather`` stage: the
source-value gather of every edge slot, whatever implements it (the
``csr_spmv`` kernel and the gathers that put its output back in edge
order, or XLA's own gathers), with ``src_vid`` and the program's send.
Read from the operations' ``op_name`` scope (``bench.scopes``)."""
from bench import scopes

STAGE = "pregel.gather"


def read(run):
    return scopes.stage_ms_per_step(run.trace, STAGE)
