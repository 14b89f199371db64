"""Device milliseconds per superstep of the ``pregel.edge_gate`` stage:
the gather of each edge slot's send gate from its source vertex (and,
under the left-outer plan, the compaction of the edge stream to the
frontier's edges). Read from the operations' ``op_name`` scope
(``bench.scopes``)."""
from bench import scopes

STAGE = "pregel.edge_gate"


def read(run):
    return scopes.stage_ms_per_step(run.trace, STAGE)
