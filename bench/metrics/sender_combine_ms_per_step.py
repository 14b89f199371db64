"""Device milliseconds per superstep of the ``pregel.sender_combine``
stage: the sort of each partition's messages by destination, the
segmented fold, and the compaction to the rows the buckets take,
whatever sorts or folds them. Read from the operations' ``op_name``
scope (``bench.scopes``)."""
from bench import scopes

STAGE = "pregel.sender_combine"


def read(run):
    return scopes.stage_ms_per_step(run.trace, STAGE)
