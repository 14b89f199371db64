"""Device milliseconds per superstep of the ``pregel.route`` stage:
bucketing the messages by owning partition and the exchange (the
``all_to_all`` under ``shard_map``). Read from the operations'
``op_name`` scope (``bench.scopes``)."""
from bench import scopes

STAGE = "pregel.route"


def read(run):
    return scopes.stage_ms_per_step(run.trace, STAGE)
