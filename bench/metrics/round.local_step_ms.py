"""round.local_step_ms: device time of the ops under the round's
``local_step`` name scope (each local step's forward, loss and backward;
``launch/fl_train.py``), per round and per chip, in ms (``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.read_scope(ctx, "local_step")
