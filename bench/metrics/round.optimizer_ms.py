"""round.optimizer_ms: device time of the ops under the round's
``optimizer`` name scope (global-norm clipping and the AdamW update of
``optim/adamw.py``), per round and per chip, in ms (``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.read_scope(ctx, "optimizer")
