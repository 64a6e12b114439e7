"""Device ms per decode-step run of the ops under the ``attention`` scope,
its ``cache_update`` apart: norm, q/k/v projections, rope, the attention core
and the output projection (``scopes.py``)."""
import scopes


def read(run, cell):
    return scopes.read(run, cell, "attention")
