"""Device ms per decode-step run of the ops whose innermost scope is
``layers`` itself: the layer scan's slicing and carry of the stacked weights and
cache, and the copies of its results (``scopes.py``)."""
import scopes


def read(run, cell):
    return scopes.read(run, cell, "layer_loop")
