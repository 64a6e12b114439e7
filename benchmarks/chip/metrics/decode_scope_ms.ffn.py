"""Device ms per decode-step run of the ops under the ``ffn`` scope: norm,
gate, up and down projections (``scopes.py``)."""
import scopes


def read(run, cell):
    return scopes.read(run, cell, "ffn")
