"""Device ms per decode-step run of the ops under the ``logits_sample`` scope:
the final norm, the head and the sampling (``scopes.py``)."""
import scopes


def read(run, cell):
    return scopes.read(run, cell, "logits_sample")
