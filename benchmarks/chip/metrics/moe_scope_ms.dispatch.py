"""Device ms per decode-step run of the ops under the ``moe_dispatch`` scope
of the MoE layer: the sort of the (token, expert) pairs by expert, the
gather of their rows, and the weighted combine (``moe_scopes.py``)."""
import moe_scopes


def read(run, cell):
    return moe_scopes.read(run, cell, "dispatch")
