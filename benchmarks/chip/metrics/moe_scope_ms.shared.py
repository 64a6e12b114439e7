"""Device ms per decode-step run of the ops under the ``shared_experts`` scope
of the MoE layer: the shared experts (``moe_scopes.py``)."""
import moe_scopes


def read(run, cell):
    return moe_scopes.read(run, cell, "shared")
