"""Device ms per decode-step run of the ops under the ``moe_route`` scope of
the MoE layer: the router (float32 logits over all experts, softmax, group-
limited top-k) (``moe_scopes.py``)."""
import moe_scopes


def read(run, cell):
    return moe_scopes.read(run, cell, "route")
