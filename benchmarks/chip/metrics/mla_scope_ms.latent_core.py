"""Device ms per decode-step run of the ops under the ``latent_core`` scope
of latent attention: the scores against the latent cache and rope keys, the
softmax, and the weighted latent sum (``moe_scopes.py``)."""
import moe_scopes


def read(run, cell):
    return moe_scopes.read(run, cell, "latent_core")
