"""``decode_step_ms`` less the five scoped parts: the embedding, ops under no
scope, and the gaps between ops inside the program (``scopes.py``)."""
import scopes


def read(run, cell):
    return scopes.read(run, cell, "other")
