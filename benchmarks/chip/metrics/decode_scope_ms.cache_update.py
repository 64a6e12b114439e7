"""Device ms per decode-step run of the ops under the ``cache_update`` scope:
the writes of the new key and value into the cache (``scopes.py``)."""
import scopes


def read(run, cell):
    return scopes.read(run, cell, "cache_update")
