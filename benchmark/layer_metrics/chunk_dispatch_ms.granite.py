"""``chunk_dispatch_ms.granite``: ``chunk_dispatch_ms`` in ``granite_toolcalls``."""

from benchmark import lib

read = lib.load_module("layer_metrics", "chunk_dispatch_ms").read
