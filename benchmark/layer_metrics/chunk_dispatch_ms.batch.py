"""``chunk_dispatch_ms.batch``: ``chunk_dispatch_ms`` in the cells judged by ``serve_tput``."""

from benchmark import lib

read = lib.load_module("layer_metrics", "chunk_dispatch_ms").read
