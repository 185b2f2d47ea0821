"""``step_sync_wait_ms.granite``: ``step_sync_wait_ms`` in ``granite_toolcalls``."""

from benchmark import lib

read = lib.load_module("layer_metrics", "step_sync_wait_ms").read
