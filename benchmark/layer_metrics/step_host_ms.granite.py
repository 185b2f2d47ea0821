"""``step_host_ms.granite``: ``step_host_ms`` in ``granite_toolcalls``."""

from benchmark import lib

read = lib.load_module("layer_metrics", "step_host_ms").read
