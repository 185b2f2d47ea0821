"""``step_sync_wait_ms.batch``: ``step_sync_wait_ms`` in the cells judged by ``serve_tput``."""

from benchmark import lib

read = lib.load_module("layer_metrics", "step_sync_wait_ms").read
