"""``step_host_ms.batch``: ``step_host_ms`` in the cells judged by ``serve_tput``."""

from benchmark import lib

read = lib.load_module("layer_metrics", "step_host_ms").read
