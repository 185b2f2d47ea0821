"""``step_host_ms.sdar``: ``step_host_ms`` in ``sdar_blockgen`` (the step log's
phases other than ``device_sync``, the ``unmask`` dispatch among them)."""

from benchmark import lib

read = lib.load_module("layer_metrics", "step_host_ms").read
