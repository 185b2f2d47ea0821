"""A tiny copy of the benchmark for rehearsals on the CPU: the same files,
with the configurations and the traffic cut to toy sizes, run through
``run.py``'s own ``main`` in a child process whose look for a chip (and, in
a traced run, whose profiler capture, which needs a chip's trace) the child's
start-up lines replace.  No flag and no environment variable of the benchmark
is involved: the patching is the test's."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
RECORDED = os.path.join(ROOT, "benchmark", "tests", "data",
                        "trace_v5e_serve.json")

TINY_RESNET = {"stage_sizes": [1, 1, 1, 1], "width": 8, "num_classes": 10,
               "image_size": 32, "compute_dtype": "float32"}
TINY_MISTRAL = {"hidden_size": 64, "intermediate_size": 128,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256}
TINY_SHAPES = {"own_prompt_quantiles": [[0.0, 3], [0.5, 9], [1.0, 30]],
               "output_quantiles": [[0.0, 2], [0.5, 4], [1.0, 8]]}


def _edit(path: str, fn) -> None:
    with open(path) as f:
        d = json.load(f)
    fn(d)
    with open(path, "w") as f:
        json.dump(d, f)


def make_copy(dst: str) -> str:
    """``dst/BENCHMARK.json`` and ``dst/benchmark`` at toy sizes."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = os.path.join(dst, "benchmark")
    def resnet(d):
        d.update(TINY_RESNET)
        d["optimizer"]["lr_per_chip"] = 0.001     # 8 images overfit in a step

    _edit(os.path.join(b, "configs", "resnet50.json"), resnet)
    _edit(os.path.join(b, "configs", "mistral-7b-v0.3.json"),
          lambda d: d.update(TINY_MISTRAL))
    for mix in ("synthetic_b128", "synthetic_b128_dp"):
        _edit(os.path.join(b, "traffic", mix + ".json"),
              lambda d: d.update(per_chip_batch=8, warm_steps=1,
                                 trace_s=0.2))

    def chat(d):
        d["engine"].update(n_slots=4, max_len=64, chunk=8)
        d["arrivals"]["rate_rps"] = 12.0
        d.update(lead_in_s=0.5, trace_s=0.3)
        d["shapes"].update(TINY_SHAPES)
        d["shapes"]["system_prompts"] = {"count": 2, "tokens": 8}
        d["check"] = {"sample": 3, "pad_to": 64}

    def longdoc(d):
        d["engine"].update(n_slots=2, max_len=64, chunk=8)
        d.update(requests_per_window_second=6.0, trace_s=0.3)
        d["shapes"].update(TINY_SHAPES)
        d["shapes"]["tail_tokens"] = 4
        d["check"] = {"sample": 3, "pad_to": 64}

    _edit(os.path.join(b, "traffic", "chat.json"), chat)
    _edit(os.path.join(b, "traffic", "longdoc.json"), longdoc)
    return dst


CHILD = r"""
import json, sys
sys.path.insert(0, {copy!r})
import benchmark.run as run
from benchmark import capture, trace_reduce
assert run.ROOT == {copy!r}, run.ROOT
{extra}

def cpu_for_tpu(chips):
    import jax
    assert jax.device_count() >= chips, (jax.device_count(), chips)
    return {{"platform": "tpu", "kind": "TPU v5 lite",
            "count": jax.device_count()}}
if {patch_device}:
    run.check_device = cpu_for_tpu

class Recorded(capture.WindowTrace):
    # the CPU's trace has no chip in it: start and stop for real, then
    # reduce the recorded chip trace in its place
    def reduce(self):
        import shutil
        shutil.rmtree(self.dir, ignore_errors=True)
        with open({recorded!r}) as f:
            return trace_reduce.reduce(json.load(f), self.spans)
capture.WindowTrace = Recorded
sys.exit(run.main({argv!r}))
"""


def run_in_copy(copy: str, workload: str, *, seed: int = 3,
                seconds: float = 1.5, trace: int = 0, devices: int = 4,
                patch_device: bool = True, extra: str = "",
                timeout: int = 600) -> tuple:
    """``(exit code, last line parsed or None, stdout, stderr)``."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_COMPILATION_CACHE_DIR=os.path.join(copy, ".jax_cache"))
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    code = CHILD.format(copy=copy, argv=argv, recorded=RECORDED,
                        patch_device=patch_device, extra=extra)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=copy,
                       capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    last = None
    if p.returncode == 0 and lines:
        last = json.loads(lines[-1])
    return p.returncode, last, p.stdout, p.stderr
