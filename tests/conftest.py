"""Test harness: a virtual 8-device CPU mesh.

The reference runs its whole suite under ``mpirun -np 2`` on one host
(reference: .travis.yml; SURVEY.md §4) — multi-node simulated by multiple
processes.  The TPU-native analogue is multiple XLA host devices in ONE
process: ``--xla_force_host_platform_device_count=8`` gives an 8-"chip" CPU
mesh on which every collective compiles and runs exactly as it would over
ICI.

Must run before any jax backend initialization.  The suite always runs on
the CPU mesh, whatever ``JAX_PLATFORMS`` says: a machine with a chip must
not spend it on unit tests.
"""

import faulthandler
import glob
import os

# Belt and braces with pytest's faulthandler plugin (whose
# faulthandler_timeout ini, set in pyproject.toml, prints all stacks
# when a test wedges): enable the handler even under `-p no:...` runs
# so a hard fault or external SIGABRT always dumps stacks instead of
# dying mute.
if not faulthandler.is_enabled():
    faulthandler.enable()

_FLAG = "--xla_force_host_platform_device_count=8"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.parallel.flash_attention import interpret_mode  # noqa: E402
from horovod_tpu.utils.env import compile_cache_dir  # noqa: E402

# One persistent compilation cache for the suite, where chip_smoke.py and
# benchmark/run.py keep theirs: a case builds its own engine, and its
# programs are the ones twenty cases before it lowered.  Small programs are
# kept too (most of the suite's are).  The settings go into the environment
# under jax's own names as well, so the processes that tests start
# (tests/multiprocess_*_worker.py) read them as they import jax.
for _name, _value in (
        ("jax_compilation_cache_dir", compile_cache_dir(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))))),
        ("jax_persistent_cache_min_compile_time_secs", 0.0),
        ("jax_persistent_cache_min_entry_size_bytes", -1)):
    jax.config.update(_name, _value)
    os.environ[_name.upper()] = str(_value)


@pytest.fixture(scope="session", autouse=True)
def _hvd_world():
    """Session-wide init — the analogue of hvd.init() at test-module import
    (reference test/test_torch.py:33)."""
    assert jax.device_count() == 8, (
        "test harness expects 8 virtual CPU devices; check XLA_FLAGS ordering"
    )
    hvd.init()
    # Mosaic has no CPU target: here, and only here, the flash kernels run
    # in the Pallas interpreter.
    with interpret_mode():
        yield
    hvd.shutdown()


@pytest.fixture
def tp_devices(_hvd_world):
    """Devices for `tp`-marked sharded-serving tests.  The conftest
    already forces an 8-virtual-device CPU mesh; if a stray XLA_FLAGS
    ordering (or a real single-chip backend) left fewer than 2 devices,
    skip instead of failing — the subprocess worker test still covers
    the sharded path by re-exec'ing with the flag forced."""
    if jax.device_count() < 2:
        pytest.skip("tensor-parallel tests need >= 2 (faked) devices")
    return jax.devices()


@pytest.fixture(autouse=True)
def _ensure_world(_hvd_world):
    """Re-init the full world if a prior test (or an in-process example
    run — lifecycle tests, scaling/elastic examples) left it shut down or
    on a device subset, so test outcomes never depend on file ordering
    (r4 regression: an example's trailing shutdown() starved a later
    module's world-size-8 assertions)."""
    if not hvd.is_initialized() or hvd.size() != jax.device_count():
        if hvd.is_initialized():
            hvd.shutdown()
        hvd.init()
    yield


@pytest.fixture
def no_compile_cache():
    """The persistent compilation cache off around one test: for a case
    whose outcome hangs on a compilation being made, or on the time one
    takes."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


class HostTrace:
    """What jax's profiler recorded of the host inside a ``with`` block
    (python tracer off, as the benchmark takes its traces): ``lines`` has
    one list per host thread of its ``TraceAnnotation`` spans as
    ``(name, start_ns, end_ns)``, sorted by start."""

    def __init__(self, path: str):
        self.path = path
        self.lines: list[list[tuple]] = []

    def __enter__(self) -> "HostTrace":
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.path, profiler_options=opts)
        return self

    def __exit__(self, *exc) -> None:
        jax.profiler.stop_trace()
        pb, = glob.glob(os.path.join(self.path, "**", "*.xplane.pb"),
                        recursive=True)
        data = jax.profiler.ProfileData.from_file(pb)
        host, = [p for p in data.planes if p.name == "/host:CPU"]
        for line in host.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if not e.name.startswith("$")]
            if evs:         # an enclosing span sorts before what it holds
                self.lines.append(sorted(evs, key=lambda e: (e[1], -e[2])))

    def line_with(self, name: str) -> list[tuple]:
        """The one thread that carries spans called ``name``."""
        line, = [ln for ln in self.lines if any(e[0] == name for e in ln)]
        return line

    @staticmethod
    def children(line: list[tuple], parent: tuple) -> list[tuple]:
        """The spans of ``line`` directly inside ``parent``."""
        kids: list[tuple] = []
        for e in line:
            if (e is not parent and parent[1] <= e[1] and e[2] <= parent[2]
                    and (not kids or e[1] >= kids[-1][2])):
                kids.append(e)
        return kids


@pytest.fixture
def host_trace(tmp_path):
    """``with host_trace() as tr:`` takes a profiler trace of the block;
    one at a time in a process."""
    n = iter(range(1000))
    return lambda: HostTrace(str(tmp_path / f"xplane{next(n)}"))
