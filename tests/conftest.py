"""Pytest harness for dynolog_tpu.

- Builds the C++ tree (cmake + ninja) once per session.
- Forces JAX onto a virtual 8-device CPU mesh for sharding tests, mirroring
  how the driver dry-runs the multichip path.
"""

import pathlib
import subprocess

# Force the virtual 8-device CPU mesh before any backend initializes (fast +
# deterministic; the real chip is for chip_smoke.py and perfbench/).
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from dynolog_tpu._jaxinit import force_cpu_devices

force_cpu_devices(8)

import pytest

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
BUILD_DIR = REPO_ROOT / "build"


def _forget_foreign_cache(build_dir: pathlib.Path, source_dir: pathlib.Path) -> bool:
    """A build/ configured for another checkout (a tree that was copied with
    its build directory) makes cmake refuse to configure: "CMakeCache.txt
    directory ... is different". Drop the cache, keep nothing of it: the
    objects were compiled from the other tree's paths. True when dropped."""
    import shutil

    cache = build_dir / "CMakeCache.txt"
    try:
        text = cache.read_text(errors="replace")
    except OSError:
        return False
    home = next((line.split("=", 1)[1] for line in text.splitlines()
                 if line.startswith("CMAKE_HOME_DIRECTORY:")), None)
    if home is None or pathlib.Path(home).resolve() == source_dir.resolve():
        return False
    cache.unlink()
    shutil.rmtree(build_dir / "CMakeFiles", ignore_errors=True)
    return True


def _configure_cpp(source_dir: pathlib.Path, build_dir: pathlib.Path) -> None:
    _forget_foreign_cache(build_dir, source_dir)
    subprocess.run(
        [
            "cmake",
            "-S",
            str(source_dir),
            "-B",
            str(build_dir),
            "-G",
            "Ninja",
            "-DCMAKE_BUILD_TYPE=Release",
        ],
        check=True,
        capture_output=True,
    )


def _build_cpp() -> None:
    # DYNO_PREBUILT=1: trust existing build/src binaries instead of
    # requiring cmake/ninja — for containers that build the C++ tree by
    # other means (manual g++, a cached image layer). Explicitly opt-in:
    # stale binaries silently passing for new code would be worse than a
    # missing-toolchain error.
    import fcntl
    import os

    if os.environ.get("DYNO_PREBUILT") and (BUILD_DIR / "src" / "dynologd").exists():
        return
    BUILD_DIR.mkdir(exist_ok=True)
    # One build at a time: every xdist worker has a session of its own, and
    # two ninjas in one directory overwrite each other's objects.
    with open(BUILD_DIR / ".pytest_build.lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        _configure_cpp(REPO_ROOT, BUILD_DIR)
        subprocess.run(
            ["cmake", "--build", str(BUILD_DIR)], check=True, capture_output=True
        )


@pytest.fixture(scope="session")
def cpp_build() -> pathlib.Path:
    """Configured+built C++ tree; returns the build dir."""
    _build_cpp()
    return BUILD_DIR


@pytest.fixture(scope="session")
def bin_dir(cpp_build: pathlib.Path) -> pathlib.Path:
    return cpp_build / "src"


# Opt-in slow lane (DYNO_SLOW_TESTS=1): multi-minute tests whose coverage
# is redundant with a cheaper default-lane test (for the multichip dryrun:
# test_sharded_train_step_matches_single_device, tests/test_sharded_job.py).
# Keeps the default suite's wall time bounded on the 1-core CI host
# without deleting coverage — CI's slow job (and any dev with the env
# var) still runs them.
import os  # noqa: E402

slow_lane = pytest.mark.skipif(
    not os.environ.get("DYNO_SLOW_TESTS"),
    reason="slow lane: set DYNO_SLOW_TESTS=1 to run",
)
