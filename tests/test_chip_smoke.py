"""What chip_smoke.py and its helpers do where there is no chip.

The smoke itself runs on a TPU through the chip tool; here, on the CPU, the
contract is the other half: it fails, fast and by name, and prints no
result. Also the compile-cache helper every compiling entry point shares,
and that the capture path's Python children stay off JAX (a chip belongs
to one process, the job).
"""

import os
import pathlib
import shutil
import subprocess
import sys
import time

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _run(args, env=None, cwd=REPO_ROOT, timeout=120):
    return subprocess.run(
        [sys.executable, *map(str, args)], capture_output=True, text=True,
        timeout=timeout, cwd=str(cwd), env={**os.environ, **(env or {})})


def test_refuses_the_cpu_within_seconds_and_names_it():
    t0 = time.time()
    proc = _run([REPO_ROOT / "chip_smoke.py"], env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert time.time() - t0 < 30
    assert "platform 'cpu', not 'tpu'" in proc.stderr, proc.stderr[-1000:]
    assert '"ok"' not in proc.stdout


def test_fails_without_the_program_beside_it(tmp_path):
    shutil.copy(REPO_ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _run([tmp_path / "chip_smoke.py"], env={"JAX_PLATFORMS": ""},
                cwd=tmp_path)
    assert proc.returncode != 0
    assert "not a checkout" in proc.stderr, proc.stderr[-1000:]
    assert '"ok"' not in proc.stdout


_CACHE_PROBE = (
    "from dynolog_tpu._jaxinit import enable_compile_cache\n"
    "import jax\n"
    "print(enable_compile_cache()); print(enable_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n")


def test_compile_cache_is_a_fixed_path_inside_the_checkout():
    proc = _run(["-c", _CACHE_PROBE],
                env={"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": ""})
    assert proc.returncode == 0, proc.stderr[-1000:]
    first, second, configured = proc.stdout.split()
    assert first == second == configured == str(REPO_ROOT / ".jax_cache")


def test_compile_cache_placed_from_outside_is_left_alone(tmp_path):
    proc = _run(["-c", _CACHE_PROBE],
                env={"JAX_PLATFORMS": "cpu",
                     "JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert proc.returncode == 0, proc.stderr[-1000:]
    # JAX read the variable itself; the helper set nothing over it.
    assert proc.stdout.split() == [str(tmp_path)] * 3


def test_capture_path_children_import_no_jax():
    proc = _run(["-c",
                 "import sys\n"
                 "from dynolog_tpu.trace import write_derived_artifacts\n"
                 "import dynolog_tpu.cluster.unitrace\n"
                 "assert 'jax' not in sys.modules, 'jax imported'\n"])
    assert proc.returncode == 0, proc.stderr[-1000:]
