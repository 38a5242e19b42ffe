"""tests/conftest.py's C++ build fixture survives a tree that was copied
with its build directory: cmake refuses a cache made at another path
("CMakeCache.txt directory ... is different"), which failed every
daemon-backed test in its fixture."""

from __future__ import annotations

import shutil
import subprocess

import pytest

import conftest

pytestmark = pytest.mark.skipif(
    not (shutil.which("cmake") and shutil.which("ninja")),
    reason="needs cmake and ninja")


def copy_sources(dest):
    dest.mkdir()
    shutil.copy(conftest.REPO_ROOT / "CMakeLists.txt", dest)
    shutil.copytree(conftest.REPO_ROOT / "src", dest / "src")
    return dest


def test_copied_tree_with_its_build_directory_configures(tmp_path):
    first = copy_sources(tmp_path / "first")
    conftest._configure_cpp(first, first / "build")
    cache = (first / "build" / "CMakeCache.txt").read_text()
    assert f"CMAKE_HOME_DIRECTORY:INTERNAL={first}" in cache

    second = tmp_path / "second"
    shutil.copytree(first, second)
    # what the fixture did before: cmake over the copied cache, exit 1
    plain = subprocess.run(
        ["cmake", "-S", str(second), "-B", str(second / "build"), "-G",
         "Ninja"], capture_output=True, text=True)
    assert plain.returncode != 0 and "CMakeCache.txt" in plain.stderr

    conftest._configure_cpp(second, second / "build")
    cache = (second / "build" / "CMakeCache.txt").read_text()
    assert f"CMAKE_HOME_DIRECTORY:INTERNAL={second}" in cache
    assert (second / "build" / "build.ninja").is_file()


def test_own_cache_is_kept(tmp_path):
    tree = copy_sources(tmp_path / "tree")
    conftest._configure_cpp(tree, tree / "build")
    assert conftest._forget_foreign_cache(tree / "build", tree) is False
    assert conftest._forget_foreign_cache(tmp_path / "absent", tree) is False
    assert (tree / "build" / "CMakeCache.txt").is_file()
