#!/bin/bash
# Manual g++ build for containers without cmake/ninja (see
# .claude/skills/verify — "Round-6 additions"). Incremental: a source
# file is recompiled only when newer than its object. Produces
# build/src/{dynologd,dyno} and build/tests/<every test main>.
# Usage: scripts/manual_build.sh [--tests]
set -e
cd "$(dirname "$0")/.."
mkdir -p build/obj build/src build/tests
CXX=${CXX:-g++}
FLAGS="-std=c++17 -O2 -g -I. -pthread"

# Library sources: the add_library(dynotpu_core ...) list in
# src/CMakeLists.txt, parsed so the two lists can't drift.
srcs=$(sed -n '/add_library(dynotpu_core STATIC/,/)/p' src/CMakeLists.txt |
  grep -oE '[a-zA-Z0-9_/]+\.cpp')
objs=""
for s in $srcs; do
  obj="build/obj/$(echo "$s" | tr / _).o"
  objs="$objs $obj"
  if [ ! -f "$obj" ] || [ "src/$s" -nt "$obj" ] ||
     [ -n "$(find src -name '*.h' -newer "$obj" -print -quit)" ]; then
    echo "CXX src/$s"
    $CXX $FLAGS -c "src/$s" -o "$obj"
  fi
done
ar rcs build/obj/libdynotpu_core.a $objs

echo "LINK build/src/dynologd"
$CXX $FLAGS src/daemon/Main.cpp build/obj/libdynotpu_core.a \
  -o build/src/dynologd -lpthread -ldl
echo "LINK build/src/dyno"
$CXX $FLAGS src/cli/dyno.cpp build/obj/libdynotpu_core.a \
  -o build/src/dyno -lpthread -ldl

if [ "$1" = "--tests" ]; then
  for t in src/tests/*Test.cpp; do
    name=$(basename "$t" .cpp)
    out="build/tests/$name"
    if [ ! -f "$out" ] || [ "$t" -nt "$out" ] ||
       [ build/obj/libdynotpu_core.a -nt "$out" ]; then
      echo "LINK $out"
      $CXX $FLAGS "$t" build/obj/libdynotpu_core.a -o "$out" \
        -lpthread -ldl
    fi
  done
fi
echo "build OK"
