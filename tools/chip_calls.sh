#!/bin/sh
# What a builder sends through the chip tool (one process on the chip
# at a time; everything that shares a compile goes into one call):
#   tools/chip_calls.sh smoke   cold run, then warm-cache run
#   tools/chip_calls.sh mesh    chip_smoke.py --mesh (four chips)
# Output too long for the tool's tail lands in chiprun_out/.
set -u
mkdir -p chiprun_out
echo "JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-<unset>}"
run() {  # name, then the command
    name=$1; shift
    "$@" > "chiprun_out/$name.out" 2> "chiprun_out/$name.err"
    echo "== $name rc=$?"
    tail -n 25 "chiprun_out/$name.out"
    tail -n 5 "chiprun_out/$name.err"
}
case "${1:-smoke}" in
smoke)
    run smoke_cold python3 chip_smoke.py
    run smoke_warm python3 chip_smoke.py
    ;;
mesh)
    run smoke_mesh python3 chip_smoke.py --mesh
    ;;
esac
