#!/usr/bin/env bash
# clean_proof.sh — chip_smoke.py from the committed files alone.
#
#   scripts/clean_proof.sh prepare      here: stage everything and unpack what
#                                       git would commit into _clean/ (ignored)
#   chiprun --timeout 1500 -- bash scripts/clean_proof.sh run
#                                       there: the smoke from _clean/ on an empty
#                                       compile cache, again on the warm one,
#                                       then the script alone in a directory
#
# Outputs land in chiprun_out/clean_{cold,warm}.{out,err} and alone.out. The
# run exits 0 only if both smoke runs passed and the lone script failed.
set -u
cd "$(dirname "$0")/.."

case "${1:-}" in
prepare)
    git add -A
    rm -rf _clean && mkdir _clean
    git archive "$(git write-tree)" | tar -x -C _clean
    echo "_clean/: $(find _clean -type f | wc -l) files," \
         "$(du -sm _clean | cut -f1) MiB"
    ;;
run)
    mkdir -p chiprun_out
    rc=0
    for pass in cold warm; do
        (cd _clean && python3 chip_smoke.py) \
            > "chiprun_out/clean_$pass.out" 2> "chiprun_out/clean_$pass.err"
        code=$?
        echo "clean $pass: exit $code"
        tail -n 2 "chiprun_out/clean_$pass.out" | cut -c1-400
        [ "$code" -eq 0 ] || rc=1
    done
    alone=$(mktemp -d)
    cp _clean/chip_smoke.py "$alone/"
    (cd "$alone" && python3 chip_smoke.py) > chiprun_out/alone.out 2>&1
    code=$?
    echo "alone in a directory: exit $code"
    tail -n 2 chiprun_out/alone.out | cut -c1-300
    [ "$code" -ne 0 ] || rc=1
    rm -rf "$alone"
    exit $rc
    ;;
*)
    sed -n '2,12p' "$0"
    exit 2
    ;;
esac
