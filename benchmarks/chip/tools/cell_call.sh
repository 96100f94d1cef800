# Run one cell once per seed, each run a process of its own, from the
# checkout's root on the machine that holds the chip; the bounds are set
# from two such sets on the same seeds (tags A and B), and traced runs
# take --trace 1.  Stops at the first run that exits nonzero or prints
# no result line.
#
#   bash benchmarks/chip/tools/cell_call.sh CELL OUTDIR TAG TRACE "S1 S2 .."
cell=$1; out=$2; tag=$3; trace=$4; seeds=$5
mkdir -p "$out"
for s in $seeds; do
  python benchmarks/chip/run.py --workload "$cell" --seed "$s" \
    --seconds 45 --trace "$trace" > "$out/${tag}_$s.out" 2> "$out/${tag}_$s.err"
  rc=$?
  echo "$tag $s rc=$rc $(grep -v -i warn "$out/${tag}_$s.err" \
    | grep -E '^window|^occupancy|compilations|^check' | tr '\n' ' ')"
  if [ $rc -ne 0 ] || ! tail -1 "$out/${tag}_$s.out" | grep -q '^{'; then
    tail -20 "$out/${tag}_$s.err"; echo ABORT; exit 1
  fi
  tail -1 "$out/${tag}_$s.out" | cut -c1-400
done
