# PR 37, call 15 (second round): the two fixtures by the recorder as committed (call 12's died after the
# first, on a key the reader no longer has)
mkdir -p chiprun_out/pr37/call15
python3 benchmark/tests/record_pair_fixture.py > chiprun_out/pr37/call15/record_pair_fixture.log 2> chiprun_out/pr37/call15/record_pair_fixture.err
echo "record_pair_fixture rc=$? after $SECONDS s"; grep -v "^I0000\|^WARNING\|^W0000\|^xplane_join" chiprun_out/pr37/call15/record_pair_fixture.log | cut -c1-1200
