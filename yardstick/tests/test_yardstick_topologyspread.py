"""The topologyspread-5000n deployment's rehearsal twin through run.py on
the CPU. Since PR 35 a gang round commits what ``maxSkew`` leaves room for
in each zone, and the full-size cell places every pod on its first attempt;
here ``maxGangRounds`` 2 at batches of 16 still runs about one batch in four
out of rounds (its proposals bunch in one zone and want a third round), so a
few of the 36 pods are called unschedulable, explained, backed off and
retried, and the three metrics the cell brought find something to read. The
reference, the generator and the counter are held by
tests/test_topologyspread_deployment.py."""

from conftest import KEYS, run_cell

NEW = {"gang_rounds_exhausted_share.burst", "unschedulable_per_kpod.burst",
       "explain_ms_per_drain.burst"}


def test_the_rehearsal_binds_every_pod_the_long_way_round():
    rc, lines, last = run_cell("rehearsal-topologyspread.burst",
                               2147483659, 30)
    assert rc == 0, lines[-5:]
    assert KEYS <= set(last)
    assert last["correct"] is True and last["failed"] == 0, lines[-4:]
    assert last["attempted"] == 36
    assert set(last["metrics"]) == {"bound_rate", "setup_s"}


def test_a_traced_rehearsal_reports_the_cells_three_metrics():
    rc, lines, last = run_cell("rehearsal-topologyspread.burst",
                               3000000019, 30, trace=1)
    assert rc == 0, lines[-5:]
    assert last["correct"] is True and last["failed"] == 0, lines[-4:]
    got = last["metrics"]
    assert NEW <= set(got)
    assert 0.0 < got["gang_rounds_exhausted_share.burst"]["value"] <= 1.0
    assert got["unschedulable_per_kpod.burst"]["value"] > 0.0
    assert got["explain_ms_per_drain.burst"]["value"] > 0.0
    assert got["gang_rounds_per_batch.burst"]["value"] == 2.0
