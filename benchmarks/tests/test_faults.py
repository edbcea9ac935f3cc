"""The control and the planted faults: the rest of a run, with the harness's
look for a chip skipped (`rehearse`) and the timed path broken underneath,
has to come out as not correct; the same run unbroken as correct.

The control breaks the guarantee a later PR would be tempted to break — it
sheds packets to keep its deadlines: the server's own fault injector drops
1 % of ingest (`faults.drop_pct`), which is what shedding under load looks
like from a subscriber's socket. The faults: an answer altered where it is
produced (a payload byte flipped at staging) and a device step that returns
its state unchanged. (The injector's `dup_pct` is no fault to plant: the
server's ingest removes a repeated SN before the tick sees it, and such a
run reads clean, as it should.)
"""

import json

import pytest

from benchmarks import run

CELLS = [w["name"] for w in json.loads((run.ROOT / "BENCHMARK.json").read_text())["workloads"]]


def numbers(line):
    return {k: v["value"] for k, v in line["check"].items()}


@pytest.mark.parametrize("cell", CELLS)
def test_an_unbroken_run_is_correct(cell):
    code, line = run.run_cell(cell, 4_100_000_007, 3.0, False, True)
    assert code == 0 and line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] > 1000
    assert all(v == 0 for v in numbers(line).values())
    assert line["device"]["platform"] == "cpu"
    assert all(k.startswith("rehearsal.") for k in line["metrics"])


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_sheds_one_packet_in_a_hundred_and_fails(cell):
    code, line = run.run_cell(cell, 11, 3.0, False, True, server_overrides={
        "faults": {"enabled": True, "seed": 5, "drop_pct": 0.01}})
    assert code == 0 and line["correct"] is False
    assert numbers(line)["missing"] > 0 and line["failed"] == numbers(line)["missing"]


@pytest.mark.parametrize("fault, number", [("alter", "corrupt"), ("stale_state", None)])
def test_a_planted_fault_fails(fault, number):
    code, line = run.run_cell(CELLS[0], 12, 3.0, False, True, faults=(fault,))
    # a server that dies under the fault gives no result line: failed as well
    assert code != 0 or line["correct"] is False, line
    if number:
        assert numbers(line)[number] > 0

