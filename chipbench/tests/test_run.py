"""A whole run against a pure-Python worker on the CPU: the line it prints,
what it refuses, the control and the broken paths."""

import io
import json
import os
import time

import pytest

from chipbench.harness.bench import EXIT_NO_CHIP, WARM_UP, judged_positions, run_cell

from .conftest import DATA, HERE

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
BROKEN_ENTRY = os.path.join(HERE, "control_worker_entry.py")


def _run(tiny_bench, workload, seed, trace=False, entry=None, mode=None, **kw):
    out, err = io.StringIO(), io.StringIO()
    kw.setdefault("require_tpu", False)
    kw.setdefault("worker_overrides", {"backend": "eager"})
    if entry:
        kw["worker_entry"] = entry
        kw["worker_entry_args"] = [mode]
    rc = run_cell(
        tiny_bench, workload, seed, 0.5, trace, t0=time.perf_counter(),
        root=DATA, out=out, err=err, **kw,
    )
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()


def test_a_run_prints_the_contracts_line(tiny_bench):
    rc, line, err = _run(tiny_bench, "tiny.clean", 2**31 + 7)
    assert rc == 0
    keys = list(line)
    assert keys[: len(CONTRACT_KEYS)] == CONTRACT_KEYS
    assert keys[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 2 * line["run"]["flushes"] > 0
    assert set(line["metrics"]) == {
        "verifies_per_s", "flush_ms.p50", "flush_ms.p95", "setup_s"
    }
    assert all(m["value"] > 0 and m["unit"] for m in line["metrics"].values())
    # every answer of the window, the warm-up and the probe is judged
    for entry in ("answers_checked_by_reference", "answers_checked_by_construction"):
        checked = line["compared"][entry]
        assert checked["value"] == checked["at_least"] == line["attempted"] + 2 * 2
    # flushes of 2 are judged whole: the warm-up's, the probe's, the pool's 6
    assert line["run"]["judged_requests"] == 2 * (1 + 1 + 6)
    assert line["run"]["probe_flush_s"] > 0
    # the numbers compared are the last lines of the standard error too
    tail = err.strip().splitlines()[-(len(line["compared"]) + 1):]
    assert tail[-1] == "chipbench: correct = True"
    assert all(t.startswith("chipbench: compared ") for t in tail[:-1])


def test_no_device_metric_without_a_tpu(tiny_bench):
    # as the command line runs it: a worker that holds no TPU is refused
    rc, line, err = _run(tiny_bench, "tiny.clean", 3, require_tpu=True)
    assert rc == EXIT_NO_CHIP and line is None
    assert "TPU" in err
    # and a traced run the tests force through reports no device number
    rc, line, _ = _run(tiny_bench, "tiny.clean", 3, trace=True)
    assert rc == 0 and "breakdown" not in line
    assert "busy_s" not in line["device"]
    assert set(line["metrics"]) == {"rpc_overhead_ms", "worker_flush_ms"}


def test_a_decrypt_cell_is_files_and_entries(tiny_bench):
    """``hb4.decrypt`` is a configuration file, a traffic file and entries
    of ``tiny_bench``: a ciphertext check and decryption shares, one of them
    wrong, go through the harness, each judged by its own kind's verifier,
    with no code of their own."""
    rc, line, err = _run(tiny_bench, "hb4.decrypt", 2**31 + 29)
    assert rc == 0, err
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0
    assert line["attempted"] == 3 * line["run"]["flushes"] > 0
    # the window's answers, the warm-up's and the probe's
    checked = line["compared"]["answers_checked_by_reference"]
    assert checked["value"] == checked["at_least"] == line["attempted"] + 3 + 3
    assert all(
        entry["value"] == 0
        for entry in line["compared"].values() if "limit" in entry
    )


@pytest.mark.parametrize(
    "workload,mode",
    [
        ("tiny.clean", "control"),  # the Miller loop cut short
        ("tiny.byz", "control"),
        ("hb4.decrypt", "control"),
        ("tiny.clean", "flip"),     # an answer altered where it is produced
        ("tiny.byz", "flip"),
        ("hb4.decrypt", "flip"),
        ("tiny.clean", "accept"),   # verification skipped: the probe shows it
        ("tiny.byz", "accept"),
        ("hb4.decrypt", "accept"),
    ],
)
def test_a_broken_timed_path_is_not_correct(tiny_bench, workload, mode):
    rc, line, _ = _run(tiny_bench, workload, 11, entry=BROKEN_ENTRY, mode=mode)
    assert rc == 0
    assert line["correct"] is False
    compared = {k: v["value"] for k, v in line["compared"].items()}
    # the construction still agrees with the reference: the fault is the path's
    assert compared["construction_differing_from_reference"] == 0
    assert compared["requests_not_answered_by_chip_path"] == 0
    assert compared["answers_differing_from_reference"] > 0
    assert (
        compared["answers_differing_from_construction"]
        == compared["answers_differing_from_reference"]
    )
    run = line["run"]
    if mode == "accept":
        # every wrong request of the set-up and the window was let through,
        # and nothing else differs
        wrong = {"tiny.clean": 1, "tiny.byz": 2, "hb4.decrypt": 2}  # the probe's
        per_flush = {"tiny.byz": 1, "hb4.decrypt": 1}.get(workload, 0)
        assert compared["answers_differing_from_reference"] == (
            wrong[workload] + per_flush * (1 + run["flushes"])
        )
    if mode == "flip":
        # one answer of every flush: the warm-up's, the probe's, the window's
        assert compared["answers_differing_from_reference"] == 2 + run["flushes"]


WIDE_SEED = 173  # its draws leave position 19 unjudged in the warm-up and flushes 1, 2


@pytest.mark.parametrize("mode", ["flip", "accept"])
def test_a_broken_answer_outside_the_sample_is_not_correct(tiny_bench, mode):
    """``tiny.wide``'s flushes of 20 are judged at 16 drawn positions (and the
    probe whole): an answer altered where the reference did not look is
    caught by the construction's verdict on it."""
    unjudged = [
        19 not in judged_positions(WIDE_SEED, i, [True] * 20) for i in (WARM_UP, 1, 2)
    ]
    assert all(unjudged)
    rc, line, _ = _run(tiny_bench, "tiny.wide", WIDE_SEED, entry=BROKEN_ENTRY, mode=mode)
    assert rc == 0
    assert line["correct"] is False
    compared = {k: v["value"] for k, v in line["compared"].items()}
    assert compared["construction_differing_from_reference"] == 0
    run = line["run"]
    # the warm-up and each pool flush judged at 16 positions, the probe whole
    assert run["judged_requests"] == 20 + 16 * 3
    assert compared["answers_checked_by_reference"] == 20 + 16 * (1 + run["flushes"])
    assert compared["answers_checked_by_construction"] == 20 * (2 + run["flushes"])
    if mode == "flip":
        # the last answer of every flush: the reference sees the probe's alone
        assert run["flushes"] <= 2  # flushes 1 and 2, not wrapped
        assert compared["answers_differing_from_construction"] == 2 + run["flushes"]
        assert compared["answers_differing_from_reference"] == 1
    else:
        # the probe's one wrong share let through, judged whole
        assert compared["answers_differing_from_construction"] == 1
        assert compared["answers_differing_from_reference"] == 1
