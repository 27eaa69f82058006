"""A whole run against a pure-Python worker on the CPU: the line it prints,
what it refuses, the control and the broken paths."""

import io
import json
import os
import time

import pytest

from chipbench.harness.bench import EXIT_NO_CHIP, run_cell

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
        tiny_bench, workload, seed, 2.0, trace, t0=time.perf_counter(),
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
    checked = line["compared"]["answers_checked_by_reference"]
    assert checked["value"] == checked["at_least"] == line["attempted"] + 2 * 2
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


@pytest.mark.parametrize(
    "workload,mode",
    [
        ("tiny.clean", "control"),  # the Miller loop cut short
        ("tiny.byz", "control"),
        ("tiny.clean", "flip"),     # an answer altered where it is produced
        ("tiny.byz", "flip"),
        ("tiny.clean", "accept"),   # verification skipped: the probe shows it
        ("tiny.byz", "accept"),
    ],
)
def test_a_broken_timed_path_is_not_correct(tiny_bench, workload, mode):
    rc, line, _ = _run(tiny_bench, workload, 11, entry=BROKEN_ENTRY, mode=mode)
    assert rc == 0
    assert line["correct"] is False
    differing = (
        line["compared"]["answers_differing_from_reference"]["value"]
        + line["compared"]["answers_differing_from_construction"]["value"]
    )
    assert differing > 0
