"""How the plain reference's verdicts are made: built and judged apart, a
flush judged in slices by every helper, a large flush at a seeded sample,
and a run that is cut leaves no process."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from chipbench import kinds
from chipbench.harness import bench
from chipbench.harness.bench import JUDGED_PER_FLUSH, PROBE, WARM_UP, Cell, Prepared
from chipbench.reference.verify import Reference

from .conftest import DATA, ROOT


def test_the_sample_depends_on_seed_and_index_alone_and_holds_every_expected_false():
    n = 40
    clean = [True] * n
    wrong = [i % 7 != 3 for i in range(n)]  # 6 expected false
    for seed in (5, 2**31 + 5, 2**33 + 1):
        for index in (WARM_UP, 1, 2):
            drawn = bench.judged_positions(seed, index, clean)
            assert len(drawn) == JUDGED_PER_FLUSH == len(set(drawn))
            assert drawn == sorted(drawn) and drawn == bench.judged_positions(seed, index, clean)
            # the wrong requests are judged besides the same draws
            assert bench.judged_positions(seed, index, wrong) == sorted(
                set(drawn) | {i for i, ok in enumerate(wrong) if not ok}
            )
        assert bench.judged_positions(seed, 1, clean) != bench.judged_positions(seed, 2, clean)
    assert bench.judged_positions(5, 1, clean) != bench.judged_positions(6, 1, clean)
    # the probe and a flush of up to 16 are judged whole
    assert bench.judged_positions(5, PROBE, clean) == list(range(n))
    assert bench.judged_positions(5, 1, [True] * 16) == list(range(16))


def test_split_judging_gives_the_verdicts_of_a_whole_flush_in_one_process(
    tiny_bench, monkeypatch
):
    monkeypatch.setattr(bench, "JUDGE_SLICE", 3)  # the probe's 20 in 7 tasks
    cell = Cell(tiny_bench, "tiny.wide", DATA)
    prep = Prepared(cell, 2**31 + 37, pool_flushes=1)
    try:
        prep.finish()
    finally:
        prep.abandon()
    reference = Reference()
    for index in (WARM_UP, PROBE, 1):
        flush = prep.get(index)
        whole = [kinds.load(k).verify(reference, *w) for k, w in zip(flush.kinds, flush.wire)]
        judged = bench.judged_positions(prep.seed, index, flush.expected)
        assert prep.reference(index) == [
            whole[p] if p in judged else None for p in range(len(whole))
        ]
    assert prep.reference(PROBE).count(False) == 1  # in order: the wrong one where it was
    assert prep.judged_requests == 20 + 16 * 2
    assert prep.reference_s > 0


def test_a_flush_is_handed_out_before_it_is_judged(tiny_bench, monkeypatch):
    # one helper: every build is queued before any verdict
    monkeypatch.setattr(bench, "HELPERS", 1)
    cell = Cell(tiny_bench, "tiny.wide", DATA)
    prep = Prepared(cell, 2**31 + 41, pool_flushes=2)
    try:
        flush = prep.get(WARM_UP)
        judging = prep._judging.get(WARM_UP, [])
        assert len(flush.requests) == 20
        assert not judging or not all(f.done() for _, f in judging)
        assert WARM_UP not in prep.verdicts
        prep.finish()
    finally:
        prep.abandon()
    assert sum(v is not None for v in prep.reference(WARM_UP)) == JUDGED_PER_FLUSH


def _processes():
    """pid: (parent pid, state, command line) of every process we can see."""
    seen = {}
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/stat") as f:
                stat = f.read()
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmdline = f.read().replace(b"\0", b" ").decode(errors="replace")
        except OSError:
            continue
        fields = stat.rsplit(")", 1)[1].split()
        seen[int(pid)] = (int(fields[1]), fields[0], cmdline)
    return seen


def _descendants(root):
    seen = _processes()
    found, todo = {}, [root]
    while todo:
        parent = todo.pop()
        for pid, (ppid, _, cmdline) in seen.items():
            if ppid == parent and pid not in found:
                found[pid] = cmdline
                todo.append(pid)
    return found


def _running(pids):
    seen = _processes()
    return [p for p in pids if p in seen and seen[p][1] != "Z"]


def test_a_run_cut_in_set_up_leaves_no_worker_and_no_helper(tiny_bench, tmp_path):
    """SIGTERM to ``chipbench/run.py`` while its helpers build a long pool:
    it exits with 143, prints no result, and its worker (in a process group
    of its own) and its helpers are gone."""
    (tmp_path / "traffic").mkdir()
    with open(tmp_path / "traffic" / "long_setup.json", "w") as f:
        json.dump({
            "name": "long_setup", "generator": "sig_share_rounds",
            "params": {"requests": 2, "wrong": 0}, "pool_flushes": 400,
            "trace": {"flushes": 1},
        }, f)
    cut = dict(tiny_bench)
    cut["paths"] = ["."]
    cut["configs"] = [
        {"name": "coin16", "file": os.path.join(ROOT, "chipbench", "configs", "coin16.json")}
    ]
    cut["workloads"] = [
        {"name": "cut.clean", "config": "coin16", "traffic": "long_setup", "chips": 1}
    ]
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {ROOT!r})\n"
        "from chipbench import run\n"
        f"sys.exit(run.main(['--workload', 'cut.clean', '--seed', '7', '--seconds', '1'],"
        f" bench=json.loads({json.dumps(cut)!r}), root={str(tmp_path)!r},"
        " require_tpu=False, worker_overrides={'backend': 'eager'}))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-c", code], stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, env=env,
    )
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            kids = _descendants(proc.pid)
            worker = [p for p, c in kids.items() if "worker_entry" in c]
            helpers = [p for p, c in kids.items() if "spawn_main" in c]
            if worker and helpers:
                break
            assert proc.poll() is None, proc.communicate()
            time.sleep(0.2)
        else:
            pytest.fail("no worker and helper seen")
        time.sleep(1.0)  # into the pool's building
        kids = _descendants(proc.pid)
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    assert proc.returncode == 128 + signal.SIGTERM, err
    assert out == ""
    deadline = time.monotonic() + 20
    while _running(kids) and time.monotonic() < deadline:
        time.sleep(0.2)
    assert not _running(kids), {p: kids[p] for p in _running(kids)}
