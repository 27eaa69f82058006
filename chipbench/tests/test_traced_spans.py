"""A traced run on the CPU backend over a ``TpuBackend`` whose two programs
are stubs: the worker's profiler window holds the program's spans, and the
readers bring them into the line.  No device plane, so no device metric."""

import os

import pytest

from chipbench.harness import reduce_spans

from .conftest import DATA, HERE
from .test_run import _run

STUB_KERNEL_ENTRY = os.path.join(HERE, "stub_kernel_worker_entry.py")

DEVICE_METRICS = {
    "scan_ms", "pair_ms", "scan_roofline", "pair_roofline", "device_busy_ms",
    "flush_roofline", "flush_host_ms", "device_programs_per_flush",
}


@pytest.mark.parametrize("workload,checks", [("tiny.clean", 1), ("tiny.byz", 5)])
def test_a_traced_run_reads_the_programs_spans(tiny_bench, monkeypatch, workload, checks):
    trace_dir = os.path.join(DATA, ".trace")  # where a run under DATA traces
    monkeypatch.setattr(reduce_spans, "TRACE_DIR", trace_dir)
    monkeypatch.setattr(reduce_spans, "CACHE", os.path.join(trace_dir, "spans.json"))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc, line, err = _run(
        tiny_bench, workload, 5, trace=True, entry=STUB_KERNEL_ENTRY,
        mode="oracle", worker_overrides={"backend": "tpu"},
    )
    assert rc == 0, err
    assert line["correct"] is True, line["compared"]
    assert "busy_s" not in line["device"] and "breakdown" not in line
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert not DEVICE_METRICS & set(metrics)
    assert metrics["checks_per_flush"] == checks
    assert 0 < metrics["hash_to_g2_ms"] < metrics["host_prep_ms"]
    assert metrics["host_prep_ms"] < metrics["worker_flush_ms"]
    assert 0 < metrics["rpc_server_decode_ms"]
    run = line["run"]
    per = run["spans_per_flush"]
    assert per["crypto.flush"]["count"] == per["crypto.rpc.serve"]["count"] == 1
    assert per["crypto.tpu.check"]["count"] == checks
    # the spans' flush and the timer's may be two flushes (two windows)
    assert 0.5 < per["crypto.flush"]["ms"] / metrics["worker_flush_ms"] < 2.0
    assert per["crypto.flush"]["ms"] < per["crypto.rpc.serve"]["ms"]
    assert run["reduce_spans_s"] > 0
    assert "idle_by_span_s" not in run  # no device op, so no gap to name
