"""The reduction from a device trace to per-layer numbers: by hand on a
tiny extract, and on an extract of a real TPU v5e trace kept beside this
file."""
from __future__ import annotations

import json
import pathlib

import pytest

from bench import trace

HERE = pathlib.Path(__file__).resolve().parent

TINY = {
    "devices": {
        "0": {"XLA Ops": [["fusion.1", 0, 10], ["fusion.2", 5, 10],
                          ["collective-permute-start", 30, 5]],
              "XLA Modules": [["jit_dpm_plan_exact(7)", 0, 15],
                              ["jit__run_batch(3)", 30, 5]]},
        "1": {"XLA Ops": [["fusion.1", 100, 40]],
              "XLA Modules": [["jit_dpm_plan_exact(7)", 100, 40]]},
    },
    "host_spans": [["bench.step", 12, 30], ["bench.outer", 0, 100]],
}


def test_busy_and_idle_by_hand():
    r = trace.Reduced(TINY, [0], 100e-9)
    assert r.busy[("0")] == [[0, 15], [30, 35]]
    assert r.busy_s == pytest.approx(20e-9)
    assert r.idle_share() == pytest.approx(0.8)
    # the 15 ns gap [15, 30) is named by the innermost span around 22
    assert r.idle_gaps(5) == [["bench.step", 15e-9]]
    assert r.top_ops(2) == [["fusion.1", 10e-9], ["fusion.2", 10e-9]]


def test_two_devices_average():
    r = trace.Reduced(TINY, [0, 1], 200e-9)
    assert r.busy_s == pytest.approx((20 + 40) / 2 * 1e-9)
    assert r.module_seconds("dpm_plan_exact") == (pytest.approx(27.5e-9), 1)
    assert r.op_seconds(lambda n: "collective-permute" in n) == pytest.approx(
        2.5e-9)


def test_no_device_events_reads_nothing():
    r = trace.Reduced({"devices": {}, "host_spans": []}, [0], 1.0)
    assert r.idle_share() is None and r.busy_s == 0.0


def _union_by_sweep(events) -> int:
    """Busy nanoseconds by a sweep over start and end points."""
    points = sorted([(s, 1) for _, s, d in events] +
                    [(s + d, -1) for _, s, d in events])
    busy, depth, last = 0, 0, None
    for t, step in points:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


@pytest.mark.parametrize("name,module", [
    ("v5e_bulk_extract.json", "dpm_plan_exact"),
    ("v5e_stream_extract.json", "dpm_plan_exact"),
])
def test_real_extract(name, module):
    ex = json.loads((HERE / "data" / name).read_text())
    ops = ex["devices"]["0"]["XLA Ops"]
    assert 100 <= len(ops) and all(" = " not in n for n, _, _ in ops)
    span = max(s + d for _, s, d in ops) - min(s for _, s, _ in ops)
    r = trace.Reduced(ex, [0], span / 1e9)
    assert r.busy_s * 1e9 == pytest.approx(_union_by_sweep(ops))
    assert 0.0 <= r.idle_share() <= 1.0
    seconds, launches = r.module_seconds(module)
    mods = [d for n, _, d in ex["devices"]["0"]["XLA Modules"] if module in n]
    assert launches == len(mods) > 0
    assert seconds == pytest.approx(sum(mods) / 1e9)
    top = r.top_ops(10)
    assert [d for _, d in top] == sorted((d for _, d in top), reverse=True)
