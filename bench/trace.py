"""From a profiler trace to the numbers the per-layer readers use.

``extract(xplane)`` keeps, from JAX's ``.xplane.pb``, the events of each
device plane's op and module lines and the benchmark's own host spans
(names starting ``bench.``), as plain ``[name, start_ns, duration_ns]``
lists. ``Reduced`` computes from that extract, so the reduction runs the
same on a trace fresh from the chip and on the small extract kept with the
tests.
"""
from __future__ import annotations

import glob
import json
import os

OPS, MODULES = "XLA Ops", "XLA Modules"


def _device_id(plane: str) -> int | None:
    if not plane.startswith("/device:TPU:"):
        return None
    tail = plane.rsplit(":", 1)[1]
    return int(tail) if tail.isdigit() else None


def _short(name: str) -> str:
    """An op's name without the HLO text that follows it."""
    return name.split(" = ", 1)[0].lstrip("%")


def extract(path: str) -> dict:
    """Device op/module events and ``bench.`` host spans of one trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        dev = _device_id(plane.name)
        for line in plane.lines:
            if dev is not None and line.name in (OPS, MODULES):
                devices.setdefault(str(dev), {})[line.name] = [
                    [_short(e.name), int(e.start_ns), int(e.duration_ns)]
                    for e in line.events
                ]
            elif dev is None and plane.name.startswith("/host"):
                spans += [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events if e.name.startswith("bench.")]
    return {"devices": devices, "host_spans": spans}


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Reduced:
    """Busy time, op and module time and idle gaps of the devices used."""

    def __init__(self, ex: dict, device_ids, window_s: float):
        self.ex = ex
        self.ids = [str(i) for i in device_ids]
        self.window_s = window_s
        self.busy = {
            i: _union((s, s + d) for _, s, d in
                      ex["devices"].get(i, {}).get(OPS, []))
            for i in self.ids
        }

    def events(self, line: str, device: str):
        return self.ex["devices"].get(device, {}).get(line, [])

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices used."""
        tot = sum(e - s for i in self.ids for s, e in self.busy[i])
        return tot / len(self.ids) / 1e9

    def idle_share(self) -> float | None:
        if not self.window_s or not any(self.busy.values()):
            return None
        return max(0.0, 1.0 - self.busy_s / self.window_s)

    def op_seconds(self, match) -> float:
        """Device seconds of ops whose name ``match`` accepts, averaged
        over the devices used."""
        tot = sum(d for i in self.ids for n, _, d in self.events(OPS, i)
                  if match(n))
        return tot / len(self.ids) / 1e9

    def module_seconds(self, substr: str) -> tuple[float, int]:
        """(device seconds, launches) of jitted modules whose name holds
        ``substr``, averaged over the devices used."""
        ev = [d for i in self.ids for n, _, d in self.events(MODULES, i)
              if substr in n]
        return sum(ev) / len(self.ids) / 1e9, len(ev) // len(self.ids)

    def top_ops(self, k: int) -> list:
        acc: dict = {}
        for i in self.ids:
            for n, _, d in self.events(OPS, i):
                acc[n] = acc.get(n, 0) + d
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n, d / len(self.ids) / 1e9] for n, d in top]

    def idle_gaps(self, k: int) -> list:
        """The ``k`` longest gaps between device ops on the first device,
        each named by the innermost benchmark host span around its middle."""
        busy = self.busy[self.ids[0]]
        gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])]
        gaps.sort(reverse=True)
        spans = sorted(self.ex["host_spans"], key=lambda s: s[2])
        out = []
        for g, s, e in gaps[:k]:
            mid = (s + e) // 2
            name = next((n for n, t, d in spans if t <= mid < t + d),
                        "outside benchmark spans")
            out.append([name, g / 1e9])
        return out


def reduce_dir(trace_dir: str, device_ids, window_s: float) -> Reduced:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    ex = extract(max(files, key=os.path.getmtime))
    keep = os.environ.get("BENCH_KEEP_TRACE_EXTRACT")
    if keep:  # a small sample of the trace, to check the reduction against
        with open(keep, "w") as f:
            json.dump(sample(ex, 300), f)
    return Reduced(ex, device_ids, window_s)


def sample(ex: dict, k: int) -> dict:
    """The first ``k`` events of each line and the first ``k`` spans."""
    return {"devices": {d: {line: ev[:k] for line, ev in lines.items()}
                        for d, lines in ex["devices"].items()},
            "host_spans": ex["host_spans"][:k]}
