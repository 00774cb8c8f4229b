"""Reduction from a profiler trace to the benchmark's device numbers.

The JAX profiler writes an ``.xplane.pb``; :func:`load` reads it with
``jax.profiler.ProfileData`` into plain tuples, and everything after that
is arithmetic on ``(name, start_ns, end_ns)`` intervals that the tests
check on a small recorded trace (``bench/tests/data``).

- Device busy time is the union of the intervals of the programs that ran
  on the device (the TPU plane's ``XLA Modules`` line); its idle share is
  one minus busy over the traced window.
- A module's device time is the sum of its events' durations, keyed by the
  jitted function's name (``jit_fill_holes(123)`` -> ``fill_holes``).
- Each idle gap is attributed to the harness's own host span
  (``jax.profiler.TraceAnnotation``) that overlaps it most: what the host
  was doing while the device waited.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[str, int, int]  # (name, start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(event_name: str) -> str:
    """``jit_fill_holes(42)`` -> ``fill_holes``; other names unchanged but
    for the trailing program id."""
    name = _SUFFIX.sub("", event_name.strip())
    return name[4:] if name.startswith("jit_") else name


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str, span_names: Iterable[str]) -> Tuple[Dict[int, List[Interval]], List[Interval]]:
    """Device module events per TPU id, and the host spans named in
    ``span_names``, from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    wanted = set(span_names)
    device: Dict[int, List[Interval]] = {}
    host: List[Interval] = []
    data = ProfileData.from_file(path)
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    device.setdefault(int(m.group(1)), []).extend(
                        (ev.name, int(ev.start_ns), int(ev.end_ns)) for ev in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        host.append((ev.name, int(ev.start_ns), int(ev.end_ns)))
    return device, host


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge ``(start, end)`` pairs into disjoint sorted intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_ns(events: Sequence[Interval], lo: int, hi: int) -> int:
    """Length of the union of the events' intervals inside ``[lo, hi]``."""
    return sum(e - s for s, e in clip(union((s, e) for _, s, e in events), lo, hi))


def gaps(events: Sequence[Interval], lo: int, hi: int) -> List[Tuple[int, int]]:
    """The idle intervals of ``[lo, hi]``: where no event runs."""
    out, cur = [], lo
    for s, e in clip(union((s, e) for _, s, e in events), lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def per_module(events: Sequence[Interval], lo: int, hi: int) -> Dict[str, Tuple[int, int]]:
    """``{module: (calls, device ns)}`` over the events that start in ``[lo, hi)``."""
    out: Dict[str, Tuple[int, int]] = {}
    for name, s, e in events:
        if lo <= s < hi:
            key = module_name(name)
            n, t = out.get(key, (0, 0))
            out[key] = (n + 1, t + (e - s))
    return out


def attribute(gap: Tuple[int, int], spans: Sequence[Interval]) -> str:
    """The host span that overlaps the gap most, or ``idle`` if none does."""
    best, best_ns = "idle", 0
    for name, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best_ns:
            best, best_ns = name, ov
    return best


def breakdown(
    events: Sequence[Interval], spans: Sequence[Interval], lo: int, hi: int, top: int = 10
) -> Dict[str, List[List]]:
    """The device modules that took most time, and the longest idle gaps
    named by what the host was doing in them; seconds, unrounded."""
    mods = per_module(events, lo, hi)
    ops = sorted(mods.items(), key=lambda kv: -kv[1][1])[:top]
    idle = sorted(gaps(events, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[name, t / 1e9] for name, (_, t) in ops],
        "idle_gaps": [[attribute(g, spans), (g[1] - g[0]) / 1e9] for g in idle],
    }


def roofline_share(
    mods: Dict[str, Tuple[int, int]], least_bytes: Dict[str, int], peak_bytes_per_s: float
) -> Optional[float]:
    """Percent of the bandwidth roofline that the named modules reach: their
    calls times each call's least HBM bytes, over the peak bandwidth, over
    their device time. ``None`` where none of them ran."""
    calls = [(mods[k][0], mods[k][1], b) for k, b in least_bytes.items() if k in mods]
    ns = sum(t for _, t, _ in calls)
    if ns <= 0:
        return None
    least_s = sum(n * b for n, _, b in calls) / peak_bytes_per_s
    return 100.0 * least_s / (ns / 1e9)
