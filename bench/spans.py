"""The join from device programs to the program's task spans.

The program opens a host span ``sa/task/<task>`` around each task it runs
(``repro.tracing``); the spans land in the same ``.xplane.pb`` as the
device's programs, on the host line of the thread that ran the task. A
recorded v5e trace links each program back to the launch that made it,
through flow ids (``_c`` on the event a flow comes from, ``_p`` where it
goes):

- the ``XLA Modules`` event on ``/device:TPU:<n>`` carries ``_c``, the
  ``_p`` of the ``DoEnqueueProgram`` that enqueued it;
- that enqueue lies inside a ``tpu::System::Execute=>IssueSequencedEvent``
  whose ``_c`` is the ``_p`` of the ``tpu::System::Execute`` of the launch.
  The two may lie on different host lines: a thread that sends its own
  program to the device also sends those queued behind it by other
  threads. (An enqueue outside any sequenced event belongs to the launch
  it lies in.)
- that ``tpu::System::Execute`` lies inside a
  ``PJRT_LoadedExecutable_Execute``, whose ``_c`` is the ``_p`` of the
  ``PJRT_LoadedExecutable_Execute linkage`` event on the line of the Python
  thread that launched the program, at the launch.

A program belongs to the innermost task span on that line that covers its
launch. :func:`load` reads the trace once into plain tuples; everything
after it is arithmetic on them, checked on hand-made tuples and on a
recorded trace (``bench/tests/data/threads.xplane.pb``).

Run on a trace of the program, taken with ``jax.profiler.start_trace`` or
from a profiler server, it prints the join as one JSON object::

    python3 bench/spans.py <trace dir or .xplane.pb> [--window <host span>]

The window is the recorded modules' extent, or the named host span's (the
benchmark's ``traced``): per task its spans, launches and device seconds,
``task_device_ms``, ``launches_per_task``, ``unattributed_device_share``,
the leaves' completions (``done``) and the longest idle gaps named by the
innermost program span that covers them.
"""

from __future__ import annotations

import argparse
import bisect
import json
import re
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

PROGRAM = "sa/"
TASK = "sa/task/"
LINKAGE = "PJRT_LoadedExecutable_Execute linkage"
EXECUTE = "PJRT_LoadedExecutable_Execute"
SYSTEM = "tpu::System::Execute"
SEQUENCED = "tpu::System::Execute=>IssueSequencedEvent"
ENQUEUE = "DoEnqueueProgram"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"

Module = Tuple[str, int, int, Optional[int]]  # (name, start_ns, end_ns, flow id)
Span = Tuple[str, int, int, int, Dict[str, Any]]  # (name, line, start_ns, end_ns, stats)
Mark = Tuple[int, int, Optional[int]]  # (line, time_ns, flow id)
Interval = Tuple[int, int, int, Optional[int]]  # (line, start_ns, end_ns, flow id)
# host events read, by name: the kind of tuple kept and the flow stat
MARKS = {LINKAGE: ("linkages", "_p"), SYSTEM: ("systems", "_p"), ENQUEUE: ("enqueues", "_p")}
INTERVALS = {EXECUTE: ("executes", "_c"), SEQUENCED: ("sequenced", "_c")}


def load(path: str) -> Dict[str, list]:
    """From one ``.xplane.pb``: the first TPU's modules, the program's
    spans (every host event named ``sa/...``) with their stats, and the
    launch events of :data:`MARKS` and :data:`INTERVALS`, each host line
    numbered once."""
    from jax.profiler import ProfileData

    devices: Dict[int, List[Module]] = {}
    out: Dict[str, list] = {"spans": []}
    for key, _ in list(MARKS.values()) + list(INTERVALS.values()):
        out[key] = []
    line_id = 0
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    devices.setdefault(int(m.group(1)), []).extend(
                        (ev.name, int(ev.start_ns), int(ev.end_ns), _flow(ev, "_c"))
                        for ev in line.events
                    )
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            line_id += 1
            for ev in line.events:
                name = ev.name
                if name.startswith(PROGRAM):
                    out["spans"].append(
                        (name, line_id, int(ev.start_ns), int(ev.end_ns), dict(ev.stats))
                    )
                elif name in MARKS:
                    key, stat = MARKS[name]
                    out[key].append((line_id, int(ev.start_ns), _flow(ev, stat)))
                elif name in INTERVALS:
                    key, stat = INTERVALS[name]
                    out[key].append((line_id, int(ev.start_ns), int(ev.end_ns), _flow(ev, stat)))
    out["modules"] = devices[min(devices)] if devices else []
    return out


def _flow(ev, key: str) -> Optional[int]:
    for k, v in ev.stats:
        if k == key:
            return int(v)
    return None


def _innermost(intervals: Sequence[Tuple[int, int, int]]):
    """A lookup ``(line, t) -> index`` into ``intervals``, given as
    ``(line, start_ns, end_ns)``: the innermost interval on ``line`` that
    covers ``t``, i.e. the latest to start at or before ``t`` that has not
    ended by then; ``None`` where none covers it."""
    by_line: Dict[int, List[int]] = {}
    for i in sorted(range(len(intervals)), key=lambda i: intervals[i][1]):
        by_line.setdefault(intervals[i][0], []).append(i)
    starts = {line: [intervals[i][1] for i in idx] for line, idx in by_line.items()}

    def find(line: int, t: int) -> Optional[int]:
        idx = by_line.get(line, [])
        k = bisect.bisect_right(starts.get(line, []), t) - 1
        while k >= 0:
            if t <= intervals[idx[k]][2]:
                return idx[k]
            k -= 1
        return None

    return find


def launch_sites(
    linkages: Sequence[Mark],
    executes: Sequence[Interval],
    systems: Sequence[Mark],
    sequenced: Sequence[Interval],
    enqueues: Sequence[Mark],
) -> Dict[int, Tuple[int, int]]:
    """``{module flow id: (line, launch time)}``: from each enqueue, through
    the sequenced event that holds it to the launch's ``tpu::System::Execute``,
    then the execute that holds that on its line, to the linkage on the
    launching thread's line."""
    linked = {flow: (line, t) for line, t, flow in linkages if flow is not None}
    launched_by = {flow: (line, t) for line, t, flow in systems if flow is not None}
    sequenced_at = _innermost([iv[:3] for iv in sequenced])
    execute_at = _innermost([iv[:3] for iv in executes])
    sites: Dict[int, Tuple[int, int]] = {}
    for line, t, flow in enqueues:
        if flow is None:
            continue
        i = sequenced_at(line, t)
        if i is not None:
            line, t = launched_by.get(sequenced[i][3], (line, t))
        x = execute_at(line, t)
        site = linked.get(executes[x][3]) if x is not None else None
        if site is not None:
            sites[flow] = site
    return sites


def join(
    modules: Sequence[Module],
    sites: Dict[int, Tuple[int, int]],
    spans: Sequence[Span],
    launches: Sequence[Mark],
    lo: int,
    hi: int,
) -> Dict[str, Any]:
    """The traced span ``[lo, hi)``'s device time by task.

    - ``tasks``: ``{task: [tasks, launches, device ns]}`` over the modules
      that start in the span and the task spans that launched them; a span
      counts its ``nodes`` stat if it has one, else 1.
    - ``module_ns``, ``unclaimed_ns``: those modules' device time, and the
      part of it that no task span launched.
    - ``done``: ``[ns after lo, runs, bucket]`` for each leaf task span
      whose programs were all recorded and whose last program ended inside
      the span: the device clock's completion of its evaluations.

    ``launches`` are the linkage marks, counted per span to tell whether
    every program a span launched was recorded."""
    tasks = [s for s in spans if s[0].startswith(TASK)]
    owner = _innermost([s[1:4] for s in tasks])
    launched = [0] * len(tasks)
    for line, t, _ in launches:
        i = owner(line, t)
        if i is not None:
            launched[i] += 1
    found = [0] * len(tasks)
    last_end = [0] * len(tasks)
    claimed_ns = [0] * len(tasks)
    claimed_n = [0] * len(tasks)
    module_ns = unclaimed_ns = 0
    for _, s, e, flow in modules:
        site = sites.get(flow)
        i = owner(*site) if site is not None else None
        if i is not None:
            found[i] += 1
            last_end[i] = max(last_end[i], e)
        if not lo <= s < hi:
            continue
        module_ns += e - s
        if i is None:
            unclaimed_ns += e - s
        else:
            claimed_ns[i] += e - s
            claimed_n[i] += 1
    table: Dict[str, List[int]] = {}
    done: List[List[Any]] = []
    for i, (name, _, _, _, stats) in enumerate(tasks):
        if claimed_n[i]:
            row = table.setdefault(name[len(TASK):], [0, 0, 0])
            row[0] += int(stats.get("nodes", 1))
            row[1] += claimed_n[i]
            row[2] += claimed_ns[i]
        if stats.get("leaf") and found[i] and found[i] == launched[i] and lo <= last_end[i] <= hi:
            done.append([last_end[i] - lo, int(stats.get("runs", 1)), stats.get("bucket", "")])
    done.sort(key=lambda d: d[0])
    return {"tasks": table, "module_ns": module_ns, "unclaimed_ns": unclaimed_ns, "done": done}


def reduce(loaded: Dict[str, list], lo: int, hi: int) -> Dict[str, Any]:
    """:func:`join` over what :func:`load` read."""
    sites = launch_sites(
        loaded["linkages"], loaded["executes"], loaded["systems"], loaded["sequenced"],
        loaded["enqueues"],
    )
    return join(loaded["modules"], sites, loaded["spans"], loaded["linkages"], lo, hi)


def summary(joined: Dict[str, Any]) -> Dict[str, Any]:
    """The join as numbers: per task its spans, launches and device
    seconds; device ms and launches per task span over every task; and the
    share of module time that no task span launched (``None`` where there
    is nothing to divide)."""
    rows = joined["tasks"].values()
    n = sum(r[0] for r in rows)
    return {
        "tasks": {
            name: {"tasks": k, "launches": launches, "device_s": ns / 1e9}
            for name, (k, launches, ns) in joined["tasks"].items()
        },
        "task_device_ms": sum(r[2] for r in rows) / n / 1e6 if n else None,
        "launches_per_task": sum(r[1] for r in rows) / n if n else None,
        "unattributed_device_share": (
            joined["unclaimed_ns"] / joined["module_ns"] if joined["module_ns"] else None
        ),
    }


def gap_spans(program: Sequence[Span]) -> List[Tuple[str, int, int]]:
    """The program's spans in ``bench/trace.py``'s form, shortest first.
    ``trace.attribute`` keeps the first span of the largest overlap, so a
    gap goes to the innermost program span that covers it; spans listed
    after these name only the gaps that no program span covers."""
    return sorted(((s[0], s[2], s[3]) for s in program), key=lambda s: s[2] - s[1])


def main(argv=None) -> int:
    import trace as tr  # bench/trace.py: the script's directory comes first

    ap = argparse.ArgumentParser(description="Join a trace's device programs to the task spans.")
    ap.add_argument("trace", help="a .xplane.pb, or a directory holding one")
    ap.add_argument("--window", help="the host span that bounds the window, e.g. traced")
    args = ap.parse_args(argv)
    path = args.trace if args.trace.endswith(".xplane.pb") else tr.find_xplane(args.trace)
    loaded = load(path)
    events = [m[:3] for m in loaded["modules"]]
    if args.window:
        marks = tr.load(path, (args.window,))[1]
        if not marks:
            print(f"bench/spans.py: no host span {args.window!r} in {path}", file=sys.stderr)
            return 1
        lo, hi = marks[0][1], marks[0][2]
    elif events:
        lo, hi = min(m[1] for m in events), max(m[2] for m in events)
    else:
        print(f"bench/spans.py: no device programs in {path}", file=sys.stderr)
        return 1
    joined = reduce(loaded, lo, hi)
    out = {"window_s": (hi - lo) / 1e9, "busy_s": tr.busy_ns(events, lo, hi) / 1e9}
    out.update(summary(joined))
    out["done"] = [[ns / 1e9, runs, bucket] for ns, runs, bucket in joined["done"]]
    out["breakdown"] = tr.breakdown(events, gap_spans(loaded["spans"]), lo, hi)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
